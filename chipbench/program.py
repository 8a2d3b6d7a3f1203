"""The system under test, as the benchmark drives it.

Everything here goes through the program's public runtime API, the path
``repro.launch.train`` runs: ``build_runtime`` on a mesh, the train state
placed by ``train_state_shardings``, and the jitted train step of
``make_train_fn`` with the state donated.  No scheduler is built by hand.

The benchmark makes the weights itself (``reference.init_params``) and hands
them to the program through :func:`to_program`, the one place that knows the
program's parameter layout:

- norms:    the program's ``scale`` is the reference's offset ``s``;
- experts:  canonical ``[E*etp, H, F/etp]`` virtual shards; virtual expert v
            holds columns v*F/etp .. of the reference's [H, E*F] (rows of
            w_down), so the layouts differ by a reshape;
- query heads: the program pairs query head i with key/value head
            i mod n_kv, the reference (as published GQA does) with i div
            (n_q/n_kv); the query columns of ``wq`` and rows of ``wo`` are
            permuted to match, which leaves the model unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as ref


class Cell(NamedTuple):
    step: Callable                 # jitted (state, batch) -> (state, metrics)
    init_state: Callable           # jitted key -> placed train state
    to_program: Callable           # reference tree -> program master tree
    batch_specs: dict              # {"tokens", "labels"} ShapeDtypeStructs
    runtime: Any


def _path(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx",
                                                 getattr(k, "name", k)))))
    return "/".join(out)


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file: the registered
    architecture with every size the file states."""
    from repro.configs import get_config
    m, prog = conf["model"], conf["program"]
    cfg = get_config(prog["arch"])
    fixed = {"pattern": ("attn",), "norm": "rms", "ffn_kind": "swiglu",
             "qkv_bias": False, "logit_softcap": 0.0, "window": 0,
             "moe": True, "qk_norm": False, "mrope_sections": ()}
    for k, v in fixed.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{prog['arch']}: the program's {k}={getattr(cfg, k)!r}"
                             f" is not what the reference models ({v!r})")
    return dataclasses.replace(
        cfg, num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        vocab=m["vocab_size"], num_experts=m["num_experts"],
        top_k=m["num_experts_per_tok"], moe_d_ff=m["moe_intermediate_size"],
        etp=prog["etp"], tie_embeddings=bool(m["tie_word_embeddings"]),
        rope_theta=float(m["rope_theta"]))


def _q_perm(d: ref.Dims) -> np.ndarray:
    """Program query head n = g*n_kv + j  <-  reference head j*rep + g."""
    rep = d.heads // d.kv_heads
    n = np.arange(d.heads)
    return (n % d.kv_heads) * rep + n // d.kv_heads


def make_to_program(d: ref.Dims, master_shapes) -> Callable:
    flat, treedef = jax.tree_util.tree_flatten_with_path(master_shapes)
    perm = _q_perm(d)

    def qcols(w):                 # [H, nq*hd]: permute head blocks
        h = w.shape[0]
        return w.reshape(h, d.heads, d.head_dim)[:, perm].reshape(h, -1)

    def qrows(w):                 # [nq*hd, H]
        return w.reshape(d.heads, d.head_dim, -1)[perm].reshape(-1,
                                                                 w.shape[-1])

    per_layer = {
        "ln1/scale": lambda lp: lp["ln1"],
        "ln2/scale": lambda lp: lp["ln2"],
        "attn/wq": lambda lp: qcols(lp["wq"]),
        "attn/wk": lambda lp: lp["wk"],
        "attn/wv": lambda lp: lp["wv"],
        "attn/wo": lambda lp: qrows(lp["wo"]),
        "moe/router": lambda lp: lp["router"],
    }

    def leaf(name, shape, p):
        if name == "embed":
            return p["embed"]
        if name == "head":
            return p["head"]
        if name == "final_norm/scale":
            return p["final_norm"]
        if name.startswith("layers_scan/0/"):
            sub = name[len("layers_scan/0/"):]
            if sub in per_layer:
                return jnp.stack([per_layer[sub](lp) for lp in p["layers"]])
            if sub.startswith("moe/experts/"):
                w = sub.rsplit("/", 1)[1]
                _, ev, a, b = shape
                if w == "w_down":            # [E*F, H] -> [Ev, F/etp, H]
                    return jnp.stack([lp[w].reshape(ev, a, b)
                                      for lp in p["layers"]])
                # [H, E*F] -> [Ev, H, F/etp]
                return jnp.stack([lp[w].reshape(a, ev, b).transpose(1, 0, 2)
                                  for lp in p["layers"]])
        raise ValueError(f"program parameter {name!r} has no counterpart "
                         f"in the reference")

    def to_program(p):
        leaves = []
        for path, s in flat:
            v = leaf(_path(path), s.shape, p)
            if v.shape != s.shape:
                raise ValueError(f"{_path(path)}: reference gives {v.shape},"
                                 f" program holds {s.shape}")
            leaves.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return to_program


def leaf_names(tree) -> list:
    return [_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> jax.Array:
    """f32[leaves]: the 2-norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def build(conf: dict, chips: int, seq_len: int, devices=None,
          impl=None) -> Cell:
    """The program's train step for one configuration on ``chips`` devices.

    ``devices`` defaults to ``jax.devices()[:chips]``; the mesh is
    (data, model) as the file's entry for this chip count says.  ``impl``
    names the expert kernel (None: the program's default for the backend,
    the Pallas kernel on a TPU)."""
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import InputShape
    from repro.engine import PlacementSpec, RuntimeConfig
    from repro.launch import runtime as R
    from repro.optim.adamw import AdamWConfig

    mesh_conf = conf["meshes"][str(chips)]
    prog = conf["program"]
    cfg = arch_config(conf)
    devs = np.asarray((devices if devices is not None
                       else jax.devices())[:chips])
    if devs.size != chips:
        raise ValueError(f"need {chips} devices, found {devs.size}")
    mesh = Mesh(devs.reshape(mesh_conf["data"], mesh_conf["model"]),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rc = RuntimeConfig(placement=PlacementSpec(mesh_conf["placement"]),
                       dtype=prog["dtype"], remat=prog["remat"], impl=impl)
    dr = R.build_runtime(cfg, mesh, rc)
    ts_sh = dr.train_state_shardings()
    opt = AdamWConfig(**conf["optimizer"])
    step = jax.jit(R.make_train_fn(dr, n_micro=mesh_conf["n_micro"],
                                   opt_cfg=opt, with_expert_load=True),
                   out_shardings=(ts_sh, None), donate_argnums=0)
    d = ref.dims(conf["model"])
    to_program = make_to_program(d, dr.master_sds())

    def make_state(key):
        ts = dr.new_train_state(jax.random.PRNGKey(0))
        return ts._replace(master=to_program(ref.init_params(key, d)))

    init_state = jax.jit(make_state, out_shardings=ts_sh)
    specs = R.input_specs(dr, InputShape("bench", seq_len,
                                         mesh_conf["global_batch"], "train"))
    return Cell(step=step, init_state=init_state, to_program=to_program,
                batch_specs=specs, runtime=dr)
