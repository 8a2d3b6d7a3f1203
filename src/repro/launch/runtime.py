"""Distributed runtime: wires the decoder to a mesh.

Construction goes through the engine API: ``build_runtime(cfg, mesh,
RuntimeConfig(...))`` builds one :class:`repro.engine.MicroEPEngine` per
MicroEP group (placement, statics, scheduler, dispatch statics) and installs
its ``moe_spec`` in the shard_map island below; the legacy keyword surface
is a shim over :meth:`RuntimeConfig.from_kwargs`.

GSPMD (jit + sharding constraints) distributes everything EXCEPT the MoE
dispatch; the paper's contribution — per-micro-batch LP scheduling + token
dispatch across the MicroEP group — runs as an explicit ``shard_map`` island
(DESIGN.md §3).  The island's group axes are ('data','model'): one MicroEP
group per pod; the 'pod' axis carries only gradient reduction.

Placement grid == mesh grid: rows = data axis, cols = model axis.  Expert
tensor parallelism (dbrx etp=2, mixtral etp=2) is implemented as *virtual
experts*: expert e is stored as etp shards with d_ff/etp each, a token
routed to e visits all shards, and the combine's top-(K·etp) weighted sum
reconstructs the full down-projection.  This keeps expert-TP inside the
standard dispatch/combine collectives — no sub-axis process groups, which
XLA SPMD cannot express (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from .. import sharding as sh
from ..configs.base import ArchConfig, InputShape
from ..core.memory import MemoryModel
from ..core.placement import Placement
from ..core.scheduler import ScheduleStatics
from ..core.solver_jax import SolverState
from ..data.synthetic import frontend_stub_batch
from ..engine import (ConfigError, MicroEPEngine, PlacementSpec,
                      RuntimeConfig, SchedulePolicy, placement_strategies)
from ..models import decoder as dec
from ..moe.layer import MoEMetrics, moe_ffn
from ..moe.router import top_k_gating
from ..optim.adamw import AdamWConfig, AdamWState, adamw_init
from ..train.loop import LayoutHooks, TrainState, make_train_step

__all__ = ["DistRuntime", "build_runtime", "make_placement", "input_specs",
           "init_master"]


def make_placement(cfg: ArchConfig, mi: sh.MeshInfo,
                   strategy: str = "latin", seed: int = 0,
                   loads: Optional[np.ndarray] = None) -> Placement:
    """Expert placement over the (data × model) grid (paper §6).

    Thin wrapper over the engine's placement-strategy registry; ``strategy``
    is any registered key (built-ins: vanilla, random, latin, asymmetric)."""
    e_virt = cfg.num_experts * max(cfg.etp, 1)
    fn = placement_strategies.get(strategy)
    return fn(mi.data, mi.model, e_virt, seed=seed, loads=loads)


@dataclasses.dataclass
class DistRuntime:
    """Everything needed to run one architecture on one mesh."""

    cfg: ArchConfig
    mesh: Mesh
    mi: sh.MeshInfo
    rt: dec.Runtime                   # decoder runtime (moe island installed)
    hooks: LayoutHooks                # master -> working transform
    engine: Optional[MicroEPEngine]   # MicroEP machinery (None for dense)
    config: RuntimeConfig             # the full typed configuration
    capacity_factor: float
    mode: str                          # "microep" | "vanilla"
    dtype: Any
    layout: str = "scan"               # "scan" | "list" (dry-run cost pass)

    # -------- engine-derived views (kept for existing consumers) ---------
    @property
    def placement(self) -> Optional[Placement]:
        return self.engine.placement if self.engine is not None else None

    @property
    def sched_statics(self) -> Optional[ScheduleStatics]:
        return self.engine.statics if self.engine is not None else None

    # ---------------- abstract shapes for lowering ----------------------
    def master_sds(self):
        return _master_sds(self.cfg, self.mi, self.layout)

    def train_state_shardings(self) -> TrainState:
        """Where the train state lives: the master (and Adam's moments) in
        the ``sh.master_pspecs`` layout, step and warm start replicated."""
        m_sh = jax.tree_util.tree_map(lambda s: s.sharding, self.master_sds())
        rep = self.mi.named(P())
        solver_sds = self.solver_sds()
        return TrainState(
            master=m_sh, opt=AdamWState(step=rep, mu=m_sh, nu=m_sh),
            solver=None if solver_sds is None else jax.tree_util.tree_map(
                lambda s: s.sharding, solver_sds),
            step=rep)

    def new_train_state(self, key) -> TrainState:
        master = dec.init_params(key, self.cfg, jnp.float32,
                                 layout=self.layout)
        return TrainState(master=master, opt=adamw_init(master),
                          solver=self.init_solver(),
                          step=jnp.zeros((), jnp.int32))

    def init_train_state(self, key) -> TrainState:
        """The train state built where it lives (jit ``out_shardings``), so
        no device ever holds the whole tree first."""
        return jax.jit(self.new_train_state,
                       out_shardings=self.train_state_shardings())(key)

    def params_sds(self):
        master = self.master_sds()
        shapes = jax.eval_shape(self.hooks.to_working, master)
        specs = sh.param_pspecs(shapes, self.mi, self.cfg)
        return jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=self.mi.named(sp)),
            shapes, specs)

    def solver_sds(self):
        if not self.cfg.moe:
            return None
        r = self.sched_statics.max_replicas
        e = self.cfg.num_experts * max(self.cfg.etp, 1)
        shapes = jax.eval_shape(
            functools.partial(_init_solver, self.cfg, self.mi.pods, e, r,
                              self.layout))
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=self.mi.named(P("pod" if self.mi.has_pod else None))),
            shapes)

    def init_solver(self):
        e = self.cfg.num_experts * max(self.cfg.etp, 1)
        r = self.sched_statics.max_replicas if self.cfg.moe else 1
        return _init_solver(self.cfg, self.mi.pods, e, r, self.layout)


def _master_sds(cfg: ArchConfig, mi: sh.MeshInfo, layout: str):
    shapes = jax.eval_shape(
        lambda k: dec.init_params(k, cfg, jnp.float32, layout=layout),
        jax.random.PRNGKey(0))
    specs = sh.master_pspecs(shapes, mi, cfg)
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=mi.named(sp)),
        shapes, specs)


def init_master(cfg: ArchConfig, mesh: Mesh, key, layout: str = "scan"):
    """The f32 master built where it lives (``sh.master_pspecs``) rather
    than on the default device."""
    out = jax.tree_util.tree_map(
        lambda s: s.sharding, _master_sds(cfg, sh.MeshInfo(mesh), layout))
    return jax.jit(lambda k: dec.init_params(k, cfg, jnp.float32,
                                             layout=layout),
                   out_shardings=out)(key)


def _init_solver(cfg: ArchConfig, pods: int, e_virt: int, r: int,
                 layout: str = "scan"):
    if not cfg.moe:
        return None
    reps, rem = cfg.num_layers // len(cfg.pattern), \
        cfg.num_layers % len(cfg.pattern)

    def one():
        return SolverState(x=jnp.zeros((pods, e_virt, r), jnp.float32))

    if layout == "list":
        return {"list": tuple(one() for _ in range(cfg.num_layers))}
    st = {}
    if reps > 0:
        st["scan"] = tuple(
            jax.tree_util.tree_map(lambda x: jnp.stack([x] * reps), one())
            for _ in cfg.pattern)
    if rem > 0:
        st["rem"] = tuple(one() for _ in range(rem))
    return st


# --------------------------------------------------------------------------
# the MoE shard_map island
# --------------------------------------------------------------------------


def _build_moe_apply(cfg: ArchConfig, mi: sh.MeshInfo,
                     engine: MicroEPEngine, config: RuntimeConfig):
    etp = max(cfg.etp, 1)
    top_k_eff = cfg.top_k * etp
    act = "swiglu" if cfg.ffn_kind == "gelu_mlp" else cfg.ffn_kind
    group_axes = ("data", "model")
    all_axes = (("pod",) if mi.has_pod else ()) + group_axes
    total_dev = mi.group_size * mi.pods

    def moe_apply(p_moe, x2d, state, valid=None):
        n, h = x2d.shape
        pad = (-n) % total_dev
        npad = n + pad
        if pad:
            x2d = jnp.concatenate(
                [x2d, jnp.zeros((pad, h), x2d.dtype)], axis=0)
        row_ok = jnp.arange(npad) < n
        if valid is not None:     # inactive serving slots (SERVING.md)
            row_ok = row_ok & jnp.concatenate(
                [valid, jnp.zeros((pad,), bool)])
        valid = row_ok
        t_local = npad // total_dev
        stages = config.pipeline_stages
        mem_caps = None
        if engine.memory_model is not None:
            # MemFine (DESIGN.md §16): price this token geometry at trace
            # time — the plan's chunk count widens the dispatch pipeline
            # and its per-device token caps constrain the scheduler
            plan = engine.memory_plan(t_local, top_k_eff)
            stages = max(stages, plan.chunks)
            mem_caps = np.asarray(plan.token_caps, np.float32)
        spec = engine.moe_spec(
            t_local, top_k_eff, activation=act, group_axes=group_axes,
            capacity_factor=config.capacity_factor,
            kernel_impl=config.impl,
            pipeline_stages=stages,
            mem_caps=mem_caps)

        def inner(w_router, experts, x_loc, st_loc, valid_loc):
            experts_loc = jax.tree_util.tree_map(lambda w: w[0, 0], experts)
            st = jax.tree_util.tree_map(lambda s: s[0], st_loc) \
                if st_loc is not None else None
            r = top_k_gating(x_loc, w_router, cfg.top_k, valid=valid_loc)
            r = dec.expand_router_etp(r, etp)
            out, metrics, new_st = moe_ffn(
                spec, x_loc, w_router, experts_loc, state=st, router_out=r)
            metrics = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v.astype(jnp.float32), all_axes),
                metrics)
            new_st = jax.tree_util.tree_map(lambda s: s[None], new_st)
            return out, metrics, new_st

        tok_spec = P(("pod",) + group_axes if mi.has_pod else group_axes)
        pod_spec = P("pod") if mi.has_pod else P()
        out, metrics, new_state = shard_map(
            inner, mesh=mi.mesh,
            in_specs=(P(), P("data", "model"), tok_spec, pod_spec, tok_spec),
            out_specs=(tok_spec, P(), pod_spec),
            check_vma=False,
        )(p_moe["router"], p_moe["experts"], x2d, state, valid)
        return out[:n], metrics, new_state

    return moe_apply


# --------------------------------------------------------------------------
# layout hooks: canonical master <-> working placement layout
# --------------------------------------------------------------------------


def _build_hooks(cfg: ArchConfig, mi: sh.MeshInfo,
                 placement: Optional[Placement], dtype) -> LayoutHooks:
    if placement is None:
        return LayoutHooks.cast_only(dtype)
    # empty (budgeted) slots carry -1; clamp the gather — the dead slot
    # holds a copy of expert 0 that no token is ever scheduled toward
    table = jnp.maximum(jnp.asarray(placement.table, jnp.int32), 0)
    work_spec = mi.named(P("data", "model", None, None, None))

    def to_working(master):
        def leaf(path, x):
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            ps = sh._path_str(path)
            if "experts" in ps:
                # canonical [E_virt, H, F] (maybe scanned [R, E, H, F])
                if x.ndim == 4:   # scanned
                    w = x[:, table]        # [R, D, M, S, H, F]
                    w = w.astype(dtype)
                    return jax.lax.with_sharding_constraint(
                        w, mi.named(P(None, "data", "model", None, None, None)))
                w = x[table].astype(dtype)
                return jax.lax.with_sharding_constraint(w, work_spec)
            return x.astype(dtype)
        flat, treedef = jax.tree_util.tree_flatten_with_path(master)
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(p, x) for p, x in flat])

    # compiled, so that called outside a step (serving) the gather and
    # cast run as one program instead of one device copy per op
    return LayoutHooks(to_working=jax.jit(to_working))


# --------------------------------------------------------------------------
# runtime builder
# --------------------------------------------------------------------------


def build_runtime(
    cfg: ArchConfig,
    mesh: Mesh,
    config: Optional[RuntimeConfig] = None,
    *,
    placement_table: Optional[Placement] = None,
    **legacy_kwargs,
) -> DistRuntime:
    """Build the distributed runtime for one (arch config, mesh) pair.

    Preferred form::

        build_runtime(cfg, mesh, RuntimeConfig(
            placement=PlacementSpec("latin"),
            policy=SchedulePolicy(mode="microep"), dtype="float32"))

    ``placement_table`` installs a pre-built :class:`Placement` instead of
    the strategy named by ``config.placement`` — the adaptive replacement
    path (paper §6.4): the serving loop rebuilds the runtime around the
    regenerated table and re-materializes working params from the canonical
    master (the redistribute collective, moe/sync.py).

    The historical keyword surface (``dtype=``, ``placement_strategy=``,
    ``mode=``, ``capacity_factor=``, ...) keeps working as a shim and maps
    onto :meth:`RuntimeConfig.from_kwargs`.
    """
    if config is None:
        config = RuntimeConfig.from_kwargs(**legacy_kwargs)
    elif not isinstance(config, RuntimeConfig):
        raise ConfigError(
            f"build_runtime(config=...) must be a RuntimeConfig, "
            f"got {config!r}")
    elif legacy_kwargs:
        raise ConfigError(
            f"pass either a RuntimeConfig or legacy keyword options, not "
            f"both (got extra {sorted(legacy_kwargs)})")
    mi = sh.MeshInfo(mesh)
    engine = moe_apply = None
    if cfg.moe:
        e_virt = cfg.num_experts * max(cfg.etp, 1)
        if config.device_profiles is not None and \
                len(config.device_profiles) != mi.data * mi.model:
            raise ConfigError(
                f"device_profiles has {len(config.device_profiles)} "
                f"entries but the mesh's MicroEP group is "
                f"{mi.data}x{mi.model} = {mi.data * mi.model} devices "
                f"(one 'weight[@slots]' entry per flat device, row-major)")
        engine = MicroEPEngine.build(
            e_virt, (mi.data, mi.model),
            placement=(placement_table if placement_table is not None
                       else config.placement),
            policy=config.policy,
            device_profiles=config.device_profiles)
        if config.memory.enabled:
            # MemFine (DESIGN.md §16): price activations in the working
            # dtype; the engine caches a plan per token geometry and the
            # MoE island threads its chunk count + token caps through
            bytes_per_el = {"bfloat16": 2, "float16": 2, "float32": 4}[
                config.dtype]
            engine.install_memory(
                MemoryModel.from_arch(cfg, bytes_per_el),
                config.memory.budget_bytes,
                headroom=config.memory.headroom,
                recompute_policy=config.memory.recompute_policy,
                max_chunks=config.memory.max_chunks)
        moe_apply = _build_moe_apply(cfg, mi, engine, config)
    rt = dec.Runtime(moe_apply=moe_apply,
                     shard=sh.act_constraint(
                         mi, seq_parallel=config.seq_parallel),
                     impl=config.impl, remat=config.remat,
                     unroll=config.unroll)
    hooks = _build_hooks(cfg, mi,
                         engine.placement if engine is not None else None,
                         config.jax_dtype)
    return DistRuntime(cfg=cfg, mesh=mesh, mi=mi, rt=rt, hooks=hooks,
                       engine=engine, config=config,
                       capacity_factor=config.capacity_factor,
                       mode=config.policy.mode,
                       dtype=config.jax_dtype, layout=config.layout)


# --------------------------------------------------------------------------
# step functions + abstract inputs per input shape
# --------------------------------------------------------------------------


def make_train_fn(dr: DistRuntime, n_micro: int = 8,
                  opt_cfg: AdamWConfig = AdamWConfig(),
                  grad_rs: bool = False, with_expert_load: bool = False):
    """jit-able train_step(TrainState, batch) on the mesh.

    ``grad_rs``: constrain master grads to the ZeRO-1 master layout so the
    DP reduction lowers as reduce-scatter (§Perf lever).
    ``with_expert_load``: add the layer-summed per-expert load vector to
    the metrics dict (telemetry capture, TELEMETRY.md)."""
    constraint = None
    if grad_rs:
        mi, cfg = dr.mi, dr.cfg

        def constraint(grads):
            specs = sh.master_pspecs(grads, mi, cfg)
            return jax.tree_util.tree_map(
                lambda g, sp: jax.lax.with_sharding_constraint(
                    g, mi.named(sp)), grads, specs)

    step = make_train_step(dr.cfg, dr.rt, opt_cfg, dr.hooks,
                           n_micro=n_micro,
                           master_grad_constraint=constraint,
                           with_expert_load=with_expert_load)
    return step


def make_serve_fn(dr: DistRuntime):
    """serve_step(params, state, batch) -> (next_tokens, new_state)."""
    cfg, rt = dr.cfg, dr.rt

    def serve_step(params, state, batch):
        logits, new_state = dec.decode_step(params, cfg, state, batch, rt)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt, new_state

    return serve_step


def make_forward_fn(dr: DistRuntime, last_only: bool = True):
    """prefill_step(params, batch) -> logits.  Serving prefill needs only
    the final position's next-token distribution; the full-logit variant
    (last_only=False) exists for evaluation jobs."""
    cfg, rt = dr.cfg, dr.rt

    def prefill_step(params, batch):
        logits, _, _ = dec.forward(params, cfg, batch, rt,
                                   last_only=last_only)
        return logits

    return prefill_step


def input_specs(dr: DistRuntime, shape: InputShape, with_labels: bool = True):
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no
    allocation) for every model input of one (arch × input-shape) pair."""
    cfg, mi = dr.cfg, dr.mi
    b, t = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def sds(shape_, dtype, spec):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=mi.named(spec))

    if shape.kind in ("train", "prefill"):
        batch = {}
        bspec = sh.batch_pspecs({"x": jax.ShapeDtypeStruct((b,), i32)},
                                mi)["x"]
        row = bspec[0] if len(bspec) else None
        if cfg.frontend_stub == "vision":
            batch["embeds"] = sds((b, t, cfg.d_model), dr.dtype,
                                  P(row, None, None))
            batch["positions"] = sds((b, t, 3), i32, P(row, None, None))
        else:
            batch["tokens"] = sds((b, t), i32, P(row, None))
        if with_labels and shape.kind == "train":
            batch["labels"] = sds((b, t), i32, P(row, None))
        return batch

    # decode: one new token against a seq_len cache
    batch = {}
    bspec = sh.batch_pspecs({"x": jax.ShapeDtypeStruct((b,), i32)}, mi)["x"]
    row = bspec[0] if len(bspec) else None
    if cfg.frontend_stub == "vision":
        batch["embeds"] = sds((b, 1, cfg.d_model), dr.dtype, P(row, None, None))
    else:
        batch["tokens"] = sds((b, 1), i32, P(row, None))
    return batch


def decode_state_sds(dr: DistRuntime, shape: InputShape):
    cfg, mi = dr.cfg, dr.mi
    shapes = jax.eval_shape(
        functools.partial(dec.init_decode_state, cfg, shape.global_batch,
                          shape.seq_len, dr.dtype, layout=dr.layout))
    specs = sh.cache_pspecs(shapes, mi, cfg, shape.global_batch)
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=mi.named(sp)),
        shapes, specs)
