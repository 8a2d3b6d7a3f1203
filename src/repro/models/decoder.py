"""Composable decoder-only model covering every assigned architecture family.

One parameterized decoder handles: dense GQA/MQA transformers (gemma, qwen),
5:1 local:global sliding-window stacks (gemma-3), MoE transformers with
MicroEP dispatch (dbrx, olmoe, the paper's GPT/Mixtral), attention-free
RWKV-6 (ssm), RG-LRU hybrids (recurrentgemma), M-RoPE VLM backbones
(qwen2-vl, vision frontend stubbed to patch embeddings) and audio decoders
over EnCodec tokens (musicgen).

Distribution model (DESIGN.md §3): the step function is pure JAX and runs
under ``jax.jit`` with GSPMD sharding constraints for everything EXCEPT the
MoE dispatch, which is the paper's contribution and runs as an explicit
``shard_map`` island supplied through ``Runtime.moe_apply``.  With
``rt=None`` (CPU smoke tests, quickstart) the same code runs the full MicroEP
machinery on a degenerate single-device group.

Layer stacking: layers are grouped by the config's ``pattern`` and scanned
with ``lax.scan`` over pattern repetitions (compile time stays O(pattern),
not O(num_layers)); the non-divisible remainder is unrolled.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.solver_jax import SolverState
from ..engine import MicroEPEngine
from ..moe.experts import ExpertParams, init_canonical_experts
from ..moe.layer import MoEFFNSpec, MoEMetrics, moe_ffn
from ..moe.router import top_k_gating
from .layers.attention import (AttnConfig, KVCache, attention,
                               decode_attention, init_attention,
                               init_kv_cache)
from .layers.ffn import ffn, init_ffn
from .layers.norms import init_ln, init_rms, layer_norm, rms_norm
from .layers.rglru import RGLRUState, init_rglru_block, rglru_block
from .layers.rwkv6 import (RWKVState, init_rwkv6, init_rwkv6_channel,
                           rwkv6_channel_mix, rwkv6_time_mix)

__all__ = ["Runtime", "Metrics", "init_params", "forward", "lm_loss",
           "loss_fn", "init_decode_state", "decode_step", "expand_router_etp",
           "local_moe_apply", "param_dtypes", "reset_decode_slots",
           "extract_decode_slot", "insert_decode_slot", "decode_slot_bytes",
           "n_moe_layers"]


# --------------------------------------------------------------------------
# runtime: how the model touches the mesh
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Everything the decoder needs to know about its execution environment.

    moe_apply: (p_moe, x2d, solver_state, valid=None) -> (out2d, MoEMetrics,
      new_state); ``valid`` is an optional bool[T] row mask (inactive
      serving slots).  None = build a single-device MicroEP group locally
      (CPU smoke path).
    shard: activation-constraint hook ``shard(x, name)``; identity if None.
    impl: kernel implementation ('ref' | 'interpret' | 'pallas').
    seq_axis: mesh axis carrying the sequence shards of global-attention
      KV caches in long-context decode (DESIGN.md §6), else None.
    """

    moe_apply: Optional[Callable] = None
    shard: Optional[Callable] = None
    impl: Optional[str] = None
    seq_axis: Optional[str] = None
    seq_shards: int = 1
    remat: bool = False
    # Unroll the layer scan into straight-line HLO.  Needed for roofline
    # extraction: XLA's cost_analysis counts a while-loop body ONCE, so a
    # scanned stack under-reports FLOPs/bytes by the trip count.
    unroll: bool = False

    def constrain(self, x: jax.Array, name: str) -> jax.Array:
        return self.shard(x, name) if self.shard is not None else x


_NULL_RT = Runtime()


class Metrics(NamedTuple):
    loss: jax.Array
    ce_loss: jax.Array
    aux_loss: jax.Array
    z_loss: jax.Array
    balance: jax.Array    # mean over MoE layers of max/mean device load
    overflow: jax.Array   # total capacity-overflow rows (0 in practice)


# --------------------------------------------------------------------------
# config helpers
# --------------------------------------------------------------------------


def _attn_cfg(cfg: ArchConfig, kind: str) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        qk_norm=cfg.qk_norm,
        logit_softcap=cfg.logit_softcap,
        window=cfg.window if kind == "attn_local" else 0,
        rope_theta=cfg.rope_theta,
        mrope_sections=tuple(cfg.mrope_sections),
    )


def _norm_init(cfg: ArchConfig, d: int, dtype):
    return init_ln(d, dtype) if cfg.norm == "ln" else init_rms(d, dtype)


def _norm(cfg: ArchConfig, p, x):
    return layer_norm(p, x) if cfg.norm == "ln" else rms_norm(p, x)


def _pattern_counts(cfg: ArchConfig):
    p = len(cfg.pattern)
    return cfg.num_layers // p, cfg.num_layers % p


# --------------------------------------------------------------------------
# parameter initialization
# --------------------------------------------------------------------------


def _init_moe_part(key, cfg: ArchConfig, dtype, moe_param_init):
    kr, ke = jax.random.split(key)
    router = (jax.random.normal(kr, (cfg.d_model, cfg.num_experts))
              * cfg.d_model ** -0.5).astype(jnp.float32)
    if moe_param_init is not None:
        experts = moe_param_init(ke)
    else:  # local single-device group: slots = all (virtual) experts
        experts = init_canonical_experts(
            ke, cfg.num_experts * max(cfg.etp, 1), cfg.d_model,
            cfg.moe_d_ff // max(cfg.etp, 1), dtype)
    return {"router": router, "experts": experts}


def _init_block(key, cfg: ArchConfig, kind: str, dtype, moe_param_init):
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": _norm_init(cfg, cfg.d_model, dtype),
               "ln2": _norm_init(cfg, cfg.d_model, dtype)}
    if kind.startswith("attn"):
        p["attn"] = init_attention(ks[0], _attn_cfg(cfg, kind), dtype)
        if cfg.moe:
            p["moe"] = _init_moe_part(ks[1], cfg, dtype, moe_param_init)
        else:
            p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_kind,
                                dtype=dtype)
    elif kind == "rwkv":
        p["time"] = init_rwkv6(ks[0], cfg.d_model, cfg.num_heads, dtype=dtype)
        p["chan"] = init_rwkv6_channel(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif kind == "rglru":
        p["rec"] = init_rglru_block(ks[0], cfg.d_model, cfg.lru_width,
                                    cfg.conv_k, dtype)
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_kind,
                            dtype=dtype)
    else:
        raise ValueError(kind)
    return p


def init_params(key, cfg: ArchConfig, dtype=jnp.float32,
                moe_param_init=None, layout: str = "scan") -> dict:
    """Full parameter pytree.  ``moe_param_init(key) -> ExpertParams`` lets
    the launcher install working-layout (placement) expert slots; default is
    the local canonical layout used by CPU smoke tests.

    layout="scan": layers stacked [reps, ...] for lax.scan (production).
    layout="list": one tuple entry per layer (no stacked buffers) — used by
    the dry-run cost pass, where stacked-buffer gradient scatters add an
    O(L²) cost-model artifact."""
    reps, rem = _pattern_counts(cfg)
    pat = cfg.pattern
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def one_group(k):
        kk = jax.random.split(k, len(pat))
        return tuple(
            _init_block(kk[i], cfg, pat[i], dtype, moe_param_init)
            for i in range(len(pat))
        )

    params: dict = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dtype),
        "final_norm": _norm_init(cfg, cfg.d_model, dtype),
    }
    if layout == "list":
        kk = jax.random.split(k_layers, cfg.num_layers)
        params["layers_list"] = tuple(
            _init_block(kk[i], cfg, pat[i % len(pat)], dtype,
                        moe_param_init)
            for i in range(cfg.num_layers))
    else:
        if reps > 0:
            keys = jax.random.split(k_layers, reps)
            groups = [one_group(k) for k in keys]
            params["layers_scan"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *groups)
        if rem > 0:
            kk = jax.random.split(k_head, rem)
            params["layers_rem"] = tuple(
                _init_block(kk[i], cfg, pat[i], dtype, moe_param_init)
                for i in range(rem)
            )
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab))
                          * cfg.d_model ** -0.5).astype(dtype)
    return params


def param_dtypes(params, dtype):
    """Cast all floating leaves (for bf16 working copies)."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


# --------------------------------------------------------------------------
# MoE block (the paper's technique lives behind rt.moe_apply)
# --------------------------------------------------------------------------


def expand_router_etp(r, etp: int):
    """Virtual-expert expansion for intra-expert tensor parallelism.

    Expert e is stored as ``etp`` shards (virtual experts e*etp+j) with
    d_ff/etp each; a token routed to e visits *all* shards and the combine
    sum over the K*etp rows reconstructs the full down-projection (partial
    sums).  This keeps expert-TP inside the standard dispatch/combine
    collectives — no sub-axis process groups needed (DESIGN.md §2)."""
    if etp <= 1:
        return r
    t, k = r.expert_ids.shape
    ids = (r.expert_ids[:, :, None] * etp
           + jnp.arange(etp, dtype=jnp.int32)[None, None, :]).reshape(t, k * etp)
    gw = jnp.repeat(r.gate_w, etp, axis=1)
    return r._replace(expert_ids=ids, gate_w=gw)


@functools.lru_cache(maxsize=32)
def _local_moe_engine(num_virtual: int) -> MicroEPEngine:
    """Degenerate single-device MicroEP group (G=1): all slots local."""
    return MicroEPEngine.build(num_virtual, (1, 1), placement="vanilla")


def _local_moe_spec(num_virtual: int, top_k_eff: int, tokens: int,
                    activation: str, impl: Optional[str]) -> MoEFFNSpec:
    return _local_moe_engine(num_virtual).moe_spec(
        tokens, top_k_eff, activation=activation, group_axes=(),
        capacity_factor=2.0, bm=8, kernel_impl=impl)


def local_moe_apply(p_moe, x2d, cfg: ArchConfig, state, impl=None,
                    valid=None):
    etp = max(cfg.etp, 1)
    act = "swiglu" if cfg.ffn_kind == "gelu_mlp" else cfg.ffn_kind
    spec = _local_moe_spec(cfg.num_experts * etp, cfg.top_k * etp,
                           int(x2d.shape[0]), act, impl)
    r = top_k_gating(x2d, p_moe["router"], cfg.top_k, valid=valid)
    r = expand_router_etp(r, etp)
    return moe_ffn(spec, x2d, p_moe["router"], p_moe["experts"],
                   state=state, router_out=r)


def _moe_block(p_moe, x, cfg: ArchConfig, rt: Runtime, state, valid=None):
    """``valid``: optional bool[B] row mask (continuous batching feeds pad
    tokens on inactive slots; masking keeps them out of routing, capacity
    and the load metrics)."""
    b, t, h = x.shape
    x2d = x.reshape(b * t, h)
    valid2d = None if valid is None else jnp.repeat(valid, t)
    if rt.moe_apply is not None:
        out2d, metrics, new_state = rt.moe_apply(p_moe, x2d, state,
                                                 valid=valid2d)
    else:
        out2d, metrics, new_state = local_moe_apply(
            p_moe, x2d, cfg, state, impl=rt.impl, valid=valid2d)
    return out2d.reshape(b, t, h), metrics, new_state


_ZERO_MOE = MoEMetrics(*(jnp.zeros(()) for _ in range(6)))


def _zero_moe(cfg: ArchConfig) -> MoEMetrics:
    """Shape-correct zero metrics accumulator: ``expert_load`` is [E_virt]
    for MoE configs so scan carries stay shape-stable under accumulation
    (dense-layer zeros broadcast into it)."""
    z = jnp.zeros(())
    if not cfg.moe:
        return _ZERO_MOE
    e = jnp.zeros((cfg.num_experts * max(cfg.etp, 1),))
    return MoEMetrics(z, z, z, z, z, e)


def n_moe_layers(cfg: ArchConfig) -> int:
    """Number of MoE layers (normalizes summed per-layer metrics)."""
    if not cfg.moe:
        return 0
    return sum(1 for i in range(cfg.num_layers)
               if cfg.pattern[i % len(cfg.pattern)].startswith("attn"))


# --------------------------------------------------------------------------
# forward (training / prefill)
# --------------------------------------------------------------------------


def _block_fwd(p, cfg: ArchConfig, rt: Runtime, kind: str,
               x, positions, state):
    """One block.  ``state`` is the MoE solver warm-start (or None)."""
    metrics = _ZERO_MOE
    new_state = state
    if kind.startswith("attn"):
        h = _norm(cfg, p["ln1"], x)
        h = attention(p["attn"], _attn_cfg(cfg, kind), h, positions,
                      unroll=rt.unroll)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        if cfg.moe:
            h, metrics, new_state = _moe_block(p["moe"], h, cfg, rt, state)
        else:
            h = ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + h
    elif kind == "rwkv":
        h = _norm(cfg, p["ln1"], x)
        h, _, _ = rwkv6_time_mix(p["time"], h, cfg.num_heads, impl=rt.impl)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        h, _ = rwkv6_channel_mix(p["chan"], h)
        x = x + h
    elif kind == "rglru":
        h = _norm(cfg, p["ln1"], x)
        h, _ = rglru_block(p["rec"], h, conv_k=cfg.conv_k)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        h = ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + h
    else:
        raise ValueError(kind)
    x = rt.constrain(x, "act")
    return x, metrics, new_state


def _accum(acc, m: MoEMetrics):
    return MoEMetrics(acc.aux_loss + m.aux_loss, acc.z_loss + m.z_loss,
                      acc.max_load + m.max_load, acc.balance + m.balance,
                      acc.overflow + m.overflow.astype(jnp.float32),
                      acc.expert_load + m.expert_load)


def _default_positions(cfg: ArchConfig, b: int, t: int):
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (b, t, 3))
    return pos


def forward(params, cfg: ArchConfig, batch: dict, rt: Runtime = _NULL_RT,
            solver_states=None, return_hidden: bool = False,
            last_only: bool = False):
    """Full forward pass -> (logits, moe_metrics_sum, new_solver_states).

    batch: {"tokens": int32[B, T]} and/or {"embeds": [B, T, dm]},
    optional {"positions": int32[B, T] or [B, T, 3]}.

    ``return_hidden`` skips the output head (the chunked-CE loss path owns
    it); ``last_only`` computes logits for the final position only (serving
    prefill — the decode loop needs just the next-token distribution).
    """
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"]
        b, t, _ = x.shape
    else:
        tokens = batch["tokens"]
        b, t = tokens.shape
        x = params["embed"][tokens]
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, b, t)
    x = rt.constrain(x, "act")

    reps, rem = _pattern_counts(cfg)
    pat = cfg.pattern
    acc = _zero_moe(cfg)
    new_states: dict = {}

    block = _block_fwd
    if rt.remat:
        block = jax.checkpoint(_block_fwd,
                               static_argnums=(1, 2, 3))  # cfg, rt, kind

    if "layers_list" in params:   # flat per-layer layout (cost pass)
        st_list = (solver_states or {}).get("list")
        new_list = []
        for i in range(cfg.num_layers):
            st = None if st_list is None else st_list[i]
            x, m, s = block(params["layers_list"][i], cfg, rt,
                            pat[i % len(pat)], x, positions, st)
            acc = _accum(acc, m)
            new_list.append(s)
        new_states["list"] = tuple(new_list)
        reps = rem = 0   # skip the scan/rem paths below

    if reps > 0:
        def body(carry, xs):
            x, acc = carry
            p_group, st_group = xs
            new_st = []
            for i, kind in enumerate(pat):
                st = None if st_group is None else st_group[i]
                x, m, s = block(p_group[i], cfg, rt, kind, x,
                                positions, st)
                acc = _accum(acc, m)
                new_st.append(s)
            return (x, acc), tuple(new_st)

        st_scan = (solver_states or {}).get("scan")
        xs = (params["layers_scan"], st_scan)
        if rt.unroll:
            outs = []
            for r in range(reps):
                xs_r = jax.tree_util.tree_map(lambda a: a[r], xs)
                (x, acc), st_r = body((x, acc), xs_r)
                outs.append(st_r)
            st_out = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *outs)
        else:
            (x, acc), st_out = jax.lax.scan(body, (x, acc), xs)
        new_states["scan"] = st_out

    if rem > 0:
        st_rem = (solver_states or {}).get("rem")
        new_rem = []
        for i in range(rem):
            st = None if st_rem is None else st_rem[i]
            x, m, s = block(params["layers_rem"][i], cfg, rt, pat[i],
                            x, positions, st)
            acc = _accum(acc, m)
            new_rem.append(s)
        new_states["rem"] = tuple(new_rem)

    x = _norm(cfg, params["final_norm"], x)
    if not cfg.moe:
        new_states = solver_states   # keep carry structure for scan loops
    if return_hidden:
        return x, acc, new_states
    head = params.get("head")
    w_out = head if head is not None else params["embed"].T
    if last_only:
        x = x[:, -1:]
    logits = rt.constrain(x @ w_out, "logits")
    return logits, acc, new_states


def init_solver_states(cfg: ArchConfig, num_replicas: int,
                       layout: str = "scan") -> Optional[dict]:
    """Warm-start carry for every MoE layer ([E_virt, R] zeros)."""
    if not cfg.moe:
        return None
    reps, rem = _pattern_counts(cfg)
    e_virt = cfg.num_experts * max(cfg.etp, 1)

    def one():
        return SolverState(x=jnp.zeros((e_virt, num_replicas), jnp.float32))

    if layout == "list":
        return {"list": tuple(one() for _ in range(cfg.num_layers))}
    st: dict = {}
    if reps > 0:
        st["scan"] = tuple(
            jax.tree_util.tree_map(lambda x: jnp.stack([x] * reps), one())
            for _ in cfg.pattern)
    if rem > 0:
        st["rem"] = tuple(one() for _ in range(rem))
    return st


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------


def lm_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross entropy; labels < 0 are masked."""
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)


def lm_loss_chunked(x: jax.Array, w_out: jax.Array, labels: jax.Array,
                    chunk_t: int = 512, unroll: bool = False,
                    constrain=None):
    """Cross entropy over [B, T, dm] hidden states with the [B, T, V]
    logits never materialized at once: the TIME axis is processed in chunks
    (batch sharding is preserved — flattening tokens would destroy it and
    replicate logit compute across the data axis) and each chunk's logits
    live only inside a rematerialized block."""
    b, t, dm = x.shape
    chunk = min(chunk_t, t)
    pad = (-t) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((b, pad, dm), x.dtype)], axis=1)
        labels = jnp.concatenate(
            [labels, -jnp.ones((b, pad), labels.dtype)], axis=1)
    n_chunks = (t + pad) // chunk
    xc = x.reshape(b, n_chunks, chunk, dm).swapaxes(0, 1)
    lc = labels.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(xi, li):
        logits = (xi @ w_out).astype(jnp.float32)   # [B, chunk, V]
        if constrain is not None:
            logits = constrain(logits, "logits")
        mask = (li >= 0).astype(jnp.float32)
        safe = jnp.maximum(li, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * mask), mask.sum()

    if unroll:
        parts = [one(xc[i], lc[i]) for i in range(n_chunks)]
        nll = sum(p[0] for p in parts)
        cnt = sum(p[1] for p in parts)
    else:
        def body(carry, inp):
            s, c = carry
            ds, dc = one(*inp)
            return (s + ds, c + dc), None
        (nll, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                     (xc, lc))
    return nll / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ArchConfig, batch: dict, rt: Runtime = _NULL_RT,
            solver_states=None, aux_coeff: float = 1e-4,
            z_coeff: float = 1e-4, loss_chunk_t: int = 512,
            with_expert_load: bool = False):
    """Scalar training loss (CE + MoE aux) -> (loss, (Metrics, new_states)).

    ``with_expert_load=True`` appends the layer-summed per-expert routed
    token counts (f32[E_virt], ``MoEMetrics.expert_load``) to the aux tuple
    — the training-side feed for the telemetry recorder (TELEMETRY.md)."""
    hidden, moe, new_states = forward(params, cfg, batch, rt, solver_states,
                                      return_hidden=True)
    head = params.get("head")
    w_out = head if head is not None else params["embed"].T
    ce = lm_loss_chunked(hidden, w_out, batch["labels"],
                         chunk_t=loss_chunk_t, unroll=rt.unroll,
                         constrain=rt.shard)
    n_moe = max(sum(1 for k in cfg.pattern if k.startswith("attn")), 1) \
        * max(_pattern_counts(cfg)[0], 1) if cfg.moe else 1
    loss = ce + aux_coeff * moe.aux_loss + z_coeff * moe.z_loss
    metrics = Metrics(loss=loss, ce_loss=ce, aux_loss=moe.aux_loss,
                      z_loss=moe.z_loss,
                      balance=moe.balance / n_moe,
                      overflow=moe.overflow)
    if with_expert_load:
        return loss, (metrics, new_states, moe.expert_load)
    return loss, (metrics, new_states)


# --------------------------------------------------------------------------
# decode (serve_step)
# --------------------------------------------------------------------------


def _init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_seq: int,
                      dtype, rt: Runtime):
    if kind.startswith("attn"):
        return init_kv_cache(
            _attn_cfg(cfg, kind), batch, max_seq, dtype,
            seq_shards=rt.seq_shards if kind == "attn" else 1)
    if kind == "rwkv":
        hd = cfg.d_model // cfg.num_heads
        return RWKVState(
            wkv=jnp.zeros((batch, cfg.num_heads, hd, hd), jnp.float32),
            shift_t=jnp.zeros((batch, cfg.d_model), dtype),
            shift_c=jnp.zeros((batch, cfg.d_model), dtype),
        )
    if kind == "rglru":
        return RGLRUState(
            h=jnp.zeros((batch, cfg.lru_width), dtype),
            conv=jnp.zeros((batch, cfg.conv_k - 1, cfg.lru_width), dtype),
        )
    raise ValueError(kind)


def init_decode_state(cfg: ArchConfig, batch: int, max_seq: int,
                      dtype=jnp.float32, rt: Runtime = _NULL_RT,
                      layout: str = "scan", per_slot: bool = False) -> dict:
    """Per-layer decode caches, stacked to mirror the scan layout.

    ``per_slot=True`` makes the position counter an int32[batch] vector so
    every batch slot decodes at its own sequence position — the continuous-
    batching mode (SERVING.md); the fixed-batch default keeps the scalar."""
    reps, rem = _pattern_counts(cfg)
    pat = cfg.pattern
    state: dict = {"pos": jnp.zeros((batch,) if per_slot else (),
                                    jnp.int32)}
    if layout == "list":
        state["list"] = tuple(
            _init_block_cache(cfg, pat[i % len(pat)], batch, max_seq,
                              dtype, rt)
            for i in range(cfg.num_layers))
        return state
    if reps > 0:
        state["scan"] = tuple(
            jax.tree_util.tree_map(
                lambda x: jnp.stack([x] * reps),
                _init_block_cache(cfg, pat[i], batch, max_seq, dtype, rt))
            for i in range(len(pat)))
    if rem > 0:
        state["rem"] = tuple(
            _init_block_cache(cfg, pat[i], batch, max_seq, dtype, rt)
            for i in range(rem))
    return state


def _block_decode(p, cfg: ArchConfig, rt: Runtime, kind: str, x, cache,
                  pos, solver_st=None, active=None):
    """x: [B, 1, dm].  Returns (x, new_cache, moe_metrics, new_solver)."""
    metrics = _ZERO_MOE
    new_solver = solver_st
    if kind.startswith("attn"):
        h = _norm(cfg, p["ln1"], x)
        cache = cache._replace(length=pos)
        h, cache = decode_attention(
            p["attn"], _attn_cfg(cfg, kind), h, cache,
            seq_axis=rt.seq_axis if kind == "attn" else None)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        if cfg.moe:
            h, metrics, new_solver = _moe_block(p["moe"], h, cfg, rt,
                                                solver_st, valid=active)
        else:
            h = ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + h
        return x, cache, metrics, new_solver
    if kind == "rwkv":
        h = _norm(cfg, p["ln1"], x)
        h, new_wkv, shift_t = rwkv6_time_mix(p["time"], h, cfg.num_heads,
                                             state=cache)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        h, shift_c = rwkv6_channel_mix(p["chan"], h, state_prev=cache.shift_c)
        x = x + h
        return (x, RWKVState(wkv=new_wkv, shift_t=shift_t, shift_c=shift_c),
                metrics, new_solver)
    if kind == "rglru":
        h = _norm(cfg, p["ln1"], x)
        h, new_state = rglru_block(p["rec"], h, state=cache, conv_k=cfg.conv_k)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        h = ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + h
        return x, new_state, metrics, new_solver
    raise ValueError(kind)


def decode_step(params, cfg: ArchConfig, state: dict, batch: dict,
                rt: Runtime = _NULL_RT, with_metrics: bool = False):
    """One-token decode: batch {"tokens": int32[B, 1]} or {"embeds":
    [B, 1, dm]} -> (logits [B, 1, V], new_state).

    ``state["pos"]`` may be a scalar (fixed batch) or int32[B] per-slot
    positions (continuous batching).  An optional batch {"active": bool[B]}
    mask keeps inactive serving slots (pad tokens) out of MoE routing,
    capacity and load metrics.  When ``state`` carries a "solver" entry
    (from :func:`init_solver_states` / ``DistRuntime.init_solver``) the MoE
    scheduler re-solves every decode step on the live batch's expert loads
    with the warm start threaded through steps, exactly as in training
    (SERVING.md).  ``with_metrics=True`` additionally returns the
    per-layer-summed :class:`MoEMetrics` (balance ratio, expert loads) as a
    third output.
    """
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"]
    else:
        x = params["embed"][batch["tokens"]]
    b = x.shape[0]
    pos = state["pos"]
    solver = state.get("solver")
    active = batch.get("active")
    x = rt.constrain(x, "act")

    reps, rem = _pattern_counts(cfg)
    pat = cfg.pattern
    acc = _zero_moe(cfg)
    new_state: dict = {"pos": pos + 1}
    new_solver: dict = {}

    if "layers_list" in params:   # flat per-layer layout (cost pass)
        st_list = None if solver is None else solver.get("list")
        new_list, new_sl = [], []
        for i in range(cfg.num_layers):
            st = None if st_list is None else st_list[i]
            x, c, m, s = _block_decode(params["layers_list"][i], cfg, rt,
                                       pat[i % len(pat)], x,
                                       state["list"][i], pos, st, active)
            acc = _accum(acc, m)
            new_list.append(c)
            new_sl.append(s)
        new_state["list"] = tuple(new_list)
        if solver is not None:
            new_solver["list"] = tuple(new_sl)
        reps = rem = 0

    if reps > 0:
        st_scan = None if solver is None else solver.get("scan")

        def body(carry, xs):
            x, acc = carry
            p_group, c_group, st_group = xs
            new_c, new_st = [], []
            for i, kind in enumerate(pat):
                st = None if st_group is None else st_group[i]
                x, c, m, s = _block_decode(p_group[i], cfg, rt, kind, x,
                                           c_group[i], pos, st, active)
                acc = _accum(acc, m)
                new_c.append(c)
                new_st.append(s)
            return (x, acc), (tuple(new_c), tuple(new_st))

        xs = (params["layers_scan"], state["scan"], st_scan)
        if rt.unroll:
            outs = []
            for r in range(reps):
                xs_r = jax.tree_util.tree_map(lambda a: a[r], xs)
                (x, acc), ys_r = body((x, acc), xs_r)
                outs.append(ys_r)
            c_out, st_out = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *outs)
        else:
            (x, acc), (c_out, st_out) = jax.lax.scan(body, (x, acc), xs)
        new_state["scan"] = c_out
        if solver is not None:
            new_solver["scan"] = st_out

    if rem > 0:
        st_rem = None if solver is None else solver.get("rem")
        new_rem, new_sr = [], []
        for i in range(rem):
            st = None if st_rem is None else st_rem[i]
            x, c, m, s = _block_decode(params["layers_rem"][i], cfg, rt,
                                       pat[i], x, state["rem"][i], pos, st,
                                       active)
            acc = _accum(acc, m)
            new_rem.append(c)
            new_sr.append(s)
        new_state["rem"] = tuple(new_rem)
        if solver is not None:
            new_solver["rem"] = tuple(new_sr)

    if "solver" in state:
        new_state["solver"] = new_solver if solver is not None else None

    x = _norm(cfg, params["final_norm"], x)
    head = params.get("head")
    logits = x @ (head if head is not None else params["embed"].T)
    if with_metrics:
        return logits, new_state, acc
    return logits, new_state


def reset_decode_slots(state: dict, mask: jax.Array) -> dict:
    """Clear the per-sequence decode caches of masked batch slots.

    The continuous-batching admit/evict hook (SERVING.md): ``mask`` is
    bool[B]; slot i's KV / recurrent caches and position counter are zeroed
    where ``mask[i]`` so a new request can be admitted into (or an evicted
    one removed from) the slot.  The solver warm start ("solver") is a
    property of the expert-load stream, not of any one sequence, and is
    kept.  Requires per-slot positions (``init_decode_state(...,
    per_slot=True)``)."""
    b = mask.shape[0]

    def clear(axis, leaf):
        if getattr(leaf, "ndim", 0) <= axis or leaf.shape[axis] != b:
            return leaf               # scalar lengths, odd-shaped leaves
        shape = [1] * leaf.ndim
        shape[axis] = b
        m = mask.reshape(shape)
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    out = dict(state)
    if getattr(state["pos"], "ndim", 0) != 1:
        raise ValueError("reset_decode_slots needs per-slot positions; "
                         "build the state with init_decode_state(..., "
                         "per_slot=True)")
    out["pos"] = jnp.where(mask, 0, state["pos"])
    for key, axis in (("scan", 1), ("rem", 0), ("list", 0)):
        if key in state:
            out[key] = jax.tree_util.tree_map(
                functools.partial(clear, axis), state[key])
    return out


# The per-slot cache axes of a per-slot decode state: "scan" leaves are
# stacked [reps, B, ...], "rem"/"list" leaves are [B, ...].  Shared with
# reset_decode_slots; extract/insert below carry one slot's slice across
# states of *different* batch widths (the prefill->decode KV handoff of
# SERVING.md / DESIGN.md §13).
_SLOT_AXES = (("scan", 1), ("rem", 0), ("list", 0))


def extract_decode_slot(state: dict, slot: int) -> dict:
    """Slice one slot's per-sequence caches out of a per-slot decode state.

    Returns the KV-handoff payload of a completed prefill: the slot's
    position counter plus, for every cache leaf that carries a slot axis,
    the slot's slice (slot axis removed).  Leaves without a slot axis
    (scalar lengths, shared statics) pass through unchanged and are
    ignored by :func:`insert_decode_slot`.  The "solver" warm start is a
    property of a fleet's expert-load stream, not of any one sequence,
    and is excluded."""
    if getattr(state["pos"], "ndim", 0) != 1:
        raise ValueError("extract_decode_slot needs per-slot positions; "
                         "build the state with init_decode_state(..., "
                         "per_slot=True)")
    b = state["pos"].shape[0]

    def take(axis, leaf):
        if getattr(leaf, "ndim", 0) <= axis or leaf.shape[axis] != b:
            return leaf
        return jnp.take(leaf, slot, axis=axis)

    out: dict = {"pos": state["pos"][slot]}
    for key, axis in _SLOT_AXES:
        if key in state:
            out[key] = jax.tree_util.tree_map(
                functools.partial(take, axis), state[key])
    return out


def insert_decode_slot(state: dict, payload: dict, slot: int) -> dict:
    """Write a KV-handoff payload (from :func:`extract_decode_slot`, on a
    state of any batch width but the same ``max_seq``) into ``slot`` of a
    per-slot decode state — the receive side of the prefill->decode
    boundary.  Returns the new state; the "solver" entry (if any) is the
    receiving fleet's and is kept untouched."""
    if getattr(state["pos"], "ndim", 0) != 1:
        raise ValueError("insert_decode_slot needs per-slot positions; "
                         "build the state with init_decode_state(..., "
                         "per_slot=True)")
    b = state["pos"].shape[0]

    def put(axis, leaf, pl):
        if getattr(leaf, "ndim", 0) <= axis or leaf.shape[axis] != b:
            return leaf
        idx = [slice(None)] * leaf.ndim
        idx[axis] = slot
        return leaf.at[tuple(idx)].set(jnp.asarray(pl, leaf.dtype))

    out = dict(state)
    out["pos"] = state["pos"].at[slot].set(
        jnp.asarray(payload["pos"], state["pos"].dtype))
    for key, axis in _SLOT_AXES:
        if key in state:
            out[key] = jax.tree_util.tree_map(
                functools.partial(put, axis), state[key], payload[key])
    return out


def decode_slot_bytes(state: dict) -> int:
    """Bytes one slot's KV-handoff payload occupies (the staged-transfer
    size a :class:`repro.serve.HandoffBuffer` entry accounts): per-slot
    cache bytes / batch width, position counter included."""
    if getattr(state["pos"], "ndim", 0) != 1:
        raise ValueError("decode_slot_bytes needs per-slot positions")
    b = state["pos"].shape[0]
    total = state["pos"].dtype.itemsize

    def add(axis, leaf):
        nonlocal total
        if getattr(leaf, "ndim", 0) > axis and leaf.shape[axis] == b:
            total += leaf.nbytes // b

    for key, axis in _SLOT_AXES:
        if key in state:
            jax.tree_util.tree_map(functools.partial(add, axis), state[key])
    return int(total)
