"""The MoE FFN layer with MicroEP scheduling — the paper's technique as a
first-class module.

``moe_ffn`` is a *per-device* function (call it inside shard_map; or with
``group_axes=()`` on a single device — the degenerate G=1 group used by CPU
smoke tests).  Steps (paper §4 "Runtime"):

  gate -> counts all-gather -> schedule (LP solve + rounding + Alg.1 routing)
       -> dispatch all-to-all -> grouped expert FFN -> combine all-to-all
       -> weighted top-K merge

The scheduler's solver state (warm start) threads through micro-batches.

With ``pipeline_stages > 1`` the dispatch/compute/combine critical path
runs destination-chunked (DESIGN.md §2): the collectives split into stages
of G/n destination offsets and the grouped FFN runs per chunk, so chunk
i's compute and chunk i+1's collective are independent in the dataflow
graph — XLA's scheduler can overlap them.  The pipelined path is
bit-identical to the monolithic one (rows keep their replica/segment
assignment; the FFN is row-wise).

Heterogeneous groups (DESIGN.md §11) need no layer-level branching: the
scheduler inside the spec solves the weighted LP when its statics carry
device weights, the dispatch statics derive a weight-aware capacity, and
empty budgeted placement slots are masked at the plan level — both the
monolithic and the chunked path inherit all three through the spec.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scheduler import MicroEPScheduler, ScheduleStatics
from ..core.solver_jax import SolverState
from . import dispatch as D
from .experts import ExpertParams, expert_ffn_flat, expert_ffn_flat_chunked
from .router import RouterOut, top_k_gating

__all__ = ["MoEMetrics", "moe_ffn", "MoEFFNSpec"]


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array
    z_loss: jax.Array
    max_load: jax.Array      # scheduled max device load (tokens)
    balance: jax.Array       # max / mean device load; on a heterogeneous
                             # group (device profiles, DESIGN.md §11) the
                             # max is over weight-normalized loads L_g/w_g
    overflow: jax.Array      # rows dropped to residual by capacity clipping
    expert_load: jax.Array   # f32[E] group-wide routed tokens per expert
                             # (feeds the serving replacement manager;
                             # scalar 0 on dense layers)


class MoEFFNSpec(NamedTuple):
    """Static configuration bundle for one MoE layer.

    pipeline_stages — destination chunks of the dispatch/combine pipeline
                      (1 = monolithic; non-divisors of the group size fall
                      back to the largest divisor below).
    dispatch_mode   — 'packed' (int32-scatter + row gathers, default) |
                      'scatter' (legacy dense zero-buffer scatters).
                      Applies to the *monolithic* path only: the pipelined
                      path (pipeline_stages > 1) is packed-gather by
                      construction and ignores this knob.
    chunk_comm      — per-stage collective of the pipelined path:
                      'ppermute' (schedulable overlap) | 'a2a' (portable
                      full-shape reference).
    mem_caps        — f32[G] per-device MemFine token caps for this
                      geometry (DESIGN.md §16), passed to the scheduler
                      so token splits respect the activation-memory
                      budget.  None = memory-oblivious (bit-identical to
                      the pre-MemFine layer).
    """

    statics: D.DispatchStatics
    scheduler: MicroEPScheduler
    top_k: int
    activation: str
    group_axes: tuple
    tp_axis: Optional[str] = None   # intra-expert tensor axis (F sharded)
    kernel_impl: Optional[str] = None
    pipeline_stages: int = 1
    dispatch_mode: str = "packed"
    chunk_comm: str = "ppermute"
    mem_caps: Optional[np.ndarray] = None


def _gather_counts(cnt: jax.Array, group_axes: Sequence[str]) -> jax.Array:
    """int32[E] local counts -> int32[E, G] per-source counts."""
    if not group_axes:
        return cnt[:, None]
    g = jax.lax.all_gather(cnt, tuple(group_axes), tiled=False)  # [G, E]
    return g.T


def moe_ffn(
    spec: MoEFFNSpec,
    x: jax.Array,                  # [T, H] local tokens
    w_router: jax.Array,           # [H, E] (replicated)
    experts: ExpertParams,         # local slots [S, H, F_local]
    state: Optional[SolverState] = None,
    router_out: Optional[RouterOut] = None,  # override (synthetic benches)
    valid: jax.Array | None = None,
):
    t, h = x.shape
    st = spec.statics
    k = spec.top_k

    r = router_out if router_out is not None else top_k_gating(
        x, w_router, k, valid=valid
    )

    # token-replica rows: [T*K]
    ex = r.expert_ids.reshape(-1)
    rows = jnp.repeat(x, k, axis=0)

    cnt = jnp.zeros(st.num_experts + 1, jnp.int32).at[ex].add(1)[: st.num_experts]
    input_eg = _gather_counts(cnt, spec.group_axes)          # [E, G]

    sched = spec.scheduler(input_eg, state,
                           mem_caps=None if spec.mem_caps is None
                           else jnp.asarray(spec.mem_caps, jnp.float32))
    my_index = (
        jax.lax.axis_index(spec.group_axes).astype(jnp.int32)
        if spec.group_axes else jnp.zeros((), jnp.int32)
    )

    n_stages = D.effective_stages(spec.pipeline_stages, st.group_size) \
        if spec.group_axes else 1
    if n_stages > 1:
        # destination-chunked pipelined hot path: chunk c's FFN depends
        # only on stage c's collective, so compute overlaps communication
        plan = D.make_chunked_plan(st, ex, sched.flow, my_index, n_stages)
        flat_chunks = D.dispatch_pipelined(
            st, plan, rows, spec.group_axes, my_index,
            chunk_comm=spec.chunk_comm)
        out_chunks = expert_ffn_flat_chunked(
            flat_chunks, plan.group_start, plan.group_end, experts,
            spec.activation, impl=spec.kernel_impl, bm=st.bm,
        )
        if spec.tp_axis is not None:
            out_chunks = tuple(jax.lax.psum(o, spec.tp_axis)
                               for o in out_chunks)
        out_rows = D.combine_pipelined(
            st, plan, out_chunks, spec.group_axes, my_index,
            chunk_comm=spec.chunk_comm)
    else:
        plan = D.make_plan(st, ex, sched.flow, my_index)
        flat = D.dispatch(st, plan, rows, spec.group_axes,
                          mode=spec.dispatch_mode)
        out_flat = expert_ffn_flat(
            flat, plan.group_start, plan.group_end, experts,
            spec.activation, impl=spec.kernel_impl, bm=st.bm,
        )
        if spec.tp_axis is not None:
            out_flat = jax.lax.psum(out_flat, spec.tp_axis)
        out_rows = D.combine(st, plan, out_flat, spec.group_axes,
                             mode=spec.dispatch_mode)

    out = (out_rows.reshape(t, k, h) * r.gate_w[:, :, None].astype(x.dtype)
           ).sum(axis=1)

    metrics = MoEMetrics(
        aux_loss=r.aux_loss,
        z_loss=r.z_loss,
        max_load=sched.max_load,
        balance=sched.balance,
        overflow=plan.overflow,
        expert_load=input_eg.sum(axis=1).astype(jnp.float32),
    )
    return out, metrics, sched.solver_state
