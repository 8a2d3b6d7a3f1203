"""Expert FFN parameters and slot-grouped compute.

Working-layout storage (paper Fig. 4): each device owns ``S`` expert replica
slots; the slot->expert binding comes from the placement table.  Weights live
as [S, ...] arrays sharded over the mesh ((data, model) -> device), i.e. the
global arrays are [D, M, S, ...] with spec P('data', 'model').

``expert_ffn_flat`` consumes the dispatcher's flat slot-sorted buffer and
calls the Pallas grouped kernel (or its oracle on CPU).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops

__all__ = ["ExpertParams", "init_expert_slots", "expert_ffn_flat",
           "expert_ffn_flat_chunked", "init_canonical_experts"]


class ExpertParams(NamedTuple):
    w_gate: jax.Array   # [S, H, F]
    w_up: jax.Array     # [S, H, F]
    w_down: jax.Array   # [S, F, H]


def init_canonical_experts(
    key: jax.Array, num_experts: int, h: int, f: int, dtype=jnp.float32
) -> ExpertParams:
    """Canonical layout [E, ...]: expert e's parameters at index e."""
    kg, ku, kd = jax.random.split(key, 3)
    sg = (2.0 / (h + f)) ** 0.5
    return ExpertParams(
        w_gate=(jax.random.normal(kg, (num_experts, h, f)) * sg).astype(dtype),
        w_up=(jax.random.normal(ku, (num_experts, h, f)) * sg).astype(dtype),
        w_down=(jax.random.normal(kd, (num_experts, f, h)) * sg).astype(dtype),
    )


def init_expert_slots(canonical: ExpertParams, placement) -> ExpertParams:
    """Materialize the working layout [D, M, S, ...] from canonical [E, ...]
    on the host (initialization path; runtime migration uses moe/sync.py)."""
    table = placement.table  # [D, M, S]
    return ExpertParams(
        w_gate=canonical.w_gate[table],
        w_up=canonical.w_up[table],
        w_down=canonical.w_down[table],
    )


def expert_ffn_flat(
    flat: jax.Array,          # [N, H]
    group_start: jax.Array,   # int32[S]
    group_end: jax.Array,     # int32[S]
    params: ExpertParams,     # local slots [S, H, F] etc.
    activation: str,
    impl: str | None = None,
    bm: int = 128,
) -> jax.Array:
    return ops.grouped_ffn_flat(
        flat, group_start, group_end,
        params.w_gate, params.w_up, params.w_down,
        activation=activation, impl=impl, bm=bm,
    )


def expert_ffn_flat_chunked(
    flat_chunks,              # sequence of [N_c, H] chunk sub-buffers
    group_starts: jax.Array,  # int32[n, S] chunk-relative
    group_ends: jax.Array,    # int32[n, S]
    params: ExpertParams,
    activation: str,
    impl: str | None = None,
    bm: int = 128,
):
    """Pipelined variant: one grouped-FFN call per dispatch chunk, weights
    padded once (kernels.ops.grouped_ffn_flat_chunked)."""
    return ops.grouped_ffn_flat_chunked(
        flat_chunks, group_starts, group_ends,
        params.w_gate, params.w_up, params.w_down,
        activation=activation, impl=impl, bm=bm,
    )
