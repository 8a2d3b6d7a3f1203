"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has its semantics defined HERE; the Pallas
implementations must match these to ~1e-5 (f32) / ~2e-2 (bf16) under
``interpret=True`` across the shape/dtype sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gated_act", "grouped_ffn_ref", "grouped_matmul_ref",
           "wkv6_chunk_ref"]


def gated_act(h_gate, h_up, activation: str):
    if activation == "geglu":
        return jax.nn.gelu(h_gate) * h_up
    if activation == "swiglu":
        return jax.nn.silu(h_gate) * h_up
    if activation == "relu_sq":
        return jnp.square(jax.nn.relu(h_gate)) * h_up
    raise ValueError(activation)


def grouped_ffn_ref(
    x: jax.Array,        # [S, C, H]  slot-grouped tokens (rows >= counts are junk)
    counts: jax.Array,   # int32[S]   valid rows per slot
    w_gate: jax.Array,   # [S, H, F]
    w_up: jax.Array,     # [S, H, F]
    w_down: jax.Array,   # [S, F, H]
    activation: str = "swiglu",
) -> jax.Array:
    """Per-slot gated FFN over ragged groups; invalid rows produce zeros."""
    s, c, h = x.shape
    mask = (jnp.arange(c)[None, :] < counts[:, None])[..., None]  # [S, C, 1]
    xm = jnp.where(mask, x, 0).astype(jnp.float32)
    wg = w_gate.astype(jnp.float32)
    wu = w_up.astype(jnp.float32)
    wd = w_down.astype(jnp.float32)
    hg = jnp.einsum("sch,shf->scf", xm, wg)
    hu = jnp.einsum("sch,shf->scf", xm, wu)
    act = gated_act(hg, hu, activation)
    out = jnp.einsum("scf,sfh->sch", act, wd)
    return jnp.where(mask, out, 0).astype(x.dtype)


def grouped_ffn_flat_ref(
    x: jax.Array,          # [N, H] rows sorted by group, bm-aligned starts
    group_start: jax.Array,  # int32[S]
    group_end: jax.Array,    # int32[S] (start + count)
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    activation: str = "swiglu",
) -> jax.Array:
    """Flat-layout oracle: rows outside [start, end) per group produce zeros.

    Dense evaluation: every group's weights applied to every row, then select
    by row->group membership.  O(N·S·H·F) — fine at test sizes.
    """
    n, h = x.shape
    s = w_gate.shape[0]
    rows = jnp.arange(n)[None, :]
    member = (rows >= group_start[:, None]) & (rows < group_end[:, None])  # [S, N]
    xf = x.astype(jnp.float32)
    hg = jnp.einsum("nh,shf->snf", xf, w_gate.astype(jnp.float32))
    hu = jnp.einsum("nh,shf->snf", xf, w_up.astype(jnp.float32))
    act = gated_act(hg, hu, activation)
    out_s = jnp.einsum("snf,sfh->snh", act, w_down.astype(jnp.float32))
    out = jnp.einsum("sn,snh->nh", member.astype(jnp.float32), out_s)
    return out.astype(x.dtype)


def grouped_matmul_ref(
    x: jax.Array,        # [S, C, H]
    counts: jax.Array,   # int32[S]
    w: jax.Array,        # [S, H, F]
) -> jax.Array:
    """Per-slot plain matmul over ragged groups (zeros on invalid rows)."""
    s, c, h = x.shape
    mask = (jnp.arange(c)[None, :] < counts[:, None])[..., None]
    xm = jnp.where(mask, x, 0).astype(jnp.float32)
    out = jnp.einsum("sch,shf->scf", xm, w.astype(jnp.float32))
    return jnp.where(mask, out, 0).astype(x.dtype)


def wkv6_chunk_ref(
    q: jax.Array,        # [T, Hd]  (single head; callers vmap over heads/batch)
    k: jax.Array,        # [T, Hd]
    v: jax.Array,        # [T, Hd]
    w: jax.Array,        # [T, Hd]  per-step decay in (0, 1) (already exp(-exp(.)))
    u: jax.Array,        # [Hd]     bonus for the current token (RWKV-6 "u")
    state: jax.Array,    # [Hd, Hd] incoming recurrent state S_{t0-1}
):
    """RWKV-6 recurrence oracle, sequential over T.

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = (q_t (S_{t-1} + u ⊙ k_t v_t^T))  — current token contributes via u.
    Returns (o[T, Hd], final_state[Hd, Hd]).
    """
    def step(s, qkvw):
        qt, kt, vt, wt = qkvw
        kv = jnp.outer(kt, vt)
        ot = qt @ (s + u[:, None] * kv)
        s = wt[:, None] * s + kv
        return s, ot

    final, o = jax.lax.scan(step, state.astype(jnp.float32),
                            (q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), w.astype(jnp.float32)))
    return o.astype(q.dtype), final
