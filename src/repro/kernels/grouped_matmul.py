"""Pallas TPU kernel: grouped gated FFN over ragged, flat expert groups.

This is the compute hotspot of the paper's system (§7.4: expert computation
dominates the MoE layer).  On GPU the standard answer is MegaBlocks' grouped
GEMM; the TPU-native adaptation here:

  * rows arrive flat ``[N, H]``, sorted by expert slot, each group starting
    on a bm-aligned row — the dispatcher's native layout (moe/dispatch.py);
  * grid = (N/bm, F/bf): each step computes one (bm × bf) tile of the
    hidden activation h = act(x·Wg) ⊙ (x·Wu) and accumulates h·Wd into a
    VMEM f32 accumulator of shape (bm, H), written back once per row tile;
  * the per-tile group id and the group ends are scalar-prefetched (SMEM),
    so each tile's weight DMAs are addressed before it runs; tiles with no
    valid row skip both matmuls via ``pl.when`` — padded capacity costs
    O(1) control per tile, not O(bm·H·F) FLOPs;
  * operands enter the MXU in their own dtype (bf16 in training and
    serving) with f32 accumulation; only h is cast back for the down dot.

VMEM reckoning, bf16 at bm=128, bf=512 (every block double-buffered):
  x tile bm·H·2B, Wg/Wu/Wd tiles H·bf·2B each, out tile bm·H·2B, plus the
  f32 accumulator bm·H·4B and ~3 f32 (bm, bf) temporaries.  At H=2048
  that is 1 + 12 + 1 + 1 + 0.75 ≈ 16 MiB — just over the 16 MiB default
  scoped-VMEM limit of a v5e core, so the kernel asks for ``_VMEM_LIMIT``
  (64 MiB of the core's 128 MiB).  That covers H up to 6144 (≈ 46 MiB).
  F only sets the number of f-steps, so F=1024 and F=8192 need the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import gated_act

__all__ = ["grouped_ffn_flat_pallas"]

_VMEM_LIMIT = 64 * 2 ** 20


def _ffn_flat_kernel(meta_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref,
                     *, activation: str, bm: int, nf: int):
    """meta_ref holds [gid_per_tile (NT) | group_end (S)]."""
    row_tile = pl.program_id(0)
    f_tile = pl.program_id(1)
    nt = pl.num_programs(0)

    gid = meta_ref[row_tile]
    end = meta_ref[nt + gid]
    row_active = row_tile * bm < end

    @pl.when(f_tile == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(row_active)
    def _compute():
        x = x_ref[...]                              # (bm, H)
        # mask rows beyond the group's end so junk never enters the MXU
        rows = row_tile * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        x = jnp.where(rows < end, x, jnp.zeros_like(x))
        hg = jax.lax.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        hu = jax.lax.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = gated_act(hg, hu, activation).astype(wd_ref.dtype)
        acc_ref[...] += jax.lax.dot(h, wd_ref[0],
                                    preferred_element_type=jnp.float32)

    @pl.when(f_tile == nf - 1)
    def _write():
        out = jnp.where(row_active, acc_ref[...], 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("activation", "bm", "bf", "interpret")
)
def grouped_ffn_flat_pallas(
    x: jax.Array,            # [N, H] rows sorted by group, starts bm-aligned
    tile_gid: jax.Array,     # int32[N // bm] group id per row tile
    group_end: jax.Array,    # int32[S] last valid row (exclusive) per group
    w_gate: jax.Array,       # [S, H, F]
    w_up: jax.Array,         # [S, H, F]
    w_down: jax.Array,       # [S, F, H]
    activation: str = "swiglu",
    bm: int = 128,
    bf: int = 512,
    interpret: bool = False,
) -> jax.Array:
    n, h = x.shape
    s, _, f = w_gate.shape
    assert n % bm == 0 and f % bf == 0, (n, bm, f, bf)
    nf = f // bf
    meta = jnp.concatenate(
        [tile_gid.astype(jnp.int32), group_end.astype(jnp.int32)]
    )
    kernel = functools.partial(
        _ffn_flat_kernel, activation=activation, bm=bm, nf=nf
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # meta
            grid=(n // bm, nf),
            in_specs=[
                pl.BlockSpec((bm, h), lambda i, j, meta: (i, 0)),
                pl.BlockSpec((1, h, bf), lambda i, j, meta: (meta[i], 0, j)),
                pl.BlockSpec((1, h, bf), lambda i, j, meta: (meta[i], 0, j)),
                pl.BlockSpec((1, bf, h), lambda i, j, meta: (meta[i], j, 0)),
            ],
            out_specs=pl.BlockSpec((bm, h), lambda i, j, meta: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bm, h), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_ffn",
    )(meta, x, w_gate, w_up, w_down)
