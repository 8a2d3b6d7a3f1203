"""Jit'd public wrappers for the Pallas kernels.

``impl`` picks the implementation: 'pallas' (the kernel compiled natively),
'interpret' (the same kernel in Pallas interpret mode — CPU tests) or
'ref' (the jnp oracle of kernels/ref.py).  ``impl=None`` resolves once, in
:func:`default_impl`, from the backend: 'pallas' on a TPU, so no device
path ever falls back to the oracle without saying so.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .grouped_matmul import grouped_ffn_flat_pallas
from .wkv6_chunk import wkv6_pallas

__all__ = ["grouped_ffn", "grouped_ffn_flat", "grouped_ffn_flat_chunked",
           "wkv6", "default_impl"]


def default_impl() -> str:
    """'pallas' on TPU, 'ref' elsewhere (interpret mode reserved for tests)."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_ffn_weights(w_gate, w_up, w_down, bf: int):
    """Pad the FFN weights' f dimension to a bf multiple — hoisted so
    pipelined call sites pad once, not once per chunk."""
    return (_pad_axis(w_gate, 2, bf), _pad_axis(w_up, 2, bf),
            _pad_axis(w_down, 1, bf))


def grouped_ffn(
    x: jax.Array,
    counts: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    activation: str = "swiglu",
    impl: str | None = None,
    bm: int = 128,
    bf: int = 512,
) -> jax.Array:
    """Ragged per-slot gated FFN.  x: [S, C, H] -> [S, C, H].

    The Pallas path runs the flat kernel on the slots laid end to end:
    slot s starts at row s·C (C padded to a bm multiple) and ends at
    s·C + counts[s]."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.grouped_ffn_ref(x, counts, w_gate, w_up, w_down, activation)
    s, c0, h = x.shape
    xp = _pad_axis(x, 1, bm)
    c = xp.shape[1]
    start = jnp.arange(s, dtype=jnp.int32) * c
    out = grouped_ffn_flat(xp.reshape(s * c, h), start,
                           start + counts.astype(jnp.int32),
                           w_gate, w_up, w_down, activation=activation,
                           impl=impl, bm=bm, bf=bf)
    return out.reshape(s, c, h)[:, :c0, :]


def grouped_ffn_flat(
    x: jax.Array,            # [N, H], N a multiple of bm, sorted by group
    group_start: jax.Array,  # int32[S], bm-aligned
    group_end: jax.Array,    # int32[S]
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    activation: str = "swiglu",
    impl: str | None = None,
    bm: int = 128,
    bf: int = 512,
) -> jax.Array:
    """Flat MegaBlocks-style ragged FFN (dispatcher's native layout)."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.grouped_ffn_flat_ref(
            x, group_start, group_end, w_gate, w_up, w_down, activation
        )
    wgp, wup, wdp = _pad_ffn_weights(w_gate, w_up, w_down, bf)
    return _flat_padded(x, group_start, group_end, wgp, wup, wdp,
                        activation, bm, bf, impl == "interpret")


def _row_groups(n: int, group_start, group_end):
    """Ragged-dot group sizes covering all N rows (group g owns rows from
    its start to the next group's start, padding included) and the bool[N]
    mask of rows inside some group's [start, end) — the kernel's mask."""
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              group_start[1:].astype(jnp.int32),
                              jnp.full((1,), n, jnp.int32)])
    sizes = jnp.diff(bounds)
    rows = jnp.arange(n, dtype=jnp.int32)
    gid = jnp.clip(jnp.searchsorted(group_start, rows, side="right") - 1,
                   0, group_start.shape[0] - 1)
    valid = (rows >= group_start[gid]) & (rows < group_end[gid])
    return sizes, valid


def _ragged_ffn(x, sizes, valid, wg, wu, wd, activation):
    """The flat grouped FFN as three ``lax.ragged_dot``s: O(N·H·F)."""
    xm = jnp.where(valid[:, None], x, jnp.zeros_like(x))
    hg = jax.lax.ragged_dot(xm, wg, sizes, preferred_element_type=jnp.float32)
    hu = jax.lax.ragged_dot(xm, wu, sizes, preferred_element_type=jnp.float32)
    h = ref.gated_act(hg, hu, activation).astype(wd.dtype)
    out = jax.lax.ragged_dot(h, wd, sizes, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flat_padded(x, group_start, group_end, wgp, wup, wdp,
                 activation, bm, bf, interpret):
    """Pallas flat call on already-padded weights (chunk-range inner).

    Forward: the Pallas kernel.  Backward: the vjp of :func:`_ragged_ffn`
    (forward recomputed with ``lax.ragged_dot``), ragged like the kernel —
    O(rows·H·F), never the dense oracle.  A Pallas backward is later work.
    """
    n = x.shape[0]
    s = wgp.shape[0]
    # tile group ids from the (bm-aligned) starts
    tiles = jnp.arange(n // bm, dtype=jnp.int32) * bm
    tile_gid = jnp.clip(
        jnp.searchsorted(group_start, tiles, side="right") - 1, 0, s - 1
    ).astype(jnp.int32)
    return grouped_ffn_flat_pallas(
        x, tile_gid, group_end, wgp, wup, wdp,
        activation=activation, bm=bm, bf=bf, interpret=interpret,
    )


def _flat_padded_fwd(x, group_start, group_end, wgp, wup, wdp,
                     activation, bm, bf, interpret):
    out = _flat_padded(x, group_start, group_end, wgp, wup, wdp,
                       activation, bm, bf, interpret)
    return out, (x, group_start, group_end, wgp, wup, wdp)


def _flat_padded_bwd(activation, bm, bf, interpret, res, g):
    x, group_start, group_end, wgp, wup, wdp = res
    with jax.named_scope("grouped_ffn_bwd"):
        sizes, valid = _row_groups(x.shape[0], group_start, group_end)
        _, vjp = jax.vjp(
            lambda x_, wg, wu, wd: _ragged_ffn(x_, sizes, valid, wg, wu, wd,
                                               activation),
            x, wgp, wup, wdp)
        dx, dwg, dwu, dwd = vjp(g)
    return dx, None, None, dwg, dwu, dwd


_flat_padded.defvjp(_flat_padded_fwd, _flat_padded_bwd)


def grouped_ffn_flat_chunked(
    x_chunks,                # sequence of [N_c, H] chunk sub-buffers
    group_starts: jax.Array,  # int32[n, S] chunk-relative, bm-aligned
    group_ends: jax.Array,    # int32[n, S]
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    activation: str = "swiglu",
    impl: str | None = None,
    bm: int = 128,
    bf: int = 512,
):
    """Chunk-range entry point of the flat kernel (pipelined hot path).

    Runs :func:`grouped_ffn_flat` semantics independently over each chunk
    sub-buffer with that chunk's own group ranges, padding the weights
    once for all chunks.  Each returned chunk depends only on its input
    chunk — the property the dispatch/compute/combine overlap relies on
    (DESIGN.md §2).  Returns a tuple of [N_c, H] outputs."""
    impl = impl or default_impl()
    if impl == "ref":
        return tuple(
            ref.grouped_ffn_flat_ref(
                xc, group_starts[c], group_ends[c],
                w_gate, w_up, w_down, activation)
            for c, xc in enumerate(x_chunks))
    wgp, wup, wdp = _pad_ffn_weights(w_gate, w_up, w_down, bf)
    return tuple(
        _flat_padded(xc, group_starts[c], group_ends[c], wgp, wup, wdp,
                     activation, bm, bf, impl == "interpret")
        for c, xc in enumerate(x_chunks))


def wkv6(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lw: jax.Array,
    u: jax.Array,
    chunk: int = 128,
    impl: str | None = None,
) -> jax.Array:
    """RWKV-6 recurrence over [BH, T, D] (zero initial state)."""
    impl = impl or default_impl()
    if impl == "ref":
        d = q.shape[-1]
        o, _ = jax.vmap(
            lambda q_, k_, v_, lw_, u_: ref.wkv6_chunk_ref(
                q_, k_, v_, jnp.exp(lw_), u_, jnp.zeros((d, d), jnp.float32)
            )
        )(q, k, v, lw, u)
        return o
    t = q.shape[1]
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        q, k, v = (_pad_axis(a, 1, chunk) for a in (q, k, v))
        lw = _pad_axis(lw, 1, chunk)
    out = wkv6_pallas(q, k, v, lw, u, chunk=chunk, interpret=(impl == "interpret"))
    return out[:, :t, :]
