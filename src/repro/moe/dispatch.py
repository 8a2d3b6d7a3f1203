"""Capacity-buffered token dispatch/combine for (Micro)EP — paper §4-5.

All functions here run *per device inside shard_map*.  The flow tensor
``F[E, G, R]`` produced by the scheduler is identical on every device
(deterministic distributed scheduling, §5.3), so sender-side offsets and
receiver-side layouts are derived independently yet consistently, with pure
cumsums — no coordination round-trip beyond the tiny counts all-gather.

Data layout (static shapes; the TPU/XLA adaptation of the paper's ragged
NCCL all-to-all — see DESIGN.md §2):

  send buffer  [G * cap, H]    chunk d = rows destined to device d (remote)
  recv buffer  [G * cap, H]    chunk g = rows arriving from device g (a2a)
  flat buffer  [N_flat,  H]    rows sorted by local expert slot, bm-aligned
                               group starts (grouped-FFN layout)

  N_flat = (G-1)·cap + C_in + S·bm, rounded up to bm: the rows a device can
  receive — at most ``cap`` from each of the G-1 other sources (senders
  clip there, and the receiver's layout clips its segments the same way)
  plus its own C_in local rows, which never enter the exchange — and the
  alignment slack.  The device's own chunk of the recv buffer is never
  used; a group of one device (G = 1) has no remote path at all, so
  ``dispatch``/``combine`` build no send or recv buffer and run no
  all-to-all there.

**Locality fast path** (paper §5.2 locality-aware routing): rows whose
scheduled replica lives on their own device never enter the all-to-all —
they are scattered straight into the flat buffer.  This is both the
bandwidth saving the paper measures (Fig. 11) and what keeps the static
per-chunk capacity small: only *remote* flow crosses the network, and the
LP + Algorithm 1 keep remote flow spread across destinations.

Within the chunk (src g → dst d), rows are segment-ordered by the
*destination's local slot index*; segment sizes are entries of F, so both
sides compute identical layouts.  Within a segment (one expert), the sender
orders its expert-e tokens by local rank and splits them across replicas in
the canonical order «local replica first, then ascending replica index»
(Algorithm 1's sequencing).

**Buffer movement** comes in two modes (DESIGN.md §2):

* ``"scatter"`` (legacy) — rows are scattered into zero-initialized send /
  flat buffers with dense ``.at[].set``: every MoE layer materializes and
  rewrites O(G·cap + N_flat) rows of zeros.
* ``"packed"`` (default) — the scatter moves only *int32 indices*: the
  inverse maps (buffer position → source row) are built with an integer
  scatter and the H-wide rows move through pure gathers with a trailing
  zero row as the trash target.  No full-width zero buffer is ever
  materialized; bench_hotpath measures the gap.

**Destination-chunked pipelining** (`make_chunked_plan` /
`dispatch_pipelined` / `combine_pipelined`): the all-to-all is split into
``pipeline_stages`` chunks of destination devices — stage c carries the
relative device offsets ``[c·G/n, (c+1)·G/n)`` — and the flat buffer is
laid out chunk-major so chunk c's grouped-FFN call depends only on stage
c's collective.  Chunk i's compute therefore overlaps chunk i+1's
collective in the dataflow graph.  Stage exchanges are expressed either as
per-offset ``lax.ppermute`` (the variant XLA's latency-hiding scheduler
can overlap; each permute moves one (src, dst) cap-chunk) or as
full-shape ``lax.all_to_all`` slices carrying only the stage's destination
chunks (the portable reference form).  Every variant is bit-identical to
the monolithic path: rows keep their (replica, segment) assignment, the
grouped FFN is row-wise, and only buffer *positions* change.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scheduler import ScheduleStatics

__all__ = ["DispatchStatics", "DispatchPlan", "ChunkedDispatchPlan",
           "build_statics", "make_plan", "make_chunked_plan",
           "dispatch", "combine", "dispatch_pipelined", "combine_pipelined",
           "flat_buffer_size", "effective_stages", "chunk_caps"]


@dataclasses.dataclass(frozen=True)
class DispatchStatics:
    """Trace-time constants derived from placement (host numpy)."""

    sched: ScheduleStatics
    # [G, S]: expert hosted at (device, slot) and its replica row in dev[E,R]
    exp_of_dev_slot: np.ndarray
    rep_of_dev_slot: np.ndarray
    tokens_per_device: int
    top_k: int
    cap: int          # rows per (src, dst) remote chunk
    bm: int           # row-tile alignment of the flat buffer
    num_slots: int

    @property
    def group_size(self) -> int:
        return self.sched.num_devices

    @property
    def num_experts(self) -> int:
        return self.sched.num_experts

    @property
    def c_in(self) -> int:
        return self.tokens_per_device * self.top_k


def build_statics(
    sched: ScheduleStatics, tokens_per_device: int, top_k: int,
    capacity_factor: float = 2.0, bm: int = 128,
) -> DispatchStatics:
    """Derive the trace-time dispatch constants from the schedule statics.

    Empty placement slots (budgeted placements, table entry -1) get
    ``exp_of_dev_slot = -1`` and are masked out of every segment layout —
    no row is ever scheduled toward them, so their recv segments are
    always zero.

    **Heterogeneous capacity** (DESIGN.md §11): SPMD requires one static
    ``cap`` on every device, but under weighted scheduling the heaviest
    device receives  w_max / w̄  times its uniform share.  With group
    weights present the per-(src, dst) chunk capacity therefore scales to
    cover the heaviest destination, ``cap = ceil(C_in · f · w_max / Σw)``
    — which reduces bit-exactly to the uniform ``ceil(C_in · f / G)``
    when weights are absent (uniform profiles canonicalize to None)."""
    p = sched.placement
    g, s = p.num_devices, p.slots
    flat = p.flat()
    exp_of = flat.astype(np.int32)
    rep_of = np.zeros((g, s), np.int32)
    for gi in range(g):
        for si in range(s):
            e = int(flat[gi, si])
            if e < 0:
                continue                      # empty (budgeted) slot
            rep_of[gi, si] = int(np.nonzero(sched.dev[e] == gi)[0][0])
    c_in = tokens_per_device * top_k
    if sched.weights is None:
        cap = int(np.ceil(c_in * capacity_factor / max(g, 1)))
    else:
        w = np.asarray(sched.weights, np.float64)
        cap = int(np.ceil(c_in * capacity_factor * float(w.max())
                          / max(float(w.sum()), 1e-30)))
    cap = max(cap, 8)
    return DispatchStatics(
        sched=sched, exp_of_dev_slot=exp_of, rep_of_dev_slot=rep_of,
        tokens_per_device=tokens_per_device, top_k=top_k,
        cap=cap, bm=bm, num_slots=s,
    )


def flat_buffer_size(st: DispatchStatics) -> int:
    """Rows of the slot-sorted flat buffer: up to ``cap`` rows from each of
    the G-1 other devices + the C_in own local rows + per-group bm
    alignment slack, rounded up to a bm multiple.  Equals
    ``chunk_caps(st, 1)[0]``."""
    n = (st.group_size - 1) * st.cap + st.c_in + st.num_slots * st.bm
    return int(np.ceil(n / st.bm) * st.bm)


def effective_stages(pipeline_stages: int, group_size: int) -> int:
    """Largest divisor of ``group_size`` that is <= ``pipeline_stages``.

    Chunks are relative destination-device offsets, so the stage count must
    divide the group size; non-divisors (and stage counts beyond the group
    size) fall back deterministically rather than erroring — the CPU smoke
    geometries (G=1, 2) keep working with any configured stage count."""
    n = max(1, min(int(pipeline_stages), group_size))
    while group_size % n:
        n -= 1
    return n


def chunk_caps(st: DispatchStatics, n_stages: int) -> tuple:
    """Static per-chunk flat sub-buffer sizes (rows, bm multiples).

    Chunk 0 carries the local fast-path rows (offset 0, up to C_in of them
    — no capacity clipping applies locally) plus m-1 remote cap-chunks;
    chunks 1..n-1 carry m remote cap-chunks each.  Every chunk pays up to
    S·bm alignment slack for its own bm-aligned group starts, so the
    pipelined buffer totals  (G-1)·cap + C_in + n·S·bm  rows before
    rounding — (n-1)·S·bm more than the monolithic layout (DESIGN.md §2)."""
    m = st.group_size // n_stages
    bm = st.bm

    def up(x):
        return int(np.ceil(x / bm) * bm)

    first = up((m - 1) * st.cap + st.c_in + st.num_slots * bm)
    rest = up(m * st.cap + st.num_slots * bm)
    return (first,) + (rest,) * (n_stages - 1)


class DispatchPlan(NamedTuple):
    """Per-device gather/scatter indices for one micro-batch."""

    send_pos: jax.Array     # int32[C_in] remote rows: send-buffer pos (trash = G*cap)
    local_pos: jax.Array    # int32[C_in] local rows: flat-buffer pos (trash = N_flat)
    flat_pos: jax.Array     # int32[G*cap] recv row -> flat row (trash = N_flat)
    group_start: jax.Array  # int32[S] bm-aligned starts in the flat buffer
    group_end: jax.Array    # int32[S] start + received rows per slot
    overflow: jax.Array     # int32[] token-replicas dropped to residual
    valid: jax.Array        # bool[C_in] row actually dispatched
    is_local: jax.Array     # bool[C_in] row took the local fast path


def _expert_ranks(ex: jax.Array, num_experts: int):
    """Per-row rank among rows of the same expert."""
    c_in = ex.shape[0]
    order = jnp.argsort(ex, stable=True)
    sorted_ex = ex[order]
    counts = jnp.zeros(num_experts + 1, jnp.int32).at[ex].add(1)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(c_in, dtype=jnp.int32) - starts[sorted_ex]
    rank = jnp.zeros(c_in, jnp.int32).at[order].set(rank_sorted)
    return rank


class _SenderLayout(NamedTuple):
    """Sender-side row assignment shared by the monolithic and chunked
    plans: which (device, slot) each local row goes to and where inside the
    (src, dst) cap-chunk it sits.  Identical for every pipelining layout —
    pipelining only re-homes cap-chunks, never rows within them."""

    dst_dev: jax.Array      # int32[C_in]
    dst_slot: jax.Array     # int32[C_in]
    seg_off_row: jax.Array  # int32[C_in] offset inside the slot segment
    chunk_off: jax.Array    # int32[C_in] offset inside the (src, dst) chunk
    row_local: jax.Array    # bool[C_in]
    remote_ok: jax.Array    # bool[C_in]
    overflowed: jax.Array   # bool[C_in]
    routed: jax.Array       # bool[C_in]
    send_pos: jax.Array     # int32[C_in] destination-major send buffer pos


def _sender_layout(
    st: DispatchStatics, ex: jax.Array, flow: jax.Array, my_index: jax.Array,
) -> _SenderLayout:
    e_n, g_n, r_n = flow.shape
    cap = st.cap
    dev = jnp.asarray(st.sched.dev, jnp.int32)          # [E, R]
    slot = jnp.asarray(st.sched.slot, jnp.int32)        # [E, R]
    exp_of = jnp.asarray(st.exp_of_dev_slot, jnp.int32)  # [G, S]
    rep_of = jnp.asarray(st.rep_of_dev_slot, jnp.int32)  # [G, S]

    my_flow = flow[:, my_index, :]                       # [E, R] my sends
    valid_rep = dev >= 0

    # ---- sender: replica choice per local row --------------------------
    # canonical per-(expert, src) replica order: local replica first, then
    # ascending replica index (Algorithm 1's sequencing).
    is_local_rep = (dev == my_index) & valid_rep
    order_key = jnp.where(is_local_rep, -1, jnp.arange(r_n)[None, :])
    order_key = jnp.where(valid_rep, order_key, r_n + 1)
    rep_order = jnp.argsort(order_key, axis=1)           # [E, R]
    flow_sorted = jnp.take_along_axis(my_flow, rep_order, axis=1)
    cum_sorted = jnp.cumsum(flow_sorted, axis=1)         # [E, R]

    rank = _expert_ranks(ex, e_n)
    cum_row = cum_sorted[jnp.minimum(ex, e_n - 1)]        # [C_in, R]
    pos_in_order = jnp.sum(rank[:, None] >= cum_row, axis=1)
    pos_clamped = jnp.minimum(pos_in_order, r_n - 1)
    rep_row = jnp.take_along_axis(
        rep_order[jnp.minimum(ex, e_n - 1)], pos_clamped[:, None], axis=1)[:, 0]
    routed = (pos_in_order < r_n) & (ex < e_n)
    seg_off_row = rank - jnp.where(
        pos_clamped > 0,
        jnp.take_along_axis(cum_row, (pos_clamped - 1)[:, None], axis=1)[:, 0],
        0,
    )
    dst_dev = dev[jnp.minimum(ex, e_n - 1), rep_row]      # [C_in]
    dst_slot = slot[jnp.minimum(ex, e_n - 1), rep_row]
    row_local = routed & (dst_dev == my_index)

    # ---- chunk layouts (sender & receiver compute these identically) ----
    # send_seg[d, s] = rows I send into segment (dst d, slot s); empty
    # (budgeted) slots carry exp_of = -1 and contribute zero-size segments
    send_seg = jnp.where(exp_of >= 0,
                         flow[jnp.maximum(exp_of, 0), my_index, rep_of], 0)
    send_seg_start = jnp.cumsum(send_seg, axis=1) - send_seg
    chunk_off = send_seg_start[dst_dev, dst_slot] + seg_off_row
    overflowed = ~row_local & (chunk_off >= cap)
    remote_ok = routed & ~row_local & ~overflowed
    send_pos = jnp.where(remote_ok, dst_dev * cap + chunk_off, g_n * cap)
    return _SenderLayout(
        dst_dev=dst_dev, dst_slot=dst_slot, seg_off_row=seg_off_row,
        chunk_off=chunk_off, row_local=row_local, remote_ok=remote_ok,
        overflowed=overflowed, routed=routed,
        send_pos=send_pos.astype(jnp.int32))


def _recv_segments(st: DispatchStatics, flow: jax.Array,
                   my_index: jax.Array) -> jax.Array:
    """int32[G, S] rows arriving from each source device into each of my
    slots: recv_seg[g, s] = flow[exp_of[me, s], g, rep_of[me, s]].  The
    (src, dst) within-chunk layout both plans derive from this is the
    contract the sender's `_sender_layout` fills against.  Empty
    (budgeted) slots have exp_of = -1 and receive nothing.

    A remote source's segments are clipped to its ``cap`` window, as its
    sender clips (rows at chunk offset >= cap overflow to the residual):
    clipping leaves every segment start below ``cap`` where it was, and
    bounds the rows a device receives by (G-1)·cap + C_in.  The own
    source's row is the local fast path and is never clipped."""
    exp_of = jnp.asarray(st.exp_of_dev_slot, jnp.int32)[my_index]   # [S]
    rep_of = jnp.asarray(st.rep_of_dev_slot, jnp.int32)[my_index]
    seg = flow[jnp.maximum(exp_of, 0), :, rep_of]                   # [S, G]
    seg = jnp.where(exp_of[None, :] >= 0, seg.T, 0)                 # [G, S]
    end = jnp.cumsum(seg, axis=1)
    clipped = jnp.minimum(end, st.cap) - jnp.minimum(end - seg, st.cap)
    src = jnp.arange(seg.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(src == my_index, seg, clipped)


def _chunk_row_slots(seg_start: jax.Array, seg: jax.Array, cap: int):
    """Map every row of a [*, cap] chunk to its slot segment.

    seg_start/seg: int32[*, S] per-chunk segment starts/sizes.  Returns
    (slot_of, off_in_seg), both int32[*, cap]: the slot whose segment
    covers each in-chunk position (slot = #segment ends <= position,
    clamped) and the offset within that segment.  Shared by the monolithic
    and chunked receiver layouts so the two can never diverge."""
    s_n = seg.shape[-1]
    c_ids = jnp.arange(cap, dtype=jnp.int32)[None, :]
    seg_edges = seg_start + seg                               # [*, S] ends
    slot_of = jnp.sum(c_ids[:, :, None] >= seg_edges[:, None, :], axis=-1)
    slot_of = jnp.minimum(slot_of, s_n - 1)                   # [*, cap]
    off_in_seg = c_ids - jnp.take_along_axis(seg_start, slot_of, axis=1)
    return slot_of, off_in_seg


def make_plan(
    st: DispatchStatics,
    ex: jax.Array,            # int32[C_in] expert id per local row (E = pad)
    flow: jax.Array,          # int32[E, G, R] the schedule's flow tensor
    my_index: jax.Array,      # int32[] flat device index in the group
) -> DispatchPlan:
    e_n, g_n, r_n = flow.shape
    s_n, cap, bm = st.num_slots, st.cap, st.bm
    exp_of = jnp.asarray(st.exp_of_dev_slot, jnp.int32)  # [G, S]
    rep_of = jnp.asarray(st.rep_of_dev_slot, jnp.int32)  # [G, S]
    n_flat = flat_buffer_size(st)

    snd = _sender_layout(st, ex, flow, my_index)
    dst_slot, seg_off_row = snd.dst_slot, snd.seg_off_row
    row_local, remote_ok = snd.row_local, snd.remote_ok
    routed, overflowed, send_pos = snd.routed, snd.overflowed, snd.send_pos

    # ---- receiver layout: recv/local rows -> flat slot-sorted buffer ----
    # recv_seg[g, s] = rows from src g into my slot s
    #                = flow[exp_of[me, s], g, rep_of[me, s]]
    recv_seg = _recv_segments(st, flow, my_index)             # [G, S]
    recv_seg_start = jnp.cumsum(recv_seg, axis=1) - recv_seg  # within chunk
    slot_counts = recv_seg.sum(axis=0)                        # [S]
    group_sizes_pad = ((slot_counts + bm - 1) // bm) * bm
    group_start = jnp.cumsum(group_sizes_pad) - group_sizes_pad
    group_end = group_start + slot_counts
    inter_src = jnp.cumsum(recv_seg, axis=0) - recv_seg       # [G, S]

    c_ids = jnp.arange(cap, dtype=jnp.int32)[None, :]         # [1, cap]
    slot_of, off_in_seg = _chunk_row_slots(recv_seg_start, recv_seg, cap)
    src_ids = jnp.arange(g_n, dtype=jnp.int32)[:, None]
    in_use = (c_ids < recv_seg.sum(axis=1)[:, None]) & (src_ids != my_index)
    flat_row = (
        group_start[slot_of]
        + jnp.take_along_axis(inter_src, slot_of, axis=1)
        + off_in_seg
    )
    flat_pos = jnp.where(in_use & (flat_row < n_flat), flat_row, n_flat)
    flat_pos = flat_pos.reshape(-1)

    # local fast-path rows: same formula with src = me, c = chunk_off
    loc_flat = (
        group_start[dst_slot]
        + inter_src[my_index, dst_slot]
        + seg_off_row
    )
    loc_ok = row_local & (loc_flat < n_flat)
    local_pos = jnp.where(loc_ok, loc_flat, n_flat)

    overflow = jnp.sum(overflowed & routed) + jnp.sum(row_local & ~loc_ok)
    return DispatchPlan(
        send_pos=send_pos.astype(jnp.int32),
        local_pos=local_pos.astype(jnp.int32),
        flat_pos=flat_pos.astype(jnp.int32),
        group_start=group_start.astype(jnp.int32),
        group_end=group_end.astype(jnp.int32),
        overflow=overflow.astype(jnp.int32),
        valid=(remote_ok | loc_ok),
        is_local=loc_ok,
    )


def _inverse_index(pos: jax.Array, size: int, fill: int) -> jax.Array:
    """int32[size] inverse of a partial position map: out[pos[i]] = i,
    ``fill`` where no source row lands.  ``pos`` uses ``size`` as trash."""
    src = jnp.full((size + 1,), fill, jnp.int32)
    return src.at[pos].set(jnp.arange(pos.shape[0], dtype=jnp.int32))[:size]


def _gather_rows(buf: jax.Array, idx: jax.Array) -> jax.Array:
    """buf[idx] with ``idx == buf.shape[0]`` selecting a zero row, without
    materializing a padded copy of ``buf``."""
    n = buf.shape[0]
    ok = idx < n
    out = buf[jnp.minimum(idx, n - 1)]
    return jnp.where(ok[..., None], out, 0)


def dispatch(
    st: DispatchStatics,
    plan: DispatchPlan,
    rows: jax.Array,                 # [C_in, H] token-replica hidden states
    group_axes: Sequence[str],
    mode: str = "packed",
) -> jax.Array:
    """Send rows to their replicas; returns the flat slot-sorted buffer.

    ``mode="packed"`` builds the buffers with int32-scatter + row gathers
    (no zero-buffer materialization); ``mode="scatter"`` is the legacy
    dense ``.at[].set`` path kept for the bench comparison.  Both are
    bit-identical.  A group of one device (or no ``group_axes``) has only
    local rows: no send buffer, no all-to-all."""
    g_n, cap, h = st.group_size, st.cap, rows.shape[-1]
    c_in = rows.shape[0]
    n_flat = flat_buffer_size(st)
    remote = bool(group_axes) and g_n > 1
    if mode == "scatter":
        flat = jnp.zeros((n_flat + 1, h), rows.dtype)
        # local fast path: no collective
        flat = flat.at[plan.local_pos].set(
            jnp.where(plan.is_local[:, None], rows, 0))
        if remote:
            send = jnp.zeros((g_n * cap + 1, h), rows.dtype)
            send = send.at[plan.send_pos].set(rows)[: g_n * cap]
            recv = jax.lax.all_to_all(
                send.reshape(g_n, cap, h), tuple(group_axes),
                split_axis=0, concat_axis=0, tiled=False,
            ).reshape(g_n * cap, h)
            flat = flat.at[plan.flat_pos].add(recv)
        return flat[:n_flat]
    if mode != "packed":
        raise ValueError(
            f"dispatch mode={mode!r} is not a registered option; "
            f"choose one of: packed, scatter")
    # packed: the only scatters move int32 indices; rows move via gathers.
    # flat sources: [0, C_in) = local rows, [C_in, C_in + G*cap) = recv
    # rows, C_in + G*cap = the zero row.
    if remote:
        send_src = _inverse_index(plan.send_pos, g_n * cap, c_in)
        send = _gather_rows(rows, send_src)               # [G*cap, H]
        recv = jax.lax.all_to_all(
            send.reshape(g_n, cap, h), tuple(group_axes),
            split_axis=0, concat_axis=0, tiled=False,
        ).reshape(g_n * cap, h)
        zero_idx = c_in + g_n * cap
        flat_src = jnp.full((n_flat + 1,), zero_idx, jnp.int32)
        flat_src = flat_src.at[plan.flat_pos].set(
            c_in + jnp.arange(g_n * cap, dtype=jnp.int32))
        flat_src = flat_src.at[plan.local_pos].set(
            jnp.arange(c_in, dtype=jnp.int32))[:n_flat]
        both = jnp.concatenate([rows, recv])
        return _gather_rows(both, flat_src)
    flat_src = _inverse_index(plan.local_pos, n_flat, c_in)
    return _gather_rows(rows, flat_src)


def combine(
    st: DispatchStatics,
    plan: DispatchPlan,
    flat_out: jax.Array,             # [N_flat, H] expert outputs
    group_axes: Sequence[str],
    mode: str = "packed",
) -> jax.Array:
    """Inverse of dispatch: returns per-local-row outputs [C_in, H]."""
    g_n, cap, h = st.group_size, st.cap, flat_out.shape[-1]
    remote = bool(group_axes) and g_n > 1
    if mode == "scatter":
        pad = jnp.zeros((1, h), flat_out.dtype)
        flat_padded = jnp.concatenate([flat_out, pad])
        out_local = flat_padded[plan.local_pos]               # [C_in, H]
        if remote:
            recv = flat_padded[plan.flat_pos]                 # [G*cap, H]
            send = jax.lax.all_to_all(
                recv.reshape(g_n, cap, h), tuple(group_axes),
                split_axis=0, concat_axis=0, tiled=False,
            ).reshape(g_n * cap, h)
            send = jnp.concatenate([send, pad])
            out_remote = send[plan.send_pos]
        else:
            out_remote = jnp.zeros_like(out_local)
    elif mode == "packed":
        out_local = _gather_rows(flat_out, plan.local_pos)    # [C_in, H]
        if remote:
            recv = _gather_rows(flat_out, plan.flat_pos)      # [G*cap, H]
            send = jax.lax.all_to_all(
                recv.reshape(g_n, cap, h), tuple(group_axes),
                split_axis=0, concat_axis=0, tiled=False,
            ).reshape(g_n * cap, h)
            out_remote = _gather_rows(send, plan.send_pos)
        else:
            out_remote = jnp.zeros_like(out_local)
    else:
        raise ValueError(
            f"combine mode={mode!r} is not a registered option; "
            f"choose one of: packed, scatter")
    out = jnp.where(plan.is_local[:, None], out_local, out_remote)
    return jnp.where(plan.valid[:, None], out, 0)


# --------------------------------------------------------------------------
# destination-chunked pipelining (DESIGN.md §2)
# --------------------------------------------------------------------------


class ChunkedDispatchPlan(NamedTuple):
    """Per-device indices for the pipelined (chunk-major) hot path.

    Stage c owns the relative destination offsets [c·m, (c+1)·m), m =
    G/n_stages; offset 0 (this device itself) is the local fast path and
    lives in chunk 0.  The flat buffer is a concatenation of n statically
    sized chunk sub-buffers (`chunk_caps`), each slot-sorted with its own
    bm-aligned group starts, so the grouped-FFN call on chunk c depends
    only on stage c's collective."""

    send_pos: jax.Array     # int32[C_in] offset-major send pos (trash G*cap)
    local_rel: jax.Array    # int32[C_in] chunk-0-relative flat pos of local
                            # rows (trash = chunk_caps[0])
    stage_rel: jax.Array    # int32[G, cap] offset-major recv row -> its
                            # chunk-relative flat pos (trash = that chunk's
                            # cap; offset 0 rows are always trash)
    group_start: jax.Array  # int32[n, S] chunk-relative bm-aligned starts
    group_end: jax.Array    # int32[n, S] start + received rows per slot
    overflow: jax.Array     # int32[] token-replicas dropped to residual
    valid: jax.Array        # bool[C_in] row actually dispatched
    is_local: jax.Array     # bool[C_in] row took the local fast path

    @property
    def n_stages(self) -> int:
        return self.group_start.shape[0]


def make_chunked_plan(
    st: DispatchStatics,
    ex: jax.Array,            # int32[C_in] expert id per local row (E = pad)
    flow: jax.Array,          # int32[E, G, R] the schedule's flow tensor
    my_index: jax.Array,      # int32[] flat device index in the group
    n_stages: int,
) -> ChunkedDispatchPlan:
    """Chunk-major variant of :func:`make_plan`.

    Row -> (replica, segment, chunk offset) assignment is *identical* to
    the monolithic plan (shared :func:`_sender_layout`), so the same rows
    dispatch, overflow and combine — only buffer positions differ, which
    is what makes the pipelined path bit-compatible."""
    e_n, g_n, r_n = flow.shape
    s_n, cap, bm = st.num_slots, st.cap, st.bm
    m = g_n // n_stages
    caps = chunk_caps(st, n_stages)
    caps_arr = jnp.asarray(caps, jnp.int32)               # [n]

    snd = _sender_layout(st, ex, flow, my_index)

    # ---- sender: destination-major -> offset-major send positions -------
    offset_row = (snd.dst_dev - my_index) % g_n           # [C_in]
    send_pos = jnp.where(snd.remote_ok,
                         offset_row * cap + snd.chunk_off, g_n * cap)

    # ---- receiver: chunk-major flat layout ------------------------------
    offs = jnp.arange(g_n, dtype=jnp.int32)               # offset ids
    srcs = (my_index - offs) % g_n                        # src dev per offset
    recv_seg = _recv_segments(st, flow, my_index)         # [G(src), S]
    recv_seg_start = jnp.cumsum(recv_seg, axis=1) - recv_seg
    seg_o = recv_seg[srcs]                                # [G(offset), S]
    seg_o_start = recv_seg_start[srcs]                    # within cap chunk
    seg_cs = seg_o.reshape(n_stages, m, s_n)
    slot_counts = seg_cs.sum(axis=1)                      # [n, S]
    intra_o = jnp.cumsum(seg_cs, axis=1) - seg_cs         # [n, m, S]
    sizes_pad = ((slot_counts + bm - 1) // bm) * bm
    group_start = jnp.cumsum(sizes_pad, axis=1) - sizes_pad    # [n, S] rel
    group_end = group_start + slot_counts

    # remote recv rows, offset-major [G, cap]: chunk-relative positions
    c_ids = jnp.arange(cap, dtype=jnp.int32)[None, :]     # [1, cap]
    slot_of, off_in_seg = _chunk_row_slots(seg_o_start, seg_o, cap)
    chunk_of = offs // m                                  # [G]
    o_idx = offs % m
    rel = (
        group_start[chunk_of[:, None], slot_of]
        + intra_o[chunk_of[:, None], o_idx[:, None], slot_of]
        + off_in_seg
    )
    cap_of = caps_arr[chunk_of]                           # [G]
    in_use = (c_ids < seg_o.sum(axis=1)[:, None]) & (offs != 0)[:, None]
    stage_rel = jnp.where(in_use & (rel < cap_of[:, None]), rel,
                          cap_of[:, None])

    # local fast-path rows: offset 0 is the first source of chunk 0, so the
    # intra-source term vanishes
    loc_rel = group_start[0, snd.dst_slot] + snd.seg_off_row
    loc_ok = snd.row_local & (loc_rel < caps[0])
    local_rel = jnp.where(loc_ok, loc_rel, caps[0])

    overflow = jnp.sum(snd.overflowed & snd.routed) + \
        jnp.sum(snd.row_local & ~loc_ok)
    return ChunkedDispatchPlan(
        send_pos=send_pos.astype(jnp.int32),
        local_rel=local_rel.astype(jnp.int32),
        stage_rel=stage_rel.astype(jnp.int32),
        group_start=group_start.astype(jnp.int32),
        group_end=group_end.astype(jnp.int32),
        overflow=overflow.astype(jnp.int32),
        valid=(snd.remote_ok | loc_ok),
        is_local=loc_ok,
    )


def _stage_offsets(n_stages: int, g_n: int, c: int):
    m = g_n // n_stages
    return list(range(c * m, (c + 1) * m))


def _stage_exchange(send_all, g_n, n_stages, c, my_index, group_axes,
                    chunk_comm, reverse: bool):
    """One stage's collective: offset-major [m*cap, H] in, same out.

    Forward moves each offset-o cap chunk to device (d + o) mod G; reverse
    returns expert outputs to the sender ((d - o) mod G).  ``send_all`` is
    the full offset-major buffer [G*cap, H] (forward) or the stage's back
    buffer [m*cap, H] (reverse, with ``c`` fixing which offsets it holds).
    """
    cap = send_all.shape[0] // (g_n if not reverse else g_n // n_stages)
    h = send_all.shape[-1]
    m = g_n // n_stages
    offsets = _stage_offsets(n_stages, g_n, c)
    axes = tuple(group_axes)

    if chunk_comm == "ppermute":
        parts = []
        for j, o in enumerate(offsets):
            base = (o if not reverse else j) * cap
            sl = jax.lax.dynamic_slice_in_dim(send_all, base, cap)
            if o == 0:
                parts.append(jnp.zeros_like(sl))
                continue
            perm = [((d + o) % g_n, d) for d in range(g_n)] if reverse \
                else [(d, (d + o) % g_n) for d in range(g_n)]
            parts.append(jax.lax.ppermute(sl, axes, perm=perm))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    if chunk_comm != "a2a":
        raise ValueError(
            f"chunk_comm={chunk_comm!r} is not a registered option; "
            f"choose one of: ppermute, a2a")
    # a2a reference variant: a full-shape all_to_all per stage carrying
    # only the stage's destination chunks (zeros elsewhere).  Portable but
    # not volume-reducing — the ppermute variant is the schedulable one.
    devs = jnp.arange(g_n, dtype=jnp.int32)
    cpos = jnp.arange(cap, dtype=jnp.int32)[None, :]
    if not reverse:
        offs_of_dst = (devs - my_index) % g_n             # [G]
        in_stage = (offs_of_dst >= offsets[0]) & \
            (offs_of_dst <= offsets[-1]) & (offs_of_dst != 0)
        idx = offs_of_dst[:, None] * cap + cpos           # [G, cap]
        buf = send_all[idx.reshape(-1)]
        buf = jnp.where(jnp.repeat(in_stage, cap)[:, None], buf, 0)
        recv = jax.lax.all_to_all(
            buf.reshape(g_n, cap, h), axes,
            split_axis=0, concat_axis=0, tiled=False,
        ).reshape(g_n * cap, h)
        # offset-major stage view: offset o's rows came from (me - o) % G
        srcs = (my_index - jnp.asarray(offsets, jnp.int32)) % g_n
        idx2 = srcs[:, None] * cap + cpos
        return recv[idx2.reshape(-1)]
    # reverse: return chunk o to source (me - o) % G via slice (me - o)
    offs_of_src = (my_index - devs) % g_n                 # [G]
    in_stage = (offs_of_src >= offsets[0]) & \
        (offs_of_src <= offsets[-1]) & (offs_of_src != 0)
    idx = jnp.clip(offs_of_src - offsets[0], 0, m - 1)[:, None] * cap + cpos
    buf = send_all[idx.reshape(-1)]
    buf = jnp.where(jnp.repeat(in_stage, cap)[:, None], buf, 0)
    ret = jax.lax.all_to_all(
        buf.reshape(g_n, cap, h), axes,
        split_axis=0, concat_axis=0, tiled=False,
    ).reshape(g_n * cap, h)
    # offset-major: offset o's returns come from destination (me + o) % G
    dsts = (my_index + jnp.asarray(offsets, jnp.int32)) % g_n
    idx2 = dsts[:, None] * cap + cpos
    return ret[idx2.reshape(-1)]


def dispatch_pipelined(
    st: DispatchStatics,
    plan: ChunkedDispatchPlan,
    rows: jax.Array,                 # [C_in, H] token-replica hidden states
    group_axes: Sequence[str],
    my_index: jax.Array,
    chunk_comm: str = "ppermute",
):
    """Destination-chunked dispatch: returns a tuple of n flat chunk
    sub-buffers.  Chunk c depends only on stage c's collective, so the
    caller's per-chunk grouped-FFN calls overlap later stages' collectives
    in the dataflow graph (DESIGN.md §2)."""
    g_n, cap, h = st.group_size, st.cap, rows.shape[-1]
    c_in = rows.shape[0]
    n = plan.n_stages
    m = g_n // n
    caps = chunk_caps(st, n)

    send_src = _inverse_index(plan.send_pos, g_n * cap, c_in)
    send_all = _gather_rows(rows, send_src)               # [G*cap, H]

    chunks = []
    for c in range(n):
        recv = _stage_exchange(send_all, g_n, n, c, my_index, group_axes,
                               chunk_comm, reverse=False)  # [m*cap, H]
        rel = plan.stage_rel[c * m:(c + 1) * m].reshape(-1)
        # chunk sources: [0, m*cap) = stage recv rows, then (chunk 0 only)
        # [m*cap, m*cap+C_in) = local rows; one past the end = zero row
        if c == 0:
            src = jnp.full((caps[c] + 1,), m * cap + c_in, jnp.int32)
            src = src.at[rel].set(jnp.arange(m * cap, dtype=jnp.int32))
            src = src.at[plan.local_rel].set(
                m * cap + jnp.arange(c_in, dtype=jnp.int32))
            source = jnp.concatenate([recv, rows])
        else:
            src = _inverse_index(rel, caps[c], m * cap)
            source = recv
        chunks.append(_gather_rows(source, src[:caps[c]] if c == 0 else src))
    return tuple(chunks)


def combine_pipelined(
    st: DispatchStatics,
    plan: ChunkedDispatchPlan,
    out_chunks,                      # tuple of [caps[c], H] expert outputs
    group_axes: Sequence[str],
    my_index: jax.Array,
    chunk_comm: str = "ppermute",
) -> jax.Array:
    """Inverse of :func:`dispatch_pipelined`: per-local-row outputs
    [C_in, H].  Stage c's reverse collective depends only on chunk c's
    FFN output — the combine side of the overlap."""
    g_n, cap = st.group_size, st.cap
    h = out_chunks[0].shape[-1]
    n = plan.n_stages
    m = g_n // n
    caps = chunk_caps(st, n)

    ret_parts = []
    for c in range(n):
        rel = plan.stage_rel[c * m:(c + 1) * m].reshape(-1)
        back = _gather_rows(out_chunks[c], rel)           # [m*cap, H]
        ret_parts.append(
            _stage_exchange(back, g_n, n, c, my_index, group_axes,
                            chunk_comm, reverse=True))
    ret_all = jnp.concatenate(ret_parts) if n > 1 else ret_parts[0]

    out_remote = _gather_rows(ret_all, plan.send_pos)     # [C_in, H]
    out_local = _gather_rows(out_chunks[0], plan.local_rel)
    out = jnp.where(plan.is_local[:, None], out_local, out_remote)
    return jnp.where(plan.valid[:, None], out, 0)
