"""Pallas TPU kernel: RWKV-6 (Finch) chunked linear-attention recurrence.

The assigned rwkv6-7b architecture is attention-free: its token-mixing layer
is the data-dependent-decay recurrence

    S_t = diag(w_t) S_{t-1} + k_t v_t^T,
    o_t = q_t (S_{t-1} + u ⊙ k_t v_t^T)           (q is RWKV's "r")

A naive scan is T sequential outer products — VPU-bound and latency-bound on
TPU.  The TPU-native formulation processes the sequence in chunks that turn
most of the work into MXU matmuls while keeping every exponential factor
bounded in (0, 1]:

  * grid = (BH, T/chunk); the (D, D) f32 state lives in VMEM scratch and is
    carried across the chunk dimension (sequential on TPU); it resets when a
    new (batch, head) row starts.
  * within a chunk, steps are processed in sub-chunks of τ=16.  With local
    cumulative log-decays c_t = Σ_{i<=t} log w_i (c ≤ 0 always):
       cross  : o += (q_t ⊙ exp(c_{t-1})) @ S_in          — one (τ,D)x(D,D)
       intra  : score[t,s] = Σ_d q[t,d] k[s,d] exp(c[t-1,d] - c[s,d]), s<t
                plus the diagonal bonus (q_t · (u ⊙ k_t)) v_t
       update : S ← diag(exp(c_τ)) S_in + Σ_s (k_s ⊙ exp(c_τ - c_s)) v_s^T
    Every exp argument is ≤ 0 (c is non-increasing and s ≤ t-1 inside the
    causal mask), so no normalization pass is needed — this is why the
    sub-chunked form is preferred over the classic "divide by W_s" GLA form,
    which overflows for strong decay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_pallas"]


def _wkv6_kernel(q_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref,
                 *, chunk: int, sub: int, d: int, nchunks: int):
    t_chunk = pl.program_id(1)

    @pl.when(t_chunk == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                  # (1, D)
    f32 = jnp.float32
    # lower-triangular ones: prefix sums over a sub-chunk as one matmul
    r_ids = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    c_ids = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    tril = (r_ids >= c_ids).astype(f32)
    causal = r_ids > c_ids                            # strictly causal
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))

    def sub_step(i, s_in):
        sl = pl.multiple_of(i * sub, sub)
        rows = (0, pl.ds(sl, sub), slice(None))
        qs = q_ref[rows].astype(f32)                  # (τ, D)
        ks = k_ref[rows].astype(f32)
        vs = v_ref[rows].astype(f32)
        lws = lw_ref[rows].astype(f32)
        c = jax.lax.dot(tril, lws,                    # c_t, t=1..τ
                        precision=jax.lax.Precision.HIGHEST)
        c_prev = c - lws                              # c_{t-1}
        # cross-subchunk: (τ, D) x (D, D)
        o_sub = jax.lax.dot(qs * jnp.exp(c_prev), s_in)
        # intra-subchunk, strictly causal, per-dim bounded exponents
        expo = c_prev[:, None, :] - c[None, :, :]     # (τ, τ, D)
        amat = jnp.exp(jnp.minimum(expo, 0.0))
        score = jnp.sum(qs[:, None, :] * ks[None, :, :] * amat, axis=-1)
        score = jnp.where(causal, score, 0.0)
        o_sub += jax.lax.dot(score, vs)
        # current-token bonus
        o_sub += jnp.sum(qs * u * ks, axis=-1, keepdims=True) * vs
        o_ref[rows] = o_sub.astype(o_ref.dtype)
        # state update: S ← diag(exp(c_τ)) S + Σ_s (k_s ⊙ exp(c_τ - c_s)) v_s^T
        c_tau = c[sub - 1:sub, :]                     # (1, D)
        k_dec = ks * jnp.exp(c_tau - c)
        decay = jnp.where(eye, jnp.exp(c_tau), 0.0)   # diag(exp(c_τ))
        return jax.lax.dot(decay, s_in) + jax.lax.dot_general(
            k_dec, vs, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    s_ref[...] = jax.lax.fori_loop(0, chunk // sub, sub_step, s_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def wkv6_pallas(
    q: jax.Array,    # [BH, T, D]
    k: jax.Array,    # [BH, T, D]
    v: jax.Array,    # [BH, T, D]
    lw: jax.Array,   # [BH, T, D] log-decay (<= 0), i.e. -exp(w_proj)
    u: jax.Array,    # [BH, D]
    chunk: int = 128,
    sub: int = 16,
    interpret: bool = False,
) -> jax.Array:
    bh, t, d = q.shape
    assert t % chunk == 0 and chunk % sub == 0, (t, chunk, sub)
    nchunks = t // chunk
    grid = (bh, nchunks)
    kernel = functools.partial(
        _wkv6_kernel, chunk=chunk, sub=sub, d=d, nchunks=nchunks
    )
    blk = lambda b, i: (b, i, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), blk),
            pl.BlockSpec((1, chunk, d), blk),
            pl.BlockSpec((1, chunk, d), blk),
            pl.BlockSpec((1, chunk, d), blk),
            # u as [BH, 1, D]: the block's last two dims are full extents
            pl.BlockSpec((1, 1, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), blk),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, lw, u[:, None, :])
