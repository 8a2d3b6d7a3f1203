"""Top-K gating (paper §2.1) with the standard auxiliary losses.

The router is *unmodified* model logic: MicroEP is a systematic solution, so
the token->expert assignment the router produces is never altered (no drops,
no capacity truncation at the router).  The small load-balancing auxiliary
loss mirrors the paper's experimental setup (§7.1 "a small auxiliary loss").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import scopes

__all__ = ["RouterOut", "top_k_gating", "zipf_gating"]


class RouterOut(NamedTuple):
    expert_ids: jax.Array   # int32[T, K]
    gate_w: jax.Array       # f32[T, K] combine weights (softmax renormalized)
    aux_loss: jax.Array     # f32[] Switch-style load-balance loss
    z_loss: jax.Array       # f32[] router logit z-loss
    probs: jax.Array        # f32[T, E] full router probabilities


@jax.named_scope(scopes.ROUTER)
def top_k_gating(
    x: jax.Array,          # [T, H]
    w_router: jax.Array,   # [H, E]
    top_k: int,
    valid: jax.Array | None = None,  # bool[T] padding mask
) -> RouterOut:
    t, h = x.shape
    e = w_router.shape[1]
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, expert_ids = jax.lax.top_k(probs, top_k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    if valid is None:
        valid = jnp.ones((t,), bool)
    vf = valid.astype(jnp.float32)
    denom = jnp.maximum(vf.sum(), 1.0)

    # Switch aux loss: E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)  # [T, K, E]
    f_e = (onehot.sum(1) * vf[:, None]).sum(0) / (denom * top_k)
    p_e = (probs * vf[:, None]).sum(0) / denom
    aux = e * jnp.sum(f_e * p_e)

    zl = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)) * vf) / denom

    expert_ids = jnp.where(valid[:, None], expert_ids, e)  # pad sentinel
    return RouterOut(expert_ids.astype(jnp.int32), gate_w.astype(jnp.float32),
                     aux, zl, probs)


def zipf_gating(
    key: jax.Array, t: int, num_experts: int, top_k: int, s: float
) -> RouterOut:
    """Synthetic Zipfian router for the load-balancing benchmarks (Fig. 7):
    token's k-th choice drawn (without replacement per token) from a Zipf(s)
    distribution over experts."""
    ranks = jnp.arange(1, num_experts + 1, dtype=jnp.float32)
    p = ranks ** (-s)
    p = p / p.sum()
    logits = jnp.log(p)[None, :] + jax.random.gumbel(key, (t, num_experts))
    _, expert_ids = jax.lax.top_k(logits, top_k)
    gate_w = jnp.full((t, top_k), 1.0 / top_k, jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    probs = jnp.broadcast_to(p[None, :], (t, num_experts))
    return RouterOut(expert_ids.astype(jnp.int32), gate_w, zero, zero, probs)
