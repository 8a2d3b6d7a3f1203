"""Token ids drawn by Zipf's law over the vocabulary.

Parameters (a mix's JSON file): ``exponent`` s, ``seq_len``, ``pool``.
Rank r (1-based) has probability proportional to r**-s; ranks map to ids
through a permutation drawn from the key.  Each row is seq_len + 1 draws:
tokens are the first seq_len, labels the next token at every position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(params: dict, *, vocab: int, batch: int, seq_len: int, pool: int,
         key) -> dict:
    """{"tokens", "labels"}: int32[pool, batch, seq_len], on the device."""
    k_perm, k_draw = jax.random.split(key)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    w = ranks ** -float(params["exponent"])
    cdf = jnp.cumsum(w / jnp.sum(w))
    u = jax.random.uniform(k_draw, (pool, batch, seq_len + 1))
    r = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
    ids = jax.random.permutation(k_perm, vocab).astype(jnp.int32)[r]
    return {"tokens": ids[..., :-1], "labels": ids[..., 1:]}
