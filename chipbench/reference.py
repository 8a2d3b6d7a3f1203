"""Plain float32 reference of a sparse-expert decoder's training step.

Written from the configuration file alone: it imports nothing of the program
under test and takes nothing that the program made.  Every matrix product
runs at ``precision="highest"``.

Model (one decoder block, repeated ``num_hidden_layers`` times):

    x   = embed[tokens]
    x  += Attn(RMS(x; ln1))              causal, rotary, grouped KV heads
    x  += MoE(RMS(x; ln2))               softmax router, top-k, SwiGLU experts
    out = RMS(x; final_norm) @ embed.T   -> next-token CE
          (@ head, a leaf of its own, where tie_word_embeddings is false)
    loss = CE + aux_coef * sum_layers aux + z_coef * sum_layers z

RMS(x; s) = x / sqrt(mean(x^2) + eps) * (1 + s), with s starting at 0.
The router's Switch load-balancing loss and z-loss are taken over each
device's share of a micro-batch's tokens (contiguous rows) and averaged over
the shares: ``groups`` is the number of shares.

Experts are held as one wide gated FFN: ``w_gate``/``w_up`` are [H, E*F]
(expert e owns columns e*F .. e*F+F-1) and ``w_down`` is [E*F, H].  Every
token goes through every expert and is weighted by its (mostly zero) gate:
dense, slow and plainly right.  Rows are processed in blocks so that the
intermediate [rows, E*F] fits.

``quant="fp8"`` is the control: the same step computed one precision below
the configuration's bfloat16, as float8 training does it.  Every tensor the
program keeps in its working precision is rounded with a per-tensor scale:
to float8 e4m3 in the forward pass (the weights as used, every matrix
product's operands and result, the norms' outputs, the residual stream) and
to float8 e5m2 in the backward pass (the cotangent at each of those
points, and the sum of the micro-batches' gradients).  Accumulation inside
a matrix product, softmax and the loss stay in float32.

The training step averages the gradients of ``n_micro`` micro-batches and
applies AdamW with global-norm clipping and decoupled weight decay on every
leaf.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512


class Dims(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_ff: int
    vocab: int
    layers: int
    rope_theta: float
    eps: float
    tied: bool
    aux_coef: float
    z_coef: float


def dims(model: dict) -> Dims:
    """The sizes the reference needs, from a configuration's ``model``."""
    return Dims(
        hidden=model["hidden_size"], heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        expert_ff=model["moe_intermediate_size"], vocab=model["vocab_size"],
        layers=model["num_hidden_layers"],
        rope_theta=float(model["rope_theta"]),
        eps=float(model["rms_norm_eps"]),
        tied=bool(model["tie_word_embeddings"]),
        aux_coef=float(model["router_aux_loss_coef"]),
        z_coef=float(model["router_z_loss_coef"]))


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def init_params(key, d: Dims) -> dict:
    """Seeded float32 weights: normal, scaled by fan-in (experts by
    sqrt(2 / (H + F))); norm offsets start at 0."""
    h, ef = d.hidden, d.experts * d.expert_ff
    qd, kd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    def layer(k):
        ks = jax.random.split(k, 8)
        sx = (2.0 / (h + d.expert_ff)) ** 0.5
        return {
            "ln1": jnp.zeros((h,), jnp.float32),
            "ln2": jnp.zeros((h,), jnp.float32),
            "wq": normal(ks[0], (h, qd), h ** -0.5),
            "wk": normal(ks[1], (h, kd), h ** -0.5),
            "wv": normal(ks[2], (h, kd), h ** -0.5),
            "wo": normal(ks[3], (qd, h), qd ** -0.5),
            "router": normal(ks[4], (h, d.experts), h ** -0.5),
            "w_gate": normal(ks[5], (h, ef), sx),
            "w_up": normal(ks[6], (h, ef), sx),
            "w_down": normal(ks[7], (ef, h), sx),
        }

    p = {"embed": normal(k_embed, (d.vocab, h), h ** -0.5),
         "final_norm": jnp.zeros((h,), jnp.float32),
         "layers": tuple(layer(k)
                         for k in jax.random.split(k_layers, d.layers))}
    if not d.tied:
        p["head"] = normal(k_head, (h, d.vocab), h ** -0.5)
    return p


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _round_fp8(x, dtype):
    # scaled so that the largest magnitude is the format's largest finite
    # value; clipped first, since e4m3fn has no infinity and a value past
    # its largest converts to NaN
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    y = jnp.clip(x / scale, -top, top)
    return y.astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x, jnp.float8_e4m3fn)


_fp8.defvjp(lambda x: (_round_fp8(x, jnp.float8_e4m3fn), None),
            lambda _, g: (_round_fp8(g, jnp.float8_e5m2),))


def _q(x, quant):
    """x as the working precision holds it: float32 as is, or float8."""
    return _fp8(x) if quant == "fp8" else x


def _mm(a, b, quant):
    return _q(jnp.matmul(_q(a, quant), _q(b, quant), precision=HIGHEST),
              quant)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, theta):
    """x [..., T, D]: rotate the two halves of D by position angles."""
    t, dd = x.shape[-2], x.shape[-1]
    half = dd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(lp, h, d: Dims, quant):
    """h [T, H] of one sequence -> [T, H]."""
    t = h.shape[0]
    q = _mm(h, lp["wq"], quant).reshape(t, d.heads, d.head_dim)
    k = _mm(h, lp["wk"], quant).reshape(t, d.kv_heads, d.head_dim)
    v = _mm(h, lp["wv"], quant).reshape(t, d.kv_heads, d.head_dim)
    q = _q(_rope(q.transpose(1, 0, 2), d.rope_theta), quant)   # [Hq, T, D]
    k = _q(_rope(k.transpose(1, 0, 2), d.rope_theta), quant)   # [Hkv, T, D]
    v = v.transpose(1, 0, 2)
    rep = d.heads // d.kv_heads                             # q head i uses
    k, v = jnp.repeat(k, rep, 0), jnp.repeat(v, rep, 0)     # kv head i//rep
    s = jnp.einsum("htd,hsd->hts", q, k, precision=HIGHEST) \
        * d.head_dim ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _q(jnp.einsum("hts,hsd->htd", w, v, precision=HIGHEST), quant)
    return _mm(o.transpose(1, 0, 2).reshape(t, -1), lp["wo"], quant)


def _router(lp, x, d: Dims, groups: int, quant):
    """x [N, H] -> dense gates [N, E], summed aux and z losses."""
    logits = jnp.matmul(x, _q(lp["router"], quant), precision=HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, d.top_k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, d.experts, dtype=jnp.float32)  # [N,K,E]
    gates = jnp.einsum("nk,nke->ne", top_w, onehot, precision=HIGHEST)
    # Switch aux E * sum_e f_e p_e and the z-loss mean(lse^2), each over a
    # device's share of rows, averaged over the shares
    chosen = onehot.sum(1).reshape(groups, -1, d.experts)
    f = chosen.mean(1) / d.top_k
    p = probs.reshape(groups, -1, d.experts).mean(1)
    aux = jnp.mean(d.experts * jnp.sum(f * p, -1))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return gates, aux, z


def _experts(lp, x, gates, d: Dims, quant):
    """Dense mixture over all experts, in row blocks: [N, H] -> [N, H]."""
    n, h = x.shape
    blk = math.gcd(n, ROW_BLOCK)

    @jax.checkpoint
    def block(args):
        xb, gb = args
        a = jax.nn.silu(_mm(xb, lp["w_gate"], quant)) \
            * _mm(xb, lp["w_up"], quant)
        a = a * jnp.repeat(gb, d.expert_ff, axis=1)
        return _mm(a, lp["w_down"], quant)

    out = jax.lax.map(block, (x.reshape(n // blk, blk, h),
                              gates.reshape(n // blk, blk, d.experts)))
    return out.reshape(n, h)


def _ce_sum(x, w_out, labels, quant):
    """Summed next-token CE over rows with labels >= 0, in row blocks."""
    n, h = x.shape
    blk = math.gcd(n, ROW_BLOCK)

    @jax.checkpoint
    def block(args):
        xb, lb = args
        logits = _mm(xb, w_out, quant)
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[:, None],
                                  -1)[:, 0]
        return jnp.sum(jnp.where(lb >= 0, lse - tgt, 0.0))

    return jnp.sum(jax.lax.map(block, (x.reshape(n // blk, blk, h),
                                       labels.reshape(n // blk, blk))))


def loss_fn(p, tokens, labels, d: Dims, groups: int, quant=None):
    """Mean CE over labelled tokens plus the router losses, for one
    micro-batch of [B, T] tokens."""
    b, t = tokens.shape
    x = _q(p["embed"][tokens], quant)                        # [B, T, H]
    aux = z = 0.0
    for lp in p["layers"]:
        hn = _q(_rms(x, lp["ln1"], d.eps), quant)
        x = _q(x + jax.lax.map(
            jax.checkpoint(lambda s: _attention(lp, s, d, quant)), hn), quant)
        hn = _q(_rms(x, lp["ln2"], d.eps), quant).reshape(b * t, -1)
        gates, a, zz = _router(lp, hn, d, groups, quant)
        x = _q(x + _experts(lp, hn, gates, d, quant).reshape(b, t, -1), quant)
        aux, z = aux + a, z + zz
    x = _q(_rms(x, p["final_norm"], d.eps), quant).reshape(b * t, -1)
    w_out = p["embed"].T if d.tied else p["head"]
    lab = labels.reshape(-1)
    ce = _ce_sum(x, w_out, lab, quant) / jnp.maximum(jnp.sum(lab >= 0), 1)
    return ce + d.aux_coef * aux + d.z_coef * z


# --------------------------------------------------------------------------
# training step
# --------------------------------------------------------------------------


class AdamW(NamedTuple):
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float


class State(NamedTuple):
    params: dict
    mu: dict
    nu: dict
    count: jax.Array


def init_state(params) -> State:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return State(params, zeros, zeros, jnp.zeros((), jnp.int32))


def grads(params, batch, d: Dims, n_micro: int, groups: int, quant=None):
    """(mean loss, mean gradient) over ``n_micro`` contiguous micro-batches
    of ``batch`` = {"tokens", "labels"} [B, T].  The control sums the
    micro-batches' gradients in its working precision (float8 e5m2), as the
    program sums them in its own."""
    split = lambda a: a.reshape((n_micro, -1) + a.shape[1:])
    vg = jax.value_and_grad(functools.partial(loss_fn, d=d, groups=groups,
                                              quant=quant))
    add = jnp.add
    if quant == "fp8":
        add = lambda a, b: _round_fp8(a + b, jnp.float8_e5m2)
    loss = 0.0
    g = jax.tree_util.tree_map(jnp.zeros_like, params)
    for tok, lab in zip(split(batch["tokens"]), split(batch["labels"])):
        l, gi = vg(params, tok, lab)
        loss = loss + l
        g = jax.tree_util.tree_map(add, g, gi)
    return loss / n_micro, jax.tree_util.tree_map(lambda a: a / n_micro, g)


def adamw(st: State, g, opt: AdamW):
    """One AdamW update.  Returns (new state, the clipped gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(gnorm, 1e-9))
    g = jax.tree_util.tree_map(lambda a: a * scale, g)
    count = st.count + 1
    c1 = 1.0 - opt.b1 ** count.astype(jnp.float32)
    c2 = 1.0 - opt.b2 ** count.astype(jnp.float32)
    mu = jax.tree_util.tree_map(lambda m, a: opt.b1 * m + (1 - opt.b1) * a,
                                st.mu, g)
    nu = jax.tree_util.tree_map(
        lambda v, a: opt.b2 * v + (1 - opt.b2) * a * a, st.nu, g)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - opt.lr * ((m / c1) / (jnp.sqrt(v / c2) + opt.eps)
                                      + opt.weight_decay * p),
        st.params, mu, nu)
    return State(params, mu, nu, count), g
