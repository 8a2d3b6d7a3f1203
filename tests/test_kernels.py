"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), swept over
shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.wkv6_chunk import wkv6_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- grouped ffn

@pytest.mark.parametrize("s,c,h,f", [
    (1, 128, 128, 512), (2, 256, 128, 512), (4, 128, 256, 1024),
    (3, 384, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", ["swiglu", "geglu"])
def test_grouped_ffn_vs_ref(s, c, h, f, dtype, activation):
    key = jax.random.PRNGKey(s * 1000 + c)
    ks = jax.random.split(key, 5)
    x = (jax.random.normal(ks[0], (s, c, h)) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (s, h, f)) * h ** -0.5).astype(dtype)
    wu = (jax.random.normal(ks[2], (s, h, f)) * h ** -0.5).astype(dtype)
    wd = (jax.random.normal(ks[3], (s, f, h)) * f ** -0.5).astype(dtype)
    counts = jax.random.randint(ks[4], (s,), 0, c + 1).astype(jnp.int32)
    out = ops.grouped_ffn(x, counts, wg, wu, wd, activation=activation,
                          impl="interpret")
    expect = ref.grouped_ffn_ref(x, counts, wg, wu, wd, activation)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               **_tol(dtype))


def test_grouped_ffn_empty_groups_skipped():
    """Zero-count groups must produce exact zeros (pl.when skip path)."""
    s, c, h, f = 3, 128, 128, 512
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (s, c, h), jnp.float32)
    wg = wu = jax.random.normal(key, (s, h, f)) * 0.05
    wd = jax.random.normal(key, (s, f, h)) * 0.05
    counts = jnp.asarray([0, 64, 0], jnp.int32)
    out = ops.grouped_ffn(x, counts, wg, wu, wd, impl="interpret")
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(out[2]).max()) == 0.0
    assert float(jnp.abs(out[1, :64]).max()) > 0.0
    assert float(jnp.abs(out[1, 64:]).max()) == 0.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_ffn_flat_vs_ref(dtype):
    """Flat MegaBlocks-style layout (the dispatcher's native format)."""
    bm, s, h, f = 128, 3, 128, 512
    key = jax.random.PRNGKey(1)
    counts = jnp.asarray([100, 0, 250], jnp.int32)
    sizes_pad = ((counts + bm - 1) // bm) * bm
    group_start = jnp.cumsum(sizes_pad) - sizes_pad
    group_end = group_start + counts
    n = int(sizes_pad.sum())
    ks = jax.random.split(key, 4)
    x = (jax.random.normal(ks[0], (n, h)) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (s, h, f)) * h ** -0.5).astype(dtype)
    wu = (jax.random.normal(ks[2], (s, h, f)) * h ** -0.5).astype(dtype)
    wd = (jax.random.normal(ks[3], (s, f, h)) * f ** -0.5).astype(dtype)
    out = ops.grouped_ffn_flat(x, group_start, group_end, wg, wu, wd,
                               impl="interpret")
    expect = ref.grouped_ffn_flat_ref(x, group_start, group_end, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("activation", ["swiglu", "geglu"])
def test_grouped_ffn_flat_custom_vjp_matches_ref_grad(activation):
    """The Pallas path's custom VJP (ragged-dot backward) gives the
    gradients of the oracle, junk rows outside [start, end) included."""
    bm, s, h, f = 128, 3, 128, 256
    counts = jnp.asarray([100, 0, 250], jnp.int32)
    sizes_pad = ((counts + bm - 1) // bm) * bm
    group_start = jnp.cumsum(sizes_pad) - sizes_pad
    group_end = group_start + counts
    n = int(sizes_pad.sum()) + bm            # one trailing tile of junk
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (n, h)) * 0.5
    rows = jnp.arange(n)[:, None]
    member = jnp.any((rows >= group_start) & (rows < group_end), axis=1)
    x = jnp.where(member[:, None], x, 1e3)   # junk must not leak
    wg = jax.random.normal(ks[1], (s, h, f)) * h ** -0.5
    wu = jax.random.normal(ks[2], (s, h, f)) * h ** -0.5
    wd = jax.random.normal(ks[3], (s, f, h)) * f ** -0.5
    probe = jax.random.normal(ks[4], (n, h))

    def loss(impl):
        def fn(x_, wg_, wu_, wd_):
            out = ops.grouped_ffn_flat(x_, group_start, group_end, wg_, wu_,
                                       wd_, activation=activation, impl=impl)
            return jnp.sum(out * probe)
        return fn

    got = jax.grad(loss("interpret"), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    want = jax.grad(loss("ref"), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_grouped_ffn_flat_ref_vs_grouped_ref():
    """The two oracle layouts agree on the same logical groups."""
    s, c, h, f = 2, 128, 64, 128
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    counts = jnp.asarray([50, 90], jnp.int32)
    x3 = jax.random.normal(ks[0], (s, c, h))
    wg = jax.random.normal(ks[1], (s, h, f)) * 0.1
    wu = jax.random.normal(ks[2], (s, h, f)) * 0.1
    wd = jax.random.normal(ks[3], (s, f, h)) * 0.1
    o3 = ref.grouped_ffn_ref(x3, counts, wg, wu, wd)
    group_start = jnp.asarray([0, c], jnp.int32)
    group_end = group_start + counts
    flat = x3.reshape(s * c, h)
    of = ref.grouped_ffn_flat_ref(flat, group_start, group_end, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(of.reshape(s, c, h)),
                               np.asarray(o3), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------------- wkv6

@pytest.mark.parametrize("bh,t,d", [(2, 128, 64), (1, 256, 128), (4, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_vs_ref(bh, t, d, dtype):
    key = jax.random.PRNGKey(bh * 100 + t)
    ks = jax.random.split(key, 5)
    q = (jax.random.normal(ks[0], (bh, t, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (bh, t, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (bh, t, d)) * 0.5).astype(dtype)
    # log-decay <= 0, realistic magnitudes (strong and weak decay mixed)
    lw = -jnp.exp(jax.random.normal(ks[3], (bh, t, d)) - 1.0).astype(dtype)
    u = (jax.random.normal(ks[4], (bh, d)) * 0.5).astype(dtype)
    out = wkv6_pallas(q, k, v, lw, u, chunk=64, interpret=True)
    exp = jax.vmap(lambda q_, k_, v_, lw_, u_: ref.wkv6_chunk_ref(
        q_, k_, v_, jnp.exp(lw_.astype(jnp.float32)), u_,
        jnp.zeros((d, d), jnp.float32))[0])(q, k, v, lw, u)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol)


def test_wkv6_ops_wrapper_pads_t():
    q = k = v = jnp.ones((1, 100, 64)) * 0.1
    lw = -jnp.ones((1, 100, 64))
    u = jnp.zeros((1, 64))
    out_i = ops.wkv6(q, k, v, lw, u, chunk=64, impl="interpret")
    out_r = ops.wkv6(q, k, v, lw, u, impl="ref")
    assert out_i.shape == (1, 100, 64)
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_state_continuity():
    """Chunked evaluation equals one long sequential evaluation (state
    carried correctly across chunks)."""
    d, t = 64, 256
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 4)
    q, k, v = (jax.random.normal(ks[i], (t, d)) * 0.3 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (t, d)))  # decay in (0,1)
    u = jnp.zeros((d,))
    o_full, s_full = ref.wkv6_chunk_ref(q, k, v, w, u,
                                        jnp.zeros((d, d)))
    o1, s1 = ref.wkv6_chunk_ref(q[:128], k[:128], v[:128], w[:128], u,
                                jnp.zeros((d, d)))
    o2, s2 = ref.wkv6_chunk_ref(q[128:], k[128:], v[128:], w[128:], u, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2])),
                               np.asarray(o_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-5, atol=1e-5)
