"""Compile the main-path Pallas kernels for a described TPU v5e at real
widths, with no chip attached: the TPU compiler refuses here what it would
refuse on the device (scoped-VMEM overruns, blocks not aligned to the
tiling), at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-chip compile cannot be read back without the chip."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _ffn_args(sharding, s, h, f, n):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (sds((n, h), jnp.bfloat16), sds((s,), jnp.int32),
            sds((s,), jnp.int32), sds((s, h, f), jnp.bfloat16),
            sds((s, h, f), jnp.bfloat16), sds((s, f, h), jnp.bfloat16))


# (S, H, F): OLMoE-1B-7B's expert layer, and the paper's F=8192 experts
FFN_WIDTHS = [(64, 2048, 1024), (16, 2048, 8192)]


@pytest.mark.parametrize("s,h,f", FFN_WIDTHS)
def test_grouped_ffn_forward_compiles(one_chip, s, h, f):
    def fwd(x, gs, ge, wg, wu, wd):
        return ops.grouped_ffn_flat(x, gs, ge, wg, wu, wd, impl="pallas")
    hlo = _hlo(fwd, *_ffn_args(one_chip, s, h, f, 8192))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("s,h,f", FFN_WIDTHS)
def test_grouped_ffn_backward_compiles(one_chip, s, h, f):
    def loss(x, gs, ge, wg, wu, wd):
        out = ops.grouped_ffn_flat(x, gs, ge, wg, wu, wd, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    def grads(x, gs, ge, wg, wu, wd):
        return jax.grad(loss, argnums=(0, 3, 4, 5))(x, gs, ge, wg, wu, wd)

    hlo = _hlo(grads, *_ffn_args(one_chip, s, h, f, 8192))
    assert "tpu_custom_call" in hlo      # the forward kernel stays Pallas


def test_wkv6_compiles_at_rwkv6_7b_width(one_chip):
    bh, t, d = 64, 2048, 64              # rwkv6-7b: 64 heads of 64, T=2048
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    hlo = _hlo(lambda q, k, v, lw, u: ops.wkv6(q, k, v, lw, u, impl="pallas"),
               sds((bh, t, d)), sds((bh, t, d)), sds((bh, t, d)),
               sds((bh, t, d)), sds((bh, d)))
    assert "tpu_custom_call" in hlo
