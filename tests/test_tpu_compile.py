"""The Pallas kernels compile for a TPU v5e chip at real widths.

The chip is described (``v5e:2x2``), not attached: each test lowers a
kernel for device 0 of the description and compiles it with the TPU
compiler that ships with JAX, which refuses what the chip would refuse
(block shapes off the tiling, primitives with no TPU lowering). Nothing
runs. The topology is described inside a fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Device 0 of the description, with JAX's persistent compile cache
    off: a program compiled for it could not be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _flash(window, dh):
    from repro.kernels.flash_attention.ops import flash_attention
    s, h, kh = 2048, 48, 4
    return (lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=window),
            [((1, s, h, dh), jnp.bfloat16), ((1, s, kh, dh), jnp.bfloat16),
             ((1, s, kh, dh), jnp.bfloat16)])


def _rglru():
    from repro.kernels.rglru.ops import rglru
    return rglru, [((1, 2048, 4096), jnp.float32)] * 2


def _ssd():
    from repro.kernels.ssd.ops import ssd
    b, s, h, p, g, n = 4, 2048, 64, 64, 1, 128
    return (lambda *a: ssd(*a, chunk=256),
            [((b, s, h, p), jnp.bfloat16), ((b, s, h), jnp.float32),
             ((h,), jnp.float32), ((b, s, g, n), jnp.bfloat16),
             ((b, s, g, n), jnp.bfloat16)])


N_CODEC = 4096 * 1024  # 4096 tiles of 1024


def _codec_encode():
    from repro.kernels.ckpt_codec.ops import delta_encode
    return delta_encode, [((N_CODEC,), jnp.float32)] * 2


def _codec_decode():
    from repro.kernels.ckpt_codec.ops import delta_decode
    return (lambda q, s, b: delta_decode(q, s, b, shape=(N_CODEC,),
                                         dtype=jnp.float32),
            [((4096, 1024), jnp.int8), ((4096, 1), jnp.float32),
             ((N_CODEC,), jnp.float32)])


def _moe_gmm():
    from repro.kernels.moe_gmm.kernel import gmm
    t, d, e, f = 4096, 2048, 8, 4096
    return gmm, [((t, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16),
                 ((t // 128,), jnp.int32)]


@pytest.mark.parametrize("build", [
    pytest.param(lambda: _flash(0, 128), id="flash_attention"),
    pytest.param(lambda: _flash(2048, 256), id="flash_attention_window"),
    pytest.param(_rglru, id="rglru"),
    pytest.param(_ssd, id="ssd"),
    pytest.param(_codec_encode, id="ckpt_codec_encode"),
    pytest.param(_codec_decode, id="ckpt_codec_decode"),
    pytest.param(_moe_gmm, id="moe_gmm"),
])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build()
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
