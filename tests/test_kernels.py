"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,s,kh,g,dh,causal,window,cap,dtype",
    [
        (2, 128, 2, 4, 64, True, 0, 0.0, jnp.bfloat16),
        (1, 256, 1, 8, 128, True, 64, 50.0, jnp.bfloat16),
        (2, 128, 4, 1, 64, False, 0, 0.0, jnp.float32),
        (1, 256, 2, 2, 64, True, 128, 0.0, jnp.float32),
        (1, 128, 2, 3, 32, True, 0, 30.0, jnp.bfloat16),  # odd group
    ])
def test_flash_attention(b, s, kh, g, dh, causal, window, cap, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, kh * g, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kh, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kh, dh), jnp.float32).astype(dtype)
    o = flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                        interpret=True, bq=64, bk=64)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal,
                        window=window, cap=cap).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


# ---------------------------------------------------------------------------
# rglru
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,w,block", [(2, 64, 32, 16), (1, 128, 64, 32),
                                         (3, 96, 16, 32)])
def test_rglru(b, s, w, block):
    from repro.kernels.rglru.ops import rglru
    from repro.kernels.rglru.ref import rglru_ref
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    log_a = -jnp.abs(jax.random.normal(k1, (b, s, w))) * 0.2 - 1e-3
    gated = jax.random.normal(k2, (b, s, w))
    h = rglru(log_a, gated, block=block, interpret=True)
    href = rglru_ref(log_a, gated)
    np.testing.assert_allclose(np.asarray(h), np.asarray(href), atol=1e-5,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# ssd (mamba2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [(2, 64, 4, 16, 2, 32, 16),
                                               (1, 48, 2, 8, 1, 16, 16),
                                               (1, 64, 4, 16, 4, 16, 32),
                                               (1, 50, 2, 8, 1, 16, 16)])
def test_ssd(b, s, h, p, g, n, chunk):
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y, st = ssd(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    yref, stref = ssd_ref(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), a,
                          bb.transpose(0, 2, 1, 3), cc.transpose(0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(yref.transpose(0, 2, 1, 3)),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st),
                               np.asarray(stref.transpose(0, 1, 3, 2)),
                               atol=1e-4, rtol=1e-4)


def test_ssd_matches_model_chunked():
    """kernels/ssd == models/ssm.ssd_chunked (two independent impls)."""
    from repro.kernels.ssd.ops import ssd
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, p, g, n = 2, 64, 4, 8, 1, 16
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y1, st1 = ssd(x, dt, a, bb, cc, chunk=16, interpret=True)
    y2, st2 = ssd_chunked(x, dt, a, bb, cc, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2, np.float32),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# moe grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,d,e,f,bt", [(64, 32, 4, 64, 8), (32, 16, 2, 32, 8)])
def test_gmm(t, d, e, f, bt):
    from repro.kernels.moe_gmm.kernel import gmm
    from repro.kernels.moe_gmm.ref import gmm_ref
    x = jax.random.normal(jax.random.PRNGKey(0), (t, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, d, f)) * 0.1
    be = (jnp.arange(t // bt) % e).astype(jnp.int32)
    y = gmm(x, w, be, bt=bt, bf=min(32, f), interpret=True)
    yref = gmm_ref(x, w, be, bt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), atol=1e-4,
                               rtol=1e-4)


def test_moe_ffn_sorted_vs_dense():
    from repro.kernels.moe_gmm.ops import moe_ffn_sorted
    T, D, E, F = 64, 32, 4, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    eid = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, E)
    wi = jax.random.normal(jax.random.PRNGKey(2), (E, D, F)) * 0.1
    wg = jax.random.normal(jax.random.PRNGKey(3), (E, D, F)) * 0.1
    wo = jax.random.normal(jax.random.PRNGKey(4), (E, F, D)) * 0.1
    ym = moe_ffn_sorted(x, eid, wi, wg, wo, n_experts=E, bt=8, bf=32,
                        interpret=True)
    h = jnp.einsum("td,edf->tef", x, wi)
    g = jnp.einsum("td,edf->tef", x, wg)
    yall = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h, wo)
    yd = yall[jnp.arange(T), eid]
    np.testing.assert_allclose(np.asarray(ym), np.asarray(yd), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# checkpoint codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,dtype", [(5000, jnp.float32), (2048, jnp.bfloat16),
                                     (1024, jnp.float32)])
def test_ckpt_codec_roundtrip(n, dtype):
    from repro.kernels.ckpt_codec.ops import delta_decode, delta_encode
    base = jax.random.normal(jax.random.PRNGKey(0), (n,)).astype(dtype)
    new = base + (jax.random.normal(jax.random.PRNGKey(1), (n,)) * 0.01
                  ).astype(dtype)
    q, s = delta_encode(new, base, interpret=True)
    dec = delta_decode(q, s, base, shape=(n,), dtype=dtype, interpret=True)
    err = np.abs(np.asarray(dec, np.float32) - np.asarray(new, np.float32))
    # absmax-int8: per-tile error bounded by scale (=absmax/127) + eps
    bound = np.repeat(np.asarray(s)[:, 0], 1024)[:n] + 1e-6
    assert (err <= bound).all()


def test_ckpt_codec_kernel_matches_ref():
    from repro.kernels.ckpt_codec.ops import delta_encode
    from repro.kernels.ckpt_codec.ref import encode_ref
    new = np.random.RandomState(0).randn(4096).astype(np.float32)
    base = new + np.random.RandomState(1).randn(4096).astype(np.float32) * .1
    q, s = delta_encode(jnp.asarray(new), jnp.asarray(base), interpret=True)
    qr, sr = encode_ref(new.reshape(-1, 1024), base.reshape(-1, 1024))
    assert (np.asarray(q) == qr).mean() > 0.999  # rounding ties may differ
    np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-6)
