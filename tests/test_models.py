"""Model substrate: per-arch forward/prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as T

S = 24
B = 2


def _inputs(cfg, key=1):
    text_len = S - cfg.prefix_len
    tokens = jax.random.randint(jax.random.PRNGKey(key), (B, text_len), 0,
                                cfg.vocab_size)
    kw = {}
    if cfg.enc_dec:
        kw["enc_frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, S, cfg.d_model), jnp.float32) * 0.1
    if cfg.prefix_len:
        kw["prefix_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.prefix_len, cfg.d_model),
            jnp.float32) * 0.1
    return tokens, kw


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_forward_prefill_decode_consistent(arch):
    cfg = registry.get_smoke_config(arch)
    rt = T.ModelRuntime(tp=1, attn_impl="naive", max_seq=32, remat=False)
    params, specs = T.init_params(jax.random.PRNGKey(0), cfg, rt)
    tokens, kw = _inputs(cfg)
    hidden, _, _ = T.forward(params, cfg, rt, tokens, **kw)
    full_logits = T.lm_head(params, cfg, hidden)
    assert bool(jnp.isfinite(full_logits).all())
    logits_pre, cache = T.prefill(params, cfg, rt, tokens[:, :-1], **kw)
    logits_dec, _, _ = T.decode_step(params, cfg, rt, cache, tokens[:, -1],
                                     jnp.int32(S - 1))
    assert float(jnp.max(jnp.abs(logits_pre - full_logits[:, -2]))) < 0.05
    assert float(jnp.max(jnp.abs(logits_dec - full_logits[:, -1]))) < 0.05


@pytest.mark.parametrize("arch", ["gemma2-9b", "qwen2-72b", "mamba2-1.3b",
                                  "recurrentgemma-9b"])
def test_blockwise_matches_naive(arch):
    cfg = registry.get_smoke_config(arch)
    rt1 = T.ModelRuntime(tp=1, attn_impl="naive", max_seq=32, remat=False)
    rt2 = T.ModelRuntime(tp=1, attn_impl="blockwise", max_seq=32,
                         remat=False)
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg, rt1)
    tokens, kw = _inputs(cfg)
    h1, _, _ = T.forward(params, cfg, rt1, tokens, **kw)
    h2, _, _ = T.forward(params, cfg, rt2, tokens, **kw)
    assert float(jnp.max(jnp.abs(h1.astype(jnp.float32) -
                                 h2.astype(jnp.float32)))) < 0.1


def test_padded_heads_equivalent():
    """TP head padding must not change the function: run the padded layout
    and the exact layout with the same underlying weights."""
    from repro.models.attention import make_head_layout
    cfg = registry.get_smoke_config("deepseek-coder-33b")  # 6 heads, kv 2
    rt1 = T.ModelRuntime(tp=1, attn_impl="naive", max_seq=32, remat=False)
    rt4 = T.ModelRuntime(tp=4, attn_impl="naive", max_seq=32, remat=False)
    l1 = rt1.head_layout(cfg)   # group 3 (exact)
    l4 = rt4.head_layout(cfg)   # group padded to 4 -> 8 q heads
    assert l1.group == 3 and l4.group == 4 and l4.q_heads == 8
    params4, _ = T.init_params(jax.random.PRNGKey(0), cfg, rt4)

    def depad(p4):
        """Strip padded q-head rows (group-major layout)."""
        import copy
        p1 = jax.tree.map(lambda x: x, p4)
        g4, g1, kh = l4.group, l1.group, l4.kv_heads
        keep = np.concatenate([np.arange(k * g4, k * g4 + g1)
                               for k in range(kh)])
        for grp in ("group0",):
            lp = p1[grp]["p0"]["mixer"]
            lp["wq"] = lp["wq"][:, :, keep]
            lp["wo"] = lp["wo"][:, keep]
        return p1

    params1 = depad(params4)
    tokens, kw = _inputs(cfg)
    h4, _, _ = T.forward(params4, cfg, rt4, tokens, **kw)
    h1, _, _ = T.forward(params1, cfg, rt1, tokens, **kw)
    assert float(jnp.max(jnp.abs(h4.astype(jnp.float32) -
                                 h1.astype(jnp.float32)))) < 1e-2


def test_local_attention_matches_masked_blockwise():
    from repro.models.attention import blockwise_attention, local_attention, \
        naive_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 2, 16), jnp.float32)
    o1 = local_attention(q, k, v, window=16, bq=16)
    o2 = naive_attention(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5,
                               rtol=1e-4)


def test_causal_future_independence():
    """Changing future tokens must not change past hidden states (covers
    attention, RG-LRU, and SSD causality at once)."""
    for arch in ["gemma2-9b", "mamba2-1.3b", "recurrentgemma-9b"]:
        cfg = registry.get_smoke_config(arch)
        rt = T.ModelRuntime(tp=1, attn_impl="naive", max_seq=32, remat=False)
        params, _ = T.init_params(jax.random.PRNGKey(0), cfg, rt)
        tokens, kw = _inputs(cfg)
        t2 = tokens.at[:, -4:].set((tokens[:, -4:] + 7) % cfg.vocab_size)
        h1, _, _ = T.forward(params, cfg, rt, tokens, **kw)
        h2, _, _ = T.forward(params, cfg, rt, t2, **kw)
        cut = S - 4
        diff = float(jnp.max(jnp.abs(
            h1[:, :cut].astype(jnp.float32) -
            h2[:, :cut].astype(jnp.float32))))
        assert diff == 0.0, (arch, diff)
