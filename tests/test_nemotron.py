"""nemotron3-nano-30b-a3b: the hybrid Mamba-2 / GQA / expert model against
its plain reference, the expert layer's shares against the uncut layer, the
SSD kernel at its grouped widths, the decode counters, and the three
benchmark configurations' layer groups and parameter trees, which the new
layer kinds must leave as they were.

The reference is the benchmark's (``benchmarks/chip/configs/nemotron_h.py``,
loaded by its path), so the test suite and the benchmark hold the program
to one reference."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as T

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's harness modules and the Nemotron-H reference."""
    sys.path.insert(0, str(BENCH))
    from harness import common, weights
    ref = common.load_module(BENCH / "configs" / "nemotron_h.py")
    return weights, ref


def _model_group(cfg) -> dict:
    """The configuration-file ``model`` group the reference reads."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
            "rms_eps": cfg.rms_eps,
            "pattern": [{"mixer": s.mixer, "mlp": s.mlp}
                        for s in cfg.pattern],
            "ssm": dataclasses.asdict(cfg.ssm),
            "moe": dataclasses.asdict(cfg.moe)}


def _smoke():
    return registry.get_smoke_config("nemotron3-nano-30b-a3b")


def test_configs_state_the_published_sizes():
    full = registry.get_config("nemotron3-nano-30b-a3b")
    ep = registry.get_config("nemotron3-nano-30b-a3b-ep16")
    kinds = "".join({"ssd": "M", "none": "E", "attn_global": "*"}[s.mixer]
                    for s in full.layer_specs)
    assert kinds == "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert full.ssm.d_inner(full.d_model) == 4096
    assert (ep.moe.n_experts, ep.moe.held, full.moe.held) == (128, 8, 128)
    assert [(len(p), r) for p, r in full.groups] == \
        [(7, 5), (2, 3), (2, 1), (2, 4), (1, 1)]
    assert full.param_count() == pytest.approx(31.58e9, rel=2e-3)
    assert ep.param_count() == pytest.approx(4.04e9, rel=2e-3)


@pytest.mark.parametrize("seed", [0, 3])
def test_prefill_then_decode_matches_the_reference(bench, seed):
    """Prefill of 24 tokens, then 15 decode steps through the cache, with
    the weights in float32: the program's logits at each position equal
    the reference's full forward pass to 1e-4 (the same mathematics in
    float32; only the order of the sums differs, and the logits are O(1)).
    In the served bfloat16 a router whose top-k scores nearly tie can
    pick another expert, so no tight bound holds there; the benchmark's
    ``logit_gap_mean`` judges that precision on the chip."""
    weights, ref = bench
    cfg = _smoke()
    rt = T.ModelRuntime(tp=1, max_seq=64, remat=False)
    shapes, _ = T.abstract_params(cfg, rt)
    w = weights.make(seed, shapes, rules=ref.WEIGHT_RULES)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, 40).astype(np.int32)
    want = ref.logits(w, _model_group(cfg), toks)
    n_pre = 24
    prefill = jax.jit(lambda p, t: T.prefill(p, cfg, rt, t))
    decode = jax.jit(lambda p, c, t, i: T.decode_step(p, cfg, rt, c, t, i))
    got, cache = prefill(w32, jnp.asarray(toks[None, :n_pre]))
    rows = [np.asarray(got[0])]
    for i in range(n_pre, len(toks) - 1):
        got, cache, _ = decode(w32, cache, jnp.asarray(toks[i:i + 1]),
                               jnp.int32(i))
        rows.append(np.asarray(got[0]))
    np.testing.assert_allclose(np.stack(rows), want[n_pre - 1:-1],
                               atol=1e-4, rtol=0)


def test_expert_shares_add_up_to_the_uncut_layer(bench):
    """The 4 shares of one expert layer (4 of 16 experts each, every
    share routing over all 16), with the shared expert counted once, add
    up to the reference's uncut layer (float32). A chip holds the first
    experts of its router, so rank r's router lists its experts 4r..4r+3
    first."""
    from repro.models import moe
    from repro.models.layers import rms_norm
    weights, ref = bench
    smoke = _smoke()
    n = smoke.moe.n_experts
    uncut = dataclasses.replace(smoke, moe=dataclasses.replace(
        smoke.moe, n_held=n))
    rt = T.ModelRuntime(tp=1, max_seq=64, remat=False)
    w = weights.make(11, T.abstract_params(uncut, rt)[0],
                     rules=ref.WEIGHT_RULES)
    lw = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      w["group0"]["p1"])          # the first E layer
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, smoke.d_model),
                          jnp.float32)
    h = rms_norm(x, lw["norm2"]["w"], eps=smoke.rms_eps)
    held = smoke.moe.held
    total = jnp.zeros_like(x)
    for rank in range(n // held):
        mine = np.arange(rank * held, (rank + 1) * held)
        order = np.concatenate([mine, np.delete(np.arange(n), mine)])
        p = dict(lw["mlp"], router=lw["mlp"]["router"][:, order],
                 router_bias=lw["mlp"]["router_bias"][order])
        for k in ("wi", "wo"):
            p[k] = p[k][:, mine]
        if rank:
            del p["shared"]
        y, _, _ = moe.apply_moe_gshard(p, h, smoke)
        total = total + y
    m = _model_group(uncut)
    want = ref.moe_block(lw, x[0], m, ref.exact) - x[0]
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_ssd_kernel_at_grouped_head_count_widths():
    """kernels/ssd in interpret mode at the model's SSM widths (64 heads
    of 64 from the head count, 8 groups of B/C, state 128, chunk 128)
    against ``ssd_chunked``."""
    from repro.kernels.ssd.ops import ssd
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n = 1, 256, 64, 64, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.0))
    bb = jax.random.normal(ks[3], (b, s, g, n), jnp.float32) * 0.3
    cc = jax.random.normal(ks[4], (b, s, g, n), jnp.float32) * 0.3
    y, st = ssd(x, dt, a, bb, cc, chunk=128, interpret=True)
    y2, st2 = ssd_chunked(x, dt, a, bb, cc, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=2e-3,
                               rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2), atol=2e-3,
                               rtol=2e-3)


def _fixed_routing(params, cfg, chosen):
    """Weights whose router picks the experts ``chosen`` for every token:
    zero router weights (every score 0.5) and a bias that ranks them."""
    bias = np.zeros(cfg.moe.n_experts, np.float32)
    bias[list(chosen)] = 1.0

    def fix(path, a):
        name = path[-1].key
        if name == "router":
            return jnp.zeros_like(a)
        if name == "router_bias":
            return jnp.broadcast_to(jnp.asarray(bias, a.dtype), a.shape)
        return a
    return jax.tree_util.tree_map_with_path(fix, params)


def test_decode_counts_the_picks_and_suspend_the_state_bytes(cluster):
    """With every token routed to experts 0, 6 and 9 (expert 0 of them
    held, of 0-3), each decoded token makes 3 picks in each of the 4
    expert layers and 1 held pick; a suspend counts the state it releases
    by kind."""
    from repro.serve.engine import ServeEngine
    from repro.serve.sessions import state_bytes
    cfg = _smoke()
    rt = T.ModelRuntime(tp=1, max_seq=32, remat=False)
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg, rt)
    params = _fixed_routing(params, cfg, (0, 6, 9))
    eng = ServeEngine(cfg, rt, params, tiered=cluster.tiered)
    sm = cluster.sessions
    sm.start("s", eng)
    tok = eng.prefill(np.arange(8, dtype=np.int32)[None])
    eng.decode(tok, 5)
    eng.decode(tok, 2)
    reg = cluster.tiered.obs.registry
    assert reg.counter("serve.moe.picks").value == 7 * 4 * 3
    assert reg.counter("serve.moe.picks_held").value == 7 * 4
    assert reg.counter("serve.decode.host_syncs").value == 2
    kinds = state_bytes(jax.tree.map(np.asarray, eng.cache))
    kv = 2 * 32 * 2 * 16 * 2 * 2 + 2 * 32 * 4     # 2 attention layers
    assert kinds["kv"] == kv and kinds["recurrent"] > 0
    sm.suspend("s", wait=True)
    for kind, n in kinds.items():
        assert reg.counter(f"serve.session.state_bytes.{kind}").value == n
    sm.resume("s", eng)
    eng.decode(tok, 1)
    sm.end("s")


# ---- the benchmark configurations' groups and trees, as before -----------

MAMBA_LEAVES = {
    "['final_norm']['w']": (2048,),
    "['group0']['p0']['mixer']['a_log']": (64,),
    "['group0']['p0']['mixer']['conv_bc']": (256, 4),
    "['group0']['p0']['mixer']['conv_x']": (4096, 4),
    "['group0']['p0']['mixer']['d_skip']": (64,),
    "['group0']['p0']['mixer']['dt_bias']": (64,),
    "['group0']['p0']['mixer']['norm_w']": (64, 64),
    "['group0']['p0']['mixer']['w_out']": (64, 64, 2048),
    "['group0']['p0']['mixer']['wbc']": (2048, 256),
    "['group0']['p0']['mixer']['wdt']": (2048, 64),
    "['group0']['p0']['mixer']['wx']": (2048, 64, 64),
    "['group0']['p0']['mixer']['wz']": (2048, 64, 64),
    "['group0']['p0']['norm1']['w']": (2048,),
    "['in_embed']": (50432, 2048),
    "['out_embed']": (2048, 50432),
}
STAR_LEAVES = {
    "['final_norm']['b']": (6144,), "['final_norm']['w']": (6144,),
    "['group0']['p0']['mixer']['bk']": (4, 128),
    "['group0']['p0']['mixer']['bo']": (6144,),
    "['group0']['p0']['mixer']['bq']": (48, 128),
    "['group0']['p0']['mixer']['bv']": (4, 128),
    "['group0']['p0']['mixer']['wk']": (6144, 4, 128),
    "['group0']['p0']['mixer']['wo']": (48, 128, 6144),
    "['group0']['p0']['mixer']['wq']": (6144, 48, 128),
    "['group0']['p0']['mixer']['wv']": (6144, 4, 128),
    "['group0']['p0']['mlp']['b1']": (24576,),
    "['group0']['p0']['mlp']['b2']": (6144,),
    "['group0']['p0']['mlp']['w1']": (6144, 24576),
    "['group0']['p0']['mlp']['w2']": (24576, 6144),
    "['group0']['p0']['norm1']['b']": (6144,),
    "['group0']['p0']['norm1']['w']": (6144,),
    "['group0']['p0']['norm2']['b']": (6144,),
    "['group0']['p0']['norm2']['w']": (6144,),
    "['in_embed']": (49152, 6144), "['out_embed']": (6144, 49152),
}
FLOAT32 = ("['a_log']", "['dt_bias']")


@pytest.mark.parametrize("arch,layers,leaves", [
    ("mamba2-1.3b", 4, MAMBA_LEAVES), ("mamba2-1.3b", 48, MAMBA_LEAVES),
    ("starcoder2-15b", 10, STAR_LEAVES)])
def test_benchmark_configs_keep_their_groups_and_trees(arch, layers,
                                                       leaves):
    """The three benchmark configurations (mamba2-1.3b at 4 and 48 layers,
    starcoder2-15b at 10): one group of all layers, and the same leaves,
    shapes and dtypes as before hybrids and shared experts came in, so
    the benchmark draws their weights bit for bit as before."""
    cfg = dataclasses.replace(registry.get_config(arch), n_layers=layers)
    assert [(len(p), r) for p, r in cfg.groups] == [(1, layers)]
    shapes, _ = T.abstract_params(cfg, T.ModelRuntime(tp=1))
    got = {jax.tree_util.keystr(p): (a.shape, str(a.dtype)) for p, a in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    want = {k: ((layers,) + s if "group0" in k else s,
                "float32" if k.endswith(FLOAT32) else "bfloat16")
            for k, s in leaves.items()}
    assert got == want
