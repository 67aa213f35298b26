"""The configuration door: the program config a file states, the weights
drawn for its tree, and its FLOP count, for layer patterns of several
kinds and expert layers. The registry's recurrentgemma-9b, arctic-480b
and grok-1-314b serve as fixtures here, never as configurations.

The three configurations of ``BENCHMARK.json`` are held against frozen
copies of the rules, draws and FLOP counts they were first measured with:
their weights must come out bit for bit and their counts number for
number the same."""
from __future__ import annotations

import copy
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, HYBRID, SSM, hybrid_config
from harness import flops, model, weights

CONFIGS = ["mamba2-1.3b-4l", "starcoder2-15b-10l", "mamba2-1.3b"]

RGEMMA = {"family": "hybrid", "n_layers": 6, "d_model": 4096,
          "n_heads": 16, "n_kv_heads": 1, "head_dim": 256, "d_ff": 12288,
          "vocab_size": 256000,
          "pattern": [{"mixer": "rglru", "mlp": "geglu"},
                      {"mixer": "rglru", "mlp": "geglu"},
                      {"mixer": "attn_local", "mlp": "geglu"}],
          "window": 2048, "rglru": {"width": 4096, "conv_width": 4}}
ARCTIC = {"family": "moe", "n_layers": 2, "d_model": 7168, "n_heads": 56,
          "n_kv_heads": 8, "head_dim": 128, "d_ff": 4864,
          "vocab_size": 32000,
          "pattern": [{"mixer": "attn_global", "mlp": "moe",
                       "dense_residual": True}],
          "moe": {"n_experts": 128, "top_k": 2, "capacity_factor": 1.25}}
GROK = {"family": "moe", "n_layers": 3, "d_model": 6144, "n_heads": 48,
        "n_kv_heads": 8, "head_dim": 128, "d_ff": 32768,
        "vocab_size": 131072, "mixer": "attn_global", "mlp": "moe",
        "attn_softcap": 30.0, "final_softcap": 30.0,
        "moe": {"n_experts": 8, "top_k": 2, "router_softcap": 30.0}}
FIXTURES = {"recurrentgemma-9b": RGEMMA, "arctic-480b": ARCTIC,
            "grok-1-314b": GROK}


def _file(arch: str, m: dict) -> dict:
    return {"name": f"{arch}-fixture", "arch": arch,
            "precision": "bfloat16", "model": copy.deepcopy(m)}


def _config_file(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# ---- program_config --------------------------------------------------------

@pytest.mark.parametrize("arch", sorted(FIXTURES))
def test_program_config_takes_a_stated_pattern_and_moe_group(arch):
    cfg = model.program_config(_file(arch, FIXTURES[arch]))
    assert cfg.n_layers == FIXTURES[arch]["n_layers"]
    assert len(model.layers(FIXTURES[arch])) == cfg.n_layers
    specs = [s for period, reps in cfg.groups for s in period * reps]
    assert [dataclasses.asdict(s) for s in specs] == model.layers(
        FIXTURES[arch])


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_takes_the_benchmark_files(name):
    cj = _config_file(name)
    cfg = model.program_config(cj)
    assert cfg.n_layers == cj["model"]["n_layers"]


def _edit(path, value):
    def go(m):
        *parents, last = path
        for p in parents:
            m = m[p]
        if value is None:
            del m[last]
        else:
            m[last] = value
    return go


@pytest.mark.parametrize("arch,edit,named", [
    ("recurrentgemma-9b", _edit(["pattern", 2, "mixer"], "attn_global"),
     "pattern[2].mixer"),
    ("recurrentgemma-9b", _edit(["pattern", 1, "mlp"], "gelu"),
     "pattern[1].mlp"),
    ("recurrentgemma-9b", _edit(["pattern", 2, "window"], 16),
     "pattern[2].window"),
    ("recurrentgemma-9b", _edit(["pattern"], [{"mixer": "rglru",
                                               "mlp": "geglu"}]),
     "pattern: program 3 layer specs"),
    ("arctic-480b", _edit(["pattern", 0, "dense_residual"], None),
     "pattern[0].dense_residual"),
    ("arctic-480b", _edit(["moe", "n_experts"], 64), "moe.n_experts"),
    ("grok-1-314b", _edit(["moe", "top_k"], 3), "moe.top_k"),
    ("grok-1-314b", _edit(["moe", "shared_d_ff"], 8192),
     "moe.shared_d_ff: the program has no field"),
    ("grok-1-314b", _edit(["head_dim"], 64), "head_dim"),
    ("grok-1-314b", _edit(["sliding"], 4096),
     "sliding: the program has no field"),
    ("grok-1-314b", _edit(["ssm"], dict(SSM)), "ssm: the program has no"),
])
def test_program_config_refuses_naming_the_difference(arch, edit, named):
    m = copy.deepcopy(FIXTURES[arch])
    edit(m)
    with pytest.raises(ValueError) as err:
        model.program_config(_file(arch, m))
    assert named in str(err.value)


def test_program_config_refuses_a_pattern_beside_a_mixer():
    m = dict(GROK, pattern=[{"mixer": "attn_global", "mlp": "moe"}])
    with pytest.raises(ValueError, match="both a pattern"):
        model.program_config(_file("grok-1-314b", m))


def test_program_config_refuses_another_precision():
    cj = _config_file("mamba2-1.3b")
    cj["precision"] = "float32"
    with pytest.raises(ValueError, match="precision"):
        model.program_config(cj)


def test_a_hybrid_expert_file_passes_every_door(monkeypatch):
    """program_config, weights.make and flops take the tiny hybrid expert
    file with no edit to the harness (the serve driver runs it in
    test_correct.py)."""
    from repro.configs import registry
    from repro.models import transformer as tfm
    monkeypatch.setattr(registry, "get_config", lambda arch: hybrid_config())
    cfg = model.program_config(_file("tiny-hybrid", HYBRID))
    shapes, _ = tfm.abstract_params(cfg, tfm.ModelRuntime(tp=1))
    w = weights.make(5, shapes)
    assert w["group0"]["p1"]["mlp"]["wi"].shape == (1, 1, 4, 64, 96)
    assert w["group1"]["p0"]["mixer"]["wx"].shape[0] == 1
    assert flops.prefill_flops(HYBRID, 32) > 0


# ---- weights ---------------------------------------------------------------

def _tree(cfg):
    from repro.models import transformer as tfm
    return tfm.abstract_params(cfg, tfm.ModelRuntime(tp=1))[0]


def _std(a) -> float:
    return float(np.std(np.asarray(a, np.float32)))


def test_expert_leaves_are_drawn_by_the_width_they_contract():
    from repro.configs import registry
    cfg = dataclasses.replace(registry.get_smoke_config("arctic-480b"),
                              d_model=256, d_ff=384)
    w = weights.make(2**31 + 3, _tree(cfg))
    layer = w["group0"]["p0"]
    want = {("mlp", "router"): 256, ("mlp", "wi"): 256, ("mlp", "wg"): 256,
            ("mlp", "wo"): 384, ("dense_mlp", "w1"): 256,
            ("dense_mlp", "w3"): 256, ("dense_mlp", "w2"): 384,
            ("mixer", "wo"): cfg.n_heads * cfg.head_dim}
    for (parent, leaf), fan in want.items():
        got = _std(layer[parent][leaf])
        assert got == pytest.approx(1 / math.sqrt(fan), rel=0.05), \
            (parent, leaf, got, fan)


def _rglru_rules():
    return {"w_in": ("fan_in", 1), "w_gate": ("fan_in", 1),
            "conv_w": ("conv", 0), "conv_b": ("bias", 0),
            "bd_a": ("expert", 0), "bd_x": ("expert", 0),
            "bd_a_bias": ("bias", 0), "bd_x_bias": ("bias", 0),
            "lam": ("bias", 0)}


def test_a_leaf_with_no_rule_fails_naming_it_and_its_path():
    from repro.configs import registry
    shapes = _tree(registry.get_smoke_config("recurrentgemma-9b"))
    with pytest.raises(KeyError, match="'bd_a' at group0/p0/mixer/bd_a"):
        weights.make(1, shapes)


def test_weight_rules_of_a_reference_add_leaves():
    from repro.configs import registry
    cfg = dataclasses.replace(
        registry.get_smoke_config("recurrentgemma-9b"), d_model=256)
    w = weights.make(1, _tree(cfg), rules=_rglru_rules())
    mixer = w["group0"]["p0"]["mixer"]
    width = cfg.rglru.width
    assert _std(mixer["w_out"]) == pytest.approx(1 / math.sqrt(width),
                                                 rel=0.05)
    assert _std(mixer["w_in"]) == pytest.approx(1 / math.sqrt(256),
                                                rel=0.05)
    assert _std(mixer["bd_a"]) == pytest.approx(
        1 / math.sqrt(mixer["bd_a"].shape[-2]), rel=0.05)


@pytest.mark.parametrize("key", ["wx", "mixer/wx", "mlp/wi"])
def test_weight_rules_may_not_replace_the_harness_rules(key):
    """A leaf the table draws, by its name or by its place, is refused
    when the reference names it too."""
    from repro.configs import registry
    cfg = registry.get_smoke_config("mamba2-1.3b") if key != "mlp/wi" \
        else registry.get_smoke_config("grok-1-314b")
    with pytest.raises(ValueError, match="only add leaves"):
        weights.builder(_tree(cfg), rules={key: ("fan_in", 2)})


# ---- the parent's rules, draws and counts, frozen --------------------------

PARENT_RULES = {
    "in_embed": ("embed", 0), "out_embed": ("fan_in", 1),
    "wq": ("fan_in", 1), "wk": ("fan_in", 1), "wv": ("fan_in", 1),
    "wo": ("fan_in", 2),
    "w1": ("fan_in", 1), "w2": ("fan_in", 1), "w3": ("fan_in", 1),
    "wz": ("fan_in", 1), "wx": ("fan_in", 1), "wbc": ("fan_in", 1),
    "wdt": ("fan_in", 1), "w_out": ("fan_in", 2),
    "conv_x": ("conv", 0), "conv_bc": ("conv", 0),
    "a_log": ("ssm_a", 0), "dt_bias": ("ssm_dt", 0),
    "d_skip": ("gain", 0), "norm_w": ("gain", 0), "w": ("gain", 0),
    "b": ("bias", 0), "b1": ("bias", 0), "b2": ("bias", 0),
    "bq": ("bias", 0), "bk": ("bias", 0), "bv": ("bias", 0),
    "bo": ("bias", 0),
}


def _parent_leaf(key, name, shape, dtype, stacked):
    rule, fan_dims = PARENT_RULES[name]
    lead = 1 if stacked else 0
    if rule == "embed":
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif rule == "fan_in":
        fan = math.prod(shape[lead:lead + fan_dims])
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan)
    elif rule == "conv":
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-1])
    elif rule == "ssm_a":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif rule == "ssm_dt":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif rule == "gain":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def _parent_builder(shapes):
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(paths):
            names = [getattr(k, "key", None) for k in path]
            stacked = any(isinstance(n, str) and n.startswith("group")
                          for n in names)
            out.append(_parent_leaf(jax.random.fold_in(key, i), names[-1],
                                    sd.shape, sd.dtype, stacked))
        return jax.tree_util.tree_unflatten(treedef, out)
    return build


def _parent_ssm_dims(m):
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    return {"d_inner": d_inner, "h": d_inner // s["head_dim"],
            "p": s["head_dim"], "g": s["n_groups"], "n": s["d_state"],
            "q": s["chunk_size"], "cw": s["conv_width"]}


def _parent_ssd_chunk_flops(m, seq):
    d = _parent_ssm_dims(m)
    q = min(d["q"], -(-seq // 8) * 8)
    chunks = -(-seq // q)
    per_chunk = 2 * q * (q * d["n"] + q * d["p"] + 2 * d["n"] * d["p"])
    return float(chunks * per_chunk * d["h"])


def _parent_layer_matmul_params(m):
    dm = m["d_model"]
    if m["family"] == "ssm":
        d = _parent_ssm_dims(m)
        return float(dm * (2 * d["d_inner"] + 2 * d["g"] * d["n"] + d["h"])
                     + d["d_inner"] * dm)
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    mlp = {"gelu": 2, "swiglu": 3, "geglu": 3}[m["mlp"]]
    return float(dm * h * dh + 2 * dm * kv * dh + h * dh * dm
                 + mlp * dm * m["d_ff"])


def _parent_layer_extra_flops(m, seq, ctx_sum):
    if m["family"] == "ssm":
        d = _parent_ssm_dims(m)
        conv = 2 * d["cw"] * (d["d_inner"] + 2 * d["g"] * d["n"]) * seq
        return _parent_ssd_chunk_flops(m, seq) + conv
    return 4.0 * m["n_heads"] * m["head_dim"] * ctx_sum


def _parent_prefill_flops(m, seq):
    ctx = seq * (seq + 1) / 2.0
    per_layer = 2 * _parent_layer_matmul_params(m) * seq + \
        _parent_layer_extra_flops(m, seq, ctx)
    return m["n_layers"] * per_layer + 2.0 * m["d_model"] * m["vocab_size"]


def _parent_train_flops_per_token(m, seq):
    ctx = seq * (seq + 1) / 2.0
    fwd = m["n_layers"] * (2 * _parent_layer_matmul_params(m) * seq +
                           _parent_layer_extra_flops(m, seq, ctx)) / seq
    fwd += 2.0 * m["d_model"] * m["vocab_size"]
    return 3.0 * fwd


@pytest.mark.parametrize("name", CONFIGS)
def test_flop_counts_are_the_parents(name):
    cj = _config_file(name)
    m = cj["model"]
    for seq in (1, 7, 100, 256, 512, 1000, 1024, 2048, 3584, 4096, 8192):
        assert flops.prefill_flops(m, seq) == _parent_prefill_flops(m, seq)
        assert flops.train_flops_per_token(m, seq) == \
            _parent_train_flops_per_token(m, seq)
        if "ssm" in m:
            assert flops.ssd_chunk_flops(m, seq) == \
                _parent_ssd_chunk_flops(m, seq)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_parents_bit_for_bit(name):
    """Each leaf of the file's tree, cut to one layer, drawn from one
    seed by the harness and by the parent's frozen copy (one leaf per
    compiled program, so that a whole tree is never held twice)."""
    cj = _config_file(name)
    cj["model"]["n_layers"] = 1
    shapes = _tree(model.program_config(cj))
    new, old = weights.builder(shapes), _parent_builder(shapes)
    key = weights.seed_key(2**31 + 977)
    n = len(jax.tree.leaves(shapes))
    for j in range(n):
        a, b = (np.asarray(jax.jit(lambda k, f=f: jax.tree.leaves(f(k))[j])(
            key)) for f in (new, old))
        assert a.dtype == b.dtype and a.shape == b.shape
        width = f"u{a.dtype.itemsize}"
        assert np.array_equal(a.view(width), b.view(width)), \
            jax.tree_util.keystr(jax.tree_util.tree_flatten_with_path(
                shapes)[0][j][0])
        del a, b


# ---- FLOPs by layer kind ---------------------------------------------------

def test_hybrid_expert_flops_by_hand():
    s, d, h, kv, dh, f, e, k = 24, 64, 4, 2, 16, 96, 4, 2
    ssd = 2 * flops._mixer_params(HYBRID, "ssd") * s + \
        flops._mixer_extra(HYBRID, "ssd", s)
    attn = 2 * (d * h * dh + 2 * d * kv * dh + h * dh * d) * s + \
        sum(4 * h * dh * (i + 1) for i in range(s))
    router, expert = d * e, 3 * d * f
    moe = 2 * (router + k * expert) * s
    head = 2 * d * HYBRID["vocab_size"]
    # three layers: ssd, attention with experts, ssd
    assert flops.prefill_flops(HYBRID, s) == 2 * ssd + attn + moe + head


def test_dense_residual_counted_beside_the_experts():
    d, f, ff, e, k = 64, 96, 128, 8, 2
    m = {"n_layers": 1, "d_model": d, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": ff, "vocab_size": 512,
         "pattern": [{"mixer": "none", "mlp": "moe",
                      "dense_residual": True}],
         "moe": {"n_experts": e, "top_k": k, "d_ff": f,
                 "capacity_factor": 1.25}}
    per_tok = d * e + k * 3 * d * f + 3 * d * ff
    assert flops.prefill_flops(m, 10) == 2 * per_tok * 10 + 2 * d * 512


def test_local_attention_counts_its_window():
    m = dict(RGEMMA, pattern=[{"mixer": "attn_local", "mlp": "none"}],
             n_layers=1, window=4)
    del m["rglru"]
    dense = dict(m, pattern=[{"mixer": "attn_global", "mlp": "none"}])
    h, dh = m["n_heads"], m["head_dim"]
    seq = 10
    ctx = sum(min(i + 1, 4) for i in range(seq))
    full = seq * (seq + 1) // 2
    assert flops.prefill_flops(dense, seq) - flops.prefill_flops(m, seq) \
        == 4 * h * dh * (full - ctx)


@pytest.mark.parametrize("where,edit", [
    ("ssm.n_heads", lambda m: m["ssm"].update(n_heads=4)),
    ("moe.shared_d_ff", lambda m: m["moe"].update(shared_d_ff=192)),
    ("moe.mlp", lambda m: m["moe"].update(mlp="swiglu")),
    ("pattern.window", lambda m: m["pattern"][1].update(window=8)),
    ("rglru", lambda m: m.update(rglru={"width": 64})),
])
def test_flops_refuse_a_key_they_do_not_count(where, edit):
    """A stated size the count neither reads nor knows to change no
    matmul is refused, naming it, rather than left out of the count."""
    m = copy.deepcopy(HYBRID)
    edit(m)
    with pytest.raises(ValueError, match=f"'{where}'"):
        flops.prefill_flops(m, 16)
    with pytest.raises(ValueError, match=f"'{where}'"):
        flops.train_flops_per_token(m, 16)


@pytest.mark.parametrize("kind,spec", [
    ("mixer 'rglru'", {"mixer": "rglru", "mlp": "none"}),
    ("mlp 'relu2'", {"mixer": "none", "mlp": "relu2"}),
])
def test_flops_refuse_a_layer_kind_they_do_not_count(kind, spec):
    m = dict(HYBRID, pattern=[spec])
    with pytest.raises(ValueError, match=f"no FLOP count for {kind}"):
        flops.prefill_flops(m, 16)
