"""The nemotron3-nano-30b-a3b-ep16 configuration through the harness's
doors, on the CPU: its file as the program config, every leaf of its tree
drawn by a rule, the decode-roofline byte count against the tree, and its
serve cell end to end at the program's smoke sizes (the file with each
size the smoke config's, 10 layers of every kind), with each fault the
cell can have planted under the timed path and the float8 control, all of
which ``logit_gap_mean`` must catch (``harness/serve_routed.py``)."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, _write
from harness import common, model, weights

ARCH = "nemotron3-nano-30b-a3b-ep16"
FILE = BENCH / "configs" / f"{ARCH}.json"
# logit_gap_mean at these sizes (CPU readings, 240 checked tokens): sound
# 0.0022-0.0405 over 9 seeds (1-8 and the tests' seed 2**31 + 11, 0.033);
# the float8 control 0.176-0.294 at the same seeds; planted faults at the
# tests' seed and seed 8: KV cache lost on resume 0.080 / 0.103, SSM state
# lost 0.348 / 0.491, an expert dropped 0.305 / 0.481, top_k one less
# 0.399 / 0.513, routed scale left out 0.377 / 0.452
LIMIT = 0.06
# the cell's traffic at smoke sizes
TRAFFIC = dict(prompt_buckets=[16, 32, 48], prompt_median=32,
               max_context=320, max_seq=320, out_min=4, out_max=32,
               out_median=16, rate_per_s=4.0, ssd_impl="jnp", sessions=4,
               check_tokens=240)
SECONDS = 4.0


def _config_file() -> dict:
    return json.loads(FILE.read_text())


def _smoke():
    from repro.configs import registry
    return registry.get_smoke_config("nemotron3-nano-30b-a3b")


def _smoke_file() -> dict:
    """The configuration file with each size of its model group the smoke
    config's: the same keys, the pattern of the smoke's 10 layers."""
    cj = _config_file()
    cfg = _smoke()
    m = cj["model"]
    for key in m:
        if key in ("ssm", "moe"):
            sub = dataclasses.asdict(getattr(cfg, key))
            m[key] = {k: sub[k] for k in m[key]}
        elif key == "pattern":
            m[key] = [{"mixer": s.mixer, "mlp": s.mlp} for s in cfg.pattern]
        else:
            m[key] = getattr(cfg, key)
    cj["name"] = "tiny-nemotron"
    cj["limits"] = {"logit_gap_mean": LIMIT}
    return cj


def _shapes(cfg):
    from repro.models import transformer as tfm
    return tfm.abstract_params(cfg, tfm.ModelRuntime(tp=1))[0]


@pytest.mark.parametrize("n_layers", [7, 17, 52])
def test_the_file_is_the_program_config_at_any_depth(n_layers):
    cj = _config_file()
    cj["model"]["n_layers"] = n_layers
    cfg = model.program_config(cj)
    specs = [dataclasses.asdict(s) for s in cfg.layer_specs]
    assert specs == model.layers(cj["model"])
    assert [s for p, r in cfg.groups for s in p * r] == list(cfg.layer_specs)


def test_every_leaf_is_drawn_by_a_rule():
    """The whole tree's rules resolve (the table's, and the reference's
    WEIGHT_RULES for the router bias and the conv biases), on shapes
    alone; one expert layer and one Mamba layer are drawn and their new
    leaves are as the rules say."""
    ref = common.load_module(BENCH / "configs" / "nemotron_h.py")
    cfg = model.program_config(_config_file())
    weights.builder(_shapes(cfg), ref.WEIGHT_RULES)
    small = dataclasses.replace(cfg, n_layers=2, d_model=256,
                                vocab_size=512)
    w = weights.make(5, _shapes(small), rules=ref.WEIGHT_RULES)
    mix, mlp = w["group0"]["p0"]["mixer"], w["group0"]["p1"]["mlp"]
    assert mlp["wi"].shape == (1, 1, 8, 256, 1856)
    assert mlp["shared"]["w1"].shape == (1, 256, 3712)
    assert mlp["router"].shape == (1, 256, 128)
    for leaf in (mix["conv_x_b"], mix["conv_bc_b"], mlp["router_bias"]):
        std = float(jnp.std(leaf.astype(jnp.float32)))
        assert 0.015 < std < 0.025, std


def test_decode_bytes_are_the_trees_but_embedding_and_routed_experts():
    """decode_roofline's count from the file equals the bytes of the
    program's tree without the input embedding and the routed experts."""
    reader = common.load_module(BENCH / "metrics" / "decode_roofline.py")
    cj = _config_file()
    want = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            _shapes(model.program_config(cj)))[0]:
        names = [k.key for k in path]
        if names[0] == "in_embed" or names[-2:] in (["mlp", "wi"],
                                                    ["mlp", "wo"]):
            continue
        want += leaf.size * 2
    assert reader.token_bytes(cj["model"]) == want
    assert 3.70e9 < want < 3.71e9
    with pytest.raises(ValueError, match="moe.capacity_factor"):
        reader.token_bytes(dict(cj["model"], moe=dict(
            cj["model"]["moe"], capacity_factor=1.0)))
    with pytest.raises(ValueError, match="mixer 'rglru'"):
        reader.token_bytes(dict(cj["model"], n_layers=1,
                                pattern=[{"mixer": "rglru", "mlp": "none"}]))


# ---- the serve cell at smoke sizes ---------------------------------------

@pytest.fixture
def nemo(tmp_path, monkeypatch):
    """A benchmark root with the nemotron cell at smoke sizes; returns a
    function that runs it as run.py does past its chip check."""
    import run as bench
    from harness import peaks
    from repro.configs import registry
    real = registry.get_config
    monkeypatch.setattr(registry, "get_config", lambda arch: _smoke()
                        if arch == ARCH else real(arch))
    devices = jax.devices()
    monkeypatch.setitem(peaks.PEAKS, devices[0].device_kind, {
        "bf16_flops": 1.0, "hbm_bytes_s": 1.0})
    _write(tmp_path / "configs/tiny-nemotron.json", _smoke_file())
    t = json.loads((BENCH / "traffic/serve-chat-long.json").read_text())
    t.update(TRAFFIC)
    _write(tmp_path / "traffic/serve-chat-long.json", t)
    spec = json.loads((common.ROOT / "BENCHMARK.json").read_text())
    per_layer = [m for m in spec["per_layer"]
                 if m["name"] in ("decode_idle", "decode_roofline")]
    for m in per_layer:
        m["workloads"] = ["t-nemo"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-nemotron",
                     "file": "configs/tiny-nemotron.json"}],
        "workloads": [{"name": "t-nemo", "config": "tiny-nemotron",
                       "traffic": "serve-chat-long", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "tpot_ms", "unit": "ms"}],
        "per_layer": per_layer})

    def go(seed: int = 2**31 + 11, trace: bool = False, controls=()):
        cell = common.load_cell("t-nemo", trace, root=tmp_path,
                                bench=tmp_path)
        if controls:
            from harness import serve_routed
            return serve_routed.run(cell, seed, SECONDS, None,
                                    bench.Clock(devices), controls=controls)
        return bench.execute(cell, seed, SECONDS, trace, devices,
                             bench.Clock(devices))
    return go


def _compared(out):
    return [(c.name, c.value, c.limit) for c in out["compared"]]


def test_sound_run_is_correct(nemo):
    out = nemo()
    assert out["correct"], _compared(out)
    assert out["attempted"] > 0
    assert out["metrics"]["tpot_ms"]["value"] > 0


def test_traced_run_reads_the_decode_metrics(nemo):
    out = nemo(trace=True)
    assert out["correct"], _compared(out)
    assert set(out["metrics"]) == {"decode_idle", "decode_roofline"}


def _wrap_moe(monkeypatch, wrap):
    from repro.models import moe
    monkeypatch.setattr(moe, "apply_moe_gshard", wrap(moe.apply_moe_gshard))


def _expert_dropped(monkeypatch):
    _wrap_moe(monkeypatch, lambda fn: lambda p, x, cfg: fn(
        dict(p, wo=p["wo"].at[:, 0].set(0)), x, cfg))


def _with_moe(monkeypatch, **change):
    _wrap_moe(monkeypatch, lambda fn: lambda p, x, cfg: fn(
        p, x, dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, **{k: v(cfg.moe) for k, v in change.items()}))))


def _top_k_less(monkeypatch):
    _with_moe(monkeypatch, top_k=lambda mo: mo.top_k - 1)


def _routed_scale_left_out(monkeypatch):
    _with_moe(monkeypatch, routed_scale=lambda mo: 1.0)


def _resumed_leaves_lost(monkeypatch, leaves):
    from repro.serve.sessions import SessionManager
    orig = SessionManager.resume

    def resume(self, name, engine):
        orig(self, name, engine)
        engine.cache = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a)
            if path[-1].key in leaves else a, engine.cache)
    monkeypatch.setattr(SessionManager, "resume", resume)


def _kv_cache_lost(monkeypatch):
    _resumed_leaves_lost(monkeypatch, ("k", "v"))


def _ssm_state_lost(monkeypatch):
    _resumed_leaves_lost(monkeypatch, ("h", "conv"))


@pytest.mark.parametrize("fault", [_expert_dropped, _top_k_less,
                                   _routed_scale_left_out, _kv_cache_lost,
                                   _ssm_state_lost])
def test_fault_is_caught(nemo, monkeypatch, fault):
    fault(monkeypatch)
    out = nemo()
    assert not out["correct"], _compared(out)


def test_float8_control_fails(nemo):
    rec = nemo(seed=7, controls=("fp8",))
    assert rec["controls"]["fp8"]["logit_gap_mean"] > LIMIT
