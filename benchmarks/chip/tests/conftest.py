"""Self-checks of the benchmark harness, on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip

A tiny benchmark (two-layer models at the program's smoke sizes, short
traffic) is written to a temporary root so that the harness runs end to
end in seconds."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

SSM = {"d_state": 16, "head_dim": 8, "n_groups": 1, "conv_width": 4,
       "chunk_size": 16, "expand": 2}
MAMBA = {"family": "ssm", "n_layers": 2, "d_model": 64, "vocab_size": 512,
         "mixer": "ssd", "mlp": "none", "norm": "rmsnorm", "ssm": SSM}
STAR = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 512,
        "mixer": "attn_global", "mlp": "gelu", "norm": "layernorm",
        "linear_bias": True, "rope_theta": 100000.0}
# a hybrid expert model at smoke sizes: an SSD layer with no MLP, then
# attention whose MLP is a mixture of experts (reference: hybrid_moe.py)
HYBRID = {"family": "hybrid", "n_layers": 3, "d_model": 64, "n_heads": 4,
          "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 512,
          "pattern": [{"mixer": "ssd", "mlp": "none"},
                      {"mixer": "attn_global", "mlp": "moe"}],
          "norm": "rmsnorm", "rope_theta": 10000.0, "ssm": SSM,
          "moe": {"n_experts": 4, "top_k": 2, "d_ff": 96,
                  "router_softcap": 0.0}}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_norm": 1.0, "warmup": 10,
       "moments_dtype": "float32"}
# limits for these sizes, from CPU readings of sound runs (loss 2e-4,
# leaf gaps under 2e-2, logit gap under 1e-2, the hybrid's under 2.5e-2
# over 12 seeds) and of the controls and faults (float8: loss 1.6e-3,
# grad 0.88, gap 0.36, the hybrid's gap 1.18 at seed 7; half batch: loss
# 1e-2; the hybrid's expert dropped, top_k one less and attention cache
# lost: gaps 0.81-3.15)
LIMITS = {"loss_rel": 8e-4, "grad_leaf_rel": 0.06, "update_leaf_rel": 0.08,
          "logit_gap": 0.06}


def hybrid_config():
    """The program config the ``tiny-hybrid`` file states."""
    from repro.configs.base import (LayerSpec, ModelConfig, MoEConfig,
                                    SSMConfig)
    m = HYBRID
    return ModelConfig(
        name="tiny-hybrid", family="hybrid", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"],
        pattern=tuple(LayerSpec(**s) for s in m["pattern"]),
        rope_theta=m["rope_theta"], ssm=SSMConfig(**SSM),
        moe=MoEConfig(capacity_factor=1.5, **m["moe"]))


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A benchmark root with one train and three serve cells at the
    program's smoke sizes; for the duration of the test the program's
    registry hands out its smoke configs and the hybrid expert config,
    and the hybrid's reference is read from this directory."""
    from harness import common
    from repro.configs import registry
    smoke = registry.get_smoke_config
    monkeypatch.setattr(registry, "get_config", lambda arch: hybrid_config()
                        if arch == "tiny-hybrid" else smoke(arch))
    beside = common.Cell.reference
    monkeypatch.setattr(common.Cell, "reference", lambda cell: (
        common.load_module(HERE / cell.config["reference"])
        if cell.config["reference"] == "hybrid_moe.py" else beside(cell)))
    _write(tmp_path / "configs/tiny-mamba.json", {
        "name": "tiny-mamba", "arch": "mamba2-1.3b",
        "reference": "mamba2.py", "precision": "bfloat16", "model": MAMBA,
        "optimizer": OPT, "limits": LIMITS})
    _write(tmp_path / "configs/tiny-star.json", {
        "name": "tiny-star", "arch": "starcoder2-15b",
        "reference": "starcoder2.py", "precision": "bfloat16",
        "model": STAR, "limits": LIMITS})
    _write(tmp_path / "configs/tiny-hybrid.json", {
        "name": "tiny-hybrid", "arch": "tiny-hybrid",
        "reference": "hybrid_moe.py", "precision": "bfloat16",
        "model": HYBRID, "limits": LIMITS})
    train = json.loads((BENCH / "traffic/train-ckpt.json").read_text())
    train.update(batch=4, seq=64, rows_per_shard=64, ckpt_every=4)
    _write(tmp_path / "traffic/train.json", train)
    for mix in ("serve-code", "serve-chat"):
        t = json.loads((BENCH / f"traffic/{mix}.json").read_text())
        t.update(prompt_buckets=[16, 32, 48], prompt_median=32,
                 max_context=160, max_seq=160, out_min=2, out_max=12,
                 out_median=5, rate_per_s=4.0, ssd_impl="jnp", sessions=4,
                 check_tokens=60)
        _write(tmp_path / f"traffic/{mix}.json", t)
    e2e = [{"name": "setup_s", "unit": "s"},
           {"name": "train_tokens_per_s", "unit": "tokens/s",
            "workloads": ["t-train"]},
           {"name": "tpot_ms", "unit": "ms",
            "workloads": ["t-code", "t-chat", "t-hybrid"]}]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-mamba", "file": "configs/tiny-mamba.json"},
                    {"name": "tiny-star", "file": "configs/tiny-star.json"},
                    {"name": "tiny-hybrid",
                     "file": "configs/tiny-hybrid.json"}],
        "workloads": [
            {"name": "t-train", "config": "tiny-mamba", "traffic": "train",
             "chips": 1},
            {"name": "t-code", "config": "tiny-star",
             "traffic": "serve-code", "chips": 1},
            {"name": "t-chat", "config": "tiny-mamba",
             "traffic": "serve-chat", "chips": 1},
            {"name": "t-hybrid", "config": "tiny-hybrid",
             "traffic": "serve-code", "chips": 1}],
        "end_to_end": e2e, "per_layer": []})
    return tmp_path


@pytest.fixture
def drive(tiny, monkeypatch):
    """Runs a tiny cell as run.py does past its chip check; returns the
    result line's fields."""
    import jax

    import run as bench
    from harness import common, peaks

    devices = jax.devices()
    monkeypatch.setitem(peaks.PEAKS, devices[0].device_kind, {
        "bf16_flops": 1.0, "hbm_bytes_s": 1.0})

    def go(workload: str, seconds: float = 2.0, seed: int = 2**31 + 11,
           trace: bool = False):
        cell = common.load_cell(workload, trace, root=tiny, bench=tiny)
        return bench.execute(cell, seed, seconds, trace, devices,
                             bench.Clock(devices))
    return go
