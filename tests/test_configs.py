"""Config registry: every assigned arch resolves, with sane param counts."""
import pytest

from repro.configs import SHAPES, cells, get_config, get_smoke_config
from repro.configs.registry import ARCH_IDS

# advertised sizes (embeddings untied in our impl -> tolerance is generous)
EXPECT = {
    "recurrentgemma-9b": 9e9,
    "whisper-tiny": 39e6,
    "gemma2-9b": 9e9,
    "qwen2-72b": 72e9,
    "starcoder2-15b": 15e9,
    "deepseek-coder-33b": 33e9,
    "grok-1-314b": 314e9,
    "arctic-480b": 480e9,
    "mamba2-1.3b": 1.3e9,
    "internvl2-26b": 26e9,
    "nemotron3-nano-30b-a3b": 31.6e9,
    "nemotron3-nano-30b-a3b-ep16": 4.04e9,   # 8 of 128 experts held
}


def test_ten_archs():
    assert len(ARCH_IDS) == 12


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_config_loads(arch):
    cfg = get_config(arch)
    assert cfg.n_layers > 0 and cfg.d_model > 0 and cfg.vocab_size > 0
    smoke = get_smoke_config(arch)
    assert smoke.d_model <= 128


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_count_close(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    expect = EXPECT[arch]
    assert 0.5 * expect < n < 1.75 * expect, (arch, n, expect)


def test_cell_matrix_is_40():
    all_cells = list(cells())
    assert len(all_cells) == 48
    skipped = [c for c in all_cells if c.skip]
    runnable = [c for c in all_cells if not c.skip]
    # long_500k runs only for the sub-quadratic archs
    long_runs = [c for c in runnable if c.shape.name == "long_500k"]
    assert sorted(c.arch for c in long_runs) == ["mamba2-1.3b",
                                                 "recurrentgemma-9b"]
    assert len(skipped) == 10
    for c in skipped:
        assert c.shape.name == "long_500k"


def test_padded_vocab_divisible():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size


def test_padded_heads():
    cfg = get_config("deepseek-coder-33b")
    assert cfg.padded_heads(16) == 64  # 56 -> 64
    cfg = get_config("qwen2-72b")
    assert cfg.padded_heads(16) == 64  # already divisible


def test_moe_active_params():
    cfg = get_config("arctic-480b")
    assert cfg.active_param_count() < 0.1 * cfg.param_count()
    dense = get_config("qwen2-72b")
    assert dense.active_param_count() == dense.param_count()
