"""nemotron3-nano-30b-a3b — hybrid Mamba-2 / GQA / mixture of experts.

[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json] 52 blocks,
each one pre-normed mixer or one MLP with its own residual, in the
non-periodic order ``hybrid_override_pattern`` gives: M = Mamba-2 (64 heads
x 64, state 128, 8 groups, conv 4 with bias, chunk 128), E = a mixture of
128 relu^2 experts of width 1856 (top-6 by sigmoid score plus a correction
bias, gates renormalised and scaled by 2.5) plus one shared relu^2 expert
of width 3712, * = GQA attention (32 q / 2 kv heads of 128, no bias, no
positional encoding). d_model 2688, vocab 131072, untied embeddings,
RMSNorm eps 1e-5.

``nemotron3-nano-30b-a3b-ep16`` is one chip's share of a deployment in
which 16 chips share each expert layer (expert parallelism 16): this chip
holds experts 0-7 of each of the 23 expert layers and everything else
whole (Mamba, attention, embeddings and head data-parallel).
"""
import dataclasses

from repro.configs.base import (ATTN_GLOBAL, MIXER_NONE, MLP_MOE, MLP_NONE,
                                MLP_RELU2, ROUTER_SIGMOID_BIAS, SSD,
                                LayerSpec, ModelConfig, MoEConfig, SSMConfig)

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
SPECS = {"M": LayerSpec(mixer=SSD, mlp=MLP_NONE),
         "E": LayerSpec(mixer=MIXER_NONE, mlp=MLP_MOE),
         "*": LayerSpec(mixer=ATTN_GLOBAL, mlp=MLP_NONE)}
EP = 16                           # chips that share each expert layer


def _pattern(letters: str):
    return tuple(SPECS[c] for c in letters)


def config() -> ModelConfig:
    return ModelConfig(
        name="nemotron3-nano-30b-a3b",
        family="hybrid",
        n_layers=len(PATTERN),
        d_model=2688,
        n_heads=32,
        n_kv_heads=2,
        head_dim=128,
        d_ff=1856,
        vocab_size=131_072,
        pattern=_pattern(PATTERN),
        rope=False,
        rms_eps=1e-5,
        ssm=SSMConfig(d_state=128, head_dim=64, n_groups=8, conv_width=4,
                      chunk_size=128, expand=2, n_heads=64, conv_bias=True),
        moe=MoEConfig(n_experts=128, top_k=6, d_ff=1856,
                      router_softcap=0.0, router=ROUTER_SIGMOID_BIAS,
                      routed_scale=2.5, mlp=MLP_RELU2, shared_ff=3712),
    )


def ep16_config() -> ModelConfig:
    cfg = config()
    return dataclasses.replace(
        cfg, name="nemotron3-nano-30b-a3b-ep16",
        moe=dataclasses.replace(cfg.moe, n_held=cfg.moe.n_experts // EP))


def smoke_config() -> ModelConfig:
    """Every kind of block (M, E, *) at tiny widths, 4 of 16 experts held,
    in a non-periodic order that the groups cut into runs."""
    return ModelConfig(
        name="nemotron3-smoke",
        family="hybrid",
        n_layers=10,
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=32,
        vocab_size=512,
        pattern=_pattern("MEMEM*EME*"),
        rope=False,
        rms_eps=1e-5,
        ssm=SSMConfig(d_state=16, head_dim=8, n_groups=2, conv_width=4,
                      chunk_size=16, n_heads=8, conv_bias=True),
        moe=MoEConfig(n_experts=16, top_k=3, d_ff=32, router_softcap=0.0,
                      router=ROUTER_SIGMOID_BIAS, routed_scale=2.5,
                      mlp=MLP_RELU2, shared_ff=48, n_held=4),
    )
