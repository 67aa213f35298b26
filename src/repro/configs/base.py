"""Configuration dataclasses for models, shapes, meshes and runs.

Every assigned architecture is expressed as a ``ModelConfig`` built out of a
repeating ``block pattern`` of (mixer, mlp) layer specs, which is what lets a
single transformer implementation cover dense / GQA / MoE / SSM / hybrid /
encoder-decoder families while still compiling to a compact scan-over-layers
HLO.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Layer specs
# ---------------------------------------------------------------------------

# mixer kinds
ATTN_GLOBAL = "attn_global"      # full (causal for decoder) attention
ATTN_LOCAL = "attn_local"        # sliding-window attention
RGLRU = "rglru"                  # RG-LRU recurrent block (RecurrentGemma)
SSD = "ssd"                      # Mamba2 state-space-duality block
MIXER_NONE = "none"              # no mixer (an MLP-only block)

# mlp kinds
MLP_GELU = "gelu"                # plain 2-matmul MLP
MLP_SWIGLU = "swiglu"            # gated 3-matmul MLP (llama-style)
MLP_GEGLU = "geglu"              # gated with gelu (gemma-style)
MLP_MOE = "moe"                  # mixture-of-experts FFN
MLP_RELU2 = "relu2"              # 2-matmul MLP, down(relu(up x)^2)
MLP_NONE = "none"                # no MLP (mamba2 blocks are mixer-only)

# expert routers
ROUTER_SOFTMAX = "softmax"       # top-k of softmax(logits), renormalised
# top-k of sigmoid(logits) + a correction bias (the bias takes part in the
# choice only); the chosen sigmoid scores are renormalised and scaled by
# ``routed_scale``
ROUTER_SIGMOID_BIAS = "sigmoid_bias"


@dataclass(frozen=True)
class LayerSpec:
    mixer: str = ATTN_GLOBAL
    mlp: str = MLP_SWIGLU
    # MoE-with-parallel-dense-residual (snowflake-arctic style)
    dense_residual: bool = False


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    d_ff: int = 0                 # expert hidden size (0 -> ModelConfig.d_ff)
    router_softcap: float = 30.0  # grok-style router logit cap (0 = off)
    router: str = ROUTER_SOFTMAX
    routed_scale: float = 1.0     # factor on the routed experts' sum
    mlp: str = MLP_SWIGLU         # expert MLP kind: swiglu | relu2
    shared_ff: int = 0            # hidden width of one shared expert (0: none)
    # expert parallelism: this chip holds experts [0, n_held) of the
    # n_experts the router scores (0 -> all of them)
    n_held: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64            # P
    n_groups: int = 1             # B/C groups
    conv_width: int = 4
    chunk_size: int = 256
    expand: int = 2               # d_inner = expand * d_model
    n_heads: int = 0              # d_inner = n_heads * head_dim (0: expand)
    conv_bias: bool = False       # a bias on the causal conv's channels

    def d_inner(self, d_model: int) -> int:
        return self.n_heads * self.head_dim if self.n_heads \
            else self.expand * d_model


@dataclass(frozen=True)
class RGLRUConfig:
    width: int = 0                # recurrent width (0 -> d_model)
    conv_width: int = 4
    block_width: int = 256        # kernel scan block


# longest period whose layers a scan body unrolls (see ModelConfig.groups)
MAX_PERIOD = 8


def _runs(specs: Sequence[LayerSpec]
          ) -> Tuple[Tuple[Tuple[LayerSpec, ...], int], ...]:
    """``specs`` as (period, repeats) runs, greedily from the front."""
    out, loose, i, n = [], [], 0, len(specs)

    def repeats(at: int, p: int) -> int:
        r = 1
        while at + (r + 1) * p <= n and \
                specs[at + r * p:at + (r + 1) * p] == specs[at:at + p]:
            r += 1
        return r

    while i < n:
        best = max(((repeats(i, p), p) for p in range(1, MAX_PERIOD + 1)
                    if i + p <= n),
                   key=lambda rp: (rp[1] * rp[0] * (rp[0] > 1), -rp[1]))
        reps, p = best
        if reps < 2:
            loose.append(specs[i])
            i += 1
            if len(loose) == MAX_PERIOD:
                out.append((tuple(loose), 1))
                loose = []
            continue
        if loose:
            out.append((tuple(loose), 1))
            loose = []
        out.append((tuple(specs[i:i + p]), reps))
        i += p * reps
    if loose:
        out.append((tuple(loose), 1))
    return tuple(out)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense|moe|ssm|hybrid|audio|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    # attention details
    window: int = 4096            # sliding window for ATTN_LOCAL
    rope_theta: float = 10_000.0
    rope: bool = True             # rotary positions (False: none at all)
    qkv_bias: bool = False
    linear_bias: bool = False     # biases on all projections (starcoder2/whisper)
    attn_softcap: float = 0.0     # gemma2: 50.0
    final_softcap: float = 0.0    # gemma2: 30.0
    post_norms: bool = False      # gemma2 sandwich norms
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    rms_eps: float = 1e-6         # epsilon of every RMSNorm, gated ones too
    # sub-configs
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    # encoder-decoder (whisper)
    enc_dec: bool = False
    n_enc_layers: int = 0
    # multimodal prefix stub (vlm / audio frontends)
    prefix_len: int = 0           # precomputed embeddings prepended to tokens
    # numerics
    param_dtype: str = "bfloat16"
    # vocab padding granularity for TP
    vocab_pad_to: int = 256
    # whether long_500k applies (sub-quadratic decoders only)
    subquadratic: bool = False
    tie_embeddings: bool = False  # documented deviation: we always untie

    # ----- derived -----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        g = self.vocab_pad_to
        return (self.vocab_size + g - 1) // g * g

    def padded_heads(self, model_par: int) -> int:
        """Q heads zero-padded up to a multiple of the TP degree."""
        return (self.n_heads + model_par - 1) // model_par * model_par

    @property
    def rotary_theta(self) -> float:
        """The base of the rotary positions, 0 where attention has none."""
        return self.rope_theta if self.rope else 0.0

    @property
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """The spec of each of the ``n_layers`` layers, in order."""
        p = self.pattern
        return tuple(p[i % len(p)] for i in range(self.n_layers))

    @property
    def groups(self) -> Tuple[Tuple[Tuple[LayerSpec, ...], int], ...]:
        """Split n_layers into (period, repeats) groups; scan runs over
        repeats with the period body unrolled.

        A pattern of at most ``MAX_PERIOD`` specs is one group of whole
        periods plus an optional tail period. A longer pattern (one given
        layer by layer, such as a hybrid's non-periodic sequence) is cut
        into runs: at each layer the repeated period of at most
        ``MAX_PERIOD`` layers that covers the most layers, with layers
        that start no repeat gathered into a group of their own.
        """
        p = len(self.pattern)
        if p <= MAX_PERIOD:
            reps, tail = divmod(self.n_layers, p)
            out = []
            if reps:
                out.append((tuple(self.pattern), reps))
            if tail:
                out.append((tuple(self.pattern[:tail]), 1))
            return tuple(out)
        return _runs(self.layer_specs)

    def param_count(self, model_par: int = 1, padded: bool = False) -> int:
        """Analytic parameter count (used for 6·N·D model-FLOPs roofline).

        With ``padded=True`` counts the physically-materialized (head/vocab
        padded) parameters instead of the logical ones.
        """
        d, dh = self.d_model, self.resolved_head_dim
        hq = self.padded_heads(model_par) if padded else self.n_heads
        hkv = self.n_kv_heads
        v = self.padded_vocab if padded else self.vocab_size
        total = 2 * v * d  # untied in+out embeddings
        nm = {MLP_GELU: 2, MLP_SWIGLU: 3, MLP_GEGLU: 3, MLP_RELU2: 2}
        for s in self.layer_specs:
            if s.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
                total += d * hq * dh + 2 * d * hkv * dh + hq * dh * d
            elif s.mixer == RGLRU:
                w = (self.rglru.width or d) if self.rglru else d
                total += 2 * d * w + w * d + w * self.rglru.conv_width + 3 * w
            elif s.mixer == SSD:
                sc = self.ssm
                dinner = sc.d_inner(d)
                h = dinner // sc.head_dim
                conv_ch = dinner + 2 * sc.n_groups * sc.d_state
                total += d * (2 * dinner + 2 * sc.n_groups * sc.d_state + h)
                total += conv_ch * (sc.conv_width + sc.conv_bias)
                total += 2 * h + dinner * d
            if s.mlp == MLP_MOE:
                mo = self.moe
                total += mo.held * self.expert_params + d * mo.n_experts
                total += mo.n_experts * (mo.router == ROUTER_SIGMOID_BIAS)
                total += nm[mo.mlp] * d * mo.shared_ff
            elif s.mlp != MLP_NONE:
                total += nm[s.mlp] * d * self.d_ff
            if s.dense_residual:
                total += 3 * d * self.d_ff
            total += 2 * d  # norms
        if self.enc_dec:
            # encoder layers: self-attn + mlp ; decoder adds cross-attn
            enc = self.n_enc_layers * (2 * (d * hq * dh + 2 * d * hkv * dh + hq * dh * d) * 0 + 0)
            # counted explicitly below for clarity
            per_enc = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 2 * d * self.d_ff + 2 * d
            per_cross = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + d
            total += self.n_enc_layers * per_enc + self.n_layers * per_cross
        return int(total)

    @property
    def expert_params(self) -> int:
        """Parameters of one routed expert."""
        mo = self.moe
        mats = 2 if mo.mlp == MLP_RELU2 else 3
        return mats * self.d_model * (mo.d_ff or self.d_ff)

    @property
    def n_moe_layers(self) -> int:
        return sum(s.mlp == MLP_MOE for s in self.layer_specs)

    def active_param_count(self, model_par: int = 1) -> int:
        """Active params per token (MoE: top_k of n_experts) for 6·N_active·D.
        Of the experts held here a token activates top_k * held /
        n_experts on average."""
        if self.moe is None:
            return self.param_count(model_par)
        mo = self.moe
        idle = mo.held - mo.top_k * mo.held / mo.n_experts
        inactive = self.n_moe_layers * idle * self.expert_params
        return int(self.param_count(model_par) - inactive)


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------

TRAIN, PREFILL, DECODE = "train", "prefill", "decode"


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == DECODE


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, TRAIN),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, PREFILL),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, DECODE),
    "long_500k": ShapeConfig("long_500k", 524288, 1, DECODE),
}


# ---------------------------------------------------------------------------
# Run / parallelism config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    dp: int = 1                   # data axis
    tp: int = 1                   # model axis
    pods: int = 1                 # pod axis (DP by default, PP optional)
    pod_role: str = "dp"          # dp | pp
    seq_parallel: bool = False    # shard residual stream on seq over model
    microbatches: int = 1         # gradient-accumulation splits
    remat: str = "block"          # none | block (remat each layer body)
    zero1: bool = True            # shard optimizer moments over dp
    grad_compression: str = "none"  # none | int8ef
    moe_impl: str = "etp"         # etp | gshard (dense fallback)
    attn_impl: str = "blockwise"  # naive | blockwise | pallas | interpret
    ce_chunk: int = 512           # chunked cross-entropy sequence block


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    parallel: ParallelConfig = ParallelConfig()
    seed: int = 0
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    opt_moments_dtype: str = "float32"   # float32 | int8 (blockwise-quantized)
    master_weights: bool = False
