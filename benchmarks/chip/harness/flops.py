"""Operations and bytes the algorithms need, from shapes alone.

A multiply-add counts as 2 FLOPs. Model FLOPs count each matmul once per
pass (training: forward + backward = 3 forward passes); recomputation
under remat is not counted. The sizes come from a configuration file's
``model`` group, layer by layer as its pattern gives them, so these
functions import nothing of the program.

The count knows the layers the program has: attention (global, and local
over its ``window``), the SSD mixer with inner width ``expand *
d_model``, MLPs of each kind, and the program's expert layer (a router
over ``moe.n_experts``, ``moe.top_k`` SwiGLU experts of width ``moe.d_ff``
per token, and a SwiGLU ``dense_residual`` MLP). A file that states a
key the count neither reads nor knows to change no matmul is refused,
naming the key: a size the count does not know cannot drift from the
program unseen.
"""
from __future__ import annotations

from typing import Dict, Optional

from harness.model import layers

# matmuls of width d_ff in an MLP of each kind
MLP_MATMULS = {"gelu": 2, "swiglu": 3, "geglu": 3}

# per group of the model group ("" the group itself, "pattern" each layer
# spec): the keys the count reads, and those that change no matmul
READS = {
    "": {"n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
         "vocab_size", "window", "mixer", "mlp", "pattern", "ssm", "moe"},
    "ssm": {"d_state", "head_dim", "n_groups", "conv_width", "chunk_size",
            "expand"},
    "moe": {"n_experts", "top_k", "d_ff"},
    "pattern": {"mixer", "mlp", "dense_residual"},
}
NO_MATMUL = {
    "": {"family", "norm", "rope_theta", "qkv_bias", "linear_bias",
         "attn_softcap", "final_softcap", "post_norms", "vocab_pad_to",
         "subquadratic", "tie_embeddings"},
    "moe": {"capacity_factor", "router_softcap"},
}


def _check_keys(m: Dict) -> None:
    groups = [("", m)] + [(g, m[g]) for g in ("ssm", "moe") if g in m] + \
        [("pattern", s) for s in m.get("pattern", ())]
    unknown = sorted({f"{g}.{k}" if g else k for g, group in groups
                      for k in group
                      if k not in READS[g] and k not in NO_MATMUL.get(g, ())})
    if unknown:
        raise ValueError(f"no FLOP count for the stated keys {unknown}")


def _ssm_dims(m: Dict) -> Dict[str, int]:
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    return {"d_inner": d_inner, "h": d_inner // s["head_dim"],
            "p": s["head_dim"], "g": s["n_groups"], "n": s["d_state"],
            "q": s["chunk_size"], "cw": s["conv_width"]}


def _ssd_chunk(m: Dict, seq: int) -> int:
    d = _ssm_dims(m)
    q = min(d["q"], -(-seq // 8) * 8)
    chunks = -(-seq // q)
    per_chunk = 2 * q * (q * d["n"] + q * d["p"] + 2 * d["n"] * d["p"])
    return chunks * per_chunk * d["h"]


def ssd_chunk_flops(m: Dict, seq: int) -> float:
    """One sequence through one layer's chunked SSD scan (kernels/ssd):
    per chunk of q rows and per head, C.B^T [q,q,N], (L*scores) @ xdt
    [q,q,P], C @ h [q,N,P] and B^T @ xdt [q,N,P]."""
    return float(_ssd_chunk(m, seq))


def _heads(m: Dict):
    return m["n_heads"], m["n_kv_heads"], \
        m.get("head_dim") or m["d_model"] // m["n_heads"]


def _mixer_params(m: Dict, mixer: str) -> int:
    """Weights of a layer's mixer multiplied once per token."""
    dm = m["d_model"]
    if mixer in ("attn_global", "attn_local"):
        h, kv, dh = _heads(m)
        return dm * h * dh + 2 * dm * kv * dh + h * dh * dm
    if mixer == "ssd":
        d = _ssm_dims(m)
        return dm * (2 * d["d_inner"] + 2 * d["g"] * d["n"] + d["h"]) + \
            d["d_inner"] * dm
    if mixer == "none":
        return 0
    raise ValueError(f"no FLOP count for mixer {mixer!r}")


def _mlp_params(m: Dict, spec: Dict) -> int:
    """Weights of a layer's MLP multiplied once per token: for an expert
    layer the router and the token's ``top_k`` experts."""
    dm = m["d_model"]
    if spec["mlp"] == "moe":
        mo = m["moe"]
        f = mo.get("d_ff") or m["d_ff"]
        out = dm * mo["n_experts"] + \
            mo["top_k"] * MLP_MATMULS["swiglu"] * dm * f
    elif spec["mlp"] == "none":
        out = 0
    elif spec["mlp"] in MLP_MATMULS:
        out = MLP_MATMULS[spec["mlp"]] * dm * m["d_ff"]
    else:
        raise ValueError(f"no FLOP count for mlp {spec['mlp']!r}")
    if spec["dense_residual"]:
        out += MLP_MATMULS["swiglu"] * dm * m["d_ff"]
    return out


def _context_sum(seq: int, window: Optional[int]) -> int:
    """Sum over the tokens of the positions each attends to."""
    if not window or seq <= window:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _mixer_extra(m: Dict, mixer: str, seq: int) -> int:
    """Per layer, beyond the matmul weights: q.k and p.v over the context
    for attention; the SSD scan and the causal conv for an SSM."""
    if mixer in ("attn_global", "attn_local"):
        h, _, dh = _heads(m)
        window = m["window"] if mixer == "attn_local" else None
        return 4 * h * dh * _context_sum(seq, window)
    if mixer == "ssd":
        d = _ssm_dims(m)
        conv = 2 * d["cw"] * (d["d_inner"] + 2 * d["g"] * d["n"]) * seq
        return _ssd_chunk(m, seq) + conv
    return 0


def _layers_flops(m: Dict, seq: int) -> int:
    """One sequence of ``seq`` tokens through every layer."""
    _check_keys(m)
    return sum(2 * (_mixer_params(m, s["mixer"]) + _mlp_params(m, s)) * seq
               + _mixer_extra(m, s["mixer"], seq) for s in layers(m))


def prefill_flops(m: Dict, seq: int) -> float:
    """One prompt of ``seq`` tokens, batch 1: every layer over every token,
    the LM head for the last token only (what the engine computes)."""
    return float(_layers_flops(m, seq)) + \
        2.0 * m["d_model"] * m["vocab_size"]


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward + backward per token of sequences of ``seq`` tokens, LM
    head and loss over every token: 3x the forward."""
    fwd = _layers_flops(m, seq) / seq
    fwd += 2.0 * m["d_model"] * m["vocab_size"]
    return 3.0 * fwd
