"""Operations and bytes the algorithms need, from shapes alone.

A multiply-add counts as 2 FLOPs. Model FLOPs count each matmul once per
pass (training: forward + backward = 3 forward passes); recomputation
under remat is not counted. The sizes come from a configuration file's
``model`` group, so these functions import nothing of the program.
"""
from __future__ import annotations

from typing import Dict


def _ssm_dims(m: Dict) -> Dict[str, int]:
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    return {"d_inner": d_inner, "h": d_inner // s["head_dim"],
            "p": s["head_dim"], "g": s["n_groups"], "n": s["d_state"],
            "q": s["chunk_size"], "cw": s["conv_width"]}


def ssd_chunk_flops(m: Dict, seq: int) -> float:
    """One sequence through one layer's chunked SSD scan (kernels/ssd):
    per chunk of q rows and per head, C.B^T [q,q,N], (L*scores) @ xdt
    [q,q,P], C @ h [q,N,P] and B^T @ xdt [q,N,P]."""
    d = _ssm_dims(m)
    q = min(d["q"], -(-seq // 8) * 8)
    chunks = -(-seq // q)
    per_chunk = 2 * q * (q * d["n"] + q * d["p"] + 2 * d["n"] * d["p"])
    return float(chunks * per_chunk * d["h"])


def _layer_matmul_params(m: Dict) -> float:
    """Weights multiplied once per token in one layer."""
    dm = m["d_model"]
    if m["family"] == "ssm":
        d = _ssm_dims(m)
        return float(dm * (2 * d["d_inner"] + 2 * d["g"] * d["n"] + d["h"])
                     + d["d_inner"] * dm)
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    mlp = {"gelu": 2, "swiglu": 3, "geglu": 3}[m["mlp"]]
    return float(dm * h * dh + 2 * dm * kv * dh + h * dh * dm
                 + mlp * dm * m["d_ff"])


def _layer_extra_flops(m: Dict, seq: int, ctx_sum: float) -> float:
    """Per layer, beyond the matmul weights: the SSD scan and the causal
    conv for an SSM; q.k and p.v over the context for attention.
    ``ctx_sum`` is the sum over the tokens of the positions each attends
    to."""
    if m["family"] == "ssm":
        d = _ssm_dims(m)
        conv = 2 * d["cw"] * (d["d_inner"] + 2 * d["g"] * d["n"]) * seq
        return ssd_chunk_flops(m, seq) + conv
    return 4.0 * m["n_heads"] * m["head_dim"] * ctx_sum


def prefill_flops(m: Dict, seq: int) -> float:
    """One prompt of ``seq`` tokens, batch 1: every layer over every token,
    the LM head for the last token only (what the engine computes)."""
    ctx = seq * (seq + 1) / 2.0
    per_layer = 2 * _layer_matmul_params(m) * seq + \
        _layer_extra_flops(m, seq, ctx)
    return m["n_layers"] * per_layer + 2.0 * m["d_model"] * m["vocab_size"]


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward + backward per token of sequences of ``seq`` tokens, LM
    head and loss over every token: 3x the forward."""
    ctx = seq * (seq + 1) / 2.0
    fwd = m["n_layers"] * (2 * _layer_matmul_params(m) * seq +
                           _layer_extra_flops(m, seq, ctx)) / seq
    fwd += 2.0 * m["d_model"] * m["vocab_size"]
    return 3.0 * fwd
