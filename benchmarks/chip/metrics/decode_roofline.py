"""Share of the HBM roofline that the serve model's decode step reaches, in
percent: the bytes every exact batch-1 decode token must read on this chip,
times the window's decoded tokens, over the chip's busy time inside the
harness's decode spans and its peak bandwidth.

The bytes are every parameter but the input embedding table (a token reads
one row of it) and the routed experts (a token may route to none of those
held here), at 2 bytes: norms, mixers, routers and shared experts, the
final norm and the head, counted from the configuration's ``model`` group.
The count refuses a layer kind or a stated key it does not know. Caches and
recurrent state are not counted, and the first token of each resumed turn
is decoded inside the spans but not counted in ``decode_tokens``, so the
share errs low.
"""
from __future__ import annotations

from typing import Dict

from harness.model import layers

SPAN = "bench.engine.decode"
BYTES = 2   # bfloat16

KNOWN = {
    "": {"family", "n_layers", "d_model", "n_heads", "n_kv_heads",
         "head_dim", "d_ff", "vocab_size", "pattern", "mixer", "mlp", "norm",
         "rms_eps", "rope", "rope_theta", "ssm", "moe"},
    "ssm": {"d_state", "head_dim", "n_groups", "conv_width", "chunk_size",
            "expand", "n_heads", "conv_bias"},
    "moe": {"n_experts", "top_k", "d_ff", "router_softcap", "router",
            "routed_scale", "mlp", "shared_ff", "n_held"},
}
MLP_MATS = {"relu2": 2, "gelu": 2, "swiglu": 3, "geglu": 3}


def _check(m: Dict) -> None:
    groups = [("", m)] + [(g, m[g]) for g in ("ssm", "moe") if g in m]
    unknown = sorted(f"{g}.{k}" if g else k for g, grp in groups
                     for k in grp if k not in KNOWN[g])
    if unknown:
        raise ValueError(f"decode_roofline: no byte count for {unknown}")
    if m.get("norm", "rmsnorm") != "rmsnorm":
        raise ValueError(f"decode_roofline: no byte count for norm "
                         f"{m['norm']!r}")


def _mixer(m: Dict, mixer: str) -> int:
    d = m["d_model"]
    if mixer == "none":
        return 0
    if mixer == "attn_global":
        h, kv = m["n_heads"], m["n_kv_heads"]
        dh = m.get("head_dim") or d // h
        return d + 2 * d * h * dh + 2 * d * kv * dh
    if mixer == "ssd":
        s = m["ssm"]
        d_inner = s["n_heads"] * s["head_dim"] if s.get("n_heads") \
            else s["expand"] * d
        heads = d_inner // s["head_dim"]
        conv = d_inner + 2 * s["n_groups"] * s["d_state"]
        return (d + d * (2 * d_inner + 2 * s["n_groups"] * s["d_state"]
                         + heads) + conv * (s["conv_width"]
                                            + bool(s.get("conv_bias")))
                + 3 * heads + d_inner + d_inner * d)
    raise ValueError(f"decode_roofline: no byte count for mixer {mixer!r}")


def _mlp(m: Dict, mlp: str) -> int:
    """An MLP block's weights a token reads whatever it routes to: for an
    expert layer the router and the shared expert."""
    d = m["d_model"]
    if mlp == "none":
        return 0
    if mlp == "moe":
        mo = m["moe"]
        router = d * mo["n_experts"] + mo["n_experts"] * (
            mo.get("router") == "sigmoid_bias")
        shared = MLP_MATS[mo.get("mlp", "swiglu")] * d * mo.get("shared_ff", 0)
        return d + router + shared
    raise ValueError(f"decode_roofline: no byte count for mlp {mlp!r}")


def token_bytes(m: Dict) -> int:
    """Bytes every exact batch-1 decode token reads on this chip."""
    _check(m)
    total = 0
    for spec in layers(m):
        if spec.get("dense_residual"):
            raise ValueError("decode_roofline: no byte count for a dense "
                             "residual")
        total += _mixer(m, spec["mixer"]) + _mlp(m, spec["mlp"])
    total += m["d_model"] + m["d_model"] * m["vocab_size"]  # final norm, head
    return BYTES * total


def read(run):
    tr = run["trace"]
    if tr is None or not tr.span_s.get(SPAN) or not run["decode_tokens"]:
        return None
    busy = tr.span_s[SPAN] - tr.idle_by_span.get(SPAN, 0.0)
    return 100.0 * token_bytes(run["model"]) * run["decode_tokens"] / \
        (busy * run["peak"]["hbm_bytes_s"])
