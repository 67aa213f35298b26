"""Plain reference of the Nemotron-H hybrids (NVIDIA Nemotron 3 Nano
30B-A3B, arXiv:2504.03624 and the model's published config and modeling
code), in float32.

A model is a sequence of blocks in the order of its ``pattern``; each
block is RMSNorm -> one mixer or one MLP -> residual:

- ``ssd`` (M, Mamba-2): in-projections z, x, B, C, dt; causal depthwise
  conv over (x, B, C) plus its bias, SiLU; the SSD scan with A = -exp(a_log)
  and dt = softplus(dt + dt_bias), + D x (``configs/mamba2.py``'s scan);
  gated by SiLU(z); RMSNorm per group of d_inner / n_groups channels;
  out-projection. The inner width is ``ssm.n_heads`` x ``ssm.head_dim``.
- ``attn_global`` (*): grouped-query attention with no positional encoding
  and no biases, causal, scale head_dim^-1/2 (``configs/starcoder2.py``'s
  attention, in blocks of query rows).
- ``moe`` (E): the router's logits in float32, sigmoid scores; the top_k
  experts by score + correction bias (the bias takes part in the choice
  only); their scores renormalised and scaled by ``moe.routed_scale``.
  Each expert is down(relu(up x)^2). The layer adds the part that the
  first ``moe.n_held`` experts, those held here, give, and one shared
  expert of the same kind.

Every RMSNorm, the gated one too, takes the file's ``rms_eps``. Final
RMSNorm and an untied LM head. Departures from the published model, as
the configuration file lists them: of the routed experts only those held
here, as in the deployment the file states; activations in float32.
Weights come in the layout ``harness/weights.py`` makes; which stacked
group and slot holds each layer is read from that tree, in the layers'
order, and checked against the pattern. Nothing of the program is
imported.

To fit beside the served model's weights on one chip, the reference upcasts
one layer at a time, runs one jitted function per kind of block (a new
context length compiles a few small programs), computes attention in
blocks of query rows and the head in blocks of rows, and returns the
logits on the host.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import common
from harness.model import layers
from harness.refmath import Quant, exact, f32, mm, rms_norm, silu, softplus

HEAD_ROWS = 512
mamba2 = common.load_module(common.BENCH / "configs" / "mamba2.py")
starcoder2 = common.load_module(common.BENCH / "configs" / "starcoder2.py")

# leaves the harness's weight table lacks: the router's correction bias and
# the conv biases (the shared expert's w1 / w2 are the table's)
WEIGHT_RULES = {"router_bias": ("bias", 0), "conv_x_b": ("bias", 0),
                "conv_bc_b": ("bias", 0)}


def ssd_block(lw: Dict, x, m: Dict, q: Quant):
    ss = m["ssm"]
    hp, p = ss["n_heads"], ss["head_dim"]
    d_inner, g = hp * p, ss["n_groups"]
    gn = g * ss["d_state"]
    mx = lw["mixer"]
    s, eps = x.shape[0], m["rms_eps"]
    h = rms_norm(x, lw["norm1"]["w"], eps)
    z = mm("sd,dhp->shp", h, mx["wz"], q).reshape(s, d_inner)
    xs = mm("sd,dhp->shp", h, mx["wx"], q).reshape(s, d_inner)
    bc = mm("sd,dc->sc", h, mx["wbc"], q)
    dt = softplus(mm("sd,dh->sh", h, mx["wdt"], q) + mx["dt_bias"])
    a = -jnp.exp(mx["a_log"])
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    w = jnp.concatenate([mx["conv_x"], mx["conv_bc"]], axis=0)   # [C, K]
    bias = jnp.concatenate([mx["conv_x_b"], mx["conv_bc_b"]])
    k = w.shape[1]
    padded = jnp.pad(conv_in, ((k - 1, 0), (0, 0)))
    conv = silu(sum(padded[i:i + s] * w[:, i] for i in range(k)) + bias)
    xh = conv[:, :d_inner].reshape(s, hp, p)
    b = conv[:, d_inner:d_inner + gn].reshape(s, g, -1)
    c = conv[:, d_inner + gn:].reshape(s, g, -1)
    y = mamba2.ssd_scan(xh, dt, a, b, c) + xh * mx["d_skip"][:, None]
    y = y.reshape(s, d_inner) * silu(z)
    y = rms_norm(y.reshape(s, g, -1), mx["norm_w"].reshape(g, -1), eps)
    return x + mm("shp,hpd->sd", y.reshape(s, hp, p), mx["w_out"], q)


def attn_block(lw: Dict, x, m: Dict, q: Quant):
    at = lw["mixer"]
    h = rms_norm(x, lw["norm1"]["w"], m["rms_eps"])
    qh, kh, vh = (mm("sd,dhk->shk", h, at[n], q) for n in ("wq", "wk", "wv"))
    o = starcoder2.attention(qh, kh, vh, q)
    return x + mm("shk,hkd->sd", o, at["wo"], q)


def relu2(v):
    return jnp.square(jnp.maximum(v, 0.0))


def route(p: Dict, h, m: Dict, q: Quant) -> Tuple[jax.Array, jax.Array]:
    """(gates [S, k], expert ids [S, k]) of the sigmoid router."""
    mo = m["moe"]
    scores = jax.nn.sigmoid(mm("sd,de->se", h, p["router"], q))
    _, ids = jax.lax.top_k(scores + p["router_bias"], mo["top_k"])
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * mo["routed_scale"], ids


def moe_block(lw: Dict, x, m: Dict, q: Quant):
    mo, p = m["moe"], lw["mlp"]
    h = rms_norm(x, lw["norm2"]["w"], m["rms_eps"])
    gates, ids = route(p, h, m, q)
    wi, wo = (p[n].reshape((-1,) + p[n].shape[-2:]) for n in ("wi", "wo"))
    held = wi.shape[0]
    assert held == mo["n_held"], (held, mo["n_held"])
    weight = jnp.sum(jax.nn.one_hot(ids, held) * gates[..., None], 1)
    y = mm("sef,efd->sed", relu2(mm("sd,edf->sef", h, wi, q)), wo, q)
    out = jnp.einsum("sed,se->sd", y, weight)
    sh = p["shared"]
    out = out + mm("sf,fd->sd", relu2(mm("sd,df->sf", h, sh["w1"], q)),
                   sh["w2"], q)
    return x + out


BLOCKS = {("ssd", "none"): ssd_block, ("attn_global", "none"): attn_block,
          ("none", "moe"): moe_block}


def _kind(lw: Dict) -> Tuple[str, str]:
    """A layer's (mixer, mlp) from the leaves its weights hold."""
    mixer = lw.get("mixer", {})
    mlp = lw.get("mlp", {})
    return ("ssd" if "wx" in mixer else "attn_global" if "wq" in mixer
            else "none" if not mixer else "?",
            "moe" if "router" in mlp else "none" if not mlp else "?")


def _layers(w, m: Dict) -> Iterator[Tuple[Dict, Dict]]:
    """(spec, weights) of each layer, in order: the stacked groups of the
    tree in turn, each its repeats of its period."""
    slots = []
    g = 0
    while f"group{g}" in w:
        grp = w[f"group{g}"]
        reps = jax.tree.leaves(grp)[0].shape[0]
        slots += [(g, i, r) for r in range(reps) for i in range(len(grp))]
        g += 1
    specs = layers(m)
    if len(slots) != len(specs):
        raise ValueError(f"the tree holds {len(slots)} layers, the pattern "
                         f"{len(specs)}")
    for n, ((g, i, r), spec) in enumerate(zip(slots, specs)):
        lw = jax.tree.map(lambda t: t[r], w[f"group{g}"][f"p{i}"])
        if _kind(lw) != (spec["mixer"], spec["mlp"]):
            raise ValueError(f"layer {n} (group{g}/p{i}[{r}]) holds a "
                             f"{_kind(lw)} block, the pattern says {spec}")
        yield spec, lw


def logits(w, m: Dict, tokens: np.ndarray, q: Quant = exact) -> np.ndarray:
    """Logits [S, V_padded] of one sequence, on the host: one layer's
    weights upcast at a time, the head in blocks of rows."""
    run = {kind: jax.jit(lambda lw, x, f=fn: f(f32(lw), x, m, q))
           for kind, fn in BLOCKS.items()}
    x = w["in_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for spec, lw in _layers(w, m):
        x = run[spec["mixer"], spec["mlp"]](lw, x)
    head = jax.jit(lambda x, fw, ow: mm(
        "sd,dv->sv", rms_norm(x, fw, m["rms_eps"]), ow, q))
    return np.concatenate([
        np.asarray(head(x[lo:lo + HEAD_ROWS], w["final_norm"]["w"],
                        w["out_embed"]))
        for lo in range(0, x.shape[0], HEAD_ROWS)])
