"""Plain reference of StarCoder2 (arXiv:2402.19173), in float32.

Block: LayerNorm -> grouped-query attention with biases and rotary
positions (rotate-half, base 100000), causal -> residual -> LayerNorm ->
GELU (tanh) MLP with biases -> residual. Final LayerNorm and an untied LM
head. Query head h reads key/value head h // (heads / kv_heads).

Departures, as the configuration file lists them: global attention in
place of the 4096-token sliding window (exact for contexts of at most
4096), untied embeddings. Weights come in the layout
``harness/weights.py`` makes: ``w["group0"]["p0"][...][layer]``. Nothing
of the program is imported.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.refmath import Quant, exact, f32, gelu_tanh, layer_norm, mm

EPS = 1e-5
Q_BLOCK = 512


def rope(x, pos, theta):
    """x [S, H, D] rotated by position (rotate-half convention)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(qh, kh, vh, q: Quant):
    """Causal GQA: qh [S,H,D], kh/vh [S,KV,D] -> [S,H,D], in blocks of
    query rows."""
    s, h, d = qh.shape
    kv = kh.shape[1]
    qg = qh.reshape(s, kv, h // kv, d)
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        blk = qg[lo:lo + Q_BLOCK]
        sc = mm("qkgd,tkd->kgqt", blk, kh, q) / np.sqrt(d)
        qpos = lo + jnp.arange(blk.shape[0])
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm("kgqt,tkd->qkgd", p, vh, q).reshape(-1, h, d))
    return jnp.concatenate(outs, 0)


def block(lw: Dict, x, m: Dict, q: Quant):
    at = lw["mixer"]
    s = x.shape[0]
    pos = jnp.arange(s)
    h = layer_norm(x, lw["norm1"]["w"], lw["norm1"]["b"], EPS)
    qh = mm("sd,dhk->shk", h, at["wq"], q) + at["bq"]
    kh = mm("sd,dhk->shk", h, at["wk"], q) + at["bk"]
    vh = mm("sd,dhk->shk", h, at["wv"], q) + at["bv"]
    qh, kh = rope(qh, pos, m["rope_theta"]), rope(kh, pos, m["rope_theta"])
    o = attention(qh, kh, vh, q)
    x = x + mm("shk,hkd->sd", o, at["wo"], q) + at["bo"]
    ml = lw["mlp"]
    h = layer_norm(x, lw["norm2"]["w"], lw["norm2"]["b"], EPS)
    h = gelu_tanh(mm("sd,df->sf", h, ml["w1"], q) + ml["b1"])
    return x + mm("sf,fd->sd", h, ml["w2"], q) + ml["b2"]


def logits(w, m: Dict, tokens: np.ndarray, q: Quant = exact) -> jax.Array:
    """Logits [S, V_padded] of one sequence, one layer's weights upcast
    at a time."""
    layer = jax.jit(lambda lw, x: block(f32(lw), x, m, q))
    x = w["in_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["n_layers"]):
        x = layer(jax.tree.map(lambda t: t[i], w["group0"]["p0"]), x)
    fn = w["final_norm"]
    head = jax.jit(lambda x, a, b, ow: mm(
        "sd,dv->sv", layer_norm(x, a, b, EPS), ow.astype(jnp.float32), q))
    return head(x, fn["w"], fn["b"], w["out_embed"])
