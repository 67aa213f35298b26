"""Plain reference of the self-checks' hybrid expert model, in float32.

The layers follow the configuration's ``pattern``: an ``ssd`` layer with
no MLP is ``configs/mamba2.py``'s block; an ``attn_global`` layer with a
``moe`` MLP is RMSNorm -> grouped-query attention with rotary positions
(``configs/starcoder2.py``'s, no biases) -> residual -> RMSNorm -> a
mixture of SwiGLU experts -> residual. The router takes the softmax of
its logits (soft-capped where ``moe.router_softcap`` is set), keeps the
``top_k`` largest and renormalises them; a token's output is the
weighted sum of its experts' ``wo(silu(wg x) * wi x)``. Final RMSNorm
and an untied LM head. Weights come in the layout ``harness/weights.py``
makes, ``n_layers`` split over the period as the program splits it.
Nothing of the program is imported.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness import common
from harness.model import pattern
from harness.refmath import Quant, exact, f32, mm, rms_norm, silu

EPS = 1e-6
mamba2 = common.load_module(common.BENCH / "configs" / "mamba2.py")
starcoder2 = common.load_module(common.BENCH / "configs" / "starcoder2.py")


def experts(p: Dict, h, m: Dict, q: Quant):
    """h [S, D] through the mixture; expert weights [1, E, ., .]."""
    mo = m["moe"]
    logits = mm("sd,de->se", h, p["router"], q)
    cap = mo.get("router_softcap") or 0.0
    if cap:
        logits = cap * jnp.tanh(logits / cap)
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), mo["top_k"])
    gates = gates / gates.sum(-1, keepdims=True)
    wi, wg, wo = (p[k].reshape((-1,) + p[k].shape[-2:])
                  for k in ("wi", "wg", "wo"))
    act = silu(mm("sd,edf->sef", h, wg, q)) * mm("sd,edf->sef", h, wi, q)
    y = mm("sef,efd->sed", act, wo, q)
    weight = jnp.sum(jax.nn.one_hot(ids, wi.shape[0]) * gates[..., None], 1)
    return jnp.einsum("sed,se->sd", y, weight)


def attn_moe_block(lw: Dict, x, m: Dict, q: Quant):
    at = lw["mixer"]
    pos = jnp.arange(x.shape[0])
    h = rms_norm(x, lw["norm1"]["w"], EPS)
    qh, kh, vh = (mm("sd,dhk->shk", h, at[k], q) for k in ("wq", "wk", "wv"))
    qh = starcoder2.rope(qh, pos, m["rope_theta"])
    kh = starcoder2.rope(kh, pos, m["rope_theta"])
    o = starcoder2.attention(qh, kh, vh, q)
    x = x + mm("shk,hkd->sd", o, at["wo"], q)
    return x + experts(lw["mlp"], rms_norm(x, lw["norm2"]["w"], EPS), m, q)


BLOCKS = {("ssd", "none"): mamba2.block,
          ("attn_global", "moe"): attn_moe_block}


def _layers(w, m: Dict):
    """(block, weights) of each layer, in order."""
    specs = pattern(m)
    reps, tail = divmod(int(m["n_layers"]), len(specs))
    for i in range(reps * len(specs) + tail):
        g, r = (0, i // len(specs)) if i < reps * len(specs) else (1, 0)
        j = i % len(specs)
        spec = specs[j]
        lw = jax.tree.map(lambda t: t[r], w[f"group{g}"][f"p{j}"])
        yield BLOCKS[spec["mixer"], spec["mlp"]], lw


def logits(w, m: Dict, tokens: np.ndarray, q: Quant = exact) -> jax.Array:
    """Logits [S, V_padded] of one sequence, one layer's weights upcast
    at a time."""
    run = {blk: jax.jit(lambda lw, x, blk=blk: blk(f32(lw), x, m, q))
           for blk in BLOCKS.values()}
    x = w["in_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for blk, lw in _layers(w, m):
        x = run[blk](lw, x)
    return mm("sd,dv->sv", rms_norm(x, w["final_norm"]["w"], EPS),
              w["out_embed"], q)
