"""Plain reference of Mamba-2 (arXiv:2405.21060), in float32.

Block: RMSNorm -> in-projections z, x, B, C, dt -> causal depthwise conv
over (x, B, C) and SiLU -> SSD scan -> + D x -> gate by SiLU(z) -> RMSNorm
over the inner width -> out-projection -> residual. Final RMSNorm and an
untied LM head. The scan is the SSD recurrence h_t = exp(dt_t A) h_{t-1}
+ dt_t x_t B_t^T, y_t = C_t h_t, evaluated in chunks of 64 rows (the
intra-chunk quadratic form plus a carried state); that is the same
mathematics as the sequential recurrence, in another order.

Departures from the published model, as the configuration file lists
them: untied embeddings, RMSNorm eps 1e-6, logits over the padded
vocabulary. Weights come in the layout ``harness/weights.py`` makes:
``w["group0"]["p0"][leaf][layer]``. Nothing of the program is imported.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness.refmath import (HI, Quant, exact, f32, mm, rms_norm, silu,
                             softplus)

EPS = 1e-6
CHUNK = 64


def ssd_scan(x, dt, a, b, c):
    """x [S,H,P], dt [S,H], a [H] (negative), b/c [S,G,N] -> y [S,H,P]."""
    s, h, p = x.shape
    g = b.shape[1]
    pad = (-s) % CHUNK
    x, dt, b, c = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                   for t in (x, dt, b, c))
    nc = (s + pad) // CHUNK
    bh = jnp.repeat(b, h // g, axis=1).reshape(nc, CHUNK, h, -1)
    ch = jnp.repeat(c, h // g, axis=1).reshape(nc, CHUNK, h, -1)
    xdt = (x * dt[..., None]).reshape(nc, CHUNK, h, p)
    cs = jnp.cumsum((dt * a).reshape(nc, CHUNK, h), axis=1)
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))[None, :, :, None]
    diff = cs[:, :, None, :] - cs[:, None, :, :]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))       # [nc,i,j,h]
    scores = jnp.einsum("cihn,cjhn->cijh", ch, bh, precision=HI)
    y = jnp.einsum("cijh,cjhp->cihp", scores * decay, xdt, precision=HI)
    to_end = jnp.exp(cs[:, -1:, :] - cs)
    states = jnp.einsum("cjhn,cjhp->chpn", bh * to_end[..., None], xdt,
                        precision=HI)
    chunk_decay = jnp.exp(cs[:, -1, :])

    def carry(hprev, inp):
        st, dec = inp
        return hprev * dec[:, None, None] + st, hprev

    _, before = jax.lax.scan(carry, jnp.zeros(states.shape[1:]),
                             (states, chunk_decay))
    y = y + jnp.einsum("cihn,chpn->cihp", ch * jnp.exp(cs)[..., None],
                       before, precision=HI)
    return y.reshape(nc * CHUNK, h, p)[:s]


def block(lw: Dict, xres, m: Dict, q: Quant):
    """One layer on one sequence; ``lw`` holds this layer's weights."""
    ss = m["ssm"]
    d_inner = ss["expand"] * m["d_model"]
    hp = d_inner // ss["head_dim"]
    gn = ss["n_groups"] * ss["d_state"]
    mx = lw["mixer"]
    s = xres.shape[0]
    h = rms_norm(xres, lw["norm1"]["w"], EPS)
    z = mm("sd,dhp->shp", h, mx["wz"], q)
    xs = mm("sd,dhp->shp", h, mx["wx"], q).reshape(s, d_inner)
    bc = mm("sd,dc->sc", h, mx["wbc"], q)
    dt = softplus(mm("sd,dh->sh", h, mx["wdt"], q) + mx["dt_bias"])
    a = -jnp.exp(mx["a_log"])
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    w = jnp.concatenate([mx["conv_x"], mx["conv_bc"]], axis=0)   # [C, K]
    k = w.shape[1]
    padded = jnp.pad(conv_in, ((k - 1, 0), (0, 0)))
    conv = sum(padded[i:i + s] * w[:, i] for i in range(k))
    conv = silu(conv)
    xh = conv[:, :d_inner].reshape(s, hp, ss["head_dim"])
    b = conv[:, d_inner:d_inner + gn].reshape(s, ss["n_groups"], -1)
    c = conv[:, d_inner + gn:].reshape(s, ss["n_groups"], -1)
    y = ssd_scan(xh, dt, a, b, c) + xh * mx["d_skip"][:, None]
    y = y * silu(z)
    y = rms_norm(y.reshape(s, -1), mx["norm_w"].reshape(-1), EPS)
    out = mm("shp,hpd->sd", y.reshape(s, hp, -1), mx["w_out"], q)
    return xres + out


def _layer(w, i):
    return jax.tree.map(lambda t: t[i], w["group0"]["p0"])


def hidden(w, m: Dict, tokens, q: Quant):
    """Final-normed hidden states [S, D] of one sequence, all layers."""
    x = w["in_embed"][tokens].astype(jnp.float32)
    for i in range(m["n_layers"]):
        x = block(_layer(w, i), x, m, q)
    return rms_norm(x, w["final_norm"]["w"], EPS)


def logits(w, m: Dict, tokens: np.ndarray, q: Quant = exact) -> jax.Array:
    """Logits [S, V_padded] of one sequence, layer by layer so that one
    layer's weights are upcast at a time."""
    layer = jax.jit(lambda lw, x: block(f32(lw), x, m, q))
    x = w["in_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["n_layers"]):
        x = layer(_layer(w, i), x)
    head = jax.jit(lambda x, fw, ow: mm(
        "sd,dv->sv", rms_norm(x, fw, EPS), ow.astype(jnp.float32), q))
    return head(x, w["final_norm"]["w"], w["out_embed"])


# ---- training -------------------------------------------------------------

def _row_nll(w, m, q, tokens, labels, mask):
    x = hidden(w, m, tokens, q)
    lg = mm("sd,dv->sv", x, w["out_embed"], q)
    valid = jnp.arange(lg.shape[-1]) < m["vocab_size"]
    lg = jnp.where(valid, lg, -1e30)
    lse = jax.nn.logsumexp(lg, axis=-1)
    lbl = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - lbl) * mask)


def _norms(tree) -> Dict[str, float]:
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))))
        for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def train(w, m: Dict, opt: Dict, batches: List[Dict[str, np.ndarray]],
          q: Quant = exact, rows: slice = slice(None)) -> Dict:
    """AdamW from the weights ``w`` (bfloat16, as served) over
    ``batches``, one row at a time. Returns each step's loss, the norm
    of each leaf of the first gradient as AdamW takes it (clipped), and
    the norm of each leaf's change after the last step. ``rows`` picks
    the rows of each batch that count (all of them, for the reference)."""
    grad_row = jax.jit(jax.value_and_grad(
        lambda wf, t, l, k: _row_nll(wf, m, q, t, l, k)))
    params = w
    mom = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    vel = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    losses, first_grad = [], None
    for step, batch in enumerate(batches, start=1):
        wf = f32(params)
        toks, lbls, mask = (batch[k][rows] for k in
                            ("tokens", "labels", "loss_mask"))
        total = max(float(mask.sum()), 1.0)
        nll, grads = 0.0, None
        for r in range(toks.shape[0]):
            v, g = grad_row(wf, toks[r], lbls[r], mask[r])
            nll += float(v)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        del wf
        grads = jax.tree.map(lambda g: g / total, grads)
        losses.append(nll / total)
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g)
                                   for g in jax.tree.leaves(grads))))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        if first_grad is None:
            first_grad = _norms(grads)
        lr = opt["lr"] * min(step / max(opt["warmup"], 1), 1.0)
        b1c = 1.0 - opt["b1"] ** step
        b2c = 1.0 - opt["b2"] ** step
        mom = jax.tree.map(lambda a, g: opt["b1"] * a + (1 - opt["b1"]) * g,
                           mom, grads)
        vel = jax.tree.map(lambda a, g: opt["b2"] * a +
                           (1 - opt["b2"]) * g * g, vel, grads)

        def update(p, a, b):
            upd = (a / b1c) / (jnp.sqrt(b / b2c) + opt["eps"])
            upd = upd + opt["weight_decay"] * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        params = jax.tree.map(update, params, mom, vel)
        del grads
    change = jax.tree.map(lambda a, b: a.astype(jnp.float32) -
                          b.astype(jnp.float32), params, w)
    return {"losses": losses, "grad": first_grad, "change": _norms(change)}
