"""Mamba2 SSD (state-space duality) blocks.

Block: in_proj -> (z, x, B, C, dt); causal conv over (x,B,C) (with a bias
where ``SSMConfig.conv_bias``); SSD scan; gated RMSNorm, taken per group of
d_inner / n_groups channels; out_proj. The inner width is ``n_heads *
head_dim`` where the config sets a head count, else ``expand * d_model``.
The SSD scan is the chunked algorithm from arXiv:2405.21060 (intra-chunk
quadratic term + inter-chunk state recurrence); kernels/ssd provides the
Pallas fast path.

Shapes: x [B,S,H,P], dt [B,S,H], A [H] (negative), B/C [B,S,G,N] (G groups
broadcast over heads).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ParamBuilder, conv1d_channels, rms_norm

Params = Dict[str, Any]


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int, int]:
    sc = cfg.ssm
    d_inner = sc.d_inner(cfg.d_model)
    n_heads = d_inner // sc.head_dim
    return d_inner, n_heads, sc.head_dim, sc.n_groups, sc.d_state


def init_ssd(pb: ParamBuilder, cfg: ModelConfig) -> None:
    d = cfg.d_model
    d_inner, h, p_, g, n = ssm_dims(cfg)
    cw = cfg.ssm.conv_width
    pb.param("wz", (d, h, p_), (None, "ssm_heads", None), init="fan_in")
    pb.param("wx", (d, h, p_), (None, "ssm_heads", None), init="fan_in")
    pb.param("wbc", (d, 2 * g * n), (None, None), init="fan_in")
    pb.param("wdt", (d, h), (None, "ssm_heads"), init="fan_in")
    pb.param("conv_x", (d_inner, cw), ("ssm_flat", None), init="fan_in")
    pb.param("conv_bc", (2 * g * n, cw), (None, None), init="fan_in")
    if cfg.ssm.conv_bias:
        pb.param("conv_x_b", (d_inner,), ("ssm_flat",), init="zeros")
        pb.param("conv_bc_b", (2 * g * n,), (None,), init="zeros")
    pb.param("a_log", (h,), ("ssm_heads",), init="ssm_a")
    pb.param("d_skip", (h,), ("ssm_heads",), init="ones")
    pb.param("dt_bias", (h,), ("ssm_heads",), init="ssm_dt")
    pb.param("norm_w", (h, p_), ("ssm_heads", None), init="ones")
    pb.param("w_out", (h, p_, d), ("ssm_heads", None, None), init="fan_in")


def _segsum(log_a: jax.Array) -> jax.Array:
    """log_a [..., Q] -> L [..., Q, Q] with L[i,j] = sum_{k=j+1..i} log_a_k
    for i>=j, else -inf."""
    q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # [., i, j] = cs_i - cs_j
    mask = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x [B,S,H,P]; dt [B,S,H] (f32, post-softplus); a [H] (negative, f32);
    b,c [B,S,G,N]; h0 optional initial state [B,H,P,N].
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    B_, S, H, P = x.shape
    G, N = b.shape[-2], b.shape[-1]
    q = min(chunk, S)
    s_orig = S
    if S % q:  # pad tail: dt=0 rows are exact no-ops (decay 1, contribution 0)
        pad = q - S % q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // q
    rep = H // G
    dtype = x.dtype

    da = dt * a  # [B,S,H] negative decay logs
    xdt = x * dt[..., None].astype(dtype)

    xc = xdt.reshape(B_, nc, q, H, P)
    dac = da.reshape(B_, nc, q, H)
    bc_ = b.reshape(B_, nc, q, G, N)
    cc = c.reshape(B_, nc, q, G, N)
    bh = jnp.repeat(bc_, rep, axis=-2)  # [B,nc,q,H,N]
    ch = jnp.repeat(cc, rep, axis=-2)

    # --- intra-chunk (quadratic within chunk) ---
    L = jnp.exp(_segsum(dac.transpose(0, 1, 3, 2)))  # [B,nc,H,q,q]
    scores = jnp.einsum("bciht,bcjht->bchij", ch, bh,
                        preferred_element_type=jnp.float32)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp",
                         (scores * L).astype(dtype), xc)

    # --- chunk summaries: state contribution of each chunk ---
    cs = jnp.cumsum(dac, axis=2)  # [B,nc,q,H]
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # [B,nc,q,H]
    states = jnp.einsum("bcqht,bcqhp->bchpt",
                        (bh * decay_to_end[..., None]).astype(dtype), xc)

    # --- inter-chunk recurrence ---
    chunk_decay = jnp.exp(jnp.sum(dac, axis=2))  # [B,nc,H]

    def step(carry, xs):
        st, dec = xs
        new = carry * dec[..., None, None].astype(carry.dtype) + \
            st.astype(carry.dtype)
        return new, carry  # emit state *before* this chunk

    init = jnp.zeros((B_, H, P, N), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    final, prev_states = jax.lax.scan(
        step, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N]

    # --- inter-chunk output: y_i += C_i . (decay_in * prev_state) ---
    decay_in = jnp.exp(cs)  # [B,nc,q,H]
    y_inter = jnp.einsum("bcqht,bchpt->bcqhp",
                         (ch * decay_in[..., None]).astype(dtype),
                         prev_states.astype(dtype))
    y = (y_intra + y_inter).reshape(B_, S, H, P)[:, :s_orig]
    return y, final


def ssd_decode_step(h: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array, d_skip: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD update. h [B,H,P,N]; x [B,H,P]; dt [B,H];
    b,c [B,G,N]. Returns (y [B,H,P], h_new)."""
    G = b.shape[-2]
    rep = h.shape[1] // G
    bh = jnp.repeat(b, rep, axis=-2)  # [B,H,N]
    ch = jnp.repeat(c, rep, axis=-2)
    decay = jnp.exp(dt * a)  # [B,H]
    upd = jnp.einsum("bhp,bhn->bhpn", x * dt[..., None].astype(x.dtype),
                     bh.astype(x.dtype))
    h_new = h * decay[..., None, None].astype(h.dtype) + upd.astype(h.dtype)
    y = jnp.einsum("bhpn,bhn->bhp", h_new.astype(x.dtype), ch.astype(x.dtype))
    y = y + x * d_skip[:, None].astype(x.dtype)
    return y, h_new


def apply_ssd(p: Params, xin: jax.Array, cfg: ModelConfig,
              state: Optional[Params] = None, impl: str = "jnp",
              return_state: bool = False
              ) -> Tuple[jax.Array, Optional[Params]]:
    """xin [B,S,D]. state (decode): {'h': [B,H,P,N], 'conv': [B,K-1,Cc]}."""
    d_inner, H, P, G, N = ssm_dims(cfg)
    B_, S, _ = xin.shape
    cw = cfg.ssm.conv_width
    z = jnp.einsum("bsd,dhp->bshp", xin, p["wz"])
    x = jnp.einsum("bsd,dhp->bshp", xin, p["wx"]).reshape(B_, S, d_inner)
    bcb = jnp.einsum("bsd,dc->bsc", xin, p["wbc"])
    dt_raw = jnp.einsum("bsd,dh->bsh", xin, p["wdt"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    conv_in = jnp.concatenate([x, bcb], axis=-1)  # [B,S,Cc]
    conv_w = jnp.concatenate([p["conv_x"], p["conv_bc"]], axis=0)
    carry = None if state is None else state["conv"]
    new_conv = None
    if state is not None or return_state:
        prev = carry if carry is not None else \
            jnp.zeros((B_, cw - 1, conv_in.shape[-1]), conv_in.dtype)
        new_conv = jnp.concatenate([prev.astype(conv_in.dtype), conv_in],
                                   axis=1)[:, -(cw - 1):]
    conv_out = conv1d_channels(conv_in, conv_w, carry)
    if "conv_x_b" in p:
        conv_out = conv_out + jnp.concatenate(
            [p["conv_x_b"], p["conv_bc_b"]]).astype(conv_out.dtype)
    conv_out = jax.nn.silu(conv_out)
    x = conv_out[..., :d_inner].reshape(B_, S, H, P)
    b = conv_out[..., d_inner:d_inner + G * N].reshape(B_, S, G, N)
    c = conv_out[..., d_inner + G * N:].reshape(B_, S, G, N)

    if state is None:
        if impl in ("pallas", "interpret"):
            from repro.kernels.ssd import ops as ssd_ops
            y, h_fin = ssd_ops.ssd(x, dt, a, b, c, chunk=cfg.ssm.chunk_size,
                                   interpret=(impl == "interpret"))
        else:
            y, h_fin = ssd_chunked(x, dt, a, b, c, cfg.ssm.chunk_size)
        y = y + x * p["d_skip"].astype(x.dtype)[:, None]
        new_state = {"h": h_fin, "conv": new_conv} if return_state else None
    else:
        y1, h_new = ssd_decode_step(state["h"], x[:, 0], dt[:, 0], a,
                                    b[:, 0], c[:, 0], p["d_skip"])
        y = y1[:, None]
        new_state = {"h": h_new, "conv": new_conv}

    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    if G == 1:
        y = rms_norm(y.reshape(B_, -1, H * P), p["norm_w"].reshape(-1),
                     eps=cfg.rms_eps).reshape(y.shape)
    else:  # per group of the heads that share B and C
        y = rms_norm(y.reshape(B_, -1, G, H * P // G),
                     p["norm_w"].reshape(G, -1),
                     eps=cfg.rms_eps).reshape(y.shape)
    out = jnp.einsum("bshp,hpd->bsd", y, p["w_out"])
    return out, new_state


def init_ssd_state(cfg: ModelConfig, batch: int) -> Params:
    d_inner, H, P, G, N = ssm_dims(cfg)
    cc = d_inner + 2 * G * N
    return {"h": jnp.zeros((batch, H, P, N), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm.conv_width - 1, cc),
                              jnp.bfloat16)}
