"""Flash-decoding: sequence-sharded KV-cache attention for serve_step.

The KV cache is sharded [batch -> data axes, seq -> model]; the new token's
query (tiny) is replicated across the model axis. Every model shard computes
partial attention (m, l, o) over its KV slice for *all* Q heads, the partials
are combined with a pmax/psum log-sum-exp, and the new token's K/V is written
only by the ring-slot-owning shard. This is what makes decode cells shardable
even with 1-8 KV heads (head-sharding alone cannot use tp=16), and it turns
the decode bottleneck into a single small psum instead of a KV all-gather.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distributed.mesh import batch_axes
from repro.models.attention import NEG_INF, repeat_kv


def _partial_attend(q, kc, vc, kp, pos, window, cap):
    """Local partial attention. q [B,H,D]; kc/vc [B,Sloc,Kh,D]; kp [B,Sloc].
    Returns (o [B,H,D] f32, m [B,H], l [B,H])."""
    g = q.shape[1] // kc.shape[2]
    kk, vv = repeat_kv(kc, g), repeat_kv(vc, g)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhd,bshd->bhs", q, kk).astype(jnp.float32) * scale
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    valid = (kp >= 0) & (kp <= pos)
    if window > 0:
        valid &= kp > pos - window
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - jnp.maximum(m, -1e30)[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", p, vv.astype(jnp.float32))
    return o, m, l


def make_flash_decode(mesh: Mesh):
    """Build the decode-attention fn with the jnp-fallback signature:
    (k_cache, v_cache, kpos, k_new, v_new, q, pos, *, window, cap)
    -> (o [B,H,D], {'k','v','kpos'})."""
    batch = batch_axes(mesh) or None
    has_model = "model" in mesh.axis_names and mesh.shape["model"] > 1

    dp = 1
    if batch:
        for a in (batch if isinstance(batch, tuple) else (batch,)):
            dp *= mesh.shape[a]

    def flash_decode(k_cache, v_cache, kpos, k_new, v_new, q, pos, *,
                     window: int, cap: float):
        write = k_new is not None
        b = k_cache.shape[0]
        bspec = batch if (batch and b % dp == 0) else None
        seq_ok = has_model and k_cache.shape[1] % mesh.shape["model"] == 0
        sspec = "model" if seq_ok else None

        def inner(kc, vc, kp, q_, pos_, *new):
            sc_loc = kc.shape[1]
            if seq_ok:
                midx = jax.lax.axis_index("model")
                nshard = jax.lax.axis_size("model")
            else:
                midx, nshard = 0, 1
            if write:
                kn, vn = new
                slot = pos_ % (sc_loc * nshard)   # global ring slot
                local = slot % sc_loc
                own = (slot // sc_loc) == midx
                # in-place-friendly masked write: read the current row,
                # select, DUS back (no full-buffer select).
                cur_k = jax.lax.dynamic_slice(
                    kc, (0, local, 0, 0), (kc.shape[0], 1) + kc.shape[2:])
                cur_v = jax.lax.dynamic_slice(
                    vc, (0, local, 0, 0), (vc.shape[0], 1) + vc.shape[2:])
                cur_p = jax.lax.dynamic_slice(kp, (0, local),
                                              (kp.shape[0], 1))
                kn_w = jnp.where(own, kn[:, None].astype(kc.dtype), cur_k)
                vn_w = jnp.where(own, vn[:, None].astype(vc.dtype), cur_v)
                kp_w = jnp.where(own, jnp.broadcast_to(
                    pos_, (kp.shape[0], 1)).astype(kp.dtype), cur_p)
                kc = jax.lax.dynamic_update_slice(kc, kn_w, (0, local, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, vn_w, (0, local, 0, 0))
                kp = jax.lax.dynamic_update_slice(kp, kp_w, (0, local))
            o, m, l = _partial_attend(q_, kc, vc, kp, pos_, window, cap)
            if seq_ok:
                m_g = jax.lax.pmax(m, "model")
                corr = jnp.exp(jnp.maximum(m, -1e30) -
                               jnp.maximum(m_g, -1e30))
                l_g = jax.lax.psum(l * corr, "model")
                o_g = jax.lax.psum(o * corr[..., None], "model")
            else:
                l_g, o_g = l, o
            out = (o_g / jnp.maximum(l_g, 1e-30)[..., None])
            return out.astype(q_.dtype), kc, vc, kp

        kv_spec = P(bspec, sspec, None, None)
        kp_spec = P(bspec, sspec)
        new_specs = (P(bspec, None, None),) * 2 if write else ()
        fn = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(kv_spec, kv_spec, kp_spec, P(bspec, None, None), P())
            + new_specs,
            out_specs=(P(bspec, None, None), kv_spec, kv_spec, kp_spec),
            check_vma=False)
        args = (k_cache, v_cache, kpos, q, pos) + \
            ((k_new, v_new) if write else ())
        o, kc, vc, kp = fn(*args)
        return o, {"k": kc, "v": vc, "kpos": kp}

    return flash_decode
