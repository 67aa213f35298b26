"""Jit'd wrapper: model layout [B,S,H,P] <-> kernel layout [B,H,S,P]."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_bhsp


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, *, chunk: int = 256, interpret: bool = False):
    """x [B,S,H,P]; dt [B,S,H]; a [H]; b,c [B,S,G,N].

    Returns (y [B,S,H,P], final_state [B,H,P,N]) matching
    models.ssm.ssd_chunked's contract. Any S: the tail is padded to a
    whole chunk (a multiple of 8 rows) with dt=0 rows, which are exact
    no-ops (decay 1, contribution 0).
    """
    s = x.shape[1]
    q = min(chunk, -(-s // 8) * 8)
    pad = (-s) % q
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    xt = x.transpose(0, 2, 1, 3)
    dtt = dt.transpose(0, 2, 1).astype(jnp.float32)
    bt = b.transpose(0, 2, 1, 3)
    ct = c.transpose(0, 2, 1, 3)
    y, st = ssd_bhsp(xt, dtt, a.astype(jnp.float32), bt, ct, chunk=q,
                     interpret=interpret)
    return y.transpose(0, 2, 1, 3)[:, :s], st.transpose(0, 1, 3, 2)
