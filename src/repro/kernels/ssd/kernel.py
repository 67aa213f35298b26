"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel.

Grid = (batch, heads, num_chunks) with the chunk axis innermost/sequential;
the running SSM state (one [N, P] tile) persists in VMEM scratch across
chunks. Within a chunk the intra-chunk term is a pair of [Q,Q]x[Q,P] MXU
matmuls (the "duality": the quadratic attention-like form), and the
inter-chunk term is two [Q,N]x[N,P] matmuls against the carried state —
exactly the decomposition from arXiv:2405.21060 mapped onto MXU tiles.

B/C are per-group; the index_map folds head -> group so grouped B/C tensors
are streamed without materializing the head-broadcast copies in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, st_ref,
                h_scr, *, q: int, nc: int):
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)       # [q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)     # [q, 1] (column layout)
    a = a_ref[pl.program_id(1)]               # scalar decay coeff (SMEM)
    bb = b_ref[0, 0].astype(jnp.float32)      # [q, N]
    cc = c_ref[0, 0].astype(jnp.float32)      # [q, N]

    # in-chunk inclusive cumsum of the decay logs as masked sums over the
    # causal mask (TPU has no cumsum lowering), both as a column cs[i]
    # and as a row cs_row[j]; the diagonal mask moves da into a row
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ii >= jj
    da = dt * a                                                       # [q,1]
    da_row = jnp.sum(jnp.where(ii == jj, da, 0.0), axis=0, keepdims=True)
    cs = jnp.sum(jnp.where(causal, da_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(ii <= jj, da, 0.0), axis=0, keepdims=True)
    cs_last = cs[q - 1:q, :]                                          # [1,1]
    xdt = x * dt                                                      # [q,P]

    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i >= j
    L = jnp.where(causal, jnp.exp(cs - cs_row), 0.0)
    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [q,q]
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # [q,P]

    # inter-chunk: y += (C * exp(cs)) @ h_prev
    h_prev = h_scr[...]                       # [N, P]
    c_dec = cc * jnp.exp(cs)
    y = y + jax.lax.dot_general(c_dec, h_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: h = exp(cs[-1]) h_prev + B^T diag(exp(cs[-1]-cs)) Xdt
    b_dec = bb * jnp.exp(cs_last - cs)                                # [q,N]
    contrib = jax.lax.dot_general(b_dec, xdt, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h_scr[...] = h_prev * jnp.exp(cs_last) + contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(cj == nc - 1)
    def _emit_state():
        st_ref[0, 0] = h_scr[...].astype(st_ref.dtype)


def ssd_bhsp(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 256, interpret: bool = False):
    """x [B,H,S,P]; dt [B,H,S]; a [H]; b,c [B,G,S,N] (H % G == 0).

    Returns (y [B,H,S,P], final_state [B,H,N,P]).
    """
    B, H, S, P = x.shape
    G, N = b.shape[1], b.shape[3]
    q = min(chunk, S)
    assert S % q == 0 and H % G == 0
    nc = S // q
    rep = H // G
    dt2 = dt.reshape(B, H, S, 1)  # column tiles [q, 1]

    kernel = functools.partial(_ssd_kernel, q=q, nc=nc)
    grid = (B, H, nc)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # a: whole [H] array
            pl.BlockSpec((1, 1, q, P), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, N),
                         lambda b_, h_, c_, r=rep: (b_, h_ // r, c_, 0)),
            pl.BlockSpec((1, 1, q, N),
                         lambda b_, h_, c_, r=rep: (b_, h_ // r, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, P), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(a, x, dt2, b, c)
    return y, st
