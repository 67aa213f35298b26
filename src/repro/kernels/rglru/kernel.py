"""RG-LRU blocked linear-recurrence Pallas kernel.

h_t = a_t * h_{t-1} + b_t,  a_t = exp(log_a_t),
b_t = sqrt(1 - a_t^2) * gated_t.

Tiling: grid = (batch, channel_blocks, seq_blocks) with the sequence axis
innermost/sequential; the running hidden state h (one row of bw channels)
persists in VMEM scratch across sequence blocks. Within a block the
recurrence is solved with a log2(bs)-step inclusive scan on the VPU
(elementwise ops only — the recurrence is diagonal, so there is no MXU
work; the kernel exists to keep the whole scan in VMEM in one pass over
HBM, which is what makes it memory-bound-optimal on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(loga_ref, gated_ref, o_ref, h_scr, *, bs: int, bw: int):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    log_a = loga_ref[0].astype(jnp.float32)          # [bs, bw]
    gated = gated_ref[0].astype(jnp.float32)
    a = jnp.exp(log_a)
    # 1 - a^2 == -expm1(2 log_a) == -tanh(log_a) (1 + a^2): exact near
    # a -> 1 without expm1, which has no TPU lowering
    b = jnp.sqrt(jnp.maximum(-jnp.tanh(log_a) * (1.0 + a * a), 1e-12)) \
        * gated

    # In-block inclusive scan (Blelloch-style doubling on dense arrays):
    # after k rounds, (A[t], B[t]) compose the last 2^k steps ending at t.
    av, bv = a, b
    shift = 1
    while shift < bs:
        a_prev = jnp.pad(av, ((shift, 0), (0, 0)), constant_values=1.0)[:bs]
        b_prev = jnp.pad(bv, ((shift, 0), (0, 0)))[:bs]
        bv = bv + av * b_prev
        av = av * a_prev
        shift *= 2
    # av[t] = prod a_{0..t}, bv[t] = h_t given h_{-1}=0; add carry term.
    h0 = h_scr[...]                                   # [1, bw]
    h = bv + av * h0
    o_ref[0] = h.astype(o_ref.dtype)
    h_scr[...] = h[-1:, :]


def rglru_blocked(log_a: jax.Array, gated: jax.Array, *, bs: int = 256,
                  bw: int = 512, interpret: bool = False) -> jax.Array:
    """log_a, gated: [B, S, W] (f32). Returns h [B, S, W] (f32)."""
    B, S, W = log_a.shape
    bs = min(bs, S)
    bw = min(bw, W)
    assert S % bs == 0 and W % bw == 0, (S, bs, W, bw)
    grid = (B, W // bw, S // bs)
    kernel = functools.partial(_rglru_kernel, bs=bs, bw=bw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda b, w, s: (b, s, w)),
            pl.BlockSpec((1, bs, bw), lambda b, w, s: (b, s, w)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda b, w, s: (b, s, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(log_a, gated)
