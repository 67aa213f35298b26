"""Checkpoint delta + int8 quantization codec Pallas kernels.

The paper's node-local B-APM checkpointing story is bandwidth-bound; this
codec cuts checkpoint (and compressed-collective) bytes ~4x by storing
``int8 round((new - base) / scale)`` with one f32 absmax scale per tile.

encode: (new, base) -> (q int8, scales f32)   [one scale per TILE row]
decode: (q, scales, base) -> new'

A tile is one row of 1024 = 8 x 128 lanes, the same unit the host-side
numpy codec (ref.py) uses. Each grid step streams a block of ``rows``
tiles; ``rows`` must be a multiple of 32 (int8 packs 32 sublanes on TPU)
and divide the tile count, which ops.py pads to match.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024
ROWS = 256  # tiles per grid step: 1 MiB f32 per input block


def _encode_kernel(new_ref, base_ref, q_ref, scale_ref):
    d = new_ref[...].astype(jnp.float32) - base_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(d), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(d / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale.astype(jnp.float32)


def _decode_kernel(q_ref, scale_ref, base_ref, out_ref):
    d = q_ref[...].astype(jnp.float32) * scale_ref[...].astype(jnp.float32)
    out_ref[...] = (base_ref[...].astype(jnp.float32) + d) \
        .astype(out_ref.dtype)


def encode_tiles(new: jax.Array, base: jax.Array, *, rows: int = ROWS,
                 interpret: bool = False):
    """new, base: [n_tiles, TILE] -> (q int8 [n,TILE], scales f32 [n,1]).
    ``n_tiles`` must divide by ``rows``."""
    n = new.shape[0]
    assert n % rows == 0, (n, rows)
    return pl.pallas_call(
        _encode_kernel,
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, TILE), lambda i: (i, 0)),
                  pl.BlockSpec((rows, TILE), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, TILE), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, TILE), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret,
    )(new, base)


def decode_tiles(q: jax.Array, scales: jax.Array, base: jax.Array, *,
                 dtype=jnp.bfloat16, rows: int = ROWS,
                 interpret: bool = False) -> jax.Array:
    n = q.shape[0]
    assert n % rows == 0, (n, rows)
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, TILE), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, TILE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, TILE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, TILE), dtype),
        interpret=interpret,
    )(q, scales, base)
