"""Jit'd wrappers: flatten/pad arbitrary arrays through the tile codec."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ckpt_codec.kernel import (ROWS, TILE, decode_tiles,
                                             encode_tiles)


def _block_rows(n_tiles: int) -> int:
    """Tiles per grid step: ROWS, or fewer (a multiple of 32) for a
    small array so that padding stays under one block."""
    return min(ROWS, -(-n_tiles // 32) * 32)


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = (-x.shape[0]) % rows
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _to_tiles(x: jax.Array) -> Tuple[jax.Array, int]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % TILE
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, TILE), n


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_encode(new: jax.Array, base: jax.Array, *,
                 interpret: bool = False):
    """Any-shape arrays -> (q int8 [n_tiles, TILE], scales [n_tiles, 1])."""
    nt, _ = _to_tiles(new)
    bt, _ = _to_tiles(base)
    n_tiles = nt.shape[0]
    rows = _block_rows(n_tiles)
    q, s = encode_tiles(_pad_rows(nt, rows), _pad_rows(bt, rows),
                        rows=rows, interpret=interpret)
    return q[:n_tiles], s[:n_tiles]


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "interpret"))
def delta_decode(q: jax.Array, scales: jax.Array, base: jax.Array, *,
                 shape: Tuple[int, ...], dtype=jnp.bfloat16,
                 interpret: bool = False) -> jax.Array:
    bt, n = _to_tiles(base)
    rows = _block_rows(bt.shape[0])
    out = decode_tiles(_pad_rows(q, rows), _pad_rows(scales, rows),
                       _pad_rows(bt, rows), dtype=dtype, rows=rows,
                       interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)
