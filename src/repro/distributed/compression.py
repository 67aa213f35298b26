"""Compressed data-parallel gradient reduction with error feedback.

XLA's all-reduce cannot run a custom reduction on quantized payloads, so the
classic "int8 ring all-reduce" is decomposed the way production JAX stacks
do it: reduce_scatter in bf16 (the arithmetic part) + QUANTIZED all_gather
(the broadcast part, int8 + per-block f32 scales = ~4x fewer broadcast
bytes), with persistent error-feedback on the quantization residual so the
bias vanishes over steps. Wire bytes drop from 2N to N + N/4 (~1.8x);
the collective-roofline win shows up directly in the dry-run HLO.

Used by train/train_step.py when ParallelConfig.grad_compression == "int8ef".
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

BLOCK = 1024


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x [n] f32 -> (q int8 [n], scales f32 [n/BLOCK])."""
    xb = x.reshape(-1, BLOCK)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-20)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale.reshape(-1)


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.reshape(-1, BLOCK).astype(jnp.float32) *
            scale[:, None]).reshape(-1)


def compressed_psum_scatter_gather(x: jax.Array, axis: str,
                                   err: jax.Array
                                   ) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map: mean-reduce ``x`` [n] over ``axis`` with int8
    compressed broadcast + error feedback state ``err`` [n/devices].

    Returns (reduced [n], new_err). n must divide (devices * BLOCK).
    """
    nd = jax.lax.axis_size(axis)
    # 1) bf16 reduce_scatter: each device owns n/nd reduced elements
    shard = jax.lax.psum_scatter(x.astype(jnp.bfloat16), axis,
                                 scatter_dimension=0, tiled=True)
    shard = shard.astype(jnp.float32) / nd + err
    # 2) int8 quantize + all_gather (compressed broadcast)
    q, scale = _quantize(shard)
    deq = _dequantize(q, scale)
    new_err = shard - deq
    qg = jax.lax.all_gather(q, axis, axis=0, tiled=True)
    sg = jax.lax.all_gather(scale, axis, axis=0, tiled=True)
    return _dequantize(qg, sg), new_err


def init_error_state(n: int, devices: int) -> jax.Array:
    assert n % (devices * BLOCK) == 0
    return jnp.zeros((n // devices,), jnp.float32)
