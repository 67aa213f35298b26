"""Plain float32 building blocks for the references beside the
configurations: every matmul at ``HIGHEST`` precision, every operand
passed through a quantizer first (the identity for the reference, a
float8 round trip for its lower-precision control). Imports nothing of
the program."""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Quant = Callable[[jax.Array], jax.Array]
F8_MAX = 448.0   # largest finite float8_e4m3fn


def exact(a: jax.Array) -> jax.Array:
    return a.astype(jnp.float32)


def fp8(a: jax.Array) -> jax.Array:
    """Per-tensor scaled round trip through float8_e4m3fn: the precision
    below the configurations' bfloat16."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANTS = {"exact": exact, "fp8": fp8}


def mm(eq: str, a: jax.Array, b: jax.Array, q: Quant) -> jax.Array:
    return jnp.einsum(eq, q(a), q(b), precision=HI,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def layer_norm(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + \
        b.astype(jnp.float32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 *
                                      (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
