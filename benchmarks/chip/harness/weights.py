"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the plain reference
regenerates the very same arrays from the same seed and so takes nothing
that the program made. The tree's layout (names, shapes, dtypes) is the
program's; the values follow the rules below, by leaf name. Matrices are
normal with std 1/sqrt(fan_in) over their true input dims; norm scales,
skip gains and biases are drawn around their usual values so that a path
that drops one of them shows in the output.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> (rule, number of leading per-layer dims that are fan-in)
RULES: Dict[str, Tuple[str, int]] = {
    "in_embed": ("embed", 0),
    "out_embed": ("fan_in", 1),
    "wq": ("fan_in", 1), "wk": ("fan_in", 1), "wv": ("fan_in", 1),
    "wo": ("fan_in", 2),
    "w1": ("fan_in", 1), "w2": ("fan_in", 1), "w3": ("fan_in", 1),
    "wz": ("fan_in", 1), "wx": ("fan_in", 1), "wbc": ("fan_in", 1),
    "wdt": ("fan_in", 1), "w_out": ("fan_in", 2),
    "conv_x": ("conv", 0), "conv_bc": ("conv", 0),
    "a_log": ("ssm_a", 0), "dt_bias": ("ssm_dt", 0),
    "d_skip": ("gain", 0), "norm_w": ("gain", 0), "w": ("gain", 0),
    "b": ("bias", 0), "b1": ("bias", 0), "b2": ("bias", 0),
    "bq": ("bias", 0), "bk": ("bias", 0), "bv": ("bias", 0),
    "bo": ("bias", 0),
}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (more than 32 bits is fine)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(key, name: str, shape, dtype, stacked: bool) -> jax.Array:
    rule, fan_dims = RULES[name]
    lead = 1 if stacked else 0
    if rule == "embed":
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif rule == "fan_in":
        fan = math.prod(shape[lead:lead + fan_dims])
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan)
    elif rule == "conv":
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-1])
    elif rule == "ssm_a":     # A = -exp(a_log), A in [1, 16]
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif rule == "ssm_dt":    # softplus(dt_bias) = dt in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif rule == "gain":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:                     # bias
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def make(seed: int, shapes: Any, shardings: Any = None) -> Any:
    """Arrays shaped like ``shapes`` (a tree of ShapeDtypeStruct in the
    program's layout), drawn from ``seed`` on the device."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(paths):
            names = [getattr(k, "key", None) for k in path]
            stacked = any(isinstance(n, str) and n.startswith("group")
                          for n in names)
            out.append(_leaf(jax.random.fold_in(key, i), names[-1],
                             sd.shape, sd.dtype, stacked))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build) if shardings is None else \
        jax.jit(build, out_shardings=shardings)
    return fn(seed_key(seed))
