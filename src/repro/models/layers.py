"""Common layers: norms, RoPE, MLPs, embeddings, and the ParamBuilder.

Parameters are plain nested-dict pytrees. Alongside every param tree we build
a mirror tree of *logical axis* tuples (e.g. ``("embed", "heads", None)``)
which ``distributed/sharding.py`` maps onto mesh axes. This keeps the model
code mesh-free while still giving GSPMD full sharding information.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
Specs = Dict[str, Any]


class ParamBuilder:
    """Collects (param, logical-axes) pairs under a PRNG key."""

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16):
        self._key = key
        self.dtype = dtype
        self.params: Params = {}
        self.specs: Specs = {}

    def _next(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(self, name: str, shape: Tuple[int, ...], axes: Tuple,
              init: str = "normal", scale: float = 0.02) -> jax.Array:
        assert len(axes) == len(shape), (name, shape, axes)
        if init == "normal":
            w = jax.random.normal(self._next(), shape, jnp.float32) * scale
        elif init == "fan_in":
            fan = shape[0] if len(shape) else 1
            w = jax.random.normal(self._next(), shape, jnp.float32)
            w = w / math.sqrt(max(fan, 1))
        elif init == "zeros":
            w = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            w = jnp.ones(shape, jnp.float32)
        elif init == "lru_lambda":  # RG-LRU Λ init: a in [0.9, 0.999]
            u = jax.random.uniform(self._next(), shape, jnp.float32,
                                   minval=0.9 ** 2, maxval=0.999 ** 2)
            # a = exp(-c*softplus(Λ)); choose Λ s.t. softplus(Λ) = -log(a)/c
            c = 8.0
            sp = -jnp.log(u) / (2.0 * c)  # u = a^2
            w = jnp.log(jnp.expm1(jnp.maximum(sp, 1e-8)))
        elif init == "ssm_a":  # mamba2 A_log init: A in [1, 16]
            u = jax.random.uniform(self._next(), shape, jnp.float32,
                                   minval=1.0, maxval=16.0)
            w = jnp.log(u)
        elif init == "ssm_dt":  # dt bias: softplus^-1 of dt in [1e-3, 1e-1]
            u = jax.random.uniform(self._next(), shape, jnp.float32,
                                   minval=math.log(1e-3), maxval=math.log(1e-1))
            dt = jnp.exp(u)
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(init)
        keep_f32 = init in ("lru_lambda", "ssm_a", "ssm_dt")
        w = w.astype(jnp.float32 if keep_f32 else self.dtype)
        self.params[name] = w
        self.specs[name] = tuple(axes)
        return w

    def child(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self._next(), self.dtype)
        self.params[name] = sub.params
        self.specs[name] = sub.specs
        return sub

    def stacked(self, name: str, n: int,
                init_fn: Callable[[jax.Array], Tuple[Params, Specs]]) -> None:
        """vmap an init fn over ``n`` keys -> leaves with leading layer dim."""
        keys = jax.random.split(self._next(), n)
        params, specs = init_fn(keys[0])  # specs are static; take from one
        stacked = jax.vmap(lambda k: init_fn(k)[0])(keys)
        self.params[name] = stacked
        self.specs[name] = jax.tree.map(
            lambda s: (None,) + tuple(s), specs,
            is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
             gemma_scale: bool = False) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if gemma_scale \
        else w.astype(jnp.float32)
    return (y * scale).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               *, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def init_norm(pb: ParamBuilder, name: str, d: int, kind: str,
              gemma_scale: bool) -> None:
    c = pb.child(name)
    c.param("w", (d,), (None,), init="zeros" if gemma_scale else "ones")
    if kind == "layernorm":
        c.param("b", (d,), (None,), init="zeros")


def apply_norm(p: Params, x: jax.Array, kind: str, gemma_scale: bool,
               rms_eps: float = 1e-6) -> jax.Array:
    if kind == "layernorm":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"], eps=rms_eps, gemma_scale=gemma_scale)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    if theta <= 0:
        return x
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    sin = jnp.sin(ang)[..., None, :]  # broadcast over heads
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(pb: ParamBuilder, d: int, f: int, kind: str, bias: bool) -> None:
    gated = kind in ("swiglu", "geglu")
    pb.param("w1", (d, f), (None, "mlp"), init="fan_in")
    if gated:
        pb.param("w3", (d, f), (None, "mlp"), init="fan_in")
    pb.param("w2", (f, d), ("mlp", None), init="fan_in")
    if bias:
        pb.param("b1", (f,), ("mlp",), init="zeros")
        pb.param("b2", (d,), (None,), init="zeros")


def apply_mlp(p: Params, x: jax.Array, kind: str) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, p["w1"])
    if "b1" in p:
        h = h + p["b1"]
    if kind == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("...d,df->...f", x, p["w3"])
    elif kind == "geglu":
        h = jax.nn.gelu(h) * jnp.einsum("...d,df->...f", x, p["w3"])
    elif kind == "gelu":
        h = jax.nn.gelu(h)
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(kind)
    y = jnp.einsum("...f,fd->...d", h, p["w2"])
    if "b2" in p:
        y = y + p["b2"]
    return y


# ---------------------------------------------------------------------------
# Embeddings (vocab padded to a TP-friendly multiple; untied in/out)
# ---------------------------------------------------------------------------

def init_embeddings(pb: ParamBuilder, vocab_padded: int, d: int) -> None:
    # both tables vocab-sharded; GSPMD lowers the in_embed gather to masked
    # local lookups + all-reduce (col-sharding trips the SPMD partitioner
    # under remat+scan on this XLA version).
    pb.param("in_embed", (vocab_padded, d), ("vocab", None),
             init="normal", scale=0.02)
    pb.param("out_embed", (d, vocab_padded), (None, "vocab"), init="fan_in")


def embed_tokens(p: Params, tokens: jax.Array) -> jax.Array:
    return p["in_embed"][tokens]


def conv1d_channels(x: jax.Array, w: jax.Array,
                    carry: Optional[jax.Array] = None) -> jax.Array:
    """Causal depthwise temporal conv. x: [B, S, C]; w: [C, K].

    With ``carry`` [B, K-1, C] (previous tokens) prepended; else zero-pad.
    """
    k = w.shape[-1]
    if carry is None:
        pad = jnp.zeros(x.shape[:-2] + (k - 1, x.shape[-1]), x.dtype)
    else:
        pad = carry.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=-2)  # [B, S+K-1, C]
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[..., i:i + x.shape[-2], :] * w[:, i]
    return out
