"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the plain reference
regenerates the very same arrays from the same seed and so takes nothing
that the program made. The tree's layout (names, shapes, dtypes) is the
program's; the values follow the rules below, found by the leaf's place
in the tree: ``"<parent>/<leaf>"`` first, then the leaf's name. Matrices
are normal with std 1/sqrt(fan_in) over the dims they contract, never
over the stacked layer, slot or expert dims; norm scales, skip gains and
biases are drawn around their usual values so that a path that drops one
of them shows in the output.

A configuration's reference module may export ``WEIGHT_RULES``, rules as
below, for the leaves this table lacks; a leaf that both resolve is
refused, so a reference cannot redraw what the table draws.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# (rule, fan_dims). "fan_in": fan_dims dims after the stacked layer dim,
# or every dim but the last where fan_dims is -1; "expert": the dim before
# the last (an expert matrix [..., slots, experts, in, out]).
Rule = Tuple[str, int]
RULES: Dict[str, Rule] = {
    "in_embed": ("embed", 0),
    "out_embed": ("fan_in", 1),
    "wq": ("fan_in", 1), "wk": ("fan_in", 1), "wv": ("fan_in", 1),
    "wo": ("fan_in", -1),
    "w1": ("fan_in", 1), "w2": ("fan_in", 1), "w3": ("fan_in", 1),
    "wz": ("fan_in", 1), "wx": ("fan_in", 1), "wbc": ("fan_in", 1),
    "wdt": ("fan_in", 1), "w_out": ("fan_in", -1),
    "conv_x": ("conv", 0), "conv_bc": ("conv", 0),
    "a_log": ("ssm_a", 0), "dt_bias": ("ssm_dt", 0),
    "d_skip": ("gain", 0), "norm_w": ("gain", 0), "w": ("gain", 0),
    "b": ("bias", 0), "b1": ("bias", 0), "b2": ("bias", 0),
    "bq": ("bias", 0), "bk": ("bias", 0), "bv": ("bias", 0),
    "bo": ("bias", 0),
    # mixture of experts (models/moe.py): router [D, E]; experts
    # wi, wg [slots, E_loc, D, F] and wo [slots, E_loc, F, D]
    "router": ("fan_in", 1),
    "mlp/wi": ("expert", 0), "mlp/wg": ("expert", 0),
    "mlp/wo": ("expert", 0),
}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (more than 32 bits is fine)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _draw(key, rule: Rule, shape, lead: int) -> jax.Array:
    kind, fan_dims = rule
    if kind == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind in ("fan_in", "expert"):
        if kind == "expert":
            fan = shape[-2]
        elif fan_dims < 0:
            fan = math.prod(shape[lead:-1])
        else:
            fan = math.prod(shape[lead:lead + fan_dims])
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan)
    if kind == "conv":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-1])
    if kind == "ssm_a":       # A = -exp(a_log), A in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "ssm_dt":      # softplus(dt_bias) = dt in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "gain":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(f"unknown weight rule {kind!r}")


def _lookup(names, rules: Dict[str, Rule]) -> Optional[Rule]:
    leaf = names[-1]
    placed = f"{names[-2]}/{leaf}" if len(names) > 1 else None
    for key in (placed, leaf):
        if key in rules:
            return rules[key]
    return None


def rule_for(names, extra: Optional[Dict[str, Rule]] = None) -> Rule:
    """The rule of the leaf at ``names`` (its path's keys): the table's,
    or else the reference's ``WEIGHT_RULES`` (``extra``)."""
    where = "/".join(map(str, names))
    own, theirs = _lookup(names, RULES), _lookup(names, extra or {})
    if own is not None and theirs is not None:
        raise ValueError(f"WEIGHT_RULES may only add leaves; {where} is "
                         f"drawn by the harness's table")
    if own is None and theirs is None:
        raise KeyError(f"no weight rule for leaf {names[-1]!r} at {where}: "
                       f"add it, or '<parent>/<leaf>', to the reference's "
                       f"WEIGHT_RULES")
    return own if own is not None else theirs


def make(seed: int, shapes: Any, shardings: Any = None,
         rules: Optional[Dict[str, Rule]] = None) -> Any:
    """Arrays shaped like ``shapes`` (a tree of ShapeDtypeStruct in the
    program's layout), drawn from ``seed`` on the device. ``rules`` are
    the configuration's ``WEIGHT_RULES``."""
    build = builder(shapes, rules)
    fn = jax.jit(build) if shardings is None else \
        jax.jit(build, out_shardings=shardings)
    return fn(seed_key(seed))


def builder(shapes: Any, rules: Optional[Dict[str, Rule]] = None):
    """The traceable function ``key -> tree`` that ``make`` jits; leaf
    ``i`` of the flattened tree is drawn from ``fold_in(key, i)``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, sd in paths:
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        lead = int(any(isinstance(n, str) and n.startswith("group")
                       for n in names))
        plan.append((rule_for(names, rules), sd.shape, sd.dtype, lead))

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), rule, shape, lead).astype(dt)
            for i, (rule, shape, dt, lead) in enumerate(plan)])
    return build
