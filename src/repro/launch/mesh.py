"""Production mesh builders (assignment-mandated shapes).

Functions, not module-level constants, so importing never touches jax
device state. Every axis is ``AxisType.Auto``: shardings are propagated
by GSPMD from the arguments' and constraints' specs.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """A mesh over ``devices`` (default: all local devices) with all
    axes Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
