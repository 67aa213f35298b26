"""Pipeline parallelism over the 'pod' axis (optional role, GPipe schedule).

The multi-pod mesh's "pod" axis defaults to data-parallel; this module lets
it act as a pipeline axis instead: layer groups are stacked [n_stages, ...]
and sharded P('pod'); microbatches stream through stages with
collective_permute handoffs. Fill/drain bubbles are the standard
(n_stages - 1) / (n_micro + n_stages - 1) fraction.

This is exercised by tests/benchmarks as a scaling option; the default
dry-run keeps pod = DP (see DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distributed.mesh import batch_axes


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params,
                   x_micro: jax.Array, axis: str = "pod") -> jax.Array:
    """Run ``stage_fn(params_stage, x) -> x`` over pipeline stages.

    stage_params: pytree with leading [n_stages] dim (sharded on ``axis``).
    x_micro: [n_micro, mb, ...] microbatched activations (replicated on
    ``axis``). Returns [n_micro, mb, ...] outputs of the LAST stage.
    """
    n_stages = mesh.shape[axis]
    other = tuple(a for a in mesh.axis_names if a != axis)

    def inner(params, xm):
        # params: leading dim 1 (my stage); xm [n_micro, mb, ...] replicated
        my_params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        n_micro = xm.shape[0]
        total = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            take = jnp.clip(t, 0, n_micro - 1)
            buf = jnp.where(sid == 0, xm[take], buf)
            y = stage_fn(my_params, buf)
            # last stage emits microbatch t - (n_stages - 1)
            emit_idx = t - (n_stages - 1)
            do_emit = (sid == n_stages - 1) & (emit_idx >= 0)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], jnp.maximum(emit_idx, 0), axis=0),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(total))
        # broadcast last stage's outputs to all stages for a clean out_spec
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda x: False), P())
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)
