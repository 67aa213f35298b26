"""Sharding plans: logical param axes -> mesh PartitionSpecs + ModelRuntime.

``models/*`` annotate every param/cache leaf with logical axis names; this
module is the single place where those names meet the mesh. It also builds
the ``ModelRuntime`` injection (sharding-constraint hook, flash-decoding
attention, ETP MoE) for a given (config, shape, mesh).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import (DECODE, ModelConfig, ParallelConfig,
                                ShapeConfig)
from repro.distributed.mesh import batch_axes, model_axis_size

# logical axis name -> mesh axis ("__batch__" resolves to the batch axes)
LOGICAL_RULES: Dict[Optional[str], Optional[str]] = {
    None: None,
    "vocab": "model",
    "embed_shard": "model",       # in_embed d_model dim (local-gather lookup)
    "heads": "model",
    "mlp": "model",
    "rglru": "model",
    "rglru_heads": "model",
    "ssm_heads": "model",
    "ssm_flat": "model",
    "expert_slots": "model",
    "kv_batch": "__batch__",
    "kv_seq": "model",
    "zero_flat": "__all__",       # flattened optimizer blocks: all axes
    "expert_slots_dp": "__batch__",  # 2D expert parallelism (training)
}


def spec_to_pspec(spec: Tuple, mesh: Mesh,
                  overrides: Optional[Dict[str, str]] = None) -> P:
    axes = []
    for name in spec:
        tgt = (overrides or {}).get(name, LOGICAL_RULES.get(name))
        if tgt == "__batch__":
            axes.append(batch_axes(mesh) or None)
        elif tgt == "__all__":
            axes.append(tuple(a for a in mesh.axis_names
                              if mesh.shape[a] > 1) or None)
        elif tgt is not None and tgt in mesh.axis_names and \
                mesh.shape[tgt] > 1:
            axes.append(tgt)
        else:
            axes.append(None)
    return P(*axes)


def _axes_size(entry, mesh: Mesh) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def _fit_pspec(pspec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the array dim (e.g. a
    batch=1 long-context cell cannot be data-sharded)."""
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    out = []
    for e, s in zip(entries, shape):
        out.append(e if s % _axes_size(e, mesh) == 0 else None)
    return P(*out)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and \
        all(isinstance(e, (str, type(None))) for e in x)


def tree_shardings(shapes, specs, mesh: Mesh, zero1: bool = False,
                   overrides: Optional[Dict[str, str]] = None):
    """NamedShardings for a (ShapeDtypeStruct tree, logical-axes tree) pair.

    zero1: additionally shard the first divisible replicated dim over the
    batch (data) axes — optimizer-state sharding.
    """
    baxes = batch_axes(mesh)
    dp = 1
    for a in baxes:
        dp *= mesh.shape[a]

    def one(sds, spec):
        ps = _fit_pspec(spec_to_pspec(spec, mesh, overrides), sds.shape,
                        mesh)
        if zero1 and dp > 1:
            entries = list(ps) + [None] * (len(sds.shape) - len(ps))
            used = set()
            for e in entries:
                for a in (e if isinstance(e, tuple) else (e,)):
                    if a:
                        used.add(a)
            if not used.intersection(baxes):
                for i, (e, s) in enumerate(zip(entries, sds.shape)):
                    if e is None and s % dp == 0 and s > 0:
                        entries[i] = baxes if len(baxes) > 1 else baxes[0]
                        ps = P(*entries)
                        break
        return NamedSharding(mesh, ps)

    return jax.tree.map(one, shapes, specs, is_leaf=lambda x: _is_spec(x))


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sharding plan for one (model, shape, mesh) cell."""
    mesh: Mesh
    cfg: ModelConfig
    shape: ShapeConfig
    parallel: ParallelConfig

    @property
    def tp(self) -> int:
        return model_axis_size(self.mesh)

    @property
    def batch(self) -> Tuple[str, ...]:
        return batch_axes(self.mesh)

    # ---- activation specs ----
    def resid_spec(self) -> P:
        if self.parallel.seq_parallel and self.shape.kind != DECODE:
            return P(self.batch or None, "model", None)
        return P(self.batch or None, None, None)

    def tokens_spec(self) -> P:
        return P(self.batch or None, None)

    def logits_spec(self) -> P:
        return P(self.batch or None, None, "model")

    def constrain(self, x, kind: str):
        if self.tp == 1 and not self.batch:
            return x
        if kind == "resid" and x.ndim == 3:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, self.resid_spec()))
        if kind == "logits":
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, self.logits_spec()))
        return x

    # ---- runtime (injection into models/transformer) ----
    def runtime(self):
        from repro.distributed import decode_attn as da
        from repro.distributed import moe_parallel as mp
        from repro.models.moe import can_use_2d, etp_supported
        from repro.models.transformer import ModelRuntime
        tp = self.tp
        decode_fn = None
        moe_fn = None
        moe_dp = 1
        baxes = self.batch
        dp = 1
        for a in baxes:
            dp *= self.mesh.shape[a]
        etp = self.cfg.moe is not None and etp_supported(self.cfg)
        if tp > 1 or baxes:
            if self.shape.kind == DECODE:
                decode_fn = da.make_flash_decode(self.mesh)
                if etp:
                    moe_fn = mp.make_moe_replicated(self.mesh,
                                                    expert_2d=True)
            elif etp:
                last = self.mesh.shape[baxes[-1]] if baxes else 0
                if can_use_2d(self.cfg, tp, dp, last):
                    moe_fn = mp.make_moe_etp2d(self.mesh)
                    moe_dp = dp
                else:
                    moe_fn = mp.make_moe_etp(self.mesh)
        return ModelRuntime(
            tp=tp,
            attn_impl=self.parallel.attn_impl,
            moe_fn=moe_fn,
            decode_attn_fn=decode_fn,
            constrain=self.constrain,
            remat=(self.parallel.remat != "none"),
            remat_policy="dots" if self.parallel.remat == "dots" else "full",
            max_seq=self.shape.seq_len,
            moe_dp=moe_dp,
        )
