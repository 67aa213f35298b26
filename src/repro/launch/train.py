"""End-to-end training driver: --arch <id> [--smoke] on the local devices.

Builds the model and the train step on a (1, n_devices) ("data", "model")
mesh, with params and optimizer state placed by their logical axes and
donated to the step, wires the pmem cluster (staged data, async
node-local checkpoints, heartbeats), and runs the loop. By default it
trains the published config; --smoke trains the reduced one (CPU-runnable).
"""
from __future__ import annotations

import argparse
import dataclasses
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ModelConfig, ParallelConfig, ShapeConfig, registry
from repro.core.cluster import SimCluster
from repro.core.pmem import scratch_root
from repro.data.pipeline import StagedDataset
from repro.distributed import sharding as shd
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer as tfm
from repro.train import loop as train_loop
from repro.train import optimizer as opt
from repro.train import train_step as ts


@dataclasses.dataclass
class Trainer:
    """A model's train state placed on a mesh, and its jitted step. The
    step donates ``params`` and ``opt_state``: after a call only its
    outputs are valid."""
    mesh: Mesh
    step_fn: Callable
    params: Any
    opt_state: Any
    shardings: Tuple[Any, Any]   # NamedSharding trees (params, opt_state)

    def state_bytes(self) -> int:
        """Bytes of one checkpoint of (params, opt_state)."""
        return sum(x.nbytes for x in
                   jax.tree.leaves((self.params, self.opt_state)))


def build_trainer(cfg: ModelConfig, shape: ShapeConfig, *,
                  devices: Optional[Sequence] = None,
                  lr: float = 1e-3) -> Trainer:
    """Initialise ``cfg`` from seed 0 straight onto a (1, len(devices))
    ("data", "model") mesh (default: every local device), sharded by the
    params' logical axes as the dry-run shards them."""
    devices = list(devices) if devices is not None else jax.devices()
    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    plan = shd.Plan(mesh, cfg, shape, ParallelConfig(attn_impl="blockwise"))
    rt = plan.runtime()
    adamw = opt.AdamWConfig(lr=lr, warmup=10)

    p_shapes, p_specs = tfm.abstract_params(cfg, rt)
    o_shapes = jax.eval_shape(lambda p: opt.init_opt_state(p, adamw),
                              p_shapes)
    p_sh = shd.tree_shardings(p_shapes, p_specs, mesh)
    o_sh = shd.tree_shardings(o_shapes, opt.opt_state_specs(p_specs, adamw),
                              mesh, zero1=True)
    params = jax.jit(lambda k: tfm.init_params(k, cfg, rt)[0],
                     out_shardings=p_sh)(jax.random.PRNGKey(0))
    opt_state = jax.jit(lambda p: opt.init_opt_state(p, adamw),
                        out_shardings=o_sh)(params)
    step_fn = jax.jit(
        ts.make_train_step(cfg, rt, plan.constrain, adamw, ce_chunk=128),
        in_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
        out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    return Trainer(mesh, step_fn, params, opt_state, (p_sh, o_sh))


def build_cluster(root: Path, cfg: ModelConfig, shape: ShapeConfig, *,
                  nodes: int, state_bytes: int, delta: bool = False
                  ) -> Tuple[SimCluster, StagedDataset]:
    """The pmem cluster a run checkpoints to and its staged dataset. Each
    node's pool has room for both rotating slots of a whole state, home
    and buddy copies, even if every leaf lands on that one node."""
    cluster = SimCluster(root, n_nodes=nodes, delta=delta,
                         pmem_capacity=max(1 << 32, 4 * state_bytes))
    data = StagedDataset(cluster, cfg, shape, n_shards=4,
                         seqs_per_shard=max(shape.global_batch * 2, 16))
    return cluster, data


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--delta-ckpt", action="store_true")
    ap.add_argument("--fault-at", type=int, default=None)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--root", default=None,
                    help="pmem root (default: a fresh scratch directory, "
                         "removed at exit)")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = registry.get_smoke_config(args.arch) if args.smoke \
        else registry.get_config(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    tr = build_trainer(cfg, shape, lr=args.lr)
    root = Path(args.root) if args.root else scratch_root("repro_train_")
    cluster, data = build_cluster(root, cfg, shape, nodes=args.nodes,
                                  state_bytes=tr.state_bytes(),
                                  delta=args.delta_ckpt)
    lc = train_loop.LoopConfig(steps=args.steps,
                               ckpt_every=args.ckpt_every,
                               delta_ckpt=args.delta_ckpt)
    try:
        t0 = time.time()
        state = train_loop.run(tr.step_fn, tr.params, tr.opt_state,
                               data.batches(args.steps), cluster, lc,
                               fault_at=args.fault_at)
        dt = time.time() - t0
    finally:
        cluster.shutdown()
        if not args.root:
            shutil.rmtree(root, ignore_errors=True)
    print(f"arch={cfg.name} steps={state.step} "
          f"loss {state.losses[0]:.3f} -> {state.losses[-1]:.3f} "
          f"({dt:.1f}s, ckpt avg {np.mean(state.ckpt_seconds or [0]):.3f}s, "
          f"recoveries={state.recovered_at})")
    assert state.losses[-1] < state.losses[0], "loss did not decrease"
    return state


if __name__ == "__main__":
    main()
