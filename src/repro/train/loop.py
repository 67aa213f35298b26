"""Training loop: steps + the paper's systemware hooks.

Per step: train_step (jit) -> heartbeat -> straggler stats. Every
``ckpt_every`` steps the loop copies the state to the host (span
``train.ckpt.d2h``) and hands it to the TieredIO engine via
``save_async`` (span ``train.ckpt.submit``) — even the node-local pmem write now
overlaps the next step's compute; the loop only ever blocks on slot
backpressure (a write two checkpoints old still in flight). In-flight
futures are joined exactly twice: at clean shutdown, and (via
``TieredIO.quiesce``) before a failure restore so the checkpoint index
is stable and errors from dead nodes are swallowed — the paper's §II-A
resume story over the §V-B data scheduler.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np

from repro.core.cluster import SimCluster
from repro.core.resilience import StragglerDetector


@dataclass
class LoopConfig:
    steps: int = 20
    ckpt_every: int = 5
    delta_ckpt: bool = False     # incremental checkpoints vs last full
    drain_every: int = 0         # 0 = drain only at the end
    heartbeat_node: str = "node0"
    # run the continuous RepairDaemon alongside training: node losses
    # are repaired in the background (rate-limited below foreground
    # I/O) instead of waiting for the fault hook / next recovery point
    repair_daemon: bool = False
    daemon_poll_s: float = 0.02


@dataclass
class LoopState:
    step: int = 0
    losses: List[float] = field(default_factory=list)
    # host seconds per step, up to the loss on the host (so the device
    # finished the step)
    step_seconds: List[float] = field(default_factory=list)
    ckpt_seconds: List[float] = field(default_factory=list)
    recovered_at: List[int] = field(default_factory=list)
    # acknowledged durability of the final checkpoint at shutdown
    # ("LOCAL" / "REPLICATED" / "DRAINED"; None if no checkpoint ran) —
    # a run report can now say what a node loss right after exit costs
    final_ckpt_durability: Optional[str] = None


def run(train_step_fn: Callable, params, opt_state,
        batches: Iterator[Dict[str, np.ndarray]], cluster: SimCluster,
        loop_cfg: LoopConfig,
        fault_at: Optional[int] = None) -> LoopState:
    """Drive training with checkpoint/restart. ``fault_at`` kills a node
    after that step (test/demo hook) to exercise recovery.

    ``train_step_fn`` may donate its params and opt_state: the loop reads
    only the state a step returned, and a restore lands on the layout the
    run started on (the shardings are taken before the first step)."""
    state = LoopState()
    layout = jax.tree.map(lambda x: x.sharding, (params, opt_state))
    sd = StragglerDetector()
    last_full = None
    last_ticket = None
    dead_nodes: set = set()
    daemon = cluster.start_repair_daemon(poll_s=loop_cfg.daemon_poll_s) \
        if loop_cfg.repair_daemon else None
    try:
        for step, batch in enumerate(batches):
            t0 = time.time()
            params, opt_state, metrics = \
                train_step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            state.losses.append(loss)
            state.step = step + 1
            dt = time.time() - t0
            state.step_seconds.append(dt)
            for nid in cluster.node_ids:
                if nid in dead_nodes:
                    continue  # a forgotten victim must STAY forgotten:
                    # recording it again would re-skew the fleet median
                cluster.heartbeat.beat(nid, step)
                sd.record(nid, dt)
            if (step + 1) % loop_cfg.ckpt_every == 0:
                # fail fast: a checkpoint that failed to COMMIT must
                # surface now, not after hours of unprotected training
                cluster.tiered.raise_if_failed()
                t0 = time.time()
                with cluster.obs.span("train.ckpt.d2h", step=step + 1):
                    host_state = {
                        "params": jax.tree.map(np.asarray, params),
                        "opt": jax.tree.map(np.asarray, opt_state)}
                base = last_full if loop_cfg.delta_ckpt else None
                with cluster.obs.span("train.ckpt.submit", step=step + 1):
                    last_ticket = cluster.tiered.save_async(
                        step + 1, host_state, base_step=base,
                        drain=bool(loop_cfg.drain_every))
                if not loop_cfg.delta_ckpt or last_full is None:
                    last_full = step + 1
                # what the step pays: the submit (+ slot backpressure)
                state.ckpt_seconds.append(time.time() - t0)
            if fault_at is not None and step + 1 == fault_at:
                # simulate node loss at a replication-quiescent point:
                # join in-flight saves/replicas BEFORE the kill so the
                # hook deterministically exercises buddy recovery. (A
                # failure landing inside the replication window instead
                # loses the un-replicated tail;
                # restore_latest_recoverable walks back to the newest
                # fully-replicated checkpoint in that case.) Going
                # through recovery.quiesce_inflight records any
                # swallowed errors on the recovery object for forensics.
                cluster.recovery.quiesce_inflight()
                victim = cluster.node_ids[-1]
                # the victim's stale step times must not keep skewing
                # the fleet median the survivors are judged by
                sd.forget(victim)
                dead_nodes.add(victim)
                cluster.kill_node(victim)
                restored, manifest = \
                    cluster.checkpointer.restore_latest_recoverable(
                        lost_nodes=[victim])
                # restore the replication factor before resuming: every
                # acked shard the victim homed or buddied is down to
                # one copy, and the CONTINUED run must survive the next
                # loss too. With the daemon running, the sweep already
                # started in the background — join its ledger; a sweep
                # that cannot converge in time (or no daemon) falls
                # back to an inline repair, because continuing on
                # single copies would break the durability promise.
                if daemon is None or \
                        not daemon.wait_for([victim], timeout=60.0):
                    cluster.tiered.repair([victim])
                params, opt_state = jax.device_put(
                    (restored["params"], restored["opt"]), layout)
                state.recovered_at.append(step + 1)
                fault_at = None
    finally:
        if daemon is not None:
            cluster.stop_repair_daemon()
    # clean shutdown: strict barrier — a run whose checkpoints silently
    # all failed must not report success
    cluster.tiered.join()
    cluster.checkpointer.wait_async()
    if last_ticket is not None:
        # after the barrier this reflects the PERSISTED ack map
        state.final_ckpt_durability = last_ticket.durability()
    return state
