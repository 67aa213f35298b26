"""Per-node asynchronous data scheduler (the paper's §V-B).

A daemon per node moves data without blocking the application:
  stage_in   - external store -> node pmem (burst-buffer pre-load, Fig. 8)
  drain      - node pmem -> external store (async checkpoint flush)
  replicate  - node pmem -> buddy-node pmem (the paper's remote B-APM
               access over the fabric; used for failure tolerance)

Work items run on per-node worker threads with priority queues; idle nodes
can *steal* stage-in work from overloaded ones (straggler mitigation,
core/resilience.py). Byte counters per channel feed the benchmarks.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from repro.analysis.annotations import rehydration_entry
# SupersededError and _check_expect_meta live with the copy primitives
# in object_store now; re-exported here for the existing import sites
from repro.core.object_store import (PMemObjectStore,  # noqa: F401
                                     SupersededError, _check_expect_meta,
                                     copy_object, export_object,
                                     import_object, is_wire_object)
from repro.obs.metrics import Registry, StatsView


class ExternalStore:
    """The 'external high performance filesystem' of Fig. 4 (emulated as a
    directory with configurable artificial bandwidth for benchmarks)."""

    def __init__(self, root: Path, bandwidth_bytes_s: Optional[float] = None):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.bandwidth = bandwidth_bytes_s

    def _throttle(self, nbytes: int) -> None:
        if self.bandwidth:
            time.sleep(nbytes / self.bandwidth)

    def put(self, name: str, tree) -> None:
        import pickle
        p = self.root / (name.replace("/", "_") + ".pkl")
        data = pickle.dumps(tree)
        self._throttle(len(data))
        tmp = p.with_suffix(".tmp")
        tmp.write_bytes(data)
        tmp.replace(p)

    def get(self, name: str):
        import pickle
        p = self.root / (name.replace("/", "_") + ".pkl")
        data = p.read_bytes()
        self._throttle(len(data))
        return pickle.loads(data)

    def exists(self, name: str) -> bool:
        return (self.root / (name.replace("/", "_") + ".pkl")).exists()


@dataclass(order=True)
class _Task:
    priority: int
    seq: int
    fn: Callable = field(compare=False)
    future: Future = field(compare=False)


class DataScheduler:
    """Async movement daemons over {node_id -> PMemObjectStore}."""

    def __init__(self, stores: Dict[str, PMemObjectStore],
                 external: ExternalStore, workers_per_node: int = 1,
                 obs=None):
        self.stores = stores
        self.external = external
        self.obs = obs
        self.queues: Dict[str, "queue.PriorityQueue[_Task]"] = {
            nid: queue.PriorityQueue() for nid in stores}
        # per-channel byte counters live in the telemetry registry;
        # ``stats`` keeps the legacy dict shape as a read-through view.
        # Workers update the internally-locked counters directly, which
        # retires the old unguarded ``self.stats[nid][...] += n`` writes
        reg = obs.registry if obs is not None else Registry()
        self._counters = {
            nid: {k: reg.counter(f"sched.{k}_bytes.{nid}")
                  for k in ("staged_in", "drained", "replicated")}
            for nid in stores}
        self.stats = {nid: StatsView(self._counters[nid])
                      for nid in stores}
        self._depth = {nid: reg.gauge(f"sched.queue_depth.{nid}")
                       for nid in stores}
        self._qwait = reg.histogram("sched.queue_wait_s")
        self._task_s = reg.histogram("sched.task_s")
        self._seq = 0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        for nid in stores:
            for w in range(workers_per_node):
                t = threading.Thread(target=self._worker, args=(nid,),
                                     daemon=True, name=f"dsched-{nid}-{w}")
                t.start()
                self._threads.append(t)

    # ---- worker loop with work stealing ----
    def _worker(self, nid: str) -> None:
        while not self._stop.is_set():
            task = self._next_task(nid)
            if task is None:
                time.sleep(0.002)
                continue
            try:
                task.future.set_result(task.fn())
            except Exception as e:  # surfaced via the future
                task.future.set_exception(e)

    def _next_task(self, nid: str) -> Optional[_Task]:
        try:
            return self.queues[nid].get_nowait()
        except queue.Empty:
            pass
        # steal from the deepest queue (straggler mitigation)
        victim = max(self.queues, key=lambda n: self.queues[n].qsize())
        if victim != nid and self.queues[victim].qsize() > 1:
            try:
                return self.queues[victim].get_nowait()
            except queue.Empty:
                return None
        return None

    def _submit(self, nid: str, fn: Callable, priority: int,
                label: str = "task",
                span: Optional[dict] = None) -> Future:
        fut: Future = Future()
        with self._lock:
            self._seq += 1
            seq = self._seq
        obs = self.obs
        t_enq = time.time()

        def run():
            # queue-depth/wait instruments + (when a caller threaded a
            # trace context through ``span=``) a child span bracketing
            # the task body on the executing node's flight ring
            self._qwait.observe(time.time() - t_enq)
            self._depth[nid].dec()
            sp = None
            if obs is not None and span is not None:
                sp = obs.begin(f"sched.{label}", node=nid, local=True,
                               trace=span.get("trace"),
                               parent=span.get("span", 0))
            t0 = time.time()
            try:
                out = fn()
            except Exception:
                self._task_s.observe(time.time() - t0)
                if sp is not None:
                    obs.end(sp, status="error")
                raise
            self._task_s.observe(time.time() - t0)
            if sp is not None:
                obs.end(sp)
            return out

        self._depth[nid].inc()
        self.queues[nid].put(_Task(priority, seq, run, fut))
        return fut

    # ---- public channels ----
    @rehydration_entry
    def stage_in(self, nid: str, external_name: str, obj_name: str,
                 version: int = 0, priority: int = 0,
                 meta: Optional[dict] = None,
                 on_complete: Optional[Callable[[Any], None]] = None,
                 span: Optional[dict] = None) -> Future:
        """External -> pmem pre-load. ``meta`` stamps the staged object
        (drain-tier rehydration stages a checkpoint shard back and must
        carry its step tag so restore's slot-reuse check still holds);
        ``on_complete`` runs inside the task once the pmem copy is
        durable — same ack discipline as replicate/drain. A wire payload
        (the drain channel's export format) ingests through
        ``import_object`` — leaf bytes land at manifest offsets with the
        carried manifest committed over them, no tree is ever built, and
        an encoded payload stays encoded (decoded on demand by readers);
        legacy pickled trees still go through ``put``."""
        def go():
            obj = self.external.get(external_name)
            if is_wire_object(obj):
                man = import_object(self.stores[nid], obj, obj_name,
                                    version, meta_update=meta)
            else:
                man = self.stores[nid].put(obj_name, obj, version,
                                           meta=meta)
            self._counters[nid]["staged_in"].inc(man["nbytes"])
            if on_complete is not None:
                on_complete(man)
            return man
        return self._submit(nid, go, priority, label="stage_in",
                            span=span)

    @rehydration_entry
    def drain(self, nid: str, obj_name: str, external_name: str,
              version: int = 0, priority: int = 1,
              delete_after: bool = False,
              expect_meta: Optional[dict] = None,
              on_complete: Optional[Callable[[Any], None]] = None,
              codec=None,
              span: Optional[dict] = None) -> Future:
        def go():
            # zero-copy export against ONE manifest snapshot: leaf bytes
            # stream out CRC-verified (a concurrent slot reuse raises
            # SupersededError instead of draining torn bytes) and are
            # serialized exactly ONCE, at the external boundary below;
            # ``expect_meta`` additionally pins the object identity
            # (e.g. checkpoint step) the caller intended. ``codec``
            # engages the delta-int8 wire codec on the exported bytes.
            wire = export_object(self.stores[nid], obj_name, version,
                                 expect_meta=expect_meta, codec=codec,
                                 obs=self.obs)
            self.external.put(external_name, wire)
            self._counters[nid]["drained"].inc(
                wire["manifest"]["nbytes"])
            if delete_after:
                self.stores[nid].delete(obj_name, version)
            # ack hook: runs INSIDE the task, after the external copy is
            # durable, so a recorded ack always describes a finished
            # transfer; if recording fails, the task (and its future)
            # fails and no one can mistake the step for drained.
            if on_complete is not None:
                on_complete(external_name)
            return external_name
        return self._submit(nid, go, priority, label="drain",
                            span=span)

    @rehydration_entry
    def replicate(self, src: str, obj_name: str, dst: str,
                  version: int = 0, priority: int = 2,
                  dst_name: Optional[str] = None,
                  expect_meta: Optional[dict] = None,
                  on_complete: Optional[Callable[[Any], None]] = None,
                  codec=None,
                  span: Optional[dict] = None) -> Future:
        """Copy an object to another node's pmem under ``dst_name``
        (defaults to replica/<src>/<obj> so it never shadows the
        destination's own objects). ``expect_meta`` pins the object
        identity the caller intended (e.g. the checkpoint step);
        ``on_complete`` runs inside the task once the replica is placed —
        the replication channel uses it to record per-node acks.
        ``codec`` engages the delta-int8 wire codec at the source (an
        already-encoded source raw-streams, never double-encodes)."""
        name = dst_name or f"replica/{src}/{obj_name}"

        def go():
            # zero-copy raw path against ONE manifest snapshot: region
            # bytes stream src -> dst in bounded chunks with a rolling
            # CRC checked against the manifest's own leaf CRCs, and the
            # source manifest commits verbatim on dst. No tree is ever
            # materialized and no CRC recomputed. A concurrent source
            # overwrite (checkpoint slot reuse racing this queued task)
            # raises SupersededError before the manifest commit — the
            # overwriting save queues its own replicate, so dropping
            # this one is benign (filtered at join). Destination-side
            # failures (dead pool, capacity) still propagate as real
            # errors. replica_of records the ORIGIN node: when repair
            # copies an existing replica off a surviving holder, the
            # source meta already carries the origin — preserve it, so
            # a twice-moved replica still says whose data it is.
            man = copy_object(
                self.stores[src], self.stores[dst], obj_name, version,
                dst_name=name, expect_meta=expect_meta, codec=codec,
                meta_update=lambda m: {
                    "replica_of": m.get("replica_of", src)},
                obs=self.obs)
            self._counters[src]["replicated"].inc(man["nbytes"])
            # ack hook after the replica is durable on ``dst`` — a
            # failure here fails the task, never records a false ack
            if on_complete is not None:
                on_complete(man)
            return man
        return self._submit(src, go, priority, label="replicate",
                            span=span)

    def run_job(self, nid: str, fn: Callable, priority: int = 3,
                span: Optional[dict] = None) -> Future:
        """Compute channel: run a workflow job body on node ``nid``'s
        worker. Jobs ride the same priority queues as data movement
        (movement outranks them) and the same work stealing, so ready
        jobs placed on different nodes genuinely run concurrently while
        an overloaded node's backlog can drain elsewhere."""
        return self._submit(nid, fn, priority, label="run_job",
                            span=span)

    def queue_depth(self, nid: str) -> int:
        return self.queues[nid].qsize()

    def shutdown(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
