"""TieredIO: one nonblocking engine over the B-APM memory hierarchy.

The paper's architecture (Fig. 4 data scheduler, Fig. 8 burst-buffer
staging) hinges on a single property: the application never blocks on a
tier slower than node-local B-APM. The repo grew three separate paths
with that goal — the shadow-slot checkpoint writer (core/checkpoint.py),
the drain/replicate/stage-in scheduler (core/data_scheduler.py) and the
SLM/DLM placement policies (core/tiering.py). ``TieredIO`` unifies them
behind one engine; the existing modules remain as thin policy layers.

API surface:

  save_async(step, tree)  -> SaveTicket (a Future): checkpoint writes
        happen on a dedicated I/O thread, double-buffered across the
        checkpointer's pmem slots, so the step-N write overlaps step-N+1
        compute. Post-commit drain/replicate futures ride on the ticket.
  offload(name, tree)     -> Future: generic object persist (serve KV /
        session state) through the DLM write-back cache.
  fetch(name) / prefetch(names): demand vs. anticipatory reads through
        the DLM cache — prefetch warms DRAM from pmem in the background
        and feeds the serve engine's cold KV pages.
  stage_in(names)         -> burst-buffer pre-load, external -> pmem,
        delegated to the data scheduler (hit-rate accounted).
  evict_cold(max_idle_s)  -> spill idle DRAM entries back to pmem.
  quiesce()               -> join every in-flight future, collecting
        (not raising) errors — the recovery path consumes in-flight
        work safely even when a buddy node died mid-replicate.

Backpressure: at most ``checkpointer.slots`` save tickets may be in
flight; submitting another blocks until the oldest commits. Combined
with the FIFO I/O thread this guarantees a slot is never overwritten
while a write to it is still in flight.

Replication channel and the ``SaveTicket.durability()`` contract
----------------------------------------------------------------
Checkpoint replicate/drain fan-out is a first-class TieredIO channel
(``ReplicationChannel``), not an inline step of the checkpointer: each
replicate/drain task records a per-node ACK into the manifest's ack map
(replicated to every live pool) the moment its transfer is durable.
``SaveTicket.durability()`` reports the acknowledged durability level:

  "PENDING"     the node-local commit has not finished yet;
  "FAILED"      the commit itself raised (nothing durable);
  "LOCAL"       committed to node-local pmem only — a node loss inside
                this window loses the step (recovery walks back);
  "REPLICATED"  every shard owner has an acknowledged buddy replica —
                any single node loss is recoverable over the fabric;
  "DRAINED"     every shard owner's drain to the external store has
                been acknowledged — survives cluster-wide pmem loss.

Levels are monotonic in that order; DRAINED ranks above REPLICATED even
when replication was disabled (external durability subsumes it). The
levels are derived from the PERSISTED ack map, not in-process futures,
so ``restore_latest_recoverable`` ranks steps by the same records after
a crash: a step whose ack map shows a lost shard owner without a replica
ack is skipped without a single store read.

DLM and dataset acks — the whole data plane, not just checkpoints
----------------------------------------------------------------
The same under-promise discipline covers the other two ack surfaces:

  * **DLM objects** (``offload``, serve KV/session spill): every buddy
    copy of ``dlm/<name>`` is registered through the replication channel
    and acknowledged into the replicated ack log ``dlm/ackslog``
    (``DLMAckRegistry`` — one ``MetaLog`` event per registration; a
    legacy ``dlm/acks.json`` from a pre-log deployment is read as the
    replay base). A dirty DLM write-back (eviction/flush of a mutated
    object) re-queues the buddy copy through the same path, so replicas
    never go stale behind the cache. Replica-fallback reads consult the
    acked targets first.
  * **Datasets** (``DatasetCatalog.publish``): the exchange channel's
    ack is appended to the catalog's record log (``acks.replica`` in
    the folded record).

Every ack records the full ``targets`` list of nodes holding an
acknowledged copy (legacy records carry a single ``target``; readers
treat it as a one-element list). An object is recoverable for a lost
set as long as ANY acked copy survives it.

The metadata log durability contract
------------------------------------
All three ack surfaces (and the catalog records and workflow journals)
persist through ``MetaLog`` (core/meta_log.py) — an append-only,
CRC-guarded record log replicated to every live pool — instead of
rewriting whole JSON blobs per update. The guarantees recovery relies
on:

  * **Committed-tail appends**: an update is one appended entry — entry
    bytes are flushed BEFORE the header's committed tail advances, so a
    torn append is invisible to replay; an ack visible to any reader is
    complete and durable on at least one pool.
  * **Union replay**: recovery replays the newest snapshot plus the
    seq-union of newer entries across all readable copies — an ack that
    landed on any surviving pool is never lost, exactly like the old
    per-pool JSON merge, at O(tail) instead of O(state) read cost.
  * **Acked compaction**: the log folds its prefix into a snapshot only
    after the snapshot file is written + flushed on every live pool;
    the prefix trim is a per-pool atomic rename. A crash anywhere in
    compaction leaves every pool with a log that replays the identical
    state (old log, or snapshot-equivalent new one).
  * **Per-pool cursors + reseed**: the writer tracks (epoch, tail) per
    copy; a pool that missed appends (down, then rejoined) is reseeded
    with a full snapshot before the next entry lands on it, so every
    synced copy is individually sufficient for replay.

The ranking in ``restore_latest_recoverable``, the repair scans and the
workflow resume decisions all read these logs' folded state — still
metadata-only, zero blind object-store probes.

Replica repair — restoring the replication factor after node loss
-----------------------------------------------------------------
Write-time replication alone decays: one node loss silently drops every
object it homed or buddied to a single copy, and a SECOND loss then
destroys data that was "REPLICATED" the whole time. ``RepairChannel``
(``TieredIO.repair(lost_nodes)``) closes that loop. It walks the three
ack surfaces — ``ckpt/acks_step<N>.json``, the catalog records' ``acks``
and ``dlm/acks.json`` — and for every object whose acked copies
intersect ``lost_nodes`` down to a SINGLE survivor, re-replicates the
surviving copy to a fresh live buddy through the data scheduler,
re-acking (with the pruned + extended ``targets`` list) only when the
new copy is durable. The scan is metadata-only: zero blind object-store
probes — the only object reads are the sources of the copies actually
made. Objects that were never acked are not repair's business (nothing
promised), and objects with zero surviving pmem copies are reported
(``unrepairable`` / ``drain_only``) rather than guessed at. A source
overwritten since its ack (checkpoint slot reuse) raises the benign
``SupersededError`` and is skipped. After ``repair``, every previously
acked object again tolerates any single node loss, and recovery after a
SECOND loss still decides from acks alone.

Continuous repair daemon and drain-tier rehydration
---------------------------------------------------
Recovery-point repair still leaves a WINDOW: between a node loss and the
next ``check_and_recover``/``resume``, every object the loss touched
sits on a single pmem copy. ``RepairDaemon`` closes it:

  * **Single-copy window**: the daemon polls ``Heartbeat.dead_nodes``
    every ``poll_s`` and sweeps on each NEW death, so the window shrinks
    from "until the next recovery point" to roughly one poll interval
    plus the (rate-limited) repair makespan
    (``benchmarks/bench_repair_daemon.py`` measures both). Sweeps are
    incremental — an already-handled death never re-triggers — and a
    membership change mid-sweep re-plans the cumulative dead set from
    the acks on the next poll (the persisted ``targets`` lists make
    re-planning idempotent and safe).
  * **Rehydration**: a checkpoint shard whose pmem copies ALL died but
    whose acked external drain survives (``drain_only``) is staged back
    from the external tier into a live pmem pool under its replica
    name, re-replicated to a second live node, and re-acked — restoring
    fast-tier redundancy, not just external survivability. The scan
    stays metadata-only: the ONLY external reads are the rehydration
    sources, and each ack is written only after its copy is durable
    (a crash between the two stages leaves a truthful single-target
    ack the next sweep extends).
  * **Rate limiting**: repair transfers run at a background scheduler
    priority (below stage-in/drain/replicate/compute) and at most
    ``max_inflight`` of them are queued/running at once, so a repair
    storm after a loss never swamps foreground saves or serving I/O
    (the report's ``peak_inflight`` records the high-water mark; the
    bench measures foreground step-time overhead under a storm).
  * **Ledger**: ``covers(lost)`` / ``report()`` let recovery points
    (``FailureRecovery.check_and_recover``,
    ``WorkflowScheduler.resume``, ``ServeEngine.repair``) reuse the
    daemon's already-completed sweeps instead of re-scanning from
    scratch; the daemon never quiesces foreground work, which is safe
    because acks only ever describe already-durable transfers.

Zero-copy byte-range data plane and the wire codec
--------------------------------------------------
Every channel above moves bytes through the object store's raw copy
primitives (``copy_object``/``export_object``/``import_object``) — no
transfer materializes a tree. The durability contract each channel
inherits from them:

  * **Replicate (pmem -> pmem)**: the backing region streams src -> dst
    in bounded chunks, each chunk flushed before the next is written; a
    rolling CRC per physical segment is checked against the SOURCE
    manifest's own leaf CRCs, and that manifest commits on dst verbatim
    (same leaf table, same digests). The commit point is the dst pool's
    atomic manifest rename — a crash at ANY earlier instruction leaves
    data bytes without a manifest, invisible to every reader and to
    recovery. Acks record only after the commit returns, so the ack map
    still under-promises. A source overwritten mid-copy (slot reuse)
    fails the CRC or the manifest snapshot check and raises the benign
    ``SupersededError`` — never a torn replica.
  * **Drain (pmem -> external)**: ``export_object`` reads the region
    once against one manifest snapshot and serializes exactly once, at
    the external-store boundary; stage-in ingests the wire payload with
    ``import_object`` (leaf bytes at manifest offsets, carried manifest
    committed over them) so a rehydrated shard is byte-identical to the
    drained one, CRCs included.
  * **Wire codec (opt-in, ``wire_codec=``)**: the pallas delta-int8
    codec encodes eligible float leaves at the SOURCE of replicate /
    drain / repair transfers; encoded tiles + per-tile scales land on
    the destination with their own CRCs recorded in the manifest's
    ``meta["wire_codec"]`` — the leaf table keeps the ORIGINAL digests,
    so acks, repair scans and ``content_digest`` stay metadata-only and
    encoding-invariant. Readers decode on demand (``get_leaf`` /
    ``read_leaf_slice`` decode just the tiles they touch); strict mode
    (default) snaps scales to powers of two and verifies round-trip
    bit-equality at encode time, falling back to raw per leaf when the
    data won't survive quantization. A second-hop copy of an encoded
    replica raw-streams the encoded segments — never double-encodes.
  * **Byte-range reads**: ``fetch_leaf`` (DLM), ``get_leaf`` and
    ``DistributedCheckpointer.restore_leaves``/``restore_shard`` read
    only the byte range of the leaves they need — sibling leaves are
    never touched, which is what makes N->M warm resize and partial
    KV-page reads O(bytes needed), not O(object).

Telemetry plane — metrics, spans, and the crash-persistent recorder
-------------------------------------------------------------------
Every channel reports into an optional ``TelemetryPlane``
(``repro.obs``), threaded through the ``obs=`` constructor kwarg of
every component (``SimCluster`` wires one automatically; ``obs=None``
degrades every hook to a no-op or a DRAM-only counter update):

  * **Metrics**: channel counters (``tiered.saves`` etc. — the legacy
    ``TieredIO.stats`` dict survives as a registry-backed ``StatsView``
    alias), queue-depth gauges, and bounded histograms for the
    latencies the paper's analysis needs: ``ckpt.save_commit_s`` (the
    node-local commit the trainer blocks on) and
    ``ckpt.submit_to_ack_s`` (submit -> durable ack, per transfer —
    the replication/drain QoS signal).
  * **Trace spans**: ``save_async`` mints one trace id per checkpoint;
    it rides the manifest into the replication channel (per-node
    ``ckpt.replicate``/``ckpt.drain`` child spans), the scheduler's
    task meta (``sched.*`` spans with queue-wait), and the persisted
    ack records (``"trace"`` key) — so one save's
    commit -> replicate -> drain -> ack fan-out reconstructs as a
    single causally-ordered tree, post-hoc, from durable state alone.
    Repair sweeps (``repair.sweep``) and workflow DAGs (``wf.job``)
    mint their own traces the same way. Trace keys are NEVER added to
    ``expect_meta`` (which is equality-compared at the destination).
  * **Flight recorder**: span/point events append to a fixed-size
    per-node pmem ring (``obs/flightring``) under the same
    committed-tail discipline as ``MetaLog`` — slot bytes -> flush ->
    tail -> flush — so a torn final event is invisible to replay and
    everything behind the committed tail survives a crash.
    ``python -m repro.obs.report <pmem-root>`` replays surviving rings
    into the merged timeline; ``analysis/README.md`` documents the
    recording contract and overhead bounds
    (``benchmarks/bench_obs.py`` enforces <5% on the save path).

Serve-tier sessions — leased catalog datasets, not bare keys
------------------------------------------------------------
The multi-tenant serve tier (``serve/sessions.py``) stores every
session's KV/cursor state and every shared prefix cache as a dataset in
the exchange catalog (``sess/<name>`` / ``prefix/<name>``, workflow
``serve``), which makes the session durability contract a corollary of
the dataset one above — no serve-specific machinery:

  * **Spill = publish**: each suspend publishes version N+1 (home
    chosen by stable hash across live pools; lineage = producing engine
    + previous version + forked prefix; content digest; buddy replica
    acked into the record). A session is loss-of-one-node durable
    exactly when its ack lands (``serve.spill_to_ack_s`` measures the
    window; the publish itself rides ``run_async`` on the I/O thread so
    the decode loop never blocks, and ``quiesce`` covers it).
  * **Liveness = lease**: the manager holds a lease on the latest
    version of every live session; ``catalog.gc`` therefore can never
    reclaim one (acquire's under-lock reclaimed check closes the
    acquire/gc race), and the DLM cache's lease-pinned admission
    (``DLMCache.protected``) keeps leased sessions DRAM-resident under
    capacity pressure. Eviction of a cold session is a LEASE RELEASE —
    a metadata write — never byte deletion; ``end()`` unretains every
    version and lets the next gc sweep reclaim the bytes (records and
    lineage survive).
  * **Recovery = records**: ``recoverable_sessions(lost)`` and the
    eviction choice are ``@metadata_only`` (lint-enforced); post-kill
    resumes read the home or an ACKED replica holder — zero blind
    probes — and session repair rides the existing catalog-record scan
    of ``RepairChannel``/``RepairDaemon`` with zero new scan code.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.annotations import metadata_only, rehydration_entry
from repro.core.checkpoint import DistributedCheckpointer
from repro.core.data_scheduler import DataScheduler, SupersededError
from repro.core.dataset_exchange import ack_targets, read_json_copies
from repro.core.meta_log import MetaLog
from repro.core.object_store import _flatten
from repro.core.tiering import DLMCache
from repro.core.wire_codec import normalize_codec
from repro.obs.metrics import Registry, StatsView
from repro.obs.trace import annotate, ctx as _span_ctx


#: acknowledged durability levels, weakest to strongest (module
#: docstring has the full contract)
DURABILITY_LEVELS = ("PENDING", "FAILED", "LOCAL", "REPLICATED", "DRAINED")


class SaveTicket:
    """Handle for one asynchronous checkpoint save.

    ``result()`` blocks until the node-local pmem commit (the manifest
    rename) finishes and returns the global manifest. ``post_commit``
    holds the background drain/replicate futures, which may complete —
    or fail, e.g. when a buddy node dies — long after the commit.
    ``durability()`` reports the acknowledged durability level from the
    persisted ack map (see module docstring).
    """

    def __init__(self, step: int, slot: Optional[int] = None,
                 checkpointer: Optional[DistributedCheckpointer] = None):
        self.step = step
        self.slot = slot  # filled in once the writer allocates it
        self.future: Future = Future()
        self.post_commit: List[Future] = []
        self._checkpointer = checkpointer

    def result(self, timeout: Optional[float] = None) -> dict:
        return self.future.result(timeout)

    def done(self) -> bool:
        return self.future.done()

    def exception(self, timeout: Optional[float] = None):
        return self.future.exception(timeout)

    def wait_post_commit(self, timeout: Optional[float] = None
                         ) -> List[Exception]:
        """Join drain/replicate; returns their errors instead of raising
        (a dead replica target must not poison an otherwise-good save)."""
        errors: List[Exception] = []
        for f in self.post_commit:
            try:
                f.result(timeout)
            except Exception as e:  # noqa: BLE001 — collected for caller
                errors.append(e)
        return errors

    @metadata_only
    def durability(self) -> str:
        """Acknowledged durability of this save (DURABILITY_LEVELS).
        Reads the persisted ack map, so it stays truthful after the
        ticket is retired and across processes — an unacked replicate
        still in flight (or dead with its node) keeps the step LOCAL.
        For a delta checkpoint the level is capped by the base chain's:
        a delta whose base lost its replicas is NOT single-node-loss
        safe, however fully its own slot replicated."""
        if not self.future.done():
            return "PENDING"
        if self.future.exception() is not None:
            return "FAILED"
        ckpt = self._checkpointer
        if ckpt is None:
            return "LOCAL"
        man = self.future.result()
        return _acked_level(ckpt, self.step,
                            man.get("nodes") or ckpt.nodes,
                            man.get("delta_base"))


_LEVEL_RANK = {lvl: i for i, lvl in enumerate(DURABILITY_LEVELS)}


@metadata_only
def _acked_level(ckpt: DistributedCheckpointer, step: int,
                 ring: Sequence[str], delta_base: Optional[int]) -> str:
    acks = ckpt.acks(step)
    if ring and all(acks.get(n, {}).get("drain") for n in ring):
        level = "DRAINED"
    elif len(ring) > 1 and \
            all(acks.get(n, {}).get("replica") for n in ring):
        level = "REPLICATED"
    else:
        level = "LOCAL"
    if delta_base is not None and delta_base < step:
        try:
            bman = ckpt._meta_get_json(
                f"ckpt/manifest_step{delta_base}.json")
        except (IOError, FileNotFoundError):
            return "LOCAL"  # base manifest gone: chain not protected
        base_level = _acked_level(ckpt, delta_base,
                                  bman.get("nodes") or ckpt.nodes,
                                  bman.get("delta_base"))
        if _LEVEL_RANK[base_level] < _LEVEL_RANK[level]:
            level = base_level
    return level


class ReplicationChannel:
    """First-class replicate/drain fan-out with per-node acks.

    One ``submit`` per committed checkpoint: every shard owner's slot
    object is replicated to its ring buddy (and optionally drained to
    the external store) through the data scheduler, and each task
    records its ack into the manifest's ack map the moment the transfer
    is durable. A superseded or failed transfer records nothing — the
    ack map can under-promise durability, never over-promise it.
    """

    def __init__(self, checkpointer: DistributedCheckpointer,
                 scheduler: DataScheduler, obs=None, codec=None):
        self.checkpointer = checkpointer
        self.scheduler = scheduler
        self.obs = obs
        # wire codec spec (already normalized by TieredIO): encodes at
        # the source of every replicate/drain this channel submits
        self.codec = codec
        reg = obs.registry if obs is not None else Registry()
        # submit -> durable-ack wall clock, per transfer (the QoS
        # feedback signal ROADMAP item 5 needs)
        self._ack_s = reg.histogram("ckpt.submit_to_ack_s")

    def _begin(self, name: str, nid: str, tid: int, parent: int,
               **attrs):
        """Child span on ``nid``'s ring when the manifest carried a
        trace context (None otherwise — spans are opt-in per save)."""
        if self.obs is None or not tid:
            return None
        return self.obs.begin(name, node=nid, trace=tid, parent=parent,
                              **attrs)

    @rehydration_entry
    def submit(self, manifest: dict, *, drain: bool = False,
               sink: Optional[List[Future]] = None) -> List[Future]:
        ckpt = self.checkpointer
        step, slot = manifest["step"], manifest["slot"]
        ring = manifest.get("nodes") or ckpt.nodes
        obj = f"ckpt/slot{slot}"
        # trace context minted at save_async and stamped into the
        # manifest: every per-node transfer gets a child span, and the
        # trace id rides the ack info into the durable ack log
        trace = manifest.get("trace") or {}
        tid, root = trace.get("trace", 0), trace.get("span", 0)
        futs: List[Future] = []
        if ckpt.buddy and len(ring) > 1:
            for nid in ring:
                buddy = ckpt.buddy_of(nid, ring)
                sp = self._begin("ckpt.replicate", nid, tid, root,
                                 step=step, target=buddy)
                info = {"target": buddy, "targets": [buddy]}
                if tid:
                    info["trace"] = tid
                futs.append(self.scheduler.replicate(
                    nid, obj, buddy, expect_meta={"step": step},
                    codec=self.codec, span=_span_ctx(sp),
                    on_complete=self._ack(step, nid, "replica", info,
                                          span=sp)))
        if drain and ckpt.external is not None:
            for nid in ring:
                ext = f"ckpt_step{step}_{nid}"
                sp = self._begin("ckpt.drain", nid, tid, root,
                                 step=step, external=ext)
                info = {"external": ext}
                if tid:
                    info["trace"] = tid
                futs.append(self.scheduler.drain(
                    nid, obj, ext, expect_meta={"step": step},
                    codec=self.codec, span=_span_ctx(sp),
                    on_complete=self._ack(step, nid, "drain", info,
                                          span=sp)))
        if sink is not None:
            sink.extend(futs)
        return futs

    @rehydration_entry
    def replicate_object(self, src: str, name: str, dst: str,
                         dst_name: Optional[str] = None,
                         expect_meta: Optional[dict] = None,
                         on_complete=None) -> Future:
        """Replicate a non-checkpoint pmem object (DLM page, session
        state) to a buddy node — readable as ``replica/<src>/<name>``
        when the home pool dies (multi-node DLM fallback). ``on_complete``
        runs inside the task once the copy is durable — the DLM ack
        registry records per-object acks through it."""
        return self.scheduler.replicate(src, name, dst, dst_name=dst_name,
                                        expect_meta=expect_meta,
                                        codec=self.codec,
                                        on_complete=on_complete)

    def _ack(self, step: int, nid: str, kind: str, info: dict,
             span=None):
        ckpt = self.checkpointer
        obs = self.obs
        t_submit = time.time()

        def record(_result) -> None:
            ckpt.record_ack(step, nid, kind, info)
            self._ack_s.observe(time.time() - t_submit)
            if obs is not None and span is not None:
                # the ack lands as a point event on the transfer's span,
                # then the span closes: submit -> durable ack, one arc
                obs.event(f"ckpt.ack.{kind}", node=nid,
                          trace=span.trace, parent=span.span, step=step)
                obs.end(span)
        return record


class ExchangeChannel:
    """Dataset replica fan-out with per-dataset acks — the dataset
    exchange's sibling of ``ReplicationChannel``. One ``submit`` per
    published dataset version: the home node's object is copied to a
    buddy through the data scheduler, and ``on_ack`` (the catalog's
    record updater) runs inside the task the moment the replica is
    durable. A failed or superseded transfer records nothing — the
    catalog's placement map under-promises durability, never
    over-promises it. TieredIO tracks the futures so ``quiesce``/``join``
    cover in-flight dataset replication alongside checkpoints."""

    def __init__(self, scheduler: DataScheduler, track=None, codec=None):
        self.scheduler = scheduler
        self._track = track  # TieredIO future-tracking hook
        self.codec = codec   # wire codec for dataset replica fan-out

    @rehydration_entry
    def submit(self, src: str, obj: str, dst: str, *, version: int = 0,
               dst_name: Optional[str] = None,
               expect_meta: Optional[dict] = None,
               on_ack=None, priority: int = 2,
               span: Optional[dict] = None) -> Future:
        """``dst_name`` overrides the replica name — repair copies a
        surviving replica ``replica/<home>/<obj>`` from its HOLDER, so
        the destination name must keep the original home, not the
        holder, or reads would never find it. ``priority`` passes
        through to the scheduler (the repair daemon runs at background
        priority so foreground I/O outranks it)."""
        fut = self.scheduler.replicate(src, obj, dst, version=version,
                                       dst_name=dst_name,
                                       expect_meta=expect_meta,
                                       codec=self.codec,
                                       on_complete=on_ack,
                                       priority=priority, span=span)
        if self._track is not None:
            self._track(fut)
        return fut


def _fold_dlm_acks(state: dict, ev: dict) -> None:
    """MetaLog reducer for the DLM ack registry: state maps the full
    object name to its ack record; a ``record`` event wins wholesale
    (the repair-pruned targets list must not be resurrected)."""
    state[ev["name"]] = {"home": ev["home"],
                         "targets": list(ev["targets"]),
                         "ts": ev["ts"]}


class DLMAckRegistry:
    """Per-object replica acks for DLM objects — the third ack surface.

    The registry is an append-only replicated pmem log (``dlm/ackslog``,
    a ``MetaLog``): each ack APPENDS one small entry to every live pool
    instead of rewriting the whole object map, and the folded head state
    maps object names to their newest record — for the same object the
    latest entry wins wholesale, so a repair that PRUNED dead targets
    never has them resurrected by a stale copy (log order replaces the
    old per-``ts`` merge). State entries:

      {"dlm/<name>": {"home": nid, "targets": [nids], "ts": ...}}

    ``record`` is called from scheduler worker threads inside the
    replicate task, after the buddy copy is durable — a failed copy
    records nothing, so the registry under-promises, never
    over-promises. A fresh process replays the log cold; the legacy
    pre-log ``dlm/acks.json`` record (if present) is folded in as the
    replay base, so old deployments migrate transparently."""

    NAME = "dlm/acks.json"  # legacy pre-log record (read-only base)
    LOG = "dlm/ackslog"

    def __init__(self, stores, nodes: Sequence[str], obs=None):
        self.stores = stores
        self.nodes = sorted(nodes)
        self._lock = threading.Lock()
        self._log = MetaLog(stores, self.nodes, self.LOG,
                            fold=_fold_dlm_acks, base=self._legacy_base,
                            obs=obs)

    def _legacy_base(self) -> Dict[str, dict]:
        try:
            copies = read_json_copies(self.stores, self.nodes, self.NAME)
        except (IOError, FileNotFoundError):
            return {}
        merged: Dict[str, dict] = {}
        for c in copies:
            for name, rec in (c.get("objects") or {}).items():
                if name not in merged or \
                        rec.get("ts", 0) > merged[name].get("ts", 0):
                    merged[name] = rec
        return merged

    def record(self, name: str, home: str, target: str,
               targets: Optional[Sequence[str]] = None) -> None:
        """Ack one durable buddy copy of ``name`` (a full store object
        name, e.g. ``dlm/serve/sess``). Default: ``target`` joins the
        existing target set. Repair passes an explicit ``targets`` list
        to REPLACE it (pruning targets lost with their nodes)."""
        with self._lock:
            if targets is None:
                targets = sorted(
                    set(ack_targets(self._log.state().get(name)))
                    | {target})
            self._log.append({"op": "record", "name": name,
                              "home": home,
                              "targets": sorted(targets)})

    def objects(self) -> Dict[str, dict]:
        """The merged per-object ack map ({} when nothing ever acked)."""
        with self._lock:
            return dict(self._log.state())

    def targets(self, name: str) -> List[str]:
        """Acked replica holders of ``name`` (possibly empty)."""
        with self._lock:
            return ack_targets(self._log.state().get(name))


class RepairChannel:
    """Ack-driven replica repair: restore the replication factor.

    ``repair(lost_nodes)`` scans the three ack surfaces (checkpoint
    step acks, dataset catalog records, the DLM ack registry) for
    objects whose acked copy set — {home} ∪ acked targets — intersects
    ``lost_nodes`` down to exactly ONE survivor, and re-replicates each
    from that survivor to a fresh live buddy via data-scheduler tasks,
    re-acking (pruned targets + the new one) only when the copy is
    durable. Decisions come from the persisted ack records alone; the
    only object-store reads are the sources of the copies made."""

    def __init__(self, tiered: "TieredIO"):
        self.tiered = tiered

    # ---- shared mechanics --------------------------------------------
    @staticmethod
    def _single_survivor(home: str, targets: Sequence[str],
                         lost: Set[str]) -> Optional[str]:
        """The lone surviving acked copy holder, or None when the object
        needs no repair (>= 2 survivors), was never replicated (nothing
        was promised), or lost every pmem copy (repair cannot invent
        bytes; the drain tier, when acked, still covers checkpoints)."""
        pre = {home} | set(targets)
        cur = pre - lost
        if len(pre) >= 2 and len(cur) == 1:
            return next(iter(cur))
        return None

    def _new_target(self, live: Sequence[str], survivor: str,
                    exclude: Set[str]) -> Optional[str]:
        """The next live node after ``survivor`` in ring order that
        holds no copy yet — the same rotation ``buddy_of`` uses, so
        repair load spreads instead of piling onto one node."""
        ring = list(live)
        if survivor not in ring:
            return None
        i = ring.index(survivor)
        for k in range(1, len(ring)):
            cand = ring[(i + k) % len(ring)]
            if cand not in exclude:
                return cand
        return None

    def _live(self, lost: Set[str]) -> List[str]:
        ckpt = self.tiered.checkpointer
        nodes = ckpt._live_nodes() if ckpt is not None else \
            sorted(self.tiered.scheduler.stores)
        return [n for n in nodes if n not in lost]

    @metadata_only
    def _plan(self, home: str, targets: Sequence[str], lost: Set[str],
              live: Sequence[str], report: dict, *,
              drain_ok: bool = False
              ) -> Optional[Tuple[str, str, List[str]]]:
        """One object's repair decision + report accounting, shared by
        the three scans: (survivor, new_target, new_targets) when a
        re-replication is due, else None after counting the object as
        ``healthy`` (>= 2 surviving copies), ``skipped`` (never acked a
        replica — repair does not own single-copy-by-design objects),
        or ``unrepairable`` (no surviving pmem copy, or no live node
        left to host a new one; ``drain_only`` when an acked external
        drain still covers it)."""
        survivor = self._single_survivor(home, targets, lost)
        if survivor is None:
            pre = {home} | set(targets)
            if len(pre) < 2:
                report["skipped"] += 1
            elif not (pre - lost):
                report["unrepairable"] += 1
                if drain_ok:
                    report["drain_only"] += 1
            else:
                report["healthy"] += 1
            return None
        new = self._new_target(live, survivor,
                               ({home} | set(targets)) - lost)
        if new is None:
            report["unrepairable"] += 1
            return None
        return survivor, new, sorted((set(targets) - lost) | {new})

    def _rehydrate_target(self, nid: str, live: Sequence[str],
                          exclude: Set[str]) -> Optional[str]:
        """Where a rehydrated shard of dead node ``nid`` should land:
        the first live node after ``nid``'s position in the full ring
        (same rotation as ``buddy_of``/``_new_target``, so rehydration
        load spreads instead of piling onto one node)."""
        ckpt = self.tiered.checkpointer
        ring = ckpt.nodes if ckpt is not None else sorted(live)
        i = ring.index(nid) if nid in ring else 0
        for k in range(1, len(ring) + 1):
            cand = ring[(i + k) % len(ring)]
            if cand in live and cand not in exclude:
                return cand
        return None

    # ---- the scan ----------------------------------------------------
    @metadata_only
    def repair(self, lost_nodes: Sequence[str], *,
               max_inflight: Optional[int] = None,
               priority: Optional[int] = None,
               rehydrate: bool = True) -> dict:
        """Scan + re-replicate + join. Returns a report:
        ``checkpoint``/``dataset``/``dlm`` count completed re-acked
        copies, ``repaired`` lists them as (surface, object, survivor,
        new_target), ``rehydrated`` counts drain-tier rehydrations
        (checkpoint shards with zero surviving pmem copies staged back
        from the acked external drain and re-replicated to a live
        buddy), ``healthy`` objects that still have >= 2 surviving
        acked copies (nothing to do), ``superseded`` sources overwritten
        since their ack (benign — the newer object carries its own
        acks), ``unrepairable`` objects with no surviving pmem copy or
        no live node left to host a new one (``drain_only`` the subset
        an acked external drain still covers but that was NOT
        rehydrated), ``skipped`` single-copy objects that never acked a
        replica (repair does not own them), and ``errors`` real copy
        failures.

        ``max_inflight`` is the repair-traffic budget: at most that many
        repair transfers are queued/running at once, the rest wait — the
        continuous daemon uses it so a repair storm never swamps
        foreground I/O (``peak_inflight`` in the report records the high
        water mark). ``priority`` overrides the scheduler priority of
        every repair task (the daemon passes a background priority so
        foreground saves/stage-ins always outrank repairs). Plans run in
        newest-checkpoint-first order. ``rehydrate=False`` disables the
        drain-tier path (drain-only objects are then only counted)."""
        lost = set(lost_nodes)
        report = {"checkpoint": 0, "dataset": 0, "dlm": 0,
                  "rehydrated": 0, "healthy": 0, "superseded": 0,
                  "unrepairable": 0, "drain_only": 0, "skipped": 0,
                  "peak_inflight": 0, "repaired": [], "errors": []}
        obs = self.tiered.obs
        sweep_span = None
        if obs is not None:
            # one trace per sweep: scan + every copy/re-ack hangs off it
            sweep_span = obs.begin("repair.sweep", local=True,
                                   lost=sorted(lost))
        sctx = _span_ctx(sweep_span)
        live = self._live(lost)
        plans: collections.deque = collections.deque()
        if self.tiered.checkpointer is not None:
            self._scan_checkpoints(lost, live, report, plans,
                                   priority=priority, rehydrate=rehydrate,
                                   span=sctx)
        self._scan_dlm(lost, live, report, plans, priority=priority,
                       span=sctx)
        if self.tiered.catalog is not None:
            self._scan_datasets(lost, live, report, plans,
                                priority=priority, span=sctx)
        self._execute(plans, report, max_inflight)
        if obs is not None:
            for k in ("checkpoint", "dataset", "dlm", "rehydrated",
                      "healthy", "superseded", "unrepairable",
                      "drain_only", "skipped"):
                obs.counter(f"repair.{k}").inc(report[k])
            obs.counter("repair.errors").inc(len(report["errors"]))
            obs.end(sweep_span, repaired=len(report["repaired"]),
                    errors=len(report["errors"]))
        return report

    def _execute(self, plans: "collections.deque", report: dict,
                 max_inflight: Optional[int]) -> None:
        """Run repair plans through a bounded submission window.
        Each plan: {surface, obj, survivor, new, submit, then?,
        on_error?}. ``then`` chains a follow-up plan on success
        (rehydration stages external->pmem, THEN replicates pmem->pmem);
        it re-enters at the FRONT of the queue so a chain completes
        before new objects start. Completion of a plan without ``then``
        is what the per-surface counters and ``repaired`` record."""
        outstanding: collections.deque = collections.deque()
        while plans or outstanding:
            while plans and (max_inflight is None
                             or len(outstanding) < max_inflight):
                p = plans.popleft()
                outstanding.append((p, p["submit"]()))
                report["peak_inflight"] = max(report["peak_inflight"],
                                              len(outstanding))
            p, fut = outstanding.popleft()
            try:
                fut.result()
            except SupersededError:
                report["superseded"] += 1
            except Exception as e:  # noqa: BLE001 — reported, not raised
                report["errors"].append(e)
                if p.get("on_error") is not None:
                    p["on_error"](e)
            else:
                then = p.get("then")
                if then is not None:
                    plans.appendleft(then)
                    continue
                report[p["counter"]] += 1
                report["repaired"].append(
                    (p["surface"], p["obj"], p["survivor"], p["new"]))

    @metadata_only
    def _scan_checkpoints(self, lost: Set[str], live: List[str],
                          report: dict, plans: "collections.deque", *,
                          priority: Optional[int],
                          rehydrate: bool,
                          span: Optional[dict] = None) -> None:
        ckpt = self.tiered.checkpointer
        sched = self.tiered.scheduler
        prio = {} if priority is None else {"priority": priority}
        if span is not None:
            prio["span"] = span
        seen_slots: Set[int] = set()
        for step in sorted(ckpt.available_steps(), reverse=True):
            try:
                rec_map = ckpt.ack_record(step)
                if rec_map is None:
                    continue  # pre-ack legacy step: nothing promised
                slot = ckpt._meta_get_json(
                    f"ckpt/manifest_step{step}.json")["slot"]
            except (IOError, FileNotFoundError, KeyError):
                continue  # pre-ack legacy step: nothing was promised
            if slot in seen_slots:
                # a newer step reused this slot: the bytes on pmem are
                # no longer this step's (its own replicate would only
                # raise SupersededError) — skip on metadata alone. The
                # same holds for rehydration: the replica name is keyed
                # by slot, so staging the old step back would collide
                # with the newer step's replicas.
                report["superseded"] += 1
                continue
            seen_slots.add(slot)
            ring = rec_map.get("ring") or ckpt.nodes
            acks = rec_map.get("acks") or {}
            obj = f"ckpt/slot{slot}"
            for nid in ring:
                targets = ack_targets(acks.get(nid, {}).get("replica"))
                drain_rec = acks.get(nid, {}).get("drain") \
                    if ckpt.external is not None else None
                if rehydrate and drain_rec and \
                        not (({nid} | set(targets)) - lost):
                    # drain-tier rehydration: every pmem copy died, the
                    # acked external drain survives — stage it back into
                    # a live pool (the only external read this scan
                    # makes), then re-replicate to a fresh buddy
                    self._plan_rehydration(step, nid, slot, drain_rec,
                                           live, report, plans, prio)
                    continue
                plan = self._plan(
                    nid, targets, lost, live, report,
                    drain_ok=bool(drain_rec))
                if plan is None:
                    continue
                survivor, new, new_targets = plan
                src_obj = obj if survivor == nid else \
                    f"replica/{nid}/{obj}"

                def ack(_man, step=step, nid=nid, new=new,
                        new_targets=new_targets) -> None:
                    info = {"target": new, "targets": new_targets}
                    if span is not None:
                        info["trace"] = span["trace"]
                    ckpt.record_ack(step, nid, "replica", info)
                plans.append({"surface": "checkpoint",
                              "counter": "checkpoint",
                              "obj": f"step{step}/{nid}",
                              "survivor": survivor, "new": new,
                              "submit": lambda s=survivor, so=src_obj,
                              n=new, st=step, ni=nid, a=ack, o=obj:
                              sched.replicate(
                                  s, so, n, dst_name=f"replica/{ni}/{o}",
                                  expect_meta={"step": st},
                                  codec=self.tiered.wire_codec,
                                  on_complete=a, **prio)})

    def _plan_rehydration(self, step: int, nid: str, slot: int,
                          drain_rec: dict, live: List[str], report: dict,
                          plans: "collections.deque",
                          prio: dict) -> None:
        """Queue the two-stage rehydration of ``nid``'s shard at
        ``step``: (1) stage the acked external drained copy into a live
        pool under the replica name (acked immediately — one durable
        pmem copy), (2) replicate that staged copy to a second live node
        and re-ack the pair. Either stage failing counts the object as
        ``unrepairable``/``drain_only`` (the drain still covers it), and
        a later sweep re-plans from whatever the acks then say."""
        ckpt = self.tiered.checkpointer
        sched = self.tiered.scheduler
        t1 = self._rehydrate_target(nid, live, set())
        if t1 is None:
            report["unrepairable"] += 1
            report["drain_only"] += 1
            return
        t2 = self._rehydrate_target(nid, live, {t1})
        ext = drain_rec.get("external") or f"ckpt_step{step}_{nid}"
        rep = f"replica/{nid}/ckpt/slot{slot}"
        obj = f"step{step}/{nid}"

        def count_lost(_e) -> None:
            report["unrepairable"] += 1
            report["drain_only"] += 1

        def ack_stage(_man, targets=(t1,)) -> None:
            # the staged pmem copy is durable: ack it alone first —
            # under-promise, so a crash between the stages leaves a
            # truthful single-target record the next sweep extends
            ckpt.record_ack(step, nid, "replica",
                            {"target": t1, "targets": sorted(targets)})

        stage = {"surface": "rehydrate", "counter": "rehydrated",
                 "obj": obj, "survivor": "external", "new": t1,
                 "on_error": count_lost,
                 "submit": lambda: sched.stage_in(
                     t1, ext, rep,
                     meta={"step": step, "replica_of": nid},
                     on_complete=ack_stage, **prio)}
        if t2 is not None:
            def ack_pair(_man) -> None:
                ckpt.record_ack(step, nid, "replica",
                                {"target": t2,
                                 "targets": sorted((t1, t2))})
            stage["then"] = {
                "surface": "rehydrate", "counter": "rehydrated",
                "obj": obj, "survivor": "external", "new": t1,
                "on_error": count_lost,
                "submit": lambda: sched.replicate(
                    t1, rep, t2, dst_name=rep,
                    expect_meta={"step": step},
                    codec=self.tiered.wire_codec,
                    on_complete=ack_pair, **prio)}
        plans.append(stage)

    @metadata_only
    def _scan_dlm(self, lost: Set[str], live: List[str],
                  report: dict, plans: "collections.deque", *,
                  priority: Optional[int],
                  span: Optional[dict] = None) -> None:
        reg = self.tiered.dlm_acks
        if reg is None:
            return
        sched = self.tiered.scheduler
        prio = {} if priority is None else {"priority": priority}
        if span is not None:
            prio["span"] = span
        for name, rec in reg.objects().items():
            home = rec.get("home")
            targets = ack_targets(rec)
            plan = self._plan(home, targets, lost, live, report)
            if plan is None:
                continue
            survivor, new, new_targets = plan
            src_obj = name if survivor == home else \
                f"replica/{home}/{name}"

            def ack(_man, name=name, home=home, new=new,
                    new_targets=new_targets) -> None:
                reg.record(name, home, new, targets=new_targets)
            plans.append({"surface": "dlm", "counter": "dlm",
                          "obj": name, "survivor": survivor, "new": new,
                          "submit": lambda s=survivor, so=src_obj, n=new,
                          h=home, nm=name, a=ack: sched.replicate(
                              s, so, n, dst_name=f"replica/{h}/{nm}",
                              codec=self.tiered.wire_codec,
                              on_complete=a, **prio)})

    @metadata_only
    def _scan_datasets(self, lost: Set[str], live: List[str],
                       report: dict, plans: "collections.deque", *,
                       priority: Optional[int],
                       span: Optional[dict] = None) -> None:
        catalog = self.tiered.catalog
        sched = self.tiered.scheduler
        prio = {} if priority is None else {"priority": priority}
        if span is not None:
            prio["span"] = span
        for rec in catalog.records():
            if rec.get("reclaimed"):
                continue
            home = rec["home"]
            targets = ack_targets((rec.get("acks") or {}).get("replica"))
            plan = self._plan(home, targets, lost, live, report)
            if plan is None:
                continue
            survivor, new, new_targets = plan
            wf, name, v = rec["workflow"], rec["name"], rec["version"]
            src_obj = rec["object"] if survivor == home else \
                f"replica/{home}/{rec['object']}"
            dst_name = f"replica/{home}/{rec['object']}"

            def ack(_man, wf=wf, name=name, v=v, new=new,
                    new_targets=new_targets) -> None:
                catalog.record_repair_ack(wf, name, v, target=new,
                                          targets=new_targets)
            chan = self.tiered.exchange
            key = f"exch/{wf}/{name}@v{v}"

            def submit(survivor=survivor, src_obj=src_obj, new=new,
                       v=v, name=name, dst_name=dst_name, ack=ack,
                       chan=chan) -> Future:
                if chan is not None:
                    return chan.submit(
                        survivor, src_obj, new, version=v,
                        dst_name=dst_name,
                        expect_meta={"dataset": name, "version": v},
                        on_ack=ack, **prio)
                return sched.replicate(
                    survivor, src_obj, new, version=v, dst_name=dst_name,
                    expect_meta={"dataset": name, "version": v},
                    codec=self.tiered.wire_codec,
                    on_complete=ack, **prio)
            plans.append({"surface": "dataset", "counter": "dataset",
                          "obj": key, "survivor": survivor, "new": new,
                          "submit": submit})


def _merge_sweep(acc: dict, sweep: dict) -> None:
    """Fold one sweep's report into the daemon's accumulated ledger.
    Event counters (copies made, rehydrations, supersedes, errors,
    repaired entries) accumulate across sweeps; STATE counters
    (healthy / unrepairable / drain_only / skipped) are the LAST
    sweep's values — every sweep re-scans all three ack surfaces
    against the cumulative dead set, so the newest scan is the current
    truth (an object sweep N rehydrated must not keep an old sweep's
    ``drain_only`` count alive)."""
    for k in ("checkpoint", "dataset", "dlm", "rehydrated",
              "superseded"):
        acc[k] = acc.get(k, 0) + sweep.get(k, 0)
    for k in ("healthy", "unrepairable", "drain_only", "skipped"):
        acc[k] = sweep.get(k, 0)
    acc["peak_inflight"] = max(acc.get("peak_inflight", 0),
                               sweep.get("peak_inflight", 0))
    acc.setdefault("repaired", []).extend(sweep.get("repaired", ()))
    acc.setdefault("errors", []).extend(sweep.get("errors", ()))


class RepairDaemon:
    """Continuous, heartbeat-driven background repair sweeps.

    PR 4's repair runs only at recovery points (``check_and_recover`` /
    ``resume``), so an object sits on a single pmem copy for the whole
    window between a node loss and the next recovery event. The daemon
    closes that window: it polls ``Heartbeat.dead_nodes`` and, on every
    NEW death, runs ``RepairChannel.repair`` over the CUMULATIVE dead
    set — incrementally (already-handled deaths don't re-trigger),
    rate-limited (``max_inflight`` bounds concurrent repair transfers;
    ``priority`` puts them below every foreground channel in the
    scheduler queues), newest-checkpoint-first, and with drain-tier
    rehydration on. It quiesces nothing: repair decisions come from
    persisted acks, which are only ever written after a transfer is
    durable, so the sweep coexists with in-flight foreground I/O.

    A second loss mid-sweep simply fails the transfers aimed at the
    newly-dead node; the next poll sees an unhandled death and
    re-plans the whole cumulative set from the acks (PR 4's ``targets``
    lists make the re-plan safe). Error-only sweeps retry up to
    ``max_retries`` times before the dead set is marked handled with
    the errors kept in the ledger.

    The **ledger**: ``covers(lost)`` says whether every node in
    ``lost`` has been swept cleanly, and ``report()`` returns the
    merged accumulated report — recovery points
    (``FailureRecovery.check_and_recover``,
    ``WorkflowScheduler.resume``, ``ServeEngine.repair``) consult it
    instead of re-scanning from scratch. ``wait_for(lost)`` blocks
    until the ledger covers ``lost`` (the train loop's fault hook uses
    it to resume only after the replication factor is back)."""

    def __init__(self, tiered: "TieredIO", heartbeat, *,
                 timeout_s: float = 10.0, poll_s: float = 0.05,
                 max_inflight: int = 2, priority: int = 4,
                 max_retries: int = 3, rehydrate: bool = True):
        self.tiered = tiered
        self.hb = heartbeat
        self.timeout_s = timeout_s
        self.poll_s = poll_s
        self.max_inflight = max_inflight
        self.priority = priority
        self.max_retries = max_retries
        self.rehydrate = rehydrate
        self.handled: Set[str] = set()
        self._attempts: Dict[frozenset, int] = {}
        self._ledger: dict = {"sweeps": 0}
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---- lifecycle ---------------------------------------------------
    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> "RepairDaemon":
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="repair-daemon")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=60.0)
            if t.is_alive():
                # a wedged sweep survived the join timeout: keep the
                # thread visible (running stays True) so a later
                # start() cannot spawn a SECOND daemon racing this one
                # on the ledger; the stop flag ends it when it unwedges
                return
            self._thread = None

    def _run(self) -> None:
        backoff = self.poll_s
        while not self._stop.is_set():
            try:
                self.poll_once()
                backoff = self.poll_s
            except Exception as e:  # noqa: BLE001 — daemon must survive
                # a sweep that RAISES (vs per-object errors, which the
                # report collects) means even the metadata scan failed;
                # back off exponentially so a dead cluster doesn't fill
                # the ledger at poll rate
                with self._cv:
                    self._ledger.setdefault("errors", []).append(e)
                backoff = min(backoff * 2, 1.0)
            self._stop.wait(backoff)

    # ---- one poll/sweep (also the unit tests' entry point) -----------
    def poll_once(self, now: Optional[float] = None) -> Optional[dict]:
        """Detect new deaths and sweep if any; returns that sweep's
        report, or None when nothing new happened. Runs inline on the
        caller's thread — the background loop is just this on a timer."""
        dead = set(self.hb.dead_nodes(self.timeout_s, now))
        with self._cv:
            # a rejoined node may die again later: it leaves the
            # handled set the moment it stops being dead
            self.handled &= dead
            new = dead - self.handled
        if not new:
            return None
        sweep = self.tiered.repair(sorted(dead),
                                   max_inflight=self.max_inflight,
                                   priority=self.priority,
                                   rehydrate=self.rehydrate)
        key = frozenset(dead)
        with self._cv:
            _merge_sweep(self._ledger, sweep)
            self._ledger["sweeps"] += 1
            if not sweep["errors"]:
                self.handled |= dead
                self._attempts.clear()
            else:
                # transfers died mid-sweep (e.g. a SECOND loss): leave
                # the set unhandled so the next poll re-plans from the
                # acks — but give up after max_retries so a permanent
                # failure doesn't storm the scheduler forever
                self._attempts[key] = self._attempts.get(key, 0) + 1
                if self._attempts.get(key, 0) >= self.max_retries:
                    self.handled |= dead
            self._cv.notify_all()
        obs = self.tiered.obs
        if obs is not None:
            obs.counter("repair.daemon_sweeps").inc()
            obs.event("repair.daemon_sweep", dead=sorted(dead),
                      errors=len(sweep["errors"]))
        return sweep

    # ---- the ledger --------------------------------------------------
    def covers(self, lost_nodes: Sequence[str]) -> bool:
        """True when every node in ``lost_nodes`` has been swept: a
        recovery point may then take ``report()`` instead of running a
        redundant scan of its own."""
        with self._cv:
            return set(lost_nodes) <= self.handled

    def wait_for(self, lost_nodes: Sequence[str],
                 timeout: Optional[float] = None) -> bool:
        """Block until the ledger covers ``lost_nodes`` (or timeout)."""
        lost = set(lost_nodes)
        with self._cv:
            return self._cv.wait_for(lambda: lost <= self.handled,
                                     timeout)

    def report(self) -> dict:
        """The accumulated ledger: merged sweep reports plus ``sweeps``
        (count) and ``handled`` (nodes swept cleanly)."""
        with self._cv:
            out = dict(self._ledger)
            out["repaired"] = list(self._ledger.get("repaired", ()))
            out["errors"] = list(self._ledger.get("errors", ()))
            out["handled"] = sorted(self.handled)
            return out


class TieredIO:
    """Async engine over checkpointer + scheduler + DLM cache."""

    def __init__(self, checkpointer: Optional[DistributedCheckpointer] = None,
                 scheduler: Optional[DataScheduler] = None,
                 cache: Optional[DLMCache] = None,
                 max_inflight_saves: Optional[int] = None,
                 wire_codec=None, obs=None):
        self.checkpointer = checkpointer
        self.scheduler = scheduler
        self.cache = cache
        self.obs = obs
        # opt-in delta-int8 wire codec for every fabric/external
        # transfer this engine submits (True -> defaults, or a spec
        # dict); None keeps every channel raw
        self.wire_codec = normalize_codec(wire_codec)
        reg = obs.registry if obs is not None else Registry()
        # the replication channel owns ALL replicate/drain fan-out; the
        # checkpointer delegates to it at every save commit
        self.replication: Optional[ReplicationChannel] = None
        if checkpointer is not None and scheduler is not None:
            self.replication = ReplicationChannel(checkpointer, scheduler,
                                                  obs=obs,
                                                  codec=self.wire_codec)
            checkpointer.replication = self.replication
        # dataset-exchange fan-out (catalog attached via attach_catalog)
        self.exchange: Optional[ExchangeChannel] = None
        self.catalog = None
        if scheduler is not None:
            self.exchange = ExchangeChannel(scheduler,
                                            track=self._track_future,
                                            codec=self.wire_codec)
        # home node of the DLM cache (whose store it fronts): replica
        # fallback reads resolve relative to it
        self._home_nid: Optional[str] = None
        # per-object DLM replica acks (dlm/acks.json) + the repair scan
        # over all three ack surfaces
        self.dlm_acks: Optional[DLMAckRegistry] = None
        self.repair_channel = RepairChannel(self)
        # the continuous RepairDaemon, when one is running against this
        # engine (FailureRecovery.start_daemon wires it): recovery
        # points consult its ledger instead of re-scanning
        self.repair_daemon: Optional[RepairDaemon] = None
        # dlm/<name>s the caller opted out of replicating (offload
        # replicate=False): dirty write-backs skip them too
        self._dlm_no_replicate: Set[str] = set()
        if checkpointer is not None:
            self._home_nid = checkpointer.nodes[0]
            self.dlm_acks = DLMAckRegistry(checkpointer.stores,
                                           checkpointer.nodes, obs=obs)
            if cache is not None:
                for nid, st in checkpointer.stores.items():
                    if st is cache.store:
                        self._home_nid = nid
                        break
                if cache.fallback_reader is None:
                    cache.fallback_reader = self._dlm_replica_read
                if cache.on_writeback is None:
                    # every durable DLM write-back (offload flush, dirty
                    # eviction) re-queues the buddy copy + ack, so the
                    # replica tier never lags the home pool
                    cache.on_writeback = self._queue_dlm_replica
        self.max_inflight = max_inflight_saves or (
            checkpointer.slots if checkpointer is not None else 2)
        self.errors: List[Exception] = []       # post-commit failures
        self.save_errors: List[Exception] = []  # checkpoint COMMIT failures
        # registry-backed channel counters; ``stats`` stays dict-shaped
        # (StatsView) so existing callers/tests read it unchanged
        self._counters = {k: reg.counter(f"tiered.{k}")
                          for k in ("saves", "offloads", "prefetch_hits",
                                    "prefetch_loads", "stage_in_hits",
                                    "stage_in_loads")}
        self.stats = StatsView(self._counters)
        self._g_inflight = reg.gauge("tiered.inflight_saves")
        self._t_commit = reg.histogram("ckpt.save_commit_s")
        self._tickets: "collections.deque[SaveTicket]" = collections.deque()
        self._retired: List[SaveTicket] = []  # committed, drains may run
        self._futures: List[Future] = []   # offload/prefetch futures
        self._lock = threading.Lock()
        # one FIFO writer thread: serialises pmem writes (slot safety),
        # overlaps them with the caller's compute. Reads (prefetch
        # warms) go through their own pool so a large warm-up batch
        # never delays the next checkpoint commit.
        self._io = ThreadPoolExecutor(max_workers=1,
                                      thread_name_prefix="tiered-io-wr")
        self._read = ThreadPoolExecutor(max_workers=2,
                                        thread_name_prefix="tiered-io-rd")

    def _submit(self, fn) -> Future:
        return self._io.submit(fn)  # raises RuntimeError after shutdown

    def run_async(self, fn) -> Future:
        """Run ``fn`` on the engine's FIFO I/O thread, tracked like an
        offload: ``quiesce``/``join`` cover the returned future, so a
        crash-time drain never strands it. The serve tier's nonblocking
        session spill (a catalog ``publish`` that must not stall the
        decode loop) rides this hook."""
        fut = self._submit(fn)
        self._track_future(fut)
        return fut

    def _track_future(self, fut: Future) -> None:
        with self._lock:
            self._prune_done_locked()
            self._futures.append(fut)

    def attach_catalog(self, catalog) -> None:
        """Wire a DatasetCatalog into the engine: its replica fan-out
        goes through the exchange channel (futures joined by quiesce),
        its reads admit into the DLM cache, and ``evict_cold`` keeps the
        catalog's actively-leased datasets DRAM-resident."""
        self.catalog = catalog
        catalog.exchange = self.exchange
        if self.cache is not None:
            catalog.cache = self.cache
            if self.cache.protected is None:
                # lease-pinned admission: capacity-pressure LRU never
                # evicts a dataset someone holds a live lease on (serve
                # sessions mid-request, workflow consumers mid-lease)
                self.cache.protected = catalog.leased_cache_keys

    # ---- checkpoint channel ------------------------------------------
    def save_async(self, step: int, tree, *,
                   base_step: Optional[int] = None,
                   drain: bool = False) -> SaveTicket:
        """Nonblocking checkpoint: returns immediately (modulo slot
        backpressure); the write overlaps the caller's next step."""
        assert self.checkpointer is not None, "no checkpointer attached"
        ckpt = self.checkpointer
        ticket = SaveTicket(step, checkpointer=ckpt)
        retiring: List[SaveTicket] = []
        with self._lock:
            self._prune_done_locked()
            # double-buffer backpressure: never exceed the slot count.
            # The FIFO writer thread already serialises the pmem writes;
            # this only bounds how far the caller can run ahead. Only
            # the node-local COMMIT of the retiring ticket gates it —
            # its drain/replicate futures keep overlapping.
            while len(self._tickets) >= self.max_inflight:
                retiring.append(self._tickets.popleft())
            self._tickets.append(ticket)
            self._g_inflight.set(len(self._tickets))
        obs = self.obs
        # wait OUTSIDE the lock: offload/prefetch submissions must not
        # stall behind a write. The span is opened for every save, so its
        # count is the number of saves even where no slot was taken.
        with (obs.span("tiered.save.slot_wait", step=step)
              if obs is not None else annotate("tiered.save.slot_wait",
                                               step=step)):
            for old in retiring:
                try:
                    old.result()
                except Exception as e:  # noqa: BLE001 — surfaced by
                    self.save_errors.append(e)  # raise_if_failed/quiesce
                with self._lock:
                    self._retired.append(old)

        root = None
        if obs is not None:
            # root span of the whole checkpoint trace: commit + every
            # per-node replicate/drain/ack hangs off this id
            root = obs.begin("ckpt.save", node=self._home_nid,
                             step=step, drain=drain)

        def _save():
            t0 = time.time()
            try:
                # the pmem commit on the writer thread; its store.put
                # spans nest inside (ckpt.save_commit_s times it too)
                with annotate("ckpt.commit", step=step):
                    man = ckpt.save(step, tree, base_step=base_step,
                                    drain=drain,
                                    post_commit=ticket.post_commit,
                                    trace=_span_ctx(root))
            except Exception:
                if obs is not None:
                    obs.end(root, status="error")
                raise
            self._t_commit.observe(time.time() - t0)
            ticket.slot = man["slot"]
            self._counters["saves"].inc()
            if obs is not None:
                obs.end(root, slot=man["slot"])
            return man

        # chain into the ticket's pre-existing future: the ticket is
        # already visible (in _tickets) to concurrent quiesce callers
        def _chain(f: Future) -> None:
            e = f.exception()
            if e is not None:
                ticket.future.set_exception(e)
            else:
                ticket.future.set_result(f.result())

        try:
            self._submit(_save).add_done_callback(_chain)
        except RuntimeError:
            with self._lock:
                self._tickets.remove(ticket)
            raise
        return ticket

    def raise_if_failed(self) -> None:
        """Raise the first pending checkpoint COMMIT failure. The
        training loop calls this at every checkpoint boundary so a run
        doesn't continue for hours believing it is protected while every
        save fails. Post-commit drain/replicate errors (e.g. a dead
        buddy) are NOT raised here — they degrade durability, not the
        node-local checkpoint itself.

        The raised error is POPPED: one failed commit surfaces exactly
        once, so a run that recovers (e.g. restores and resumes on the
        survivors) is not re-failed forever at every later boundary by
        the same stale record."""
        with self._lock:
            for t in list(self._tickets):
                if t.done() and t.exception() is not None:
                    self.save_errors.append(t.exception())
                    self._tickets.remove(t)
            if self.save_errors:
                raise self.save_errors.pop(0)

    def _prune_done_locked(self) -> None:
        """Drop fully-completed retired tickets and offload/prefetch
        futures so steady-state training/serving doesn't accumulate one
        record per checkpoint/spill forever. Failures are folded into
        ``errors`` before the record is dropped."""
        keep_t = []
        for t in self._retired:
            if all(f.done() for f in t.post_commit):
                for f in t.post_commit:
                    e = f.exception()
                    if e is not None:
                        self.errors.append(e)
            else:
                keep_t.append(t)
        self._retired = keep_t
        keep_f = []
        for f in self._futures:
            if f.done():
                e = f.exception()
                if e is not None:
                    self.errors.append(e)
            else:
                keep_f.append(f)
        self._futures = keep_f

    def _drain_ticket(self, ticket: SaveTicket) -> None:
        try:
            ticket.result()
        except Exception as e:  # noqa: BLE001 — kept for quiesce callers
            self.save_errors.append(e)
        self.errors.extend(ticket.wait_post_commit())

    def last_ticket(self) -> Optional[SaveTicket]:
        with self._lock:
            return self._tickets[-1] if self._tickets else None

    # ---- object channel (serve KV pages, session state) --------------
    def _queue_dlm_replica(self, name: str) -> None:
        """Queue a buddy copy of ``dlm/<name>`` + its ack (into the
        DLM ack registry) the moment the home-pool bytes are durable.
        Called by ``offload`` and by the cache's write-back hook (dirty
        eviction/flush), so replicas track every durable write, not
        just the first. The buddy comes from the LIVE ring, like the
        checkpoint path: after the static buddy dies, replicas must
        land on a survivor instead of failing forever."""
        ckpt, home = self.checkpointer, self._home_nid
        if (self.replication is None or ckpt is None or home is None
                or name in self._dlm_no_replicate):
            return
        ring = ckpt._live_nodes()
        if home not in ring or len(ring) < 2:
            return
        buddy = ckpt.buddy_of(home, ring)
        obj = f"dlm/{name}"
        reg = self.dlm_acks

        def ack(_man) -> None:
            if reg is not None:
                # REPLACE the target list: this copy carries the bytes
                # just written back, so every other acked copy is now
                # stale (a repair-added extra, or a buddy that died and
                # may rejoin with old pmem) and must leave the record —
                # acked targets always hold the CURRENT bytes
                reg.record(obj, home, buddy, targets=[buddy])
        rfut = self.replication.replicate_object(
            home, obj, buddy, on_complete=ack)
        self._track_future(rfut)

    def offload(self, name: str, tree, *, replicate: bool = True) -> Future:
        """Persist an object through the DLM write-back cache (or the
        checkpointer's meta store when no cache is attached). The future
        resolves once the object is durable in the home node's pmem;
        with ``replicate`` (default) a buddy replica is then queued
        through the replication channel — acked per object into
        ``dlm/acks.json`` when durable — so reads survive the home
        node's death (multi-node DLM) and ``repair`` can restore the
        replication factor after a loss. ``replicate=False`` marks the
        object node-local: later dirty write-backs skip it too."""
        if replicate:
            self._dlm_no_replicate.discard(name)
        else:
            self._dlm_no_replicate.add(name)

        def _persist():
            if self.cache is not None:
                self.cache.put(name, tree)
                # write back just this object; the cache's write-back
                # hook queues the buddy replica + ack
                self.cache.flush(name)
            else:
                assert self.checkpointer is not None
                self.checkpointer._meta_store().put(f"dlm/{name}", tree)
                self._queue_dlm_replica(name)
            self._counters["offloads"].inc()
            return name

        fut = self._submit(_persist)
        with self._lock:
            self._prune_done_locked()
            self._futures.append(fut)
        return fut

    def _dlm_candidates(self, name: str) -> Tuple[str, List[str]]:
        """Replica name + fallback read order for ``dlm/<name>``:
        ack-recorded targets first, then the home's ring buddy, then
        every other surviving node (home itself excluded)."""
        ckpt = self.checkpointer
        home = self._home_nid
        assert ckpt is not None and home is not None
        rep = f"replica/{home}/dlm/{name}"
        acked = self.dlm_acks.targets(f"dlm/{name}") \
            if self.dlm_acks is not None else []
        order = acked + [ckpt.buddy_of(home)] + \
            [n for n in ckpt.nodes if n != home]
        out: List[str] = []
        seen: Set[str] = set()
        for nid in order:
            if nid not in seen and nid != home:
                seen.add(nid)
                out.append(nid)
        return rep, out

    def _dlm_replica_read(self, name: str):
        """Multi-node DLM fallback: when the home node's pool is dead
        (or no longer holds ``dlm/<name>``), read the buddy replica
        placed by ``offload``/``repair`` — preferring the ack-recorded
        targets, then the home's ring buddy, then any surviving node
        holding ``replica/<home>/dlm/<name>``."""
        ckpt = self.checkpointer
        rep, order = self._dlm_candidates(name)
        last: Optional[Exception] = None
        for nid in order:
            try:
                if ckpt.stores[nid].exists(rep):
                    return ckpt.stores[nid].get(rep)
            except IOError as e:  # that node is dead too — keep walking
                last = e
        if last is not None:
            raise last
        raise FileNotFoundError(
            f"dlm/{name} (home {self._home_nid} unreadable and no node "
            f"holds {rep})")

    def fetch_leaf(self, name: str, leaf: str):
        """Byte-range demand read: ONE leaf of ``dlm/<name>`` without
        touching its siblings. A DRAM-resident cache copy serves from
        memory (it may be dirtier than pmem); otherwise the leaf's byte
        range is read straight from the home pool — falling back to
        acked replicas exactly like ``fetch`` — decoding only the tiles
        of that leaf when the copy travelled wire-encoded. The partial
        object is never admitted into the cache. Raises ``KeyError``
        when the object exists but has no such leaf."""
        if self.cache is not None and self.cache.contains(name):
            flat = dict(_flatten(self.cache.get(name)))
            if leaf not in flat:
                raise KeyError(leaf)
            return flat[leaf]
        ckpt = self.checkpointer
        home = self._home_nid
        assert ckpt is not None and home is not None, "no pmem backend"
        try:
            return ckpt.stores[home].get_leaf(f"dlm/{name}", leaf)
        except IOError:
            pass  # home pool dead or object gone — walk the replicas
        rep, order = self._dlm_candidates(name)
        last: Optional[Exception] = None
        for nid in order:
            try:
                if ckpt.stores[nid].exists(rep):
                    return ckpt.stores[nid].get_leaf(rep, leaf)
            except IOError as e:
                last = e
        if last is not None:
            raise last
        raise FileNotFoundError(f"dlm/{name} leaf {leaf!r} (home {home} "
                                f"unreadable and no node holds {rep})")

    def fetch(self, name: str):
        """Demand read through the DLM cache (hit/miss accounted), or
        straight from pmem when no cache is attached — symmetric with
        ``offload`` so an engine without a cache still round-trips."""
        if self.cache is not None:
            return self.cache.get(name)
        assert self.checkpointer is not None, "no pmem backend attached"
        return self.checkpointer._meta_store().get(f"dlm/{name}")

    def prefetch(self, names: Iterable[str]) -> Future:
        """Warm DRAM with ``names`` from pmem in the background. The
        future resolves to ``{"hits": n_already_resident, "loads":
        n_pulled_from_pmem, "missing": n_not_in_pmem}``. Advisory: an
        object absent from pmem is counted, never raised — the demand
        path is the arbiter of real misses."""
        assert self.cache is not None, "no DLM cache attached"
        names = list(names)

        def _warm():
            obs = self.obs
            sp = obs.begin("dlm.prefetch", node=self._home_nid,
                           local=True, n=len(names)) \
                if obs is not None else None
            hits = loads = missing = 0
            for n in names:
                try:
                    if self.cache.prefetch(n):
                        hits += 1
                    else:
                        loads += 1
                except (IOError, FileNotFoundError, KeyError):
                    missing += 1
            self._counters["prefetch_hits"].inc(hits)
            self._counters["prefetch_loads"].inc(loads)
            if obs is not None:
                obs.end(sp, hits=hits, loads=loads, missing=missing)
            return {"hits": hits, "loads": loads, "missing": missing}

        fut = self._read.submit(_warm)
        with self._lock:
            self._prune_done_locked()
            self._futures.append(fut)
        return fut

    def evict_cold(self, max_idle_s: float = 0.0) -> int:
        """Spill idle DRAM entries back to pmem; returns count evicted.
        Lease-aware: datasets the attached catalog holds live leases on
        are pinned (a consumer mid-lease never loses DRAM residency)."""
        if self.cache is None:
            return 0
        keep = (self.catalog.leased_cache_keys()
                if self.catalog is not None else ())
        return self.cache.evict_cold(max_idle_s, keep=keep)

    def prefetch_datasets(self, refs, workflow: str = "default") -> Future:
        """Anticipatory dataset warm-up through the catalog: resolve each
        named dataset (home pmem or acked replica) on the read pool and
        admit it into the DLM cache, so a consumer job's first ``read``
        hits DRAM. Same advisory contract as ``prefetch``: absent or
        reclaimed datasets are counted, never raised."""
        assert self.catalog is not None, "no catalog attached"
        refs = list(refs)

        def _warm():
            obs = self.obs
            sp = obs.begin("exch.prefetch", node=self._home_nid,
                           local=True, n=len(refs)) \
                if obs is not None else None
            hits = loads = missing = 0
            from repro.core.dataset_exchange import cache_key
            for name in refs:
                try:
                    rec = self.catalog.record(name, workflow)
                    key = cache_key(workflow, name, rec["version"])
                    if self.cache is not None and self.cache.contains(key):
                        hits += 1
                        continue
                    self.catalog.get(name, workflow)
                    loads += 1
                except (KeyError, IOError, FileNotFoundError):
                    missing += 1
            self._counters["prefetch_hits"].inc(hits)
            self._counters["prefetch_loads"].inc(loads)
            if obs is not None:
                obs.end(sp, hits=hits, loads=loads, missing=missing)
            return {"hits": hits, "loads": loads, "missing": missing}

        fut = self._read.submit(_warm)
        with self._lock:
            self._prune_done_locked()
            self._futures.append(fut)
        return fut

    # ---- repair channel (restore the replication factor) -------------
    def repair(self, lost_nodes: Sequence[str], **kw) -> dict:
        """Re-replicate every acked object (checkpoint shard, dataset,
        DLM object) whose copies ``lost_nodes`` reduced to a single
        survivor, to a fresh live buddy — re-acked when durable — and
        rehydrate drain-only checkpoint shards back into pmem. Joins
        the copies; returns the RepairChannel report (kwargs —
        ``max_inflight``, ``priority``, ``rehydrate`` — pass through).
        Call after the recovery path has quiesced in-flight work
        (FailureRecovery and WorkflowScheduler.resume do this wiring
        for you); the continuous RepairDaemon calls it WITHOUT
        quiescing, which is safe because acks only ever describe
        already-durable transfers."""
        return self.repair_channel.repair(lost_nodes, **kw)

    # ---- burst-buffer channel (external -> pmem) ---------------------
    def stage_in(self, nid: str, names: Sequence[str],
                 prefix: str = "staged/") -> List[Future]:
        """Pre-load external objects into node ``nid``'s pmem (Fig. 8
        steps 1-3). Objects already resident count as stage-in hits."""
        assert self.scheduler is not None, "no scheduler attached"
        obs = self.obs
        sp = obs.begin("stage.stage_in", node=nid, local=True,
                       n=len(names)) if obs is not None else None
        futs: List[Future] = []
        for name in names:
            obj = prefix + name
            if self.scheduler.stores[nid].exists(obj):
                self._counters["stage_in_hits"].inc()
                done: Future = Future()
                done.set_result(None)
                futs.append(done)
                continue
            self._counters["stage_in_loads"].inc()
            futs.append(self.scheduler.stage_in(nid, name, obj,
                                                span=_span_ctx(sp)))
        if obs is not None:
            obs.end(sp, submitted=len(futs))
        with self._lock:
            self._prune_done_locked()
            self._futures.extend(futs)
        return futs

    def stage_in_hit_rate(self) -> float:
        tot = self.stats["stage_in_hits"] + self.stats["stage_in_loads"]
        return self.stats["stage_in_hits"] / tot if tot else 0.0

    # ---- lifecycle ---------------------------------------------------
    def quiesce(self) -> List[Exception]:
        """Join every in-flight save/offload/prefetch. Errors are
        collected (and returned), never raised: recovery must be able to
        consume in-flight futures even when nodes died under them."""
        while True:
            with self._lock:
                if self._tickets:
                    ticket, fresh = self._tickets.popleft(), True
                elif self._retired:
                    ticket, fresh = self._retired.pop(), False
                else:
                    break
            if fresh:
                self._drain_ticket(ticket)
            else:  # commit already joined at backpressure time
                self.errors.extend(ticket.wait_post_commit())
        while True:
            with self._lock:
                if not self._futures:
                    break
                fut = self._futures.pop()
            try:
                fut.result()
            except Exception as e:  # noqa: BLE001
                self.errors.append(e)
        with self._lock:
            errors = self.save_errors + self.errors
            self.save_errors, self.errors = [], []
        return errors

    def join(self) -> None:
        """Strict barrier: wait for all in-flight work, raising the first
        REAL error. A ``SupersededError`` (a drain/replicate outpaced by
        slot reuse — the newer checkpoint's own transfer covers it) is
        benign and must not fail an otherwise-clean run. Use at clean
        shutdown; recovery paths use ``quiesce``."""
        errors = [e for e in self.quiesce()
                  if not isinstance(e, SupersededError)]
        if errors:
            raise errors[0]

    def shutdown(self) -> None:
        self.quiesce()
        self._io.shutdown(wait=True)
        self._read.shutdown(wait=True)
