"""Telemetry plane: metrics aliases, flight-recorder crash persistence,
and end-to-end trace propagation (PR 8)."""
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core.pmem import PMemPool
from repro.obs import report as obs_report
from repro.obs.metrics import Counter, Histogram, Registry, StatsView
from repro.obs.recorder import EVT_BEGIN, EVT_END, EVT_POINT, \
    FlightRecorder
from repro.obs.trace import build_traces, connected_to_root, span_names


# ---- metrics / StatsView aliases -------------------------------------

def test_registry_counters_and_histograms():
    reg = Registry()
    c = reg.counter("x")
    assert reg.counter("x") is c  # create-or-get
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.dec()
    assert g.value == 2
    h = reg.histogram("lat")
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["min"] <= 0.001 and s["max"] >= 0.1
    assert s["p50"] <= s["p99"]
    snap = reg.snapshot()
    assert snap["counters"]["x"] == 5
    assert snap["histograms"]["lat"]["count"] == 4


def test_statsview_is_dict_shaped():
    counters = {"a": Counter("a"), "b": Counter("b")}
    view = StatsView(counters)
    counters["a"].inc(3)
    assert view["a"] == 3 and view["b"] == 0
    view["b"] += 2  # __getitem__ + __setitem__ round-trip
    assert counters["b"].value == 2
    assert view == {"a": 3, "b": 2}          # dict equality both ways
    assert dict(view) == {"a": 3, "b": 2}
    assert set(view) == {"a", "b"} and len(view) == 2


def test_legacy_stats_surfaces_are_registry_backed(cluster):
    c = cluster
    # TieredIO.stats reads through to tiered.* counters
    assert c.tiered.stats["saves"] == 0
    c.tiered.obs.registry.counter("tiered.saves").inc()
    assert c.tiered.stats["saves"] == 1
    # DLMCache int attributes read through to dlm.* counters
    assert c.dlm.hits == c.tiered.obs.registry.counter("dlm.hits").value


# ---- flight recorder -------------------------------------------------

def _mkpool(tmp=None):
    root = Path(tmp or tempfile.mkdtemp(prefix="repro_obs_"))
    return PMemPool(root, "node0"), root


def test_ring_wraparound_keeps_newest_events():
    pool, _ = _mkpool()
    rec = FlightRecorder(pool, slots=8, slot_bytes=128)
    for i in range(25):
        assert rec.record(EVT_POINT, f"ev{i}", attrs={"i": i})
    events = FlightRecorder.replay(pool)
    assert [e["seq"] for e in events] == list(range(17, 25))
    assert [e["attrs"]["i"] for e in events] == list(range(17, 25))


def test_recorder_reopen_adopts_committed_ring():
    pool, root = _mkpool()
    rec = FlightRecorder(pool, slots=16, slot_bytes=128)
    for i in range(5):
        rec.record(EVT_POINT, f"a{i}")
    # fresh process: different default geometry args must NOT reformat
    rec2 = FlightRecorder(PMemPool(root, "node0"))
    assert rec2.slots == 16 and rec2.committed == 5
    rec2.record(EVT_POINT, "after-restart")
    events = FlightRecorder.replay(pool)
    assert len(events) == 6
    assert events[-1]["name"] == "after-restart"


def test_record_on_dead_pool_is_counted_drop():
    pool, _ = _mkpool()
    rec = FlightRecorder(pool, slots=8, slot_bytes=128)
    assert rec.record(EVT_POINT, "alive")
    pool.fail()
    assert rec.record(EVT_POINT, "dead") is False
    assert rec.drops == 1
    assert rec.committed == 1  # the failed append committed nothing


def test_torn_tail_replay_is_committed_prefix(pmem_sanitizer):
    """Every crash image the sanitizer can enumerate (stores lost /
    persisted / final store torn) replays to a clean PREFIX of the
    committed event stream — the committed-tail discipline, proven by
    enumeration exactly like MetaLog's crash tests."""
    pool, _ = _mkpool()
    rec = FlightRecorder(pool, slots=8, slot_bytes=128)
    for i in range(6):
        rec.record(EVT_POINT, f"ev{i}", attrs={"i": i})
    full = [e["attrs"]["i"] for e in FlightRecorder.replay(pool)]
    assert full == list(range(6))
    spool, _ = _mkpool()
    n_images = 0
    for label, img in pmem_sanitizer.crash_images("flightring"):
        n_images += 1
        pmem_sanitizer.materialize(img, spool, "obs/flightring")
        got = [e["attrs"]["i"]
               for e in FlightRecorder.replay(spool)]
        assert got == full[:len(got)], label  # prefix, never torn/gappy
    assert n_images > 0


# ---- end-to-end trace propagation ------------------------------------

def _replay_cluster(c):
    events = []
    for nid, pool in c.pools.items():
        for ev in FlightRecorder.replay(pool):
            ev["node"] = nid
            events.append(ev)
    return events


def test_save_async_yields_one_connected_span_tree(cluster):
    c = cluster
    state = {"w": b"\x01" * 512}
    t = c.tiered.save_async(0, state, drain=True)
    t.result()
    c.tiered.quiesce()
    c.checkpointer.wait_async()
    traces = build_traces(_replay_cluster(c))
    ckpt_traces = [
        (tid, tr) for tid, tr in traces.items()
        if tid and any(tr["spans"][r]["name"] == "ckpt.save"
                       for r in tr["roots"])]
    assert len(ckpt_traces) == 1  # ONE save -> ONE trace
    tid, tr = ckpt_traces[0]
    names = span_names(tr)
    assert "ckpt.replicate" in names and "ckpt.drain" in names
    assert "sched.replicate" in names and "sched.drain" in names
    # every span in the trace hangs off the single ckpt.save root
    assert len(tr["roots"]) == 1
    for sid in tr["spans"]:
        assert connected_to_root(tr, sid)
    # the ack point events attached to their transfer spans
    acked = [ev["name"] for sp in tr["spans"].values()
             for ev in sp["events"]]
    assert "ckpt.ack.replica" in acked and "ckpt.ack.drain" in acked
    # ... and the trace id was persisted into the durable ack records,
    # so the correlation survives process death
    rec = c.checkpointer.ack_record(0)
    for nid in rec["ring"]:
        assert rec["acks"][nid]["replica"]["trace"] == tid
        assert rec["acks"][nid]["drain"]["trace"] == tid


def test_repair_sweep_is_traced(cluster):
    c = cluster
    c.tiered.save_async(0, {"w": b"\x02" * 256}).result()
    c.tiered.quiesce()
    c.checkpointer.wait_async()
    c.kill_node("node1")
    c.repair(["node1"])
    traces = build_traces(_replay_cluster(c))
    sweeps = [tr for tid, tr in traces.items()
              if tid and any(tr["spans"][r]["name"] == "repair.sweep"
                             for r in tr["roots"])]
    assert sweeps
    reg = c.tiered.obs.registry
    assert reg.counter("repair.checkpoint").value >= 1


def test_postcrash_report_recovers_timeline(cluster, capsys):
    """Kill a node mid-flight, then diagnose from the surviving rings
    alone via the report CLI — the ISSUE's acceptance scenario."""
    c = cluster
    c.tiered.save_async(0, {"w": b"\x03" * 512}, drain=True).result()
    c.tiered.quiesce()
    c.checkpointer.wait_async()
    t = c.tiered.save_async(1, {"w": b"\x04" * 512}, drain=True)
    c.kill_node("node2")  # crash while step 1's fan-out is in flight
    try:
        t.result()
    except Exception:
        pass
    c.tiered.quiesce()
    rc = obs_report.main([str(c.root / "pmem")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ckpt.save" in out
    assert "last event per ring:" in out
    # no clean shutdown happened: the rings ARE the record
    assert "no metrics snapshot found" in out
    # the dead node's ring is gone; survivors still reconstruct step 0
    events = _replay_cluster(c)
    assert {"node0", "node1", "node3"} <= {e["node"] for e in events}
    traces = build_traces(events)
    saves = [tr for tid, tr in traces.items()
             if tid and any(tr["spans"][r]["name"] == "ckpt.save"
                            for r in tr["roots"])]
    assert len(saves) >= 1


def test_clean_shutdown_persists_metrics_snapshot():
    from repro.core.cluster import SimCluster
    root = Path(tempfile.mkdtemp(prefix="repro_obs_"))
    c = SimCluster(root, n_nodes=2)
    c.tiered.save_async(0, {"w": b"\x05" * 128}).result()
    c.tiered.quiesce()
    c.checkpointer.wait_async()
    c.shutdown()
    snap = c.pools["node0"].get_json("obs/metrics.json")
    assert snap["counters"]["tiered.saves"] == 1
    assert "ckpt.save_commit_s" in snap["histograms"]


def test_workflow_jobs_share_one_trace(cluster):
    from repro.core.workflow import JobSpec
    c = cluster

    def produce(ctx):
        return {"out_a": {"x": b"\x06" * 64}}

    def consume(ctx):
        ctx.read("out_a")
        return {}

    c.workflows.run([JobSpec("p", produce),
                     JobSpec("q", consume, after=["p"],
                             inputs=["out_a"])])
    traces = build_traces(_replay_cluster(c))
    wf_traces = [tr for tid, tr in traces.items()
                 if tid and "wf.job" in span_names(tr)]
    assert wf_traces
    jobs = [sp["attrs"].get("job") for tr in wf_traces
            for sp in tr["spans"].values() if sp["name"] == "wf.job"]
    # both DAG jobs landed in a single workflow trace
    assert any({"p", "q"} <= set(
        sp["attrs"].get("job") for sp in tr["spans"].values()
        if sp["name"] == "wf.job") for tr in wf_traces), jobs


def test_telemetry_off_records_nothing(tmp_path):
    from repro.core.cluster import SimCluster
    from test_obs_profile import _engine, _train
    c = SimCluster(tmp_path, n_nodes=2, telemetry=False)
    c.tiered.save_async(0, {"w": b"\x07" * 128}).result()
    c.tiered.quiesce()
    c.checkpointer.wait_async()
    assert c.tiered.stats["saves"] == 1  # DRAM metrics still work
    # the profiler spans of the checkpoint path and the decode loop
    # (train.ckpt.*, tiered.save.slot_wait, ckpt.commit, store.put.*,
    # engine.decode.*) write no ring event either
    _train(c, 2)
    _engine(c).decode(np.zeros(1, np.int32), 3)
    assert c.tiered.stats["saves"] == 3
    hist = c.obs.snapshot()["histograms"]
    assert hist["span.train.ckpt.d2h.s"]["count"] == 2
    for pool in c.pools.values():
        assert FlightRecorder.replay(pool) == []
    c.shutdown()
