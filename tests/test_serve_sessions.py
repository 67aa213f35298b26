"""Multi-tenant serve tier: sessions as leased exchange datasets.

Covers the SessionManager lifecycle (spill -> lease handoff -> gc
safety -> lease-release eviction -> end), cross-process adoption,
metadata-only recoverability, the replica read path after a home-node
death (with a store-read audit proving zero blind probes), the
wire-codec + replica + byte-range `peek` composition, and the two
engine-level bug regressions (jitted prefill routing, spill-ticket
host-copy ownership).
"""
import tempfile
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest


@pytest.fixture()
def cluster_codec():
    """4-node cluster with the delta-int8 wire codec on every
    replicate/drain/repair transfer."""
    from repro.core.cluster import SimCluster
    root = Path(tempfile.mkdtemp(prefix="repro_test_"))
    c = SimCluster(root, n_nodes=4, wire_codec=True)
    yield c
    c.shutdown()


def _state(seed=0, n=64):
    r = np.random.RandomState(seed)
    return {"cache": {"k": r.randn(2, n).astype(np.float32),
                      "v": r.randn(2, n).astype(np.float32)},
            "pos": np.int32(7 + seed)}


class FakeEngine:
    """export_state/install_state contract double — the manager never
    looks at the math, only at the state tree handoff."""

    def __init__(self, label="e0"):
        self.label = label
        self.cache = None
        self._state = None

    def export_state(self, release=False):
        assert self._state is not None, "no session state resident"
        out = {"cache": dict(self._state["cache"]),
               "pos": np.int32(self._state["pos"])}
        if release:
            self._state = None
        return out

    def install_state(self, obj):
        self._state = {"cache": {k: np.asarray(v)
                                 for k, v in obj["cache"].items()},
                       "pos": int(obj["pos"])}

    def seed(self, tree):
        self.install_state(tree)
        return self

    @property
    def pos(self):
        return self._state["pos"]


def _record_store_reads(c):
    """Audit every object-store DATA read (get_with_manifest / exists /
    get_leaf) across the cluster; returns the list the wrappers append
    to. Metadata (pool JSON) reads are not data probes and don't count."""
    reads = []
    for nid, st in c.stores.items():
        for meth in ("get_with_manifest", "exists", "get_leaf"):
            orig = getattr(st, meth)

            def wrapped(name, *a, _orig=orig, _nid=nid, **kw):
                reads.append((_nid, name))
                return _orig(name, *a, **kw)

            setattr(st, meth, wrapped)
    return reads


# ---------------------------------------------------------------------------
# lifecycle: spill publishes a leased dataset with lineage
# ---------------------------------------------------------------------------

def test_spill_publishes_versioned_dataset_with_lineage(cluster):
    sm = cluster.sessions
    sm.publish_prefix("sys", _state(1))
    eng = FakeEngine().seed(_state(2))
    sm.start("chat", eng, prefix="sys")
    # the fork actually installed the prefix state
    assert eng.pos == int(_state(1)["pos"])
    rec = sm.spill("chat")
    assert rec["version"] == 1 and rec["digest"]
    assert ["prefix/sys", "serve", 1] in rec["lineage"]["inputs"]
    rec2 = sm.spill("chat")
    assert rec2["version"] == 2
    assert ["sess/chat", "serve", 1] in rec2["lineage"]["inputs"]
    # the whole derivation chain is queryable from the catalog
    chain = cluster.catalog.lineage("sess/chat", "serve")
    names = [r.get("name") for r in chain if "name" in r]
    assert "prefix/sys" in names


def test_gc_never_reclaims_live_leased_session(cluster):
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(3))
    sm.start("live", eng)
    sm.spill("live")
    cluster.tiered.quiesce()
    assert cluster.catalog.gc() == []  # leased + retained: untouchable
    # superseded version IS reclaimed once a newer spill supersedes it
    sm.spill("live")
    cluster.tiered.quiesce()
    assert cluster.catalog.gc() == [("serve", "sess/live", 1)]
    # ... but the record survives reclaim (lineage outlives bytes)
    assert cluster.catalog.record("sess/live", "serve", 1)["reclaimed"]
    # end(): every version unretained -> bytes reclaimed next sweep
    sm.end("live")
    cluster.tiered.quiesce()
    assert ("serve", "sess/live", 2) in cluster.catalog.gc()


def test_eviction_releases_lease_instead_of_deleting(cluster):
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(4))
    sm.start("cold", eng)
    sm.suspend("cold")
    # bound sessions are never eviction candidates
    eng2 = FakeEngine().seed(_state(5))
    sm.start("hot", eng2)
    assert sm.choose_evictions(0.0) == ["cold"]
    assert sm.evict_cold(0.0) == ["cold"]
    assert sm._sessions["cold"].lease is None
    # bytes stayed durable: resume re-acquires the lease and reads back
    sm.resume("cold", FakeEngine("e1"))
    assert sm._sessions["cold"].lease is not None
    cluster.tiered.quiesce()
    # still leased again -> gc still can't touch it
    assert ("serve", "sess/cold", 1) not in cluster.catalog.gc()


def test_resume_rejects_double_bind_and_unknown(cluster):
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(6))
    sm.start("s", eng)
    with pytest.raises(RuntimeError):
        sm.resume("s", FakeEngine())
    with pytest.raises(KeyError):
        sm.resume("nope", FakeEngine())


# ---------------------------------------------------------------------------
# fleet: cross-process adoption + replica resume with zero blind probes
# ---------------------------------------------------------------------------

def test_adoption_resumes_session_published_elsewhere(cluster):
    from repro.serve.sessions import SessionManager
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(7))
    sm.start("shared", eng)
    sm.suspend("shared")
    # "another process": a fresh manager over the same catalog
    sm2 = SessionManager(cluster.tiered, cluster.catalog, obs=cluster.obs)
    eng2 = FakeEngine("e2")
    sm2.resume("shared", eng2)
    assert eng2.pos == int(_state(7)["pos"])
    # the persisted trace id reconnected the lifetime span tree
    rec = cluster.catalog.record("sess/shared", "serve")
    assert rec["annotations"]["session"] == "shared"
    assert sm2._sessions["shared"].span.trace == \
        rec["annotations"]["trace"]


def test_resume_from_acked_replica_zero_probes_after_home_death(cluster):
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(8))
    sm.start("surv", eng)
    sm.suspend("surv")
    cluster.tiered.quiesce()
    rec = cluster.catalog.record("sess/surv", "serve")
    home = rec["home"]
    buddy = rec["acks"]["replica"]["targets"][0]
    # metadata-only recoverability BEFORE touching any bytes
    reads = _record_store_reads(cluster)
    assert "surv" in sm.recoverable_sessions([home])
    assert reads == [], f"recoverable_sessions probed stores: {reads}"
    cluster.kill_node(home)
    # DLM may hold a DRAM copy from the spill — drop it so the resume
    # exercises the replica read path
    sm.evict_cold(0.0)
    cluster.catalog.cache and cluster.catalog.cache.drop(
        f"exch/serve/sess/surv@v{rec['version']}")
    del reads[:]
    eng2 = FakeEngine("e2")
    sm.resume("surv", eng2)
    assert eng2.pos == int(_state(8)["pos"])
    # every byte off a LIVE node came from the ACKED buddy replica — no
    # blind fan-out (the one failed touch of the dead home is the read
    # path learning the pool is gone, not a probe of a live store)
    data_reads = [(n, o) for n, o in reads
                  if not o.endswith(".json") and n != home]
    assert data_reads, "resume never touched pmem?"
    for nid, obj in data_reads:
        assert obj.startswith("replica/"), (nid, obj)
        assert nid == buddy, (nid, obj, buddy)


# ---------------------------------------------------------------------------
# satellite: peek on a WIRE-ENCODED spill off an acked replica after the
# home node dies (codec + replica fallback + byte-range composition)
# ---------------------------------------------------------------------------

def test_peek_session_wire_codec_replica_after_home_death(cluster_codec):
    from repro.serve.engine import ServeEngine
    c = cluster_codec
    eng = ServeEngine.__new__(ServeEngine)  # no model needed for spill
    eng.tiered, eng.store = c.tiered, None
    state = _state(9, n=256)
    eng.cache, eng.pos = state["cache"], int(state["pos"])
    eng.spill("wired")  # replicate rides the delta-int8 wire codec
    c.tiered.quiesce()
    c.tiered.evict_cold(0.0)  # drop DRAM residency: read pmem bytes
    c.kill_node("node0")  # the DLM home — only the replica survives
    reads = _record_store_reads(c)
    np.testing.assert_array_equal(eng.peek_session("wired", "cache/k"),
                                  state["cache"]["k"])
    assert int(eng.peek_session("wired", "pos")) == int(state["pos"])
    data_reads = [(n, o) for n, o in reads
                  if not o.endswith(".json") and n != "node0"]
    assert data_reads, "peek never touched pmem?"
    for nid, obj in data_reads:
        assert obj.startswith("replica/"), (nid, obj)


def test_manager_peek_wire_codec_replica_after_home_death(cluster_codec):
    c = cluster_codec
    sm = c.sessions
    state = _state(10, n=256)
    eng = FakeEngine().seed(state)
    sm.start("wired2", eng)
    sm.suspend("wired2")
    c.tiered.quiesce()
    rec = c.catalog.record("sess/wired2", "serve")
    c.kill_node(rec["home"])
    np.testing.assert_array_equal(sm.peek("wired2", "cache/v"),
                                  state["cache"]["v"])
    assert int(sm.peek("wired2", "pos")) == int(state["pos"])
    assert c.catalog.stats["replica_reads"] >= 2


# ---------------------------------------------------------------------------
# failed async suspend parks the host copy (nothing is ever lost)
# ---------------------------------------------------------------------------

def test_failed_async_suspend_parks_state_and_resume_recovers(cluster):
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(11))
    sm.start("flaky", eng)
    orig = cluster.catalog.publish

    def boom(*a, **kw):
        raise IOError("injected publish failure")

    cluster.catalog.publish = boom
    try:
        fut = sm.suspend("flaky", wait=False)
        with pytest.raises(IOError):
            fut.result(timeout=30)
        sm.join()
        assert sm._sessions["flaky"].pending_state is not None
        # resume installs straight from the parked DRAM copy
        eng2 = FakeEngine("e2")
        sm.resume("flaky", eng2)
        assert eng2.pos == int(_state(11)["pos"])
    finally:
        cluster.catalog.publish = orig
    # next successful spill clears the parked copy
    sm.spill("flaky")
    assert sm._sessions["flaky"].pending_state is None


def test_engine_spill_ticket_owns_host_copy_on_failure():
    """Satellite regression: spill(wait=False) used to free self.cache
    before the async offload was durable — a failed future silently
    lost the session. The ticket now parks the host copy and names the
    session in the error."""
    from repro.serve.engine import ServeEngine, SpillTicket

    class _FailingTiered:
        obs = None

        def offload(self, name, obj, replicate=True):
            fut = Future()
            fut.set_exception(IOError("pmem died mid-offload"))
            return fut

    eng = ServeEngine.__new__(ServeEngine)
    eng.tiered, eng.store = _FailingTiered(), None
    eng.failed_spills = {}
    state = _state(12)
    eng.cache, eng.pos = state["cache"], int(state["pos"])
    ticket = eng.spill("doomed", wait=False)
    assert isinstance(ticket, SpillTicket)
    assert eng.cache is None  # DRAM freed as before ...
    with pytest.raises(RuntimeError, match="doomed"):
        ticket.result(timeout=30)
    # ... but the host copy survived, owned by the ticket -> engine
    assert "doomed" in eng.failed_spills
    eng.restore_failed_spill("doomed")
    np.testing.assert_array_equal(np.asarray(eng.cache["k"]),
                                  state["cache"]["k"])
    assert eng.pos == int(state["pos"])


# ---------------------------------------------------------------------------
# satellite regression: prefill must route through the jitted partial
# ---------------------------------------------------------------------------

def test_prefill_routes_through_jitted_path():
    import jax
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serve import engine as engine_mod

    cfg = registry.get_smoke_config("qwen2-72b")
    rt = T.ModelRuntime(tp=1, attn_impl="naive", max_seq=64, remat=False)
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg, rt)
    eng = engine_mod.ServeEngine(cfg, rt, params)
    toks = np.arange(8, dtype=np.int32)[None, :] % cfg.vocab_size
    first = eng.prefill(toks)  # traces + compiles self._prefill
    assert first.shape == (1,)

    def _unjitted_call(*a, **kw):  # pragma: no cover - must not run
        raise AssertionError("prefill bypassed the jitted path")

    orig = engine_mod.tfm.prefill
    engine_mod.tfm.prefill = _unjitted_call
    try:
        # same shapes: a jitted prefill hits the compile cache and never
        # re-enters the python fn; the old unjitted call would blow up
        again = eng.prefill(toks + 1)
    finally:
        engine_mod.tfm.prefill = orig
    assert again.shape == (1,)


# ---------------------------------------------------------------------------
# telemetry: gauge/histograms/span tree per session lifetime
# ---------------------------------------------------------------------------

def test_session_telemetry_surfaces(cluster):
    sm = cluster.sessions
    eng = FakeEngine().seed(_state(13))
    sm.start("obs1", eng)
    assert cluster.obs.registry.gauge("serve.sessions_active").value == 1
    sm.suspend("obs1")
    assert cluster.obs.registry.gauge("serve.sessions_active").value == 0
    sm.resume("obs1", FakeEngine("e2"))
    snap = cluster.obs.snapshot()
    assert snap["counters"]["serve.spills"] >= 1
    assert snap["counters"]["serve.resumes"] >= 1
    assert snap["histograms"]["span.serve.resume.s"]["count"] >= 1
    # spill-to-ack probe fires once the buddy ack lands
    cluster.tiered.quiesce()
    deadline = time.time() + 10
    while time.time() < deadline:
        snap = cluster.obs.snapshot()
        if snap["histograms"].get("serve.spill_to_ack_s",
                                  {}).get("count", 0) >= 1:
            break
        time.sleep(0.02)
    assert snap["histograms"]["serve.spill_to_ack_s"]["count"] >= 1
    sm.end("obs1")
    assert cluster.obs.registry.gauge("serve.sessions_active").value == 0
