"""TieredIO engine: async saves, crash-mid-drain safety, prefetch
accounting, cold eviction, and the mesh builder."""
import time

import numpy as np
import pytest


def _tree(seed=0):
    r = np.random.RandomState(seed)
    return {"w": r.randn(16, 8).astype(np.float32),
            "b": r.randn(8).astype(np.float32)}


# ---------------------------------------------------------------------------
# checkpoint channel
# ---------------------------------------------------------------------------

def test_save_async_completes_and_restores(cluster):
    t = _tree(1)
    ticket = cluster.tiered.save_async(1, t)
    man = ticket.result(timeout=30)
    assert man["step"] == 1
    assert ticket.wait_post_commit(timeout=30) == []
    out, man2 = cluster.checkpointer.restore()
    assert man2["step"] == 1
    np.testing.assert_array_equal(out["w"], t["w"])


def test_save_async_overlaps_and_slots_stay_safe(cluster):
    """Three overlapping saves reuse slot 0 for steps 1 and 3; FIFO
    ordering + backpressure must keep every committed manifest readable."""
    trees = {s: _tree(s) for s in (1, 2, 3)}
    tickets = [cluster.tiered.save_async(s, trees[s]) for s in (1, 2, 3)]
    for tk in tickets:
        tk.result(timeout=30)
    # step-1's background replicate may race step-3's reuse of its slot
    # and fail; that is collected, never raised, and harmless — the
    # replica would be invalid anyway (its source slot was rewritten).
    cluster.tiered.quiesce()
    # two slots -> the last two steps are restorable, bit-exact
    for s in (2, 3):
        out, man = cluster.checkpointer.restore(s)
        assert man["step"] == s
        np.testing.assert_array_equal(out["w"], trees[s]["w"])
    assert cluster.checkpointer.latest_step() == 3


def test_submit_returns_before_drain_completes(cluster_slow_external):
    """save_async must not pay for the external tier: the submit returns
    while the throttled drain is still in flight."""
    c = cluster_slow_external
    t0 = time.perf_counter()
    ticket = c.tiered.save_async(1, _tree(2), drain=True)
    submit_s = time.perf_counter() - t0
    ticket.result(timeout=30)
    assert submit_s < 0.5  # drain of ~0.5MB at 1MB/s would take ~0.5s+
    assert ticket.wait_post_commit(timeout=60) == []
    assert c.external.exists("ckpt_step1_node0")


def test_crash_mid_drain_keeps_previous_manifest(cluster):
    """A failing drain (external tier dies mid-flush) must surface on the
    ticket without touching the committed node-local checkpoint."""
    c = cluster
    c.tiered.save_async(1, _tree(3)).result(timeout=30)
    c.tiered.quiesce()

    def boom(name, tree):
        raise IOError("external store died mid-drain")
    c.external.put = boom
    ticket = c.tiered.save_async(2, _tree(4), drain=True)
    ticket.result(timeout=30)  # node-local commit is unaffected
    errors = ticket.wait_post_commit(timeout=30)
    assert errors and all("mid-drain" in str(e) for e in errors)
    # both checkpoints still restorable from pmem
    assert c.checkpointer.latest_step() == 2
    out, _ = c.checkpointer.restore(2)
    np.testing.assert_array_equal(out["w"], _tree(4)["w"])
    out, _ = c.checkpointer.restore(1)
    np.testing.assert_array_equal(out["w"], _tree(3)["w"])


def test_raise_if_failed_surfaces_commit_errors(cluster):
    """A failed checkpoint COMMIT must surface at the next checkpoint
    boundary (the loop calls raise_if_failed), not at shutdown hours
    later."""
    c = cluster

    def boom(*a, **k):
        raise MemoryError("pmem full")
    c.checkpointer.save = boom
    t = c.tiered.save_async(1, _tree(0))
    with pytest.raises(MemoryError):
        t.result(timeout=30)
    with pytest.raises(MemoryError):
        c.tiered.raise_if_failed()
    c.tiered.quiesce()  # collected errors cleared; engine reusable


def test_quiesce_swallows_inflight_errors_for_recovery(cluster):
    c = cluster
    c.tiered.save_async(1, _tree(5)).result(timeout=30)

    def boom(name, tree):
        raise IOError("dead node")
    c.external.put = boom
    c.tiered.save_async(2, _tree(6), drain=True)
    errors = c.recovery.quiesce_inflight()
    assert errors, "drain failure must be collected"
    assert c.recovery.inflight_errors
    # recovery still proceeds off the committed manifests
    out, man = c.checkpointer.restore_latest_recoverable()
    assert man["step"] == 2


def test_restore_latest_recoverable_falls_back(cluster):
    """If the newest checkpoint's shards died with a node before
    replication, recovery must fall back to the previous step."""
    c = cluster
    c.tiered.save_async(1, _tree(7)).result(timeout=30)
    c.tiered.quiesce()  # step-1 replicas are all placed
    victim = c.node_ids[-1]
    # step 2 commits, then the victim dies before its replica lands:
    # emulate by dropping both the victim's shard and its replica.
    man2 = c.tiered.save_async(2, _tree(8)).result(timeout=30)
    c.tiered.quiesce()
    slot2 = man2["slot"]
    c.stores[victim].delete(f"ckpt/slot{slot2}")
    c.stores[c.checkpointer.buddy_of(victim)].delete(
        f"replica/{victim}/ckpt/slot{slot2}")
    out, man = c.checkpointer.restore_latest_recoverable(
        lost_nodes=[victim])
    assert man["step"] == 1
    np.testing.assert_array_equal(out["w"], _tree(7)["w"])


def test_slot_rotation_even_stride(cluster):
    """Even checkpoint strides (e.g. ckpt_every=2) must still alternate
    shadow slots — raw step % slots would pin every save to slot 0."""
    m2 = cluster.checkpointer.save(2, _tree(2))
    m4 = cluster.checkpointer.save(4, _tree(4))
    assert m2["slot"] != m4["slot"]
    cluster.checkpointer.wait_async()
    for s in (2, 4):
        out, _ = cluster.checkpointer.restore(s)
        np.testing.assert_array_equal(out["w"], _tree(s)["w"])


def test_restore_rejects_reused_slot(cluster):
    """An old manifest pointing at a slot a newer save overwrote must
    raise, not silently return mixed-step data."""
    c = cluster
    for s in (1, 2, 3):  # slots: 0, 1, 0 — step 1's slot now holds step 3
        c.checkpointer.save(s, _tree(s))
    c.checkpointer.wait_async()
    with pytest.raises(IOError):
        c.checkpointer.restore(1)


def test_delta_chain_never_overwrites_base(cluster_delta):
    """Slot rotation must skip the slot holding the active delta base —
    otherwise the third delta save destroys the base and orphans every
    delta checkpoint in the chain."""
    c = cluster_delta
    base = _tree(1)
    c.checkpointer.save(1, base)  # full
    for s in (2, 3, 4):  # three deltas against the same base
        t = {k: v + np.float32(1e-3) for k, v in base.items()}
        man = c.checkpointer.save(s, t, base_step=1)
        assert man["slot"] != 0, "delta save rotated onto the base slot"
    c.checkpointer.wait_async()
    out, man = c.checkpointer.restore(4)
    assert man["delta_base"] == 1
    assert np.abs(out["w"] - (base["w"] + 1e-3)).max() < 1e-4


def test_checkpoint_index_survives_node0_loss(cluster):
    """Manifests are replicated to every live pool, so losing the first
    node (the old single meta store) keeps the index readable and
    subsequent saves land on the survivors."""
    c = cluster
    c.tiered.save_async(1, _tree(1)).result(timeout=30)
    c.tiered.quiesce()
    c.kill_node("node0")
    assert c.checkpointer.latest_step() == 1
    out, man = c.checkpointer.restore_latest_recoverable(
        lost_nodes=["node0"])
    assert man["step"] == 1
    np.testing.assert_array_equal(out["w"], _tree(1)["w"])
    # the survivors keep checkpointing
    man2 = c.checkpointer.save(2, _tree(2))
    assert "node0" not in man2["nodes"]
    c.checkpointer.wait_async()
    out, _ = c.checkpointer.restore(2)
    np.testing.assert_array_equal(out["w"], _tree(2)["w"])


# ---------------------------------------------------------------------------
# object / prefetch channel
# ---------------------------------------------------------------------------

def test_offload_fetch_prefetch_accounting(cluster):
    t = _tree(9)
    cluster.tiered.offload("serve/sessA", t).result(timeout=30)
    # resident -> prefetch hit
    res = cluster.tiered.prefetch(["serve/sessA"]).result(timeout=30)
    assert res == {"hits": 1, "loads": 0, "missing": 0}
    # evict everything, then prefetch must load from pmem
    assert cluster.tiered.evict_cold() >= 1
    res = cluster.tiered.prefetch(["serve/sessA"]).result(timeout=30)
    assert res == {"hits": 0, "loads": 1, "missing": 0}
    # demand fetch is now a DRAM hit
    h0 = cluster.dlm.hits
    out = cluster.tiered.fetch("serve/sessA")
    np.testing.assert_array_equal(out["w"], t["w"])
    assert cluster.dlm.hits == h0 + 1
    assert cluster.tiered.stats["prefetch_hits"] == 1
    assert cluster.tiered.stats["prefetch_loads"] == 1


def test_prefetch_missing_object_is_advisory(cluster):
    """Prefetch is a hint: an object absent from pmem is counted, never
    raised, and must not poison the rest of the batch or a later join."""
    cluster.tiered.offload("serve/x", _tree(0)).result(timeout=30)
    cluster.tiered.evict_cold()
    res = cluster.tiered.prefetch(
        ["serve/never-written", "serve/x"]).result(timeout=30)
    assert res == {"hits": 0, "loads": 1, "missing": 1}
    cluster.tiered.join()  # nothing fatal was recorded


def test_evict_cold_respects_idle_threshold(cluster):
    cluster.tiered.offload("serve/hot", _tree(1)).result(timeout=30)
    # nothing is older than an hour
    assert cluster.tiered.evict_cold(max_idle_s=3600.0) == 0
    assert cluster.tiered.evict_cold(max_idle_s=0.0) == 1


def test_stage_in_hit_rate(cluster):
    c = cluster
    for i in range(3):
        c.external.put(f"shard{i}", {"x": np.arange(i + 1)})
    futs = c.tiered.stage_in("node0", ["shard0", "shard1"])
    for f in futs:
        f.result(timeout=30)
    futs = c.tiered.stage_in("node0", ["shard0", "shard1", "shard2"])
    for f in futs:
        f.result(timeout=30)
    assert c.tiered.stats["stage_in_hits"] == 2
    assert c.tiered.stats["stage_in_loads"] == 3
    assert abs(c.tiered.stage_in_hit_rate() - 0.4) < 1e-9


# ---------------------------------------------------------------------------
# serve-engine integration: spill/resume/prefetch through TieredIO
# ---------------------------------------------------------------------------

def test_serve_spill_resume_via_tiered(cluster):
    from repro.serve.engine import ServeEngine
    eng = ServeEngine.__new__(ServeEngine)  # no model needed for spill
    eng.tiered = cluster.tiered
    eng.store = None
    eng.cache = {"k": np.ones((2, 4), np.float32)}
    eng.pos = 7
    eng.spill("sess0")
    assert eng.cache is None
    eng.prefetch_sessions(["sess0"]).result(timeout=30)
    eng.resume("sess0")
    assert eng.pos == 7
    np.testing.assert_array_equal(np.asarray(eng.cache["k"]),
                                  np.ones((2, 4), np.float32))


# ---------------------------------------------------------------------------
# mesh builder
# ---------------------------------------------------------------------------

def test_make_mesh_on_installed_jax():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
