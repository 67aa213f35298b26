"""Program spans on the profiler's clock: every span the program opens
through ``repro.obs.trace.annotate`` lands on its thread's line of the
``/host:CPU`` plane of a ``jax.profiler`` trace, nested as the code nests
it, and the per-token decode path writes no ring event and no histogram."""
import glob
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs.plane import TelemetryPlane
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import annotate

MAIN = "test.main"
TRAIN_SPANS = ("train.ckpt.d2h", "train.ckpt.submit",
               "tiered.save.slot_wait")
COMMIT_SPANS = ("ckpt.commit", "store.put", "store.put.write",
                "store.put.crc", "store.put.flush")
DECODE_SPANS = ("engine.decode.step", "engine.decode.sync")


def _record(log_dir: Path, body):
    """Run ``body`` inside a ``test.main`` span under the profiler;
    return its host events as (line, name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with annotate(MAIN):
            body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            out.extend((i, e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events)
    return out


def _named(events, name):
    return [e for e in events if e[1] == name]


def _inside(inner, outer) -> bool:
    """Same line, and ``inner``'s interval within ``outer``'s."""
    return inner[0] == outer[0] and outer[2] <= inner[2] \
        and inner[3] <= outer[3]


def _parent(ev, events, name):
    hits = [o for o in _named(events, name) if _inside(ev, o)]
    assert hits, f"{ev[1]} at {ev[2]} is inside no {name}"
    return hits[0]


def _train(cluster, steps: int):
    from repro.train import loop as tl

    @jax.jit
    def step(params, opt_state, batch):
        w = params["w"] + batch["x"].sum()
        return {"w": w}, {"m": opt_state["m"] + 1.0}, {"loss": w.sum()}

    params, opt_state = {"w": jnp.ones(256)}, {"m": jnp.zeros(256)}
    batches = ({"x": np.full(4, i, np.float32)} for i in range(steps))
    return tl.run(step, params, opt_state, batches, cluster,
                  tl.LoopConfig(steps=steps, ckpt_every=1))


def _engine(cluster):
    """A ServeEngine whose jitted step is a stand-in: the decode loop,
    its spans and counters are what is under test, not the model."""
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(None, None, {}, tiered=cluster.tiered)
    eng._decode = jax.jit(
        lambda p, c, t, pos, held: (
            jnp.full_like(t, 3), {"k": c["k"] + 1.0}, pos + 1, held),
        donate_argnums=(1, 3, 4))
    eng.cache, eng.pos = {"k": jnp.zeros(4)}, 0
    return eng


def test_train_checkpoint_spans_on_their_threads(cluster, tmp_path):
    _train(cluster, 1)  # compile outside the trace
    steps = 3
    ev = _record(tmp_path, lambda: _train(cluster, steps))
    drv = _named(ev, MAIN)
    assert len(drv) == 1
    drv = drv[0]
    for name in TRAIN_SPANS:
        spans = _named(ev, name)
        assert len(spans) == steps, (name, len(spans))
        assert all(_inside(s, drv) for s in spans), name
    for wait in _named(ev, "tiered.save.slot_wait"):
        _parent(wait, ev, "train.ckpt.submit")
    commits = _named(ev, "ckpt.commit")
    assert len(commits) == steps
    writer = {c[0] for c in commits}
    assert len(writer) == 1 and writer != {drv[0]}
    puts = _named(ev, "store.put")
    assert len(puts) == steps * len(cluster.node_ids)
    assert {p[4]["node"] for p in puts} == set(cluster.node_ids)
    for put in puts:
        _parent(put, ev, "ckpt.commit")
        assert int(put[4]["bytes"]) > 0
    for name in ("store.put.write", "store.put.crc", "store.put.flush"):
        spans = _named(ev, name)
        assert spans, name
        for s in spans:
            _parent(s, ev, "store.put")
    assert len(_named(ev, "store.put.flush")) == len(puts)
    # the begin/end pairs that open and close on one thread reach the
    # profiler too: the buddy copies run on the scheduler's workers
    reps = _named(ev, "sched.replicate")
    assert reps and {r[0] for r in reps}.isdisjoint({drv[0]} | writer)


def test_train_spans_observe_histograms_not_rings(cluster):
    st = _train(cluster, 2)
    cluster.tiered.quiesce()
    hist = cluster.obs.snapshot()["histograms"]
    for name in TRAIN_SPANS:
        assert hist[f"span.{name}.s"]["count"] == 2, name
    # mean d2h + submit is the stall the loop records per checkpoint
    split = sum(hist[f"span.{n}.s"]["sum"] for n in TRAIN_SPANS[:2])
    assert split <= sum(st.ckpt_seconds) + 1e-3
    names = {e["name"] for p in cluster.pools.values()
             for e in FlightRecorder.replay(p)}
    assert names.isdisjoint(TRAIN_SPANS + COMMIT_SPANS)
    assert "ckpt.save" in names  # the lifecycle root still rings


def test_decode_spans_nest_in_the_caller_on_the_main_line(cluster,
                                                          tmp_path):
    eng = _engine(cluster)
    eng.decode(np.zeros(1, np.int32), 1)  # compile outside the trace
    out = {}

    def body():
        with annotate("test.caller"):
            out["toks"] = eng.decode(np.zeros(1, np.int32), 4)

    ev = _record(tmp_path, body)
    assert out["toks"].shape == (1, 5)
    np.testing.assert_array_equal(out["toks"][0, 1:], [3, 3, 3, 3])
    caller = _named(ev, "test.caller")[0]
    assert _inside(caller, _named(ev, MAIN)[0])
    steps, syncs = [_named(ev, n) for n in DECODE_SPANS]
    assert [len(steps), len(syncs)] == [4, 1]
    assert not _named(ev, "engine.decode.sample")
    for s in steps + syncs:
        assert _inside(s, caller), s
    # the one sync waits for all four tokens, after the last dispatch
    assert max(s[3] for s in steps) <= syncs[0][2]


def test_decode_counters_grow_by_steps_per_call(cluster):
    eng = _engine(cluster)
    reg = cluster.obs.registry
    for steps in (3, 1, 5):
        t0 = reg.counter("serve.decode.tokens").value
        s0 = reg.counter("serve.decode.host_syncs").value
        eng.decode(np.zeros(1, np.int32), steps)
        assert reg.counter("serve.decode.tokens").value - t0 == steps
        assert reg.counter("serve.decode.host_syncs").value - s0 == 1
    # the per-token path takes no histogram lock and writes no ring
    hist = cluster.obs.snapshot()["histograms"]
    assert not [h for h in hist if h.startswith("span.engine.")]
    names = {e["name"] for p in cluster.pools.values()
             for e in FlightRecorder.replay(p)}
    assert names.isdisjoint(DECODE_SPANS)


def test_session_spans_reach_the_profiler(cluster, tmp_path):
    eng = _engine(cluster)
    sm = cluster.sessions
    sm.start("warm", eng)
    sm.suspend("warm", wait=True)
    sm.resume("warm", eng)
    sm.end("warm")
    eng.cache, eng.pos = {"k": jnp.zeros(4)}, 0

    def body():
        sm.start("s", eng)
        sm.suspend("s", wait=True)
        sm.resume("s", eng)

    ev = _record(tmp_path, body)
    drv = _named(ev, MAIN)[0]
    for name in ("serve.spill", "serve.resume"):
        spans = _named(ev, name)
        assert len(spans) == 1 and _inside(spans[0], drv), name
    assert _named(ev, "serve.resume")[0][4]["session"] == "s"
    hist = cluster.obs.snapshot()["histograms"]
    assert hist["span.serve.resume.s"]["count"] == 2


def test_plane_span_and_local_begin():
    plane = TelemetryPlane()
    with plane.span("x.block", k=1):
        pass
    with pytest.raises(ValueError):
        with plane.span("x.block"):
            raise ValueError("inside")
    assert plane.registry.histogram("span.x.block.s").count == 2
    sp = plane.begin("x.pair", local=True, n=3)
    assert sp.ann is not None
    plane.end(sp)
    assert sp.ann is None
    assert plane.begin("x.cross").ann is None
    assert plane.registry.histogram("span.x.pair.s").count == 1
