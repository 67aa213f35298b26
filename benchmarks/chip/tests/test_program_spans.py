"""The program-span reduction: loading the program's spans with their
lines, charging chip idle time to them by exact intersection on the
driving line, the pmem commit's phases, and the readings on stretches of
traces recorded on the chip."""
from __future__ import annotations

import json
import threading
from pathlib import Path

import numpy as np
import pytest

from harness import program_spans as ps
from harness import trace

MS = 1e6
DATA = Path(__file__).parent / "data"


def _made() -> ps.ProgramTrace:
    """One chip busy over 1-3 and 6-7 ms of a 10 ms window. The driving
    line (0) holds a decode step, its sync, and a resume with a prefetch
    inside; line 1 holds a commit open over the whole window."""
    return ps.ProgramTrace(
        ops={"/device:TPU:0": [("fusion", 1 * MS, 3 * MS),
                               ("fusion", 6 * MS, 7 * MS)]},
        window=(0.0, 10 * MS), driving_line=0,
        spans=[("bench.engine.decode", 0, 0.2 * MS, 5.2 * MS),
               ("engine.decode.step", 0, 0.5 * MS, 2 * MS),
               ("engine.decode.sync", 0, 2 * MS, 5 * MS),
               ("serve.resume", 0, 5.5 * MS, 9 * MS),
               ("dlm.prefetch", 0, 6.5 * MS, 8 * MS),
               ("ckpt.commit", 1, -2 * MS, 12 * MS)])


def test_idle_is_charged_by_exact_intersection_on_the_driving_line():
    sp = ps.split(_made())
    # idle 0-1, 3-6, 7-10: 0-0.2 none, 0.2-0.5 the harness's decode span,
    # 0.5-1 step, 3-5 sync, 5-5.2 decode span, 5.2-5.5 none, 5.5-6
    # resume, 7-8 prefetch, 8-9 resume, 9-10 none. By midpoint the sync
    # would take all of 3-6; the commit on line 1 takes nothing
    want = {ps.NONE: 1.5, ps.DECODE_SPAN: 0.5, "engine.decode.step": 0.5,
            "engine.decode.sync": 2.0, "serve.resume": 1.5,
            "dlm.prefetch": 1.0}
    assert sp.idle_s == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    assert sum(sp.idle_s.values()) == pytest.approx(7e-3)
    assert sp.span_s["ckpt.commit"] == pytest.approx(10e-3)
    assert sp.span_s[ps.DECODE_SPAN] == pytest.approx(5e-3)
    # over the 5 ms of decode spans: step 0.5 ms idle, sync 2 ms
    assert ps.decode_dispatch_idle(sp) == pytest.approx(10.0)
    assert ps.decode_sync_idle(sp) == pytest.approx(40.0)
    assert ps.decode_idle_exact(sp) == pytest.approx(60.0)


def test_a_second_chip_halves_the_idle_and_no_chip_reads_nothing():
    tr = _made()
    tr.ops["/device:TPU:1"] = [("fusion", 0.0, 10 * MS)]
    sp = ps.split(tr)
    assert sp.n_chips == 2
    assert sp.idle_s["engine.decode.sync"] == pytest.approx(1e-3)
    tr.ops = {}
    sp = ps.split(tr)
    assert sp.idle_s == {} and ps.decode_sync_idle(sp) is None
    assert ps.readings(sp) == {}


def test_commit_phases_and_checkpoint_stall_parts():
    w = 10 * MS
    spans = [
        # writer line 1: a commit that began before the window, whole
        ("ckpt.commit", 1, -2 * MS, 9 * MS),
        ("store.put", 1, 1 * MS, 4 * MS),
        ("store.put.write", 1, 1 * MS, 2 * MS),
        ("store.put.crc", 1, 2 * MS, 2.5 * MS),
        ("store.put.write", 1, 2.5 * MS, 3 * MS),
        ("store.put.crc", 1, 3 * MS, 3.2 * MS),
        ("store.put.flush", 1, 3.5 * MS, 3.9 * MS),
        ("store.put", 1, 5 * MS, 8 * MS),
        ("store.put.write", 1, 5 * MS, 6 * MS),
        ("store.put.crc", 1, 6 * MS, 7 * MS),
        ("store.put.flush", 1, 7 * MS, 7.5 * MS),
        # ends after the window: not this window's commit
        ("ckpt.commit", 1, 9.2 * MS, 12 * MS),
        ("store.put.write", 1, 9.5 * MS, 9.8 * MS),
        # driving line 0: two checkpoints' copy and submit
        ("train.ckpt.d2h", 0, 1 * MS, 2 * MS),
        ("train.ckpt.submit", 0, 2 * MS, 5 * MS),
        ("tiered.save.slot_wait", 0, 2.1 * MS, 4.9 * MS),
        ("train.ckpt.d2h", 0, 6 * MS, 6.5 * MS),
        ("train.ckpt.submit", 0, 6.5 * MS, 7 * MS),
        ("tiered.save.slot_wait", 0, 6.6 * MS, 6.8 * MS)]
    sp = ps.split(ps.ProgramTrace({}, (0.0, w), 0, spans))
    assert len(sp.commits) == 1
    c = sp.commits[0]
    assert c["store.put.write"] == pytest.approx(2.5e-3)
    assert c["store.put.crc"] == pytest.approx(1.7e-3)
    assert c["store.put.flush"] == pytest.approx(0.9e-3)
    assert c["store.put.self"] == pytest.approx(6e-3 - 5.1e-3)
    assert c["ckpt.commit.self"] == pytest.approx(11e-3 - 6e-3)
    got = ps.readings(sp)
    assert got == {"ckpt_d2h_ms": pytest.approx(0.75),
                   "ckpt_slot_wait_ms": pytest.approx(1.5),
                   "ckpt_put_write_ms": pytest.approx(2.5),
                   "ckpt_put_crc_ms": pytest.approx(1.7),
                   "ckpt_put_flush_ms": pytest.approx(0.9)}
    assert "per commit (1)" in ps.log_line(sp)
    assert ps.decode_syncs_per_token(30, 30) == 1.0
    assert ps.decode_syncs_per_token(3, 0) is None


def test_load_keeps_each_thread_on_its_own_line(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    x = jnp.ones((64, 64))

    def worker():
        with TraceAnnotation("ckpt.commit"):
            with TraceAnnotation("store.put", node="node0", bytes=8):
                (x @ x).block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(trace.WINDOW_SPAN):
            with TraceAnnotation("engine.decode.step"):
                (x + 1).block_until_ready()
            t = threading.Thread(target=worker)
            t.start()
            t.join()
            with TraceAnnotation("jax.unrelated"):
                pass
    finally:
        jax.profiler.stop_trace()
    tr = ps.load(str(tmp_path))
    lines = {n: line for n, line, _, _ in tr.spans}
    assert set(lines) == {"engine.decode.step", "ckpt.commit", "store.put"}
    assert lines["engine.decode.step"] == tr.driving_line
    assert lines["ckpt.commit"] == lines["store.put"] != tr.driving_line
    lo, hi = tr.window
    assert all(lo <= s <= e <= hi for _, _, s, e in tr.spans)
    again = ps.ProgramTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again == tr


def _recorded(name: str) -> ps.ProgramTrace:
    return ps.ProgramTrace.from_json(json.loads((DATA / name).read_text()))


def _brute_idle(tr: ps.ProgramTrace, names) -> float:
    """Idle seconds of the one chip while the innermost driving-line
    span is one of ``names``, on a 1 us grid."""
    lo, hi = tr.window
    n = int((hi - lo) / 1e3)
    busy = np.zeros(n, bool)
    for ev in tr.ops.values():
        for _, s, e in ev:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                busy[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    owner = np.full(n, "", object)
    start = np.full(n, -np.inf)
    for name, line, s, e in tr.spans:
        if line != tr.driving_line:
            continue
        a = max(int((max(s, lo) - lo) / 1e3), 0)
        b = min(int((min(e, hi) - lo) / 1e3), n)
        later = start[a:b] <= s
        owner[a:b][later] = name
        start[a:b][later] = s
    return float(np.sum(~busy & np.isin(owner, list(names)))) * 1e-6


@pytest.mark.parametrize("name", ["program_chat.json"])
def test_decode_readings_on_a_recorded_chip_trace(name):
    tr = _recorded(name)
    sp = ps.split(tr)
    assert sp.n_chips == 1
    got = ps.readings(sp)
    disp, sync = got["decode_dispatch_idle"], got["decode_sync_idle"]
    assert 0 < disp < 100 and 0 < sync < 100
    assert disp + sync <= ps.decode_idle_exact(sp) < 100
    decode = sp.span_s[ps.DECODE_SPAN]
    edges = 2e-6 * len(tr.spans) + 1e-5   # the grid's error, per edge
    for names, share in ((ps.DISPATCH, disp), ((ps.SYNC,), sync)):
        brute = _brute_idle(tr, names)
        assert share * decode / 100 == pytest.approx(brute, abs=edges)
    # the stretch may cut the last token between its step and its sync
    assert 0 <= len(sp.ended[ps.DISPATCH[0]]) - len(sp.ended[ps.SYNC]) <= 1
    busy = ps._Busy(next(iter(tr.ops.values())), *tr.window)
    assert sum(sp.idle_s.values()) == pytest.approx(
        sp.window_s - busy.between(*tr.window) * 1e-9, rel=1e-9)


@pytest.mark.parametrize("name", ["program_train.json"])
def test_commit_readings_on_a_recorded_chip_trace(name):
    tr = _recorded(name)
    sp = ps.split(tr)
    got = ps.readings(sp)
    assert len(sp.commits) == 1
    c = sp.commits[0]
    parts = [c[p] for p in ps.PUT_PHASES] + [c["store.put.self"],
                                             c["ckpt.commit.self"]]
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(c[ps.COMMIT], rel=1e-9)
    for phase in ("write", "crc", "flush"):
        assert got[f"ckpt_put_{phase}_ms"] == pytest.approx(
            c[f"store.put.{phase}"] * 1e3)
    puts = [s for s in tr.spans if s[0] == ps.PUT]
    assert puts and {s[1] for s in puts} != {tr.driving_line}


def test_spans_tool_reads_a_tiny_run(drive, tiny):
    """``spans.py``'s measurement on the CPU at the smoke sizes (``drive``
    gives the CPU device a peak): the program's spans are found, and with
    no chip there are no idle shares."""
    import jax

    import run as bench
    import spans
    from harness import common

    clock = bench.Clock(jax.devices())
    for wl in ("t-chat", "t-train"):
        cell = common.load_cell(wl, True, root=tiny, bench=tiny)
        e2e = common.load_cell(wl, False, root=tiny, bench=tiny)
        line = spans.measure(cell, e2e, 2**31 + 3, 3.0, clock)
        assert line["correct"]
        prog = line["program"]
        assert "decode_sync_idle" not in prog
        if wl == "t-chat":
            assert prog["decode_syncs_per_token"] == 1.0
            assert "tpot_ms" in line["end_to_end"]
        else:
            assert {"ckpt_d2h_ms", "ckpt_slot_wait_ms", "ckpt_put_write_ms",
                    "ckpt_put_crc_ms", "ckpt_put_flush_ms"} <= set(prog)
            assert line["commit_parts_s"]["ckpt.commit"] > 0
