#!/usr/bin/env python3
"""A cell's traced run, read through the program's own spans.

    python3 benchmarks/chip/spans.py --workload <name> --seeds 11,12 \
        --seconds 51 [--out spans.jsonl] [--trace-out f.json]

For each seed it runs the cell as ``run.py --trace 1`` does, in one
process, and prints one JSON line: the cell's end-to-end metrics (read
with the profiler on), its per-layer metrics, and the readings of the
program's spans (``harness/program_spans.py``): the checkpoint stall's
parts, the pmem commit's phases, and the decode loop's idle time by span.
Syncs per decoded token are the window's ``engine.decode.sync`` spans
over its ``engine.decode.step`` spans. The attribution line goes to
stderr. ``--trace-out`` keeps a stretch of the first seed's trace,
reduced, for the self-checks: the first ``ckpt.commit`` that starts in
the window, whole, or else the first ``--trace-ms`` of decode; each
chip's ops are kept as the union of their intervals.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run as bench  # noqa: E402
from harness import common  # noqa: E402
from harness import program_spans as ps  # noqa: E402


def stretch(tr: ps.ProgramTrace, ms: float) -> ps.ProgramTrace:
    """The stretch of ``tr`` the self-checks keep (see the module doc)."""
    lo, hi = tr.window
    commits = sorted((s, e) for n, _, s, e in tr.spans
                     if n == ps.COMMIT and lo <= s and e <= hi)
    if commits:
        a, b = commits[0]
    else:
        a = min(s for n, _, s, e in tr.spans
                if n == ps.DECODE_SPAN and s >= lo)
        b = min(a + ms * 1e6, hi)
    out = ps.clip(tr, a, b)
    out.ops = {p: [("(busy)", s, e) for s, e in
                   ps.trace.union([(s, e) for _, s, e in ev])]
               for p, ev in out.ops.items()}
    return out


def measure(cell, e2e_cell, seed: int, seconds: float, clock,
            trace_out=None, trace_ms: float = 400.0) -> dict:
    """One traced run of ``cell``; returns its JSON line's fields."""
    import importlib

    from harness import peaks
    from harness import trace as trace_mod
    driver = importlib.import_module(f"harness.{cell.traffic['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        record = driver.run(cell, seed, seconds, trace_dir, clock)
        summary = trace_mod.summarize(trace_mod.load(trace_dir))
        ptr = ps.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if trace_out:
        Path(trace_out).write_text(json.dumps(
            stretch(ptr, trace_ms).to_json()))
    bench.annotate(record, cell, peaks.peak(clock.devices[0].device_kind),
                   summary)
    sp = ps.split(ptr)
    common.log(ps.log_line(sp))
    ended = {n: len(v) for n, v in sorted(sp.ended.items())
             if ps.is_program(n)}
    return {
        "workload": cell.name, "seed": seed,
        "correct": all(c.ok for c in record["compared"]),
        "end_to_end": bench.read_metrics(e2e_cell, record),
        "per_layer": bench.read_metrics(cell, record),
        "program": ps.readings(sp, ended.get(ps.SYNC),
                               ended.get(ps.DISPATCH[0])),
        "decode_idle_exact": ps.decode_idle_exact(sp),
        "window_s": sp.window_s, "busy_s": summary.busy_s,
        "idle_gaps": summary.breakdown()["idle_gaps"],
        "idle_by_span": sp.idle_s,
        "program_span_s": {n: v for n, v in sp.span_s.items()
                           if ps.is_program(n)},
        "program_spans_ended": ended,
        "commit_parts_s": {k: sum(c[k] for c in sp.commits) /
                           len(sp.commits) for k in sp.commits[0]}
        if sp.commits else {},
        "ckpt_s": record.get("ckpt_s")}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--trace-ms", type=float, default=400.0)
    args = ap.parse_args(argv)

    cell = common.load_cell(args.workload, True)
    e2e_cell = common.load_cell(args.workload, False)
    devices = common.require_chips(cell.chips)
    clock = bench.Clock(devices)
    common.enable_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = measure(cell, e2e_cell, seed, args.seconds, clock,
                       args.trace_out if i == 0 else None, args.trace_ms)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
