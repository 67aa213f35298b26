#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates, many seeds in one
process (the benchmark's own runs never do this).

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 11,12,13 --seconds 10 [--controls fp8,half] \
        [--rate 0.8] [--out cal.jsonl] [--trace-out f.json]

For each seed it runs the cell as ``run.py`` does (a window of
``--seconds``), compares with the reference, and prints one JSON line:
the program's numbers compared, the same numbers read with each control
in the program's place (``fp8``: the reference in float8; ``half``: the
reference on half of each batch), and the cell's end-to-end metrics.
``--rate`` offers a serve cell another rate (the capacity sweep).
``--trace-out`` traces the first seed's window and keeps the first
``--trace-ms`` of it, reduced, as the self-checks' recorded trace.
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


def clip_trace(tr, ms: float):
    """``ms`` milliseconds of the window, from the first harness span in
    it, as a trace of its own (its window span cut to that stretch)."""
    from harness import trace
    lo = min(s for n, s, e in tr.spans if n != trace.WINDOW_SPAN)
    hi = lo + ms * 1e6
    ops = {p: [(n, s, e) for n, s, e in ev if e > lo and s < hi]
           for p, ev in tr.ops.items()}
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in tr.spans
             if e > lo and s < hi]
    return trace.Trace(ops, spans)


def main(argv=None) -> None:
    import importlib
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--trace-ms", type=float, default=400.0)
    args = ap.parse_args(argv)

    cell = common.load_cell(args.workload, False)
    devices = common.require_chips(cell.chips)
    clock = bench.Clock(devices)
    common.enable_compile_cache()
    driver = importlib.import_module(f"harness.{cell.traffic['driver']}")
    controls = [c for c in args.controls.split(",") if c]
    kw = {"rate": args.rate} if args.rate is not None else {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
            if args.trace_out and i == 0 else None
        try:
            rec = driver.run(cell, seed, args.seconds, trace_dir, clock,
                             controls=controls, **kw)
            if trace_dir:
                from harness import trace
                trace.save(clip_trace(trace.load(trace_dir), args.trace_ms),
                           Path(args.trace_out))
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        bench.annotate(rec, cell, None, None)
        line = {"workload": cell.name, "seed": seed, "rate": args.rate,
                "compared": {c.name: c.value for c in rec["compared"]},
                "controls": rec["controls"],
                "metrics": bench.read_metrics(cell, rec),
                "attempted": rec["attempted"],
                "memory_peak_bytes": rec["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
