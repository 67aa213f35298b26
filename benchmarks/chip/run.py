#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by the names
in ``BENCHMARK.json``: ``configs/<file>``, ``traffic/<mix>.json`` and
``metrics/<metric>.py`` under this directory. With ``--trace 0`` the line
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window. The run exits non-zero,
printing no result, without a TPU or with fewer chips than the cell asks.
"""
from __future__ import annotations

import argparse
import shutil
import signal
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from harness import common  # noqa: E402


class Clock:
    """Set-up time from process start, compile counts, memory peak."""

    def __init__(self, devices):
        self.t_start = common.process_start()
        self.devices = devices
        self.counter = common.CompileCounter()

    def setup_done(self) -> float:
        return time.time() - self.t_start

    def compiles(self) -> int:
        return self.counter.n

    def memory_peak(self) -> int:
        return common.memory_peak(self.devices)

    @staticmethod
    def profile_options():
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        return opts


def read_metrics(cell, record: dict) -> dict:
    out = {}
    for name, spec in cell.metrics.items():
        reader = common.load_module(common.BENCH / "metrics" / f"{name}.py")
        value = reader.read(record)
        if value is None:
            common.log(f"metric {name}: nothing to read")
            continue
        out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def annotate(record: dict, cell, peak, summary) -> None:
    """Add what the metric readers read beside a driver's record: the
    configuration's sizes, the traffic, the chip's peaks, the trace."""
    record.update(model=cell.config["model"], traffic=cell.traffic,
                  peak=peak, trace=summary)


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            clock) -> dict:
    """Run the cell on ``devices`` and return the result line's fields."""
    import importlib

    from harness import peaks
    peak = peaks.peak(devices[0].device_kind)
    driver = importlib.import_module(f"harness.{cell.traffic['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        record = driver.run(cell, seed, seconds, trace_dir, clock)
        summary = None
        if trace_dir:
            from harness import trace as trace_mod
            summary = trace_mod.summarize(trace_mod.load(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    annotate(record, cell, peak, summary)
    device = common.device_info(devices)
    device["memory_peak_bytes"] = record["memory_peak_bytes"]
    breakdown = None
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
    common.log(f"set-up {record['setup_s']:.3f} s, compiles in window "
               f"{record['compiles_in_window']}")
    compared = record["compared"]
    correct = all(c.ok for c in compared)
    return {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"],
            "metrics": read_metrics(cell, record), "device": device,
            "compared": compared, "breakdown": breakdown}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    # a run ended from outside unwinds, so that its pmem pools go too
    signal.signal(signal.SIGTERM, lambda sig, _: sys.exit(128 + sig))

    cell = common.load_cell(args.workload, bool(args.trace))
    devices = common.require_chips(cell.chips)
    clock = Clock(devices)
    common.enable_compile_cache()
    common.emit(**execute(cell, args.seed, args.seconds, bool(args.trace),
                          devices, clock))


if __name__ == "__main__":
    main()
