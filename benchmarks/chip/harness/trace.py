"""From a profiler trace to busy time, op time, kernel time and idle gaps.

A trace is reduced to plain lists first (``Trace``): the device ops of
each chip, as (name, start_ns, end_ns) from the "XLA Ops" line of each
``/device:`` plane, and the harness's own host spans, the
``jax.profiler.TraceAnnotation`` events whose names start with ``bench.``.
Everything after that is arithmetic on those lists, which the self-checks
run on a small trace recorded on the chip.
"""
from __future__ import annotations

import dataclasses
import glob
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start_ns, end_ns

OPS_LINE = "XLA Ops"
CUSTOM = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]          # device plane name -> its ops
    spans: List[Event]                   # harness host spans

    def to_json(self) -> dict:
        return {"ops": self.ops, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   [tuple(e) for e in d["spans"]])


def load(log_dir: str) -> Trace:
    """The ``Trace`` of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(op_name(e.name), e.start_ns,
                                        e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, spans)


def op_name(text: str) -> str:
    """An op's HLO name (``%fusion.12``) from the text the trace gives,
    marked `` (kernel)`` where it is a Pallas kernel's custom call."""
    name = text.split(" = ", 1)[0] if text.startswith("%") else text
    return name + " (kernel)" if CUSTOM in text else name


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over the chips
    op_s: Dict[str, float]                 # op name -> seconds, mean/chip
    idle_by_span: Dict[str, float]         # host span -> idle seconds
    span_s: Dict[str, float]               # host span -> seconds open
    n_chips: int

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def summarize(tr: Trace) -> Summary:
    """Busy, op and idle time inside the harness's ``bench.window`` span.
    An idle gap of a chip is charged to the innermost harness span open
    at its midpoint, or to the window itself when none is."""
    wins = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(wins)}")
    lo, hi = wins[0]
    inner = sorted((e - s, n, s, e) for n, s, e in tr.spans
                   if n != WINDOW_SPAN)
    busy_total = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    chips = [p for p, ev in tr.ops.items() if ev]
    for plane in chips:
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in tr.ops[plane]
               if e > lo and s < hi]
        for n, s, e in evs:
            op_s[n] += (e - s) * 1e-9
        busy = union([(s, e) for _, s, e in evs])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            name = next((n for _, n, s, e in inner if s <= mid < e),
                        WINDOW_SPAN)
            idle[name] += (g1 - g0) * 1e-9
    span_s: Dict[str, float] = defaultdict(float)
    for _, n, s, e in inner:
        span_s[n] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9
    k = max(len(chips), 1)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / k,
                   op_s={n: v / k for n, v in op_s.items()},
                   idle_by_span={n: v / k for n, v in idle.items()},
                   span_s=dict(span_s), n_chips=len(chips))


def save(tr: Trace, path: Path) -> None:
    path.write_text(json.dumps(tr.to_json()))
