"""The program's own spans in a profiler trace, and the chip's idle time
charged to them.

The program opens its spans through ``repro.obs.trace.annotate``; each
lands on its thread's line of the ``/host:CPU`` plane, on the device
trace's clock. ``trace.py`` keeps only the harness's ``bench.`` spans and
charges an idle gap to the one open at its midpoint. This module keeps
the program's spans, and the harness's, with the line each is on
(``ProgramTrace``), and charges each stretch of chip idle time, by exact
interval intersection, to the innermost span open at that moment on the
driving line: the line that holds ``bench.window``, the thread that
drives the cell. A harness span there is never inside a program span, so
it takes only the idle time under no program span.

From that reduction (``Split``) come the readings of the checkpoint
stall's parts, the pmem commit's phases and the decode loop's idle time
(the ``*_ms`` and ``decode_*`` functions below), each ``None`` where the
trace holds nothing to read, as from a program without these spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import statistics
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

HostSpan = Tuple[str, int, float, float]   # name, line, start_ns, end_ns

# first parts of the program's span names (``bench.`` is the harness's)
PREFIXES = ("train.", "tiered.", "ckpt.", "store.", "engine.", "serve.",
            "sched.", "repair.", "dlm.", "exch.", "stage.", "wf.")
NONE = "(none)"     # chip idle with no span but the window open
COMMIT = "ckpt.commit"
PUT = "store.put"
PUT_PHASES = ("store.put.write", "store.put.crc", "store.put.flush")
DECODE_SPAN = "bench.engine.decode"
DISPATCH = ("engine.decode.step", "engine.decode.sample")
SYNC = "engine.decode.sync"


def is_program(name: str) -> bool:
    return name.startswith(PREFIXES)


@dataclasses.dataclass
class ProgramTrace:
    ops: Dict[str, List[trace.Event]]    # device plane name -> its ops
    window: Tuple[float, float]          # the bench.window span
    driving_line: int                    # the line that holds it
    spans: List[HostSpan]                # program and harness spans

    def to_json(self) -> dict:
        return {"ops": self.ops, "window": list(self.window),
                "driving_line": self.driving_line, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        return cls({k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   tuple(d["window"]), int(d["driving_line"]),
                   [tuple(e) for e in d["spans"]])


def load(log_dir: str) -> ProgramTrace:
    """The ``ProgramTrace`` of the one ``.xplane.pb`` under ``log_dir``.
    Lines are numbered across the host planes in the order they come."""
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {files}")
    ops: Dict[str, List[trace.Event]] = {}
    spans: List[HostSpan] = []
    wins: List[Tuple[int, float, float]] = []
    line_no = 0
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops[plane.name] = [(trace.op_name(e.name), e.start_ns,
                                        e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW_SPAN:
                        wins.append((line_no, e.start_ns, e.end_ns))
                    elif is_program(e.name) or \
                            e.name.startswith(trace.SPAN_PREFIX):
                        spans.append((e.name, line_no, e.start_ns,
                                      e.end_ns))
                line_no += 1
    if len(wins) != 1:
        raise RuntimeError(f"expected one {trace.WINDOW_SPAN} span, "
                           f"found {len(wins)}")
    line, lo, hi = wins[0]
    return ProgramTrace(ops, (lo, hi), line, spans)


def _innermost(spans: Sequence[Tuple[str, float, float]], lo: float,
               hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut where any of ``spans`` (name, start, end) opens or
    closes, each piece named by the innermost span open over it (the one
    that opened last), or ``NONE``."""
    cuts = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                              if lo < x < hi})
    todo = sorted(spans, key=lambda t: t[1])
    active: List[Tuple[str, float, float]] = []
    out, j = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(todo) and todo[j][1] <= a:
            active.append(todo[j])
            j += 1
        active = [t for t in active if t[2] > a]
        name = max(active, key=lambda t: (t[1], -t[2]))[0] if active \
            else NONE
        out.append((a, b, name))
    return out


class _Busy:
    """Busy time of one chip between two instants, from its ops."""

    def __init__(self, ops: Sequence[trace.Event], lo: float, hi: float):
        iv = trace.union([(max(s, lo), min(e, hi)) for _, s, e in ops
                          if e > lo and s < hi])
        self.starts = [s for s, _ in iv]
        self.iv = iv
        self.before = [0.0]
        for s, e in iv:
            self.before.append(self.before[-1] + e - s)

    def upto(self, x: float) -> float:
        k = bisect.bisect_right(self.starts, x) - 1
        if k < 0:
            return 0.0
        s, e = self.iv[k]
        return self.before[k] + min(x, e) - s

    def between(self, a: float, b: float) -> float:
        return self.upto(b) - self.upto(a)


@dataclasses.dataclass
class Split:
    window_s: float
    span_s: Dict[str, float]        # name -> seconds inside the window
    ended: Dict[str, List[float]]   # name -> seconds of each span that
    #                                 ends inside the window, whole
    idle_s: Dict[str, float]        # driving line: chip idle seconds by
    #                                 innermost span, mean over chips
    commits: List[Dict[str, float]]  # per ckpt.commit ending inside the
    #                                  window: seconds of its parts
    n_chips: int


def _commit_parts(tr: ProgramTrace, lo: float, hi: float
                  ) -> List[Dict[str, float]]:
    """For each ``ckpt.commit`` that ends inside the window: the seconds
    of its ``store.put`` phases summed over its nodes, the puts' own time
    (create, install, manifest) and the commit's own time (the rest)."""
    by_line: Dict[int, List[HostSpan]] = defaultdict(list)
    for sp in tr.spans:
        by_line[sp[1]].append(sp)
    for v in by_line.values():
        v.sort(key=lambda t: t[2])
    out = []
    for name, line, s, e in tr.spans:
        if name != COMMIT or not lo < e <= hi:
            continue
        row = by_line[line]
        k = bisect.bisect_left([t[2] for t in row], s)
        inner = [t for t in row[k:] if t[2] < e and t[3] <= e
                 and t[0] != COMMIT]
        part = {p: 0.0 for p in PUT_PHASES}
        puts = 0.0
        for n, _, a, b in inner:
            if n in part:
                part[n] += (b - a) * 1e-9
            elif n == PUT:
                puts += (b - a) * 1e-9
        part["store.put.self"] = puts - sum(part[p] for p in PUT_PHASES)
        part["ckpt.commit.self"] = (e - s) * 1e-9 - puts
        part[COMMIT] = (e - s) * 1e-9
        out.append(part)
    return out


def split(tr: ProgramTrace) -> Split:
    lo, hi = tr.window
    span_s: Dict[str, float] = defaultdict(float)
    ended: Dict[str, List[float]] = defaultdict(list)
    for n, _, s, e in tr.spans:
        span_s[n] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9
        if lo < e <= hi:
            ended[n].append((e - s) * 1e-9)
    pieces = _innermost([(n, max(s, lo), min(e, hi)) for n, line, s, e
                         in tr.spans if line == tr.driving_line
                         and e > lo and s < hi], lo, hi)
    chips = [p for p, ev in tr.ops.items() if ev]
    idle: Dict[str, float] = defaultdict(float)
    for plane in chips:
        busy = _Busy(tr.ops[plane], lo, hi)
        for a, b, name in pieces:
            idle[name] += ((b - a) - busy.between(a, b)) * 1e-9
    k = max(len(chips), 1)
    return Split(window_s=(hi - lo) * 1e-9,
                 span_s={n: v for n, v in span_s.items() if v > 0},
                 ended=dict(ended),
                 idle_s={n: v / k for n, v in idle.items()},
                 commits=_commit_parts(tr, lo, hi), n_chips=len(chips))


# ---- readings ---------------------------------------------------------------

def _mean_ms(values: Optional[List[float]]) -> Optional[float]:
    return statistics.fmean(values) * 1e3 if values else None


def ckpt_d2h_ms(sp: Split) -> Optional[float]:
    """Mean ``train.ckpt.d2h`` (the state's copy to the host) per
    checkpoint of the window."""
    return _mean_ms(sp.ended.get("train.ckpt.d2h"))


def ckpt_slot_wait_ms(sp: Split) -> Optional[float]:
    """Mean ``tiered.save.slot_wait`` (``save_async`` waiting for a
    checkpoint slot) per checkpoint of the window."""
    return _mean_ms(sp.ended.get("tiered.save.slot_wait"))


def ckpt_put_ms(sp: Split, phase: str) -> Optional[float]:
    """Per checkpoint committed in the window, the seconds of its nodes'
    ``store.put.<phase>`` spans inside ``ckpt.commit``, in ms."""
    return _mean_ms([c[f"store.put.{phase}"] for c in sp.commits])


def _decode_share(sp: Split, names: Sequence[str]) -> Optional[float]:
    decode_s = sp.span_s.get(DECODE_SPAN)
    if not decode_s or not sp.n_chips or not sp.ended.get(names[0]):
        return None
    return 100.0 * sum(sp.idle_s.get(n, 0.0) for n in names) / decode_s


def decode_dispatch_idle(sp: Split) -> Optional[float]:
    """Chip idle under ``engine.decode.step`` or ``.sample``, in percent
    of the seconds of the harness's decode spans (``decode_idle``'s
    base)."""
    return _decode_share(sp, DISPATCH)


def decode_sync_idle(sp: Split) -> Optional[float]:
    """Chip idle under ``engine.decode.sync``, on the same base."""
    return _decode_share(sp, (SYNC,))


def decode_idle_exact(sp: Split) -> Optional[float]:
    """``decode_idle`` with its gaps cut at the decode spans' edges: all
    chip idle inside them, on the same base. ``decode_idle`` charges a
    gap that crosses an edge whole to the span at its midpoint."""
    return _decode_share(sp, DISPATCH + (SYNC, DECODE_SPAN))


def decode_syncs_per_token(syncs: Optional[float], tokens: Optional[float]
                           ) -> Optional[float]:
    """Host syncs over decoded tokens (window deltas of the counters
    ``serve.decode.host_syncs`` and ``serve.decode.tokens``)."""
    return syncs / tokens if tokens else None


def readings(sp: Split, syncs: Optional[float] = None,
             tokens: Optional[float] = None) -> Dict[str, float]:
    """Every reading above that finds something to read."""
    out = {"ckpt_d2h_ms": ckpt_d2h_ms(sp),
           "ckpt_slot_wait_ms": ckpt_slot_wait_ms(sp),
           "ckpt_put_write_ms": ckpt_put_ms(sp, "write"),
           "ckpt_put_crc_ms": ckpt_put_ms(sp, "crc"),
           "ckpt_put_flush_ms": ckpt_put_ms(sp, "flush"),
           "decode_dispatch_idle": decode_dispatch_idle(sp),
           "decode_sync_idle": decode_sync_idle(sp),
           "decode_syncs_per_token": decode_syncs_per_token(syncs, tokens)}
    return {k: v for k, v in out.items() if v is not None}


def log_line(sp: Split) -> str:
    """One line: the driving line's idle by innermost span, and the mean
    own time of ``ckpt.commit``'s parts per commit of the window."""
    idle = {n: round(v, 6) for n, v in
            sorted(sp.idle_s.items(), key=lambda kv: -kv[1])}
    parts = ""
    if sp.commits:
        keys = PUT_PHASES + ("store.put.self", "ckpt.commit.self")
        parts = "; per commit (%d), ms: %s" % (len(sp.commits), ", ".join(
            f"{k} {statistics.fmean(c[k] for c in sp.commits) * 1e3:.1f}"
            for k in keys))
    return f"program spans: driving line idle s {json.dumps(idle)}{parts}"


def clip(tr: ProgramTrace, lo: float, hi: float) -> ProgramTrace:
    """The stretch [lo, hi) of ``tr`` as a trace of its own: its window
    cut to that stretch, the ops and spans that meet it kept whole."""
    ops = {p: [(n, s, e) for n, s, e in ev if e > lo and s < hi]
           for p, ev in tr.ops.items()}
    spans = [sp for sp in tr.spans if sp[3] > lo and sp[2] < hi]
    return ProgramTrace(ops, (lo, hi), tr.driving_line, spans)
