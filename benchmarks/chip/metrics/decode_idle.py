"""Share of the time inside the harness's decode spans (the
``ServeEngine.decode`` calls of the window) in which no operation ran on
the chip, in percent: the host's part of each decoded token, its sync
and dispatch (ROADMAP A2)."""
from __future__ import annotations

SPAN = "bench.engine.decode"


def read(run):
    tr = run["trace"]
    if tr is None or not tr.span_s.get(SPAN):
        return None
    return 100.0 * tr.idle_by_span.get(SPAN, 0.0) / tr.span_s[SPAN]
