"""Process start to the window's first timed operation: start-up,
weights, compilation or cache loads, warm-up, and the cell's own set-up
(checked steps, the session pool)."""
from __future__ import annotations

def read(run):
    return run["setup_s"]
