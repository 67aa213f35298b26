"""Mean pmem commit time of the checkpoints committed in the window: the
``ckpt.save_commit_s`` histogram's sum over its count, both as deltas."""
from __future__ import annotations

def read(run):
    total, count = run["commit_s"]
    return total / count * 1e3 if count else None
