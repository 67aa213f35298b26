"""Mean host time a window step paid for a checkpoint: the device-to-host
copy and the ``save_async`` submit (``LoopState.ckpt_seconds``)."""
from __future__ import annotations

def read(run):
    s = run["ckpt_s"]
    return sum(s) / len(s) * 1e3 if s else None
