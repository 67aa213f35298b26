"""Tokens of the train steps in the window, over the window's seconds.
The window holds whole checkpoint periods of the loop in its steady state
(each ``ckpt_every`` steps and one checkpoint submit, slot backpressure
included), so this is the rate a long run keeps."""
from __future__ import annotations

def read(run):
    return run["tokens_done"] / run["window_s"]
