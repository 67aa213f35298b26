"""Model FLOPs of the train steps done in the traced run's window
(forward and backward, no recomputation), over the window's seconds and
the chip's bf16 peak, in percent."""
from __future__ import annotations

from harness import flops


def read(run):
    if run["trace"] is None or not run["tokens_done"]:
        return None
    f = flops.train_flops_per_token(run["model"], int(run["traffic"]["seq"]))
    return 100.0 * f * run["tokens_done"] / run["window_s"] / \
        run["peak"]["bf16_flops"]
