"""Share of the traced window in which no operation ran on the chip:
1 - the union of the device ops' intervals over the window, in percent."""
from __future__ import annotations

def read(run):
    tr = run["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
