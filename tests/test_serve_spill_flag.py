"""The serve tier's in-flight-spill flag: an async suspend whose publish
ends before the submit returns must not leave the session marked as
spilling, or its next suspend or end is refused."""
from __future__ import annotations

import time

import numpy as np


class StateEngine:
    """The export/install half of a ServeEngine, holding a small state."""

    label = "e0"

    def __init__(self):
        self.state = {"cache": {"h": np.ones((2, 64), np.float32)},
                      "pos": np.int32(3)}

    def export_state(self, release=False):
        out, self.state = self.state, (None if release else self.state)
        return out

    def install_state(self, obj):
        self.state = {"cache": {k: np.asarray(v)
                                for k, v in obj["cache"].items()},
                      "pos": np.int32(obj["pos"])}


def test_suspend_again_after_a_publish_that_beat_its_submit(cluster,
                                                           monkeypatch):
    """The caller is held 50 ms after each submit, as a contended TieredIO
    lock would hold it; the publish of a small state is done by then. Each
    later suspend (and the end) must still be taken."""
    tiered = cluster.tiered
    submit = tiered.run_async

    def slow_return(fn):
        fut = submit(fn)
        time.sleep(0.05)
        return fut
    monkeypatch.setattr(tiered, "run_async", slow_return)
    sm, eng = cluster.sessions, StateEngine()
    sm.start("s", eng)
    for _ in range(3):
        sm.suspend("s", wait=False).result()
        sm.resume("s", eng)
    sm.suspend("s", wait=False).result()
    sm.end("s")
