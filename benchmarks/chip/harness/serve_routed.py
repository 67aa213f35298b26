"""The serve driver for models that route each token to a few of many
experts: the sessions, traffic, window and metrics of ``harness/serve.py``,
run by it, with a comparison that a near tie in the router cannot swing.

A router keeps the top k of its experts' scores. Where two scores nearly
tie, the program in bfloat16 and the reference in float32 can keep
different experts, and the expert that the one adds and the other leaves
out moves that token's logits about as far as the float8 control moves
its worst token. So the widest gap over a thousand served tokens, which
``serve.py`` compares, reads the luck of the near ties more than the
precision. Such a flip moves few tokens; the float8 control and a
faulty program (an expert dropped, one pick too few, the routed scale
left out, a cache or state lost on resume) move many. The number compared
is therefore ``logit_gap_mean``: over the served tokens of the same
seeded sample of sessions, the mean gap between the reference's best
logit and the served token's, against the file's
``limits.logit_gap_mean``. A served token that is the reference's best
adds 0.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import draws, serve, weights
from harness.common import log
from harness.refmath import QUANTS

NAME = "logit_gap_mean"


def check(cell, seed: int, sessions: List[serve.Session], shapes,
          quants=("exact",)) -> Dict[str, float]:
    """The mean gap, over the served tokens of the sample of sessions that
    ``serve.check`` takes, between the reference's best logit and the
    logit of the served token; with ``fp8`` in ``quants`` also that of
    the tokens the float8 control puts first at the same positions."""
    import jax
    import jax.numpy as jnp
    tr, m = cell.traffic, cell.config["model"]
    ref = cell.reference()
    pool = sorted((s for s in sessions if s.window_turns),
                  key=lambda s: -len(s.served))
    g = draws.rng(seed, 3)
    pick = pool[:1] + [pool[i] for i in
                       1 + g.permutation(max(len(pool) - 1, 0))]
    sample, n = [], 0
    for s in pick:
        if n >= tr["check_tokens"] or len(sample) >= tr["check_sessions"]:
            break
        sample.append(s)
        n += len(s.served)
    w = weights.make(seed, shapes, rules=cell.weight_rules())
    total = {q: 0.0 for q in quants}
    widest = {q: 0.0 for q in quants}
    for s in sample:
        seq = s.sequence()
        p = len(s.prompt)
        pos = np.arange(p - 1, len(seq) - 1)
        ctx = np.zeros(-(-(len(seq) - 1) // serve.PAD) * serve.PAD,
                       np.int32)
        ctx[:len(seq) - 1] = seq[:-1]
        exact = ref.logits(w, m, ctx)[pos]
        best = exact.max(axis=-1)
        for q in quants:
            if q == "exact":
                tok = jnp.asarray(seq[p:])
            else:
                tok = jnp.argmax(ref.logits(w, m, ctx, QUANTS[q])[pos],
                                 axis=-1)
            gap = best - jnp.take_along_axis(exact, tok[:, None], 1)[:, 0]
            total[q] += float(gap.sum())
            widest[q] = max(widest[q], float(gap.max()))
        del exact
    out = {q: total[q] / max(n, 1) for q in quants}
    log(f"check: {len(sample)} sessions, {n} served tokens; mean gaps "
        f"{out}, widest {widest}")
    del w
    jax.clear_caches()
    return out


def run(cell, seed: int, seconds: float, trace_dir: Optional[str],
        clock, rate: Optional[float] = None,
        controls: Sequence[str] = ()) -> dict:
    """``serve.run`` with ``check`` above in place of its own; the record
    names the number compared ``logit_gap_mean``."""
    limits = {"logit_gap": cell.config["limits"][NAME]}
    inner = dataclasses.replace(cell, config={**cell.config,
                                              "limits": limits})
    own, serve.check = serve.check, check
    try:
        rec = serve.run(inner, seed, seconds, trace_dir, clock, rate,
                        controls)
    finally:
        serve.check = own
    rec["compared"] = [dataclasses.replace(c, name=NAME)
                       for c in rec["compared"]]
    rec["controls"] = {q: {NAME: v["logit_gap"]}
                       for q, v in rec["controls"].items()}
    return rec
