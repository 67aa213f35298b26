"""The serve driver: sessions through ``SessionManager`` and one
``ServeEngine``, offered as an open loop from a serve traffic file.

Set-up makes the weights from the seed, compiles every prompt length the
schedule uses and one decode, drives one throwaway session through
start, suspend, resume and end, then opens the pool: each session is
started, prefilled and suspended to pmem. The window offers the turns at
their scheduled times. A new turn ends the least recently used session
and opens one (start, prefill, decode); a resumed turn resumes a
Zipf-chosen session from pmem (resume, then decode). Each turn ends in
``suspend(wait=False)``. The time to first token runs from a turn's
scheduled arrival; a turn still waiting when the window closes counts
with the time it waited.

After the window, a sample of the sessions served in it is replayed
through the plain reference beside the configuration: every served token
is a greedy choice, so its reference logit should be the reference's
best, up to rounding.
"""
from __future__ import annotations

import dataclasses
import shutil
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import common, draws, model, weights
from harness.common import Compared, log
from harness.refmath import QUANTS

PAD = 512


@dataclasses.dataclass
class Session:
    name: str
    prompt: np.ndarray                  # [P] int32
    served: List[int] = dataclasses.field(default_factory=list)
    fed: int = 0                        # positions the engine holds
    spill: Optional[object] = None      # future of the last suspend
    last_used: float = 0.0
    window_turns: int = 0

    def sequence(self) -> np.ndarray:
        return np.concatenate([self.prompt, np.asarray(self.served,
                                                       np.int32)])

    def settle(self) -> None:
        if self.spill is not None:
            self.spill.result()
            self.spill = None


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Server:
    """One engine and its sessions, driven turn by turn."""

    def __init__(self, cell, seed: int, eng, sm, tokens_rng):
        self.cell, self.eng, self.sm = cell, eng, sm
        self.g = tokens_rng
        tr, m = cell.traffic, cell.config["model"]
        self.vocab = m["vocab_size"]
        self.perm = draws.rng(seed, 2).permutation(self.vocab)
        self.max_ctx = int(tr["max_context"])
        self.weights = draws.zipf_weights(int(tr["sessions"]),
                                          float(tr["zipf_s"]))
        self.slots: List[Session] = []
        self.done: List[Session] = []
        self.count = 0
        self.ttft: List[float] = []
        self.decode_s = 0.0
        self.decode_tokens = 0

    def prompt(self, n: int) -> np.ndarray:
        return draws.zipf_tokens(self.g, (n,), self.vocab,
                                 float(self.cell.traffic["token_zipf_s"]),
                                 self.perm)

    def open(self, n_prompt: int, n_out: int, arrival: Optional[float],
             slot: Optional[int]) -> Session:
        s = Session(f"s{self.count}", self.prompt(n_prompt))
        self.count += 1
        with _span("bench.sessions.start"):
            self.sm.start(s.name, self.eng)
        with _span("bench.engine.prefill"):
            first = self.eng.prefill(s.prompt[None])
        self._first(s, first, arrival, prefill=n_prompt)
        self._rest(s, first, n_out - 1)
        if slot is None:
            self.slots.append(s)
        else:
            self.slots[slot] = s
        return s

    def resume(self, s: Session, n_out: int, arrival: float) -> None:
        s.settle()
        with _span("bench.sessions.resume"):
            self.sm.resume(s.name, self.eng)
        with _span("bench.engine.decode"):
            out = self.eng.decode(np.asarray([s.served[-1]], np.int32), 1)
        s.fed += 1
        self._first(s, out[:, -1], arrival)
        self._rest(s, out[:, -1], n_out - 1)

    def _first(self, s: Session, tok, arrival, prefill: int = 0) -> None:
        t = time.perf_counter()
        if arrival is not None:
            self.ttft.append(t - arrival)
            s.window_turns += 1
        s.fed = max(s.fed, prefill)
        s.served.append(int(tok[0]))

    def _rest(self, s: Session, tok, n: int) -> None:
        if n > 0:
            t = time.perf_counter()
            with _span("bench.engine.decode"):
                out = self.eng.decode(tok, n)
            self.decode_s += time.perf_counter() - t
            self.decode_tokens += n
            s.fed += n
            s.served.extend(int(x) for x in out[0, 1:])
        with _span("bench.sessions.suspend"):
            s.spill = self.sm.suspend(s.name, wait=False)
        s.last_used = time.perf_counter()

    def end(self, s: Session) -> None:
        s.settle()
        self.sm.end(s.name)
        self.done.append(s)

    def turn(self, t: draws.Turn, arrival: float) -> None:
        if not t.new:
            room = [i for i, s in enumerate(self.slots)
                    if s.fed + t.n_out <= self.max_ctx]
            if room:
                w = self.weights[room] / self.weights[room].sum()
                i = room[min(int(np.searchsorted(np.cumsum(w), t.pick)),
                             len(room) - 1)]
                self.resume(self.slots[i], t.n_out, arrival)
                return
        slot = min(range(len(self.slots)),
                   key=lambda i: self.slots[i].last_used)
        self.end(self.slots[slot])
        n_prompt = t.prompt_len or min(self.cell.traffic["prompt_buckets"])
        self.open(n_prompt, t.n_out, arrival, slot)

    def settle_all(self) -> None:
        for s in self.slots:
            s.settle()


def warm(eng, sm, lengths, vocab: int) -> None:
    """Compile every prompt length and the decode step, and take one
    throwaway session through the whole session path once."""
    tok = None
    for n in sorted(set(lengths)):
        tok = eng.prefill(np.zeros((1, n), np.int32) + (n % vocab))
    eng.decode(tok, 2)
    sm.start("warm", eng)
    tok = eng.prefill(np.ones((1, min(lengths)), np.int32))
    sm.suspend("warm", wait=False).result()
    sm.resume("warm", eng)
    eng.decode(tok, 1)
    sm.suspend("warm", wait=True)
    sm.end("warm")


def check(cell, seed: int, sessions: List[Session], shapes,
          quants=("exact",)) -> Dict[str, float]:
    """The widest gap, over the served tokens of a seeded sample of
    ``sessions``, between the reference's best logit and the logit of
    the served token. With ``fp8`` in ``quants`` it also reads the gap of
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
    out = {q: 0.0 for q in quants}
    close = distinct = 0
    for s in sample:
        seq = s.sequence()
        p = len(s.prompt)
        pos = np.arange(p - 1, len(seq) - 1)
        served = jnp.asarray(seq[p:])
        # padded at the end to a multiple of PAD rows, so that sequences
        # share compiled programs; causal, so the padding changes nothing
        ctx = np.zeros(-(-(len(seq) - 1) // PAD) * PAD, np.int32)
        ctx[:len(seq) - 1] = seq[:-1]
        exact = ref.logits(w, m, ctx)[pos]
        best = exact.max(axis=-1)
        top2 = jax.lax.top_k(exact, 2)[0]
        close += int(jnp.sum(top2[:, 0] - top2[:, 1] < 0.1))
        distinct += len(set(s.served))
        for q in quants:
            if q == "exact":
                tok = served
            else:
                tok = jnp.argmax(ref.logits(w, m, ctx, QUANTS[q])[pos],
                                 axis=-1)
            gap = best - jnp.take_along_axis(exact, tok[:, None], 1)[:, 0]
            out[q] = max(out[q], float(gap.max()))
        del exact
    log(f"check: {len(sample)} sessions, {n} served tokens "
        f"({distinct} distinct in their sessions), longest "
        f"{len(pool[0].served) if pool else 0}; {close} positions with a "
        f"reference margin under 0.1; gaps {out}")
    del w
    jax.clear_caches()
    return out


def run(cell, seed: int, seconds: float, trace_dir: Optional[str],
        clock, rate: Optional[float] = None,
        controls: Sequence[str] = ()) -> dict:
    """One run of a serve cell; returns what the metric readers read.
    ``rate`` overrides the traffic file's offered rate (for the sweep that
    sets it); ``controls`` (``fp8``) also reads the float8 control."""
    import jax

    from repro.core.cluster import SimCluster
    from repro.models import transformer as tfm
    from repro.serve.engine import ServeEngine

    cj, tr = cell.config, cell.traffic
    cfg = model.program_config(cj)
    rt = tfm.ModelRuntime(tp=1, ssd_impl=tr["ssd_impl"],
                          max_seq=int(tr["max_seq"]), remat=False)
    shapes, _ = tfm.abstract_params(cfg, rt)
    sched = draws.serve_schedule(tr, seed, seconds, rate)
    root = common.pmem_root()
    cluster = SimCluster(root, n_nodes=int(tr["nodes"]),
                         pmem_capacity=int(tr["pmem_bytes_per_node"]))
    try:
        eng = ServeEngine(cfg, rt, weights.make(seed, shapes,
                                                rules=cell.weight_rules()),
                          tiered=cluster.tiered)
        sm = cluster.sessions
        warm(eng, sm, sched.pool_prompts + [t.prompt_len for t in
                                            sched.turns if t.new],
             cfg.vocab_size)
        srv = Server(cell, seed, eng, sm, draws.rng(seed, 4))
        for n in sched.pool_prompts:
            s = srv.open(n, 1, None, None)
            s.settle()
        setup_s = clock.setup_done()

        if trace_dir:
            jax.profiler.start_trace(trace_dir, profiler_options=
                                     clock.profile_options())
        win = _span("bench.window")
        win.__enter__()
        compiles0 = clock.compiles()
        t0 = time.perf_counter()
        i = 0
        while i < len(sched.turns):
            t = sched.turns[i]
            due = t0 + t.arrival
            now = time.perf_counter()
            if t.arrival >= seconds or now >= t0 + seconds:
                break
            if now < due:
                time.sleep(due - now)
            srv.turn(t, due)
            i += 1
        t_end = time.perf_counter()
        win.__exit__(None, None, None)
        waiting = [t for t in sched.turns[i:] if t.arrival < seconds]
        srv.ttft.extend(seconds - t.arrival for t in waiting)
        srv.settle_all()
        if trace_dir:
            jax.profiler.stop_trace()
        compiles = clock.compiles() - compiles0
        peak = clock.memory_peak()
        served = srv.slots + srv.done
        eng.params = eng.cache = None
    finally:
        cluster.shutdown()
        shutil.rmtree(root, ignore_errors=True)
    lags = sorted(srv.ttft)
    log(f"window: {i} turns served, {len(waiting)} waiting at close, "
        f"{compiles} compiles, loop {t_end - t0:.3f} s, ttft p50 "
        f"{np.median(lags) * 1e3:.1f} ms p90 "
        f"{np.quantile(lags, 0.9) * 1e3:.1f} ms over {len(lags)} turns")
    gaps = check(cell, seed, served, shapes, ("exact",) + tuple(controls))
    limit = cj["limits"]["logit_gap"]
    compared = [Compared("logit_gap", gaps["exact"],
                         float("nan") if limit is None else limit)]
    return {"setup_s": setup_s, "seconds": seconds,
            "decode_s": srv.decode_s, "decode_tokens": srv.decode_tokens,
            "attempted": i + len(waiting), "failed": 0,
            "compared": compared, "memory_peak_bytes": peak,
            "compiles_in_window": compiles,
            "controls": {q: {"logit_gap": gaps[q]} for q in controls}}
