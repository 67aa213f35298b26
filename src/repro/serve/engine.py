"""Per-request serving engine: prefill + batched decode (SLM mode).

The engine drives models/transformer's prefill/decode with jitted steps
and owns exactly ONE session's DRAM state (``cache``/``pos``) at a time.
Session *lifetime* — who may spill, resume, share, evict or reclaim that
state — is the SessionManager's job (``serve/sessions.py``): a fleet of
these engines checks sessions in and out of the manager, which registers
every spill as a leased, versioned Dataset in the exchange catalog.

The legacy direct spill paths on this class survive for single-engine
use and tests:
  * legacy direct-store (``store=``): synchronous object-store put/get;
  * TieredIO (``tiered=``): spill goes through the DLM write-back cache
    on the engine's I/O thread (nonblocking), and ``prefetch_sessions``
    warms cold session/KV state from pmem into DRAM *before* the next
    request needs it — the scheduler-driven cold-page prefetch of the
    paper's Fig. 8.
New serving code should go through the SessionManager instead: it rides
the catalog's leases, acks and repair instead of bare ``serve/<name>``
keys.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.object_store import PMemObjectStore
from repro.core.tiered_io import TieredIO
from repro.models import transformer as tfm
from repro.obs.trace import annotate


class SpillTicket:
    """Future-like handle for a nonblocking ``ServeEngine.spill``.

    The ticket OWNS the host copy of the session state until the pmem
    write is durable: a failed offload parks the copy in
    ``engine.failed_spills[name]`` (instead of silently losing the
    session with ``engine.cache`` already freed) and ``result()`` raises
    a ``RuntimeError`` naming the session, chained on the real cause.
    ``restore_failed_spill`` re-installs the parked copy."""

    def __init__(self, name: str, state: dict, future,
                 engine: "ServeEngine"):
        self.name = name
        self._state = state
        self._future = future
        self._engine = engine
        future.add_done_callback(self._on_done)

    def _on_done(self, fut) -> None:
        if fut.exception() is not None:
            # the spill never became durable: the host copy goes back
            # to the engine so the session is not lost
            self._engine.failed_spills[self.name] = self._state
        self._state = None  # durable (or parked): ticket drops its ref

    def done(self) -> bool:
        return self._future.done()

    def exception(self, timeout: Optional[float] = None):
        return self._future.exception(timeout)

    def result(self, timeout: Optional[float] = None):
        try:
            return self._future.result(timeout)
        except Exception as e:  # noqa: BLE001 — re-raised with context
            raise RuntimeError(
                f"spill of session {self.name!r} never became durable; "
                f"host copy retained in "
                f"ServeEngine.failed_spills[{self.name!r}]") from e


class ServeEngine:
    def __init__(self, cfg: ModelConfig, rt: tfm.ModelRuntime, params,
                 store: Optional[PMemObjectStore] = None,
                 tiered: Optional[TieredIO] = None,
                 label: str = "engine0"):
        self.cfg = cfg
        self.rt = rt
        self.params = params
        self.store = store
        self.tiered = tiered
        self.label = label  # producer id stamped into session lineage
        self.cache = None
        self.pos = 0
        # host copies of spills that failed after ``cache`` was freed
        # (see SpillTicket): {session name: state dict}
        self.failed_spills: Dict[str, dict] = {}
        # router picks per decoded token (all expert layers, one row;
        # ``cfg`` is None for a test's stand-in step)
        moe = getattr(cfg, "moe", None)
        self._picks = moe.top_k * cfg.n_moe_layers if moe else 0

        def step(p, c, t, pos, held):
            logits, c, n = tfm.decode_step(p, cfg, rt, c, t, pos)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), c,
                    pos + 1, held + n)
        # (params, cache, tokens, pos, running count of the picks held
        # here) -> (greedy next tokens, cache, pos + 1, that count with
        # this step's added). The cache, pos and count are donated, so
        # steps queued ahead of the chip reuse one cache's buffers rather
        # than each allocating its own.
        self._decode = jax.jit(step, donate_argnums=(1, 3, 4))
        self._prefill = jax.jit(
            functools.partial(tfm.prefill, cfg=cfg, rt=rt))

    # ---- lifecycle ----
    def prefill(self, tokens: np.ndarray, **frontend) -> np.ndarray:
        # cfg/rt are baked into the jitted partial; tokens must go by
        # keyword (positionally it would collide with the bound cfg)
        logits, cache = self._prefill(self.params,
                                      tokens=jnp.asarray(tokens),
                                      **frontend)
        self.cache = cache
        self.pos = tokens.shape[1] + self.cfg.prefix_len
        return np.asarray(jnp.argmax(logits, axis=-1), np.int32)

    def decode(self, first_tokens: np.ndarray, steps: int) -> np.ndarray:
        """``steps`` greedy tokens after ``first_tokens``, as ``[B, 1 +
        steps]``. The greedy chain stays on the device: each jitted step
        takes its own argmax and carries the position, so the loop only
        dispatches, and the host reads the call's tokens once, at its end.
        Each token is one profiler span on the caller's thread,
        ``engine.decode.step`` (the step's dispatch); the call's one wait
        for its tokens is ``.sync``. The call adds its tokens and its one
        sync to the counters ``serve.decode.tokens`` and
        ``serve.decode.host_syncs``. A model with expert layers also adds
        the router's top-k picks to ``serve.moe.picks``, and those that
        land on the experts held here to ``serve.moe.picks_held``: the
        jitted step carries that count, which comes to the host with the
        tokens."""
        first = np.asarray(first_tokens, np.int32)
        toks = [jnp.asarray(first)]
        pos = jnp.asarray(self.pos, jnp.int32)
        held = jnp.zeros((), jnp.int32)
        for _ in range(steps):
            with annotate("engine.decode.step"):
                tok, self.cache, pos, held = self._decode(
                    self.params, self.cache, toks[-1], pos, held)
            toks.append(tok)
        self.pos += steps
        with annotate("engine.decode.sync"):
            out, held = jax.device_get((toks[1:], held))
        obs = self._obs()
        if obs is not None:
            obs.counter("serve.decode.tokens").inc(steps)
            obs.counter("serve.decode.host_syncs").inc(1)
            if self._picks:
                obs.counter("serve.moe.picks").inc(
                    steps * self._picks * len(first))
                obs.counter("serve.moe.picks_held").inc(int(held))
        return np.stack([first, *out], axis=1)

    # ---- session-state handoff (the SessionManager's interface) ----
    def export_state(self, release: bool = False) -> dict:
        """Host copy of the session state (``{"cache", "pos"}``) —
        what the manager publishes as a dataset version. ``release``
        frees the engine's DRAM copy after the export. The next decode
        donates the device cache; the host copy keeps its bytes (on a
        CPU backend it is a view, and JAX then declines the donation)."""
        assert self.cache is not None, "no session state resident"
        host = jax.tree.map(np.asarray, self.cache)
        obj = {"cache": host, "pos": np.int32(self.pos)}
        if release:
            self.cache = None
        return obj

    def install_state(self, obj: dict) -> None:
        """Adopt a session state tree (from a resume, a shared prefix
        dataset, or a parked failed spill)."""
        self.cache = jax.tree.map(jnp.asarray, obj["cache"])
        self.pos = int(obj["pos"])

    def restore_failed_spill(self, name: str) -> None:
        """Re-install the host copy a failed nonblocking spill parked
        (see SpillTicket) — the in-process recovery for a spill whose
        pmem write died under it."""
        self.install_state(self.failed_spills.pop(name))

    # ---- pmem spill (SLM): persist serving state, restore later ----
    def spill(self, name: str, wait: bool = True, replicate: bool = True):
        """Persist the session's KV/cursor to pmem and free DRAM. With a
        TieredIO engine attached the write happens off-thread; pass
        ``wait=False`` to get a ``SpillTicket`` instead of blocking —
        the ticket owns the host copy until the write is durable, so a
        failed offload parks it in ``failed_spills`` rather than losing
        the session. With ``replicate`` (default) the spilled state also
        gets a buddy-node replica over the fabric, so ``resume``/
        ``prefetch_sessions`` keep working when the home node's pool
        dies (the TieredIO DLM cache transparently falls back to
        ``replica/<nid>/...``)."""
        assert self.tiered is not None or self.store is not None, \
            "no pmem backend attached"  # check BEFORE dropping the KV
        obj = self.export_state(release=True)
        obs = self._obs()
        if obs is not None:
            obs.counter("serve.spills").inc()
            obs.event("serve.spill", session=name, replicate=replicate)
        if self.tiered is not None:
            fut = self.tiered.offload(f"serve/{name}", obj,
                                      replicate=replicate)
            if wait:
                fut.result()
                return None
            return SpillTicket(name, obj, fut, self)
        self.store.put(f"serve/{name}", obj)
        return None

    def _obs(self):
        """The TieredIO engine's telemetry plane, when one is wired."""
        return getattr(self.tiered, "obs", None) \
            if self.tiered is not None else None

    def resume(self, name: str) -> None:
        obs = self._obs()
        sp = obs.begin("serve.resume", local=True, session=name) \
            if obs is not None else None
        if self.tiered is not None:
            obj = self.tiered.fetch(f"serve/{name}")
        else:
            assert self.store is not None
            obj = self.store.get(f"serve/{name}")
        self.install_state(obj)
        if obs is not None:
            obs.counter("serve.resumes").inc()
            obs.end(sp)

    def peek_session(self, name: str, leaf: str) -> np.ndarray:
        """Byte-range peek at ONE leaf of a spilled session — a single
        layer's KV page, or the ``pos`` cursor — without rehydrating
        the rest of the cache. The read covers exactly that leaf's
        bytes on pmem (home pool first, then acked replicas when the
        home died), decoding only its own tiles when the spill
        travelled wire-encoded; nothing is admitted into the DLM cache
        and ``self.cache`` is untouched. This is how a scheduler can
        inspect a cold session (how far did it decode? how big is its
        KV?) at O(leaf) cost instead of O(session)."""
        assert self.tiered is not None, "peek needs a TieredIO engine"
        return self.tiered.fetch_leaf(f"serve/{name}", leaf)

    def prefetch_sessions(self, names: List[str]):
        """Warm cold session state pmem -> DRAM ahead of resume (Fig. 8
        prefetch). Returns the TieredIO future (hit/load counts)."""
        assert self.tiered is not None, "prefetch needs a TieredIO engine"
        return self.tiered.prefetch([f"serve/{n}" for n in names])

    def evict_cold_sessions(self, max_idle_s: float = 0.0) -> int:
        """Spill idle cached sessions back to pmem (DRAM pressure valve).
        The SessionManager's lease-release eviction supersedes this for
        catalog-registered sessions."""
        assert self.tiered is not None, "eviction needs a TieredIO engine"
        n = self.tiered.evict_cold(max_idle_s)
        obs = self._obs()
        if obs is not None:
            obs.counter("serve.evictions").inc(n)
        return n

    def repair(self, lost_nodes) -> dict:
        """Restore the replication factor of spilled session/KV state
        after a node loss: every ``dlm/serve/...`` object whose acked
        copies the loss reduced to a single survivor regains a buddy
        (TieredIO.repair walks dlm/acks.json — no probing). Call from
        the serving control plane when the cluster monitor reports a
        dead node; sessions spilled before the loss then survive the
        NEXT one too. When the continuous RepairDaemon is running, its
        sweep is joined (bounded wait) and its ledger report returned —
        an inline scan concurrent with a mid-sweep daemon would double
        every repair transfer, exactly the storm the daemon's rate
        limit exists to prevent."""
        assert self.tiered is not None, "repair needs a TieredIO engine"
        daemon = getattr(self.tiered, "repair_daemon", None)
        if daemon is not None and daemon.running:
            daemon.wait_for(lost_nodes, timeout=60.0)
        if daemon is not None and daemon.covers(lost_nodes):
            return daemon.report()
        return self.tiered.repair(lost_nodes)
