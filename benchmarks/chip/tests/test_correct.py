"""The comparison that decides ``correct``: sound runs pass it; each
fault a cell can have, planted under the timed path, and each control in
the program's place, fail it. Tiny sizes on the CPU; the limits are the
tiny benchmark's (conftest.LIMITS)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import LIMITS


@pytest.mark.parametrize("workload", ["t-train", "t-code", "t-chat",
                                      "t-hybrid"])
def test_sound_run_is_correct(drive, workload):
    out = drive(workload)
    assert out["correct"], [(c.name, c.value, c.limit)
                            for c in out["compared"]]
    assert out["attempted"] > 0


@pytest.mark.parametrize("workload", ["t-train", "t-chat"])
def test_traced_run_reads_its_window(drive, workload):
    out = drive(workload, trace=True)
    assert out["correct"]
    assert out["device"]["window_s"] > 0


def _wrap_step(monkeypatch, make):
    from harness import train
    orig = train.setup

    def setup(cell, seed, root):
        *rest, step = orig(cell, seed, root)
        return (*rest, make(step))
    monkeypatch.setattr(train, "setup", setup)


def _state_unchanged(step):
    def broken(params, opt_state, batch):
        keep = jax.tree.map(jnp.copy, params)
        _, opt_state, metrics = step(params, opt_state, batch)
        return keep, opt_state, metrics
    return broken


def _half_batch(step):
    def broken(params, opt_state, batch):
        batch = dict(batch)
        mask = np.array(batch["loss_mask"])
        mask[mask.shape[0] // 2:] = 0.0
        batch["loss_mask"] = mask
        return step(params, opt_state, batch)
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_train_fault_is_caught(drive, monkeypatch, fault):
    _wrap_step(monkeypatch, fault)
    out = drive("t-train")
    assert not out["correct"]


def _replica_skipped(monkeypatch):
    from repro.core.tiered_io import ReplicationChannel
    monkeypatch.setattr(ReplicationChannel, "submit",
                        lambda self, manifest, **kw: None)


def _write_truncated(monkeypatch):
    from repro.core.checkpoint import DistributedCheckpointer
    orig = DistributedCheckpointer.save

    def cut(x):
        x = np.array(x)
        x.reshape(-1)[x.size // 2:] = 0
        return x

    def save(self, step, tree, **kw):
        return orig(self, step, jax.tree.map(cut, tree), **kw)
    monkeypatch.setattr(DistributedCheckpointer, "save", save)


@pytest.mark.parametrize("fault", [_replica_skipped, _write_truncated])
def test_checkpoint_fault_is_caught(drive, monkeypatch, fault):
    fault(monkeypatch)
    out = drive("t-train")
    assert not out["correct"]
    bad = {c.name for c in out["compared"] if not c.ok}
    assert bad & {"ckpt_unreplicated", "ckpt_leaves_wrong"}, bad


def _token_altered(monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine.decode

    def decode(self, first, steps):
        out = np.array(orig(self, first, steps))
        out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(ServeEngine, "decode", decode)


def _state_lost(monkeypatch):
    from repro.serve.sessions import SessionManager
    orig = SessionManager.resume

    def resume(self, name, engine):
        orig(self, name, engine)
        engine.cache = jax.tree.map(jnp.zeros_like, engine.cache)
    monkeypatch.setattr(SessionManager, "resume", resume)


@pytest.mark.parametrize("fault", [_token_altered, _state_lost])
@pytest.mark.parametrize("workload", ["t-code", "t-chat", "t-hybrid"])
def test_serve_fault_is_caught(drive, monkeypatch, workload, fault):
    fault(monkeypatch)
    out = drive(workload)
    assert not out["correct"]


def _wrap_moe(monkeypatch, wrap):
    from repro.models import moe
    monkeypatch.setattr(moe, "apply_moe_gshard", wrap(moe.apply_moe_gshard))


def _expert_dropped(monkeypatch):
    def wrap(moe_fn):
        def dropped(p, x, cfg):
            return moe_fn(dict(p, wo=p["wo"].at[:, 0].set(0)), x, cfg)
        return dropped
    _wrap_moe(monkeypatch, wrap)


def _top_k_less(monkeypatch):
    def wrap(moe_fn):
        def fewer(p, x, cfg):
            moe = dataclasses.replace(cfg.moe, top_k=cfg.moe.top_k - 1)
            return moe_fn(p, x, dataclasses.replace(cfg, moe=moe))
        return fewer
    _wrap_moe(monkeypatch, wrap)


def _attention_cache_lost(monkeypatch):
    from repro.serve.sessions import SessionManager
    orig = SessionManager.resume

    def resume(self, name, engine):
        orig(self, name, engine)
        engine.cache = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a)
            if path[-1].key in ("k", "v") else a, engine.cache)
    monkeypatch.setattr(SessionManager, "resume", resume)


@pytest.mark.parametrize("fault", [_expert_dropped, _top_k_less,
                                   _attention_cache_lost])
def test_hybrid_expert_fault_is_caught(drive, monkeypatch, fault):
    fault(monkeypatch)
    out = drive("t-hybrid")
    assert not out["correct"], [(c.name, c.value, c.limit)
                                for c in out["compared"]]


def _cell(tiny, name):
    from harness import common
    return common.load_cell(name, False, root=tiny, bench=tiny)


def test_train_controls_fail(tiny, drive):
    import run as bench
    from harness import train
    devices = jax.devices()
    rec = train.run(_cell(tiny, "t-train"), 7, 1.0, None,
                    bench.Clock(devices), controls=("fp8", "half"))
    for name, read in rec["controls"].items():
        assert any(v > LIMITS[k] for k, v in read.items()), (name, read)


@pytest.mark.parametrize("workload", ["t-code", "t-chat", "t-hybrid"])
def test_serve_control_fails(tiny, drive, workload):
    import run as bench
    from harness import serve
    devices = jax.devices()
    rec = serve.run(_cell(tiny, workload), 7, 2.0, None,
                    bench.Clock(devices), controls=("fp8",))
    assert rec["controls"]["fp8"]["logit_gap"] > LIMITS["logit_gap"]
