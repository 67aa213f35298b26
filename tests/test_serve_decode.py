"""The serve engine's greedy decode chain stays on the device.

``ServeEngine.decode`` dispatches one jitted step per token (argmax and
position inside it, cache donated) and reads its tokens on the host once
per call. These tests hold it to a plain per-token loop for each kind of
model, to one compile whatever the call's ``steps``, and check that no
host copy of a session's state loses its bytes when the next step
donates the device cache."""
import functools
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as T
from repro.serve.engine import ServeEngine

PROMPT = np.arange(1, 9, dtype=np.int32)[None]
CALLS = (1, 2, 7)  # steps of the successive decode calls


def _model(arch: str):
    cfg = registry.get_smoke_config(arch)
    rt = T.ModelRuntime(tp=1, max_seq=32, remat=False)
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg, rt)
    return cfg, rt, params


def _per_token(cfg, rt, params, n: int):
    """Prefill, then ``n`` greedy tokens one at a time, each read on the
    host before the next step: (tokens [1, 1 + n], picks held here)."""
    logits, cache = jax.jit(functools.partial(T.prefill, cfg=cfg, rt=rt))(
        params, tokens=jnp.asarray(PROMPT))
    step = jax.jit(lambda p, c, t, i: T.decode_step(p, cfg, rt, c, t, i))
    toks = [np.asarray(jnp.argmax(logits, axis=-1), np.int32)]
    held = 0
    for i in range(n):
        logits, cache, h = step(params, cache, jnp.asarray(toks[-1]),
                                jnp.int32(PROMPT.shape[1] + i))
        toks.append(np.asarray(jnp.argmax(logits, axis=-1), np.int32))
        held += int(h)
    return np.stack(toks, axis=1), held


@pytest.mark.parametrize("arch", [
    pytest.param("starcoder2-15b", id="dense-gqa"),
    pytest.param("mamba2-1.3b", id="ssd"),
    pytest.param("nemotron3-nano-30b-a3b", id="hybrid-moe"),
])
def test_decode_chain_matches_a_per_token_loop(cluster, arch):
    cfg, rt, params = _model(arch)
    want, want_held = _per_token(cfg, rt, params, sum(CALLS))
    eng = ServeEngine(cfg, rt, params, tiered=cluster.tiered)
    reg = cluster.tiered.obs.registry
    got = [eng.prefill(PROMPT)[:, None]]
    for steps in CALLS:
        s0 = reg.counter("serve.decode.host_syncs").value
        out = eng.decode(got[-1][:, -1], steps)
        assert out.shape == (1, 1 + steps) and out.dtype == np.int32
        assert reg.counter("serve.decode.host_syncs").value - s0 == 1
        got.append(out[:, 1:])
    np.testing.assert_array_equal(np.concatenate(got, axis=1), want)
    assert eng.pos == PROMPT.shape[1] + sum(CALLS)
    assert reg.counter("serve.moe.picks_held").value == want_held
    assert reg.counter("serve.decode.tokens").value == sum(CALLS)
    if cfg.moe is not None:
        assert want_held > 0
    assert eng._decode._cache_size() == 1


class _FailingTiered:
    """A pmem tier whose offloads never become durable."""
    obs = None

    def offload(self, name, obj, replicate=True):
        fut = Future()
        fut.set_exception(IOError("pmem died mid-offload"))
        return fut


def _snapshot(state: dict) -> dict:
    return jax.tree.map(np.array, state)


def _same(state: dict, want: dict) -> None:
    jax.tree.map(np.testing.assert_array_equal, state, want)


@pytest.mark.parametrize("source", ["export", "failed_spill", "install"])
def test_donated_decode_leaves_host_states_intact(source):
    """A host copy of the state keeps its bytes after the next decode
    donates the device cache: the copy a prefix publish exports and the
    engine goes on decoding; the copy a failed spill parks while another
    session decodes, and after it is put back; a state installed and
    then decoded."""
    cfg, rt, params = _model("starcoder2-15b")
    eng = ServeEngine(cfg, rt, params, tiered=_FailingTiered())
    first = eng.prefill(PROMPT)
    tok = eng.decode(first, 2)[:, -1]
    if source == "export":
        host = eng.export_state(release=False)
        want = _snapshot(host)
        eng.decode(tok, 3)
    elif source == "failed_spill":
        with pytest.raises(RuntimeError, match="doomed"):
            eng.spill("doomed", wait=False).result(timeout=30)
        host = eng.failed_spills["doomed"]
        want = _snapshot(host)
        eng.decode(eng.prefill(PROMPT[:, ::-1].copy()), 3)
        _same(host, want)
        eng.restore_failed_spill("doomed")
        eng.decode(tok, 3)
    else:
        host = eng.export_state(release=True)
        want = _snapshot(host)
        eng.install_state(host)
        eng.decode(tok, 3)
    _same(host, want)
    # the engine's own state moved on from the copy
    assert eng.pos == int(want["pos"]) + 3
    assert eng._decode._cache_size() == 1
