"""Traffic generators, FLOP and byte counts, the trace reduction, and the
refusal to run without a chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from conftest import BENCH, MAMBA, STAR
from harness import draws, flops, trace

REPO = BENCH.parents[1]


# ---- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("mix", ["serve-code", "serve-chat"])
def test_serve_schedule_same_seed_same_turns(mix):
    tr = json.loads((BENCH / f"traffic/{mix}.json").read_text())
    a = draws.serve_schedule(tr, 2**31 + 5, 30.0)
    b = draws.serve_schedule(tr, 2**31 + 5, 30.0)
    assert a == b


@pytest.mark.parametrize("mix", ["serve-code", "serve-chat"])
def test_serve_schedule_other_seed_same_work_other_order(mix):
    tr = json.loads((BENCH / f"traffic/{mix}.json").read_text())
    a = draws.serve_schedule(tr, 3, 30.0)
    b = draws.serve_schedule(tr, 2**33 + 1, 30.0)
    assert a != b
    for field in ("n_out", "prompt_len", "new"):
        assert sorted(getattr(t, field) for t in a.turns) == \
            sorted(getattr(t, field) for t in b.turns)
    assert sorted(a.pool_prompts + [t.prompt_len for t in a.turns]) == \
        sorted(b.pool_prompts + [t.prompt_len for t in b.turns])
    gaps = [np.diff([0.0] + [t.arrival for t in s.turns]) for s in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    # every block of turns offers the same load in both
    blk = int(tr["block"])
    assert np.allclose(gaps[0].reshape(-1, blk).sum(1),
                       gaps[1].reshape(-1, blk).sum(1))
    share = sum(t.new for t in a.turns) / len(a.turns)
    assert share == pytest.approx(tr["new_share"])


def test_train_shards_follow_the_seed():
    from harness import train

    class Cell:
        traffic = {"rows_per_shard": 8, "seq": 16, "shards": 2,
                   "token_zipf_s": 1.1}
        config = {"model": MAMBA}
    a, b = train.shards(Cell, 9), train.shards(Cell, 9)
    c = train.shards(Cell, 2**32 + 9)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    assert a[0].shape == (8, 17) and a[0].max() < MAMBA["vocab_size"]


# ---- FLOPs and bytes -----------------------------------------------------

def test_ssd_flops_by_hand():
    # d_inner 128 -> 16 heads of 8; N 16; chunk 16; 32 rows = 2 chunks.
    # per chunk and head: C.B^T 16*16*16, (L*S)@xdt 16*16*8,
    # C@h 16*16*8, B^T@xdt 16*16*8 multiply-adds
    per = 16 * 16 * 16 + 3 * 16 * 16 * 8
    assert flops.ssd_chunk_flops(MAMBA, 32) == 2 * per * 2 * 16


def test_dense_prefill_flops_by_hand():
    s, d, h, kv, dh, f = 10, 64, 4, 2, 16, 128
    matmuls = [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d),
               (d, f), (f, d)]
    per_tok = sum(2 * a * b for a, b in matmuls)
    attn = sum(4 * h * dh * (i + 1) for i in range(s))   # q.k and p.v
    head = 2 * d * STAR["vocab_size"]
    want = 2 * (per_tok * s + attn) + head
    assert flops.prefill_flops(STAR, s) == pytest.approx(want)


def test_train_flops_are_three_forward_passes():
    seq = 32
    fwd_tok = (flops.prefill_flops(MAMBA, seq) -
               2 * MAMBA["d_model"] * MAMBA["vocab_size"]) / seq + \
        2 * MAMBA["d_model"] * MAMBA["vocab_size"]
    assert flops.train_flops_per_token(MAMBA, seq) == \
        pytest.approx(3 * fwd_tok)


# ---- trace reduction ------------------------------------------------------

def _timeline(tr, lo, hi):
    """Brute force: busy microseconds of each chip on a 1 us grid."""
    n = int((hi - lo) / 1e3) + 1
    out = {}
    for plane, ev in tr.ops.items():
        grid = np.zeros(n, bool)
        for _, s, e in ev:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                grid[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
        out[plane] = grid
    return out


def test_summary_of_a_made_trace():
    ms = 1e6
    tr = trace.Trace(
        ops={"/device:TPU:0": [("fusion", 1 * ms, 3 * ms),
                               ("fusion", 2 * ms, 4 * ms),
                               ("%ssd.3 (kernel)", 6 * ms, 7 * ms)],
             "/device:TPU:1": [("fusion", 0 * ms, 10 * ms)]},
        spans=[("bench.window", 0, 10 * ms),
               ("bench.train_step", 0.5 * ms, 5 * ms)])
    s = trace.summarize(tr)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx((0.004 + 0.010) / 2)
    assert s.op_s["%ssd.3 (kernel)"] == pytest.approx(0.001 / 2)
    assert trace.op_name('%ssd.3 = (bf16[1]) custom-call(f32[1] %a), '
                         'custom_call_target="tpu_custom_call"') == \
        "%ssd.3 (kernel)"
    assert trace.op_name("%while.6 = (s32[]) while(%t)") == "%while.6"
    # chip 0 idles 0-1 (midpoint in the step span), 4-6 (midpoint 5, at
    # the step span's end) and 7-10; chip 1 never; means over the chips
    assert s.idle_by_span["bench.train_step"] == pytest.approx(0.001 / 2)
    assert s.idle_by_span["bench.window"] == pytest.approx(0.005 / 2)
    assert s.span_s == {"bench.train_step": pytest.approx(0.0045)}
    assert s.breakdown()["device_ops"][0] == ["fusion", pytest.approx(0.007)]


RECORDED = Path(__file__).parent / "data" / "trace_code.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_summary_of_the_recorded_chip_trace():
    tr = trace.Trace.from_json(json.loads(RECORDED.read_text()))
    s = trace.summarize(tr)
    lo, hi = next((a, b) for n, a, b in tr.spans if n == trace.WINDOW_SPAN)
    grids = _timeline(tr, lo, hi)
    brute = np.mean([g.sum() for g in grids.values() if g.any()]) * 1e-6
    assert s.n_chips == 1
    assert s.busy_s == pytest.approx(brute, abs=2e-6 * sum(
        len(v) for v in tr.ops.values()) + 1e-5)
    assert 0 < s.busy_s < s.window_s
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert sum(s.op_s.values()) >= s.busy_s * (1 - 1e-9)
    names = [n for n, _ in s.breakdown()["device_ops"]]
    assert len(names) == 10


# ---- no chip, no result ---------------------------------------------------

def test_cpu_run_exits_nonzero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "mamba2-train-ckpt", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert "correct" not in p.stdout
