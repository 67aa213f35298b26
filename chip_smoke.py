#!/usr/bin/env python3
"""Smoke check of the two main paths on a TPU, at mamba2-1.3b's widths.

    python chip_smoke.py             # one chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: the sharded train path

Train phase: ``train.loop.run`` on 4 of the 48 layers (every width
kept), default AdamW, batch 4 x 2048 tokens, a checkpoint every 3 steps
through ``TieredIO.save_async`` to a 3-node pmem cluster, a node killed
after step 4, ``restore_latest_recoverable`` and the repair.

Serve phase: all 48 layers with the SSD Pallas kernel and one
ServeEngine on the same cluster. Two sessions go through
``cluster.sessions``: each is started, prefilled with a 4 x 2048 prompt,
decoded 16 tokens and suspended to pmem; then each is resumed and
decoded 16 more. The tokens must equal an uninterrupted 32-token decode
of the same prompt. The SSD kernel is also held to
``models.ssm.ssd_chunked`` at these shapes.

``--chips 4`` runs only the 4-layer train step on the (1, 4)
("data", "model") mesh of ``launch/train.py`` against the same steps on
device 0 alone, then a save to pmem and a restore onto the 4-chip layout.

The times printed are smoke timings of one run, not benchmark results.
The last line of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``. With no TPU,
or outside a checkout of the repo, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "mamba2-1.3b"
TRAIN_LAYERS = 4     # of 48; the layer pattern's period is one layer
BATCH, SEQ = 4, 2048
NODES = 3            # a buddy is left to re-replicate to after the kill
STEPS, CKPT_EVERY, FAULT_AT = 6, 3, 4
GEN = 16             # tokens decoded before the suspend and after resume
SESSIONS = 2
CHIPS = 4            # the sharded train path's mesh is (1, CHIPS)
SHARDED_STEPS = 3
# SSD kernel vs ssd_chunked on bf16 inputs: both round through bf16, so
# the max error is held to 2% of the reference's largest magnitude
SSD_TOL = 2e-2
# sharded vs one-device loss: bf16 params, reductions in another order
LOSS_RTOL = 1e-2


def require_tpu():
    """The local devices, or exit non-zero naming the platform found."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform if devices else None
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{platform!r}")
    return devices


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


def _log(phase: str, **kv) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def train_config():
    """mamba2-1.3b cut to TRAIN_LAYERS layers, every width kept."""
    from repro.configs import registry
    return dataclasses.replace(registry.get_config(ARCH),
                               n_layers=TRAIN_LAYERS)


def _batch_spec(shape):
    import jax
    import jax.numpy as jnp
    bs = (shape.global_batch, shape.seq_len)
    return {"tokens": jax.ShapeDtypeStruct(bs, jnp.int32),
            "labels": jax.ShapeDtypeStruct(bs, jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct(bs, jnp.float32)}


def train_phase(cfg, shape, root: Path):
    """Train with pmem checkpoints, a node kill and a restore. Returns the
    cluster (the serve phase runs on it)."""
    from repro.launch.train import build_cluster, build_trainer
    from repro.train import loop as train_loop

    tr = build_trainer(cfg, shape)
    nbytes = tr.state_bytes()
    free_before = shutil.disk_usage(root).free
    # both rotating slots, home and buddy copies
    _require(free_before > 4 * nbytes,
             f"pmem root {root} has {free_before} bytes free; the train "
             f"phase needs {4 * nbytes}")
    cluster, data = build_cluster(root, cfg, shape, nodes=NODES,
                                  state_bytes=nbytes)
    try:
        t0 = time.perf_counter()
        step = tr.step_fn.lower(tr.params, tr.opt_state,
                                _batch_spec(shape)).compile()
        compile_s = time.perf_counter() - t0
        lc = train_loop.LoopConfig(steps=STEPS, ckpt_every=CKPT_EVERY)
        st = train_loop.run(step, tr.params, tr.opt_state,
                            data.batches(STEPS), cluster, lc,
                            fault_at=FAULT_AT)
        del tr  # its params and opt_state were donated to the first step
        _log("train", arch=cfg.name, layers=cfg.n_layers,
             tokens_per_step=shape.global_batch * shape.seq_len,
             compile_s=f"{compile_s:.2f}",
             step_ms_median=f"{statistics.median(st.step_seconds) * 1e3:.1f}",
             ckpt_bytes=nbytes,
             ckpt_submit_ms=[round(s * 1e3, 1) for s in st.ckpt_seconds],
             pmem_free_before=free_before,
             pmem_free_after=shutil.disk_usage(root).free)
        _log("train", losses=[round(x, 4) for x in st.losses],
             recovered_at=st.recovered_at,
             final_ckpt_durability=st.final_ckpt_durability)
        _require(st.step == STEPS, f"ran {st.step} of {STEPS} steps")
        _require(bool(np.isfinite(st.losses).all()),
                 f"non-finite loss: {st.losses}")
        _require(st.recovered_at == [FAULT_AT],
                 f"recovered_at {st.recovered_at}, expected [{FAULT_AT}]")
        _require(st.final_ckpt_durability in ("REPLICATED", "DRAINED"),
                 f"final checkpoint only {st.final_ckpt_durability}")
    except BaseException:
        cluster.shutdown()  # its I/O threads would keep the process alive
        raise
    return cluster


def serve_phase(cfg, cluster, *, batch: int = BATCH,
                prompt_len: int = SEQ, gen: int = GEN,
                ssd_impl: str = "pallas") -> None:
    """Serve sessions through ``cluster.sessions`` with a suspend to pmem
    and a resume between two decodes; the tokens must equal an
    uninterrupted decode of the same prompt."""
    from repro.launch.serve import build_engine

    eng = build_engine(cfg, cluster, max_seq=prompt_len + 2 * gen + 8,
                       ssd_impl=ssd_impl)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (batch, prompt_len))
               .astype(np.int32) for _ in range(SESSIONS)]

    # reference: each prompt decoded 2 * gen tokens without a suspend.
    # The first prefill and decode compile.
    t0 = time.perf_counter()
    eng.prefill(prompts[0])
    first_prefill_s = time.perf_counter() - t0
    refs = []
    for p in prompts:
        refs.append(eng.decode(eng.prefill(p), 2 * gen))
    eng.cache = None

    sm = cluster.sessions
    names = [f"smoke{i}" for i in range(SESSIONS)]
    halves, t = {}, {"prefill": [], "decode": [], "spill": [], "resume": []}

    def timed(key, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        t[key].append((time.perf_counter() - t0) * 1e3)
        return out

    for name, p in zip(names, prompts):
        sm.start(name, eng)
        first = timed("prefill", eng.prefill, p)
        halves[name] = timed("decode", eng.decode, first, gen)
        timed("spill", sm.suspend, name)
        _require(eng.cache is None, f"{name}: suspend left state in DRAM")
    for name, ref in zip(names, refs):
        timed("resume", sm.resume, name, eng)
        more = timed("decode", eng.decode, halves[name][:, -1], gen)
        sm.end(name)
        got = np.concatenate([halves[name], more[:, 1:]], axis=1)
        _require(np.array_equal(got, ref),
                 f"{name}: decode after resume differs from the "
                 f"uninterrupted decode at "
                 f"{np.argwhere(got != ref)[:4].tolist()}")
    _log("serve", arch=cfg.name, layers=cfg.n_layers, batch=batch,
         prompt_len=prompt_len, sessions=SESSIONS,
         first_prefill_s=f"{first_prefill_s:.2f}",
         prefill_ms=[round(x, 1) for x in t["prefill"]],
         decode_ms_per_token=[round(x / gen, 2) for x in t["decode"]],
         spill_ms=[round(x, 1) for x in t["spill"]],
         resume_ms=[round(x, 1) for x in t["resume"]],
         resumed_decode="equal")


def ssd_kernel_check(cfg, *, batch: int = BATCH, seq: int = SEQ,
                     interpret: bool = False) -> None:
    """The SSD Pallas kernel against ``models.ssm.ssd_chunked`` on seeded
    bf16 inputs at ``cfg``'s widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ssd import ops as ssd_ops
    from repro.models.ssm import ssd_chunked, ssm_dims

    _, h, p, g, n = ssm_dims(cfg)
    chunk = cfg.ssm.chunk_size
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (batch, seq, h, p)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, h)) - 3.0)
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    b = jax.random.normal(ks[3], (batch, seq, g, n)).astype(jnp.bfloat16)
    c = jax.random.normal(ks[4], (batch, seq, g, n)).astype(jnp.bfloat16)
    y1, s1 = jax.jit(lambda *t: ssd_ops.ssd(*t, chunk=chunk,
                                            interpret=interpret))(
        x, dt, a, b, c)
    y2, s2 = jax.jit(lambda *t: ssd_chunked(*t, chunk))(x, dt, a, b, c)

    def rel(u, v):
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        return float(np.abs(u - v).max() / np.abs(v).max())

    err_y, err_state = rel(y1, y2), rel(s1, s2)
    _log("ssd_kernel", shape=[batch, seq, h, p], d_state=n, chunk=chunk,
         max_err_y=f"{err_y:.2e}", max_err_state=f"{err_state:.2e}",
         tol=SSD_TOL)
    _require(err_y <= SSD_TOL and err_state <= SSD_TOL,
             f"SSD kernel off ssd_chunked by {err_y:.2e} (y), "
             f"{err_state:.2e} (state) > {SSD_TOL}")


def sharded_train_phase(cfg, shape, root: Path) -> None:
    """The train step sharded over a (1, CHIPS) mesh: SHARDED_STEPS steps
    against the same steps on device 0 alone, then a save and a restore
    onto the sharded layout and one more step."""
    import jax
    from jax.sharding import NamedSharding

    from repro.launch.train import build_cluster, build_trainer

    devices = jax.devices()
    _require(len(devices) >= CHIPS,
             f"{CHIPS} devices needed, {len(devices)} found")

    ref = build_trainer(cfg, shape, devices=devices[:1])
    cluster, data = build_cluster(root, cfg, shape, nodes=CHIPS,
                                  state_bytes=ref.state_bytes())
    try:
        batches = list(data.batches(SHARDED_STEPS + 1))

        def run(tr, bs):
            params, opt_state, losses = tr.params, tr.opt_state, []
            for b in bs:
                params, opt_state, m = tr.step_fn(params, opt_state, b)
                losses.append(float(m["loss"]))
            return params, opt_state, losses

        ref_losses = run(ref, batches)[2]
        del ref

        tr = build_trainer(cfg, shape, devices=devices[:CHIPS])

        def placement(params, opt_state):
            n_sharded = n_replicated = 0
            for x in jax.tree.leaves((params, opt_state)):
                sh = x.sharding
                _require(isinstance(sh, NamedSharding)
                         and sh.mesh == tr.mesh
                         and len(sh.device_set) == CHIPS,
                         f"leaf {x.shape} on {sh}, not on all "
                         f"{CHIPS} devices")
                if "model" in jax.tree.leaves(tuple(sh.spec)):
                    n_sharded += 1
                else:
                    n_replicated += 1
            return n_sharded, n_replicated

        n_sharded, n_replicated = placement(tr.params, tr.opt_state)
        params, opt_state, losses = run(tr, batches[:SHARDED_STEPS])

        host = {"params": jax.tree.map(np.asarray, params),
                "opt": jax.tree.map(np.asarray, opt_state)}
        t0 = time.perf_counter()
        ticket = cluster.tiered.save_async(SHARDED_STEPS, host)
        cluster.tiered.join()
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        restored, _ = cluster.checkpointer.restore(SHARDED_STEPS)
        params, opt_state = jax.device_put(
            (restored["params"], restored["opt"]), tr.shardings)
        restore_s = time.perf_counter() - t0
        placement(params, opt_state)
        for want, got in zip(jax.tree.leaves((host["params"], host["opt"])),
                             jax.tree.leaves((params, opt_state))):
            _require(np.array_equal(want, np.asarray(got)),
                     f"restored leaf {want.shape} differs from the saved one")
        losses += run(dataclasses.replace(tr, params=params,
                                          opt_state=opt_state),
                      batches[SHARDED_STEPS:])[2]
    finally:
        cluster.shutdown()
    rel = max(abs(x - r) / abs(r) for x, r in zip(losses, ref_losses))
    _log("sharded_train", arch=cfg.name, layers=cfg.n_layers,
         mesh=dict(tr.mesh.shape), leaves_sharded=n_sharded,
         leaves_replicated=n_replicated,
         losses=[round(x, 4) for x in losses],
         one_device_losses=[round(x, 4) for x in ref_losses],
         max_rel_diff=f"{rel:.2e}", tol=LOSS_RTOL,
         durability=ticket.durability(), save_s=f"{save_s:.2f}",
         restore_s=f"{restore_s:.2f}")
    _require(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    _require(rel <= LOSS_RTOL,
             f"sharded losses {losses} off the one-device losses "
             f"{ref_losses} by {rel:.2e} > {LOSS_RTOL}")
    _require(ticket.durability() in ("REPLICATED", "DRAINED"),
             f"save only {ticket.durability()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train path")
    args = ap.parse_args(argv)
    devices = require_tpu()
    _require(len(devices) >= args.chips,
             f"--chips {args.chips} but {len(devices)} devices")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import ShapeConfig, registry
    from repro.core.pmem import scratch_root
    from repro.launch.cache import enable_compile_cache

    _log("device", platform=devices[0].platform,
         kind=repr(devices[0].device_kind), count=len(devices),
         compile_cache=enable_compile_cache())
    shape = ShapeConfig("smoke", SEQ, BATCH, "train")
    root = scratch_root("repro_chip_smoke_")
    try:
        if args.chips == 4:
            sharded_train_phase(train_config(), shape, root)
        else:
            cluster = train_phase(train_config(), shape, root)
            try:
                serve_cfg = registry.get_config(ARCH)
                serve_phase(serve_cfg, cluster)
                ssd_kernel_check(serve_cfg)
            finally:
                cluster.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
