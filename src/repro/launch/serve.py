"""Serving driver: --arch <id> [--smoke] — batched prefill + decode of one
session, suspended to pmem and resumed through the cluster's
SessionManager (deliverable (b), serving flavor). By default it serves
the published config; --smoke serves the reduced one (CPU-runnable)."""
from __future__ import annotations

import argparse
import shutil
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import ModelConfig, registry
from repro.core.cluster import SimCluster
from repro.core.pmem import scratch_root
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.serve.engine import ServeEngine


def build_engine(cfg: ModelConfig, cluster: SimCluster, *, max_seq: int,
                 ssd_impl: str = "jnp") -> ServeEngine:
    """One ServeEngine over ``cfg`` initialised from seed 0 on the
    default device, spilling through ``cluster``'s TieredIO engine."""
    rt = tfm.ModelRuntime(tp=1, ssd_impl=ssd_impl, max_seq=max_seq,
                          remat=False)
    params = jax.jit(lambda k: tfm.init_params(k, cfg, rt)[0])(
        jax.random.PRNGKey(0))
    return ServeEngine(cfg, rt, params, tiered=cluster.tiered)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--root", default=None,
                    help="pmem root (default: a fresh scratch directory, "
                         "removed at exit)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = registry.get_smoke_config(args.arch) if args.smoke \
        else registry.get_config(args.arch)
    root = Path(args.root) if args.root else scratch_root("repro_serve_")
    cluster = SimCluster(root, n_nodes=2)
    # the SSD Pallas kernel runs on the TPU; elsewhere the jnp scan
    ssd_impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    try:
        eng = build_engine(cfg, cluster,
                           max_seq=args.prompt_len + args.gen + 8,
                           ssd_impl=ssd_impl)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.batch, args.prompt_len)).astype(np.int32)
        kw = {}
        if cfg.enc_dec:
            kw["enc_frames"] = rng.standard_normal(
                (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)
        sessions = cluster.sessions
        sessions.start("session0", eng)
        t0 = time.time()
        first = eng.prefill(prompts, **kw)
        t_prefill = time.time() - t0
        t0 = time.time()
        out = eng.decode(first, args.gen)
        t_decode = time.time() - t0
        # the session's state goes to pmem as a leased catalog dataset
        # and comes back into the engine
        sessions.suspend("session0")
        sessions.resume("session0", eng)
        more = eng.decode(out[:, -1], 4)
        sessions.end("session0")
    finally:
        cluster.shutdown()
        if not args.root:
            shutil.rmtree(root, ignore_errors=True)
    print(f"arch={cfg.name} batch={args.batch} prefill={t_prefill:.2f}s "
          f"decode={args.gen}tok/{t_decode:.2f}s "
          f"({args.batch * args.gen / max(t_decode, 1e-9):.1f} tok/s) "
          f"spill/resume ok, +4 more tokens: {more[:, 1:].shape}")


if __name__ == "__main__":
    main()
