import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces the compiled artifact's memory analysis (proves
HBM fit), cost analysis (FLOPs / bytes for the roofline), and the collective
schedule (parsed from the optimized HLO) -> one JSON per cell under
artifacts/dryrun/. benchmarks/roofline.py turns these into EXPERIMENTS.md
tables.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, base, registry
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch import specs as specmod
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.train import optimizer as opt
from repro.train import train_step as ts

# --- hardware constants (TPU v5e-class target; see EXPERIMENTS.md) ---
HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link
    "hbm_bytes": 16e9,           # per chip
}

def _sharded_bytes(shapes_tree, shardings_tree) -> int:
    """Exact per-device bytes of a sharded pytree (via shard_shape)."""
    total = 0
    for sds, sh in zip(jax.tree.leaves(shapes_tree),
                       jax.tree.leaves(
                           shardings_tree,
                           is_leaf=lambda x: isinstance(x, NamedSharding))):
        shard = sh.shard_shape(sds.shape)
        n = 1
        for d in shard:
            n *= d
        total += n * sds.dtype.itemsize
    return total


def analytic_memory(cfg: base.ModelConfig, shape: base.ShapeConfig,
                    mesh, mb: int, arg_bytes: int) -> dict:
    """Per-device peak model: exact argument bytes + analytic transients.

    Transients (train): remat stores one residual per layer per microbatch
    + ~6 activation-sized f32 workspaces + one gathered layer's params.
    """
    tp = mesh.shape["model"]
    dp = mesh.size // tp
    s, b = shape.seq_len, shape.global_batch
    d = cfg.d_model
    layers = cfg.n_layers + cfg.n_enc_layers
    if shape.kind == "train":
        b_micro = max(b // dp // mb, 1)
        resid = layers * b_micro * s * d * 2
        work = 8 * b_micro * s * d * 4
        gbytes = 2 if cfg.param_count() > 4e11 else 4
        grads = gbytes * cfg.param_count(tp, padded=True) // tp // dp
        transient = resid + work + grads
    elif shape.kind == "prefill":
        b_loc = max(b // dp, 1)
        transient = 10 * b_loc * s * d * 2
    else:
        transient = int(0.5 * arg_bytes) + 64 * d * 4  # cache double-buffer
    peak = arg_bytes + transient
    return {"arg_bytes_exact": arg_bytes, "transient_model": transient,
            "peak_model": peak, "fits_16GB_model": bool(peak <= 16e9)}


def pick_microbatches(cfg: base.ModelConfig, shape: base.ShapeConfig,
                      dp: int) -> int:
    """Heuristic: keep per-microbatch stored activations under ~3 GB/device
    (scan-remat stores one residual per layer)."""
    if shape.kind != "train":
        return 1
    b_loc = max(shape.global_batch // dp, 1)
    layers = cfg.n_layers + (cfg.n_enc_layers or 0)
    act = layers * b_loc * shape.seq_len * cfg.d_model * 2
    mb = 1
    while act / mb > 3e9 and mb < b_loc:
        mb *= 2
    return mb


def build_and_compile(arch: str, shape_name: str, multi_pod: bool,
                      parallel: base.ParallelConfig, mb_override=None):
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = shd.Plan(mesh, cfg, shape, parallel)
    rt = plan.runtime()
    n_dev = mesh.size
    dp = n_dev // mesh.shape["model"]
    batch_axes = plan.batch

    params_shapes, pspecs = tfm.abstract_params(cfg, rt)
    # FSDP: shard params over the data axes too when a TP-only shard would
    # not leave room for activations (>4 GB/device of params). For decode,
    # FSDP means an all-gather of the full model EVERY TOKEN — only do it
    # when TP-sharded params + cache genuinely can't fit (perf iteration 2,
    # EXPERIMENTS.md §Perf: arctic decode was collective-bound purely on
    # these gathers).
    param_bytes_tp = 2 * cfg.param_count(mesh.shape["model"], padded=True) \
        / mesh.shape["model"]
    overrides = None
    if cfg.moe is not None and shape.kind == "decode":
        # decode MoE: experts 2D-sharded (model x data on FFN hidden) ->
        # fully resident, zero per-token weight gathers; dense part is small
        fsdp = False
        overrides = {"expert_f": "__batch__"}
    else:
        # >4 GB/device of TP-sharded params leaves no room for activations
        # (train) or KV caches (prefill/decode) on a 16 GB chip.
        fsdp = param_bytes_tp > 4e9
    param_sh = shd.tree_shardings(params_shapes, pspecs, mesh, zero1=fsdp,
                                  overrides=overrides)

    t0 = time.time()
    if shape.kind == "train":
        mb = mb_override or pick_microbatches(cfg, shape, dp)
        adamw = opt.AdamWConfig(
            moments_dtype="int8" if cfg.param_count() > 1.2e11 else "float32")
        opt_shapes = jax.eval_shape(
            lambda p: opt.init_opt_state(p, adamw), params_shapes)
        opt_specs = opt.opt_state_specs(pspecs, adamw)
        opt_sh = shd.tree_shardings(opt_shapes, opt_specs, mesh, zero1=True)
        batch, _ = specmod.input_specs(cfg, shape, rt)
        batch_sh = {
            k: NamedSharding(mesh, shd._fit_pspec(
                P(batch_axes, *([None] * (v.ndim - 1))), v.shape, mesh))
            for k, v in batch.items()}
        # ZeRO-2: keep the f32 grad accumulator data-sharded
        grad_sh = shd.tree_shardings(params_shapes, pspecs, mesh, zero1=True)
        accum = jnp.bfloat16 if cfg.param_count() > 4e11 else jnp.float32
        step = ts.make_train_step(cfg, rt, plan.constrain, adamw,
                                  microbatches=mb,
                                  ce_chunk=parallel.ce_chunk,
                                  grad_shardings=grad_sh,
                                  accum_dtype=accum)
        jitted = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                         out_shardings=(param_sh, opt_sh, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_shapes, opt_shapes, batch)
        arg_bytes = _sharded_bytes((params_shapes, opt_shapes, batch),
                                   (param_sh, opt_sh, batch_sh))
        extra = {"microbatches": mb, "moments": adamw.moments_dtype,
                 "fsdp": fsdp}
    elif shape.kind == "prefill":
        batch, _ = specmod.input_specs(cfg, shape, rt)
        batch_sh = {
            k: NamedSharding(mesh, shd._fit_pspec(
                P(batch_axes, *([None] * (v.ndim - 1))), v.shape, mesh))
            for k, v in batch.items()}

        def prefill_step(params, b):
            return tfm.prefill(params, cfg, rt, b["tokens"],
                               prefix_embeds=b.get("prefix_embeds"),
                               enc_frames=b.get("enc_frames"))

        cache_shapes = jax.eval_shape(prefill_step, params_shapes, batch)[1]
        # logical specs for produced caches match init_cache's
        _, cache_specs = specmod.abstract_cache(
            cfg, rt, shape.global_batch,
            shape.seq_len if cfg.enc_dec else 0)
        cache_sh = shd.tree_shardings(cache_shapes, cache_specs, mesh)
        logits_sh = NamedSharding(mesh, shd._fit_pspec(
            P(batch_axes, "model"),
            (shape.global_batch, cfg.padded_vocab), mesh))
        jitted = jax.jit(prefill_step, in_shardings=(param_sh, batch_sh),
                         out_shardings=(logits_sh, cache_sh))
        lowered = jitted.lower(params_shapes, batch)
        arg_bytes = _sharded_bytes((params_shapes, batch, cache_shapes),
                                   (param_sh, batch_sh, cache_sh))
        extra = {"fsdp": fsdp}
    else:  # decode
        inputs, cache_specs = specmod.input_specs(cfg, shape, rt)
        cache_sh = shd.tree_shardings(inputs["cache"], cache_specs, mesh)
        tok_sh = NamedSharding(mesh, shd._fit_pspec(
            P(batch_axes), (shape.global_batch,), mesh))
        logits_sh = NamedSharding(mesh, shd._fit_pspec(
            P(batch_axes, "model"),
            (shape.global_batch, cfg.padded_vocab), mesh))

        def serve_step(params, cache, tokens, pos):
            logits, cache, _ = tfm.decode_step(params, cfg, rt, cache,
                                               tokens, pos)
            return logits, cache

        jitted = jax.jit(
            serve_step,
            in_shardings=(param_sh, cache_sh, tok_sh,
                          NamedSharding(mesh, P())),
            out_shardings=(logits_sh, cache_sh), donate_argnums=(1,))
        lowered = jitted.lower(params_shapes, inputs["cache"],
                               inputs["tokens"], inputs["pos"])
        arg_bytes = _sharded_bytes((params_shapes, inputs["cache"]),
                                   (param_sh, cache_sh))
        extra = {"fsdp": fsdp}
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    if os.environ.get("DRYRUN_SAVE_HLO"):
        Path(os.environ["DRYRUN_SAVE_HLO"]).write_text(hlo)
    # trip-count-aware analysis (XLA's cost_analysis counts while bodies
    # once -> ~layers x microbatches undercount; see hlo_analysis.py)
    ana = hlo_analysis.analyze(hlo, n_dev)
    coll = ana["collectives"]

    flops_dev = float(ana["flops"])
    bytes_dev = float(ana["bytes"])
    wire = float(ana["wire_bytes"])
    # roofline terms (seconds)
    t_comp = flops_dev / HW["peak_flops_bf16"]
    t_mem = bytes_dev / HW["hbm_bw"]
    t_coll = wire / HW["ici_bw"]
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]

    n_par = cfg.param_count()
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_act * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_act * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_act * tokens
    model_flops_dev = model_flops / n_dev

    dev_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    amem = analytic_memory(cfg, shape, mesh, extra.get("microbatches", 1),
                           arg_bytes)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "kind": shape.kind,
        "params": n_par, "active_params": n_act,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "xla_raw": {"flops": float(cost.get("flops", 0.0)),
                    "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "collective_wire_bytes": wire,
        "bytes_by_tag": ana.get("bytes_by_tag", {}),
        "flops_by_tag": ana.get("flops_by_tag", {}),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "xla_cpu_peak": dev_bytes,
            **amem,
            "fits_16GB": amem["fits_16GB_model"],
        },
        "roofline": {
            "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
            "dominant": dominant,
            "model_flops_per_device": model_flops_dev,
            "useful_compute_ratio": model_flops_dev / max(flops_dev, 1.0),
            "roofline_fraction": model_flops_dev / HW["peak_flops_bf16"] /
            max(t_comp, t_mem, t_coll, 1e-12),
        },
        "timing": {"lower_s": t_lower, "compile_s": t_compile},
        "seq_parallel": parallel.seq_parallel,
        **extra,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--mb", type=int, default=None)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--attn-impl", default="blockwise")
    ap.add_argument("--remat", default="block", choices=["block", "dots",
                                                         "none"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = list(registry.cells(args.arch, args.shape))
    parallel = base.ParallelConfig(seq_parallel=args.seq_parallel,
                                   attn_impl=args.attn_impl,
                                   remat=args.remat)
    failures = 0
    for cell in cells:
        for multi in meshes:
            mesh_name = "2x16x16" if multi else "16x16"
            tag = f"_{args.tag}" if args.tag else ""
            fname = outdir / (f"{cell.arch}_{cell.shape.name}_"
                              f"{mesh_name}{tag}.json")
            if cell.skip:
                json.dump({"arch": cell.arch, "shape": cell.shape.name,
                           "mesh": mesh_name, "skipped": cell.skip},
                          open(fname, "w"), indent=1)
                print(f"[skip] {cell.name} ({mesh_name}): {cell.skip}")
                continue
            print(f"[cell] {cell.name} ({mesh_name}) ...", flush=True)
            try:
                res = build_and_compile(cell.arch, cell.shape.name, multi,
                                        parallel, args.mb)
                json.dump(res, open(fname, "w"), indent=1)
                r = res["roofline"]
                print(f"  ok: flops/dev={res['flops_per_device']:.3e} "
                      f"dom={r['dominant']} "
                      f"roofline={r['roofline_fraction']:.3f} "
                      f"fits={res['memory']['fits_16GB']} "
                      f"compile={res['timing']['compile_s']:.1f}s",
                      flush=True)
            except Exception as e:
                failures += 1
                print(f"  FAIL: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
