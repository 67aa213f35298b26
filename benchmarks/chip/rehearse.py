#!/usr/bin/env python3
"""Compile the benchmark's largest programs for a described v5e chip,
with no chip attached, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py
    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \
        --config <file.json> [--prompt N] [--max-seq N]

Programs: the starcoder2-15b-10l prefill of the longest prompt (3584
tokens) and its decode step over a 4096-token cache; the mamba2-1.3b
prefill of 4096 tokens with the SSD kernel and its decode step; the
mamba2-1.3b-4l train step at the train cell's batch. Each is lowered on
shapes alone, as the benchmark calls it, on device 0 of a described
``v5e:2x2`` topology. With ``--config`` it compiles that configuration
file's prefill of ``--prompt`` tokens and its decode step over a
``--max-seq`` cache instead (SSD layers through the kernel), so that a
new configuration's cut can be sized before any chip run.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _report(name: str, compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["total_gb"] = round((out["argument_size_in_bytes"] +
                             out["output_size_in_bytes"] -
                             out["alias_size_in_bytes"] +
                             out["temp_size_in_bytes"]) / 1e9, 3)
    print(name, json.dumps(out), flush=True)
    return out


def serve_programs(cj: dict, prompt: int, max_seq: int, ssd_impl: str,
                   dev) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from harness import model
    from repro.models import transformer as tfm
    cfg = model.program_config(cj)
    rt = tfm.ModelRuntime(tp=1, ssd_impl=ssd_impl, max_seq=max_seq,
                          remat=False)
    one = SingleDeviceSharding(dev)

    def on_dev(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_dev(tfm.abstract_params(cfg, rt)[0])
    toks = jax.ShapeDtypeStruct((1, prompt), jnp.int32, sharding=one)
    pre = jax.jit(functools.partial(tfm.prefill, cfg=cfg, rt=rt))
    _report(f"{cj['name']} prefill[1x{prompt}]",
            pre.lower(params, tokens=toks).compile())
    cache = on_dev(jax.eval_shape(lambda: tfm.init_cache(cfg, rt, 1)[0]))
    dec = jax.jit(lambda p, c, t, pos: tfm.decode_step(p, cfg, rt, c, t,
                                                       pos))
    _report(f"{cj['name']} decode[1, cache {rt.max_seq}]",
            dec.lower(params, cache,
                      jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
                      jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
                      ).compile())


def train_program(dev) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from harness import model
    from repro.configs import ParallelConfig, ShapeConfig
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tfm
    from repro.train import optimizer as opt
    from repro.train import train_step as ts
    cj = json.loads((HERE / "configs" / "mamba2-1.3b-4l.json").read_text())
    tr = json.loads((HERE / "traffic" / "train-ckpt.json").read_text())
    cfg = model.program_config(cj)
    shape = ShapeConfig("bench", tr["seq"], tr["batch"], "train")
    # as launch.train.build_trainer builds it, on the described device
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    plan = shd.Plan(mesh, cfg, shape, ParallelConfig(attn_impl="blockwise"))
    rt = plan.runtime()
    adamw = opt.AdamWConfig(lr=cj["optimizer"]["lr"], warmup=10)
    p_shapes, p_specs = tfm.abstract_params(cfg, rt)
    o_shapes = jax.eval_shape(lambda p: opt.init_opt_state(p, adamw),
                              p_shapes)
    p_sh = shd.tree_shardings(p_shapes, p_specs, mesh)
    o_sh = shd.tree_shardings(o_shapes, opt.opt_state_specs(p_specs, adamw),
                              mesh, zero1=True)
    step = jax.jit(
        ts.make_train_step(cfg, rt, plan.constrain, adamw, ce_chunk=128),
        in_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
        out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    bs = (shape.global_batch, shape.seq_len)
    batch = {"tokens": jax.ShapeDtypeStruct(bs, jnp.int32),
             "labels": jax.ShapeDtypeStruct(bs, jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct(bs, jnp.float32)}
    _report(f"{cj['name']} train_step[{tr['batch']}x{tr['seq']}]",
            step.lower(p_shapes, o_shapes, batch).compile())


def _cell_serve(cfg_file: str, traffic: str, prompt: int, dev) -> None:
    cj = json.loads((HERE / "configs" / cfg_file).read_text())
    tr = json.loads((HERE / "traffic" / traffic).read_text())
    serve_programs(cj, prompt, int(tr["max_seq"]), tr["ssd_impl"], dev)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", help="a configuration file to compile")
    ap.add_argument("--prompt", type=int, default=4096)
    ap.add_argument("--max-seq", type=int, default=4096)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    if args.config:
        cj = json.loads(Path(args.config).read_text())
        serve_programs(cj, args.prompt, args.max_seq, "pallas", dev)
        return
    _cell_serve("starcoder2-15b-10l.json", "serve-code.json", 3584, dev)
    _cell_serve("mamba2-1.3b.json", "serve-chat.json", 4096, dev)
    train_program(dev)


if __name__ == "__main__":
    main()
