"""Distributed runtime tests (subprocesses with multi-device CPU meshes,
because the main pytest process must keep the real single-device count)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def test_flash_decode_matches_jnp():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.distributed.decode_attn import make_flash_decode
    from repro.models.transformer import _jnp_decode_attn
    mesh = make_mesh((2, 4), ("data", "model"))
    B, Sc, Kh, H, Dh = 4, 16, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    kc = jax.random.normal(ks[0], (B, Sc, Kh, Dh), jnp.float32)
    vc = jax.random.normal(ks[1], (B, Sc, Kh, Dh), jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(Sc), (B, Sc)).astype(jnp.int32)
    kpos = jnp.where(kpos < 10, kpos, -1)
    q = jax.random.normal(ks[2], (B, H, Dh), jnp.float32)
    kn = jax.random.normal(ks[3], (B, Kh, Dh), jnp.float32)
    vn = jax.random.normal(ks[4], (B, Kh, Dh), jnp.float32)
    pos = jnp.int32(10)
    fd = make_flash_decode(mesh)
    for window in (0, 8):
        o1, c1 = fd(kc, vc, kpos, kn, vn, q, pos, window=window, cap=0.0)
        o2, c2 = _jnp_decode_attn(kc, vc, kpos, kn, vn, q, pos,
                                  window=window, cap=0.0)
        assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-5, window
        assert float(jnp.max(jnp.abs(c1['k'] - c2['k']))) == 0.0
    # batch=1 long-context case must also work (no batch sharding)
    o3, _ = fd(kc[:1], vc[:1], kpos[:1], kn[:1], vn[:1], q[:1], pos,
               window=0, cap=0.0)
    o4, _ = _jnp_decode_attn(kc[:1], vc[:1], kpos[:1], kn[:1], vn[:1],
                             q[:1], pos, window=0, cap=0.0)
    assert float(jnp.max(jnp.abs(o3 - o4))) < 1e-5
    print("OK")
    """)


@pytest.mark.parametrize("n_experts", [8, 2])
def test_moe_parallel_matches_gshard(n_experts):
    _run(f"""
    import jax, jax.numpy as jnp
    from repro.configs.base import (ModelConfig, MoEConfig, LayerSpec,
                                    ATTN_GLOBAL, MLP_MOE)
    from repro.models.moe import init_moe, make_moe_layout, apply_moe_gshard
    from repro.models.layers import ParamBuilder
    from repro.distributed.moe_parallel import (make_moe_etp,
                                                make_moe_replicated)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      pattern=(LayerSpec(mixer=ATTN_GLOBAL, mlp=MLP_MOE),),
                      moe=MoEConfig(n_experts={n_experts}, top_k=2,
                                    capacity_factor=8.0))
    pb = ParamBuilder(jax.random.PRNGKey(0))
    init_moe(pb, cfg, make_moe_layout(cfg, 4))
    params = {{k: v.astype(jnp.float32) for k, v in pb.params.items()}}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32)) * 0.5
    y_ref, _, _ = apply_moe_gshard(params, x, cfg)
    etp = make_moe_etp(mesh)
    y1, _ = jax.jit(lambda p, xx: etp(p, xx, cfg))(params, x)
    rep = make_moe_replicated(mesh)
    y2, _ = jax.jit(lambda p, xx: rep(p, xx, cfg))(params, x)
    assert float(jnp.max(jnp.abs(y1 - y_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(y2 - y_ref))) < 1e-4
    print("OK")
    """)


@pytest.mark.parametrize("n_experts", [8, 2])
def test_moe_decode_2d_experts(n_experts):
    """Perf-iteration 3: fully-resident 2D-sharded experts must be exact."""
    _run(f"""
    import jax, jax.numpy as jnp
    from repro.configs.base import (ModelConfig, MoEConfig, LayerSpec,
                                    ATTN_GLOBAL, MLP_MOE)
    from repro.models.moe import init_moe, make_moe_layout, apply_moe_gshard
    from repro.models.layers import ParamBuilder
    from repro.distributed.moe_parallel import make_moe_replicated
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      pattern=(LayerSpec(mixer=ATTN_GLOBAL, mlp=MLP_MOE),),
                      moe=MoEConfig(n_experts={n_experts}, top_k=2,
                                    capacity_factor=8.0))
    pb = ParamBuilder(jax.random.PRNGKey(0))
    init_moe(pb, cfg, make_moe_layout(cfg, 4))
    params = {{k: v.astype(jnp.float32) for k, v in pb.params.items()}}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 32)) * 0.5
    y_ref, _, _ = apply_moe_gshard(params, x, cfg)
    rep2d = make_moe_replicated(mesh, expert_2d=True)
    y, _ = jax.jit(lambda p, xx: rep2d(p, xx, cfg))(params, x)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4
    print("OK")
    """)


def test_compressed_psum():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.distributed.compression import (
        compressed_psum_scatter_gather, init_error_state)
    mesh = make_mesh((8,), ("data",))
    n = 8 * 1024 * 2
    x = jax.random.normal(jax.random.PRNGKey(0), (8, n)) * 0.1
    err0 = jnp.zeros((8, n // 8), jnp.float32)
    def f(xl, el):
        y, e = compressed_psum_scatter_gather(xl[0], "data", el[0])
        return y[None], e[None]
    y, e = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P("data")),
                         check_vma=False)(x, err0)
    ref = x.mean(0)
    rel = float(jnp.abs(y[0] - ref).max() / jnp.abs(ref).max())
    assert rel < 0.02, rel  # int8 broadcast error ~1/127
    # error feedback: repeated reductions stay unbiased
    acc = jnp.zeros_like(ref); eacc = err0
    for i in range(8):
        y, eacc = jax.shard_map(f, mesh=mesh,
                                in_specs=(P("data"), P("data")),
                                out_specs=(P("data"), P("data")),
                                check_vma=False)(x, eacc)
        acc = acc + y[0]
    rel = float(jnp.abs(acc / 8 - ref).max() / jnp.abs(ref).max())
    assert rel < 0.005, rel
    print("OK")
    """)


def test_pipeline_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.distributed.pipeline import pipeline_apply
    mesh = make_mesh((4, 2), ("pod", "data"))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    def stage_fn(w, h):
        return jnp.tanh(h @ w)
    out = pipeline_apply(mesh, stage_fn, ws, x, axis="pod")
    ref = x
    for s in range(n_stages):
        ref = jnp.tanh(ref @ ws[s])
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    print("OK")
    """)


@pytest.mark.slow
def test_production_dryrun_multipod_smoke():
    """Deliverable (e): one full cell lower+compile on the 2x16x16 mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "decode_32k", "--mesh", "multi",
         "--out", "/tmp/test_dryrun_artifacts"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(REPO))
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "ok:" in p.stdout
