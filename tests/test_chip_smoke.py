"""chip_smoke.py refuses a host with no TPU, and each of its phases passes
on the CPU at the reduced mamba2 config (Pallas kernels in interpret
mode). The compile-cache rule of the entry points is checked here too."""
import importlib.util
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs import ShapeConfig, registry

REPO = Path(__file__).resolve().parents[1]
SHAPE = ShapeConfig("t", 32, 4, "train")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def cfg():
    return registry.get_smoke_config("mamba2-1.3b")


def test_refuses_a_host_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=tmp_path)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


def test_train_phase_kills_a_node_and_recovers(smoke, cfg, tmp_path):
    cluster = smoke.train_phase(cfg, SHAPE, tmp_path)
    cluster.shutdown()


def test_serve_phase_resumes_bit_exact(smoke, cfg, cluster):
    smoke.serve_phase(cfg, cluster, batch=2, prompt_len=32, gen=4,
                      ssd_impl="interpret")


def test_ssd_kernel_check(smoke, cfg):
    smoke.ssd_kernel_check(cfg, batch=2, seq=64, interpret=True)


def test_sharded_train_phase_on_four_devices():
    code = textwrap.dedent(f"""
        import shutil, sys, tempfile
        sys.path.insert(0, {str(REPO)!r})
        import chip_smoke
        from pathlib import Path
        from repro.configs import ShapeConfig, registry
        cfg = registry.get_smoke_config("mamba2-1.3b")
        root = Path(tempfile.mkdtemp(prefix="repro_test_"))
        try:
            chip_smoke.sharded_train_phase(
                cfg, ShapeConfig("t", 32, 4, "train"), root)
        finally:
            shutil.rmtree(root, ignore_errors=True)
    """)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "leaves_sharded=" in p.stdout


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch.cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        want = str(REPO / ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch):
    from repro.launch.cache import enable_compile_cache
    where = tempfile.gettempdir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", where)
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == where
    assert jax.config.jax_compilation_cache_dir == was  # nothing set
