"""What every cell shares: finding a cell's files by name, the chip
check, the compile cache, the process clock, and the result line."""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[3]
BENCH = ROOT / "benchmarks" / "chip"
CACHE_DIR = ROOT / ".jax_cache"
# the most a cell keeps in its pools: two checkpoint slots and their
# buddy copies of 3.1 GB each, the dataset, and room to spare
POOL_ROOM = 24 << 30


def process_start() -> float:
    """Wall-clock time at which this process started (from /proc), so
    that set-up counts the interpreter's own start."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text()
                    .rsplit(")", 1)[1].split()[19])
        btime = next(int(ln.split()[1]) for ln in
                     Path("/proc/stat").read_text().splitlines()
                     if ln.startswith("btime"))
        return btime + start / ticks
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def _fs_type(path: Path) -> Optional[str]:
    """The type of the file system that holds ``path`` (from
    /proc/mounts: the longest mount point above it)."""
    try:
        mounts = [ln.split()[1:3] for ln in
                  Path("/proc/mounts").read_text().splitlines()]
    except OSError:
        return None
    path = path.resolve()
    best = max((m for m in mounts if path == Path(m[0]) or
                Path(m[0]) in path.parents),
               key=lambda m: len(m[0]), default=None)
    return best[1] if best else None


def pmem_root() -> Path:
    """The directory of a run's pmem pools, emptied: DRAM-backed tmpfs,
    as the program emulates byte-addressable pmem (a train run commits
    tens of GB of checkpoints, which on a disk would be written to it).
    ``$TMPDIR/bench_pmem`` where TMPDIR is a tmpfs with ``POOL_ROOM``
    free, else ``/dev/shm/bench_pmem_<hash of the checkout's path>``. The
    name is fixed per checkout, so a run clears what a killed run of the
    same checkout left there, and never touches another checkout's."""
    tmp = Path(os.environ.get("TMPDIR") or "/tmp")
    if tmp.is_dir() and _fs_type(tmp) == "tmpfs" and \
            shutil.disk_usage(tmp).free >= POOL_ROOM:
        root = tmp / "bench_pmem"
    else:
        key = hashlib.sha1(str(ROOT).encode()).hexdigest()[:12]
        root = Path("/dev/shm") / f"bench_pmem_{key}"
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)
    return root


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names hold '.' and
    '-', so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file
    traffic: Dict[str, Any]         # the traffic file
    metrics: Dict[str, Dict]        # metric name -> its BENCHMARK.json entry

    def reference(self) -> ModuleType:
        """The plain reference module beside the configuration."""
        return load_module(BENCH / "configs" / self.config["reference"])

    def weight_rules(self) -> Dict[str, Any]:
        """The reference's ``WEIGHT_RULES`` for leaves the harness's table
        lacks (``harness/weights.py``); none by default."""
        return getattr(self.reference(), "WEIGHT_RULES", {})


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, trace: bool, root: Path = ROOT,
              bench: Path = BENCH) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in spec['workloads']]}")
    cfg = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((bench / "traffic" /
                          f"{wl['traffic']}.json").read_text())
    group = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {m["name"]: m for m in group if _applies(m, name)}
    return Cell(name, int(wl["chips"]), config, traffic, metrics)


def require_chips(n: int):
    """The local devices, or exit non-zero naming what JAX found."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform if devices else None
    if platform != "tpu":
        sys.exit(f"benchmark: needs a TPU, JAX found platform {platform!r}")
    if len(devices) < n:
        sys.exit(f"benchmark: the cell needs {n} chips, JAX found "
                 f"{len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache`` (or
    where ``JAX_COMPILATION_CACHE_DIR`` points), caching every program so
    that only a checkout's first run compiles."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env or str(CACHE_DIR)


class CompileCounter:
    """Counts backend compilations, so a window can show it had none."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def device_info(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Compared:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def emit(*, correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
         compared: List[Compared], breakdown: Optional[dict] = None
         ) -> None:
    """The result line, last on stdout; the numbers compared, each beside
    its limit, last on stderr."""
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in compared}
    sys.stdout.flush()
    for c in compared:
        log(f"compared {c.name}={c.value!r} limit={c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    print(json.dumps(out), flush=True)
