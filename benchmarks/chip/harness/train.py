"""The train driver: ``train.loop.run`` on the step that
``launch.train.build_trainer`` builds, fed by ``StagedDataset``, with
async checkpoints through ``TieredIO.save_async`` to a ``SimCluster``.

Set-up builds the trainer, puts the seed's weights in place of its own,
writes the seed's token shards to the cluster's external store (the
dataset stages them into pmem), compiles the step, and drives the first
``check_steps`` steps through ``train.loop.run``: the same compiled step,
state and feed that the window then goes on with. Those steps are the
ones the plain reference follows: each step's loss, the first gradient as
AdamW holds it after step 1 (its first moment over 1 - b1), and each
leaf's change over the steps.

The timed ``train.loop.run`` goes on from that state. Its first steps
are set-up too, until every checkpoint slot is taken and one submit more
has waited for a slot (``Window``): from there on the loop runs at the
pace it keeps in a long run. The window then holds whole checkpoint
periods, and the rate is their tokens over their time. After the loop's
final join, the checkpoint of the window's last step must be
acknowledged as replicated, and a seeded sample of its leaves reads
back, from their home pools and from every replica, equal byte for byte
to the state the loop handed to ``save_async``.
"""
from __future__ import annotations

import shutil
import statistics
import time
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from harness import common, draws, model, weights
from harness.common import Compared, log
from harness.refmath import fp8


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_leaves_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def change_norms(new, old) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
    return leaf_norms(diff(new, old))


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   skip: Optional[set] = None) -> float:
    """Largest |prog - ref| over the leaves, each against the larger of
    the reference's norm of that leaf and of the median leaf."""
    keys = [k for k in ref if not skip or k not in skip]
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


def still_leaves(ref_grad: Dict[str, float]) -> set:
    """Leaves whose reference gradient is under a thousandth of the
    median leaf's: Adam moves them by round-off alone."""
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < 1e-3 * med}


class Stepper:
    """The step handed to ``train.loop.run``: the compiled step inside a
    harness span, keeping the state it last returned."""

    def __init__(self, step, on_step=None):
        self.step, self.on_step, self.n, self.last = step, on_step, 0, None

    def __call__(self, params, opt_state, batch):
        import jax
        with jax.profiler.TraceAnnotation("bench.train_step"):
            params, opt_state, metrics = self.step(params, opt_state, batch)
        self.n += 1
        self.last = (params, opt_state)
        if self.on_step is not None:
            self.on_step(self.n, params, opt_state)
        return params, opt_state, metrics


def shards(cell, seed: int) -> List[np.ndarray]:
    tr, m = cell.traffic, cell.config["model"]
    g = draws.rng(seed, 5)
    perm = g.permutation(m["vocab_size"])
    return [draws.zipf_tokens(g, (int(tr["rows_per_shard"]),
                                  int(tr["seq"]) + 1), m["vocab_size"],
                              float(tr["token_zipf_s"]), perm)
            for _ in range(int(tr["shards"]))]


def program_readings(stepper_hooks: dict, losses: List[float],
                     b1: float) -> Dict:
    return {"losses": losses,
            "grad": {k: v / (1.0 - b1)
                     for k, v in stepper_hooks["m1"].items()},
            "change": stepper_hooks["change"]}


def compare(cell, prog: Dict, ref: Dict) -> List[Compared]:
    lim = cell.config["limits"]
    skip = still_leaves(ref["grad"])
    loss = max(abs(p - r) / abs(r) for p, r in
               zip(prog["losses"], ref["losses"]))
    nan = float("nan")
    return [Compared("loss_rel", loss, lim["loss_rel"] or nan),
            Compared("grad_leaf_rel", worst_leaf_gap(
                prog["grad"], ref["grad"], skip), lim["grad_leaf_rel"] or nan),
            Compared("update_leaf_rel", worst_leaf_gap(
                prog["change"], ref["change"], skip),
                lim["update_leaf_rel"] or nan)]


def setup(cell, seed: int, root):
    """The trainer with the seed's weights, the cluster and its dataset,
    the compiled step. Returns (trainer, shapes, cluster, data, step)."""
    import jax

    from repro.configs import ShapeConfig
    from repro.core.cluster import SimCluster
    from repro.data.pipeline import StagedDataset
    from repro.launch.train import build_trainer

    cj, tr = cell.config, cell.traffic
    cfg = model.program_config(cj)
    shape = ShapeConfig("bench", int(tr["seq"]), int(tr["batch"]), "train")
    trainer = build_trainer(cfg, shape, lr=cj["optimizer"]["lr"])
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          trainer.params)
    trainer.params = None
    trainer.params = weights.make(seed, shapes, trainer.shardings[0],
                                  cell.weight_rules())
    nbytes = trainer.state_bytes()
    cluster = SimCluster(root, n_nodes=int(tr["nodes"]),
                         pmem_capacity=max(1 << 32, 4 * nbytes))
    for i, rows in enumerate(shards(cell, seed)):
        cluster.external.put(f"data_shard_{i}", {"tokens": rows})
    data = StagedDataset(cluster, cfg, shape, n_shards=int(tr["shards"]),
                         seqs_per_shard=int(tr["rows_per_shard"]),
                         seed=seed)
    bs = (shape.global_batch, shape.seq_len)
    spec = {"tokens": jax.ShapeDtypeStruct(bs, np.int32),
            "labels": jax.ShapeDtypeStruct(bs, np.int32),
            "loss_mask": jax.ShapeDtypeStruct(bs, np.float32)}
    step = trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                 spec).compile()
    return trainer, shapes, cluster, data, step


def flat(tree, prefix: str = "") -> Dict[str, object]:
    """Leaves by path: dict keys sorted, sequence items by index, joined
    by '/', as a checkpoint's manifest names them."""
    if isinstance(tree, dict):
        out: Dict[str, object] = {}
        for key in sorted(tree):
            out.update(flat(tree[key], f"{prefix}{key}/"))
        return out
    if isinstance(tree, (list, tuple)):
        out = {}
        for i, v in enumerate(tree):
            out.update(flat(v, f"{prefix}{i}/"))
        return out
    return {} if tree is None else {prefix[:-1]: tree}


def same_bytes(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(
        np.ascontiguousarray(a).view(np.uint8),
        np.ascontiguousarray(b).view(np.uint8))


def sample(leaves: Dict[str, object], seed: int, budget: int) -> List[str]:
    """Leaves in an order drawn from the seed, taken while their bytes
    stay within ``budget``; the first is taken whatever its size."""
    out, total = [], 0
    for p in draws.rng(seed, 6).permutation(sorted(leaves)):
        n = int(leaves[p].nbytes)
        if out and total + n > budget:
            continue
        out.append(str(p))
        total += n
    return out


def readback(ckpt, step: int, nodes: Sequence[str],
             saved: Dict[str, np.ndarray]) -> int:
    """Leaves of ``saved`` whose bytes in the checkpoint at ``step``
    differ from it, read once per node with that node taken as lost: a
    read takes the lost node's rows from its acknowledged replica and the
    other rows from their home pools, so the reads cover every home copy
    and every replica of each leaf. A read that fails counts the leaf."""
    bad = set()
    for path, want in saved.items():
        for lost in nodes:
            try:
                got = ckpt.restore_leaves(step, [path],
                                          lost_nodes=[lost])[path]
            except (OSError, KeyError, ValueError) as e:
                log(f"readback: {path} with {lost} lost: {e!r}")
                bad.add(path)
                continue
            if not same_bytes(got, want):
                log(f"readback: {path} with {lost} lost differs")
                bad.add(path)
    return len(bad)


class Window:
    """The batch feed of the timed ``train.loop.run``. A checkpoint
    boundary is the moment the loop asks for a batch right after an
    iteration that submitted a checkpoint. The window opens at the first
    boundary ``warm`` steps or more into the run (set-up until then) and
    closes at the first boundary ``seconds`` or more after it, so that it
    holds whole checkpoint periods: ``every`` steps and one submit each.
    ``on_open`` runs just before the window's clock starts, ``on_close``
    just after it stops."""

    def __init__(self, feed: Iterator, warm: int, every: int,
                 seconds: float, on_open, on_close):
        self.feed, self.warm, self.every = feed, warm, every
        self.seconds, self.on_open, self.on_close = seconds, on_open, on_close
        self.t0 = self.t1 = None
        self.n0 = self.steps = 0

    def __iter__(self) -> Iterator:
        n = 0
        while True:
            if n and n % self.every == 0:
                now = time.perf_counter()
                if self.t0 is None and n >= self.warm:
                    self.on_open()
                    self.n0, self.t0 = n, time.perf_counter()
                elif self.t0 is not None and now - self.t0 >= self.seconds:
                    self.t1, self.steps = now, n - self.n0
                    self.on_close()
                    return
            yield next(self.feed)
            n += 1


def run(cell, seed: int, seconds: float, trace_dir: Optional[str],
        clock, controls: Sequence[str] = ()) -> dict:
    """One run of a train cell; returns what the metric readers read.
    ``controls`` also reads, in the program's place, the reference in
    float8 (``fp8``) and the reference on half of each batch (``half``)."""
    import jax

    from repro.train import loop as train_loop

    tr, opt = cell.traffic, cell.config["optimizer"]
    k, every = int(tr["check_steps"]), int(tr["ckpt_every"])
    root = common.pmem_root()
    cluster = None
    try:
        trainer, shapes, cluster, data, step = setup(cell, seed, root)
        feed = data.batches(1 << 40)
        first: List[Dict[str, np.ndarray]] = []
        hooks: Dict = {}

        def on_step(n, params, opt_state):
            if n == 1:
                hooks["m1"] = leaf_norms(jax.tree.map(
                    lambda mo: mo["m"], opt_state["moments"],
                    is_leaf=lambda x: isinstance(x, dict) and "m" in x))
            if n == k:
                p0 = weights.make(seed, shapes, trainer.shardings[0],
                                  cell.weight_rules())
                hooks["change"] = change_norms(params, p0)
                del p0

        def head() -> Iterator:
            for _ in range(k):
                b = next(feed)
                first.append(b)
                yield b

        stepper = Stepper(step, on_step)
        lc = train_loop.LoopConfig(steps=1 << 30, ckpt_every=every)
        st0 = train_loop.run(stepper, trainer.params, trainer.opt_state,
                             head(), cluster, lc)
        trainer.params = trainer.opt_state = None
        stepper.on_step = None
        hist = cluster.obs.registry.histogram("ckpt.save_commit_s")
        marks: Dict = {}

        def on_open():
            marks["setup_s"] = clock.setup_done()
            if trace_dir:
                jax.profiler.start_trace(trace_dir, profiler_options=
                                         clock.profile_options())
            marks["commit0"] = (hist.sum, hist.count)
            marks["compiles0"] = clock.compiles()
            # made once the trace runs: a span made before records nothing
            marks["span"] = jax.profiler.TraceAnnotation("bench.window")
            marks["span"].__enter__()

        def on_close():
            marks["commit"] = (hist.sum, hist.count)
            marks["span"].__exit__(None, None, None)

        # steady state: every checkpoint slot taken, and one submit more
        # that had to wait for a slot to free
        warm = (cluster.checkpointer.slots + 1) * every
        win = Window(feed, warm, every, seconds, on_open, on_close)
        params, opt_state = stepper.last
        stepper.last = None
        st = train_loop.run(stepper, params, opt_state, iter(win), cluster,
                            lc)
        log(f"after the window: {time.perf_counter() - win.t1:.3f} s to "
            f"the loop's final join")
        del params, opt_state
        if trace_dir:
            jax.profiler.stop_trace()
        compiles = clock.compiles() - marks["compiles0"]
        peak = clock.memory_peak()
        # the loop closed on the window's last checkpoint submit: the state
        # it handed to save_async is the last step's; a seeded sample of
        # its leaves is read back
        state = flat({"params": stepper.last[0], "opt": stepper.last[1]})
        pick = sample(state, seed, int(tr["readback_bytes"]))
        saved = {p: np.asarray(state[p]) for p in pick}
        del state
        stepper.last = None
        del trainer, step, stepper
        last = win.n0 + win.steps
        durability = st.final_ckpt_durability
        t = time.perf_counter()
        bad = readback(cluster.checkpointer, last, cluster.node_ids, saved)
        log(f"readback: {time.perf_counter() - t:.3f} s")
        del saved
    finally:
        if cluster is not None:
            cluster.shutdown()
        shutil.rmtree(root, ignore_errors=True)
    tokens = win.steps * int(tr["batch"]) * int(tr["seq"])
    ckpt_s = st.ckpt_seconds[win.n0 // every:last // every]
    log(f"window: {win.steps} steps and {len(ckpt_s)} checkpoints in "
        f"{win.t1 - win.t0:.3f} s after {win.n0} steps of set-up, "
        f"{compiles} compiles, losses {st0.losses} -> "
        f"{st.losses[-1] if st.losses else None}; checkpoint of step "
        f"{last} {durability}, {bad} of {len(pick)} leaves read back "
        f"wrong")
    t = time.perf_counter()
    ref_mod = cell.reference()
    w = weights.make(seed, shapes, rules=cell.weight_rules())
    m = cell.config["model"]
    ref = ref_mod.train(w, m, opt, first)
    log(f"reference: {time.perf_counter() - t:.3f} s")
    prog = program_readings(hooks, st0.losses, opt["b1"])
    compared = compare(cell, prog, ref) + [
        # exact: the limit is 0
        Compared("ckpt_unreplicated",
                 float(durability not in ("REPLICATED", "DRAINED")), 0.0),
        Compared("ckpt_leaves_wrong", float(bad), 0.0)]
    stand_ins = {"fp8": lambda: ref_mod.train(w, m, opt, first, q=fp8),
                 "half": lambda: ref_mod.train(
                     w, m, opt, first, rows=slice(0, int(tr["batch"]) // 2))}
    readings = {c: {x.name: x.value for x in compare(cell, stand_ins[c](),
                                                       ref)}
                for c in controls}
    del w
    c0, c1 = marks["commit0"], marks["commit"]
    return {"setup_s": marks["setup_s"], "window_s": win.t1 - win.t0,
            "tokens_done": tokens, "ckpt_s": list(ckpt_s),
            "commit_s": (c1[0] - c0[0], c1[1] - c0[1]),
            "attempted": win.steps, "failed": 0,
            "compared": compared, "memory_peak_bytes": peak,
            "compiles_in_window": compiles, "controls": readings}
