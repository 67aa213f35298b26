"""MoE expert-(tensor-)parallelism over the single 'model' mesh axis.

Virtual-slot scheme (DESIGN.md §4): slots = tp; slot ``s`` owns expert group
``s // inner`` and FFN-hidden shard ``s % inner`` with
``inner = max(1, tp // n_experts)``. Only *full-axis* collectives are needed
(subgroup psum is unsupported in shard_map): one all_to_all dispatches
tokens, and the inner-TP partial down-projections are summed with an
(inner-1)-step ppermute ring.

Two execution paths share router/dispatch semantics with models/moe.py:

  make_moe_etp        - training/prefill: tokens are sequence-sharded over
                        'model'; dispatch is gather/scatter-based (no
                        one-hot einsum blowup); all_to_all to expert owners.
  make_moe_replicated - decode: token count is tiny, so tokens stay
                        replicated over 'model'; every shard computes its
                        expert group's contribution and one psum combines
                        groups and inner F-shards simultaneously
                        (zero all_to_all on the latency-critical path).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.mesh import batch_axes, model_axis_size
from repro.models.moe import load_balance_loss, make_moe_layout, router_probs


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _route_and_slot(p, x_flat, cfg: ModelConfig, cap: int):
    """Shared routing: returns (slot [T*k], keep [T*k], gates_flat [T*k],
    aux). slot = expert_id * cap + rank-within-expert."""
    gates, ids, probs = router_probs(p, x_flat, cfg)
    e = cfg.moe.n_experts
    t, k = ids.shape
    ids_flat = ids.reshape(-1)
    gates_flat = gates.reshape(-1).astype(jnp.float32)
    order = jnp.argsort(ids_flat, stable=True)
    sorted_ids = ids_flat[order]
    ranks_sorted = jnp.arange(t * k) - jnp.searchsorted(sorted_ids,
                                                        sorted_ids, "left")
    ranks = jnp.zeros((t * k,), jnp.int32).at[order] \
        .set(ranks_sorted.astype(jnp.int32))
    keep = ranks < cap
    slot = ids_flat * cap + jnp.minimum(ranks, cap - 1)
    aux = load_balance_loss(probs, ids, e)
    return slot, keep, gates_flat, aux


def _dispatch(x_flat, slot, keep, e: int, cap: int):
    """Scatter tokens into [E, cap, D] capacity buffer (dropped -> zero)."""
    d = x_flat.shape[-1]
    src = jnp.where(keep, slot, e * cap)  # dropped rows -> overflow slot
    buf = jnp.zeros((e * cap + 1, d), x_flat.dtype)
    tk = slot.shape[0]
    t = x_flat.shape[0]
    k = tk // t
    xk = jnp.repeat(x_flat, k, axis=0)  # choice j of token t at row t*k+j
    buf = buf.at[src].set(xk)  # duplicate experts per token get one copy each
    return buf[:-1].reshape(e, cap, d)


def _combine(y_buf, slot, keep, gates_flat, t: int):
    """Gather expert outputs back to tokens with gate weighting."""
    d = y_buf.shape[-1]
    flat = y_buf.reshape(-1, d)
    y = flat[slot] * (gates_flat * keep)[:, None].astype(flat.dtype)
    return y.reshape(t, -1, d).sum(axis=1)


def _expert_ffn(recv, wi, wg, wo):
    """recv [..., D] batched over leading expert dims; w* [el, D, Fl]."""
    h = jnp.einsum("...ecd,edf->...ecf", recv, wi)
    g = jnp.einsum("...ecd,edf->...ecf", recv, wg)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    return jnp.einsum("...ecf,efd->...ecd", h, wo)


def make_moe_etp(mesh: Mesh):
    """Sequence-sharded ETP path. Signature: (params, x [B,S,D], cfg)
    -> (y [B,S,D], aux)."""
    batch = batch_axes(mesh) or None
    tp = model_axis_size(mesh)
    all_axes = tuple(mesh.axis_names)

    def moe_fn(p, x, cfg: ModelConfig):
        layout = make_moe_layout(cfg, tp)
        e = cfg.moe.n_experts
        groups, inner, el = layout.groups, layout.inner, layout.e_loc

        def inner_fn(xl, router, wi, wg, wo):
            b_loc, s_loc, d = xl.shape
            t = b_loc * s_loc
            x_flat = xl.reshape(t, d)
            cap = _round_up(max(1, int(t * cfg.moe.top_k *
                                       cfg.moe.capacity_factor / e)), 8)
            slot, keep, gates_flat, aux = _route_and_slot(
                {"router": router}, x_flat, cfg, cap)
            x_disp = _dispatch(x_flat, slot, keep, e, cap)  # [E, cap, D]
            xg = x_disp.reshape(groups, el, cap, d)
            if inner > 1:  # replicate each group's tokens to its F-shards
                xg = jnp.broadcast_to(xg[:, None], (groups, inner, el, cap, d))
            x_send = xg.reshape(tp, el, cap, d)
            if tp > 1:
                recv = jax.lax.all_to_all(x_send, "model", split_axis=0,
                                          concat_axis=0, tiled=True)
            else:
                recv = x_send
            # recv [tp(src), el, cap, D]; FFN with my F-shard
            y_part = _expert_ffn(recv, wi[0], wg[0], wo[0])
            if inner > 1:  # ring-sum partial down-projections within group
                acc = y_part
                for sigma in range(1, inner):
                    perm = [(s, (s // inner) * inner + (s % inner + sigma)
                             % inner) for s in range(tp)]
                    acc = acc + jax.lax.ppermute(y_part, "model", perm)
                y_part = acc
            if tp > 1:
                back = jax.lax.all_to_all(y_part, "model", split_axis=0,
                                          concat_axis=0, tiled=True)
            else:
                back = y_part
            # back [tp(slot), el, cap, D]; group g data identical across its
            # inner slots -> read the r==0 copy.
            y_buf = back.reshape(groups, inner, el, cap, d)[:, 0] \
                .reshape(e, cap, d)
            y = _combine(y_buf, slot, keep, gates_flat, t)
            aux = jax.lax.pmean(aux, all_axes)
            return y.reshape(b_loc, s_loc, d), aux

        fn = jax.shard_map(
            inner_fn, mesh=mesh,
            in_specs=(P(batch, "model", None), P(None, None),
                      P("model", None, None, None),
                      P("model", None, None, None),
                      P("model", None, None, None)),
            out_specs=(P(batch, "model", None), P()),
            check_vma=False)
        return fn(x, p["router"], p["wi"], p["wg"], p["wo"])

    return moe_fn


def make_moe_etp2d(mesh: Mesh):
    """2D expert-parallel training path (perf iteration 5): expert slots
    span model x data (slots = tp*dp), so the weights are FULLY RESIDENT —
    no FSDP re-gathers per layer/microbatch. Tokens travel instead: a
    two-hop all_to_all (over 'model', then over each batch axis) routes
    capacity blocks to the owning slot; partial down-projections from
    inner F-shards ring-sum with a ppermute over the innermost batch axis.
    """
    baxes = batch_axes(mesh)
    tp = model_axis_size(mesh)
    all_axes = tuple(mesh.axis_names)
    dp = 1
    for a in baxes:
        dp *= mesh.shape[a]

    def moe_fn(p, x, cfg: ModelConfig):
        layout = make_moe_layout(cfg, tp, dp)
        e = cfg.moe.n_experts
        slots, inner, el = layout.slots, layout.inner, layout.e_loc
        groups = layout.groups
        last_ax = baxes[-1]
        last_n = mesh.shape[last_ax]
        assert last_n % inner == 0, (last_n, inner)

        def inner_fn(xl, router, wi, wg, wo):
            b_loc, s_loc, d = xl.shape
            t = b_loc * s_loc
            x_flat = xl.reshape(t, d)
            cap = _round_up(max(1, int(t * cfg.moe.top_k *
                                       cfg.moe.capacity_factor / e)), 4)
            slot, keep, gates_flat, aux = _route_and_slot(
                {"router": router}, x_flat, cfg, cap)
            x_disp = _dispatch(x_flat, slot, keep, e, cap)  # [E, cap, D]
            xg = x_disp.reshape(groups, el, cap, d)
            if inner > 1:
                xg = jnp.broadcast_to(xg[:, None],
                                      (groups, inner, el, cap, d))
            x_send = xg.reshape(slots, el, cap, d)

            def hops(z, reverse=False):
                # dims: [tp, *batch_axis_sizes, el, cap, d]
                z = z.reshape((tp,) + tuple(mesh.shape[a] for a in baxes)
                              + (el, cap, d))
                seq = [("model", 0)] + [(a, 1 + i)
                                        for i, a in enumerate(baxes)]
                for ax, dim in (reversed(seq) if reverse else seq):
                    z = jax.lax.all_to_all(z, ax, split_axis=dim,
                                           concat_axis=dim, tiled=True)
                return z.reshape(slots, el, cap, d)

            recv = hops(x_send)
            y_part = _expert_ffn(recv, wi[0, 0], wg[0, 0], wo[0, 0])
            if inner > 1:  # ring-sum F-shard partials (same-group slots
                # are consecutive in the innermost batch axis)
                acc = y_part
                for sigma in range(1, inner):
                    perm = [(i, (i // inner) * inner +
                             (i % inner + sigma) % inner)
                            for i in range(last_n)]
                    acc = acc + jax.lax.ppermute(y_part, last_ax, perm)
                y_part = acc
            back = hops(y_part, reverse=True)
            y_buf = back.reshape(groups, inner, el, cap, d)[:, 0] \
                .reshape(e, cap, d)
            y = _combine(y_buf, slot, keep, gates_flat, t)
            aux = jax.lax.pmean(aux, all_axes)
            return y.reshape(b_loc, s_loc, d), aux

        w_spec = P("model", baxes if len(baxes) > 1 else baxes[0],
                   None, None, None)
        fn = jax.shard_map(
            inner_fn, mesh=mesh,
            in_specs=(P(baxes if len(baxes) > 1 else baxes[0], "model",
                        None),
                      P(None, None), w_spec, w_spec, w_spec),
            out_specs=(P(baxes if len(baxes) > 1 else baxes[0], "model",
                         None), P()),
            check_vma=False)
        return fn(x, p["router"], p["wi"], p["wg"], p["wo"])

    return moe_fn


def make_moe_replicated(mesh: Mesh, expert_2d: bool = False):
    """Decode path: tokens replicated over 'model'; one psum combines expert
    groups and inner F-shards.

    expert_2d (perf iteration 3, EXPERIMENTS.md §Perf): additionally shard
    the experts' FFN hidden dim over the *data* axes so giant MoEs
    (arctic/grok) stay fully resident — no per-token FSDP all-gather of
    expert weights. Tokens (tiny at decode) are all-gathered over the data
    axes instead, and the final psum runs over every mesh axis at once,
    folding expert-group, inner-TP, and data-F partial sums together.
    """
    batch = batch_axes(mesh) or None
    baxes = batch_axes(mesh)
    tp = model_axis_size(mesh)
    all_axes = tuple(mesh.axis_names)
    dp = 1
    for a in baxes:
        dp *= mesh.shape[a]

    def moe_fn(p, x, cfg: ModelConfig):
        layout = make_moe_layout(cfg, tp)
        e = cfg.moe.n_experts
        groups, inner, el = layout.groups, layout.inner, layout.e_loc
        use_2d = expert_2d and dp > 1 and layout.f_loc % dp == 0 and \
            x.shape[0] % dp == 0

        def inner_fn(xl, router, wi, wg, wo):
            b_loc, s, d = xl.shape
            xg = xl
            if use_2d:  # gather the (tiny) token batch across data axes
                for ax in baxes:
                    xg = jax.lax.all_gather(xg, ax, axis=0, tiled=True)
            b_tot = xg.shape[0]
            t = b_tot * s
            x_flat = xg.reshape(t, d)
            cap = _round_up(max(1, int(t * cfg.moe.top_k *
                                       cfg.moe.capacity_factor / e)), 4)
            slot, keep, gates_flat, aux = _route_and_slot(
                {"router": router}, x_flat, cfg, cap)
            x_disp = _dispatch(x_flat, slot, keep, e, cap)  # [E, cap, D]
            g_idx = jax.lax.axis_index("model") // inner if tp > 1 else 0
            x_mine = jax.lax.dynamic_slice_in_dim(
                x_disp.reshape(groups, el, cap, d), g_idx, 1, axis=0)[0]
            y_part = _expert_ffn(x_mine[None], wi[0], wg[0], wo[0])[0]
            # place my experts' outputs into the full [E, cap, D] frame
            y_all = jnp.zeros((groups, el, cap, d), y_part.dtype)
            y_all = jax.lax.dynamic_update_slice_in_dim(
                y_all, y_part[None], g_idx, axis=0).reshape(e, cap, d)
            y_tok = _combine(y_all, slot, keep, gates_flat, t)
            if use_2d:
                y_tok = jax.lax.psum(y_tok, all_axes)
                # slice my batch rows back out
                idx = jnp.int32(0)
                stride = b_tot
                for ax in baxes:
                    stride = stride // jax.lax.axis_size(ax)
                    idx = idx + jax.lax.axis_index(ax) * stride
                y_tok = jax.lax.dynamic_slice_in_dim(
                    y_tok.reshape(b_tot, s, d), idx, b_loc, axis=0)
                aux = jax.lax.pmean(aux, all_axes)
                return y_tok, aux
            if tp > 1:
                y_tok = jax.lax.psum(y_tok, "model")
            aux = jax.lax.pmean(aux, all_axes)
            return y_tok.reshape(b_loc, s, d), aux

        w_spec = P("model", None, None, batch) if use_2d else \
            P("model", None, None, None)
        wo_spec = P("model", None, batch, None) if use_2d else \
            P("model", None, None, None)
        fn = jax.shard_map(
            inner_fn, mesh=mesh,
            in_specs=(P(batch, None, None), P(None, None),
                      w_spec, w_spec, wo_spec),
            out_specs=(P(batch, None, None), P()),
            check_vma=False)
        return fn(x, p["router"], p["wi"], p["wg"], p["wo"])

    return moe_fn
