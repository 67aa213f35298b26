"""Mixture-of-experts FFN: router + dispatch.

Two implementations share router semantics:
  * ``gshard`` — dense compute-all-held-experts weighted combine (exact; the
    single-chip path, and the correctness oracle for the distributed path).
  * ``etp``    — expert-(tensor-)parallel shard_map path in
    ``distributed/moe_parallel.py`` (capacity-based dispatch, all_to_all,
    inner-TP via ppermute) — the production path.

Expert weights are stored *device-major*: [slots, E_loc, D, F_loc] where
``slots = tp`` mesh degree, slot s owns expert group ``s // inner`` and FFN
shard ``s % inner`` with ``inner = max(1, tp // n_experts)``. With tp == 1
this degenerates to [1, E, D, F] (the logical layout).

Expert parallelism over chips that each hold a share of the experts
(``MoEConfig.n_held`` of ``n_experts``, the first ones): the router
scores every expert and picks over all of them, and the layer adds the part
of the result that the held experts give; a picked expert held elsewhere
contributes nothing here. A shared expert (``MoEConfig.shared_ff``), which
every chip computes alike, is added in full.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLP_RELU2, ROUTER_SIGMOID_BIAS, ModelConfig
from repro.models.layers import (ParamBuilder, apply_mlp, init_mlp,
                                 softcap)

Params = Dict[str, Any]


class MoELayout(NamedTuple):
    slots: int       # total virtual slots (= tp, or tp*dp for 2D)
    inner: int       # FFN shards per expert group
    e_loc: int       # experts per slot group
    f_loc: int       # FFN hidden per slot
    dp: int = 1      # data-axis slot factor (2D expert parallelism)

    @property
    def groups(self) -> int:
        return self.slots // self.inner


def make_moe_layout(cfg: ModelConfig, tp: int, dp: int = 1) -> MoELayout:
    e = cfg.moe.held
    f = cfg.moe.d_ff or cfg.d_ff
    slots = tp * dp
    inner = max(1, slots // e)
    groups = slots // inner
    assert slots % inner == 0 and e % groups == 0, (e, tp, dp)
    assert f % inner == 0, (f, inner)
    if dp > 1:  # inner ring must stay within one model row (ppermute axis)
        assert inner <= dp and dp % inner == 0, (inner, dp)
    return MoELayout(slots, inner, e // groups, f // inner, dp)


def can_use_2d(cfg: ModelConfig, tp: int, dp: int,
               last_axis: int = 0) -> bool:
    if cfg.moe is None or dp <= 1:
        return False
    e = cfg.moe.n_experts
    f = cfg.moe.d_ff or cfg.d_ff
    slots = tp * dp
    inner = max(1, slots // e)
    groups = slots // inner
    last = last_axis or dp
    return (slots % inner == 0 and e % groups == 0 and f % inner == 0
            and inner <= last and last % inner == 0 and dp % inner == 0)


def _expert_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    return ("wi", "wo") if cfg.moe.mlp == MLP_RELU2 else ("wi", "wg", "wo")


def etp_supported(cfg: ModelConfig) -> bool:
    """Whether the expert-parallel shard_map paths
    (``distributed/moe_parallel.py``) compute this layer: a softmax router
    over SwiGLU experts, all held, with no shared expert. Other layers
    run ``apply_moe_gshard`` under the compiler's sharding."""
    mo = cfg.moe
    return (mo.router != ROUTER_SIGMOID_BIAS and mo.mlp != MLP_RELU2
            and not mo.shared_ff and mo.held == mo.n_experts)


def init_moe(pb: ParamBuilder, cfg: ModelConfig, layout: MoELayout) -> None:
    d = cfg.d_model
    mo = cfg.moe
    sl, el, fl = layout.slots, layout.e_loc, layout.f_loc
    pb.param("router", (d, mo.n_experts), (None, None), init="fan_in")
    if mo.router == ROUTER_SIGMOID_BIAS:
        pb.param("router_bias", (mo.n_experts,), (None,), init="zeros")
    if mo.shared_ff:
        init_mlp(pb.child("shared"), d, mo.shared_ff, mo.mlp, False)
    gated = "wg" in _expert_leaves(cfg)
    if layout.dp > 1:
        # 2D expert parallelism (training): slots span model x data — the
        # weights are fully resident, tokens travel (two-hop all_to_all).
        tp = sl // layout.dp
        pb.param("wi", (tp, layout.dp, el, d, fl),
                 ("expert_slots", "expert_slots_dp", None, None, None),
                 init="fan_in")
        if gated:
            pb.param("wg", (tp, layout.dp, el, d, fl),
                     ("expert_slots", "expert_slots_dp", None, None, None),
                     init="fan_in")
        pb.param("wo", (tp, layout.dp, el, fl, d),
                 ("expert_slots", "expert_slots_dp", None, None, None),
                 init="fan_in")
        return
    # "expert_f" is unsharded by default; decode plans map it to the data
    # axes (2D expert sharding -> giant MoEs stay resident, no FSDP gathers)
    pb.param("wi", (sl, el, d, fl), ("expert_slots", None, None, "expert_f"),
             init="fan_in")
    if gated:
        pb.param("wg", (sl, el, d, fl),
                 ("expert_slots", None, None, "expert_f"), init="fan_in")
    pb.param("wo", (sl, el, fl, d), ("expert_slots", None, "expert_f", None),
             init="fan_in")


def router_probs(p: Params, x: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [..., D] -> (top-k gate weights [..., k], expert ids [..., k],
    full probs [..., E] for aux loss). The sigmoid router's matmul runs
    in float32."""
    mo = cfg.moe
    if mo.router == ROUTER_SIGMOID_BIAS:
        logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(
            scores + p["router_bias"].astype(jnp.float32), mo.top_k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        return gates * mo.routed_scale, ids, scores
    logits = jnp.einsum("...d,de->...e", x, p["router"]) \
        .astype(jnp.float32)
    logits = softcap(logits, cfg.moe.router_softcap)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.moe.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, ids, probs


def load_balance_loss(probs: jax.Array, ids: jax.Array, n_experts: int
                      ) -> jax.Array:
    """Switch-style auxiliary load-balancing loss."""
    me = probs.reshape(-1, n_experts).mean(0)
    assign = jax.nn.one_hot(ids.reshape(-1), n_experts).mean(0) * ids.shape[-1]
    return n_experts * jnp.sum(me * assign)


def logical_expert_weights(p: Params, cfg: ModelConfig):
    """Device-major [slots, E_loc, D, F_loc] -> logical [E_held, D, F]
    views (wi, wg, wo); wg is None for ungated experts."""
    if p["wi"].ndim == 5:  # 2D layout: flatten (tp, dp) -> slots
        p = dict(p)
        for k in _expert_leaves(cfg):
            w = p[k]
            p[k] = w.reshape((w.shape[0] * w.shape[1],) + w.shape[2:])
    slots = p["wi"].shape[0]
    layout = make_moe_layout(cfg, slots)
    e, d = cfg.moe.held, cfg.d_model
    f = cfg.moe.d_ff or cfg.d_ff

    def undev(w, last_is_d):
        g, r, el, fl = layout.groups, layout.inner, layout.e_loc, layout.f_loc
        if last_is_d:  # wo: [slots, el, fl, d]
            w = w.reshape(g, r, el, fl, d).transpose(0, 2, 1, 3, 4)
            return w.reshape(e, f, d)
        w = w.reshape(g, r, el, d, fl).transpose(0, 2, 3, 1, 4)
        return w.reshape(e, d, f)

    wg = undev(p["wg"], False) if "wg" in p else None
    return undev(p["wi"], False), wg, undev(p["wo"], True)


def apply_moe_gshard(p: Params, x: jax.Array, cfg: ModelConfig
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense all-held-experts fallback (exact oracle; any slot layout): every
    held expert runs on every token and is weighted by its gate, zero for a
    token that did not pick it, so no token is dropped. x [B,S,D].
    Returns (y, load-balance loss, how many of the router's picks land on
    the experts held here: an int32 scalar)."""
    wi, wg, wo = logical_expert_weights(p, cfg)
    gates, ids, probs = router_probs(p, x, cfg)
    mo = cfg.moe
    e = mo.held
    # combine weights per held expert: [B,S,E_held] (zero for experts
    # picked elsewhere: one_hot of an id outside [0, E_held) is all zeros)
    comb = jnp.zeros(x.shape[:-1] + (e,), jnp.float32)
    for j in range(mo.top_k):
        comb = comb + jax.nn.one_hot(ids[..., j], e) * gates[..., j:j + 1]
    h = jnp.einsum("bsd,edf->bsef", x, wi)
    if wg is None:
        h = jnp.square(jax.nn.relu(h))
    else:
        g = jnp.einsum("bsd,edf->bsef", x, wg)
        h = jax.nn.silu(g) * h
    y = jnp.einsum("bsef,efd->bsed", h, wo)
    out = jnp.einsum("bsed,bse->bsd", y, comb.astype(y.dtype))
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, mo.mlp)
    aux = load_balance_loss(probs, ids, mo.n_experts)
    return out, aux, jnp.sum(ids < e, dtype=jnp.int32)
