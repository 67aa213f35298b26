"""Block-pattern transformer LM: one implementation, every architecture.

A model is a repeating pattern of (mixer, mlp) layer specs (configs/base.py).
Layers are *stacked by period position* and executed with ``lax.scan`` over
period repeats (+ remat on the body), so HLO size and compile time are
independent of depth; a non-periodic sequence is cut into repeated runs
(``ModelConfig.groups``). Covers dense/GQA, MoE, SSM (Mamba2), hybrid
(RecurrentGemma; Nemotron-H, whose blocks hold a mixer or an MLP),
encoder-decoder (Whisper) and VLM-prefix (InternVL) forms.

Distribution is injected through ``ModelRuntime``: sharding-constraint hook,
TP degree (for head/vocab padding layouts), and optional shard_map
implementations for decode attention (flash-decoding) and MoE (ETP).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, MIXER_NONE, MLP_MOE,
                                MLP_NONE, RGLRU, SSD, LayerSpec, ModelConfig)
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import HeadLayout, make_head_layout
from repro.models.layers import (ParamBuilder, apply_mlp, apply_norm,
                                 embed_tokens, init_embeddings, init_mlp,
                                 init_norm, rope, softcap)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ModelRuntime:
    """Execution-environment injection (kept out of ModelConfig so the same
    config lowers for smoke tests, dry-runs, and TPU runs)."""
    tp: int = 1
    attn_impl: str = "blockwise"          # naive|blockwise|pallas|interpret
    rglru_impl: str = "jnp"               # jnp|pallas|interpret
    ssd_impl: str = "jnp"
    moe_fn: Optional[Callable] = None     # shard_map ETP: (p, x, cfg)->(y,aux)
    decode_attn_fn: Optional[Callable] = None
    constrain: Callable = lambda x, kind: x
    remat: bool = True
    remat_policy: str = "full"            # full | dots (save matmul outputs)
    max_seq: int = 4096                   # sizes learned-pos tables / caches
    moe_dp: int = 1                       # 2D expert-parallel slot factor

    def head_layout(self, cfg: ModelConfig) -> HeadLayout:
        return make_head_layout(cfg.n_heads, cfg.n_kv_heads, self.tp)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key: jax.Array, cfg: ModelConfig, spec: LayerSpec,
                rt: ModelRuntime, cross: bool = False,
                causal: bool = True) -> Tuple[Params, Params]:
    pb = ParamBuilder(key, dtype=jnp.bfloat16)
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    if spec.mixer != MIXER_NONE:
        init_norm(pb, "norm1", cfg.d_model, cfg.norm, gemma)
    layout = rt.head_layout(cfg)
    dh = cfg.resolved_head_dim
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        sub = pb.child("mixer")
        attn_mod.init_attention(sub, cfg.d_model, layout, dh,
                                qkv_bias=cfg.qkv_bias,
                                linear_bias=cfg.linear_bias)
    elif spec.mixer == RGLRU:
        rglru_mod.init_rglru(pb.child("mixer"), cfg)
    elif spec.mixer == SSD:
        ssm_mod.init_ssd(pb.child("mixer"), cfg)
    elif spec.mixer != MIXER_NONE:
        raise ValueError(spec.mixer)
    if cfg.post_norms and spec.mixer != MIXER_NONE:
        init_norm(pb, "post_norm1", cfg.d_model, cfg.norm, gemma)
    if cross:
        init_norm(pb, "norm_cross", cfg.d_model, cfg.norm, gemma)
        sub = pb.child("cross")
        attn_mod.init_attention(sub, cfg.d_model, layout, dh,
                                linear_bias=cfg.linear_bias)
    if spec.mlp != MLP_NONE:
        init_norm(pb, "norm2", cfg.d_model, cfg.norm, gemma)
        if spec.mlp == MLP_MOE:
            moe_layout = moe_mod.make_moe_layout(cfg, rt.tp, rt.moe_dp)
            moe_mod.init_moe(pb.child("mlp"), cfg, moe_layout)
        else:
            init_mlp(pb.child("mlp"), cfg.d_model, cfg.d_ff, spec.mlp,
                     cfg.linear_bias)
        if spec.dense_residual:
            init_mlp(pb.child("dense_mlp"), cfg.d_model, cfg.d_ff, "swiglu",
                     cfg.linear_bias)
        if cfg.post_norms:
            init_norm(pb, "post_norm2", cfg.d_model, cfg.norm, gemma)
    return pb.params, pb.specs


def init_params(key: jax.Array, cfg: ModelConfig, rt: ModelRuntime
                ) -> Tuple[Params, Params]:
    """Build (params, logical-axis specs)."""
    pb = ParamBuilder(key, dtype=jnp.bfloat16)
    init_embeddings(pb, cfg.padded_vocab, cfg.d_model)
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    init_norm(pb, "final_norm", cfg.d_model, cfg.norm, gemma)
    if cfg.rope_theta <= 0:  # learned positions (whisper)
        pb.param("pos_embed", (rt.max_seq, cfg.d_model), (None, None),
                 init="normal", scale=0.02)

    def add_stack(parent: ParamBuilder, name: str, period, reps,
                  cross: bool, causal: bool) -> None:
        grp = parent.child(name)
        for i, spec in enumerate(period):
            grp.stacked(
                f"p{i}", reps,
                functools.partial(_init_layer, cfg=cfg, spec=spec, rt=rt,
                                  cross=cross, causal=causal))

    for gi, (period, reps) in enumerate(cfg.groups):
        add_stack(pb, f"group{gi}", period, reps, cross=cfg.enc_dec,
                  causal=True)
    if cfg.enc_dec:
        enc = pb.child("encoder")
        if cfg.rope_theta <= 0:
            enc.param("pos_embed", (rt.max_seq, cfg.d_model), (None, None),
                      init="normal", scale=0.02)
        init_norm(enc, "final_norm", cfg.d_model, cfg.norm, gemma)
        add_stack(enc, "group0", (LayerSpec(mixer=ATTN_GLOBAL,
                                            mlp=cfg.pattern[0].mlp),),
                  cfg.n_enc_layers, cross=False, causal=False)
    return pb.params, pb.specs


def abstract_params(cfg: ModelConfig, rt: ModelRuntime
                    ) -> Tuple[Params, Params]:
    """ShapeDtypeStruct params (no allocation) + specs, for dry-runs.

    Specs are static Python (axis-name tuples); they are captured as a side
    effect of tracing init_params under eval_shape.
    """
    holder = {}

    def go(k):
        p, s = init_params(k, cfg, rt)
        holder["specs"] = s
        return p

    shapes = jax.eval_shape(go, jax.random.PRNGKey(0))
    return shapes, holder["specs"]


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _apply_attn_full(lp: Params, x: jax.Array, spec: LayerSpec,
                     cfg: ModelConfig, rt: ModelRuntime,
                     positions: jax.Array, causal: bool,
                     kv_x: Optional[jax.Array] = None,
                     collect_cache: bool = False):
    layout = rt.head_layout(cfg)
    q, k, v = attn_mod.qkv_project(lp, x, kv_x)
    kv_pos = positions if kv_x is None else \
        jnp.arange(kv_x.shape[1], dtype=positions.dtype)
    q = rope(q, positions, cfg.rotary_theta)
    k = rope(k, kv_pos, cfg.rotary_theta)
    window = cfg.window if spec.mixer == ATTN_LOCAL else 0
    o = attn_mod.attend(q, k, v, causal=causal, window=window,
                        cap=cfg.attn_softcap, impl=rt.attn_impl)
    y = attn_mod.out_project(lp, o, layout.head_mask())
    cache = None
    if collect_cache:
        s_cache = min(window, rt.max_seq) if window else rt.max_seq
        s = k.shape[1]
        kpos = jnp.broadcast_to(kv_pos, k.shape[:2]).astype(jnp.int32)
        if s < s_cache:  # pad to cache size
            pad = s_cache - s
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
        elif s > s_cache:  # keep last window (ring layout: slot = pos % Sc)
            k, v, kpos = (t[:, -s_cache:] for t in (k, v, kpos))
            # entry j holds pos (s - s_cache + j); slot for pos p is p % Sc,
            # so new[i] = old[(i - s % Sc) % Sc]  ==  roll by +(s % Sc).
            roll = s % s_cache
            k = jnp.roll(k, roll, axis=1)
            v = jnp.roll(v, roll, axis=1)
            kpos = jnp.roll(kpos, roll, axis=1)
        cache = {"k": k, "v": v, "kpos": kpos}
    return y, cache


def _decode_attn(rt: ModelRuntime, cache: Params, k_new, v_new, q, pos,
                 *, window: int, cap: float):
    fn = rt.decode_attn_fn
    if fn is None:
        fn = _jnp_decode_attn
    return fn(cache["k"], cache["v"], cache["kpos"], k_new, v_new, q, pos,
              window=window, cap=cap)


def _jnp_decode_attn(k_cache, v_cache, kpos, k_new, v_new, q, pos, *,
                     window: int, cap: float):
    """Single-device decode attention with in-place ring-buffer update."""
    s_cache = k_cache.shape[1]
    if k_new is not None:
        slot = pos % s_cache
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new[:, None], (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new[:, None], (0, slot, 0, 0))
        kpos = jax.lax.dynamic_update_slice(
            kpos, jnp.broadcast_to(pos, (kpos.shape[0], 1)).astype(kpos.dtype),
            (0, slot))
    o = attn_mod.decode_attend(q, k_cache, v_cache, kpos, pos, window=window,
                               cap=cap)
    return o, {"k": k_cache, "v": v_cache, "kpos": kpos}


def _apply_attn_decode(lp: Params, x: jax.Array, spec: LayerSpec,
                       cfg: ModelConfig, rt: ModelRuntime, cache: Params,
                       pos: jax.Array, cross: bool = False):
    layout = rt.head_layout(cfg)
    q = jnp.einsum("bd,dhk->bhk", x[:, 0], lp["wq"])
    if "bq" in lp:
        q = q + lp["bq"]
    positions = pos[None]  # [S=1]
    if cfg.rotary_theta > 0:
        q = rope(q[:, None], positions, cfg.rotary_theta)[:, 0]
    if cross:
        k_new = v_new = None
    else:
        k_new = jnp.einsum("bd,dhk->bhk", x[:, 0], lp["wk"])
        v_new = jnp.einsum("bd,dhk->bhk", x[:, 0], lp["wv"])
        if "bk" in lp:
            k_new, v_new = k_new + lp["bk"], v_new + lp["bv"]
        k_new = rope(k_new[:, None], positions, cfg.rotary_theta)[:, 0]
    window = cfg.window if spec.mixer == ATTN_LOCAL else 0
    o, new_cache = _decode_attn(rt, cache, k_new, v_new, q, pos,
                                window=window, cap=cfg.attn_softcap)
    y = attn_mod.out_project(lp, o[:, None], layout.head_mask())
    return y, new_cache


def apply_layer(lp: Params, x: jax.Array, spec: LayerSpec, cfg: ModelConfig,
                rt: ModelRuntime, *, mode: str, positions=None, cache=None,
                enc_out=None, pos=None, causal: bool = True):
    """mode: full | prefill | decode. Returns (x, cache_out, aux, held):
    ``held`` counts the router's picks that land on the experts held here
    (0 for a layer with no experts or with ``rt.moe_fn``)."""
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    aux = jnp.zeros((), jnp.float32)
    held = jnp.zeros((), jnp.int32)
    cache_out: Dict[str, Any] = {}
    if spec.mixer != MIXER_NONE:
        x, cache_out = _apply_mixer(lp, x, spec, cfg, rt, mode=mode,
                                    positions=positions, cache=cache,
                                    pos=pos, causal=causal)

    if "cross" in lp:  # whisper decoder cross-attention
        h = apply_norm(lp["norm_cross"], x, cfg.norm, gemma, cfg.rms_eps)
        if mode == "decode":
            y, _ = _apply_attn_decode(lp["cross"], h, spec, cfg, rt,
                                      cache["cross"], pos, cross=True)
            cache_out["cross"] = cache["cross"]
        else:
            y, c = _apply_attn_full(lp["cross"], h, spec, cfg, rt, positions,
                                    causal=False, kv_x=enc_out,
                                    collect_cache=(mode == "prefill"))
            if mode == "prefill":
                cache_out["cross"] = {k2: v2 for k2, v2 in c.items()}
        x = x + y

    if spec.mlp != MLP_NONE:
        h = apply_norm(lp["norm2"], x, cfg.norm, gemma, cfg.rms_eps)
        if spec.mlp == MLP_MOE:
            if rt.moe_fn is not None:
                y, a = rt.moe_fn(lp["mlp"], h, cfg)
            else:
                y, a, held = moe_mod.apply_moe_gshard(lp["mlp"], h, cfg)
            aux = aux + a
        else:
            y = apply_mlp(lp["mlp"], h, spec.mlp)
        if spec.dense_residual:
            y = y + apply_mlp(lp["dense_mlp"], h, "swiglu")
        if cfg.post_norms:
            y = apply_norm(lp["post_norm2"], y, cfg.norm, gemma, cfg.rms_eps)
        x = x + y
        x = rt.constrain(x, "resid")
    return x, (cache_out or None), aux, held


def _apply_mixer(lp: Params, x: jax.Array, spec: LayerSpec,
                 cfg: ModelConfig, rt: ModelRuntime, *, mode: str,
                 positions, cache, pos, causal: bool):
    """The pre-normed mixer and its residual. Returns (x, cache_out)."""
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    cache_out: Dict[str, Any] = {}
    h = apply_norm(lp["norm1"], x, cfg.norm, gemma, cfg.rms_eps)

    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        if mode == "decode":
            y, c = _apply_attn_decode(lp["mixer"], h, spec, cfg, rt,
                                      cache["self"], pos)
            cache_out["self"] = c
        else:
            y, c = _apply_attn_full(lp["mixer"], h, spec, cfg, rt, positions,
                                    causal, collect_cache=(mode == "prefill"))
            if mode == "prefill":
                cache_out["self"] = c
    elif spec.mixer == RGLRU:
        st = cache["self"] if mode == "decode" else None
        y, st2 = rglru_mod.apply_rglru(lp["mixer"], h, cfg, state=st,
                                       impl=rt.rglru_impl,
                                       return_state=(mode == "prefill"))
        if mode in ("decode", "prefill"):
            cache_out["self"] = st2
    elif spec.mixer == SSD:
        st = cache["self"] if mode == "decode" else None
        y, st2 = ssm_mod.apply_ssd(lp["mixer"], h, cfg, state=st,
                                   impl=rt.ssd_impl,
                                   return_state=(mode == "prefill"))
        if mode in ("decode", "prefill"):
            cache_out["self"] = st2
    else:
        raise ValueError(spec.mixer)

    if cfg.post_norms:
        y = apply_norm(lp["post_norm1"], y, cfg.norm, gemma, cfg.rms_eps)
    x = x + y
    x = rt.constrain(x, "resid")
    return x, cache_out


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _run_groups(params: Params, cfg: ModelConfig, rt: ModelRuntime,
                x: jax.Array, *, mode: str, positions, enc_out=None,
                cache=None, pos=None, groups=None, prefix: str = "group",
                causal: bool = True):
    """Scan over each (period, repeats) group. Returns (x, caches, aux,
    held). A layer with no cache (no mixer) has no entry in the cache
    tree, nor a group of such layers."""
    groups = groups if groups is not None else cfg.groups
    total_aux = jnp.zeros((), jnp.float32)
    total_held = jnp.zeros((), jnp.int32)
    caches_out = {}
    for gi, (period, reps) in enumerate(groups):
        gp = params[f"{prefix}{gi}"]
        gcache = cache.get(f"{prefix}{gi}") if cache is not None else None

        def body(carry, xs, period=period):
            xc, aux_c, held_c = carry
            lp_all, cache_slice = xs
            new_cache_slice = {}
            for i, spec in enumerate(period):
                c_i = None if cache_slice is None else \
                    cache_slice.get(f"p{i}")
                base = functools.partial(
                    apply_layer, spec=spec, cfg=cfg, rt=rt, mode=mode,
                    positions=positions, enc_out=enc_out, pos=pos,
                    causal=causal)
                call = (lambda lp, xin, c, _f=base: _f(lp, xin, cache=c))
                if rt.remat and mode == "full":
                    pol = None if rt.remat_policy == "full" else \
                        jax.checkpoint_policies \
                        .dots_with_no_batch_dims_saveable
                    call = jax.checkpoint(call, policy=pol)
                xc, c_out, a, n_held = call(lp_all[f"p{i}"], xc, c_i)
                if c_out is not None:
                    new_cache_slice[f"p{i}"] = c_out
                aux_c = aux_c + a
                held_c = held_c + n_held
            return (xc, aux_c, held_c), (new_cache_slice or None)

        (x, total_aux, total_held), ys = jax.lax.scan(
            body, (x, total_aux, total_held), (gp, gcache))
        if ys is not None:
            caches_out[f"{prefix}{gi}"] = ys
    return x, (caches_out or None), total_aux, total_held


def encode(params: Params, cfg: ModelConfig, rt: ModelRuntime,
           frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings [B,S,D]."""
    enc = params["encoder"]
    x = frames.astype(jnp.bfloat16)
    s = x.shape[1]
    if "pos_embed" in enc:
        x = x + enc["pos_embed"][:s][None].astype(x.dtype)
    positions = jnp.arange(s)
    x, _, _, _ = _run_groups(enc, cfg, rt, x, mode="full",
                             positions=positions,
                             groups=((cfg.pattern[:1], cfg.n_enc_layers),),
                             causal=False)
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    return apply_norm(enc["final_norm"], x, cfg.norm, gemma, cfg.rms_eps)


def forward(params: Params, cfg: ModelConfig, rt: ModelRuntime,
            tokens: jax.Array, *, prefix_embeds=None, enc_frames=None,
            mode: str = "full"):
    """Returns (hidden [B,S,D], caches|None, aux). Logits via lm_head()."""
    x = embed_tokens(params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    if "pos_embed" in params:
        x = x + params["pos_embed"][:s][None].astype(x.dtype)
    x = rt.constrain(x, "resid")
    positions = jnp.arange(s)
    enc_out = None
    if cfg.enc_dec:
        assert enc_frames is not None
        enc_out = encode(params, cfg, rt, enc_frames)
    x, caches, aux, _ = _run_groups(params, cfg, rt, x, mode=mode,
                                    positions=positions, enc_out=enc_out)
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    x = apply_norm(params["final_norm"], x, cfg.norm, gemma, cfg.rms_eps)
    return x, caches, aux


def lm_head(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    logits = jnp.einsum("...d,dv->...v", hidden, params["out_embed"])
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, rt: ModelRuntime, batch: int,
               enc_len: int = 0) -> Tuple[Params, Params]:
    """Zero/empty decode caches (+ logical axis specs) for all layers."""
    layout = rt.head_layout(cfg)
    dh = cfg.resolved_head_dim

    def layer_cache(spec: LayerSpec):
        out, specs = {}, {}
        if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
            sc = min(cfg.window, rt.max_seq) if spec.mixer == ATTN_LOCAL \
                else rt.max_seq
            out["self"] = {
                "k": jnp.zeros((batch, sc, layout.kv_heads, dh),
                               jnp.bfloat16),
                "v": jnp.zeros((batch, sc, layout.kv_heads, dh),
                               jnp.bfloat16),
                "kpos": jnp.full((batch, sc), -1, jnp.int32)}
            specs["self"] = {
                "k": ("kv_batch", "kv_seq", None, None),
                "v": ("kv_batch", "kv_seq", None, None),
                "kpos": ("kv_batch", "kv_seq")}
        elif spec.mixer == RGLRU:
            st = rglru_mod.init_rglru_state(cfg, batch)
            out["self"] = st
            specs["self"] = {"h": ("kv_batch", "rglru"),
                             "conv": ("kv_batch", None, "rglru")}
        elif spec.mixer == SSD:
            st = ssm_mod.init_ssd_state(cfg, batch)
            out["self"] = st
            specs["self"] = {"h": ("kv_batch", "ssm_heads", None, None),
                             "conv": ("kv_batch", None, None)}
        if cfg.enc_dec:
            out["cross"] = {
                "k": jnp.zeros((batch, enc_len, layout.kv_heads, dh),
                               jnp.bfloat16),
                "v": jnp.zeros((batch, enc_len, layout.kv_heads, dh),
                               jnp.bfloat16),
                "kpos": jnp.zeros((batch, enc_len), jnp.int32)}
            specs["cross"] = {"k": ("kv_batch", "kv_seq", None, None),
                              "v": ("kv_batch", "kv_seq", None, None),
                              "kpos": ("kv_batch", "kv_seq")}
        return out, specs

    cache, specs = {}, {}
    for gi, (period, reps) in enumerate(cfg.groups):
        g, gs = {}, {}
        for i, spec in enumerate(period):
            c1, s1 = layer_cache(spec)
            if not c1:  # no mixer: no state
                continue
            g[f"p{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (reps,) + a.shape), c1)
            gs[f"p{i}"] = jax.tree.map(
                lambda s: (None,) + tuple(s), s1,
                is_leaf=lambda t: isinstance(t, tuple))
        if g:
            cache[f"group{gi}"] = g
            specs[f"group{gi}"] = gs
    return cache, specs


def decode_step(params: Params, cfg: ModelConfig, rt: ModelRuntime,
                cache: Params, tokens: jax.Array, pos: jax.Array):
    """One token: tokens [B] int32, pos scalar int32.
    Returns (logits [B, V], new_cache, held): ``held`` is how many of this
    step's router picks land on the experts held here, summed over the
    expert layers (an int32 scalar, 0 for a model with no experts)."""
    x = embed_tokens(params, tokens)[:, None]  # [B,1,D]
    if "pos_embed" in params:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], pos, 1, axis=0)[None].astype(x.dtype)
    x, new_cache, _, held = _run_groups(params, cfg, rt, x, mode="decode",
                                        positions=pos[None], cache=cache,
                                        pos=pos)
    gemma = cfg.norm == "rmsnorm" and cfg.post_norms
    x = apply_norm(params["final_norm"], x, cfg.norm, gemma, cfg.rms_eps)
    return lm_head(params, cfg, x[:, 0]), new_cache, held


def prefill(params: Params, cfg: ModelConfig, rt: ModelRuntime,
            tokens: jax.Array, *, prefix_embeds=None, enc_frames=None):
    """Run the prompt, return (last-token logits, decode caches)."""
    hidden, caches, _ = forward(params, cfg, rt, tokens,
                                prefix_embeds=prefix_embeds,
                                enc_frames=enc_frames, mode="prefill")
    return lm_head(params, cfg, hidden[:, -1]), caches
