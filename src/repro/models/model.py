"""Unified model builder for all assigned architecture families.

A model is a pure-pytree param dict plus three entry points:

  * ``apply(mode='train')``   — logits over a full sequence
  * ``apply(mode='prefill')`` — logits + a filled decode cache
  * ``apply(mode='decode')``  — one token per batch slot (continuous batching:
                                per-slot positions), updated cache

Layer stacking: ``ModelConfig.layer_kinds()`` is factored into
``prefix + pattern × repeats``; the repeated pattern's params are stacked on a
leading axis and executed with ``lax.scan`` (one HLO body for 9–60 layer
groups — keeps compile time and HLO size flat across the 0.5B–398B range).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs import ModelConfig, split_kind
from repro.distributed import constrain
from repro.models import layers
from repro.models.layers import Params


def find_pattern(kinds: Tuple[str, ...]) -> Tuple[Tuple[str, ...], Tuple[str, ...], int]:
    """Factor ``kinds`` as prefix + pattern*repeats with minimal pattern."""
    n = len(kinds)
    best = (kinds, (), 0)
    best_cost = n
    for plen in range(0, min(n, 4)):
        rest = kinds[plen:]
        m = len(rest)
        for pat in range(1, m + 1):
            if m % pat == 0 and rest == rest[:pat] * (m // pat):
                cost = plen + pat
                if cost < best_cost:
                    best, best_cost = (kinds[:plen], rest[:pat], m // pat), cost
                break
    return best


def _layer_dff(cfg: ModelConfig, kind: str) -> Optional[int]:
    if cfg.moe and split_kind(kind)[0] == "attn" and cfg.moe.dense_d_ff:
        return cfg.moe.dense_d_ff
    return None


class ModelOutput(NamedTuple):
    logits: jax.Array
    cache: Optional[Params]
    aux_loss: jax.Array
    # [2] int32 over the layers: token-expert pairs the held experts
    # computed, and held experts that computed any (models whose expert
    # layers hold a share of the experts; else None)
    moe_counts: Optional[jax.Array] = None


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ #
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        kinds = cfg.layer_kinds()
        prefix, pattern, repeats = find_pattern(kinds)
        k_embed, k_head, k_pre, k_grp, k_front = jax.random.split(key, 5)
        has_cross = cfg.family == "audio"

        params: Params = {
            "embed": layers._dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dt),
            "final_ln": jnp.ones((cfg.d_model,), dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers._dense_init(
                k_head, (cfg.d_model, cfg.vocab_size), dt)

        pre_keys = jax.random.split(k_pre, max(len(prefix), 1))
        params["prefix_layers"] = [
            layers.init_layer(pre_keys[i], kind, cfg,
                              d_ff=_layer_dff(cfg, kind), has_cross=has_cross)
            for i, kind in enumerate(prefix)
        ]
        grp_keys = jax.random.split(k_grp, max(len(pattern), 1))
        block: Dict[str, Params] = {}
        for i, kind in enumerate(pattern):
            ks = jax.random.split(grp_keys[i], repeats)
            block[f"pos{i}"] = jax.vmap(
                lambda kk, kind=kind: layers.init_layer(
                    kk, kind, cfg, d_ff=_layer_dff(cfg, kind),
                    has_cross=has_cross))(ks)
        params["block"] = block

        if cfg.vision is not None:
            params["vision_proj"] = layers._dense_init(
                k_front, (cfg.vision.embed_dim, cfg.d_model), dt)
        if cfg.audio is not None:
            ke1, ke2 = jax.random.split(k_front)
            params["audio_proj"] = layers._dense_init(
                ke1, (cfg.audio.embed_dim, cfg.d_model), dt)
            enc_keys = jax.random.split(ke2, cfg.audio.encoder_layers)
            params["encoder"] = jax.vmap(
                lambda kk: layers.init_layer(kk, "attn", cfg))(enc_keys)
            params["enc_ln"] = jnp.ones((cfg.d_model,), dt)
        return params

    def init_shapes(self) -> Params:
        """Param ShapeDtypeStructs without allocation (dry-run)."""
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # ------------------------------------------------------------------ #
    def _encode_audio(self, params: Params, frames: jax.Array,
                      attn_schedule: str, unroll_scan: bool = False) -> jax.Array:
        cfg = self.cfg
        x = frames.astype(params["audio_proj"].dtype) @ params["audio_proj"]
        x = constrain(x, "batch", None, None)
        pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

        def body(x, p):
            x, _, _, _ = layers.apply_layer(p, "attn", x, cfg=cfg, mode="train",
                                         positions=pos, cache=None,
                                         attn_schedule=attn_schedule)
            return x, None

        if unroll_scan:
            for g in range(cfg.audio.encoder_layers):
                x, _ = body(x, jax.tree.map(lambda a: a[g], params["encoder"]))
        else:
            x, _ = jax.lax.scan(body, x, params["encoder"])
        return layers.rmsnorm(x, params["enc_ln"], cfg.rms_eps)

    # ------------------------------------------------------------------ #
    def apply(
        self,
        params: Params,
        tokens: jax.Array,                   # [B, S] int32
        *,
        mode: str,                           # train | prefill | decode
        positions: Optional[jax.Array] = None,      # [B, S]
        cache: Optional[Params] = None,
        image_embeds: Optional[jax.Array] = None,   # [B, T_img, De]
        audio_frames: Optional[jax.Array] = None,   # [B, F, De]
        window: Optional[int] = None,
        attn_schedule: str = "full",
        remat: bool = False,
        resume: bool = False,            # prefill continues past cached tokens
        cross_cached: bool = False,      # content-cache hit: xk/xv from cache
        ctx_valid: Optional[jax.Array] = None,      # [B, T_ctx] media liveness
        seq_valid: Optional[jax.Array] = None,      # [B, S] token liveness —
                                         # right-padding mask for batched /
                                         # chunked prefill (masked KV writes,
                                         # identity SSM updates, no MoE
                                         # capacity use)
        logits_mode: str = "full",       # 'full' | 'last' (prefill: last only)
        page_table: Optional[jax.Array] = None,  # [B, P] paged-KV decode:
                                         # cache k/v leaves are page arenas
        slot_active: Optional[jax.Array] = None,  # [B] live mask (paged)
        unroll_scan: bool = False,       # python loop instead of lax.scan —
                                         # exact XLA cost_analysis (which
                                         # counts a while-loop body ONCE);
                                         # used by the dry-run roofline pass
    ) -> ModelOutput:
        cfg = self.cfg
        b, s = tokens.shape
        window_eff = cfg.sliding_window if window is None else window
        kinds = cfg.layer_kinds()
        prefix, pattern, repeats = find_pattern(kinds)

        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = jnp.take(params["embed"], tokens, axis=0)
        x = constrain(x, "batch", None, None)

        context = None
        if cfg.vision is not None and mode != "decode" and not cross_cached:
            assert image_embeds is not None, "vlm prefill/train needs image embeds"
            context = image_embeds.astype(x.dtype) @ params["vision_proj"]
            context = constrain(context, "batch", None, None)
        if cfg.audio is not None and mode != "decode" and not cross_cached:
            assert audio_frames is not None, "audio prefill/train needs frames"
            context = self._encode_audio(params, audio_frames, attn_schedule,
                                         unroll_scan)

        aux_total = jnp.zeros((), jnp.float32)
        moe_counts = None

        def add_counts(total, counts):
            return counts if total is None else total + counts

        new_prefix_caches = []
        for i, kind in enumerate(prefix):
            sub = cache["prefix"][i] if cache is not None else None
            x, c, aux, counts = layers.apply_layer(
                params["prefix_layers"][i], kind, x, cfg=cfg, mode=mode,
                positions=positions, cache=sub, window=window_eff,
                context=context, attn_schedule=attn_schedule,
                resume=resume, cross_cached=cross_cached, ctx_valid=ctx_valid,
                seq_valid=seq_valid, page_table=page_table,
                slot_active=slot_active)
            new_prefix_caches.append(c)
            aux_total += aux
            if counts is not None:
                moe_counts = add_counts(moe_counts, counts)

        def group_body(carry, xs):
            x, arena = carry
            p_slice, c_slice, layer = xs
            aux_g = jnp.zeros((), jnp.float32)
            counts_g = None
            c_out: Dict[str, Any] = {}
            arena_out: Dict[str, Any] = {}
            for i, kind in enumerate(pattern):
                pos = f"pos{i}"
                sub = c_slice[pos] if c_slice is not None else None
                if pos in arena:
                    sub = {**sub, **arena[pos]}
                x, c, aux, counts = layers.apply_layer(
                    p_slice[pos], kind, x, cfg=cfg, mode=mode,
                    positions=positions, cache=sub, window=window_eff,
                    context=context, attn_schedule=attn_schedule,
                    resume=resume, cross_cached=cross_cached,
                    ctx_valid=ctx_valid, seq_valid=seq_valid,
                    page_table=page_table, slot_active=slot_active,
                    layer=layer if pos in arena else None)
                if pos in arena:
                    arena_out[pos] = {n: c.pop(n) for n in arena[pos]}
                if c is not None:
                    c_out[pos] = c
                aux_g += aux
                if counts is not None:
                    counts_g = add_counts(counts_g, counts)
            return (x, arena_out), (c_out or None, aux_g, counts_g)

        body = jax.checkpoint(group_body) if (remat and mode == "train") else group_body
        cache_xs = cache["block"] if cache is not None else None
        # paged decode: the stacked page arenas ride the carry, each layer
        # writes its cells in place at its index and the kernel reads that
        # layer alone — as xs/ys the scan would slice every layer's arena
        # out, stack the updated one back, and the decode loop would copy
        # the whole stack into its own carry
        arena: Dict[str, Any] = {}
        if page_table is not None and cache_xs is not None:
            arena, cache_xs = _split_arena(cache_xs)
        if pattern and unroll_scan:
            ys = []
            for g in range(repeats):
                p_g = jax.tree.map(lambda a: a[g], params["block"])
                with tracing.scope("kv_arena"):
                    c_g = jax.tree.map(lambda a: a[g], cache_xs)
                (x, arena), y = body((x, arena),
                                     (p_g, c_g, g if arena else None))
                ys.append(y)
            caches_g = [y[0] for y in ys]
            aux_total += sum(y[1] for y in ys)
            if ys[0][2] is not None:
                moe_counts = add_counts(moe_counts, sum(y[2] for y in ys))
            with tracing.scope("kv_arena"):
                new_block_cache = (jax.tree.map(
                    lambda *leaves: jnp.stack(leaves), *caches_g)
                    if caches_g[0] is not None else None)
        elif pattern:
            layer_xs = jnp.arange(repeats, dtype=jnp.int32) if arena else None
            (x, arena), (new_block_cache, aux_g, counts_g) = jax.lax.scan(
                body, (x, arena), (params["block"], cache_xs, layer_xs))
            aux_total += aux_g.sum()
            if counts_g is not None:
                moe_counts = add_counts(moe_counts, counts_g.sum(0))
        else:
            new_block_cache = None
        if arena:
            new_block_cache = {pos: {**sub, **arena.get(pos, {})}
                               for pos, sub in new_block_cache.items()}

        with tracing.scope("head"):
            x = layers.rmsnorm(x, params["final_ln"], cfg.rms_eps)
            if logits_mode == "last":    # prefill: only the final position's
                x = x[:, -1:]            # logits are needed — skip S·D·V work
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = x @ head
            logits = constrain(logits, "batch", None, "vocab")

        new_cache = None
        if mode == "prefill":
            new_cache = {"prefix": new_prefix_caches, "block": new_block_cache}
        elif mode == "decode":
            new_cache = {"prefix": new_prefix_caches, "block": new_block_cache}
        return ModelOutput(logits, new_cache, aux_total, moe_counts)


def _split_arena(block: Params) -> Tuple[Params, Params]:
    """A paged block cache's stacked page-arena leaves (per position that
    has any) and the rest of it."""
    arena = {pos: {n: a for n, a in sub.items() if n in layers.ARENA_KEYS}
             for pos, sub in block.items()}
    rest = {pos: {n: a for n, a in sub.items()
                  if n not in layers.ARENA_KEYS}
            for pos, sub in block.items()}
    return {pos: a for pos, a in arena.items() if a}, rest


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# --------------------------------------------------------------------------- #
# decode-cache construction
# --------------------------------------------------------------------------- #
def _layer_cache_shape(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                       ctx_len: int, dtype) -> Params:
    out: Params = {}
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    kind = split_kind(kind)[0]
    if kind in ("attn", "moe"):
        out["k"] = jnp.zeros((batch, cache_len, hkv, hd), dtype)
        out["v"] = jnp.zeros((batch, cache_len, hkv, hd), dtype)
        if cfg.family == "audio":
            out["xk"] = jnp.zeros((batch, ctx_len, hkv, hd), dtype)
            out["xv"] = jnp.zeros((batch, ctx_len, hkv, hd), dtype)
    if kind == "xattn":
        out["xk"] = jnp.zeros((batch, ctx_len, hkv, hd), dtype)
        out["xv"] = jnp.zeros((batch, ctx_len, hkv, hd), dtype)
    if kind.startswith("ssm"):
        d_in, nheads, d_conv = layers._ssm_dims(cfg)
        out["conv"] = jnp.zeros((batch, cfg.ssm.conv_width - 1, d_conv), dtype)
        out["state"] = jnp.zeros((batch, nheads, cfg.ssm.head_dim,
                                  cfg.ssm.state_dim), jnp.float32)
    return out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, *,
               ctx_len: int = 0, dtype=None) -> Params:
    """Zeroed decode cache.  ``cache_len`` is the KV ring size (sliding-window
    archs pass the window size); ``ctx_len`` the cross-attention context
    length (image tokens / encoder frames)."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    kinds = cfg.layer_kinds()
    prefix, pattern, repeats = find_pattern(kinds)
    cache: Params = {"prefix": [
        _layer_cache_shape(cfg, kind, batch, cache_len, ctx_len, dtype)
        for kind in prefix
    ]}
    block: Dict[str, Any] = {}
    for i, kind in enumerate(pattern):
        one = _layer_cache_shape(cfg, kind, batch, cache_len, ctx_len, dtype)
        block[f"pos{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape), one)
    cache["block"] = block or None
    return cache


def cache_shapes(cfg: ModelConfig, batch: int, cache_len: int, *,
                 ctx_len: int = 0, dtype=None) -> Params:
    """ShapeDtypeStruct version of init_cache (dry-run, no allocation)."""
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, cache_len,
                          ctx_len=ctx_len, dtype=dtype))
