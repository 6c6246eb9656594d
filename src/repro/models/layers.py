"""Layer library: every block the 10 assigned architectures need.

Pure functions over pytree params (no framework dependency): each block has an
``init_*`` returning a param dict and an ``apply_*`` running one of three
modes:

  * ``train``   — full sequence, no cache IO
  * ``prefill`` — full sequence, writes a decode cache
  * ``decode``  — one token per slot, per-slot positions (continuous batching:
                  every slot sits at a different depth), ring-buffer writes
                  when the cache is a sliding window.

Sharding is expressed through :func:`repro.distributed.constrain` logical
axes; with no mesh active it's a no-op.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs import ModelConfig, RopeScaling, split_kind
from repro.distributed import constrain
from repro.kernels import ops
from repro.kernels.quant_matmul import quantize_kv_int8

Params = Dict[str, Any]

#: cache-dict keys that live in the page arena under paged KV (everything
#: else is dense per slot)
ARENA_KEYS = ("k", "v", "k_scale", "v_scale")


# --------------------------------------------------------------------------- #
# primitives
# --------------------------------------------------------------------------- #
def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) > 1 else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * w.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_gated(x: jax.Array, z: jax.Array, w: jax.Array,
                  eps: float = 1e-5) -> jax.Array:
    """Mamba-2 gated norm: RMSNorm(x * silu(z))."""
    return rmsnorm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), w, eps)


def yarn_frequencies(head_dim: int, theta: float,
                     scaling: RopeScaling) -> np.ndarray:
    """YaRN's rotation frequencies (Hugging Face ``_compute_yarn_parameters``,
    ``truncate`` on): dimensions that turn more than ``beta_fast`` times over
    the original context keep theirs, those under ``beta_slow`` times are
    divided by ``factor``, with a linear ramp between.  Constants: computed
    once when the program is traced."""
    half = head_dim // 2
    plain = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim)

    def dim_of(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    return (plain / scaling.factor * ramp + plain * (1.0 - ramp)
            ).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[RopeScaling] = None) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (per-token absolute positions).
    ``scaling``: YaRN frequencies, cos and sin times its attention factor."""
    b, s, h, d = x.shape
    half = d // 2
    if scaling is None:
        freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_frequencies(d, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs                  # [B,S,half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]     # [B,S,1,half]
    if scaling is not None:
        cos, sin = cos * scaling.attention_factor, sin * scaling.attention_factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------- #
# MLP (SwiGLU)
# --------------------------------------------------------------------------- #
def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, (d_model, d_ff), dtype),
        "w_up": _dense_init(k2, (d_model, d_ff), dtype),
        "w_down": _dense_init(k3, (d_ff, d_model), dtype),
    }


def apply_mlp(p: Params, x: jax.Array) -> jax.Array:
    with tracing.scope("mlp"):
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        h = constrain(h, "batch", *((None,) * (h.ndim - 2)), "tp")
        return h @ p["w_down"]


# --------------------------------------------------------------------------- #
# self-attention (GQA, RoPE, optional sliding window)
# --------------------------------------------------------------------------- #
def init_attn(key, cfg: ModelConfig, *, cross: bool = False,
              kv_dim: Optional[int] = None) -> Params:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kdim = kv_dim if kv_dim is not None else d
    keys = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "ln": jnp.ones((d,), dt),
        "wq": _dense_init(keys[0], (d, h * hd), dt),
        "wk": _dense_init(keys[1], (kdim, hkv * hd), dt),
        "wv": _dense_init(keys[2], (kdim, hkv * hd), dt),
        "wo": _dense_init(keys[3], (h * hd, d), dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    return p


def _qkv(p: Params, x: jax.Array, ctx: jax.Array, cfg: ModelConfig):
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"] + (p.get("bq", 0.0))
    k = ctx @ p["wk"] + (p.get("bk", 0.0))
    v = ctx @ p["wv"] + (p.get("bv", 0.0))
    q = constrain(q, "batch", None, "tp").reshape(b, -1, h, hd)
    k = constrain(k, "batch", None, "tp").reshape(b, -1, hkv, hd)
    v = constrain(v, "batch", None, "tp").reshape(b, -1, hkv, hd)
    return q, k, v


def apply_self_attn(
    p: Params,
    x: jax.Array,                    # [B, S, D]
    *,
    cfg: ModelConfig,
    mode: str,
    positions: jax.Array,            # [B, S] (decode: S=1)
    cache: Optional[Params] = None,  # {'k','v'}: [B, Sc, Hkv, hd]
    window: int = 0,
    attn_schedule: str = "full",
    resume: bool = False,            # prefill continues from cached tokens
    seq_valid: Optional[jax.Array] = None,   # [B, S] prefix mask (padding off)
    page_table: Optional[jax.Array] = None,  # [B, P] paged-KV decode only
    slot_active: Optional[jax.Array] = None,  # [B] live mask (paged decode)
    layer: Optional[jax.Array] = None,  # paged decode: the arena leaves are
                                     # stacked [L, N, ...] and this is the
                                     # layer's index into them
    rope_scaling: Optional[RopeScaling] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    b, s, _ = x.shape
    h = rmsnorm(x, p["ln"], cfg.rms_eps)
    q, k, v = _qkv(p, h, h, cfg)
    q = apply_rope(q, positions, cfg.rope_theta, rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, rope_scaling)

    if mode == "prefill" and resume:
        # continuation after a prefix-cache hit or an earlier prefill chunk:
        # append new KV to the cache, then attend over the whole cache with
        # absolute query positions — new tokens see the cached prefix (no
        # ring wrap in engine caches).  Per-row slot indices support batched
        # prefill waves where every row sits at a different resume offset;
        # ``seq_valid`` rows write their cells back unchanged, so
        # right-padding leaves no trace in the cache (the final cache is
        # bit-identical however the prompt was bucketed or chunked).
        sc = cache["k"].shape[1]
        with tracing.scope("kv_arena"):
            bidx = jnp.arange(b)[:, None]
            slots = (positions % sc).astype(jnp.int32)                  # [B,S]
            if seq_valid is not None:
                keep = seq_valid[..., None, None]
                k = jnp.where(keep, k, cache["k"][bidx, slots])
                v = jnp.where(keep, v, cache["v"][bidx, slots])
            kc = cache["k"].at[bidx, slots].set(k)
            vc = cache["v"].at[bidx, slots].set(v)
        with tracing.scope("attention_kernel"):
            out = ops.flash_attention(q, kc, vc, causal=True, window=window,
                                      q_positions=positions)
        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        out = constrain(out, "batch", None, "tp")
        return x + out @ p["wo"], {"k": kc, "v": vc}

    if mode == "decode" and page_table is not None:
        # paged KV: cache holds the global page arena [N, ps, Hkv * hd]
        # (every KV head of a cell side by side) and the slot's cells are
        # reached through page_table.  The engine's ensure_decode_capacity
        # guarantees the write target is an exclusively-owned page; frozen
        # slots (slot_active False) redirect their write to a reserved
        # per-slot trash cell so shared/retired pages are never touched (the
        # paged analogue of the dense path's select_cache_slots ring-cell
        # repair).  Inside the layer scan the arena leaves are the whole
        # stack [L, N, ps, Hkv * hd]: the cells are written in place at
        # ``layer`` and the kernel reads that layer out of the stack itself.
        # No sharding constrain on the arena: paged + distributed KV is not
        # supported.
        kc, vc = cache["k"], cache["v"]
        ksc, vsc = cache.get("k_scale"), cache.get("v_scale")
        ps = kc.shape[-2]
        sc = ps * page_table.shape[1]
        bidx = jnp.arange(b)
        lead = () if layer is None else (layer,)
        live = slot_active if slot_active is not None else jnp.ones((b,), bool)
        outs = []
        # S > 1 is speculative verification under paging: per-position
        # writes and attention in a static Python loop (one compiled graph).
        # A cell whose row is frozen OR beyond the slot's staged drafts
        # (``seq_valid`` False) redirects to the slot's reserved trash cell —
        # real pages of rejected/invalid positions are written only for
        # accepted drafts, and the verifier's rollback
        # (paged_kv.restore_page_cells) restores the rest.  Same wrap guard
        # as the dense branch (engine stages zero drafts on wrap).
        for j in range(s):
            pos_j = positions[:, j]
            with tracing.scope("kv_arena"):
                ring = (pos_j % sc).astype(jnp.int32)
                page = page_table[bidx, ring // ps]
                off = ring % ps
                ok = live if seq_valid is None else live & seq_valid[:, j]
                page = jnp.where(ok, page, (bidx // ps).astype(page.dtype))
                off = jnp.where(ok, off, (bidx % ps).astype(off.dtype))
                at = lead + (page, off)
                if ksc is not None:                         # int8 arena
                    kq, ks_j = quantize_kv_int8(k[:, j])
                    vq, vs_j = quantize_kv_int8(v[:, j])
                    kc = kc.at[at].set(kq.reshape(b, -1))
                    vc = vc.at[at].set(vq.reshape(b, -1))
                    ksc = ksc.at[at].set(ks_j)
                    vsc = vsc.at[at].set(vs_j)
                else:
                    kc = kc.at[at].set(k[:, j].reshape(b, -1))
                    vc = vc.at[at].set(v[:, j].reshape(b, -1))
            with tracing.scope("attention_kernel"):
                outs.append(ops.paged_attention(
                    q[:, j], kc, vc, page_table, pos_j, layer,
                    k_scale=ksc, v_scale=vsc, window=window))
        out = jnp.stack(outs, axis=1)                               # [B,S,H,hd]
        new_cache = ({"k": kc, "v": vc} if ksc is None else
                     {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc})
    elif mode == "decode" and s > 1:
        # speculative verification: S = k_draft + 1 candidate tokens per slot
        # run as ONE batched decode forward.  Writes take the prefill-resume
        # masked-restore trick (``seq_valid`` cells beyond a slot's staged
        # drafts — and every cell of frozen slots — are written back with
        # their previous values, leaving no trace); attention stays the
        # per-position ``ops.decode_attention`` op so each row's j = 0 query
        # is bit-identical to the S = 1 step (the flash kernel normalises in
        # a different order — see kernels/ops.py — so flash here would break
        # the greedy-ngram == off bit-exactness contract).  The engine's
        # wrap guard (core/spec_decode.py) stages zero drafts for any slot
        # whose ring has wrapped, because a wrapped ring's validity mask is
        # all-ones and query j would otherwise see the cells written for
        # j' > j in this same pass.
        kc, vc = cache["k"], cache["v"]
        sc = kc.shape[1]
        with tracing.scope("kv_arena"):
            bidx2 = jnp.arange(b)[:, None]
            slots = (positions % sc).astype(jnp.int32)                  # [B,S]
            if seq_valid is not None:
                keep = seq_valid[..., None, None]
                k = jnp.where(keep, k, kc[bidx2, slots])
                v = jnp.where(keep, v, vc[bidx2, slots])
            kc = kc.at[bidx2, slots].set(k)
            vc = vc.at[bidx2, slots].set(v)
            kc = constrain(kc, "kv_batch", "kv_seq", None, None)
            vc = constrain(vc, "kv_batch", "kv_seq", None, None)
        idx = jnp.arange(sc)[None, :]
        outs = []
        for j in range(s):
            pos_j = positions[:, j]
            valid_j = ops.ring_valid(idx, pos_j, sc, window)
            with tracing.scope("attention_kernel"):
                outs.append(ops.decode_attention(q[:, j], kc, vc, valid_j))
        out = jnp.stack(outs, axis=1)                                   # [B,S,H,hd]
        new_cache = {"k": kc, "v": vc}
    elif mode == "decode":
        kc, vc = cache["k"], cache["v"]
        sc = kc.shape[1]
        with tracing.scope("kv_arena"):
            slot = (positions[:, 0] % sc).astype(jnp.int32)             # [B]
            bidx = jnp.arange(b)
            kc = kc.at[bidx, slot].set(k[:, 0])
            vc = vc.at[bidx, slot].set(v[:, 0])
            kc = constrain(kc, "kv_batch", "kv_seq", None, None)
            vc = constrain(vc, "kv_batch", "kv_seq", None, None)
        pos = positions[:, 0]
        idx = jnp.arange(sc)[None, :]
        valid = ops.ring_valid(idx, pos, sc, window)                    # ring
        with tracing.scope("attention_kernel"):
            out = ops.decode_attention(q[:, 0], kc, vc, valid)[:, None]  # [B,1,H,hd]
        new_cache = {"k": kc, "v": vc}
    else:
        with tracing.scope("attention_kernel"):
            out = ops.flash_attention(q, k, v, causal=True, window=window,
                                      schedule=attn_schedule)
        new_cache = None
        if mode == "prefill":
            sc = cache["k"].shape[1]
            take = min(s, sc)
            with tracing.scope("kv_arena"):
                src_k = k[:, s - take:]
                src_v = v[:, s - take:]
                slots = ((s - take + jnp.arange(take)) % sc).astype(jnp.int32)
                kc = cache["k"].at[:, slots].set(src_k)
                vc = cache["v"].at[:, slots].set(src_v)
                kc = constrain(kc, "kv_batch", "kv_seq", None, None)
                vc = constrain(vc, "kv_batch", "kv_seq", None, None)
            new_cache = {"k": kc, "v": vc}

    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    out = constrain(out, "batch", None, "tp")
    return x + out @ p["wo"], new_cache


# --------------------------------------------------------------------------- #
# cross-attention (VLM image layers; audio enc-dec decoder)
# --------------------------------------------------------------------------- #
def init_xattn(key, cfg: ModelConfig, *, gated: bool) -> Params:
    p = init_attn(key, cfg, cross=True)
    if gated:
        p["xgate_attn"] = jnp.zeros((), jnp.dtype(cfg.dtype))
    return p


def apply_cross_attn(
    p: Params,
    x: jax.Array,                    # [B, S, D]
    *,
    cfg: ModelConfig,
    mode: str,
    context: Optional[jax.Array],    # [B, T, D] (prefill/train); None in decode
    cache: Optional[Params] = None,  # {'xk','xv'}: [B, T, Hkv, hd]
    gated: bool = False,
    cross_cached: bool = False,      # content-cache hit: reuse cached xk/xv
    ctx_valid: Optional[jax.Array] = None,     # [B, T] context liveness
) -> Tuple[jax.Array, Optional[Params]]:
    b, s, _ = x.shape
    h = rmsnorm(x, p["ln"], cfg.rms_eps)
    if mode == "prefill" and cross_cached:
        # Alg.3 cache hit: the per-layer cross KV was restored from the
        # content cache — skip the projection of the vision/audio context.
        xk, xv = cache["xk"], cache["xv"]
        q = (h @ p["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
        out = ops.flash_attention(q, xk, xv, causal=False, kv_valid=ctx_valid)
        new_cache = {"xk": xk, "xv": xv}
    elif mode == "decode":
        xk, xv = cache["xk"], cache["xv"]
        q = (h @ p["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
        valid = (jnp.ones((b, xk.shape[1]), bool) if ctx_valid is None
                 else ctx_valid)
        if s > 1:
            # speculative verification: cross-attention context is
            # position-independent, so every candidate shares one mask
            out = jnp.stack([ops.decode_attention(q[:, j], xk, xv, valid)
                             for j in range(s)], axis=1)
        else:
            out = ops.decode_attention(q[:, 0], xk, xv, valid)[:, None]
        new_cache = cache
    else:
        q, xk, xv = _qkv(p, h, context, cfg)
        out = ops.flash_attention(q, xk, xv, causal=False, kv_valid=ctx_valid)
        new_cache = {"xk": xk, "xv": xv} if mode == "prefill" else None
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    out = constrain(out, "batch", None, "tp")
    out = out @ p["wo"]
    if gated:
        out = jnp.tanh(p["xgate_attn"].astype(jnp.float32)).astype(out.dtype) * out
    return x + out, new_cache


# --------------------------------------------------------------------------- #
# Mixture of Experts (GShard-style capacity routing)
# --------------------------------------------------------------------------- #
def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d = cfg.d_model
    f = m.expert_d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 5)
    e = m.num_held                  # the router still scores every expert
    p = {
        "ln": jnp.ones((d,), dt),
        "router": _dense_init(keys[0], (d, m.num_experts), jnp.float32),
        "we_gate": _dense_init(keys[1], (e, d, f), dt, fan_in=d),
        "we_up": _dense_init(keys[2], (e, d, f), dt, fan_in=d),
        "we_down": _dense_init(keys[3], (e, f, d), dt, fan_in=f),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(keys[4], d, f * m.num_shared_experts, dt)
    return p


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig,
              seq_valid: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y, aux_load_balance_loss).

    ``seq_valid`` [B, S] routes right-padding tokens to the trash slot and
    keeps them out of the capacity cumsum, so padding never displaces a real
    token from an expert.  (With ``capacity_factor > 0`` the *cap itself*
    still depends on the static call shape, so capacity-dropping MoE is
    exact only in no-drop mode — the tests/exactness configuration.)"""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.num_experts, m.experts_per_token
    if m.capacity_factor <= 0:          # no-drop mode (tests / exactness)
        cap = t * k
    else:
        cap = max(8, int(math.ceil(t * k / e * m.capacity_factor)))

    h = rmsnorm(x, p["ln"], cfg.rms_eps)
    flat = h.reshape(t, d)
    logits = flat.astype(jnp.float32) @ p["router"]                     # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                                # [T,k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac * probs.mean(0)) * m.load_balance_coef

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32).reshape(t * k, e)
    if seq_valid is not None:
        tok_valid = jnp.repeat(seq_valid.reshape(t), k)                 # [T*k]
        onehot = onehot * tok_valid[:, None].astype(onehot.dtype)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    my_pos = jnp.sum(pos * onehot, axis=-1)                             # [T*k]
    expert = idx.reshape(t * k)
    keep = my_pos < cap
    if seq_valid is not None:
        keep = keep & tok_valid
    slot = jnp.where(keep, expert * cap + my_pos, e * cap)              # drop → trash

    xr = jnp.broadcast_to(flat[:, None], (t, k, d)).reshape(t * k, d)
    buf = jnp.zeros((e * cap + 1, d), flat.dtype).at[slot].set(xr)
    hbuf = buf[:-1].reshape(e, cap, d)
    hbuf = constrain(hbuf, "expert", "batch", None)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hbuf, p["we_gate"]))
    u = jnp.einsum("ecd,edf->ecf", hbuf, p["we_up"])
    h2 = constrain(g * u, "expert", "batch", "e_out")
    o = jnp.einsum("ecf,efd->ecd", h2, p["we_down"])
    o = constrain(o, "expert", "batch", None)
    obuf = jnp.concatenate([o.reshape(e * cap, d),
                            jnp.zeros((1, d), o.dtype)], axis=0)
    y = obuf[slot] * gates.reshape(t * k, 1).astype(o.dtype)
    y = y.reshape(t, k, d).sum(axis=1)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], flat)
    return x + y.reshape(b, s, d), aux


def apply_moe_held(p: Params, x: jax.Array, cfg: ModelConfig,
                   seq_valid: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """One chip's share of an expert layer: x [B, S, D] -> (x + the part
    of the layer's output that the held experts ``cfg.moe.held`` give,
    [2] int32: token-expert pairs they computed, held experts that computed
    any).

    Routing is over every expert, in float32: softmax, top-k, the k gates
    divided by their sum.  The pairs whose expert is held are sorted by
    expert and run as one grouped matmul (``ops.expert_gmm``) that computes
    those rows alone: no capacity, no drop, nothing for the experts held
    elsewhere.  A token whose experts are all held elsewhere gets zero, and
    right-padding tokens (``seq_valid`` False) route nowhere."""
    m = cfg.moe
    b, s, d = x.shape
    t, k = b * s, m.experts_per_token
    lo, hi = m.held
    with tracing.scope("moe"):
        h = rmsnorm(x, p["ln"], cfg.rms_eps)
        flat = h.reshape(t, d)
        logits = jnp.dot(flat.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)          # [T,E]
        gates, expert = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        gates = gates / gates.sum(-1, keepdims=True)
        expert = expert.reshape(t * k)
        held = (expert >= lo) & (expert < hi)
        if seq_valid is not None:
            held &= jnp.repeat(seq_valid.reshape(t), k)
        group = jnp.where(held, expert - lo, hi - lo)    # elsewhere: last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=hi - lo + 1)[:-1].astype(jnp.int32)
        out = ops.expert_gmm(flat[order // k], p["we_gate"], p["we_up"],
                             p["we_down"], sizes)       # [T*k, D], sorted
        out = out[jnp.argsort(order)].reshape(t, k, d)  # pair order again
        out = jnp.where(held.reshape(t, k, 1), out.astype(jnp.float32), 0.0)
        y = jnp.sum(out * gates[..., None], axis=1)     # float32, no MXU
        counts = jnp.stack([held.sum(), (sizes > 0).sum()]).astype(jnp.int32)
        return x + y.astype(x.dtype).reshape(b, s, d), counts


# --------------------------------------------------------------------------- #
# Mamba-2 block (SSD)
# --------------------------------------------------------------------------- #
def _ssm_dims(cfg: ModelConfig):
    ssm = cfg.ssm
    d_in = ssm.expand * cfg.d_model
    nheads = d_in // ssm.head_dim
    d_conv = d_in + 2 * ssm.ngroups * ssm.state_dim
    return d_in, nheads, d_conv


def init_ssm(key, cfg: ModelConfig) -> Params:
    ssm = cfg.ssm
    d = cfg.d_model
    d_in, nheads, d_conv = _ssm_dims(cfg)
    keys = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln": jnp.ones((d,), dt),
        "in_proj": _dense_init(keys[0], (d, 2 * d_in + 2 * ssm.ngroups
                                         * ssm.state_dim + nheads), dt),
        "conv_w": _dense_init(keys[1], (ssm.conv_width, d_conv), dt,
                              fan_in=ssm.conv_width),
        "conv_b": jnp.zeros((d_conv,), dt),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "ssm_norm": jnp.ones((d_in,), dt),
        "out_proj": _dense_init(keys[3], (d_in, d), dt),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array],
                 lengths: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d.  xbc [B,S,C]; w [W,C]; returns (out, new_state
    [B, W-1, C] = trailing inputs).  ``lengths`` [B] gathers each row's carry
    window ending at its last *valid* input instead of the physical tail, so
    right-padded rows carry exactly the state an unpadded run would."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)                            # [B,S+W-1,C]
    out = sum(xp[:, i:i + xbc.shape[1]] * w[i][None, None]
              for i in range(width))
    if lengths is None:
        new_state = xp[:, xp.shape[1] - (width - 1):]
    else:
        idx = lengths[:, None] + jnp.arange(width - 1)[None, :]         # [B,W-1]
        new_state = jnp.take_along_axis(xp, idx[..., None], axis=1)
    return jax.nn.silu(out + b[None, None]), new_state


def apply_ssm(
    p: Params,
    x: jax.Array,                    # [B, S, D]
    *,
    cfg: ModelConfig,
    mode: str,
    cache: Optional[Params] = None,  # {'conv': [B,W-1,Dc], 'state': [B,H,P,N]}
    resume: bool = False,            # prefill continues from cached state
    seq_valid: Optional[jax.Array] = None,   # [B, S] prefix mask (padding off)
) -> Tuple[jax.Array, Optional[Params]]:
    ssm = cfg.ssm
    b, s, d = x.shape
    d_in, nheads, d_conv = _ssm_dims(cfg)
    g, n, pdim = ssm.ngroups, ssm.state_dim, ssm.head_dim

    h = rmsnorm(x, p["ln"], cfg.rms_eps)
    zxbcdt = h @ p["in_proj"]
    zxbcdt = constrain(zxbcdt, "batch", None, "tp")
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_conv]
    dt_raw = zxbcdt[..., d_in + d_conv:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    if seq_valid is not None:
        # padded steps become identity updates: decay exp(a*0)=1 and a zero
        # dt-weighted input (the same trick ops.ssd plays for its own tail),
        # so the carried SSM state never sees right-padding
        dt = jnp.where(seq_valid[..., None], dt, 0.0)
    a = -jnp.exp(p["a_log"])

    conv_state = cache["conv"] if cache is not None else None
    use_state = mode == "decode" or (mode == "prefill" and resume)
    lengths = (seq_valid.sum(-1).astype(jnp.int32)
               if seq_valid is not None else None)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                 conv_state if use_state else None,
                                 lengths=lengths)
    x_ssm = xbc[..., :d_in].reshape(b, s, nheads, pdim)
    b_mat = xbc[..., d_in:d_in + g * n].reshape(b, s, g, n)
    c_mat = xbc[..., d_in + g * n:].reshape(b, s, g, n)

    if mode == "decode":
        init = cache["state"]
        y, new_state = ops.ssd_decode_step(
            x_ssm[:, 0], dt[:, 0], a, b_mat[:, 0], c_mat[:, 0], init)
        y = y[:, None]
        new_cache = {"conv": new_conv, "state": new_state}
    else:
        chunk = min(ssm.chunk_size, s)
        init = cache["state"] if (cache is not None and use_state) else None
        y, final_state = ops.ssd(x_ssm, dt, a, b_mat, c_mat, chunk=chunk,
                                 init_state=init)
        new_cache = ({"conv": new_conv, "state": final_state}
                     if mode == "prefill" else None)

    y = y + (p["d_skip"][None, None, :, None]
             * x_ssm.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(b, s, d_in)
    y = rmsnorm_gated(y, z, p["ssm_norm"], cfg.rms_eps)
    y = constrain(y, "batch", None, "tp")
    return x + y @ p["out_proj"], new_cache


# --------------------------------------------------------------------------- #
# composed layers (one per ModelConfig.layer_kinds entry)
# --------------------------------------------------------------------------- #
def init_layer(key, kind: str, cfg: ModelConfig, *, d_ff: Optional[int] = None,
               has_cross: bool = False) -> Params:
    keys = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p: Params = {}
    kind = split_kind(kind)[0]
    if kind in ("attn", "moe"):
        p["attn"] = init_attn(keys[0], cfg)
        if has_cross:                          # audio decoder: +cross to encoder
            p["cross"] = init_xattn(keys[3], cfg, gated=False)
    if kind == "xattn":
        p["cross"] = init_xattn(keys[0], cfg, gated=True)
        p["xgate_ffn"] = jnp.zeros((), dt)
    if kind.startswith("ssm"):
        p["ssm"] = init_ssm(keys[0], cfg)
    if kind.endswith("moe"):
        p["moe"] = init_moe(keys[1], cfg)
    elif cfg.d_ff > 0:
        p["ffn_ln"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = init_mlp(keys[2], cfg.d_model, d_ff or cfg.d_ff, dt)
    return p


def apply_layer(
    p: Params,
    kind: str,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    mode: str,
    positions: jax.Array,
    cache: Optional[Params],
    window: int = 0,
    context: Optional[jax.Array] = None,    # image tokens / encoder output
    attn_schedule: str = "full",
    resume: bool = False,
    cross_cached: bool = False,
    ctx_valid: Optional[jax.Array] = None,
    seq_valid: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    slot_active: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Params], jax.Array, Optional[jax.Array]]:
    """Returns (x, new_cache, aux_loss, moe_counts).  ``layer`` indexes
    stacked paged arena leaves (see :func:`apply_self_attn`).  A kind with
    a window of its own (``"moe@1024"``) attends by it, with the plain
    rotation; the others attend by ``window`` and take ``cfg.rope_scaling``.
    ``moe_counts``: what a layer of held experts computed (see
    :func:`apply_moe_held`; None for every other layer)."""
    kind, own_window = split_kind(kind)
    aux = jnp.zeros((), jnp.float32)
    moe_counts = None
    new_cache: Params = {}
    if "attn" in p:
        sub = ({k: cache[k] for k in ARENA_KEYS if k in cache}
               if cache else None)
        x, c = apply_self_attn(p["attn"], x, cfg=cfg, mode=mode,
                               positions=positions, cache=sub,
                               window=own_window or window,
                               attn_schedule=attn_schedule, resume=resume,
                               seq_valid=seq_valid, page_table=page_table,
                               slot_active=slot_active, layer=layer,
                               rope_scaling=(None if own_window
                                             else cfg.rope_scaling))
        if c:
            new_cache.update(c)
    if "cross" in p and kind != "xattn":    # audio decoder cross-attn
        sub = {k: cache[k] for k in ("xk", "xv")} if cache else None
        x, c = apply_cross_attn(p["cross"], x, cfg=cfg, mode=mode,
                                context=context, cache=sub, gated=False,
                                cross_cached=cross_cached, ctx_valid=ctx_valid)
        if c:
            new_cache.update(c)
    if kind == "xattn":
        sub = {k: cache[k] for k in ("xk", "xv")} if cache else None
        x, c = apply_cross_attn(p["cross"], x, cfg=cfg, mode=mode,
                                context=context, cache=sub, gated=True,
                                cross_cached=cross_cached, ctx_valid=ctx_valid)
        if c:
            new_cache.update(c)
    if "ssm" in p:
        sub = {k: cache[k] for k in ("conv", "state")} if cache else None
        x, c = apply_ssm(p["ssm"], x, cfg=cfg, mode=mode, cache=sub,
                         resume=resume, seq_valid=seq_valid)
        if c:
            new_cache.update(c)
    if "moe" in p and cfg.moe.held is not None:
        x, moe_counts = apply_moe_held(p["moe"], x, cfg, seq_valid=seq_valid)
    elif "moe" in p:
        x, aux = apply_moe(p["moe"], x, cfg, seq_valid=seq_valid)
    elif "ffn" in p:
        h = rmsnorm(x, p["ffn_ln"], cfg.rms_eps)
        out = apply_mlp(p["ffn"], h)
        if kind == "xattn":
            out = jnp.tanh(p["xgate_ffn"].astype(jnp.float32)).astype(out.dtype) * out
        x = x + out
    return x, (new_cache or None), aux, moe_counts
