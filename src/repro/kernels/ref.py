"""Pure-jnp oracles for every kernel in this package.

These are the *semantic definitions*: naive, O(S^2)-materialising, easy to
audit.  Tests assert the Pallas kernels (interpret=True on CPU) and the
chunked-jnp production paths in ``ops.py`` match these to tolerance across
shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, H, D] by repeating each kv head H/Hkv times."""
    b, s, hkv, d = k.shape
    rep = num_heads // hkv
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def flash_attention_ref(
    q: jax.Array,                   # [B, Sq, H, D]
    k: jax.Array,                   # [B, Skv, Hkv, D]
    v: jax.Array,                   # [B, Skv, Hkv, D]
    *,
    causal: bool = True,
    window: int = 0,                # 0 = unlimited; else sliding window size
    q_offset: int = 0,              # global position of q[0] (for chunked prefill)
    bias: jax.Array | None = None,  # [B or 1, H or 1, Sq, Skv] additive
) -> jax.Array:
    """Naive softmax attention oracle (GQA via kv-head repetition)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k = repeat_kv(k, h)
    v = repeat_kv(v, h)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(sq)[:, None]          # [Sq, 1]
    kpos = jnp.arange(skv)[None, :]                    # [1, Skv]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,                   # [B, H, D] one new token per sequence
    k_cache: jax.Array,             # [B, S, Hkv, D]
    v_cache: jax.Array,             # [B, S, Hkv, D]
    kv_valid: jax.Array,            # [B, S] bool — which cache slots are live
) -> jax.Array:
    """Single-token GQA decode over a (possibly ring-buffered) KV cache."""
    b, h, d = q.shape
    k = repeat_kv(k_cache, h)
    v = repeat_kv(v_cache, h)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(kv_valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def quant_matmul_ref(
    x: jax.Array,                   # [M, K] bf16/f32
    w_q: jax.Array,                 # [K, N] int8
    scales: jax.Array,              # [N] f32 per-output-channel scales
) -> jax.Array:
    """int8-weight matmul oracle: dequantise then matmul in f32."""
    w = w_q.astype(jnp.float32) * scales[None, :].astype(jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def ssd_ref(
    x: jax.Array,                   # [B, S, H, P]   inputs per head
    dt: jax.Array,                  # [B, S, H]      softplus'd step sizes
    a: jax.Array,                   # [H]            negative decay rates (A < 0)
    b_mat: jax.Array,               # [B, S, G, N]   input gates (groups G)
    c_mat: jax.Array,               # [B, S, G, N]   output gates
    *,
    init_state: jax.Array | None = None,   # [B, H, P, N]
) -> tuple[jax.Array, jax.Array]:
    """Mamba-2 SSD oracle: literal sequential recurrence (arXiv:2405.21060 eq. 16).

    h_t = exp(dt_t * a) * h_{t-1} + dt_t * x_t ⊗ b_t ;  y_t = h_t · c_t
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    bx = jnp.repeat(b_mat, rep, axis=2).astype(jnp.float32)   # [B,S,H,N]
    cx = jnp.repeat(c_mat, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    state = (jnp.zeros((bsz, h, p, n), jnp.float32) if init_state is None
             else init_state.astype(jnp.float32))

    def step(state, inp):
        xt, dtt, bt, ct = inp                                 # [B,H,P],[B,H],[B,H,N],[B,H,N]
        decay = jnp.exp(dtt * af[None, :])[..., None, None]   # [B,H,1,1]
        upd = (dtt[..., None, None] * xt[..., None]) * bt[:, :, None, :]
        state = decay * state + upd                           # [B,H,P,N]
        y = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(bx, 1, 0), jnp.moveaxis(cx, 1, 0))
    state, ys = jax.lax.scan(step, state, xs)
    y = jnp.moveaxis(ys, 0, 1)                                # [B,S,H,P]
    return y.astype(x.dtype), state


def paged_attention_ref(
    q: jax.Array,                   # [B, H, D] one new token per sequence
    k_pages: jax.Array,             # [N, ps, Hkv*D] global page arena
    v_pages: jax.Array,             # [N, ps, Hkv*D]
    page_table: jax.Array,          # [B, P] int32 page id per table entry
    positions: jax.Array,           # [B] int32 position of the query token
    *,
    k_scale: jax.Array | None = None,   # [N, ps, Hkv] f32 (int8 arenas)
    v_scale: jax.Array | None = None,
    window: int = 0,                # > 0: only the last ``window`` positions
) -> jax.Array:
    """Decode attention through a page table: gather each sequence's pages
    into a contiguous [B, P*ps, Hkv, D] view (the arena holds every KV head
    of a cell side by side; int8 pages are dequantised with their
    per-(position, head) scales), mask cells past the query position (ring
    semantics: a fully wrapped cache attends to everything) or, with a
    window, cells whose position is ``pos - window`` or earlier, and run the
    dense decode oracle."""
    b, _, d = q.shape
    p = page_table.shape[1]
    ps, lanes = k_pages.shape[1:]
    s = p * ps

    def gather(pages, scale):
        rows = pages[page_table].reshape(b, p, ps, lanes // d, d)
        if scale is not None:
            rows = rows.astype(jnp.float32) * scale[page_table][..., None]
        return rows.reshape(b, s, lanes // d, d)

    k = gather(k_pages, k_scale)
    v = gather(v_pages, v_scale)
    idx = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = positions[:, None]
    if window:
        held = pos - jnp.mod(pos - idx, s)       # the position in each cell
        valid = (held >= 0) & (held > pos - window)
    else:
        valid = (idx <= pos) | (pos >= s)
    return decode_attention_ref(q, k, v, valid)


def expert_gmm_ref(
    x: jax.Array,                   # [M, D] rows sorted by expert
    w_gate: jax.Array,              # [E, D, F]
    w_up: jax.Array,                # [E, D, F]
    w_down: jax.Array,              # [E, F, D]
    group_sizes: jax.Array,         # [E] int32, sum <= M
) -> jax.Array:
    """Every expert's SwiGLU MLP on every row (a dense einsum), each row
    keeping its own group's output: rows ``[start_e, end_e)`` belong to
    expert ``e``, rows past the last group get zero.  [M, D] float32; the
    hidden activation is rounded to ``x``'s dtype, as the kernel rounds it
    before the down projection."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(x.shape[0])[None, :]
    mine = (rows >= (ends - group_sizes)[:, None]) & (rows < ends[:, None])
    xf = x.astype(jnp.float32)
    g = jnp.einsum("md,edf->emf", xf, w_gate.astype(jnp.float32))
    u = jnp.einsum("md,edf->emf", xf, w_up.astype(jnp.float32))
    h = (jax.nn.silu(g) * u).astype(x.dtype).astype(jnp.float32)
    out = jnp.einsum("emf,efd->emd", h, w_down.astype(jnp.float32))
    return jnp.sum(jnp.where(mine[..., None], out, 0.0), axis=0)
