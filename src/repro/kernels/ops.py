"""Production entry points for every kernel: dispatch Pallas-on-TPU vs
chunked-jnp-on-CPU, with identical semantics (tests pin all paths to ref.py).

On TPU the Pallas kernels are compiled, never interpreted, and there is no
fallback: a kernel that fails to lower raises.  Two calls take the jnp path
on TPU by design: prefill that resumes from cached tokens (per-row
``q_positions``) and cross-attention prefill (``kv_valid``).

The chunked jnp paths are not toys: they are the implementations the dry-run
lowers (this container targets TPU but runs on CPU), so they are written
flash-style — O(S) memory via lax.scan over KV chunks — to keep
``compiled.memory_analysis()`` honest at 32k/524k sequence lengths.

``flash_attention`` exposes two schedules:
  * ``schedule='full'``   — single scan over all KV chunks (baseline; computes
    masked upper-triangle blocks too).
  * ``schedule='causal'`` — per-q-chunk KV extents (python loop over q chunks,
    static slice bounds): skips fully-masked blocks, ~2x fewer attention FLOPs
    at long context.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.expert_gmm import expert_gmm_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas, quantize_int8  # noqa: F401
from repro.kernels.ref import NEG_INF


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------- #
# flash attention (prefill / training)
# --------------------------------------------------------------------------- #
def flash_attention(
    q: jax.Array,                   # [B, Sq, H, D]
    k: jax.Array,                   # [B, Skv, Hkv, D]
    v: jax.Array,                   # [B, Skv, Hkv, D]
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    q_positions: Optional[jax.Array] = None,   # [B, Sq] absolute positions
    kv_valid: Optional[jax.Array] = None,       # [B, Skv] liveness mask
    chunk: int = 1024,
    schedule: str = "full",
) -> jax.Array:
    if _on_tpu() and q_positions is None and kv_valid is None:
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset)
    if (schedule == "causal" and causal and q.shape[1] > chunk
            and q_positions is None and kv_valid is None):
        return _flash_jnp_causal_blocks(q, k, v, window=window,
                                        q_offset=q_offset, chunk=chunk)
    return _flash_jnp(q, k, v, causal=causal, window=window,
                      q_offset=q_offset, q_positions=q_positions,
                      kv_valid=kv_valid, chunk=chunk)


def _flash_jnp(q, k, v, *, causal, window, q_offset, chunk, q_positions=None,
               kv_valid=None):
    """Flash-style chunked attention: scan over KV chunks, running (m,l,acc)."""
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    ck = min(chunk, skv)
    skv_p = -(-skv // ck) * ck
    if kv_valid is None:
        kv_valid = jnp.ones((b, skv), bool)
    if skv_p != skv:
        pad = ((0, 0), (0, skv_p - skv), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, skv_p - skv)))
    nk = skv_p // ck

    qf = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    kf = jnp.moveaxis(k.reshape(b, nk, ck, hkv, d), 1, 0).astype(jnp.float32)
    vf = jnp.moveaxis(v.reshape(b, nk, ck, hkv, d), 1, 0).astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    if q_positions is None:
        qpos = jnp.broadcast_to(q_offset + jnp.arange(sq)[None], (b, sq))
    else:
        qpos = q_positions                                 # [B, Sq]

    def step(carry, inp):
        m, l, acc = carry
        ic, kc, vc, validc = inp                           # [B,ck,Hkv,D] x2, [B,ck]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc) * scale
        kpos = ic * ck + jnp.arange(ck)
        mask = jnp.broadcast_to((kpos[None, None, :] < skv)
                                & validc[:, None, :], (b, sq, ck))
        if causal:
            mask &= kpos[None, None, :] <= qpos[:, :, None]
        if window > 0:
            mask &= kpos[None, None, :] > qpos[:, :, None] - window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vc)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    validf = jnp.moveaxis(kv_valid.reshape(b, nk, ck), 1, 0)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nk), kf, vf, validf))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(b, sq, h, d).astype(q.dtype)


def _flash_jnp_causal_blocks(q, k, v, *, window, q_offset, chunk):
    """Causal-aware schedule: q is split into chunks; each q chunk attends only
    to the KV range its causal (and window) mask permits — static slice bounds,
    so XLA never lowers the masked-out upper triangle."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    cq = min(chunk, sq)
    assert sq % cq == 0, "prefill lengths are multiples of the q chunk"
    outs = []
    for iq in range(sq // cq):
        q_c = jax.lax.slice_in_dim(q, iq * cq, (iq + 1) * cq, axis=1)
        off = q_offset + iq * cq
        hi = min(off + cq, skv)                        # causal upper bound
        lo = 0 if window <= 0 else max(0, off + 1 - window)
        # align to chunk for uniform scan shapes
        lo = (lo // cq) * cq
        hi = -(-hi // cq) * cq
        k_c = jax.lax.slice_in_dim(k, lo, min(hi, skv), axis=1)
        v_c = jax.lax.slice_in_dim(v, lo, min(hi, skv), axis=1)
        outs.append(_flash_jnp(q_c, k_c, v_c, causal=True, window=window,
                               q_offset=off - lo, chunk=cq))
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------------------------------- #
# decode attention (one token over a long cache)
# --------------------------------------------------------------------------- #
def decode_attention(
    q: jax.Array,                   # [B, H, D]
    k_cache: jax.Array,             # [B, S, Hkv, D]
    v_cache: jax.Array,             # [B, S, Hkv, D]
    kv_valid: jax.Array,            # [B, S] bool
    *,
    chunk: int = 2048,
) -> jax.Array:
    if _on_tpu():
        return decode_attention_pallas(q, k_cache, v_cache, kv_valid)
    return _decode_jnp(q, k_cache, v_cache, kv_valid)


def _decode_jnp(q, k_cache, v_cache, kv_valid):
    """One-token attention.  S is a single contraction (no scan): the decode
    cache read is one streaming pass, XLA fuses the masked softmax; memory is
    O(B·H·S) for the scores which at decode batch sizes is small next to the
    cache itself."""
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf,
                        k_cache.astype(jnp.float32)) * scale
    scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    m = scores.max(-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = jnp.where(kv_valid[:, None, None, :], p, 0.0)
    l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgs,bshd->bhgd", p / l, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_attention(
    q: jax.Array,                   # [B, H, D]
    k_pages: jax.Array,             # [L, N, ps, Hkv*D] stacked page arena,
                                    # or one layer's [N, ps, Hkv*D]
    v_pages: jax.Array,             # like k_pages
    page_table: jax.Array,          # [B, P] int32
    positions: jax.Array,           # [B] int32 query-token positions
    layer: Optional[jax.Array] = None,   # a stacked arena's layer to read
    *,
    k_scale: Optional[jax.Array] = None,   # [L, N, ps, Hkv] f32 (int8 arena)
    v_scale: Optional[jax.Array] = None,
    window: int = 0,                # > 0: attend the last ``window`` positions
) -> jax.Array:
    """Decode attention through a page table (see kernels/paged_attention).

    The CPU path gathers layer ``layer``'s pages into the contiguous
    [B, S, Hkv, D] layout and reuses ``_decode_jnp`` — at ``page_size ==
    cache_len`` (fp) the gather is an identity extraction, so the math is
    bit-identical to the dense pool.  On TPU the gather never materialises:
    the Pallas kernel rides the layer and the page indirection on its
    BlockSpec index map."""
    if _on_tpu():
        return paged_attention_pallas(q, k_pages, v_pages, page_table,
                                      positions, layer, k_scale=k_scale,
                                      v_scale=v_scale, window=window)
    b, _, d = q.shape
    p, ps, lanes = page_table.shape[1], *k_pages.shape[-2:]
    s = p * ps
    at = (page_table,) if layer is None else (layer, page_table)

    def gather(pages, scale):
        rows = pages[at].reshape(b, p, ps, lanes // d, d)
        if scale is not None:
            rows = rows.astype(jnp.float32) * scale[at][..., None]
        return rows.reshape(b, s, lanes // d, d)

    idx = jnp.arange(s, dtype=jnp.int32)[None, :]
    return _decode_jnp(q, gather(k_pages, k_scale), gather(v_pages, v_scale),
                       ring_valid(idx, positions, s, window))


def ring_valid(idx: jax.Array, positions: jax.Array, total: int,
               window: int = 0) -> jax.Array:
    """[B, S] cells of a ring of ``total`` cells that a query at
    ``positions`` [B] attends: every cell up to it (all of them once the
    ring has wrapped), or with a window only the cells holding a position
    greater than ``pos - window``."""
    pos = positions[:, None]
    if not window:
        return (idx <= pos) | (pos >= total)
    held = pos - jnp.mod(pos - idx, total)       # the position in each cell
    return (held >= 0) & (held > pos - window)


# --------------------------------------------------------------------------- #
# held experts' grouped MLP
# --------------------------------------------------------------------------- #
def expert_gmm(
    x: jax.Array,                   # [M, D] rows sorted by expert
    w_gate: jax.Array,              # [E, D, F]
    w_up: jax.Array,                # [E, D, F]
    w_down: jax.Array,              # [E, F, D]
    group_sizes: jax.Array,         # [E] int32, sum <= M
) -> jax.Array:
    """Each grouped row's expert SwiGLU MLP, [M, D] in ``x``'s dtype; the
    rows past the groups are undefined (see kernels/expert_gmm).  The CPU
    path is XLA's ragged dot, which computes only the grouped rows too."""
    if _on_tpu():
        return expert_gmm_pallas(x, w_gate, w_up, w_down, group_sizes)
    f32 = jnp.float32
    g = jax.lax.ragged_dot(x, w_gate, group_sizes, preferred_element_type=f32)
    u = jax.lax.ragged_dot(x, w_up, group_sizes, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.ragged_dot(h, w_down, group_sizes,
                              preferred_element_type=f32).astype(x.dtype)


# --------------------------------------------------------------------------- #
# quantised matmul
# --------------------------------------------------------------------------- #
def quant_matmul(x: jax.Array, w_q: jax.Array, scales: jax.Array) -> jax.Array:
    if _on_tpu():
        return quant_matmul_pallas(x, w_q, scales)
    return ref.quant_matmul_ref(x, w_q, scales)


# --------------------------------------------------------------------------- #
# Mamba-2 SSD (state-space duality) — chunked matmul form
# --------------------------------------------------------------------------- #
def _segsum(x: jax.Array) -> jax.Array:
    """x [..., Q] -> [..., Q, Q]; out[i, j] = sum_{k=j+1..i} x[k], -inf above
    the diagonal.  (Stable log-space decay matrix, per arXiv:2405.21060.)"""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, seg, -jnp.inf)


def ssd(
    x: jax.Array,                   # [B, S, H, P]
    dt: jax.Array,                  # [B, S, H] (already softplus'd, > 0)
    a: jax.Array,                   # [H] (negative)
    b_mat: jax.Array,               # [B, S, G, N]
    c_mat: jax.Array,               # [B, S, G, N]
    *,
    init_state: Optional[jax.Array] = None,    # [B, H, P, N]
    chunk: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD: intra-chunk attention-like matmuls (MXU-friendly) plus an
    inter-chunk recurrence over O(S/Q) chunk states.  Matches ``ref.ssd_ref``.

    This IS the paper-advocated TPU-friendly form: the quadratic-in-Q
    intra-chunk term runs on the MXU; the sequential part is S/Q long.
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    q_len = min(chunk, s)
    s_p = -(-s // q_len) * q_len
    if s_p != s:
        # zero-pad the tail: dt=0 gives decay exp(0)=1 and zero input, so the
        # padded steps leave the state untouched; their outputs are dropped.
        pad3 = ((0, 0), (0, s_p - s), (0, 0))
        x = jnp.pad(x, pad3 + ((0, 0),))
        dt = jnp.pad(dt, pad3)
        b_mat = jnp.pad(b_mat, pad3 + ((0, 0),))
        c_mat = jnp.pad(c_mat, pad3 + ((0, 0),))
    s_orig, s = s, s_p
    nc = s // q_len
    rep = h // g

    xf = (x * dt[..., None]).astype(jnp.float32)           # dt-weighted input
    bf = jnp.repeat(b_mat, rep, axis=2).astype(jnp.float32)
    cf = jnp.repeat(c_mat, rep, axis=2).astype(jnp.float32)
    da = (dt.astype(jnp.float32) * a.astype(jnp.float32)[None, None, :])

    def r(t, last):                                        # [B,S,...] -> [B,nc,Q,...]
        return t.reshape((bsz, nc, q_len) + last)

    xc, bc, cc = r(xf, (h, p)), r(bf, (h, n)), r(cf, (h, n))
    dac = jnp.transpose(r(da, (h,)), (0, 3, 1, 2))         # [B,H,nc,Q]
    cs = jnp.cumsum(dac, axis=-1)                          # [B,H,nc,Q]

    # 1) intra-chunk (diagonal blocks): attention-like masked matmul
    l_mat = jnp.exp(_segsum(dac))                          # [B,H,nc,Q,Q]
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", cc, bc, l_mat, xc)

    # 2) per-chunk output states
    decay_states = jnp.exp(cs[..., -1:] - cs)              # [B,H,nc,Q]
    states = jnp.einsum("bcshn,bhcs,bcshp->bchpn", bc, decay_states, xc)

    # 3) inter-chunk recurrence over nc chunk states
    chunk_decay = jnp.exp(cs[..., -1])                     # [B,H,nc]
    s0 = (jnp.zeros((bsz, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(prev, inp):
        dec, st = inp                                      # [B,H], [B,H,P,N]
        new = dec[..., None, None] * prev + st
        return new, prev                                   # emit state *entering* chunk

    (final_state, prev_states) = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, -1, 0), jnp.moveaxis(states, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)          # [B,nc,H,P,N]

    # 4) inter-chunk contribution
    state_decay = jnp.exp(cs)                              # [B,H,nc,Q]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", cc, prev_states, state_decay)

    y = (y_diag + y_off).reshape(bsz, s, h, p)[:, :s_orig].astype(x.dtype)
    return y, final_state


def ssd_decode_step(
    x: jax.Array,                   # [B, H, P] one token
    dt: jax.Array,                  # [B, H]
    a: jax.Array,                   # [H]
    b_mat: jax.Array,               # [B, G, N]
    c_mat: jax.Array,               # [B, G, N]
    state: jax.Array,               # [B, H, P, N]
) -> tuple[jax.Array, jax.Array]:
    """Single-step SSD recurrence for decode (O(1) per token)."""
    h = x.shape[1]
    rep = h // b_mat.shape[1]
    bf = jnp.repeat(b_mat, rep, axis=1).astype(jnp.float32)
    cf = jnp.repeat(c_mat, rep, axis=1).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * a.astype(jnp.float32)[None, :])[..., None, None]
    upd = (dtf[..., None] * x.astype(jnp.float32))[..., None] * bf[:, :, None, :]
    new_state = decay * state.astype(jnp.float32) + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, cf)
    return y.astype(x.dtype), new_state
