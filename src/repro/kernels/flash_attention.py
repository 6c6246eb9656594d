"""Blocked causal flash attention — Pallas TPU kernel (prefill hot path).

TPU-native design (not a CUDA port): the grid's innermost dimension iterates
KV blocks *sequentially* per core, carrying the running (m, l, acc) flash
statistics in VMEM scratch — the canonical TPU grid-carried-accumulator
pattern.  Q/K/V blocks are staged HBM→VMEM by BlockSpec; the (bq×d)·(d×bk)
score matmul and the (bq×bk)·(bk×d) PV matmul are MXU-shaped (blocks default
to 128×128, the MXU tile).

Supports causal masking, sliding windows, GQA (kv-head indexing in the
BlockSpec index_map — no materialised head repetition), and chunked prefill
via ``q_offset``.

TPU tiling: a block's last two dimensions must be multiples of (8, 128) or
span the whole array, so the wrapper moves the head axis ahead of the
sequence axis (``[B, H, S, D]``) and every block is ``(1, 1, rows, D)``.

Validated against ``ref.flash_attention_ref`` in interpret mode on the CPU;
compiles for TPU v5e (``tests/test_tpu_compile.py``) and is checked against
the reference on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import NEG_INF


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, nk, bq, bk, q_offset, skv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # block-level early-out: skip fully-masked KV blocks (upper triangle /
    # outside the sliding window / padding)
    block_live = ik * bk < skv
    if causal:
        block_live &= (ik * bk) <= (q_offset + iq * bq + bq - 1)
    if window > 0:
        block_live &= (ik * bk + bk - 1) > (q_offset + iq * bq - window)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)                # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kpos < skv
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                               # [bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])                    # [bq, bk]
        l_cur = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,                   # [B, Sq, H, D]
    k: jax.Array,                   # [B, Skv, Hkv, D]
    v: jax.Array,                   # [B, Skv, Hkv, D]
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(skv, 8))
    sq_p = -(-sq // bq) * bq
    skv_p = -(-skv // bk) * bk
    # head-major: blocks (1, 1, rows, d) meet the TPU tiling rule
    q = jnp.swapaxes(q, 1, 2)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        pad = ((0, 0), (0, 0), (0, skv_p - skv), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq, nk = sq_p // bq, skv_p // bk

    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (d ** 0.5), causal=causal, window=window,
        nk=nk, bq=bq, bk=bk, q_offset=q_offset, skv=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, iq, ik, rep=rep: (b_, h_ // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, iq, ik, rep=rep: (b_, h_ // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),      # acc
            pltpu.VMEM((bq, 128), jnp.float32),    # running max m
            pltpu.VMEM((bq, 128), jnp.float32),    # running sum l
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out[:, :, :sq], 1, 2)
