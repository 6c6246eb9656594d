"""int8-weight matmul with per-channel scales — Pallas TPU kernel.

TPU-native analogue of the paper's 4-bit GGUF/MLX quantised inference: model
weights are stored int8 in HBM (halving HBM traffic, the decode bottleneck)
and dequantised in VMEM right before the MXU matmul.  Grid is (M, N, K)
blocks with the K dimension innermost (sequential), accumulating in an f32
VMEM scratch tile; scales are applied once on the final K block.

Validated against ``ref.quant_matmul_ref`` in interpret mode on the CPU;
compiles for TPU v5e (``tests/test_tpu_compile.py``) and is checked against
the reference on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                     # [bm, bk]
    w = w_ref[...].astype(jnp.float32)                     # [bk, bn]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        scale = s_ref[...].astype(jnp.float32)             # [1, bn]
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "interpret"))
def quant_matmul_pallas(
    x: jax.Array,                   # [M, K] bf16/f32 activations
    w_q: jax.Array,                 # [K, N] int8 weights
    scales: jax.Array,              # [N] f32 per-channel scales
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    n = w_q.shape[1]
    bm = min(block_m, max(m, 8))
    bn = min(block_n, max(n, 128))
    bk = min(block_k, max(k, 128))
    m_p, n_p, k_p = (-(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk)
    if m_p != m or k_p != k:
        x = jnp.pad(x, ((0, m_p - m), (0, k_p - k)))
    if k_p != k or n_p != n:
        w_q = jnp.pad(w_q, ((0, k_p - k), (0, n_p - n)))
    if n_p != n:
        scales = jnp.pad(scales, (0, n_p - n))
    scales2d = scales.reshape(1, n_p)
    nk = k_p // bk

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, nk=nk),
        grid=(m_p // bm, n_p // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_p, n_p), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="quant_matmul",
        interpret=interpret,
    )(x, w_q, scales2d)
    return out[:m, :n]


def quantize_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8 quantisation of a [K, N] weight."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)       # [N]
    scales = jnp.maximum(absmax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scales[None, :]),
                   -127, 127).astype(jnp.int8)
    return w_q, scales


def quantize_kv_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-vector symmetric int8 quantisation over the LAST axis.

    The KV-cache variant of :func:`quantize_int8`: each head-dim vector
    (one position of one kv-head) gets its own scale, so ``x`` of shape
    ``[..., hd]`` returns ``(int8 [..., hd], f32 scales [...])`` with
    ``dequant = q.astype(f32) * scales[..., None]``.  Decode-step writes
    and prefill-commit scatters use this same function so a page holds
    identical bytes regardless of which path materialised it."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scales[..., None]), -127, 127).astype(jnp.int8)
    return q, scales
