"""Single-token GQA decode attention over a long KV cache — Pallas TPU kernel.

The decode hot path is memory-bound: it streams the whole KV cache once per
step.  The kernel tiles the cache sequence dimension into VMEM blocks
(grid-innermost, sequential) and carries flash (m, l, acc) statistics in
scratch.  A validity mask supports both plain length-masking (cache longer
than the sequence) and ring buffers (sliding-window caches where slot
liveness is non-contiguous).

TPU tiling: a block's last two dimensions must be multiples of (8, 128) or
span the whole array.  Each grid step therefore takes every KV head of a
sequence block at once: the wrapper views the cache ``[B, S, Hkv, D]`` as
``[B, S, Hkv*D]`` (heads side by side on the lane axis) and the kernel
slices one head's lanes per query group.  The mask rides as an int32
``[B, 1, S]`` row, so its block ``(1, 1, bk)`` meets the same rule.

Validated against ``ref.decode_attention_ref`` in interpret mode on the CPU;
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


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, nk, hkv, d):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = valid_ref[0] != 0                               # [1, bk]
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)                # [G, D]
        k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)   # [bk, D]
        v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)   # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, NEG_INF)                    # [G, bk]

        m_prev = m_ref[h][:, :1]                           # [G, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(live, p, 0.0)
        l_cur = l_ref[h][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h] = jnp.broadcast_to(m_cur, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_cur, l_ref.shape[1:])

    @pl.when(ik == nk - 1)
    def _finish():
        for h in range(hkv):
            l = jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_pallas(
    q: jax.Array,                   # [B, H, D]
    k_cache: jax.Array,             # [B, S, Hkv, D]
    v_cache: jax.Array,             # [B, S, Hkv, D]
    kv_valid: jax.Array,            # [B, S] bool
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    bk = min(block_k, s)
    s_p = -(-s // bk) * bk
    k_cache = k_cache.reshape(b, s, hkv * d)
    v_cache = v_cache.reshape(b, s, hkv * d)
    valid = kv_valid.astype(jnp.int32).reshape(b, 1, s)
    if s_p != s:
        pad = ((0, 0), (0, s_p - s), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        valid = jnp.pad(valid, ((0, 0), (0, 0), (0, s_p - s)))
    nk = s_p // bk
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_decode_kernel, scale=1.0 / (d ** 0.5),
                               nk=nk, hkv=hkv, d=d)
    out = pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda b_, ik: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bk, hkv * d), lambda b_, ik: (b_, ik, 0)),
            pl.BlockSpec((1, bk, hkv * d), lambda b_, ik: (b_, ik, 0)),
            pl.BlockSpec((1, 1, bk), lambda b_, ik: (b_, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), lambda b_, ik: (b_, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
        ],
        name="decode_attention",
        interpret=interpret,
    )(qg, k_cache, v_cache, valid)
    return out.reshape(b, h, d)
