"""Single-token GQA decode attention through a page table — Pallas TPU kernel.

The paged variant of :mod:`repro.kernels.decode_attention`: K/V live in one
global page arena ``[N, page_size, Hkv, D]`` shared by every sequence, and a
per-slot page table ``[B, P]`` maps each sequence's logical cache blocks to
arena pages.  The kernel rides the page indirection on the BlockSpec index
map: the page table and query positions arrive as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so grid step ``(b, ip)`` DMA's arena
page ``page_table[b, ip]`` directly into VMEM — the gather costs nothing
over a contiguous layout, because block fetches were always index-mapped.

Grid is ``(batch, pages)`` with the page axis innermost and sequential;
flash (m, l, acc) statistics carry across pages in VMEM scratch exactly as
in the dense kernel.  Each step takes every KV head of its page (the arena
is viewed as ``[N, ps, Hkv*D]``, heads side by side on the lane axis), so
the block's last two dimensions meet the TPU tiling rule.  Cell validity is
computed in-kernel from the query position (ring semantics: a fully wrapped
cache attends to every cell), so no [B, S] mask array is materialised.

A windowed layer (static ``window`` > 0) attends the positions greater than
``pos - window``.  Pages wholly before the window are neither fetched nor
computed: the index map clamps those steps to the first page in the window,
so the pipeline sees one block index and issues no copy, and ``pl.when``
skips their compute.  ``window=0`` (full attention) traces the kernel as it
was before windows existed.

Int8 arenas add per-(position, kv-head) scale operands ``[N, ps, Hkv]``
(block ``(1, ps, Hkv)``); pages are dequantised in-register after the VMEM
load (bandwidth is spent on int8 bytes, the matmul runs in f32).

Validated against ``ref.paged_attention_ref`` in interpret mode on the CPU;
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


def grid(batch: int, pages_per_slot: int) -> tuple:
    """The kernel's grid: one step per (slot, page the slot can hold),
    whether the page holds live context or not."""
    return (batch, pages_per_slot)


def first_page(pos, window: int, ps: int, total: int):
    """The first page a windowed query at ``pos`` attends (0 once the ring
    has wrapped: every page may then hold a position in the window)."""
    return jnp.where(pos >= total, 0, jnp.maximum(pos - window + 1, 0) // ps)


def _paged_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, *rest, scale,
                  num_pages, ps, hkv, d, int8, window):
    if int8:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b_ = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ring validity from the query position (2D iota: TPU requirement)
    pos = pos_ref[b_]
    total = num_pages * ps
    idx = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    attend = functools.partial(_attend_page, q_ref, k_ref, v_ref, rest,
                               scale=scale, hkv=hkv, d=d, int8=int8)
    if window:
        # the position each cell holds: the latest one <= pos on its ring cell
        r = jax.lax.rem(pos, total)
        at = idx + (pos - r) - jnp.where(idx > r, total, 0)
        live = (at >= 0) & (at > pos - window)             # [1, ps] bool

        @pl.when(ip >= first_page(pos, window, ps, total))
        def _attend():
            attend(live)
    else:
        attend((idx <= pos) | (pos >= total))              # [1, ps] bool

    @pl.when(ip == num_pages - 1)
    def _finish():
        for h in range(hkv):
            l = jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def _attend_page(q_ref, k_ref, v_ref, rest, live, *, scale, hkv, d,
                 int8):
    """Fold one page into every KV head's running (m, l, acc)."""
    if int8:
        ks_ref, vs_ref, _, acc_ref, m_ref, l_ref = rest
    else:
        _, acc_ref, m_ref, l_ref = rest
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)                # [G, D]
        k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)   # [ps, D]
        v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)   # [ps, D]
        if int8:
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, NEG_INF)                    # [G, ps]

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


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(
    q: jax.Array,                   # [B, H, D]
    k_pages: jax.Array,             # [N, ps, Hkv, D] page arena
    v_pages: jax.Array,             # [N, ps, Hkv, D]
    page_table: jax.Array,          # [B, P] int32
    positions: jax.Array,           # [B] int32 query-token positions
    *,
    k_scale: jax.Array | None = None,   # [N, ps, Hkv] f32 (int8 arena)
    v_scale: jax.Array | None = None,
    window: int = 0,                # > 0: attend the last ``window`` positions
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    n, ps, hkv = k_pages.shape[:3]
    p = page_table.shape[1]
    g = h // hkv
    int8 = k_scale is not None
    qg = q.reshape(b, hkv, g, d)

    # index maps see (grid idxs..., *scalar_prefetch_refs); the page hop is
    # pt[b_, ip] — the whole point of the kernel
    def kv_map(b_, ip, pt, pos):
        if window:      # steps before the window repeat its first page: the
            # pipeline sees no new block and copies nothing
            ip = jnp.maximum(ip, first_page(pos[b_], window, ps, p * ps))
        return (pt[b_, ip], 0, 0)

    def q_map(b_, ip, pt, pos):
        return (b_, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, g, d), q_map),
        pl.BlockSpec((1, ps, hkv * d), kv_map),
        pl.BlockSpec((1, ps, hkv * d), kv_map),
    ]
    operands = [qg, k_pages.reshape(n, ps, hkv * d),
                v_pages.reshape(n, ps, hkv * d)]
    if int8:
        in_specs += [pl.BlockSpec((1, ps, hkv), kv_map),
                     pl.BlockSpec((1, ps, hkv), kv_map)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(_paged_kernel, scale=1.0 / (d ** 0.5),
                               num_pages=p, ps=ps, hkv=hkv, d=d, int8=int8,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid(b, p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(page_table.astype(jnp.int32), positions.astype(jnp.int32), *operands)
    return out.reshape(b, h, d)
