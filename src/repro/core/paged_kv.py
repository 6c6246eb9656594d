"""Paged KV pool: a global page arena + per-slot page tables + COW sharing.

Replaces the dense per-slot ring of :class:`~repro.core.kv_cache.SlotKVPool`
for the decode batch (DESIGN_paged_kv.md).  KV memory becomes one arena of
``num_pages`` fixed-size pages per attention layer — ``k``/``v`` leaves are
``[N, page_size, Hkv, hd]`` (stacked block layers ``[L, N, ...]``) — and each
slot owns an ordered list of page ids mirrored into a device-resident page
table ``[max_batch, pages_per_slot]`` that the compiled decode block threads
through attention (:func:`repro.kernels.ops.paged_attention`).  Non-KV leaves
(``conv``/``state``/``xk``/``xv``) stay dense per-slot: they are O(1) per
slot, paging them buys nothing.

Sharing is copy-on-write at page granularity: a prefix-cache hit, an
eviction snapshot, or an ``n>1`` fan-out maps already-materialised pages
into the new owner's table with a refcount bump — no bytes move — and a
page is copied (split) only when a writer needs a cell of a page someone
else can still read.  Who may write is a host-side invariant, not a device
check: **a page is writable iff its refcount is 1**, and the engine calls
:meth:`PagedKVPool.ensure_decode_capacity` before every decode block so the
pages the block will write are exclusively owned by then.

The prefill pipeline stays dense (batch=1 rows, unchanged bit-for-bit);
pagination happens at the commit boundary (:meth:`insert_many` scatters the
final dense row into the slot's freshly-allocated pages, skipping shared
ones) and at publication (:meth:`read` gathers pages back to a dense row).

Bit-exactness: with ``page_size == cache_len`` and fp KV, every page table
is the identity mapping ``slot -> reserved + slot`` and the arena *is* the
dense pool plus a reserved prefix — the decode block computes the same
cells in the same order, so greedy decode reproduces the dense pool
bit-for-bit (tests/test_paged_kv.py pins this).

Int8 KV (``kv_dtype="int8"``): pages are stored quantised per (position,
kv-head) with the absmax/127 rule of ``kernels/quant_matmul.quantize_int8``;
scales ride in ``k_scale``/``v_scale`` arena leaves ``[N, page_size, Hkv]``
(f32) and are applied inside the attention op.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core.kv_cache import tree_bytes
from repro.kernels.quant_matmul import quantize_kv_int8
from repro.models.layers import ARENA_KEYS
from repro.models.model import cache_shapes, init_cache


class PagePoolExhausted(RuntimeError):
    """No free pages left in the arena.  The engine reacts with its pressure
    ladder: reclaim prefix-cache leases, then preempt, then fail."""


@dataclass
class PageStats:
    """Allocator counters.  ``full_copies`` counts admissions that fell back
    to materialising every page of an already-cached prefix — the COW
    acceptance gate asserts it stays 0 (sharing is by table mapping, never
    by byte copy)."""
    allocs: int = 0
    frees: int = 0
    shares: int = 0          # incref of an already-owned page (COW mapping)
    cow_splits: int = 0      # page copied because a writer hit refcount > 1
    full_copies: int = 0


class PageAllocator:
    """Host-side free-list + refcount allocator over ``num_pages`` page ids.

    Pure host bookkeeping (no device state) so the COW invariants are
    property-testable in isolation (tests/test_paged_kv.py).  Page ids
    ``[0, reserved)`` are never handed out: the engine uses them as trash
    cells for frozen-slot decode writes and as the masked-scatter scratch
    page, so a masked or frozen write can never land on a real page.
    """

    def __init__(self, num_pages: int, reserved: int = 0):
        assert num_pages > reserved >= 0
        self.num_pages = num_pages
        self.reserved = reserved
        self._free: List[int] = list(range(reserved, num_pages))[::-1]
        self._ref: List[int] = [0] * num_pages
        self.stats = PageStats()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocatable(self) -> int:
        return self.num_pages - self.reserved

    def alloc(self) -> int:
        if not self._free:
            raise PagePoolExhausted(
                f"all {self.num_allocatable} KV pages in use")
        page = self._free.pop()
        assert self._ref[page] == 0
        self._ref[page] = 1
        self.stats.allocs += 1
        return page

    def incref(self, page: int) -> None:
        assert self._ref[page] > 0, f"incref of unowned page {page}"
        self._ref[page] += 1
        self.stats.shares += 1

    def decref(self, page: int) -> None:
        assert self._ref[page] > 0, f"double free of page {page}"
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)
            self.stats.frees += 1

    def refcount(self, page: int) -> int:
        return self._ref[page]


# --------------------------------------------------------------------------- #
# jit'd arena plumbing
# --------------------------------------------------------------------------- #
def _map_arena(cache, fn_prefix, fn_block, fn_dense_prefix=None,
               fn_dense_block=None):
    """Structure-preserving map over a paged cache: arena leaves (page axis)
    through ``fn_prefix``/``fn_block``, everything else through the dense
    fns (identity by default).  ``page_table`` passes through untouched."""
    ident = lambda a: a
    dp = fn_dense_prefix or ident
    db = fn_dense_block or ident
    out = dict(cache)
    out["prefix"] = [
        {name: (fn_prefix(leaf) if name in ARENA_KEYS else dp(leaf))
         for name, leaf in sub.items()}
        for sub in cache["prefix"]
    ]
    if cache.get("block") is not None:
        out["block"] = {
            pos: {name: (fn_block(leaf) if name in ARENA_KEYS else db(leaf))
                  for name, leaf in sub.items()}
            for pos, sub in cache["block"].items()
        }
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pages_jit(cache, src: jax.Array, dst: jax.Array):
    """COW split: device-copy whole pages (all arena leaves) src -> dst."""
    return _map_arena(cache,
                      lambda a: a.at[dst].set(a[src]),
                      lambda a: a.at[:, dst].set(a[:, src]))


def _quant_pages(rows: jax.Array):
    """rows [n, ps, Hkv, hd] fp -> (int8 rows, f32 scales [n, ps, Hkv])."""
    return quantize_kv_int8(rows)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("int8",))
def _paged_insert_jit(cache, singles, slots: jax.Array, page_ids: jax.Array,
                      *, int8: bool):
    """Scatter a wave of dense batch=1 rows into the arena.

    ``page_ids`` is ``[k, P]`` int32 with every entry that must NOT be
    written (shared COW prefix pages, never-allocated tail) redirected to
    the reserved scratch page — the scatter itself is unmasked and cheap,
    and scratch-page content is garbage by contract.  Non-KV leaves take
    the dense slot scatter of ``kv_cache._insert_slots``.
    """
    k = len(singles)
    flat_ids = page_ids.reshape(-1)                        # [k*P]

    def paged_prefix(full, *ones):
        ps = full.shape[1]
        rows = jnp.concatenate(
            [o.reshape(-1, ps, *o.shape[2:]) for o in ones], axis=0)
        if int8:
            q, s = _quant_pages(rows)
            return full.at[flat_ids].set(q), s
        return full.at[flat_ids].set(rows.astype(full.dtype)), None

    def paged_block(full, *ones):                          # [L, N, ps, ...]
        ps = full.shape[2]
        rows = jnp.concatenate(
            [o.reshape(o.shape[0], -1, ps, *o.shape[3:]) for o in ones],
            axis=1)
        if int8:
            q, s = _quant_pages(rows)
            return full.at[:, flat_ids].set(q), s
        return full.at[:, flat_ids].set(rows.astype(full.dtype)), None

    def dense_prefix(full, *ones):
        many = jnp.concatenate([o.astype(full.dtype) for o in ones], axis=0)
        return full.at[slots].set(many)

    def dense_block(full, *ones):
        many = jnp.concatenate([o.astype(full.dtype) for o in ones], axis=1)
        return full.at[:, slots].set(many)

    out = dict(cache)
    out["prefix"] = []
    for i, sub in enumerate(cache["prefix"]):
        ones = [s["prefix"][i] for s in singles]
        new = {}
        scales: Dict[str, jax.Array] = {}
        for name, leaf in sub.items():
            if name in ("k", "v"):
                new[name], sc = paged_prefix(leaf, *[o[name] for o in ones])
                if sc is not None:
                    scales[name + "_scale"] = sc
            elif name in ("k_scale", "v_scale"):
                new[name] = leaf                            # filled below
            else:
                new[name] = dense_prefix(leaf, *[o[name] for o in ones])
        for sname, sc in scales.items():
            new[sname] = sub[sname].at[flat_ids].set(sc)
        out["prefix"].append(new)
    if cache.get("block") is not None:
        blk = {}
        for pos, sub in cache["block"].items():
            ones = [s["block"][pos] for s in singles]
            new = {}
            scales = {}
            for name, leaf in sub.items():
                if name in ("k", "v"):
                    new[name], sc = paged_block(leaf, *[o[name] for o in ones])
                    if sc is not None:
                        scales[name + "_scale"] = sc
                elif name in ("k_scale", "v_scale"):
                    new[name] = leaf
                else:
                    new[name] = dense_block(leaf, *[o[name] for o in ones])
            for sname, sc in scales.items():
                new[sname] = sub[sname].at[:, flat_ids].set(sc)
            blk[pos] = new
        out["block"] = blk
    return out


@functools.partial(jax.jit, static_argnames=("slot", "int8"))
def _gather_slot_jit(cache, page_ids: jax.Array, page_valid: jax.Array, *,
                     slot: int, int8: bool):
    """Gather one slot's pages back into a dense batch=1 cache row.

    Never-allocated table entries are masked to zeros so the row is
    bit-identical to what a dense pool would hold (dense rows start from
    zeros); int8 pages are dequantised back to the dense fp dtype."""
    scales: Dict[int, Dict[str, jax.Array]] = {}

    def gather(kv, sc, stacked):
        ps = kv.shape[2] if stacked else kv.shape[1]
        mask = page_valid[:, None]                        # [P, 1]
        if stacked:
            rows = kv[:, page_ids]                        # [L, P, ps, ...]
            m = mask[None, ..., None, None]
            if sc is not None:
                rows = rows.astype(jnp.float32) * sc[:, page_ids][..., None]
            rows = jnp.where(m, rows, 0)
            return rows.reshape(rows.shape[0], 1, -1, *rows.shape[3:])
        rows = kv[page_ids]                               # [P, ps, ...]
        if sc is not None:
            rows = rows.astype(jnp.float32) * sc[page_ids][..., None]
        rows = jnp.where(mask[..., None, None], rows, 0)
        return rows.reshape(1, -1, *rows.shape[2:])

    def rd_prefix(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=0)

    def rd_block(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1)

    out: Dict[str, Any] = {"prefix": []}
    for sub in cache["prefix"]:
        new = {}
        for name, leaf in sub.items():
            if name in ("k", "v"):
                sc = sub.get(name + "_scale") if int8 else None
                new[name] = gather(leaf, sc, stacked=False)
            elif name in ("k_scale", "v_scale"):
                continue
            else:
                new[name] = rd_prefix(leaf)
        out["prefix"].append(new)
    out["block"] = None
    if cache.get("block") is not None:
        blk = {}
        for pos, sub in cache["block"].items():
            new = {}
            for name, leaf in sub.items():
                if name in ("k", "v"):
                    sc = sub.get(name + "_scale") if int8 else None
                    new[name] = gather(leaf, sc, stacked=True)
                elif name in ("k_scale", "v_scale"):
                    continue
                else:
                    new[name] = rd_block(leaf)
            blk[pos] = new
        out["block"] = blk
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_nonkv_jit(cache, nonkv, slot: jax.Array):
    """Scatter a snapshot's dense non-KV leaves (conv/state/xk/xv) back into
    one slot (the KV part of a resume is pure page-table adoption)."""
    out = dict(cache)
    out["prefix"] = [
        {name: (leaf if name in ARENA_KEYS
                else leaf.at[slot].set(nonkv["prefix"][i][name].astype(
                    leaf.dtype)[0]))
         for name, leaf in sub.items()}
        for i, sub in enumerate(cache["prefix"])
    ]
    if cache.get("block") is not None:
        out["block"] = {
            pos: {name: (leaf if name in ARENA_KEYS
                         else leaf.at[:, slot].set(
                             nonkv["block"][pos][name].astype(
                                 leaf.dtype)[:, 0]))
                  for name, leaf in sub.items()}
            for pos, sub in cache["block"].items()
        }
    return out


def _read_nonkv(cache, slot: int):
    """Dense non-KV leaves of one slot as a batch=1 pytree (host-cheap jit
    slice; the KV pages themselves are snapshotted by reference)."""
    def rd_prefix(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=0)

    def rd_block(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1)

    out: Dict[str, Any] = {"prefix": [
        {name: rd_prefix(leaf) for name, leaf in sub.items()
         if name not in ARENA_KEYS}
        for sub in cache["prefix"]
    ]}
    out["block"] = None
    if cache.get("block") is not None:
        out["block"] = {
            pos: {name: rd_block(leaf) for name, leaf in sub.items()
                  if name not in ARENA_KEYS}
            for pos, sub in cache["block"].items()
        }
    return out


# --------------------------------------------------------------------------- #
# the pool
# --------------------------------------------------------------------------- #
class PagedKVPool:
    """Drop-in decode pool with a paged arena (SlotKVPool surface + paging).

    Slot allocation (``allocate``/``free``/``num_free``) is unchanged; KV
    bytes live in the shared arena and a slot's footprint is the pages it
    actually holds.  ``num_pages=None`` sizes the arena for full capacity
    (``max_batch * pages_per_slot`` + reserved) — exhaustion then requires
    cache leases, which the engine's pressure ladder can always reclaim.
    """

    def __init__(self, cfg: ModelConfig, max_batch: int, cache_len: int, *,
                 ctx_len: int = 0, dtype=None, page_size: int = 16,
                 num_pages: Optional[int] = None, kv_dtype: str = "fp"):
        assert kv_dtype in ("fp", "int8")
        page_size = min(page_size, cache_len)
        assert cache_len % page_size == 0, (
            f"cache_len={cache_len} must be a multiple of "
            f"page_size={page_size}")
        self.cfg = cfg
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.ctx_len = ctx_len
        self.page_size = page_size
        self.pages_per_slot = cache_len // page_size
        self.kv_dtype = kv_dtype
        # reserved arena prefix: one trash cell (page b//ps, offset b%ps)
        # per slot for frozen-slot decode writes, plus one scratch page for
        # masked insert-scatter entries
        trash = -(-max_batch // page_size)
        self.reserved = trash + 1
        self.scratch_page = trash
        if num_pages is None:
            num_pages = self.reserved + max_batch * self.pages_per_slot
        assert num_pages > self.reserved
        self.num_pages = num_pages
        self.allocator = PageAllocator(num_pages, reserved=self.reserved)

        self._free: List[int] = list(range(max_batch))[::-1]
        self._used: Set[int] = set()
        self._slot_pages: Dict[int, List[int]] = {}
        self._pt_host = np.zeros((max_batch, self.pages_per_slot), np.int32)
        self._zeros = None
        self._dense_dtype = jnp.dtype(dtype or cfg.dtype)
        self.cache = self._init_arena(dtype)
        self._page_bytes = self._compute_page_bytes()
        self.stats = self.allocator.stats                  # alias

    # ------------------------------------------------------------------ #
    def _init_arena(self, dtype):
        n, ps = self.num_pages, self.page_size
        int8 = self.kv_dtype == "int8"
        # the dense cache's shapes only: its K and V become the arena, and
        # building them first would hold a second KV cache the size of the
        # whole dense one (every slot at full length) while the arena is made
        dense = cache_shapes(self.cfg, self.max_batch, self.cache_len,
                             ctx_len=self.ctx_len, dtype=dtype)

        def to_arena(sub, stacked):
            out = {}
            for name, leaf in sub.items():
                if name in ("k", "v"):
                    if stacked:                           # [L, B, S, Hkv, hd]
                        shape = (leaf.shape[0], n, ps) + leaf.shape[3:]
                    else:                                 # [B, S, Hkv, hd]
                        shape = (n, ps) + leaf.shape[2:]
                    dt = jnp.int8 if int8 else leaf.dtype
                    out[name] = jnp.zeros(shape, dt)
                    if int8:
                        out[name + "_scale"] = jnp.ones(shape[:-1],
                                                        jnp.float32)
                else:
                    out[name] = jnp.zeros(leaf.shape, leaf.dtype)
            return out

        arena = {"prefix": [to_arena(sub, False) for sub in dense["prefix"]]}
        arena["block"] = (
            {pos: to_arena(sub, True) for pos, sub in dense["block"].items()}
            if dense.get("block") is not None else None)
        arena["page_table"] = jnp.asarray(self._pt_host)
        return arena

    def _compute_page_bytes(self) -> int:
        """Device bytes of ONE page summed over every arena leaf (for LRU
        byte-budget accounting of page-lease cache entries)."""
        total = 0
        for sub in self.cache["prefix"]:
            for name, leaf in sub.items():
                if name in ARENA_KEYS:
                    total += leaf[0].size * leaf.dtype.itemsize
        if self.cache.get("block") is not None:
            for sub in self.cache["block"].values():
                for name, leaf in sub.items():
                    if name in ARENA_KEYS:
                        total += leaf[:, 0].size * leaf.dtype.itemsize
        return total

    # ------------------------------------------------------------------ #
    # slot allocation (SlotKVPool surface)
    # ------------------------------------------------------------------ #
    @property
    def num_free(self) -> int:
        return len(self._free)

    def allocate(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._used.add(slot)
        return slot

    def free(self, slot: int) -> None:
        assert slot in self._used, f"double free of slot {slot}"
        self._used.remove(slot)
        self._free.append(slot)
        for page in self._slot_pages.pop(slot, []):
            self.allocator.decref(page)
        self._pt_host[slot] = 0

    # ------------------------------------------------------------------ #
    # page bookkeeping
    # ------------------------------------------------------------------ #
    def slot_pages(self, slot: int) -> List[int]:
        return self._slot_pages.get(slot, [])

    def incref_pages(self, pages: Sequence[int]) -> None:
        for p in pages:
            self.allocator.incref(p)

    def release_pages(self, pages: Sequence[int]) -> None:
        for p in pages:
            self.allocator.decref(p)

    @property
    def page_bytes(self) -> int:
        return self._page_bytes

    def pages_nbytes(self, npages: int) -> int:
        return npages * self._page_bytes

    def page_occupancy(self) -> Dict[str, int]:
        """Real arena occupancy for the admission controller's KV-headroom
        probe: ``free`` pages are immediately allocatable; ``reclaimable``
        are held only by cache leases (prefix entries / snapshots), which
        the pressure ladder can evict; ``pinned`` back live decode slots."""
        free = self.allocator.num_free
        pinned = len({p for pages in self._slot_pages.values()
                      for p in pages})
        total = self.allocator.num_allocatable
        return {"total": total, "free": free, "pinned": pinned,
                "reclaimable": total - free - pinned}

    def _sync_page_table(self) -> None:
        self.cache["page_table"] = jnp.asarray(self._pt_host)

    # ------------------------------------------------------------------ #
    # admission / publication / snapshot
    # ------------------------------------------------------------------ #
    def insert(self, slot: int, single_cache) -> None:
        self.insert_many([slot], [single_cache])

    def insert_many(self, slots: Sequence[int], single_caches,
                    consumed: Optional[Sequence[int]] = None,
                    shared: Optional[Sequence[Sequence[int]]] = None) -> None:
        """Land an admission wave: map each row's shared COW prefix pages
        (ownership of the caller's pinned refs transfers to the slot),
        allocate fresh pages for the rest, and scatter the dense rows into
        the fresh pages only — shared pages are never written (their
        table entries redirect to the scratch page in the device scatter).

        Raises :exc:`PagePoolExhausted` *before* any mutation if the fresh
        pages don't fit, so the caller can reclaim leases and retry."""
        if not slots:
            return
        ps, cap = self.page_size, self.pages_per_slot
        consumed = ([self.cache_len] * len(slots) if consumed is None
                    else list(consumed))
        shared = ([[] for _ in slots] if shared is None
                  else [list(s) for s in shared])
        need = 0
        for c, sh in zip(consumed, shared):
            npages = min(-(-c // ps), cap)
            assert len(sh) * ps <= c and len(sh) <= npages
            need += npages - len(sh)
        if need > self.allocator.num_free:
            raise PagePoolExhausted(
                f"admission wave needs {need} pages, "
                f"{self.allocator.num_free} free")

        ids = np.full((len(slots), cap), self.scratch_page, np.int32)
        for i, (slot, c, sh) in enumerate(zip(slots, consumed, shared)):
            assert not self._slot_pages.get(slot), \
                f"slot {slot} already holds pages"
            npages = min(-(-c // ps), cap)
            pages = list(sh)                        # refs transfer from caller
            for _ in range(npages - len(sh)):
                pages.append(self.allocator.alloc())
            # device scatter writes fresh pages only; shared entries stay
            # redirected at the scratch page (COW: no copy, no write)
            ids[i, len(sh):npages] = pages[len(sh):npages]
            self._slot_pages[slot] = pages
            self._pt_host[slot, :npages] = pages
            self._pt_host[slot, npages:] = 0
        self.cache = _paged_insert_jit(
            self.cache, tuple(single_caches),
            jnp.asarray(list(slots), jnp.int32), jnp.asarray(ids),
            int8=self.kv_dtype == "int8")
        self._sync_page_table()

    def adopt(self, slot: int, pages: Sequence[int], nonkv=None) -> None:
        """Resume: install a snapshot's page list into a slot, taking over
        the caller's refs (take_exact popped the entry, so its refs move
        here — no copy, no refcount churn), and scatter the snapshot's
        dense non-KV leaves back into the slot."""
        assert not self._slot_pages.get(slot), \
            f"slot {slot} already holds pages"
        pages = list(pages)
        assert len(pages) <= self.pages_per_slot
        self._slot_pages[slot] = pages
        self._pt_host[slot, :len(pages)] = pages
        self._pt_host[slot, len(pages):] = 0
        if nonkv is not None:
            self.cache = _insert_nonkv_jit(self.cache, nonkv,
                                           jnp.asarray(slot, jnp.int32))
        self._sync_page_table()

    def read(self, slot: int):
        """Gather a slot's pages back into a dense batch=1 cache row (the
        prefix cache's dense shadow for prefill interop)."""
        pages = self._slot_pages.get(slot, [])
        ids = np.zeros((self.pages_per_slot,), np.int32)
        ids[:len(pages)] = pages
        valid = np.zeros((self.pages_per_slot,), bool)
        valid[:len(pages)] = True
        return _gather_slot_jit(self.cache, jnp.asarray(ids),
                                jnp.asarray(valid), slot=slot,
                                int8=self.kv_dtype == "int8")

    def read_nonkv(self, slot: int):
        return _read_nonkv(self.cache, slot)

    # ------------------------------------------------------------------ #
    # decode-capacity planning (lazy tail allocation + COW splits)
    # ------------------------------------------------------------------ #
    def ensure_decode_capacity(self, slot_positions: Dict[int, int],
                               k_steps: int) -> bool:
        """Make every page the next decode block will write exclusively
        owned.  ``slot_positions`` maps live slot -> absolute position of
        its ``last_token`` (the block writes KV at positions
        ``pos .. pos+k-1``).  New tail pages are allocated lazily at page
        -boundary crossings; a ring wrap (or a resume/publication overlap)
        that lands a write on a ``refcount > 1`` page triggers a COW split
        (one-page device copy).  Returns False — with no partial effects —
        if the arena can't supply the fresh pages; the engine then runs
        its pressure ladder and retries."""
        ps, cap = self.page_size, self.pages_per_slot
        plans = []                                  # (slot, idx, src|None)
        for slot, pos in slot_positions.items():
            pages = self._slot_pages.get(slot)
            if pages is None:
                continue
            cur_len = len(pages)
            seen = set()
            for pg in range(pos // ps, (pos + k_steps - 1) // ps + 1):
                idx = pg % cap
                if idx in seen:
                    continue
                seen.add(idx)
                if idx < cur_len:
                    page = pages[idx]
                    if self.allocator.refcount(page) > 1:
                        plans.append((slot, idx, page))
                elif idx == cur_len:
                    plans.append((slot, idx, None))
                    cur_len += 1
                else:
                    raise AssertionError(
                        f"slot {slot}: non-contiguous page growth "
                        f"(idx {idx} > {cur_len})")
        if not plans:
            return True
        if len(plans) > self.allocator.num_free:
            return False
        src_ids, dst_ids = [], []
        for slot, idx, src in plans:
            new = self.allocator.alloc()
            pages = self._slot_pages[slot]
            if src is None:
                assert idx == len(pages)
                pages.append(new)
            else:
                # COW split: the old page stays with its other owners
                self.allocator.decref(src)
                pages[idx] = new
                src_ids.append(src)
                dst_ids.append(new)
                self.allocator.stats.cow_splits += 1
            self._pt_host[slot, idx] = new
        if src_ids:
            self.cache = _copy_pages_jit(self.cache,
                                         jnp.asarray(src_ids, jnp.int32),
                                         jnp.asarray(dst_ids, jnp.int32))
        self._sync_page_table()
        return True

    # ------------------------------------------------------------------ #
    def single_cache_zeros(self):
        """Dense batch=1 zeros row — prefill stays dense regardless of the
        pool layout (pagination happens at the commit boundary)."""
        if self._zeros is None:
            self._zeros = init_cache(self.cfg, 1, self.cache_len,
                                     ctx_len=self.ctx_len,
                                     dtype=self._dense_dtype)
        return self._zeros

    @property
    def nbytes(self) -> int:
        return tree_bytes(self.cache)


# --------------------------------------------------------------------------- #
# speculative-decode rollback (paged variant of kv_cache.gather_ring_cells)
# --------------------------------------------------------------------------- #
def gather_page_cells(cache, pages: jax.Array, offs: jax.Array):
    """Snapshot arena cells ``(pages[b, j], offs[b, j])`` from every arena
    leaf (k/v and, under int8, their scale leaves) as [B, S, ...] blocks
    (stacked block layers [L, B, S, ...]).

    The speculative verifier snapshots the S = k_draft + 1 cells its batched
    forward may write, then restores rejected ones with
    :func:`restore_page_cells`.  Callers redirect the (page, off) pairs of
    rows/cells that must not touch real pages (frozen slots, beyond-draft
    positions) to the slot's reserved trash cell, mirroring the attention
    write redirect — so rollback can never write a page another slot owns,
    and rejected-tail pages stay slot-owned (freed at slot release, never
    leaked)."""

    def g(leaf, stacked):
        if stacked:                                   # [L, N, ps, ...]
            return leaf[:, pages, offs]
        return leaf[pages, offs]

    snap = {"prefix": [
        {n: g(sub[n], False) for n in sub if n in ARENA_KEYS}
        for sub in cache["prefix"]
    ]}
    snap["block"] = (
        {pos: {n: g(sub[n], True) for n in sub if n in ARENA_KEYS}
         for pos, sub in cache["block"].items()}
        if cache.get("block") is not None else None)
    return snap


def restore_page_cells(cache, snap, pages: jax.Array, offs: jax.Array,
                       keep: jax.Array):
    """Roll back rejected speculative cells in the arena.

    ``keep`` [B, S]: True keeps the verification forward's fresh cell,
    False restores the snapshot.  Trash-redirected entries may repeat a
    cell within a row, but every such write carries the same snapshot value
    (gathered from that very cell pre-forward), so duplicate scatters are
    order-independent."""
    b, s = pages.shape

    def r(leaf, snap_cells, stacked):
        if stacked:
            cur = leaf[:, pages, offs]
            mask = keep.reshape((1, b, s) + (1,) * (cur.ndim - 3))
            return leaf.at[:, pages, offs].set(
                jnp.where(mask, cur, snap_cells))
        cur = leaf[pages, offs]
        mask = keep.reshape((b, s) + (1,) * (cur.ndim - 2))
        return leaf.at[pages, offs].set(jnp.where(mask, cur, snap_cells))

    out = dict(cache)
    out["prefix"] = [
        {n: (r(sub[n], sn[n], False) if n in sn else sub[n]) for n in sub}
        for sub, sn in zip(cache["prefix"], snap["prefix"])
    ]
    if cache.get("block") is not None:
        out["block"] = {
            pos: {n: (r(sub[n], snap["block"][pos][n], True)
                      if n in snap["block"][pos] else sub[n])
                  for n in sub}
            for pos, sub in cache["block"].items()
        }
    return out


# --------------------------------------------------------------------------- #
# decode-block select (paged variant of kv_cache.select_cache_slots)
# --------------------------------------------------------------------------- #
def select_cache_slots_paged(active: jax.Array, positions: jax.Array,
                             new_cache, old_cache):
    """Post-step cache select under paging.

    The arena needs NO repair: frozen slots' decode writes were redirected
    to their reserved trash cells inside attention (``slot_active`` masking
    in :func:`repro.models.layers.apply_self_attn`), so real pages of
    frozen slots are untouched by construction — arena leaves pass through.
    Dense recurrent leaves (``conv``/``state``) still take the per-slot
    select; pass-through leaves (``xk``/``xv``) are identity-skipped.  The
    page table is host-owned and rides along unchanged."""
    def sel(name, n, o, stacked):
        if name in ARENA_KEYS or n is o:
            return n
        if stacked:
            return jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2)),
                             n, o)
        return jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    out = {
        "prefix": [
            {name: sel(name, nc[name], oc[name], False) for name in nc}
            for nc, oc in zip(new_cache["prefix"], old_cache["prefix"])
        ]
    }
    out["block"] = (
        {pos: {name: sel(name, sub[name], old_cache["block"][pos][name],
                         True)
               for name in sub}
         for pos, sub in new_cache["block"].items()}
        if old_cache.get("block") is not None else None)
    out["page_table"] = old_cache["page_table"]
    return out
