"""Slot-based decode cache pool + device-resident decode state.

TPU adaptation of continuous batching (DESIGN.md §2): the decode batch has a
*static* shape of ``max_batch`` slots over a pre-allocated cache; requests
occupy slots, admission fills free slots at step boundaries, retirement frees
them.  The pool also provides jit'd slot read/insert (used to move prefilled
KV state / prefix-cache entries in and out of the batch cache with no
re-materialisation — the unified-memory "zero-copy" analogue: only block
indices change, plus one device-side scatter per admission *wave*: an
admission of k prefills lands in the batch cache with a single compiled
multi-slot insert instead of k full-cache updates).

:class:`DecodeState` holds everything the decode loop needs per slot — last
sampled token, absolute position, the full per-request sampler state
(temperature, top-p, top-k, min-p, and the request's base PRNG key), media
-context liveness, remaining token budget, stop-token table, and the
live/frozen mask — as one device pytree, so the engine's ``decode_block`` can
run K decode+sample iterations under ``lax.scan`` without the host
re-uploading state between tokens.  Sampler RNG is stateless per token
(``fold_in(sample_key, position)`` — see :mod:`repro.core.sampling`), so the
state carries base keys, not a split chain.
"""

from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Sequence, Set

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.model import init_cache


def tree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# --------------------------------------------------------------------------- #
# device-resident per-slot decode state
# --------------------------------------------------------------------------- #
class DecodeState(NamedTuple):
    """Per-slot decode state, device-resident (one pytree, donated through
    the compiled decode block).  ``stop_tokens`` is a fixed-width table
    padded with -1 (never a valid token id); ``active`` is the on-device
    finished-mask — a slot freezes when it samples a stop token or exhausts
    its budget, and stays frozen (masked cache writes, no position advance)
    until the host re-admits into the slot.  ``sample_key`` is the request's
    *base* PRNG key; the decode block folds the token position into it per
    step, so one slot's stream never depends on its neighbours or on K."""

    last_token: jax.Array  # [B] int32 — input to the next decode step
    positions: jax.Array  # [B] int32 — absolute position of last_token
    temps: jax.Array  # [B] float32 — 0 = greedy
    top_p: jax.Array  # [B] float32 — 1 = off
    top_k: jax.Array  # [B] int32 — 0 = off
    min_p: jax.Array  # [B] float32 — 0 = off
    sample_key: jax.Array  # [B, 2] uint32 — per-request base PRNG key
    ctx_valid: jax.Array  # [B, T] bool — media context liveness
    budget: jax.Array  # [B] int32 — tokens left before LENGTH stop
    stop_tokens: jax.Array  # [B, S] int32 — per-slot stop ids, -1 pad
    active: jax.Array  # [B] bool — False: slot frozen/empty
    draft_tokens: jax.Array  # [B, K] int32 — staged speculative proposals
    draft_len: jax.Array  # [B] int32 — proposals staged this round (<= K)
    # [2] int32 — over the last decode block's steps and expert layers:
    # token-expert pairs the held experts computed, and held experts that
    # computed any; None for models without held experts
    moe_counts: Optional[jax.Array] = None


def init_decode_state(
    max_batch: int, ctx_len: int, max_stop: int, spec_k: int = 0,
    moe_counts: bool = False
) -> DecodeState:
    return DecodeState(
        last_token=jnp.zeros((max_batch,), jnp.int32),
        positions=jnp.zeros((max_batch,), jnp.int32),
        temps=jnp.zeros((max_batch,), jnp.float32),
        top_p=jnp.ones((max_batch,), jnp.float32),
        top_k=jnp.zeros((max_batch,), jnp.int32),
        min_p=jnp.zeros((max_batch,), jnp.float32),
        sample_key=jnp.zeros((max_batch, 2), jnp.uint32),
        ctx_valid=jnp.zeros((max_batch, max(ctx_len, 1)), bool),
        budget=jnp.zeros((max_batch,), jnp.int32),
        stop_tokens=jnp.full((max_batch, max_stop), -1, jnp.int32),
        active=jnp.zeros((max_batch,), bool),
        draft_tokens=jnp.zeros((max_batch, max(spec_k, 1)), jnp.int32),
        draft_len=jnp.zeros((max_batch,), jnp.int32),
        moe_counts=jnp.zeros((2,), jnp.int32) if moe_counts else None,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def admit_decode_state(
    state: DecodeState,
    slots: jax.Array,
    last_token: jax.Array,
    positions: jax.Array,
    temps: jax.Array,
    top_p: jax.Array,
    top_k: jax.Array,
    min_p: jax.Array,
    sample_key: jax.Array,
    ctx_valid: jax.Array,
    budget: jax.Array,
    stop_tokens: jax.Array,
    active: jax.Array,
) -> DecodeState:
    """Scatter one admission wave (k slots) into the decode state."""
    return state._replace(
        last_token=state.last_token.at[slots].set(last_token),
        positions=state.positions.at[slots].set(positions),
        temps=state.temps.at[slots].set(temps),
        top_p=state.top_p.at[slots].set(top_p),
        top_k=state.top_k.at[slots].set(top_k),
        min_p=state.min_p.at[slots].set(min_p),
        sample_key=state.sample_key.at[slots].set(sample_key),
        ctx_valid=state.ctx_valid.at[slots].set(ctx_valid),
        budget=state.budget.at[slots].set(budget),
        stop_tokens=state.stop_tokens.at[slots].set(stop_tokens),
        active=state.active.at[slots].set(active),
        draft_tokens=state.draft_tokens.at[slots].set(0),
        draft_len=state.draft_len.at[slots].set(0),
    )


def select_cache_slots(active: jax.Array, positions: jax.Array, new_cache, old_cache):
    """Per-slot select between an updated and the previous decode cache.

    Frozen slots (``active == False``) keep their old cache bit-for-bit, so
    a finished request's KV/SSM state is exactly what the single-step engine
    would have published to the prefix cache — decode steps that ran while
    the slot was frozen leave no trace.

    Cost note: a decode step mutates exactly one ring cell per slot in the
    ``k``/``v`` leaves (at ``positions % cache_len`` — the frozen slot's
    position does not advance), so those are repaired with an O(B·H·D)
    gather/scatter rather than an O(B·S·H·D) full-cache select; only the
    small recurrent SSM leaves (``conv``/``state``, rewritten wholesale each
    step) pay a full per-slot select.  Pass-through leaves (``xk``/``xv``)
    are detected by identity and skipped."""
    b = active.shape[0]
    bidx = jnp.arange(b)

    def sel(name: str, n, o, stacked: bool):
        if n is o:  # decode pass-through (e.g. xk/xv)
            return n
        if name in ("k", "v"):  # single ring cell written per slot
            sc = n.shape[2] if stacked else n.shape[1]
            idx = positions % sc
            if stacked:  # [L, B, S, ...]
                mask = active.reshape((1, -1) + (1,) * (n.ndim - 3))
                cell = jnp.where(mask, n[:, bidx, idx], o[:, bidx, idx])
                return n.at[:, bidx, idx].set(cell)
            mask = active.reshape((-1,) + (1,) * (n.ndim - 2))
            cell = jnp.where(mask, n[bidx, idx], o[bidx, idx])
            return n.at[bidx, idx].set(cell)
        if stacked:  # recurrent state: full slot select
            return jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o)
        return jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    out = {
        "prefix": [
            {name: sel(name, nc[name], oc[name], False) for name in nc}
            for nc, oc in zip(new_cache["prefix"], old_cache["prefix"])
        ]
    }
    out["block"] = (
        {
            pos: {name: sel(name, sub[name], old_cache["block"][pos][name], True) for name in sub}
            for pos, sub in new_cache["block"].items()
        }
        if old_cache.get("block") is not None
        else None
    )
    return out


def gather_ring_cells(cache, slots: jax.Array):
    """Snapshot the dense-ring cells ``slots`` ([B, S] ring indices) from
    every self-attention ``k``/``v`` leaf, as a pytree of [B, S, ...] (or
    stacked [L, B, S, ...]) cell blocks.

    Speculative verification snapshots the S = k_draft + 1 cells its batched
    forward may overwrite, runs the forward, then hands the snapshot to
    :func:`restore_ring_cells` to roll back the cells of rejected drafts —
    the masked-KV-rollback half of the draft/verify contract
    (DESIGN_spec_decode.md).  Only ``k``/``v`` carry per-position ring state;
    cross-attention context (``xk``/``xv``) is read-only during decode and
    recurrent SSM leaves are excluded by the engine's family gate."""
    b, s = slots.shape
    bidx2 = jnp.arange(b)[:, None]

    def g(leaf, stacked: bool):
        if stacked:  # [L, B, C, ...]
            return leaf[:, bidx2, slots]
        return leaf[bidx2, slots]

    snap = {
        "prefix": [
            {n: g(bp[n], False) for n in bp if n in ("k", "v")} for bp in cache["prefix"]
        ]
    }
    snap["block"] = (
        {
            pos: {n: g(sub[n], True) for n in sub if n in ("k", "v")}
            for pos, sub in cache["block"].items()
        }
        if cache.get("block") is not None
        else None
    )
    return snap


def restore_ring_cells(cache, snap, slots: jax.Array, keep: jax.Array):
    """Roll back the ring cells of rejected speculative positions.

    ``slots`` is the same [B, S] cell grid handed to
    :func:`gather_ring_cells`; ``keep`` is a [B, S] bool mask — True keeps
    the verification forward's freshly-written cell (accepted draft), False
    restores the pre-forward snapshot.  Cell indices are distinct within a
    row (consecutive ring positions, S <= cache_len), so the scatter has no
    write conflicts."""
    b, s = slots.shape
    bidx2 = jnp.arange(b)[:, None]

    def r(leaf, snap_cells, stacked: bool):
        if stacked:
            cur = leaf[:, bidx2, slots]
            mask = keep.reshape((1, b, s) + (1,) * (cur.ndim - 3))
            return leaf.at[:, bidx2, slots].set(jnp.where(mask, cur, snap_cells))
        cur = leaf[bidx2, slots]
        mask = keep.reshape((b, s) + (1,) * (cur.ndim - 2))
        return leaf.at[bidx2, slots].set(jnp.where(mask, cur, snap_cells))

    out = {
        "prefix": [
            {n: (r(bp[n], sn[n], False) if n in sn else bp[n]) for n in bp}
            for bp, sn in zip(cache["prefix"], snap["prefix"])
        ]
    }
    out["block"] = (
        {
            pos: {
                n: (
                    r(sub[n], snap["block"][pos][n], True)
                    if n in snap["block"][pos]
                    else sub[n]
                )
                for n in sub
            }
            for pos, sub in cache["block"].items()
        }
        if cache.get("block") is not None
        else None
    )
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_slots(batch_cache, single_caches, slots: jax.Array):
    """Scatter k batch=1 caches into the batch cache in one compiled call.

    ``single_caches`` is a tuple of k cache pytrees; their leaves are
    concatenated on the batch axis and written with a single gather/scatter
    per leaf — an admission wave of k prefills costs one cache update, not k.
    """

    def ins_prefix(full, *ones):  # batch axis 0
        many = jnp.concatenate([o.astype(full.dtype) for o in ones], axis=0)
        return full.at[slots].set(many)

    def ins_block(full, *ones):  # [L, B, ...]: batch axis 1
        many = jnp.concatenate([o.astype(full.dtype) for o in ones], axis=1)
        return full.at[:, slots].set(many)

    out = dict(batch_cache)
    out["prefix"] = [
        jax.tree.map(ins_prefix, bp, *[s["prefix"][i] for s in single_caches])
        for i, bp in enumerate(batch_cache["prefix"])
    ]
    if batch_cache.get("block") is not None:
        out["block"] = jax.tree.map(
            ins_block, batch_cache["block"], *[s["block"] for s in single_caches]
        )
    return out


def concat_cache_rows(singles: Sequence[Any]):
    """Concatenate k batch=1 cache pytrees into one [k, ...] cache.

    Used *inside* the engine's jitted batched-prefill entry point so a wave
    of k admissions runs one [k, bucket] forward pass instead of k batch=1
    passes; the structure mirrors :func:`_insert_slots` (prefix leaves batch
    on axis 0, stacked block leaves on axis 1)."""
    first = singles[0]
    out = {
        "prefix": [
            jax.tree.map(
                lambda *ones: jnp.concatenate(ones, axis=0), *[s["prefix"][i] for s in singles]
            )
            for i in range(len(first["prefix"]))
        ]
    }
    out["block"] = (
        jax.tree.map(lambda *ones: jnp.concatenate(ones, axis=1), *[s["block"] for s in singles])
        if first.get("block") is not None
        else None
    )
    return out


def slice_cache_row(cache, row: int):
    """Extract one row of a [k, ...] prefill-output cache as a batch=1
    pytree.  Dispatched eagerly (lazy device slices, no host sync) — the
    engine uses it to hand each prefill-wave row back to its chunk job."""
    out = {"prefix": [jax.tree.map(lambda a: a[row : row + 1], bp) for bp in cache["prefix"]]}
    out["block"] = (
        jax.tree.map(lambda a: a[:, row : row + 1], cache["block"])
        if cache.get("block") is not None
        else None
    )
    return out


@functools.partial(jax.jit, static_argnames=("slot",))
def _read_slot(batch_cache, *, slot: int):
    def rd_prefix(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=0)

    def rd_block(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1)

    out = {"prefix": [jax.tree.map(rd_prefix, bp) for bp in batch_cache["prefix"]]}
    out["block"] = (
        jax.tree.map(rd_block, batch_cache["block"])
        if batch_cache.get("block") is not None
        else None
    )
    return out


class SlotKVPool:
    """Fixed-capacity decode cache with slot allocation."""

    def __init__(
        self,
        cfg: ModelConfig,
        max_batch: int,
        cache_len: int,
        *,
        ctx_len: int = 0,
        dtype=None,
    ):
        self.cfg = cfg
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.ctx_len = ctx_len
        self.cache = init_cache(cfg, max_batch, cache_len, ctx_len=ctx_len, dtype=dtype)
        self._free: List[int] = list(range(max_batch))[::-1]
        self._used: Set[int] = set()
        self._zeros = None  # lazily-built shared zeros pytree (read-only)

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

    # ------------------------------------------------------------------ #
    def insert(self, slot: int, single_cache) -> None:
        """Install a batch=1 cache (from prefill or a cache hit) into a slot."""
        self.insert_many([slot], [single_cache])

    def insert_many(self, slots: Sequence[int], single_caches) -> None:
        """Install an admission wave of batch=1 caches with one compiled
        scatter (retraces per distinct wave size only)."""
        if not slots:
            return
        self.cache = _insert_slots(
            self.cache, tuple(single_caches), jnp.asarray(list(slots), jnp.int32)
        )

    def read(self, slot: int):
        """Extract a slot's cache as a batch=1 pytree (for prefix caching)."""
        return _read_slot(self.cache, slot=slot)

    def single_cache_zeros(self):
        """One shared zeros pytree per pool (callers never mutate in place;
        every consumer is a functional jax op, so re-running ``init_cache``
        per call only re-allocated identical device buffers)."""
        if self._zeros is None:
            self._zeros = init_cache(
                self.cfg,
                1,
                self.cache_len,
                ctx_len=self.ctx_len,
                dtype=None if self.cfg.dtype is None else self.cfg.dtype,
            )
        return self._zeros

    @property
    def nbytes(self) -> int:
        return tree_bytes(self.cache)
