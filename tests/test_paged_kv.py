"""Paged KV cache (DESIGN_paged_kv.md): allocator/COW property tests, the
dense-vs-paged bit-exactness gates, zero-copy COW prefix admission, paged
snapshot/resume, int8 KV, and interpret-mode kernel validation.

The allocator property test uses ``hypothesis`` when installed and degrades
to a seeded stdlib-``random`` sweep otherwise (same op machine either way),
so the COW invariants are always exercised in tier-1.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import InferenceEngine
from repro.core.paged_kv import PageAllocator, PagedKVPool, PagePoolExhausted
from repro.core.request import FinishReason, Request, SamplingParams
from repro.kernels import ops
from repro.kernels.ref import decode_attention_ref, paged_attention_ref
from repro.models import build_model
from repro.serving.tokenizer import ByteTokenizer

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                        # pragma: no cover
    HAVE_HYPOTHESIS = False

TOK = ByteTokenizer()


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen3-0.6b-toy")


def _req(text, max_tokens=8, deadline_ms=None):
    return Request(prompt_tokens=TOK.encode(text),
                   sampling=SamplingParams(max_tokens=max_tokens),
                   deadline_ms=deadline_ms)


def _outputs(eng, reqs):
    eng.generate(reqs)
    assert all(r.finish_reason in (FinishReason.STOP, FinishReason.LENGTH)
               for r in reqs)
    return [list(r.output_tokens) for r in reqs]


# --------------------------------------------------------------------------- #
# allocator / COW property test (satellite: hypothesis w/ seeded fallback)
# --------------------------------------------------------------------------- #
def _run_allocator_machine(seed: int, steps: int = 120) -> None:
    """Random walk over allocate / share / free / COW-split against a pure
    host model (owner -> page list), checking after every op:

      * refcount conservation — allocator refcounts == model reference
        counts, page for page
      * no page aliased by two writers — a page is writable iff its
        refcount is 1, so any page held by two owners must have ref >= 2
      * free-list integrity — the free list is exactly the unreferenced
        non-reserved ids, duplicate-free; reserved ids are never handed out
    """
    rng = random.Random(seed)
    num_pages, reserved = rng.randint(6, 24), rng.randint(0, 3)
    if num_pages <= reserved:
        num_pages = reserved + 2
    alloc = PageAllocator(num_pages, reserved=reserved)
    owners = {}                               # owner id -> list of page ids
    next_owner = 0

    def check():
        refs = {}
        for pages in owners.values():
            for p in pages:
                refs[p] = refs.get(p, 0) + 1
        for p in range(num_pages):
            assert alloc.refcount(p) == refs.get(p, 0), (
                f"refcount drift on page {p}")
            if p < reserved:
                assert refs.get(p, 0) == 0    # reserved never handed out
        holders = {p: sum(p in pages for pages in owners.values())
                   for p in refs}
        for p, n in holders.items():
            if n >= 2:                         # aliased -> not writable
                assert alloc.refcount(p) >= 2
        free = alloc._free
        assert len(free) == len(set(free)), "duplicate free-list entry"
        assert set(free) == {p for p in range(reserved, num_pages)
                             if refs.get(p, 0) == 0}, "free-list drift"

    for _ in range(steps):
        op = rng.choice(("alloc", "share", "free", "cow", "alloc", "share"))
        if op == "alloc":
            if alloc.num_free:
                owners.setdefault(next_owner, []).append(alloc.alloc())
                next_owner += 1
            else:
                with pytest.raises(PagePoolExhausted):
                    alloc.alloc()
        elif op == "share" and owners:
            src = rng.choice([p for ps in owners.values() for p in ps])
            alloc.incref(src)
            owners.setdefault(next_owner, []).append(src)
            next_owner += 1
        elif op == "free" and owners:
            key = rng.choice(list(owners))
            for p in owners.pop(key):
                alloc.decref(p)
        elif op == "cow" and owners:
            # split the first aliased page found: writer gets a fresh page,
            # the old one stays with its other owners (alloc-then-decref,
            # the same order ensure_decode_capacity uses)
            for key, pages in owners.items():
                idx = next((i for i, p in enumerate(pages)
                            if alloc.refcount(p) > 1), None)
                if idx is not None and alloc.num_free:
                    old = pages[idx]
                    pages[idx] = alloc.alloc()
                    alloc.decref(old)
                    break
        check()
    stats = alloc.stats
    assert stats.allocs >= stats.frees
    assert stats.full_copies == 0


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_allocator_cow_invariants(seed):
        _run_allocator_machine(seed)
else:
    @pytest.mark.parametrize("seed", range(25))
    def test_allocator_cow_invariants(seed):
        _run_allocator_machine(seed, steps=160)


def test_allocator_guards_double_free_and_foreign_incref():
    alloc = PageAllocator(4, reserved=1)
    p = alloc.alloc()
    with pytest.raises(AssertionError):
        alloc.incref(p + 1 if p + 1 < 4 else p - 1)   # unowned page
    alloc.decref(p)
    with pytest.raises(AssertionError):
        alloc.decref(p)


# --------------------------------------------------------------------------- #
# bit-exactness gates: paged decode == dense decode under greedy
# --------------------------------------------------------------------------- #
PROMPTS = ["the paged pool must reproduce the dense pool bit for bit",
           "second request, different length",
           "third one " * 4,
           "a", "fifth prompt with some more tokens in it"]


def _dense_outputs(cfg, **kw):
    eng = InferenceEngine(cfg, max_batch=4, cache_len=128, **kw)
    return _outputs(eng, [_req(p, max_tokens=10) for p in PROMPTS])


@pytest.mark.parametrize("page_size", [128, 16])
def test_paged_fp_bit_identical_to_dense(cfg, page_size):
    """The headline acceptance gate: with fp KV, paged greedy decode matches
    the dense ring token-for-token — both at ``page_size == cache_len``
    (identity page tables, the 'paging is free' case) and at a small page
    size (lazy tail allocation + table-gathered attention)."""
    dense = _dense_outputs(cfg)
    eng = InferenceEngine(cfg, max_batch=4, cache_len=128,
                          kv_layout="paged", kv_page_size=page_size)
    paged = _outputs(eng, [_req(p, max_tokens=10) for p in PROMPTS])
    assert paged == dense
    occ = eng.pool.page_occupancy()
    assert occ["pinned"] == 0                 # all slots retired
    assert occ["total"] == occ["free"] + occ["reclaimable"]
    assert eng.pool.stats.full_copies == 0


def test_paged_int8_decodes_and_stays_close(cfg):
    """int8 KV is lossy by design: the gate is completion + bounded drift of
    the first decoded token's distribution, not bit-identity."""
    eng = InferenceEngine(cfg, max_batch=4, cache_len=128,
                          kv_layout="paged", kv_page_size=16,
                          kv_dtype="int8")
    outs = _outputs(eng, [_req(p, max_tokens=10) for p in PROMPTS])
    assert all(len(o) == 10 for o in outs)


# --------------------------------------------------------------------------- #
# COW prefix sharing: admission maps pages, never copies
# --------------------------------------------------------------------------- #
def test_cow_prefix_hit_does_zero_copies(cfg):
    """The COW acceptance gate, asserted on allocator counters (not timing):
    a second request sharing a 64-token prefix admits by mapping the cached
    pages (refcount bump) and allocates fresh pages only from the
    divergence point; ``full_copies`` stays 0 and refcounts balance."""
    eng = InferenceEngine(cfg, max_batch=2, cache_len=128,
                          kv_layout="paged", kv_page_size=16)
    base = "shared prefix " * 8               # >= 64 tokens of shared prefix
    r1 = _req(base + "tail one", max_tokens=6)
    eng.generate([r1])
    allocs_before = eng.pool.stats.allocs

    r2 = _req(base + "tail TWO!", max_tokens=6)
    eng.generate([r2])
    assert r2.cached_prefix_len >= 64         # the prefix cache actually hit
    fresh = eng.pool.stats.allocs - allocs_before
    shared = r2.cached_prefix_len // eng.pool.page_size
    total = -(-len(r2.prompt_tokens) // eng.pool.page_size)
    assert fresh <= total - shared + 1, (
        f"COW admission allocated {fresh} fresh pages, expected at most "
        f"{total - shared + 1} (only past the divergence point)")
    assert eng.pool.stats.full_copies == 0
    assert eng.pool.stats.shares > 0

    # both outputs bit-identical to a dense engine (sharing changed memory
    # layout, never semantics)
    dense = InferenceEngine(cfg, max_batch=2, cache_len=128)
    d1 = _req(base + "tail one", max_tokens=6)
    d2 = _req(base + "tail TWO!", max_tokens=6)
    dense.generate([d1])
    dense.generate([d2])
    assert r1.output_tokens == d1.output_tokens
    assert r2.output_tokens == d2.output_tokens

    occ = eng.pool.page_occupancy()
    assert occ["pinned"] == 0
    assert occ["free"] + occ["reclaimable"] == occ["total"]


def test_page_pool_exhaustion_pressure_ladder(cfg):
    """A deliberately tiny arena forces the pressure ladder: cache leases
    are reclaimed first, and every request still finishes (nothing hangs,
    nothing corrupts — outputs stay bit-identical to dense)."""
    eng = InferenceEngine(cfg, max_batch=2, cache_len=128,
                          kv_layout="paged", kv_page_size=16,
                          kv_num_pages=2 + 2 * 8)    # reserved + exactly 2 slots
    reqs = [_req(f"request {i} " + "pad " * 12, max_tokens=8)
            for i in range(4)]
    paged = _outputs(eng, reqs)
    dense = InferenceEngine(cfg, max_batch=2, cache_len=128)
    ref = _outputs(dense, [_req(f"request {i} " + "pad " * 12, max_tokens=8)
                           for i in range(4)])
    assert paged == ref
    occ = eng.pool.page_occupancy()
    assert occ["pinned"] == 0


# --------------------------------------------------------------------------- #
# preemption / snapshot / resume under paging
# --------------------------------------------------------------------------- #
def _preempt_scenario(cfg, *, paged, policy="edf", preemption=True,
                      prefix_cache=True):
    kw = dict(kv_layout="paged", kv_page_size=16) if paged else {}
    eng = InferenceEngine(cfg, max_batch=1, cache_len=256,
                          sched_policy=policy, preemption=preemption,
                          enable_prefix_cache=prefix_cache, **kw)
    batch = _req("long-running batch request " * 2, max_tokens=24)
    eng.add_request(batch)
    for _ in range(4):
        eng.step()
    urgent = _req("urgent interactive!", max_tokens=6, deadline_ms=1.0)
    eng.add_request(urgent)
    eng.run()
    return batch, urgent, eng


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_paged_preemption_resume_bit_identical(cfg, prefix_cache):
    """Eviction snapshots under paging are page-lease references (no dense
    copy); resume adopts the pages back.  Both the prefix-cache snapshot
    path and the engine-side fallback must keep the evictee bit-identical
    to an unpreempted FIFO run, and every lease must unwind (occupancy
    returns to free once both requests retire)."""
    b, u, eng = _preempt_scenario(cfg, paged=True, prefix_cache=prefix_cache)
    assert eng.scheduler.stats.preemptions >= 1
    assert eng.scheduler.stats.resumed >= 1
    ref_b, ref_u, _ = _preempt_scenario(cfg, paged=False, policy="fifo",
                                        preemption=False,
                                        prefix_cache=prefix_cache)
    assert b.output_tokens == ref_b.output_tokens
    assert u.output_tokens == ref_u.output_tokens
    occ = eng.pool.page_occupancy()
    assert occ["pinned"] == 0
    assert occ["free"] + occ["reclaimable"] == occ["total"]


# --------------------------------------------------------------------------- #
# pool-level unit coverage (no engine)
# --------------------------------------------------------------------------- #
def test_pool_insert_read_roundtrip_and_occupancy(cfg):
    pool = PagedKVPool(cfg, max_batch=2, cache_len=64, page_size=16)
    single = jax.tree.map(
        lambda a: jnp.asarray(np.random.default_rng(0).normal(
            size=a.shape).astype(a.dtype) if jnp.issubdtype(
                a.dtype, jnp.floating) else np.zeros(a.shape, a.dtype)),
        pool.single_cache_zeros())
    slot = pool.allocate()
    pool.insert_many([slot], [single], consumed=[40])   # 3 of 4 pages
    assert len(pool.slot_pages(slot)) == 3
    occ = pool.page_occupancy()
    assert occ["pinned"] == 3 and occ["free"] == occ["total"] - 3

    back = pool.read(slot)
    # written positions round-trip exactly; the never-allocated tail page
    # reads back as zeros (dense rows start from zeros)
    for i, sub in enumerate(back["prefix"]):
        if "k" not in sub:
            continue
        want = np.asarray(single["prefix"][i]["k"])
        got = np.asarray(sub["k"])
        np.testing.assert_array_equal(got[:, :48], want[:, :48])
        assert not got[:, 48:].any()

    pool.free(slot)
    occ = pool.page_occupancy()
    assert occ["pinned"] == 0 and occ["free"] == occ["total"]
    assert pool.stats.allocs == pool.stats.frees == 3


def test_pool_ensure_capacity_allocates_tail_and_splits_shared(cfg):
    pool = PagedKVPool(cfg, max_batch=2, cache_len=64, page_size=16)
    single = pool.single_cache_zeros()
    slot = pool.allocate()
    pool.insert_many([slot], [single], consumed=[16])    # one full page
    # decode at pos 16 crosses into page 1 -> lazy tail alloc
    assert pool.ensure_decode_capacity({slot: 16}, 4)
    assert len(pool.slot_pages(slot)) == 2

    # share page 0, then write into it -> COW split, sharer keeps the old id
    shared_page = pool.slot_pages(slot)[0]
    pool.incref_pages([shared_page])
    assert pool.ensure_decode_capacity({slot: 4}, 1)
    assert pool.stats.cow_splits == 1
    assert pool.slot_pages(slot)[0] != shared_page
    assert pool.allocator.refcount(shared_page) == 1     # lease survives
    pool.release_pages([shared_page])

    # exhaustion: returns False with no partial effects
    before = list(pool.slot_pages(slot))
    free_now = pool.allocator.num_free
    for _ in range(free_now):
        pool.allocator.alloc()                            # drain the arena
    assert not pool.ensure_decode_capacity({slot: 32}, 1)
    assert pool.slot_pages(slot) == before


# --------------------------------------------------------------------------- #
# paged decode: the stacked arena rides the layer scan's carry
# --------------------------------------------------------------------------- #
LAYOUTS = {            # block layouts a paged decode meets -> (arch, reduce)
    "stack": ("qwen3-0.6b-toy", None),          # one attention position
    "prefix": ("deepseek-moe-16b", {"num_layers": 3}),  # + unstacked layer
    "groups": ("qwen3-vl-toy", None),           # 2 attention positions per
                                                # group, cross-attn leaves
    "hybrid": ("jamba-1.5-large-398b", {"num_layers": 16}),  # SSM on xs
}


def _paged_decode_steps(cfg, kv_dtype, unroll, steps=3):
    """Greedy paged decode through ``Model.apply`` from a random arena and
    shuffled page tables, one slot frozen: each step's logits, the tokens
    and the final cache."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagedKVPool(cfg, 3, 64, page_size=16, kv_dtype=kv_dtype,
                       ctx_len=cfg.vision.num_image_tokens if cfg.vision
                       else 0)
    leaves, tree = jax.tree.flatten(pool.cache)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    cache = jax.tree.unflatten(tree, [
        jax.random.randint(k, a.shape, -127, 128).astype(a.dtype)
        if a.dtype == jnp.int8 else
        jax.random.normal(k, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a
        for k, a in zip(keys, leaves)])
    order = np.random.default_rng(2).permutation(3 * pool.pages_per_slot)
    table = jnp.asarray(pool.reserved + order.reshape(3, -1), jnp.int32)
    active = jnp.array([True, False, True])
    step = jax.jit(lambda c, tok, pos: model.apply(
        params, tok[:, None], mode="decode", positions=pos[:, None],
        cache=c, page_table=table, slot_active=active, unroll_scan=unroll))
    tok, pos = jnp.array([1, 2, 3]), jnp.array([5, 30, 47])
    logits, toks = [], []
    for _ in range(steps):
        out = step(cache, tok, pos)
        cache = {**out.cache, "page_table": table}
        logits.append(out.logits)
        tok = jnp.where(active, jnp.argmax(out.logits[:, 0], -1), tok)
        toks.append(tok)
        pos = pos + active
    return logits, toks, cache


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_paged_decode_scanned_matches_unrolled(layout, kv_dtype):
    """The layer scan carries the stacked arena and writes each layer's
    cells in place; the unrolled stack (the dry-run's lowering) writes the
    same cells at the same layer indices.  Tokens, logits and every cache
    leaf agree bit for bit, and the active slots' cells were written."""
    arch, reduce = LAYOUTS[layout]
    cfg = get_config(arch)
    cfg = cfg if reduce is None else cfg.reduced(**reduce)
    scanned = _paged_decode_steps(cfg, kv_dtype, unroll=False)
    unrolled = _paged_decode_steps(cfg, kv_dtype, unroll=True)
    for a, b in zip(jax.tree.leaves(scanned), jax.tree.leaves(unrolled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fresh = _paged_decode_steps(cfg, kv_dtype, unroll=False, steps=0)[2]
    written = [(new["k"], old["k"]) for new, old in zip(
        scanned[2]["prefix"] + list((scanned[2]["block"] or {}).values()),
        fresh["prefix"] + list((fresh["block"] or {}).values()))
        if "k" in old]
    assert written
    for new, old in written:
        assert not np.array_equal(new, old)


# --------------------------------------------------------------------------- #
# kernel: interpret-mode pallas vs host reference
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("int8", [False, True])
def test_paged_attention_kernel_matches_reference(rng, int8):
    from repro.kernels.paged_attention import paged_attention_pallas

    b, hq, hkv, d, ps, pages_per_slot, npages = 3, 4, 2, 32, 8, 4, 16
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    kp = rng.normal(size=(npages, ps, hkv, d)).astype(np.float32)
    vp = rng.normal(size=(npages, ps, hkv, d)).astype(np.float32)
    pt = rng.integers(1, npages, size=(b, pages_per_slot)).astype(np.int32)
    pos = np.array([5, 17, 31], np.int32)
    ks = vs = None
    if int8:
        from repro.kernels.quant_matmul import quantize_kv_int8
        kp, ks = quantize_kv_int8(kp)
        vp, vs = quantize_kv_int8(vp)
        kp, vp = np.asarray(kp), np.asarray(vp)
        ks, vs = np.asarray(ks), np.asarray(vs)
    ref = paged_attention_ref(q, kp, vp, pt, pos, k_scale=ks, v_scale=vs)
    out = paged_attention_pallas(q, kp, vp, pt, pos, k_scale=ks, v_scale=vs,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window, pos", [
    (12, [29, 5, 53, 40]),    # starts inside a page; shorter than the
                              # window; a wrapped ring; starts mid-page
    (16, [31, 15, 16, 47]),   # starts exactly on a page boundary
])
def test_windowed_paged_attention_kernel(rng, window, pos):
    """A windowed layer attends the positions after ``pos - window``.  The
    pages wholly before the window hold NaN: loading them and folding them
    in would poison the output, so matching the reference shows the kernel
    passed them by (the ring-wrapped slot reads every page)."""
    from repro.kernels.paged_attention import first_page, paged_attention_pallas

    b, hq, hkv, d, ps, per_slot = 4, 4, 2, 32, 8, 6
    total = ps * per_slot
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    kp = rng.normal(size=(b * per_slot + 1, ps, hkv, d)).astype(np.float32)
    vp = rng.normal(size=kp.shape).astype(np.float32)
    pt = 1 + np.arange(b * per_slot, dtype=np.int32).reshape(b, per_slot)
    pos = np.asarray(pos, np.int32)
    ref = paged_attention_ref(q, kp, vp, pt, pos, window=window)
    skipped = 0
    for i, p in enumerate(pos):
        first = int(first_page(p, window, ps, total))
        assert first == (0 if p >= total else max(0, p - window + 1) // ps)
        kp[pt[i, :first]] = vp[pt[i, :first]] = np.nan
        skipped += first
    assert skipped
    out = paged_attention_pallas(q, kp, vp, pt, pos, window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the CPU path reads the same cells
    np.testing.assert_allclose(
        np.asarray(ops.paged_attention(q, np.nan_to_num(kp),
                                       np.nan_to_num(vp), pt, pos,
                                       window=window)),
        np.asarray(ref), atol=2e-5, rtol=2e-5)
    # and the window is a real cut: full attention differs
    full = paged_attention_ref(q, np.nan_to_num(kp), np.nan_to_num(vp), pt,
                               pos)
    assert np.abs(np.asarray(full) - np.asarray(ref)).max() > 1e-2


def test_paged_reference_matches_dense_at_full_page(rng):
    """ps == cache_len, identity table -> the paged reference IS dense
    attention (the analytical core of the bit-exactness gate)."""
    b, hq, hkv, d, s = 2, 4, 2, 16, 32
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    pos = np.array([7, 31], np.int32)
    kv_valid = np.arange(s)[None, :] <= pos[:, None]
    ref = decode_attention_ref(q, k, v, kv_valid)
    pt = np.arange(b, dtype=np.int32)[:, None]           # slot -> page slot
    out = paged_attention_ref(q, k, v, pt, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
