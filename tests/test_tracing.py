"""The engine's own instrumentation (repro/tracing.py): counters checked
against a hand count on a toy engine, host spans once per decode block in a
CPU profiler trace with their stats, the counters served by ``/stats``, and
device scopes that leave the compiled programs unchanged."""
import contextlib
import glob
import math
import os
import time
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs import get_config
from repro.core.admission import AdmissionController
from repro.core.engine import InferenceEngine
from repro.core.request import Request, SamplingParams
from repro.kernels import paged_attention
from repro.serving.client import EngineClient
from repro.serving.tokenizer import ByteTokenizer

TOK = ByteTokenizer()
PAGE = 16
COUNTERS = ("queue_wait_s", "prefill_wait_s", "first_tokens", "host_gap_s",
            "host_gaps", "prefill_tokens", "prefill_pad_tokens",
            "kv_live_pages", "kv_grid_pages")
SCOPES = ("kv_arena", "attention_kernel", "mlp", "head", "sampler")


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen3-0.6b-toy")


def _engine(cfg, **kw):
    kw = dict(dict(max_batch=4, cache_len=128, kv_layout="paged",
                   kv_page_size=PAGE, max_decode_block=4, prefill_chunk=32),
              **kw)
    return InferenceEngine(cfg, **kw)


def _reqs(n):
    """Unshared prompts of 13 to 13 + 12 (n - 1) tokens (up to three
    32-token chunks, so waves pad both the bucket and the rows) and outputs
    of different lengths."""
    return [Request(prompt_tokens=TOK.encode(chr(ord("a") + i) * (13 + 12 * i)),
                    sampling=SamplingParams(max_tokens=6 + 3 * i))
            for i in range(n)]


class Spy:
    """Records what each decode dispatch and prefill wave group was given,
    from the engine's own state, for a count by hand."""

    def __init__(self, eng):
        self.blocks, self.waves = [], []
        decode, wave = eng._decode_block_fn, eng._run_wave_group

        def decode_block(*args, num_steps, **kw):
            live = [(len(r.prompt_tokens) + r.num_generated - 1,
                     r.sampling.max_tokens - r.num_generated)
                    for s, r in eng.scheduler.active.items()
                    if s in eng._live_slots and not r.is_finished]
            self.blocks.append((num_steps, live))
            return decode(*args, num_steps=num_steps, **kw)

        def run_wave_group(bucket, cross_cached, rows):
            self.waves.append((bucket, len(rows), [t for _, t in rows]))
            return wave(bucket, cross_cached, rows)

        eng._decode_block_fn = decode_block
        eng._run_wave_group = run_wave_group


def test_counters_match_a_hand_count(cfg):
    eng = _engine(cfg)
    spy = Spy(eng)
    reqs = _reqs(6)
    eng.generate(reqs)
    st = eng.scheduler.stats

    # pad tokens = bucket × rows padded to a power of two − real tokens
    real = sum(sum(takes) for _, _, takes in spy.waves)
    padded = sum(b * (1 << (k - 1).bit_length()) for b, k, _ in spy.waves)
    assert st.prefill_tokens == real == sum(len(r.prompt_tokens)
                                            for r in reqs)
    assert st.prefill_pad_tokens == padded - real > 0

    # live pages step by step from each slot's position and budget; the
    # grid visits every page each of the pool's slots can hold
    pages_per_slot = eng.pool.cache_len // PAGE
    live = sum(min(pages_per_slot, math.ceil((pos + 1 + s) / PAGE))
               for k, slots in spy.blocks for pos, left in slots
               for s in range(min(k, left)))
    grid = math.prod(paged_attention.grid(4, pages_per_slot))
    assert st.kv_live_pages == live
    assert st.kv_grid_pages == sum(k for k, _ in spy.blocks) * grid
    assert 0 < st.kv_live_pages < st.kv_grid_pages

    # waits are non-negative and sum per request (no preemption here)
    queue = [r.bind_time - r.arrival_time for r in reqs]
    prefill = [r.first_token_time - r.bind_time for r in reqs]
    assert min(queue) >= 0 and min(prefill) >= 0
    assert st.queue_wait_s == pytest.approx(sum(queue), abs=1e-9)
    assert st.prefill_wait_s == pytest.approx(sum(prefill), abs=1e-9)
    assert st.first_tokens == st.admitted == len(reqs)
    # one gap between each pair of consecutive blocks of one stream
    assert 0 < st.host_gaps < len(spy.blocks)
    assert st.host_gap_s > 0


def test_host_gap_does_not_grow_while_no_slot_is_live(cfg):
    eng = _engine(cfg)
    spy = Spy(eng)
    eng.generate(_reqs(2))                 # compiles every shape used below
    eng.generate(_reqs(2))
    gap_s, gaps, blocks = (eng.scheduler.stats.host_gap_s,
                           eng.scheduler.stats.host_gaps, len(spy.blocks))
    time.sleep(1.0)                        # no slot is live
    for _ in range(3):
        eng.step()                         # idle steps dispatch nothing
    eng.generate(_reqs(2))
    st = eng.scheduler.stats
    # the stream restarted: its first block has no gap before it
    assert st.host_gaps - gaps == len(spy.blocks) - blocks - 1
    assert st.host_gap_s - gap_s < 1.0


def _trace(fn, log_dir):
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans.extend((ev.name[len(tracing.PREFIX):], dict(ev.stats))
                         for ev in line.events
                         if ev.name.startswith(tracing.PREFIX))
    return spans


def test_spans_once_per_block_with_their_stats(cfg, tmp_path):
    eng = _engine(cfg)
    client = EngineClient(eng, admission=AdmissionController())
    client.start()
    try:
        for r in _reqs(3):                  # warm every shape first
            client.submit(r).result()
        spy = Spy(eng)
        steps0 = eng._fault_tick
        reqs = _reqs(3)
        spans = _trace(lambda: [h.result() for h in
                                [client.submit(r) for r in reqs]], tmp_path)
    finally:
        client.stop()
    count = Counter(name for name, _ in spans)
    steps = eng._fault_tick - steps0
    blocks = len(spy.blocks)
    assert count["engine.step"] == count["engine.admit"] == steps
    assert count["engine.commit"] == steps
    assert count["engine.decode_dispatch"] == count["engine.kv_reserve"]
    assert count["engine.decode_dispatch"] == blocks > 0
    assert count["engine.sync"] == count["engine.emit"] == blocks
    assert count["engine.prefill_dispatch"] == len(spy.waves) > 0
    # the loop wraps each step in one admission round and one delivery;
    # the last delivery may still be open when the trace stops
    assert count["client.admission"] >= steps
    assert count["client.deliver"] >= steps - 1

    dispatch = [st for name, st in spans if name == "engine.decode_dispatch"]
    order = [st["block"] for st in dispatch]
    assert order == sorted(order) and len(set(order)) == blocks
    for st, (k, live) in zip(dispatch, spy.blocks):
        n = [min(k, left) for _, left in live]
        assert (st["k"], st["live"], st["tokens"]) == (k, len(live), sum(n))
        assert st["ctx_tokens"] == sum(pos + 1 + s for (pos, _), m in
                                       zip(live, n) for s in range(m))
    waves = [st for name, st in spans if name == "engine.prefill_dispatch"]
    for st, (bucket, rows, takes) in zip(waves, spy.waves):
        assert (st["bucket"], st["rows"], st["tokens"]) == (bucket, rows,
                                                           sum(takes))
        assert st["padded_rows"] == 1 << (rows - 1).bit_length()
        assert st["pad_tokens"] == bucket * st["padded_rows"] - sum(takes)
    assert all("jobs" in st for name, st in spans if name == "engine.commit")


def test_stats_endpoint_carries_the_counters(cfg):
    from repro.serving.api import OpenAIServer

    eng = _engine(cfg)
    eng.generate(_reqs(2))
    st = OpenAIServer(eng, "toy").stats()
    for key in COUNTERS:
        assert st[key] == getattr(eng.scheduler.stats, key)
        assert isinstance(st[key], (int, float))
    assert st["first_tokens"] == 2 and st["kv_grid_pages"] > 0


def test_moe_rows_counts_every_held_pair_at_the_block_sync():
    """``moe_rows``: token-expert pairs the held experts computed in decode,
    and ``moe_experts``: held experts that computed any, read back with each
    block's tokens.  With every expert held each of the ``max_batch`` rows
    of every layer and step is one of them, k times; with half held, fewer.
    A layer's step visits at least k and at most the held experts.  The
    host syncs once per block as before, and ``/stats`` serves the
    counters."""
    import dataclasses
    from repro.serving.api import OpenAIServer
    base = get_config("qwen3-30b-a3b-toy").reduced()
    e, k = base.moe.num_experts, base.moe.experts_per_token
    counts = {}
    for held in ((0, e), (0, e // 2)):
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, held=held))
        eng = _engine(cfg)
        reqs = _reqs(3)
        eng.generate(reqs)
        st = eng.scheduler.stats
        assert st.steps == eng._decode_blocks     # one sync per block
        counts[held] = (st.moe_rows, st.moe_experts, st.device_steps)
        served = OpenAIServer(eng, "toy").stats()
        assert served["moe_rows"] == st.moe_rows
        assert served["moe_experts"] == st.moe_experts
    rows, experts, steps = counts[(0, e)]
    calls = steps * base.num_layers
    assert rows == calls * 4 * k                       # max_batch 4
    assert calls * k <= experts <= calls * e
    half, experts_half, steps_half = counts[(0, e // 2)]
    assert 0 < half < steps_half * base.num_layers * 4 * k
    assert 0 < experts_half <= steps_half * base.num_layers * e // 2


def _programs(cfg):
    """Optimised HLO of a decode block and of a prefill wave, printed with
    canonical names and no metadata, and the metadata-carrying text."""
    from jax._src.lib import xla_client

    eng = _engine(cfg)
    single = eng.pool.single_cache_zeros()
    rows = jnp.zeros((2, 32), jnp.int32)
    compiled = [
        eng._decode_block_fn.lower(eng.params, eng.pool.cache, eng.state,
                                   num_steps=2, want_logprobs=False).compile(),
        eng._prefill_fn(32, 2, False).lower(
            eng.params, rows, rows, (single, single), None, None,
            jnp.ones((2, 32), bool), jnp.zeros((2,), jnp.int32)).compile()]
    canonical = xla_client._xla.HloPrintOptions.canonical()
    return ([c.runtime_executable().hlo_modules()[0].to_string(canonical)
             for c in compiled], [c.as_text() for c in compiled])


def test_scopes_leave_the_programs_unchanged(cfg, monkeypatch):
    scoped, text = _programs(cfg)
    for name in SCOPES:
        assert f"/{name}/" in text[0], name          # decode block
    for name in ("kv_arena", "attention_kernel", "mlp", "head"):
        assert f"/{name}/" in text[1], name          # prefill wave
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain, text = _programs(cfg)
    assert "kv_arena" not in text[0] + text[1]
    assert scoped == plain
