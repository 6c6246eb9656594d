"""Build one serving replica the way ``repro.launch.serve.build_replica``
does (engine, admission control, ``EngineClient``), with the benchmark's
seeded weights passed in, and warm every program the cell's traffic uses.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core.admission import AdmissionController
from repro.core.engine import InferenceEngine
from repro.core.request import GenerationRequest, SamplingParams
from repro.launch.serve import build_parser, parse_tenant_spec
from repro.serving.client import EngineClient

WARM_SEED = 7            # warm-up prompts are the same in every run


def build(cfg, serve_flags: List[str], params) -> EngineClient:
    """``serve.py``'s defaults, overridden by the configuration's flags.

    One difference from ``build_replica``: the engine is not pinned to a
    device.  On one chip the placement is the same (JAX's default device),
    but pinning makes committed arrays (params, pool) meet uncommitted ones
    (zero prefill rows, the page table) in the same programs, and each mix
    compiles a program of its own: two ``decode_block`` programs per block
    size, and prefill programs per pattern of row origins."""
    args = build_parser().parse_args([str(f) for f in serve_flags])
    engine = InferenceEngine(
        cfg, params=params, max_batch=args.max_batch,
        cache_len=args.cache_len, seed=args.seed,
        enable_prefix_cache=not args.no_prefix_cache,
        enable_content_cache=not args.no_content_cache,
        cache_vision_embeddings=not args.no_vision_embed_cache,
        cache_vision_kv=not args.no_vision_kv_cache,
        content_cache_bytes=(None if args.content_cache_mb is None
                             else args.content_cache_mb * 1024 * 1024),
        vision_work_iters=args.vision_work_iters,
        encode_wave=args.encode_wave,
        max_decode_block=args.max_decode_block,
        top_p=args.top_p, top_k=args.top_k, min_p=args.min_p,
        prefill_chunk=args.prefill_chunk,
        max_prefill_buckets=args.max_prefill_buckets,
        sched_policy=args.sched_policy, preemption=args.preemption,
        max_preemptions=args.max_preemptions,
        speculative_fill=not args.no_spec_fill, aging_s=args.aging_s,
        kv_layout=args.kv_layout, kv_page_size=args.kv_page_size,
        kv_num_pages=args.kv_num_pages, kv_dtype=args.kv_dtype,
        spec_mode=args.spec_mode, spec_k=args.spec_k)
    admission = None
    if not args.no_admission:
        admission = AdmissionController(
            tenants=dict(parse_tenant_spec(s) for s in args.tenant),
            max_queue_depth=args.max_queue_depth,
            queue_timeout_s=args.queue_timeout,
            shed_queue_depth=args.shed_queue_depth,
            shed_wait_s=args.shed_wait)
    return EngineClient(engine, admission=admission,
                        watchdog_timeout_s=(args.watchdog_timeout
                                            if args.watchdog_timeout > 0
                                            else None))


def buckets(engine) -> List[int]:
    """Every prefill bucket the engine can pick (powers of two from its
    floor up to the chunk size, capped at the cache length)."""
    top = min(engine.pool.cache_len,
              engine._plan_bucket(min(engine.pool.cache_len,
                                      engine.prefill_chunk
                                      or engine.pool.cache_len)))
    out, b = [], engine._bucket_floor
    while b < top:
        out.append(b)
        b *= 2
    return out + [top]


def _wave(client, rng, rows: int, length: int, max_tokens: int,
          sampling: dict) -> None:
    """``rows`` distinct prompts submitted at one block boundary, so they
    prefill as one wave and commit their first tokens together."""
    vocab = client.engine.cfg.vocab_size
    reqs = [GenerationRequest(
        prompt=rng.integers(0, vocab, length).tolist(),
        sampling=SamplingParams(max_tokens=max_tokens, **sampling))
        for _ in range(rows)]
    with client._cv:            # re-entrant: submit takes it too
        handles = [client.submit(r) for r in reqs]
    for h in handles:
        res = h.result(timeout=1200)
        reason = res.choices[0].finish_reason
        if reason not in ("length", "stop"):
            raise RuntimeError(f"warm-up request finished {reason!r}")


def warm(client, traffic: dict) -> float:
    """Run every (prefill bucket, wave rows) program, every decode-block
    size and every first-token batch the traffic can reach.  Returns the
    seconds it took."""
    t0 = time.perf_counter()
    engine = client.engine
    rng = np.random.default_rng(WARM_SEED)
    sampling = dict(traffic.get("sampling", {}))
    # decode blocks of K = 1, 2, 4, 8 follow from 1, 2, 4, 8 tokens left
    lengths = [2, 3, 5, engine.max_decode_block + 1]
    rows = [int(r) for r in traffic["warm_rows"]]
    i = 0
    for b in buckets(engine):
        for r in rows:
            _wave(client, rng, r, b, lengths[i % len(lengths)], sampling)
            i += 1
    smallest = buckets(engine)[0]
    for k in range(1, int(traffic["warm_first_tokens"]) + 1):
        if k not in rows:
            _wave(client, rng, k, smallest, lengths[i % len(lengths)],
                  sampling)
            i += 1
    return time.perf_counter() - t0
