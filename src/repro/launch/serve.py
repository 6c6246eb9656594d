"""Serving launcher: start the OpenAI-compatible server over the continuous
batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b-toy \\
      --port 8177 --max-batch 8

``build_parser``, ``load_configs`` and ``build_replica`` are the pieces
``main`` assembles; ``chip_smoke.py`` builds its server from the same ones.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
from pathlib import Path
from typing import Optional, Tuple

import jax

from repro.configs import ModelConfig, get_config
from repro.core.admission import AdmissionController, TenantConfig
from repro.core.engine import InferenceEngine
from repro.core.faults import FaultInjector, parse_fault_rates
from repro.serving.api import OpenAIServer
from repro.serving.asgi import AsgiServer, uvicorn_available
from repro.serving.client import EngineClient
from repro.serving.router import ROUTER_POLICIES, Router
from repro.serving.server import ApiServer


def parse_tenant_spec(spec: str) -> tuple:
    """``name=weight[:rps[:tps]]`` → (name, TenantConfig)."""
    if "=" not in spec:
        raise ValueError(f"tenant spec {spec!r} must look like "
                         "name=weight[:rps[:tps]]")
    name, _, rest = spec.partition("=")
    parts = rest.split(":")
    weight = float(parts[0]) if parts[0] else 1.0
    rps = float(parts[1]) if len(parts) > 1 and parts[1] else 0.0
    tps = float(parts[2]) if len(parts) > 2 and parts[2] else 0.0
    return name.strip(), TenantConfig(weight=weight, rps=rps, tps=tps)


# <checkout>/.jax_cache: a fixed path (never a temp name, pid or time), so a
# second run finds what the first one compiled
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself); otherwise the cache lives in the checkout's ``.jax_cache``.
    Called by entry points only — never on import — so library users and
    the tests stay uncached.  Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b-toy")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--port", type=int, default=8177)
    ap.add_argument("--seed", type=int, default=0)
    # -- multi-replica serving (PR 10; DESIGN_router.md) ----------------- #
    ap.add_argument("--replicas", type=int, default=1,
                    help="in-process engine replicas behind the router "
                         "(1 = single engine, no router layer)")
    ap.add_argument("--router-policy", choices=ROUTER_POLICIES,
                    default="affinity",
                    help="replica placement: affinity (session pin -> "
                         "prefix-digest match -> least outstanding "
                         "tokens), least_loaded, round_robin, random")
    ap.add_argument("--transport", choices=("asgi", "threaded"),
                    default="asgi",
                    help="HTTP transport: asyncio-native ASGI app "
                         "(uvicorn when installed, bundled asyncio "
                         "server otherwise — no thread per SSE "
                         "connection), or the legacy thread-per-"
                         "connection stdlib server")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--no-content-cache", action="store_true")
    ap.add_argument("--no-vision-embed-cache", action="store_true",
                    help="content-cache ablation: keep cross-KV entries but "
                         "re-encode every frame (paper Table 4 'KV-only')")
    ap.add_argument("--no-vision-kv-cache", action="store_true",
                    help="content-cache ablation: keep frame embeddings but "
                         "re-project cross-KV (paper Table 4 "
                         "'embeddings-only')")
    ap.add_argument("--content-cache-mb", type=int, default=None,
                    help="byte budget for the content cache in MiB "
                         "(default: share the prefix cache's 512 MiB "
                         "budget figure)")
    ap.add_argument("--vision-work-iters", type=int, default=8,
                    help="vision/audio encoder work multiplier (stubbed "
                         "encoder cost; higher = heavier encode, larger "
                         "cache wins)")
    ap.add_argument("--encode-wave", type=int, default=4,
                    help="unique media encodes per engine step (0 = "
                         "unbounded): batches concurrent encoder work "
                         "behind the decode block and streams large video "
                         "frame-sets across steps")
    ap.add_argument("--max-decode-block", type=int, default=8,
                    help="decode tokens per host sync (1 = per-token loop)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="default nucleus mass for requests that omit "
                         "'top_p' (per-request values win; 1 = off)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="default top-k cutoff for requests that omit "
                         "'top_k' (per-request values win; 0 = off)")
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="default min-p mass floor for requests that omit "
                         "'min_p' (per-request values win; 0 = off)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="prompt tokens prefilled per engine step "
                         "(0 = monolithic prefill; smaller = flatter TTFT "
                         "under long-prompt load)")
    ap.add_argument("--max-prefill-buckets", type=int, default=6,
                    help="cap on distinct compiled prefill bucket shapes "
                         "(smaller = more padding, less compile churn)")
    ap.add_argument("--sched-policy", default="fifo",
                    choices=["fifo", "priority", "edf"],
                    help="request ordering for admission and the prefill "
                         "chunk queue: fifo (arrival), priority (request "
                         "'priority' field), edf (earliest 'deadline_ms' "
                         "first; deadline-less requests sort last)")
    ap.add_argument("--preemption", action="store_true",
                    help="let an urgent pending request (per --sched-policy; "
                         "fifo never preempts) evict the least urgent "
                         "active slot; the evicted request resumes "
                         "bit-identically from its snapshot under greedy "
                         "decode")
    ap.add_argument("--max-preemptions", type=int, default=2,
                    help="max times one request may be evicted (bounds "
                         "preemption churn)")
    ap.add_argument("--no-spec-fill", action="store_true",
                    help="disable speculative wave filling (backfilling "
                         "prefill-wave padding rows with chunks of "
                         "not-yet-admitted pending requests)")
    # -- overload protection (PR 6; DESIGN_overload_and_faults.md) ------- #
    ap.add_argument("--no-admission", action="store_true",
                    help="disable admission control entirely (no rate "
                         "limits, no fair queue, no shedding — the "
                         "engine's unbounded pending queue)")
    ap.add_argument("--max-queue-depth", type=int, default=256,
                    help="hard bound on waiting requests; beyond it every "
                         "submit gets a structured 503 + Retry-After")
    ap.add_argument("--queue-timeout", type=float, default=30.0,
                    help="seconds a request may wait for admission before "
                         "it expires with a typed 'timeout' finish "
                         "(0 = never)")
    ap.add_argument("--shed-queue-depth", type=int, default=None,
                    help="queue depth where batch-class shedding starts "
                         "(default max-queue-depth/2)")
    ap.add_argument("--shed-wait", type=float, default=10.0,
                    help="estimated queue wait (s) that triggers "
                         "batch-class shedding; 2x sheds everything "
                         "(0 = depth thresholds only)")
    ap.add_argument("--tenant", action="append", default=[],
                    metavar="NAME=WEIGHT[:RPS[:TPS]]",
                    help="per-tenant fair-share weight and rate limits "
                         "(repeatable); requests select a tenant via the "
                         "OpenAI 'user' field or x-tenant header")
    ap.add_argument("--aging-s", type=float, default=None,
                    help="anti-starvation aging horizon for priority/edf "
                         "policies: a request's effective priority rises "
                         "one level per aging-s seconds waited "
                         "(default: policy-specific; 0 disables)")
    ap.add_argument("--watchdog-timeout", type=float, default=60.0,
                    help="flip /readyz and log loudly when one engine "
                         "step wedges longer than this (0 = no watchdog)")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="graceful-drain budget on SIGTERM / /admin/drain: "
                         "in-flight work gets this long to finish before "
                         "live slots are snapshotted and aborted")
    ap.add_argument("--fault-rate", action="append", default=[],
                    metavar="SITE=P",
                    help="chaos harness: deterministic fault injection "
                         "rate per site (prefill/decode/codec/slow_step/"
                         "pool; repeatable) — see core/faults.py")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault injector")
    ap.add_argument("--kv-layout", choices=("dense", "paged"),
                    default="dense",
                    help="KV cache layout: dense per-slot ring, or paged "
                         "global arena with copy-on-write prefix sharing "
                         "(DESIGN_paged_kv.md)")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="tokens per KV page (paged layout; default matches "
                         "the prefix-cache block size)")
    ap.add_argument("--kv-num-pages", type=int, default=None,
                    help="page-arena size (paged layout); default sizes for "
                         "full max-batch capacity + reserved pages")
    ap.add_argument("--kv-dtype", choices=("fp", "int8"), default="fp",
                    help="KV page storage: model dtype, or int8 with "
                         "per-(position, head) scales (paged layout only)")
    # -- speculative decoding (PR 9; DESIGN_spec_decode.md) -------------- #
    ap.add_argument("--spec-mode", choices=("off", "ngram", "draft"),
                    default="off",
                    help="speculative decoding: off, self-speculative "
                         "n-gram drafting from the request's own history, "
                         "or a paired draft model (--spec-draft-config)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per round (the "
                         "scheduler halves/zeroes it when acceptance "
                         "drops or pending work needs the batch)")
    ap.add_argument("--spec-draft-config", default=None,
                    help="registered model config name for the draft "
                         "model (--spec-mode draft); must share the "
                         "target's vocab and be text-only attention")
    return ap


def load_configs(args: argparse.Namespace
                 ) -> Tuple[ModelConfig, Optional[object]]:
    """(model config, draft config name or config) for parsed ``args``."""
    cfg = get_config(args.arch)
    spec_draft = args.spec_draft_config
    if args.smoke:
        cfg = cfg.reduced()
        if spec_draft is not None:
            # shrink the draft alongside the target, or its full-size vocab
            # can never match the reduced target's
            spec_draft = get_config(spec_draft).reduced()
    return cfg, spec_draft


def build_replica(args: argparse.Namespace, cfg: ModelConfig, *,
                  index: int = 0, spec_draft: Optional[object] = None,
                  faults: Optional[FaultInjector] = None) -> EngineClient:
    """One engine + admission + lifecycle client.  Replicas share the seed,
    so they are weight-identical — the property drain/handoff bit-identity
    rests on.  Replica ``index`` keeps its params, KV cache and decode
    state on ``jax.devices()[index]`` (modulo the device count), so N
    replicas on an N-chip host each own a chip."""
    devices = jax.devices()
    engine = InferenceEngine(
        cfg, max_batch=args.max_batch, cache_len=args.cache_len,
        seed=args.seed, enable_prefix_cache=not args.no_prefix_cache,
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
        sched_policy=args.sched_policy,
        preemption=args.preemption,
        max_preemptions=args.max_preemptions,
        speculative_fill=not args.no_spec_fill,
        aging_s=args.aging_s,
        faults=faults,
        kv_layout=args.kv_layout,
        kv_page_size=args.kv_page_size,
        kv_num_pages=args.kv_num_pages,
        kv_dtype=args.kv_dtype,
        spec_mode=args.spec_mode,
        spec_k=args.spec_k,
        spec_draft_config=spec_draft,
        device=devices[index % len(devices)])
    admission = None
    if not args.no_admission:
        admission = AdmissionController(
            tenants=dict(parse_tenant_spec(s) for s in args.tenant),
            max_queue_depth=args.max_queue_depth,
            queue_timeout_s=args.queue_timeout,
            shed_queue_depth=args.shed_queue_depth,
            shed_wait_s=args.shed_wait)
    return EngineClient(
        engine, admission=admission,
        watchdog_timeout_s=(args.watchdog_timeout
                            if args.watchdog_timeout > 0 else None))


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg, spec_draft = load_configs(args)
    print(f"loading {cfg.name} ({cfg.param_count()/1e6:.1f}M params)...")
    faults = None
    rates = parse_fault_rates(args.fault_rate)
    if rates:
        faults = FaultInjector(seed=args.fault_seed, rates=rates)
        print(f"chaos: fault injection active {rates} (seed {args.fault_seed})")
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")

    if args.replicas > 1:
        client = Router([build_replica(args, cfg, index=i,
                                       spec_draft=spec_draft, faults=faults)
                         for i in range(args.replicas)],
                        policy=args.router_policy, seed=args.seed)
        print(f"router: {args.replicas} replicas, "
              f"policy={args.router_policy}")
    else:
        client = build_replica(args, cfg, spec_draft=spec_draft,
                               faults=faults)
    api = OpenAIServer(client, cfg.name)
    if args.transport == "asgi":
        server = AsgiServer(api, port=args.port)
        impl = "uvicorn" if uvicorn_available() else "bundled asyncio"
    else:
        server = ApiServer(api, port=args.port)
        impl = "threaded http.server"
    server.start()
    print(f"listening on http://127.0.0.1:{server.port} [{impl}] "
          "(chat + completions + models; stats: /stats; health: /healthz "
          "/readyz; drain: POST /admin/drain or SIGTERM)")

    # SIGTERM → graceful drain: stop admitting, finish in-flight work
    # (bounded by --drain-timeout), snapshot + abort the rest, exit 0
    drained = threading.Event()

    def _sigterm(_sig, _frm):
        print(f"SIGTERM: draining (timeout {args.drain_timeout:g}s)...")
        threading.Thread(
            target=lambda: (client.drain(timeout=args.drain_timeout),
                            drained.set()),
            daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        while not drained.wait(timeout=1.0):
            pass
        print("drain complete; exiting")
        server.stop()
        sys.exit(0)
    except KeyboardInterrupt:
        server.stop()
        client.stop()


if __name__ == "__main__":
    main()
