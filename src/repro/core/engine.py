"""The inference engine: continuous batching + two-level caching (the paper's
system, TPU-shaped), with a device-resident block-decode hot loop and a
chunked, batched, decode-overlapped admission pipeline.

Flow per ``step()`` (paper Alg.1, loop body advancing K tokens per host
iteration):
  1. **Plan admissions**: pending requests bind to free decode slots.  Each
     opens a *prefill job*: media pipeline (content-cache hits skip the
     encoder — Alg.3), text/multimodal prefix-cache lookup (skips the
     forward pass for cached tokens — Alg.2).  Jobs park in the scheduler's
     chunk queue.
  2. **Dispatch a decode block** (if any slot is live): a single compiled
     ``decode_block`` runs K decode+sample iterations inside
     ``jax.lax.scan`` — sampling, RNG splitting, stop-token detection and
     budget accounting all happen on-device.  A slot that samples a stop
     token or exhausts its budget is frozen by an on-device finished-mask
     (masked cache writes, no position advance) for the rest of the block.
     K is adaptive (``scheduler.plan_decode_block``): bounded by the
     ``max_decode_block`` knob and the smallest remaining budget among
     active slots, and collapsing to 1 while requests or prefill chunks are
     waiting, so admission/TTFT latency stays one token.
  3. **Dispatch a prefill wave** *before* blocking on the decode block's
     token sync, so prefill compute hides behind the block's host-sync
     window.  The wave packs every queued job's next chunk into right-padded
     ``[k, bucket]`` batched forward passes (per-row length masks via
     ``seq_valid``, per-row prefix-cache resume offsets via per-row
     positions) — one compiled call per (bucket, rows, cross-cached) group
     instead of k sequential batch=1 prefills.  Long prompts advance
     ``prefill_chunk`` tokens per step (carrying KV/SSM state across
     chunks), so an 8k-token prompt no longer monopolises the engine between
     decode blocks; intermediate chunk boundaries publish to the prefix
     cache so an identical prompt right behind reuses finished chunks.
     Right-padding is fully masked (masked KV writes, identity SSM updates,
     no MoE capacity use), so the final cache is **bit-identical** to a
     monolithic unchunked prefill.
  4. **Sync + emit**: the host syncs once per block (the ``np.asarray`` on
     the returned ``[K, B]`` token block), emits/retires, then commits
     completed prefills — one multi-slot cache scatter
     (``SlotKVPool.insert_many``), one scatter into the device-resident
     :class:`~repro.core.kv_cache.DecodeState`, and one batched first-token
     sample for the whole wave.  Retired requests publish their prompt KV
     state to the prefix cache (byte-budget LRU) and free the slot; frozen
     -slot cache writes are masked on-device, so the published state is
     bit-identical to what the single-step engine would publish.

Scheduling is policy-driven (``sched_policy`` ∈ {fifo, priority, edf} — see
:mod:`repro.core.scheduler`): the policy orders admission, the chunk queue,
and — with ``preemption=True`` under a preemptive policy — lets an urgent
pending request evict the least urgent live decode slot.  Eviction
snapshots the slot's cache and publishes it as an exact-sequence
prefix-cache entry (byte-budget LRU), so the evicted request resumes
bit-identically under greedy decode; a snapshot lost to cache pressure
falls back to re-prefilling the prompt+generated history.  **Speculative
wave filling** (``speculative_fill``, default on) backfills the power-of
-two padding rows of each prefill wave with first chunks of not-yet
-admitted pending requests — partial KV is carried engine-side and
published to the prefix cache at chunk boundaries, so the head-start is
never wasted even if the request is admitted elsewhere or much later.

``max_decode_block=1`` reproduces the per-token engine exactly (same event
order).  Greedy outputs are invariant to K, to ``prefill_chunk``, to
wave packing, to speculative filling, to preemption/resume, and — for the
surviving slots — to aborts of their neighbours.

**Request lifecycle** (see DESIGN_engine_client.md): every request moves
QUEUED → PREFILLING → DECODING → FINISHED, with DECODING → QUEUED on
preemption.  :meth:`InferenceEngine.abort` cancels a request wherever it
currently lives — pending queue, speculative job table, prefill chunk
queue, eviction-snapshot table, or a live decode slot — freeing the slot
immediately (the device row is frozen, so the next decode block ignores
it and the next admission reuses it).  Host-side *stop sequences*
(``SamplingParams.stop_sequences``) are enforced at block emit with the
partial match held back from the stream and the match truncated away;
per-token logprobs (``SamplingParams.logprobs``/``top_logprobs``) ride the
decode block as an optional second output (separate compiled variant, same
sampling RNG, so enabling them never changes the tokens).

**Per-request sampling** lives in the device-resident ``DecodeState``:
every slot carries its own ``temperature``/``top_p``/``top_k``/``min_p``
and its request's base PRNG key, applied inside the compiled block by one
shape-stable masked kernel (sort + cumulative-mass threshold at fixed
vocab width — heterogeneous batches never recompile; see
``core/sampling.py``).  Per-token keys are stateless
(``fold_in(base, position)``), so a slot's sampled stream is independent
of its neighbours, of K, and of preemption/resume; a request with an
explicit ``seed`` replays bit-identically across runs.  Engine-level
``top_p``/``top_k``/``min_p`` knobs are per-request fallbacks.

Cost-structure fidelity to the paper's ablation (Table 4): the media
pipeline always runs unless the *content* cache hits (so "KV-only" caching
still pays the encoder, reproducing the paper's 1.2x), and the prefix cache
skips prompt processing only (embeddings-only still pays it: 7.8x vs 19x).
"""
from __future__ import annotations

import functools
import logging
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core.content_cache import (ContentCache, CrossKVEntry,
                                      EmbeddingEntry, MediaStats,
                                      content_hash, media_set_digest)
from repro.core.faults import FaultInjector
from repro.core.kv_cache import (DecodeState, SlotKVPool, admit_decode_state,
                                 concat_cache_rows, init_decode_state,
                                 select_cache_slots, slice_cache_row,
                                 tree_bytes)
from repro.core.paged_kv import (PagedKVPool, PagePoolExhausted,
                                 select_cache_slots_paged)
from repro.core.prefix_cache import TextPrefixCache
from repro.core.request import (FinishReason, PromptTooLongError, Request,
                                RequestStatus, StreamEvent)
from repro.core.sampling import (masked_sample, masked_sample_inner,
                                 request_base_key, validate_sampling_params)
from repro.core.scheduler import ContinuousBatchingScheduler, SchedulingPolicy
from repro.core.spec_decode import (DraftModelSource, DraftSource,
                                    NGramDraftSource, SpecController,
                                    SpecStats, build_spec_verify_fn,
                                    stage_drafts)
from repro.core.streaming import StopSequenceChecker, TokenStreamDecoder
from repro.models import build_model
from repro.models.model import init_cache
from repro.serving.media import AudioEncoderStub, VisionEncoderStub, decode_media
from repro.serving.tokenizer import ByteTokenizer


log = logging.getLogger("repro.engine")


def _next_bucket(n: int, floor: int = 16) -> int:
    """Smallest power-of-two bucket ≥ n (≥ floor) — prefill shapes come from
    a small fixed set so compiled-variant churn stays bounded."""
    b = floor
    while b < n:
        b *= 2
    return b


@dataclass
class _Admission:
    """One prefilled request, staged for the batched wave commit."""
    slot: int
    req: Request
    single_cache: Any
    first_token: int
    ctx_valid: Optional[np.ndarray]      # [T] bool or None
    seq_len: int                         # tokens materialised in the cache
    logprob: Optional[float] = None      # first-token logprob (if requested)
    top_logprobs: Optional[List[Tuple[int, float]]] = None


@dataclass
class _PrefillJob:
    """One request's prefill in flight: the partial cache is carried across
    chunks outside the batch pool, and the job re-enters the scheduler's
    chunk queue until the whole sequence is materialised.

    ``slot is None`` marks a *speculative* job: the request is still
    pending (no free slot), but its chunks ride the leftover power-of-two
    padding rows of admitted waves so prefill work starts before admission.
    A speculative job lives in the engine's ``_spec_jobs`` table, not the
    chunk queue; when its request is admitted the job is bound to the slot
    and continues (or commits directly, if the prompt already finished —
    the staged ``logits`` row becomes the first-token sample).

    ``tokens`` is the sequence being materialised — the prompt for a fresh
    request, prompt+generated history for a preempted request whose
    eviction snapshot was lost to cache pressure."""
    slot: Optional[int]
    req: Request
    tokens: List[int]                    # sequence to materialise
    cache: Any                           # batch=1 cache pytree (partial)
    consumed: int                        # tokens materialised so far
    embeds: Optional[np.ndarray]         # [1, T, De] media embeddings | None
    ctx_valid: Optional[np.ndarray]      # [1, T] bool | None
    cross_cached: bool                   # cross-KV restored from content cache
    publish_xkv: bool                    # publish cross-KV after first chunk
    t0: float                            # admission start (prefill_time)
    partial_key: Optional[str] = None    # rolling chunk-boundary prefix entry
    logits: Optional[Any] = None         # staged last-row logits (speculative
                                         # job finished before a slot freed)


@dataclass
class _MediaItem:
    """One media payload of a request, resolved to an embedding either by a
    content-cache hit at job open or by an encode wave."""
    hash: str
    ntok: int                            # context tokens this item occupies
    emb: Optional[np.ndarray] = None     # [ntok, De] once resolved


@dataclass
class _MediaJob:
    """A request's media set being resolved ahead of admission: payloads are
    decoded + hashed once at job open, embedding-cache hits resolve items
    immediately, and the rest wait on shared in-flight encode tasks.  The
    request stays pending (media-ineligible for admission) until
    ``remaining == 0``; a 64-frame video therefore streams through encode
    waves across steps instead of stalling an admission synchronously."""
    req: Request
    items: List["_MediaItem"]
    remaining: int                       # items still awaiting an embedding


@dataclass
class _EncodeTask:
    """One *unique* pending encode, keyed by content hash — the singleflight
    entry.  Every request whose media set needs this hash registers as a
    waiter; the encode wave runs the encoder exactly once and delivers the
    embedding to all of them, so N concurrent requests carrying the same
    viral image cost one encoder invocation (asserted by counter)."""
    hash: str
    pixels: np.ndarray
    encoder: Any
    ntok: int
    waiters: List[_MediaJob]


class InferenceEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Optional[Any] = None,
        *,
        tokenizer: Optional[ByteTokenizer] = None,
        max_batch: int = 8,
        cache_len: int = 256,
        seed: int = 0,
        enable_prefix_cache: bool = True,
        prefix_block_size: int = 16,
        enable_content_cache: bool = True,
        cache_vision_embeddings: bool = True,
        cache_vision_kv: bool = True,
        cache_max_bytes: int = 512 * 1024 * 1024,
        content_cache_bytes: Optional[int] = None,  # None = cache_max_bytes
        encode_wave: int = 4,            # unique encodes per step (0 = all)
        top_k: int = 0,
        top_p: float = 1.0,
        min_p: float = 0.0,
        frame_tokens: Optional[int] = None,
        max_media_items: int = 4,
        vision_work_iters: int = 8,
        max_decode_block: int = 8,
        max_stop_tokens: int = 8,
        max_top_logprobs: int = 5,
        truncate_long_prompts: bool = False,
        prefill_chunk: int = 512,
        max_prefill_buckets: int = 6,
        sched_policy: Union[str, SchedulingPolicy] = "fifo",
        preemption: bool = False,
        max_preemptions: int = 2,
        speculative_fill: bool = True,
        max_spec_jobs: Optional[int] = None,
        aging_s: Optional[float] = None,
        faults: Optional[FaultInjector] = None,
        kv_layout: str = "dense",        # 'dense' ring | 'paged' arena (COW)
        kv_page_size: int = 16,          # tokens per KV page (paged layout)
        kv_num_pages: Optional[int] = None,  # arena size; None = full capacity
        kv_dtype: str = "fp",            # 'fp' | 'int8' (paged layout only)
        spec_mode: str = "off",          # 'off' | 'ngram' | 'draft'
        spec_k: int = 4,                 # max drafted tokens per round
        spec_draft_config: Optional[Any] = None,  # name | ModelConfig
        spec_draft_params: Optional[Any] = None,  # None = seeded init
        spec_ngram_max: int = 3,         # longest lookup n-gram
        device: Optional[Any] = None,    # jax.Device for params/KV/state
    ):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.seed = seed
        # every compiled program follows its committed inputs, so pinning
        # the params, the KV pool and the decode state to ``device`` runs
        # this engine on that chip (router replicas: one chip each)
        self.device = device
        key = jax.random.PRNGKey(seed)
        self.params = self._on_device(
            params if params is not None else self.model.init(key))
        self.tokenizer = tokenizer or ByteTokenizer()
        # engine-level sampling knobs are *per-request fallbacks*: a request
        # whose SamplingParams leaves top_p/top_k/min_p as None inherits
        # these; explicit per-request values win (device-resident per-slot
        # sampler state — see core/sampling.py and DecodeState)
        validate_sampling_params(top_p, top_k, min_p, None)
        self.top_k, self.top_p, self.min_p = top_k, top_p, min_p
        self.max_decode_block = max(1, max_decode_block)
        self.max_stop_tokens = max_stop_tokens
        # widest top-logprobs list the decode block can return (static shape
        # of the compiled logprobs variant); per-request `top_logprobs` is
        # validated against it at add_request
        self.max_top_logprobs = max(1, max_top_logprobs)
        self.truncate_long_prompts = truncate_long_prompts
        # admission pipeline knobs: chunk size for piecewise prefill (0 =
        # monolithic) and cap on distinct compiled prefill buckets
        self.prefill_chunk = max(0, prefill_chunk)
        # scheduling-policy subsystem: admission/chunk-queue ordering,
        # slot preemption, and speculative wave filling
        self.preemption = preemption
        self.max_preemptions = max(0, max_preemptions)
        self.speculative_fill = speculative_fill
        self.max_spec_jobs = (max_batch if max_spec_jobs is None
                              else max(0, max_spec_jobs))
        # speculative *decoding* (draft-verify, core/spec_decode.py) — a
        # different axis from speculative prefill filling above
        assert spec_mode in ("off", "ngram", "draft"), spec_mode
        self.spec_mode = spec_mode
        self.spec_k = max(1, spec_k) if spec_mode != "off" else 0
        if spec_mode != "off":
            if any(k.startswith("ssm") for k in cfg.layer_kinds()):
                raise ValueError(
                    "speculative decoding needs an attention decode path: "
                    f"family '{cfg.family}' decodes recurrent state strictly "
                    "one token at a time")
            if spec_mode == "draft" and spec_draft_config is None:
                raise ValueError("spec_mode='draft' requires "
                                 "spec_draft_config (a config name or "
                                 "ModelConfig for the draft model)")

        # media geometry
        self.media_kind = ("vision" if cfg.vision is not None
                           else "audio" if cfg.audio is not None else "none")
        if self.media_kind == "vision":
            self.image_tokens = cfg.vision.num_image_tokens
            self.frame_tokens = frame_tokens or max(4, self.image_tokens // 4)
            self.ctx_len = self.image_tokens * max_media_items
            self.embed_dim = cfg.vision.embed_dim
            self._img_encoder = VisionEncoderStub(
                self.image_tokens, self.embed_dim, work_iters=vision_work_iters)
            self._frame_encoder = VisionEncoderStub(
                self.frame_tokens, self.embed_dim, work_iters=vision_work_iters)
        elif self.media_kind == "audio":
            self.ctx_len = cfg.audio.num_frames
            self.embed_dim = cfg.audio.embed_dim
            self._audio_encoder = AudioEncoderStub(
                cfg.audio.num_frames, self.embed_dim,
                work_iters=vision_work_iters)
        else:
            self.ctx_len = 0

        assert kv_layout in ("dense", "paged"), kv_layout
        self._paged = kv_layout == "paged"
        if self._paged:
            self.pool: Any = PagedKVPool(
                cfg, max_batch, cache_len, ctx_len=self.ctx_len,
                page_size=kv_page_size, num_pages=kv_num_pages,
                kv_dtype=kv_dtype)
        else:
            assert kv_dtype == "fp", "int8 KV requires kv_layout='paged'"
            self.pool = SlotKVPool(cfg, max_batch, cache_len,
                                   ctx_len=self.ctx_len)
        self.pool.cache = self._on_device(self.pool.cache)
        # COW page leases pinned by in-flight prefill jobs (request_id ->
        # page ids incref'd at prefix-cache lookup); ownership transfers to
        # the slot at commit, or is released on job failure/termination
        self._job_leases: Dict[int, List[int]] = {}
        self.scheduler = ContinuousBatchingScheduler(max_batch,
                                                     policy=sched_policy,
                                                     aging_s=aging_s)
        # deterministic fault injection (chaos harness — core/faults.py);
        # None = all hooks inert.  Fault-boundary terminal events that arise
        # deep inside helpers buffer here and drain at the end of step()
        self.faults = faults
        self._fault_events: List[StreamEvent] = []
        self._fault_tick = 0                 # step() invocations (incl. idle)
        # installed by EngineClient: returns True while an abort/reclaim is
        # queued at the block boundary, so plan_decode_block collapses K and
        # the reclaim lands after at most one device step instead of K
        self.reclaim_hint: Optional[Callable[[], bool]] = None
        self.prefix_cache = (TextPrefixCache(prefix_block_size,
                                             cache_max_bytes,
                                             on_evict=(self._on_cache_evict
                                                       if self._paged
                                                       else None))
                             if enable_prefix_cache else None)
        self.content_cache = (ContentCache(
            cache_max_bytes if content_cache_bytes is None
            else content_cache_bytes,
            cache_embeddings=cache_vision_embeddings,
            cache_kv=cache_vision_kv,
            on_evict=self._on_content_evict if self._paged else None)
            if enable_content_cache else None)
        # batched vision encoding: per-request media jobs plus the
        # singleflight table of unique in-flight encodes (hash -> task).
        # A request with unresolved media is admission-ineligible (it keeps
        # its place in the policy queue); encode waves run overlapped behind
        # the dispatched decode block, like prefill waves
        self.encode_wave = max(0, encode_wave)
        self.media_stats = MediaStats()
        self._media_jobs: Dict[int, _MediaJob] = {}
        self._encode_tasks: Dict[str, _EncodeTask] = {}
        self._max_media_jobs = 2 * max_batch + self.max_spec_jobs

        # per-slot decode state lives on device (one pytree); the host keeps
        # only the streaming decoders.  Sampler RNG is per-request: seeded
        # requests derive their base key from the seed alone, unseeded ones
        # draw from this engine-owned chain at add_request (deterministic
        # for a fixed engine seed + submission order).
        self.state = self._on_device(init_decode_state(
            max_batch, self.ctx_len, max_stop_tokens, spec_k=self.spec_k))
        self._request_rng = jax.random.PRNGKey(seed + 1)
        self._streamers: Dict[int, TokenStreamDecoder] = {}
        # per-request stop-sequence checkers (only for requests that set
        # sampling.stop_sequences); live alongside the streamers
        self._stopchk: Dict[int, StopSequenceChecker] = {}
        self._live_slots: set = set()        # slots committed to DecodeState
        # speculative prefill jobs for not-yet-admitted pending requests
        # (request_id -> job); bounded by max_spec_jobs
        self._spec_jobs: Dict[int, _PrefillJob] = {}
        # speculative jobs that finished their whole prompt and then got a
        # slot — committed with the next wave (staged logits, no extra pass)
        self._ready_jobs: List[_PrefillJob] = []
        # preemption snapshots: request_id -> resume metadata.  The cache
        # pytree itself rides in the prefix cache (byte-budget LRU) when one
        # is enabled, so snapshot memory competes with ordinary prefix reuse;
        # with the prefix cache disabled the snapshot is held here directly.
        self._evicted: Dict[int, Dict[str, Any]] = {}
        # shared-prefix admission groups (OpenAI `n` fan-out): leader
        # request_id -> {"value": committed prompt cache or None,
        # "remaining": followers still owed a share, "failed": leader died
        # before commit}.  Followers stay queue-ineligible until the
        # leader's prompt cache commits, then admit by sharing it — COW
        # pages under the paged layout, zero full-cache copies — instead of
        # re-running the prefill.  Works with the prefix cache disabled
        # (the value is engine-owned, not an LRU entry).
        self._prefill_groups: Dict[int, Dict[str, Any]] = {}
        self.group_stats = {"groups": 0, "shared_admits": 0,
                            "independent_fallbacks": 0}

        # power-of-two prefill buckets: cap the distinct compiled shapes by
        # raising the smallest bucket (pad more, compile less).  Floor 32,
        # not 16: XLA's CPU GEMM switches kernels below ~32 rows and the
        # rounding differs, which would break the bit-identity of a short
        # final chunk vs the same tokens inside a monolithic prefill.
        self._bucket_cap = max(1, max_prefill_buckets)
        b_max = _next_bucket(min(cache_len, self.prefill_chunk or cache_len),
                             floor=32)
        floor = 32
        while floor < b_max and \
                b_max.bit_length() - floor.bit_length() + 1 > self._bucket_cap:
            floor *= 2
        self._bucket_floor = min(floor, b_max)
        # frozenset replaced wholesale on update: /stats handler threads may
        # read it while the engine loop compiles a new bucket
        self._seen_buckets: frozenset = frozenset()
        self._dummy_single = None            # zero cache row for wave padding

        self._step_count = 0
        self._prefill_fns: Dict[Tuple, Any] = {}
        self._decode_block_fn = self._build_decode_block_fn()

        # speculation infrastructure: counters + controller exist even when
        # off (stable /stats schema); the verify fn and draft source only
        # when a mode is selected
        self.spec_stats = SpecStats()
        self.spec_controller = SpecController()
        self._draft_source: Optional[DraftSource] = None
        self._spec_verify_fn = None
        if self.spec_mode != "off":
            self._spec_verify_fn = build_spec_verify_fn(
                self.model, use_ctx=self.media_kind != "none",
                n_top=self.max_top_logprobs, paged=self._paged,
                cache_len=cache_len,
                page_size=self.pool.page_size if self._paged else 0)
            if self.spec_mode == "draft":
                dcfg = spec_draft_config
                if isinstance(dcfg, str):
                    from repro.configs import get_config
                    dcfg = get_config(dcfg)
                if dcfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {dcfg.vocab_size} != target vocab "
                        f"{cfg.vocab_size}: draft proposals must be target "
                        "token ids")
                if any(k.startswith("ssm") for k in dcfg.layer_kinds()) or \
                        dcfg.vision is not None or dcfg.audio is not None:
                    raise ValueError("the draft model must be a text-only "
                                     "attention config")
                self._draft_source = DraftModelSource(
                    dcfg, spec_draft_params, max_batch=max_batch,
                    cache_len=cache_len, seed=seed)
            else:
                self._draft_source = NGramDraftSource(max_n=spec_ngram_max)

    def _on_device(self, tree):
        """``tree`` committed to this engine's device (as is without one)."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    # ------------------------------------------------------------------ #
    # compiled steps
    # ------------------------------------------------------------------ #
    def _build_decode_block_fn(self):
        """K decode+sample iterations under one jit (one trace per distinct
        K; the scheduler restricts K to powers of two ≤ max_decode_block).

        ``want_logprobs`` (static) selects a variant that additionally
        returns the sampled token's logprob and the top
        ``max_top_logprobs`` alternatives per step.  The sampling path (the
        per-slot ``fold_in`` key derivation included) is identical in both
        variants, so the emitted tokens never depend on whether logprobs
        are collected.  Sampling parameters are per-slot state
        (``temps``/``top_p``/``top_k``/``min_p``/``sample_key`` in
        :class:`DecodeState`), applied by one shape-stable masked kernel —
        heterogeneous batches never retrace, and a slot's stream depends
        only on its own key and positions (never on its neighbours)."""
        model = self.model
        use_ctx = self.media_kind != "none"
        n_top = self.max_top_logprobs
        paged = self._paged

        @functools.partial(jax.jit,
                           static_argnames=("num_steps", "want_logprobs"),
                           donate_argnums=(1, 2))
        def decode_block(params, cache, state: DecodeState, *,
                         num_steps: int, want_logprobs: bool = False):
            def body(carry, _):
                cache, st = carry
                out = model.apply(
                    params, st.last_token[:, None], mode="decode",
                    positions=st.positions[:, None], cache=cache,
                    ctx_valid=st.ctx_valid if use_ctx else None,
                    page_table=cache["page_table"] if paged else None,
                    slot_active=st.active if paged else None)
                # frozen slots keep their previous cache bit-for-bit: the
                # dense path repairs the written ring cell after the fact,
                # the paged path already redirected the write to the slot's
                # reserved trash cell inside attention
                if paged:
                    cache = select_cache_slots_paged(st.active, st.positions,
                                                     out.cache, cache)
                else:
                    cache = select_cache_slots(st.active, st.positions,
                                               out.cache, cache)
                # stateless per-token keys: the kernel folds the sampled
                # token's position into each slot's base key (replay-stable
                # across preemption/resume; independent of batch
                # composition; skipped entirely for all-greedy batches).
                # Frozen slots' sampler fields are neutralised so a
                # finished/aborted request's stale temperature (or mask
                # knobs) can't hold later blocks off the greedy / plain
                # -temperature fast paths
                nxt = masked_sample_inner(out.logits[:, 0], st.sample_key,
                                          st.positions + 1,
                                          st.temps * st.active,
                                          jnp.where(st.active, st.top_p, 1.0),
                                          jnp.where(st.active, st.top_k, 0),
                                          jnp.where(st.active, st.min_p, 0.0))
                nxt = jnp.where(st.active, nxt, st.last_token)
                emit = jnp.where(st.active, nxt, -1)          # -1 = frozen
                alive = st.active.astype(jnp.int32)
                budget = st.budget - alive
                hit_stop = jnp.any(nxt[:, None] == st.stop_tokens, axis=-1)
                finished = st.active & (hit_stop | (budget <= 0))
                st = st._replace(last_token=nxt,
                                 positions=st.positions + alive,
                                 budget=budget,
                                 active=st.active & ~finished)
                if want_logprobs:
                    lp = jax.nn.log_softmax(
                        out.logits[:, 0].astype(jnp.float32), axis=-1)
                    chosen = jnp.take_along_axis(lp, nxt[:, None],
                                                 axis=-1)[:, 0]
                    top_v, top_i = jax.lax.top_k(lp, n_top)
                    return (cache, st), (emit, chosen, top_v, top_i)
                return (cache, st), emit

            (cache, state), ys = jax.lax.scan(body, (cache, state), None,
                                              length=num_steps)
            if want_logprobs:
                toks, lp_chosen, lp_top_v, lp_top_i = ys
                return cache, state, toks, (lp_chosen, lp_top_v, lp_top_i)
            return cache, state, ys, None                     # toks: [K, B]

        return decode_block

    def _plan_bucket(self, n: int) -> int:
        return _next_bucket(n, floor=self._bucket_floor)

    def _prefill_fn(self, bucket: int, rows: int, cross_cached: bool):
        """Batched prefill for one wave group: k right-padded rows at one
        bucket, each resuming at its own prefix offset (per-row positions)
        with its own length mask (``seq_valid``)."""
        key = (bucket, rows, cross_cached)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        if bucket not in self._seen_buckets:
            self._seen_buckets = self._seen_buckets | {bucket}
            log.warning(
                "compiling new prefill bucket=%d (%d/%d power-of-two "
                "buckets; floor=%d) — chunked waves should settle into a "
                "small fixed set of shapes",
                bucket, len(self._seen_buckets), self._bucket_cap,
                self._bucket_floor)
        model, media_kind = self.model, self.media_kind

        # NOTE: no donation here — cache rows may alias LRU-cached pytrees
        # (prefix/content cache hit) or a chunk job's published partial
        # state; donating would corrupt the cache.
        @jax.jit
        def prefill(params, tokens, positions, single_caches, media,
                    ctx_valid, seq_valid, last_idx):
            cache = concat_cache_rows(single_caches)
            kw = {}
            if media_kind == "vision":
                kw["image_embeds"] = media
                kw["ctx_valid"] = ctx_valid
            elif media_kind == "audio":
                kw["audio_frames"] = media
                kw["ctx_valid"] = ctx_valid
            out = model.apply(params, tokens, mode="prefill",
                              positions=positions, cache=cache,
                              resume=True, cross_cached=cross_cached,
                              seq_valid=seq_valid, **kw)
            # per-row logits at each row's last real token
            logits = jnp.take_along_axis(out.logits,
                                         last_idx[:, None, None], axis=1)
            return logits[:, 0], out.cache

        self._prefill_fns[key] = prefill
        return prefill

    # ------------------------------------------------------------------ #
    # media pipeline (Alg.3 lines 1-10): batched encode waves + in-flight
    # dedup (singleflight on content hash) ahead of admission
    # ------------------------------------------------------------------ #
    def _has_media(self, req: Request) -> bool:
        return (self.media_kind != "none"
                and bool(req.images or req.video_frames
                         or req.audio is not None))

    def _iter_media_payloads(self, req: Request):
        """(payload, encoder, ntok) triples in context order — the one place
        the per-modality geometry lives, shared by the job-open path and the
        synchronous fallback so the two can never disagree."""
        if self.media_kind == "vision":
            for img in req.images:
                yield img, self._img_encoder, self.image_tokens
            for frame in req.video_frames:
                yield frame, self._frame_encoder, self.frame_tokens
        elif self.media_kind == "audio" and req.audio is not None:
            yield req.audio, self._audio_encoder, self.ctx_len

    def _open_media_job(self, req: Request) -> _MediaJob:
        """Decode + hash every payload once (cheap host work), resolve
        items straight from the embedding cache, and register the rest with
        the in-flight singleflight table: a hash already pending — whether
        registered by this job or a concurrent request — never spawns a
        second encode task."""
        ms = self.media_stats
        items: List[_MediaItem] = []
        job = _MediaJob(req, items, remaining=0)
        for payload, encoder, ntok in self._iter_media_payloads(req):
            pixels = decode_media(payload)
            h = content_hash(pixels)
            item = _MediaItem(h, ntok)
            items.append(item)
            entry = (self.content_cache.get_embedding(h)
                     if self.content_cache is not None else None)
            if entry is not None:
                item.emb = entry.embeddings
                req.vision_cache_hits += 1
                ms.embed_hits += 1
                continue
            req.vision_cache_misses += 1
            ms.embed_misses += 1
            job.remaining += 1
            task = self._encode_tasks.get(h)
            if task is None:
                self._encode_tasks[h] = _EncodeTask(h, pixels, encoder,
                                                    ntok, [job])
            else:
                if job not in task.waiters:
                    # joined a concurrent request's in-flight encode: this
                    # request's encoder work is eliminated outright
                    ms.dedup_joins += 1
                    task.waiters.append(job)
        # digest binds the prefix-cache salt before admission, exactly as
        # the synchronous pipeline did
        req.media_set_digest = (media_set_digest([it.hash for it in items])
                                if items else None)
        self._media_jobs[req.request_id] = job
        return job

    def _media_admissible(self, req: Request) -> bool:
        """Admission eligibility predicate (passed into the scheduler): a
        media request may bind a slot only once its whole media set is
        resolved, so the prefill path never encodes synchronously.  Opens
        the request's media job on first sight (bounded table)."""
        if not self._has_media(req):
            return True
        if req.preempt_count and req.request_id in self._evicted:
            # snapshot resume restores ctx rows from the snapshot itself —
            # no embeddings needed (and none are re-encoded)
            return True
        job = self._media_jobs.get(req.request_id)
        if job is None:
            if len(self._media_jobs) >= self._max_media_jobs:
                return False             # table full: stays queued, retried
            try:
                job = self._open_media_job(req)
            except Exception as e:       # per-request boundary (bad payload)
                self._fault_events.extend(self._fail_request(
                    req.request_id, f"media decode failed: {e}"))
                return False
        return job.remaining == 0

    # ------------------------------------------------------------------ #
    # shared-prefix admission groups (n>1 fan-out; DESIGN_router.md)
    # ------------------------------------------------------------------ #
    def _admissible(self, req: Request) -> bool:
        """Combined admission eligibility: media resolved AND (for an
        ``n>1`` follower) the group leader's prompt cache committed, so
        the follower admits by sharing it instead of prefilling again."""
        return self._media_admissible(req) and self._group_admissible(req)

    def _group_admissible(self, req: Request) -> bool:
        if req.group_leader is None or req.metadata.get("group_done"):
            return True
        if self._has_media(req):
            # media groups fall back to independent admission (the shared
            # value carries no ctx rows); content-cache dedup already
            # collapses their encoder work
            return True
        g = self._prefill_groups.get(req.group_leader)
        if g is None:
            # leader unknown to this engine (cross-replica handoff, direct
            # add): admit independently rather than wait forever
            return True
        return g["value"] is not None or g["failed"]

    def _group_value(self, req: Request) -> Optional[Dict[str, Any]]:
        """The leader's committed prompt cache for an admissible follower
        (None -> independent prefill)."""
        if (req.group_leader is None or req.metadata.get("group_done")
                or req.num_generated or self._has_media(req)):
            return None
        g = self._prefill_groups.get(req.group_leader)
        if g is None or g["value"] is None:
            return None
        return g["value"]

    def _group_consume(self, req: Request) -> None:
        """One follower leaves the group (shared admission, independent
        fallback, or termination): decrement once; the last one out
        releases the group value's page refs."""
        if req.group_leader is None or req.metadata.get("group_done"):
            return
        req.metadata["group_done"] = True
        g = self._prefill_groups.get(req.group_leader)
        if g is None:
            return
        g["remaining"] -= 1
        if g["remaining"] <= 0:
            value = g["value"]
            if value is not None:
                self._release_snapshot_value(value)
            del self._prefill_groups[req.group_leader]

    def _group_on_terminate(self, req: Request) -> None:
        """Group bookkeeping on abort/failure/detach: a dying leader that
        never committed flips the group to independent admission; a dying
        follower consumes its share."""
        if req.group_size > 1 and req.group_leader is None:
            g = self._prefill_groups.get(req.request_id)
            if g is not None and g["value"] is None:
                g["failed"] = True
        elif req.group_leader is not None:
            self._group_consume(req)

    def _cancel_media_job(self, request_id: int) -> None:
        """Drop a request's media job (abort/failure): deregister it from
        every in-flight encode task; tasks left with no waiters are dropped
        before they cost an encoder invocation."""
        job = self._media_jobs.pop(request_id, None)
        if job is None:
            return
        for h in {it.hash for it in job.items if it.emb is None}:
            task = self._encode_tasks.get(h)
            if task is None:
                continue
            task.waiters = [j for j in task.waiters if j is not job]
            if not task.waiters:
                del self._encode_tasks[h]

    def _dispatch_encode_wave(self) -> None:
        """Run up to ``encode_wave`` unique pending encodes (most urgent
        waiter first, policy order), delivering each embedding to *all*
        waiters — the singleflight guarantee.  Called between the decode
        -block dispatch and the token sync, so encoder host work overlaps
        the in-flight device block the way prefill waves do.  The per-step
        budget is what streams a 64-frame video across steps instead of
        monopolising one: interactive traffic keeps admitting between
        waves."""
        if not self._encode_tasks:
            return
        key = self.scheduler.policy.key
        order = sorted(self._encode_tasks.values(),
                       key=lambda t: min(key(j.req) for j in t.waiters))
        budget = self.encode_wave or len(order)
        self.media_stats.encode_waves += 1
        for task in order[:budget]:
            del self._encode_tasks[task.hash]
            if not task.waiters:
                continue
            try:
                emb = task.encoder(task.pixels)
            except Exception as e:       # per-request fault boundary
                for job in list(task.waiters):
                    self._fault_events.extend(self._fail_request(
                        job.req.request_id, f"media encode failed: {e}"))
                continue
            self.media_stats.encoder_invocations += 1
            if self.content_cache is not None:
                self.content_cache.put_embedding(
                    task.hash, EmbeddingEntry(emb, emb.nbytes))
            for job in task.waiters:
                for item in job.items:
                    if item.hash == task.hash and item.emb is None:
                        item.emb = emb
                        job.remaining -= 1

    def _assemble_media(self, job: _MediaJob):
        """Pack a resolved job's embeddings into the fixed context window —
        same cursor walk as the synchronous pipeline, so the device-visible
        arrays are bit-identical regardless of which path produced them."""
        embeds = np.zeros((self.ctx_len, self.embed_dim), np.float32)
        valid = np.zeros((self.ctx_len,), bool)
        cursor = 0
        for item in job.items:
            take = min(item.ntok, self.ctx_len - cursor)
            embeds[cursor:cursor + take] = item.emb[:take]
            valid[cursor:cursor + take] = True
            cursor += take
        digest = (media_set_digest([it.hash for it in job.items])
                  if job.items else None)
        salt = bytes.fromhex(digest) if digest else b""
        return embeds[None], valid[None], salt, digest

    def _media_pipeline(self, req: Request):
        """Synchronous fallback (returns (embeds [1,T,De] | zeros, ctx_valid
        [1,T], salt, set_digest)): the lost-snapshot re-prefill path and any
        open-prefill call without a resolved media job land here.  Bit
        -identical to job assembly; encoder invocations still count."""
        if self.media_kind == "none":
            return None, None, b"", None
        embeds = np.zeros((self.ctx_len, self.embed_dim), np.float32)
        valid = np.zeros((self.ctx_len,), bool)
        hashes: List[str] = []
        cursor = 0
        ms = self.media_stats

        def encode(payload, encoder, ntok):
            nonlocal cursor
            pixels = decode_media(payload)
            h = content_hash(pixels)
            hashes.append(h)
            entry = self.content_cache.get_embedding(h) if self.content_cache else None
            if entry is None:
                emb = encoder(pixels)
                ms.encoder_invocations += 1
                req.vision_cache_misses += 1
                ms.embed_misses += 1
                if self.content_cache is not None:
                    self.content_cache.put_embedding(
                        h, EmbeddingEntry(emb, emb.nbytes))
            else:
                emb = entry.embeddings
                req.vision_cache_hits += 1
                ms.embed_hits += 1
            take = min(ntok, self.ctx_len - cursor)
            embeds[cursor:cursor + take] = emb[:take]
            valid[cursor:cursor + take] = True
            cursor += take

        for payload, encoder, ntok in self._iter_media_payloads(req):
            encode(payload, encoder, ntok)

        digest = media_set_digest(hashes) if hashes else None
        salt = bytes.fromhex(digest) if digest else b""
        return embeds[None], valid[None], salt, digest

    # ------------------------------------------------------------------ #
    # cross-KV extraction / injection (content cache payloads)
    # ------------------------------------------------------------------ #
    @staticmethod
    def _extract_xkv(cache):
        out = {"prefix": [{k: v for k, v in (c or {}).items()
                           if k in ("xk", "xv")} for c in cache["prefix"]],
               "block": {}}
        if cache.get("block"):
            for pos, sub in cache["block"].items():
                picked = {k: v for k, v in sub.items() if k in ("xk", "xv")}
                if picked:
                    out["block"][pos] = picked
        return out

    @staticmethod
    def _inject_xkv(cache, xkv):
        cache = dict(cache)
        cache["prefix"] = [dict(c or {}) for c in cache["prefix"]]
        for c, x in zip(cache["prefix"], xkv["prefix"]):
            c.update(x)
        if cache.get("block"):
            block = {k: dict(v) for k, v in cache["block"].items()}
            for pos, x in xkv["block"].items():
                block[pos].update(x)
            cache["block"] = block
        return cache

    # ------------------------------------------------------------------ #
    # admission pipeline: wave packing → chunk interleave → async overlap
    # ------------------------------------------------------------------ #
    def _assign_sample_key(self, req: Request) -> None:
        """Bind the request's base PRNG key once, at add_request: seeded
        requests get ``PRNGKey(seed)`` (engine-independent, so replay holds
        across runs and processes), unseeded ones a split of the engine's
        request-key chain (deterministic per engine seed + add order).  The
        key lives on the Request, so preemption/re-admission — snapshot or
        re-prefill — resumes the exact same per-token key stream."""
        if req.sample_key is not None:
            return
        if req.sampling.seed is not None:
            req.sample_key = request_base_key(req.sampling.seed)
        else:
            self._request_rng, sub = jax.random.split(self._request_rng)
            req.sample_key = np.asarray(sub)

    def _resolve_sampling(self, req: Request) -> Tuple[float, float, int, float]:
        """Effective (temperature, top_p, top_k, min_p) for one request:
        per-request values with the engine knobs as fallbacks — the single
        place the fallback rule lives, shared by decode-state admission and
        first-token wave sampling (drift between the two would make a
        request's first token obey different knobs than its stream)."""
        sp = req.sampling
        return (sp.temperature,
                self.top_p if sp.top_p is None else float(sp.top_p),
                self.top_k if sp.top_k is None else int(sp.top_k),
                self.min_p if sp.min_p is None else float(sp.min_p))

    def _plan_admissions(self) -> None:
        """Alg.1 lines 3-6, policy-ordered: bind pending requests to free
        slots (opening a prefill job, resuming an eviction snapshot, or
        adopting a speculative job per request), then — with a preemptive
        policy — evict the least urgent live slot for each strictly more
        urgent pending request."""
        # freeze the anti-starvation aging clock once per planning pass, so
        # policy keys are static while this pass runs (the preemption loop's
        # termination argument needs per-request keys that don't move)
        self.scheduler.policy.tick(time.monotonic())
        self._admit_into_free_slots()
        if (self.preemption and self.scheduler.policy.preemptive
                and self.scheduler.pending and not self.pool.num_free):
            self._plan_preemptions()

    def _admit_into_free_slots(self) -> None:
        while (self.pool.num_free and self.scheduler.pending
               and self.scheduler.num_active < self.scheduler.max_batch):
            # media-ineligible requests (embeddings still resolving in the
            # encode waves) are skipped without losing queue position —
            # peeking also opens media jobs for newly seen requests
            head = self.scheduler.peek_pending(self._admissible)
            if head is None:
                break
            if (self.faults is not None
                    and self.faults.fires("pool", head.request_id,
                                          self._fault_tick)):
                # transient slot-allocation failure: the request stays
                # pending and is retried next step (keyed by step tick, so
                # the retry draws fresh) — never dropped, never wedged
                break
            slot = self.pool.allocate()
            admitted = self.scheduler.admit([slot], self._admissible)
            if not admitted:
                self.pool.free(slot)
                break
            _, req = admitted[0]
            try:
                self._bind_slot(slot, req)
            except Exception as e:  # per-request fault boundary (prefill)
                self._fault_events.extend(self._fail_request(
                    req.request_id, f"prefill open failed: {e}"))

    @staticmethod
    def _salt(req: Request) -> bytes:
        """Prefix-cache salt from the admission-time media digest (``b""``
        for text-only) — the one place the digest→salt rule lives, shared
        by eviction snapshots, resume lookups, partial-chunk publication
        and retire publication."""
        return (bytes.fromhex(req.media_set_digest)
                if req.media_set_digest else b"")

    # ------------------------------------------------------------------ #
    # paged-KV bookkeeping (no-ops under the dense layout)
    # ------------------------------------------------------------------ #
    def _on_cache_evict(self, key: str, value: Any) -> None:
        """Prefix-cache entry displaced (LRU squeeze, replacement, or forced
        page-pressure eviction): release the device pages it leased."""
        if isinstance(value, dict) and value.get("pages"):
            self.pool.release_pages(value["pages"])

    def _on_content_evict(self, key: str, value: Any) -> None:
        """Content-cache entry displaced (LRU squeeze, replacement, or a
        forced page-pressure eviction): release the device pages its
        cross-KV payload leased.  Embedding entries carry no lease."""
        pages = getattr(value, "pages", None)
        if pages:
            self.pool.release_pages(pages)
            self.media_stats.xkv_lease_pages -= len(pages)
            value.pages = None

    def _lease_xkv_pages(self, nbytes: int) -> Optional[List[int]]:
        """Charge a cross-KV entry's bytes against the paged arena so the
        admission headroom probe and the pressure ladder see device-resident
        media: lease ceil(nbytes / page_bytes) accounting pages, evicting
        prefix-cache LRU entries if the arena is tight.  Returns None (the
        publication is skipped) if the arena cannot spare the pages —
        serving capacity always outranks media caching.  Dense layout: no
        arena, nothing to lease."""
        if not self._paged:
            return []
        npages = -(-nbytes // self.pool.page_bytes)
        while self.pool.allocator.num_free < npages:
            if self.prefix_cache is not None and \
                    self.prefix_cache.evict_lru():
                continue
            return None
        pages = [self.pool.allocator.alloc() for _ in range(npages)]
        self.media_stats.xkv_lease_pages += npages
        return pages

    def _release_lease(self, request_id: int) -> None:
        pages = self._job_leases.pop(request_id, None)
        if pages:
            self.pool.release_pages(pages)

    def _release_snapshot_value(self, value: Any) -> None:
        """Release a popped exact-sequence snapshot that will NOT be adopted
        into a slot (terminated request, recovery)."""
        if self._paged and isinstance(value, dict) and value.get("pages"):
            self.pool.release_pages(value["pages"])

    def _live_positions(self) -> Dict[int, int]:
        """slot -> absolute position of its last sampled token (where the
        next decode step writes KV) for every live slot."""
        out = {}
        for slot in self._live_slots:
            req = self.scheduler.active.get(slot)
            if req is not None:
                out[slot] = (len(req.prompt_tokens) + req.num_generated - 1)
        return out

    def _ensure_paged_capacity(self, k_steps: int) -> None:
        """Pressure ladder before a decode block: make the pages the block
        will write exclusively owned (lazy tail allocation + COW splits).
        On exhaustion, reclaim in escalating order — (1) evict prefix-cache
        entries (their leases free real pages), (2) preempt the live slot
        holding the most pages *without* a snapshot (a snapshot would pin
        the very pages we need), (3) fail the last holdout with a typed
        error.  Terminates: every rung either frees pages or shrinks the
        live set."""
        while not self.pool.ensure_decode_capacity(self._live_positions(),
                                                   k_steps):
            if self.prefix_cache is not None and \
                    self.prefix_cache.evict_lru():
                continue
            # next rung: cached cross-KV entries surrender their accounting
            # leases before any live request is preempted — media caching
            # never outranks in-flight decode
            if self.content_cache is not None and \
                    self.content_cache.evict_cross_kv_lru():
                continue
            live = self._live_positions()
            if not live:
                return
            # preemption victims must be exactly rebuildable by re-prefill
            # (same exemption as _plan_preemptions: a ring-wrapped history
            # cannot be re-prefilled without leaking future cells)
            eligible = [s for s in live
                        if (len(self.scheduler.active[s].prompt_tokens)
                            + self.scheduler.active[s].num_generated)
                        <= self.pool.cache_len]
            if len(live) > 1 and eligible:
                victim = max(eligible,
                             key=lambda s: len(self.pool.slot_pages(s)))
                req = self.scheduler.active[victim]
                log.warning("KV page pressure: preempting slot %d "
                            "(request %d, %d pages) without snapshot",
                            victim, req.request_id,
                            len(self.pool.slot_pages(victim)))
                self._evict(victim, snapshot=False)
                continue
            slot = max(live, key=lambda s: len(self.pool.slot_pages(s)))
            req = self.scheduler.active[slot]
            self._fault_events.extend(self._fail_request(
                req.request_id,
                f"KV page pool exhausted ({self.pool.num_pages} pages)"))

    def _bind_slot(self, slot: int, req: Request) -> None:
        """Attach an admitted request to its slot: restore an eviction
        snapshot (preempted request), adopt the request's speculative
        prefill progress, or open a fresh prefill job."""
        if ((req.preempt_count or req.request_id in self._evicted)
                and self._try_resume(slot, req)):
            return
        job = self._spec_jobs.pop(req.request_id, None)
        if job is not None:
            job.slot = slot
            req.status = RequestStatus.PREFILLING
            self.scheduler.stats.spec_admitted += 1
            if job.logits is not None:   # whole prompt already materialised
                self._ready_jobs.append(job)
            else:
                self.scheduler.enqueue_prefill(job)
            return
        tokens = None
        if req.preempt_count:
            # eviction snapshot lost to cache pressure: rebuild the slot by
            # prefilling the prompt+generated history as one sequence (the
            # commit then samples the next token from the last position)
            tokens = req.prompt_tokens + req.output_tokens
        self.scheduler.enqueue_prefill(
            self._open_prefill(slot, req, tokens=tokens))

    # ------------------------------------------------------------------ #
    # slot preemption (policy-gated eviction of live decode slots)
    # ------------------------------------------------------------------ #
    def _plan_preemptions(self) -> None:
        """Evict the least urgent live slot while the most urgent pending
        request is *strictly* more urgent than it.  Keys are static per
        request, so each eviction strictly improves the active set and the
        loop terminates; per-request eviction counts are capped by
        ``max_preemptions`` to bound churn under adversarial load."""
        key = self.scheduler.policy.key
        while self.scheduler.pending and not self.pool.num_free:
            head = self.scheduler.peek_pending(self._admissible)
            # a victim must be exactly rebuildable if its snapshot is later
            # lost: the re-prefill fallback can only represent histories
            # that fit the KV ring without wrapping (wrapped prefill would
            # leak future cells through the causal mask), so slots whose
            # prompt+generated history has reached cache_len are exempt —
            # they also free soonest by just finishing
            eligible = {s for s in self._live_slots
                        if (len(self.scheduler.active[s].prompt_tokens)
                            + self.scheduler.active[s].num_generated)
                        <= self.pool.cache_len}
            victim = self.scheduler.select_victim(eligible,
                                                  self.max_preemptions)
            if head is None or victim is None:
                return
            vslot, vreq = victim
            if not key(head) < key(vreq):
                return
            self._evict(vslot)
            self._admit_into_free_slots()

    def _evict(self, slot: int, *, snapshot: bool = True) -> None:
        """Evict a live decode slot for a more urgent pending request.

        The slot's cache is snapshotted and published as an *exact-sequence*
        prefix-cache entry keyed by prompt+generated history, so the evicted
        request's work is never discarded: on re-admission the snapshot
        restores the cache and decode state bit-for-bit (greedy decode
        continues exactly as if never evicted).  Dense pools snapshot by
        jit'd copy; paged pools snapshot by *reference* — the entry increfs
        the slot's pages (zero copy) and resume adopts them back.  If the
        prefix cache is disabled the snapshot is held engine-side instead;
        if the entry is LRU-evicted under byte pressure, resume falls back
        to re-prefilling the history.  ``snapshot=False`` (page-pressure
        preemption) skips the snapshot entirely so the victim's pages
        actually free."""
        req = self.scheduler.active[slot]
        meta: Dict[str, Any] = {
            "cache": None,
            "ctx_valid": (np.asarray(self.state.ctx_valid[slot])
                          if self.media_kind != "none" else None),
        }
        if self._paged:
            value = None
            if snapshot:
                pages = list(self.pool.slot_pages(slot))
                value = {"pages": pages, "nonkv": self.pool.read_nonkv(slot),
                         "len": len(req.prompt_tokens) + req.num_generated}
                nbytes = (self.pool.pages_nbytes(len(pages))
                          + tree_bytes(value["nonkv"]))
                self.pool.incref_pages(pages)
        else:
            value = {"cache": self.pool.read(slot)}
            nbytes = tree_bytes(value["cache"])
        if value is not None:
            if self.prefix_cache is not None:
                self.prefix_cache.insert_exact(
                    req.prompt_tokens + req.output_tokens, value, nbytes,
                    salt=self._salt(req))
            else:
                meta["cache"] = value
        self._evicted[req.request_id] = meta
        req.status = RequestStatus.QUEUED
        if self.prefix_cache is None:
            # no byte-budget LRU to own the snapshots: bound engine-side
            # cache pytrees at one pool's worth, dropping the *oldest*
            # (dict = eviction order) to the re-prefill resume path —
            # mirrors an LRU squeeze instead of growing with queue depth
            holders = [rid for rid, m in self._evicted.items()
                       if m["cache"] is not None]
            for rid in holders[:-self.pool.max_batch]:
                self._release_snapshot_value(self._evicted[rid]["cache"])
                self._evicted[rid]["cache"] = None
        self.scheduler.requeue(slot)
        self.pool.free(slot)
        self._live_slots.discard(slot)
        self._spec_release(slot)
        # freeze the slot on-device so decode blocks dispatched before the
        # next admission lands there cannot advance stale state
        self._deactivate_slot(slot)

    def _spec_release(self, slot: int) -> None:
        """Drop a slot's speculation state (EWMA entry, draft-pool primed
        mark) when the slot detaches from its request — retire, eviction,
        or abort/failure.  Draft state drops cleanly on evict; a resume
        re-primes at the shared admission point."""
        if self.spec_mode != "off":
            self.spec_controller.release(slot)
            self._draft_source.release(slot)

    def _deactivate_slot(self, slot: int) -> None:
        """Freeze a slot's device row (preemption, host-side stop-sequence
        finish, abort): the next decode block masks its cache writes and
        stops advancing its positions, so the slot is immediately safe to
        hand to the next admission."""
        self.state = self.state._replace(
            active=self.state.active.at[slot].set(False))

    def _try_resume(self, slot: int, req: Request) -> bool:
        """Restore a preempted request's slot from its eviction snapshot.
        Returns False (caller re-prefills) if the snapshot was LRU-evicted
        from the prefix cache in the meantime."""
        meta = self._evicted.pop(req.request_id, None)
        if meta is None:
            return False
        value = meta["cache"]
        if value is None and self.prefix_cache is not None:
            value = self.prefix_cache.take_exact(
                req.prompt_tokens + req.output_tokens, salt=self._salt(req))
        if value is None:
            return False
        if self._paged and "pages" in value:
            # zero-copy resume: the snapshot's page refs transfer to the
            # slot (take_exact popped the entry without firing on_evict)
            self.pool.adopt(slot, value["pages"], value["nonkv"])
        else:
            self.pool.insert(slot, value["cache"])
        self._admit_rows_to_state(
            [(slot, req, req.output_tokens[-1],
              len(req.prompt_tokens) + req.num_generated - 1,
              meta["ctx_valid"], True)])
        self._live_slots.add(slot)
        req.status = RequestStatus.DECODING
        self.scheduler.stats.resumed += 1
        return True

    def _open_prefill(self, slot: Optional[int], req: Request,
                      tokens: Optional[List[int]] = None) -> _PrefillJob:
        t0 = time.monotonic()
        if self.faults is not None:
            self.faults.check("prefill", req.request_id,
                              detail=f"request {req.request_id}")
        tokens = list(req.prompt_tokens if tokens is None else tokens)
        assert tokens, "empty prompt"
        if slot is not None:
            req.status = RequestStatus.PREFILLING

        job = self._media_jobs.get(req.request_id)
        if job is not None and job.remaining == 0:
            # resolved by encode waves / embedding-cache hits ahead of
            # admission — assembly only, no encoder work on this path
            del self._media_jobs[req.request_id]
            embeds, ctx_valid, salt, set_digest = self._assemble_media(job)
        else:
            if job is not None:          # unresolved job reached prefill
                self._cancel_media_job(req.request_id)
            embeds, ctx_valid, salt, set_digest = self._media_pipeline(req)
        req.media_set_digest = set_digest

        # n>1 fan-out: a follower admits by sharing its group leader's
        # committed prompt cache — maximal match by construction (identical
        # prompt), capped to leave >=1 token for first-token logits.  Paged
        # pools lease the leader's published pages COW exactly like a
        # prefix-cache hit; dense pools resume from the leader's row.  The
        # share works with the prefix cache disabled.
        matched, single = 0, None
        gvalue = self._group_value(req)
        if gvalue is not None and len(tokens) == len(req.prompt_tokens):
            matched = min(gvalue["len"], len(tokens) - 1)
            if self._paged:
                single = gvalue["dense"]
                ps = self.pool.page_size
                shared = list(gvalue["pages"][:min(matched // ps,
                                                   len(gvalue["pages"]))])
                if shared:
                    self.pool.incref_pages(shared)
                    stale = self._job_leases.pop(req.request_id, None)
                    if stale:
                        self.pool.release_pages(stale)
                    self._job_leases[req.request_id] = shared
            else:
                single = gvalue["cache"]
            req.cached_prefix_len = matched
            self.group_stats["shared_admits"] += 1
            self._group_consume(req)
        elif req.group_leader is not None \
                and not req.metadata.get("group_done"):
            # group gone (leader died / value dropped): independent prefill
            self.group_stats["independent_fallbacks"] += 1
            self._group_consume(req)

        # Alg.2: longest cached prefix (cap: leave >=1 token for logits)
        if single is None and self.prefix_cache is not None:
            value, matched = self.prefix_cache.lookup(
                tokens, salt=salt, max_len=len(tokens) - 1)
            if value is not None:
                if "pages" in value:
                    # paged entry: the dense shadow row resumes the prefill
                    # pipeline (unchanged, bit-identical), while the entry's
                    # full pages inside the match are leased COW — pinned
                    # against LRU eviction until the commit transfers them
                    # to the slot (zero cache-copy admission)
                    single = value["dense"]
                    ps = self.pool.page_size
                    shared = list(value["pages"][:min(matched // ps,
                                                      len(value["pages"]))])
                    if shared:
                        self.pool.incref_pages(shared)
                        stale = self._job_leases.pop(req.request_id, None)
                        if stale:        # re-opened job: drop the old lease
                            self.pool.release_pages(stale)
                        self._job_leases[req.request_id] = shared
                else:
                    single = value["cache"]
                req.cached_prefix_len = matched
            else:
                matched = 0
        if single is None:
            single = self.pool.single_cache_zeros()

        # Alg.3: cross-KV reuse (skip context projection in every layer)
        cross_cached = False
        if (set_digest is not None and self.content_cache is not None):
            xkv_entry = self.content_cache.get_cross_kv(set_digest)
            if xkv_entry is not None:
                single = self._inject_xkv(single, xkv_entry.xkv)
                cross_cached = True
                self.media_stats.xkv_hits += 1
            else:
                self.media_stats.xkv_misses += 1

        return _PrefillJob(
            slot=slot, req=req, tokens=tokens, cache=single, consumed=matched,
            embeds=embeds, ctx_valid=ctx_valid, cross_cached=cross_cached,
            publish_xkv=(set_digest is not None
                         and self.content_cache is not None
                         and not cross_cached),
            t0=t0)

    def _dummy_row(self):
        """Zero cache row padding a wave to a power-of-two row count (never
        donated, never inserted — safe to share across waves)."""
        if self._dummy_single is None:
            self._dummy_single = self.pool.single_cache_zeros()
        return self._dummy_single

    def _dispatch_prefill_wave(self) -> List[Tuple[_PrefillJob, jax.Array]]:
        """Advance every queued prefill job by one chunk.

        Jobs are grouped by (bucket, cross_cached) and each group runs one
        right-padded ``[k, bucket]`` compiled forward pass; row counts pad to
        a power of two so waves reuse a bounded set of compiled shapes.
        Returns (job, logits_row) for jobs whose prompt is now fully
        materialised; unfinished jobs re-enter the chunk queue.  All device
        work here is dispatched asynchronously — the caller decides when to
        block (after the in-flight decode block's token sync).
        """
        jobs = self.scheduler.pop_prefill_wave()
        if not jobs:
            return []

        groups: Dict[Tuple[int, bool], List[Tuple[_PrefillJob, int]]] = {}
        for job in jobs:
            remaining = len(job.tokens) - job.consumed
            take = (remaining if self.prefill_chunk == 0
                    else min(self.prefill_chunk, remaining))
            # every chunk must fit the KV ring: cap ``take`` (oversized
            # sliding-window prompts auto-chunk) and clamp the bucket to
            # cache_len so one row's slot indices stay distinct mod
            # cache_len.  Padding that merely wraps is harmless (the masked
            # scatter restores those cells), but two writes in one call must
            # never collide — with a non-power-of-two cache_len the pow2
            # bucket could exceed the ring and alias real prompt cells.
            take = min(take, self.pool.cache_len)
            bucket = min(self._plan_bucket(take), self.pool.cache_len)
            groups.setdefault((bucket, job.cross_cached),
                              []).append((job, take))

        if self.speculative_fill and groups:
            self._backfill_groups(groups)

        completed: List[Tuple[_PrefillJob, jax.Array]] = []
        for (bucket, cross_cached), rows in groups.items():
            try:
                completed.extend(
                    self._run_wave_group(bucket, cross_cached, rows))
            except Exception as e:  # wave-group fault boundary
                self._fail_wave(rows, e)
        return completed

    def _fail_wave(self, rows: List[Tuple[_PrefillJob, int]],
                   exc: Exception) -> None:
        """One batched prefill pass blew up: fail the slot-bound requests
        riding it (their partial caches are unrecoverable) with typed ERROR
        events, and drop the wave's speculative rows back to pending — the
        speculated work was optional, so those requests are untouched and
        simply prefill again later.  Other wave groups and every decode slot
        are unaffected."""
        log.warning("prefill wave group failed (%d rows): %s", len(rows), exc)
        for job, _ in rows:
            if job.slot is not None:
                self._fault_events.extend(self._fail_request(
                    job.req.request_id, f"prefill wave failed: {exc}"))
            else:
                self._spec_jobs.pop(job.req.request_id, None)
                self._release_lease(job.req.request_id)

    def _backfill_groups(
            self, groups: Dict[Tuple[int, bool],
                               List[Tuple[_PrefillJob, int]]]) -> None:
        """Speculative wave filling: a group of k rows pads to the next
        power of two anyway, so the kp-k padding rows are free compute —
        fill them with the next chunk of in-flight speculative jobs and the
        *first* chunk of the most urgent not-yet-admitted pending requests
        (policy order).  A speculative row's chunk is capped at the group's
        bucket — chunk geometry is masked out of the final cache, so any
        split is bit-identical.  The wave's compiled shape never changes:
        only dummy zero rows are replaced."""
        key = self.scheduler.policy.key
        waiting = sorted((j for j in self._spec_jobs.values()
                          if j.logits is None), key=lambda j: key(j.req))
        fresh = [r for r in self.scheduler.pending_in_order()
                 if r.request_id not in self._spec_jobs
                 and not r.preempt_count
                 and self._admissible(r)]
        for (bucket, cross_cached), rows in groups.items():
            kp = 1 << (len(rows) - 1).bit_length()
            while len(rows) < kp:
                job = next((j for j in waiting
                            if j.cross_cached == cross_cached), None)
                if job is not None:
                    waiting.remove(job)
                elif fresh and len(self._spec_jobs) < self.max_spec_jobs:
                    req = fresh.pop(0)
                    try:
                        cand = self._open_prefill(None, req)
                    except Exception as e:  # per-request fault boundary
                        self._fault_events.extend(self._fail_request(
                            req.request_id, f"prefill open failed: {e}"))
                        continue
                    self._spec_jobs[req.request_id] = cand
                    self.scheduler.stats.spec_jobs += 1
                    if cand.cross_cached != cross_cached:
                        # parked for a future matching wave; stop here —
                        # hunting for a match could materialise a cache
                        # pytree per pending request in one step
                        break
                    job = cand
                else:
                    break
                take = min(len(job.tokens) - job.consumed, bucket)
                rows.append((job, take))
                self.scheduler.stats.spec_chunks += 1

    def _run_wave_group(self, bucket: int, cross_cached: bool,
                        rows: List[Tuple[_PrefillJob, int]]
                        ) -> List[Tuple[_PrefillJob, jax.Array]]:
        k = len(rows)
        kp = 1 << (k - 1).bit_length()               # pad rows to power of two
        toks = np.zeros((kp, bucket), np.int32)
        # dummy rows keep distinct positions so their (masked, no-op) cache
        # scatter never writes duplicate indices
        poss = np.broadcast_to(np.arange(bucket, dtype=np.int32),
                               (kp, bucket)).copy()
        valid = np.zeros((kp, bucket), bool)
        last_idx = np.zeros((kp,), np.int32)
        singles = []
        for i, (job, take) in enumerate(rows):
            seg = job.tokens[job.consumed:job.consumed + take]
            toks[i, :take] = seg
            poss[i] = job.consumed + np.arange(bucket, dtype=np.int32)
            valid[i, :take] = True
            last_idx[i] = take - 1
            singles.append(job.cache)
        singles.extend(self._dummy_row() for _ in range(kp - k))

        media = ctxv = None
        if self.media_kind != "none":
            zero_e = np.zeros((1, self.ctx_len, self.embed_dim), np.float32)
            zero_v = np.zeros((1, self.ctx_len), bool)
            media = np.concatenate([job.embeds for job, _ in rows]
                                   + [zero_e] * (kp - k), axis=0)
            ctxv = np.concatenate([job.ctx_valid for job, _ in rows]
                                  + [zero_v] * (kp - k), axis=0)

        fn = self._prefill_fn(bucket, kp, cross_cached)
        logits, out_cache = fn(
            self.params, jnp.asarray(toks), jnp.asarray(poss),
            tuple(singles),
            jnp.asarray(media) if media is not None else None,
            jnp.asarray(ctxv) if ctxv is not None else None,
            jnp.asarray(valid), jnp.asarray(last_idx))
        stats = self.scheduler.stats
        stats.prefill_waves += 1
        stats.prefill_chunks += k

        done: List[Tuple[_PrefillJob, jax.Array]] = []
        for i, (job, take) in enumerate(rows):
            job.cache = slice_cache_row(out_cache, i)
            job.consumed += take

            # publish cross-KV for future identical media sets (the first
            # chunk fully materialises every layer's xk/xv).  Under the
            # paged layout the entry leases accounting pages from the
            # arena, so device-resident media bytes show up in
            # page_occupancy() — the admission KV-headroom probe and the
            # pressure ladder govern them like any slot's pages
            if job.publish_xkv:
                xkv = self._extract_xkv(job.cache)
                nbytes = tree_bytes(xkv)
                pages = self._lease_xkv_pages(nbytes)
                if pages is None:
                    self.media_stats.xkv_publish_skipped += 1
                else:
                    self.content_cache.put_cross_kv(
                        job.req.media_set_digest,
                        CrossKVEntry(xkv, self.ctx_len, nbytes,
                                     pages=pages))
                job.publish_xkv = False

            if job.consumed >= len(job.tokens):
                if job.slot is None:
                    # speculative job finished before a slot freed: stage
                    # the last-position logits; admission commits directly
                    job.logits = logits[i]
                else:
                    done.append((job, logits[i]))
                continue
            # Alg.2, per chunk: publish the partial prefix so an identical
            # long prompt arriving behind us resumes from finished chunks
            # instead of re-prefilling them.  Rolling: each boundary
            # replaces the job's previous entry, so one in-flight prompt
            # holds at most one partial cache in the byte budget.  This is
            # also what makes speculative prefill work durable: even if the
            # speculated request is never admitted here, its chunks are
            # already published for whoever prefills that prompt next.
            if (self.prefix_cache is not None
                    and job.consumed >= self.prefix_cache.block_size):
                salt = self._salt(job.req)
                prefix = job.tokens[:job.consumed]
                new_key = self.prefix_cache.key_for(prefix, salt=salt)
                self.prefix_cache.insert(
                    prefix, {"cache": job.cache, "len": job.consumed},
                    tree_bytes(job.cache), salt=salt)
                if job.partial_key and job.partial_key != new_key:
                    self.prefix_cache.discard(job.partial_key)
                job.partial_key = new_key
            if job.slot is not None:
                self.scheduler.enqueue_prefill(job)
            # speculative jobs stay in _spec_jobs and ride a later wave
        return done

    def _commit_jobs(self, completed: List[Tuple[_PrefillJob, jax.Array]]
                     ) -> List[StreamEvent]:
        """Sample first tokens for the finished wave (one batched call, one
        host sync) and land the admissions in pool + decode state."""
        if not completed:
            return []
        jobs = [j for j, _ in completed]
        logits = jnp.stack([lg for _, lg in completed])          # [k, V]
        # first tokens use the same per-request sampler as the decode block:
        # key = fold_in(base, position-of-the-new-token), parameters resolved
        # through the same fallback rule — so token 0 and token 1 of a
        # request are drawn from one consistent stream
        samp = [self._resolve_sampling(j.req) for j in jobs]
        firsts = np.asarray(masked_sample(
            logits,
            jnp.asarray(np.stack([j.req.sample_key for j in jobs])),
            jnp.asarray([len(j.tokens) for j in jobs], jnp.int32),
            jnp.asarray([s[0] for s in samp], jnp.float32),
            jnp.asarray([s[1] for s in samp], jnp.float32),
            jnp.asarray([s[2] for s in samp], jnp.int32),
            jnp.asarray([s[3] for s in samp], jnp.float32)))
        # first-token logprobs for requests that asked: one host-side
        # log-softmax over the staged wave logits (tiny: [k, V])
        lp = (np.asarray(jax.nn.log_softmax(logits, axis=-1))
              if any(j.req.sampling.logprobs for j in jobs) else None)
        now = time.monotonic()
        wave = []
        for i, (job, first) in enumerate(zip(jobs, firsts)):
            req = job.req
            # guards: a preempted request resumed by re-prefill keeps its
            # original prefill/first-token timestamps (TTFT is a property
            # of the request, not of its latest slot binding)
            if req.prefill_time is None:
                req.prefill_time = now - job.t0
            if req.first_token_time is None:
                req.first_token_time = now
            req.output_tokens.append(int(first))
            logprob = top = None
            if lp is not None and req.sampling.logprobs:
                logprob, top = self._top_logprobs(lp[i], int(first),
                                                  req.sampling.top_logprobs)
            wave.append(_Admission(
                job.slot, req, job.cache, int(first),
                None if job.ctx_valid is None else job.ctx_valid[0],
                seq_len=len(job.tokens), logprob=logprob, top_logprobs=top))
        return self._commit_admissions(wave)

    @staticmethod
    def _top_logprobs(row: np.ndarray, token: int, n: int
                      ) -> Tuple[float, List[Tuple[int, float]]]:
        """(chosen logprob, top-n (token_id, logprob) pairs) from one [V]
        log-softmax row."""
        top: List[Tuple[int, float]] = []
        if n > 0:
            ids = np.argsort(row)[::-1][:n]
            top = [(int(t), float(row[t])) for t in ids]
        return float(row[token]), top

    def _commit_admissions(self, wave: List[_Admission]) -> List[StreamEvent]:
        """Land an admission wave: one compiled cache scatter, one decode-state
        scatter, then per-request stream/finish bookkeeping."""
        if self._paged:
            self._paged_insert_wave(wave)
        else:
            self.pool.insert_many([a.slot for a in wave],
                                  [a.single_cache for a in wave])
        self._live_slots.update(a.slot for a in wave)
        for a in wave:
            self._group_publish(a)
        events: List[StreamEvent] = []
        for a in wave:
            # a resumed-by-prefill request keeps its streamer (mid-UTF-8
            # decode state survives the eviction)
            if a.req.request_id not in self._streamers:
                self._streamers[a.req.request_id] = \
                    TokenStreamDecoder(self.tokenizer)
                if a.req.sampling.stop_sequences:
                    self._stopchk[a.req.request_id] = StopSequenceChecker(
                        list(a.req.sampling.stop_sequences))
            a.req.status = RequestStatus.DECODING
            try:
                events.extend(self._emit_token(a.slot, a.req, a.first_token,
                                               a.logprob, a.top_logprobs))
            except Exception as e:  # per-request fault boundary (codec)
                self._fault_events.extend(self._fail_request(
                    a.req.request_id, f"codec failure: {e}"))

        self._admit_rows_to_state(
            [(a.slot, a.req, a.first_token, a.seq_len, a.ctx_valid,
              not a.req.is_finished) for a in wave])
        return events

    def _group_publish(self, a: "_Admission") -> None:
        """n>1 group leader's commit: stage its freshly inserted prompt
        cache as the group's shared value, so followers admit against it.
        Fires exactly once (the first commit is always the prompt-only one;
        a preemption re-prefill commits with history appended and is
        guarded out).  Paged pools share the slot's prompt pages by
        incref'd reference — zero copies; dense pools share the row read
        back from the pool (generated KV lands only in later blocks, so
        the row is exactly the prompt prefill)."""
        req = a.req
        g = self._prefill_groups.get(req.request_id)
        if (g is None or g["value"] is not None or g["remaining"] <= 0
                or a.seq_len != len(req.prompt_tokens)
                or self._has_media(req)):
            return
        if self._paged:
            ps = self.pool.page_size
            pub = list(self.pool.slot_pages(a.slot)[:a.seq_len // ps])
            self.pool.incref_pages(pub)
            g["value"] = {"pages": pub, "dense": a.single_cache,
                          "len": a.seq_len}
        else:
            g["value"] = {"cache": self.pool.read(a.slot), "len": a.seq_len}

    def _paged_insert_wave(self, wave: List[_Admission]) -> None:
        """Paged admission: each row's COW-leased prefix pages map into the
        slot's table with zero copies (the lease's refs transfer), fresh
        pages are allocated only past the shared prefix, and the dense
        prefill row scatters into those fresh pages alone.  On arena
        exhaustion, prefix-cache entries are evicted (freeing their leased
        pages) and the insert retried; leases are popped only after
        success, so a failed commit still releases them via _terminate."""
        slots = [a.slot for a in wave]
        singles = [a.single_cache for a in wave]
        consumed = [a.seq_len for a in wave]
        shared = [self._job_leases.get(a.req.request_id, ())
                  for a in wave]
        while True:
            try:
                self.pool.insert_many(slots, singles, consumed=consumed,
                                      shared=shared)
                break
            except PagePoolExhausted:
                if self.prefix_cache is not None and \
                        self.prefix_cache.evict_lru():
                    continue
                if self.content_cache is not None and \
                        self.content_cache.evict_cross_kv_lru():
                    continue
                raise
        for a in wave:                  # lease ownership moved to the slot
            self._job_leases.pop(a.req.request_id, None)
        # Alg.2 publication at *commit* (the dense pool publishes at retire):
        # the slot's full prompt pages are shared into the prefix cache now,
        # so an identical prompt admitted while this one still decodes maps
        # the same pages COW.  The dense shadow row keeps the prefill
        # pipeline (chunked resume) dense and bit-identical.  A ring wrap
        # never corrupts the entry: wrapping writes COW-split first.
        if self.prefix_cache is None:
            return
        ps = self.pool.page_size
        for a in wave:
            req = a.req
            toks = req.prompt_tokens + req.output_tokens[:-1]
            assert len(toks) == a.seq_len
            if len(toks) < self.prefix_cache.block_size:
                continue
            pub = list(self.pool.slot_pages(a.slot)[:a.seq_len // ps])
            self.pool.incref_pages(pub)
            value = {"pages": pub, "dense": a.single_cache, "len": a.seq_len}
            nbytes = (self.pool.pages_nbytes(len(pub))
                      + tree_bytes(a.single_cache))
            self.prefix_cache.insert(toks, value, nbytes,
                                     salt=self._salt(req))

    def _admit_rows_to_state(self, rows: List[Tuple[int, Request, int, int,
                                                    Optional[np.ndarray],
                                                    bool]]) -> None:
        """Scatter admission rows into the device :class:`DecodeState` — the
        one place that encodes how a slot's decode state is laid out, shared
        by wave commits and preemption resumes (drift between the two would
        corrupt only resumed requests, the hardest path to notice).  Each
        row: (slot, req, last_token, position-of-last_token, ctx_valid row
        or None, active)."""
        k = len(rows)
        stops = np.full((k, self.max_stop_tokens), -1, np.int32)
        ctx = np.zeros((k, max(self.ctx_len, 1)), bool)
        for i, (_, req, _, _, ctx_valid, _) in enumerate(rows):
            ids = (self.tokenizer.EOS,) + tuple(req.sampling.stop_token_ids)
            stops[i, :len(ids)] = ids
            if ctx_valid is not None:
                ctx[i] = ctx_valid
        samp = [self._resolve_sampling(req) for _, req, *_ in rows]
        self.state = admit_decode_state(
            self.state,
            jnp.asarray([slot for slot, *_ in rows], jnp.int32),
            jnp.asarray([last for _, _, last, *_ in rows], jnp.int32),
            jnp.asarray([pos for _, _, _, pos, *_ in rows], jnp.int32),
            jnp.asarray([s[0] for s in samp], jnp.float32),
            jnp.asarray([s[1] for s in samp], jnp.float32),
            jnp.asarray([s[2] for s in samp], jnp.int32),
            jnp.asarray([s[3] for s in samp], jnp.float32),
            jnp.asarray(np.stack([req.sample_key for _, req, *_ in rows])),
            jnp.asarray(ctx),
            jnp.asarray([req.sampling.max_tokens - req.num_generated
                         for _, req, *_ in rows], jnp.int32),
            jnp.asarray(stops),
            jnp.asarray([active for *_, active in rows], bool))
        for _, req, *_ in rows:
            # `echo` + logprobs: prompt-token logprobs are computed once at
            # the first admission commit (resumes keep the stored list)
            if (req.sampling.echo and req.sampling.logprobs
                    and req.prompt_logprobs is None):
                self._compute_prompt_logprobs(req)
        if self.spec_mode == "off":
            return
        # speculation joins at the same single admission point: acceptance
        # EWMA resets optimistic, and the draft-model rung re-primes its KV
        # from the slot's committed history (preemption resume included)
        for slot, _, _, _, _, act in rows:
            if act:
                self.spec_controller.on_admit(slot)
        if isinstance(self._draft_source, DraftModelSource):
            for slot, req, last, pos, _, act in rows:
                if not act:
                    self._draft_source.release(slot)
                    continue
                base = req.prompt_tokens + req.output_tokens
                if len(base) >= pos:
                    self._draft_source.prime(slot, base[:pos] + [last])
                else:       # history unavailable: slot simply never drafts
                    self._draft_source.release(slot)
            self._draft_source.admit(
                [slot for slot, *_ in rows],
                [last for _, _, last, *_ in rows],
                [pos for _, _, _, pos, *_ in rows],
                [s[0] for s in samp], [s[1] for s in samp],
                [s[2] for s in samp], [s[3] for s in samp],
                np.stack([req.sample_key for _, req, *_ in rows]),
                [active for *_, active in rows])

    def _echo_fn(self, bucket: int):
        """Teacher-forced full-logits pass for prompt-token logprobs
        (OpenAI ``echo``): one batch=1 prefill-mode forward over the padded
        prompt, log-softmaxed.  Same forward as the admission prefill, so
        the returned values are exactly the prefill wave's logits — the
        throwaway cache is sized to the bucket and dropped."""
        if not hasattr(self, "_echo_fns"):
            self._echo_fns: Dict[int, Any] = {}
        if bucket not in self._echo_fns:
            model = self.model

            @jax.jit
            def run(params, cache, toks, length):
                pos = jnp.arange(bucket)[None, :]
                sv = (jnp.arange(bucket) < length)[None, :]
                out = model.apply(params, toks, mode="prefill",
                                  positions=pos, cache=cache, seq_valid=sv)
                return jax.nn.log_softmax(
                    out.logits[0].astype(jnp.float32), axis=-1)

            self._echo_fns[bucket] = run
        return self._echo_fns[bucket]

    def _compute_prompt_logprobs(self, req: Request) -> None:
        toks = req.prompt_tokens
        n = len(toks)
        if n <= 1:
            req.prompt_logprobs = [None] * n
            return
        bucket = _next_bucket(n, floor=self._bucket_floor)
        cache = init_cache(self.cfg, 1, bucket)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = toks
        lp = np.asarray(self._echo_fn(bucket)(
            self.params, cache, jnp.asarray(padded), jnp.int32(n)))
        out: List[Optional[float]] = [None]
        for i in range(1, n):
            out.append(float(lp[i - 1, toks[i]]))
        req.prompt_logprobs = out

    # ------------------------------------------------------------------ #
    # emit / finish / abort (the host side of the request lifecycle)
    # ------------------------------------------------------------------ #
    def _emit_token(self, slot: int, req: Request, token: int,
                    logprob: Optional[float] = None,
                    top_logprobs: Optional[List[Tuple[int, float]]] = None
                    ) -> List[StreamEvent]:
        """Stream one sampled token: incremental detokenisation, host-side
        stop-sequence filtering (text that could still become a match is
        held back; a completed match truncates and finishes the request),
        logprob attachment, and the finish checks."""
        if self.faults is not None:
            # keyed by (request, position): the same token of the same
            # request fails in every replay, nothing else does
            self.faults.check("codec", req.request_id, req.num_generated,
                              detail=f"request {req.request_id} "
                                     f"token {token}")
        text = self._streamers[req.request_id].push_token(token)
        chk = self._stopchk.get(req.request_id)
        stopped = False
        if chk is not None:
            text, stopped = chk.push(text)
        req.output_text += text
        if req.sampling.logprobs:
            req.output_logprobs.append((logprob, top_logprobs or []))
        events = [StreamEvent(req.request_id, token, text,
                              logprob=logprob, top_logprobs=top_logprobs)]
        if stopped:
            # host-detected finish: the device row is still live, so it
            # must be frozen explicitly before the slot is reused; any
            # text still buffered belongs after the match — discard it
            events.extend(self._finish(slot, req, FinishReason.STOP,
                                       publish=False, deactivate=True,
                                       drop_tail=True))
        else:
            events.extend(self._maybe_finish(slot, req, token))
        return events

    def _maybe_finish(self, slot: int, req: Request, token: int
                      ) -> List[StreamEvent]:
        stop_ids = set(req.sampling.stop_token_ids) | {self.tokenizer.EOS}
        reason = None
        if token in stop_ids:
            reason = FinishReason.STOP
        elif req.num_generated >= req.sampling.max_tokens:
            reason = FinishReason.LENGTH
        if reason is None:
            return []
        return self._finish(slot, req, reason)

    def _finish(self, slot: int, req: Request, reason: FinishReason, *,
                publish: bool = True, deactivate: bool = False,
                drop_tail: bool = False) -> List[StreamEvent]:
        """Terminal transition: flush the streamer (through the stop
        checker, so a match completing in the tail is still truncated),
        retire the slot, and emit the finished event.  ``drop_tail`` (the
        stop-sequence finish) discards everything still buffered: it all
        sits after the match, which truncation removed."""
        req.finish_reason = reason
        req.finish_time = time.monotonic()
        req.status = RequestStatus.FINISHED
        tail = self._streamers.pop(req.request_id).flush()
        chk = self._stopchk.pop(req.request_id, None)
        if drop_tail:
            tail = ""
        elif chk is not None:
            safe, stopped = chk.push(tail)
            tail = safe if stopped else safe + chk.flush()
        req.output_text += tail
        self._retire(slot, req, publish=publish)
        if deactivate:
            self._deactivate_slot(slot)
        return [StreamEvent(req.request_id, None, tail,
                            finished=True, finish_reason=reason)]

    def _retire(self, slot: int, req: Request, *, publish: bool = True
                ) -> None:
        # publish the prompt's KV/state to the prefix cache (Alg.2 insert).
        # Skip if generation ring-wrapped the cache: wrapped slots have
        # prompt KV cells overwritten by generated-token KV, so the entry
        # would be silently wrong for a future resume.  Host-side stop
        # -sequence finishes also skip (publish=False): the device kept
        # writing past the stop point for the rest of the block, so
        # num_generated undercounts the ring occupancy.
        wrapped = (len(req.prompt_tokens) + req.num_generated - 1
                   > self.pool.cache_len)
        if publish and self.prefix_cache is not None and not wrapped and \
                not self._paged and \
                len(req.prompt_tokens) >= self.prefix_cache.block_size:
            # salt from the digest stashed at admission — no media re-decode
            single = self.pool.read(slot)
            value = {"cache": single, "len": len(req.prompt_tokens)}
            self.prefix_cache.insert(req.prompt_tokens, value,
                                     tree_bytes(single), salt=self._salt(req))
        self.scheduler.retire(slot)
        self.pool.free(slot)
        self._live_slots.discard(slot)
        self._spec_release(slot)

    def abort(self, request_id: int) -> List[StreamEvent]:
        """Cancel a request wherever it currently lives (see
        DESIGN_engine_client.md for the propagation map):

        * **pending queue** — dropped before it ever binds a slot;
        * **speculative job table** — the backfill job is cancelled (chunks
          already published to the prefix cache stay: they are valid work);
        * **prefill chunk queue** — remaining chunks never ride another
          wave and the bound slot is freed;
        * **eviction-snapshot table** — the preemption snapshot is released
          (popped from the prefix cache's byte budget);
        * **live decode slot** — the slot is freed immediately and its
          device row frozen, so the next decode block ignores it and the
          next admission reuses it.

        Not thread-safe (like every engine method): callers off the engine
        thread go through :meth:`repro.serving.client.EngineClient.abort`,
        which applies aborts at the next block boundary.  Returns the final
        ABORT event (empty list if the request is unknown or already
        finished — abort-after-finish is a no-op)."""
        return self._terminate(request_id, FinishReason.ABORT)

    def _fail_request(self, request_id: int, detail: str
                      ) -> List[StreamEvent]:
        """The per-request fault boundary: fail ONE request with a typed
        ERROR finish event wherever it currently lives, leaving every other
        request untouched — survivors continue bit-identically (asserted by
        tests/test_faults.py).  Cleanup is exactly :meth:`abort`'s
        propagation map; only the terminal reason/status differ.  The
        engine loop never dies for a request-scoped failure."""
        log.warning("request %d failed: %s", request_id, detail)
        return self._terminate(request_id, FinishReason.ERROR, detail)

    def _terminate(self, request_id: int, reason: FinishReason,
                   detail: Optional[str] = None) -> List[StreamEvent]:
        req: Optional[Request] = None
        slot = next((s for s, r in self.scheduler.active.items()
                     if r.request_id == request_id), None)
        if slot is not None:
            req = self.scheduler.active[slot]
            self.scheduler.drop_prefill_jobs(request_id)
            self._ready_jobs = [j for j in self._ready_jobs
                                if j.req.request_id != request_id]
            self.scheduler.abort_slot(slot)
            self.pool.free(slot)
            self._live_slots.discard(slot)
            self._spec_release(slot)
            self._deactivate_slot(slot)
        else:
            req = self.scheduler.abort_pending(request_id)
            job = self._spec_jobs.pop(request_id, None)
            if job is not None:
                req = req or job.req
        if req is None or req.is_finished:
            return []
        self._group_on_terminate(req)
        self._cancel_media_job(request_id)
        self._release_lease(request_id)
        meta = self._evicted.pop(request_id, None)
        if meta is not None:
            # drop the preemption snapshot (byte budget / page leases)
            self._release_snapshot_value(meta["cache"])
            if self.prefix_cache is not None:
                self._release_snapshot_value(self.prefix_cache.take_exact(
                    req.prompt_tokens + req.output_tokens,
                    salt=self._salt(req)))
        req.finish_reason = reason
        req.finish_time = time.monotonic()
        if reason is FinishReason.ABORT:
            req.status = RequestStatus.ABORTED
            self.scheduler.stats.aborted += 1
        else:
            req.status = RequestStatus.FAILED
            req.error = detail
            self.scheduler.stats.failed += 1
        self._streamers.pop(request_id, None)
        self._stopchk.pop(request_id, None)
        return [StreamEvent(request_id, None, "", finished=True,
                            finish_reason=reason)]

    def _recover_decode_block(self, exc: Exception) -> None:
        """Catastrophic decode-block failure — the compiled block itself
        threw, not a per-request fault.  The block donates the KV pool's
        cache and the decode state, so both device buffers must be assumed
        gone: every live request fails with a typed ERROR event (their KV
        rows are unrecoverable), the buffers are rebuilt from scratch, and
        pending / mid-prefill requests — whose partial caches ride outside
        the pool on their jobs — are preserved and continue.  The engine
        loop survives."""
        log.error("decode block failed: %s — failing %d live request(s) "
                  "and rebuilding device buffers", exc,
                  len(self._live_slots))
        # fresh decode state first: the failure paths below touch it
        # (_deactivate_slot), and the donated one may already be invalid
        self.state = self._on_device(init_decode_state(
            self.pool.max_batch, self.ctx_len, self.max_stop_tokens,
            spec_k=self.spec_k))
        if isinstance(self._draft_source, DraftModelSource):
            # the draft pool/state may have been donated into the failed
            # round as well — rebuild both; slots re-prime at re-admission
            self._draft_source.reset()
        for slot in sorted(self._live_slots):
            req = self.scheduler.active.get(slot)
            if req is not None:
                self._fault_events.extend(self._fail_request(
                    req.request_id, f"decode block failed: {exc}"))
        # rebuild the pool's device cache; slot bookkeeping carries over
        # (slots still owned by mid-prefill requests stay marked used —
        # their wave commit scatters into the fresh buffers)
        if self._paged:
            fresh: Any = PagedKVPool(
                self.cfg, self.pool.max_batch, self.pool.cache_len,
                ctx_len=self.ctx_len, page_size=self.pool.page_size,
                num_pages=self.pool.num_pages, kv_dtype=self.pool.kv_dtype)
            # every page lease died with the arena: prefix-cache entries and
            # in-flight job leases point into the old allocator, so drop
            # them without firing release callbacks (clear() is callback
            # -free by design), and null paged snapshots the same way
            if self.prefix_cache is not None:
                self.prefix_cache.clear()
            self._job_leases.clear()
            # cross-KV accounting leases also died with the arena; the xkv
            # arrays themselves are separate device buffers and stay valid,
            # so the entries survive — only their leases detach
            if self.content_cache is not None:
                self.content_cache.detach_page_leases()
            self.media_stats.xkv_lease_pages = 0
            for m in self._evicted.values():
                if isinstance(m.get("cache"), dict) and \
                        m["cache"].get("pages"):
                    m["cache"] = None
            # group share values also leased into the dead arena: keep the
            # dense shadow (separate buffer, still valid), drop the pages
            for g in self._prefill_groups.values():
                if isinstance(g.get("value"), dict):
                    g["value"]["pages"] = []
        else:
            fresh = SlotKVPool(self.cfg, self.pool.max_batch,
                               self.pool.cache_len, ctx_len=self.ctx_len)
        fresh._free = list(self.pool._free)
        fresh._used = set(self.pool._used)
        fresh.cache = self._on_device(fresh.cache)
        self.pool = fresh

    def drain_snapshot(self) -> List[StreamEvent]:
        """Graceful-drain cutoff (EngineClient.drain timeout): publish every
        live decode slot's exact sequence to the prefix cache — the same
        exact-sequence entry a preemption eviction writes, so a warm
        restart resumes the work instead of redoing it — then abort
        everything still in flight.  Every open request gets its terminal
        ABORT event; no client hangs across shutdown."""
        events: List[StreamEvent] = []
        if self.prefix_cache is not None:
            for slot in sorted(self._live_slots):
                req = self.scheduler.active[slot]
                if self._paged:
                    pages = list(self.pool.slot_pages(slot))
                    nonkv = self.pool.read_nonkv(slot)
                    self.pool.incref_pages(pages)
                    self.prefix_cache.insert_exact(
                        req.prompt_tokens + req.output_tokens,
                        {"pages": pages, "nonkv": nonkv,
                         "len": len(req.prompt_tokens) + req.num_generated},
                        self.pool.pages_nbytes(len(pages))
                        + tree_bytes(nonkv),
                        salt=self._salt(req))
                else:
                    single = self.pool.read(slot)
                    self.prefix_cache.insert_exact(
                        req.prompt_tokens + req.output_tokens,
                        {"cache": single}, tree_bytes(single),
                        salt=self._salt(req))
        open_ids = [r.request_id for r in self.scheduler.active.values()]
        open_ids += [r.request_id
                     for r in self.scheduler.pending_in_order()]
        open_ids += list(self._spec_jobs)
        for rid in dict.fromkeys(open_ids):
            events.extend(self.abort(rid))
        events.extend(self._fault_events)
        self._fault_events.clear()
        return events

    # ------------------------------------------------------------------ #
    # cross-replica drain/handoff (DESIGN_router.md)
    # ------------------------------------------------------------------ #
    def export_handoff(self) -> List[Dict[str, Any]]:
        """Rolling-restart handoff: capture every open request as a
        portable record a successor replica resumes *bit-identically*,
        then detach them all without emitting finish events (the requests
        stay alive — their handles migrate with the records).

        Live decode slots export a dense cache snapshot (paged slots
        gather their pages back into one dense row — the same
        ``pool.read`` the eviction snapshot uses) plus their streaming
        -codec state (mid-UTF-8 decoder, stop-sequence holdback), so the
        successor restores the slot through the existing exact-sequence
        resume path.  Everything else — pending, mid-prefill, speculative,
        preempted, and media requests — exports as a queue record that
        re-prefills its prompt+history on the successor; chunked prefill
        is bit-identical to monolithic, so the continuation is too.  The
        per-request ``sample_key`` travels on the request itself, keeping
        seeded/stochastic streams exact across the hop."""
        records: List[Dict[str, Any]] = []
        for slot in sorted(self._live_slots):
            req = self.scheduler.active.get(slot)
            if req is None or req.is_finished:
                continue
            if self._has_media(req):
                continue                  # exported below as a queue record
            records.append({
                "req": req,
                "cache": {"cache": self.pool.read(slot)},
                "ctx_valid": (np.asarray(self.state.ctx_valid[slot])
                              if self.media_kind != "none" else None),
                "streamer": self._streamers.get(req.request_id),
                "stopchk": self._stopchk.get(req.request_id),
            })
        snapshotted = {r["req"].request_id for r in records}
        others = [r for r in self.scheduler.active.values()]
        others += list(self.scheduler.pending_in_order())
        others += [j.req for j in self._spec_jobs.values()]
        for req in others:
            if (req.request_id in snapshotted or req.is_finished):
                continue
            snapshotted.add(req.request_id)
            records.append({
                "req": req, "cache": None, "ctx_valid": None,
                "streamer": self._streamers.get(req.request_id),
                "stopchk": self._stopchk.get(req.request_id),
            })
        for rec in records:
            self._detach(rec["req"])
        return records

    def _detach(self, req: Request) -> None:
        """Release every engine resource a request holds — exactly
        :meth:`abort`'s propagation map — WITHOUT finishing it: no
        terminal event, status back to QUEUED.  The request object itself
        (prompt, generated history, sample key, codec state captured by
        the caller) is the handoff payload."""
        rid = req.request_id
        slot = next((s for s, r in self.scheduler.active.items()
                     if r.request_id == rid), None)
        if slot is not None:
            self.scheduler.drop_prefill_jobs(rid)
            self._ready_jobs = [j for j in self._ready_jobs
                                if j.req.request_id != rid]
            self.scheduler.abort_slot(slot)
            self.pool.free(slot)
            self._live_slots.discard(slot)
            self._spec_release(slot)
            self._deactivate_slot(slot)
        else:
            self.scheduler.abort_pending(rid)
            self._spec_jobs.pop(rid, None)
        self._group_on_terminate(req)
        self._cancel_media_job(rid)
        self._release_lease(rid)
        meta = self._evicted.pop(rid, None)
        if meta is not None:
            self._release_snapshot_value(meta["cache"])
            if self.prefix_cache is not None:
                self._release_snapshot_value(self.prefix_cache.take_exact(
                    req.prompt_tokens + req.output_tokens,
                    salt=self._salt(req)))
        self._streamers.pop(rid, None)
        self._stopchk.pop(rid, None)
        req.status = RequestStatus.QUEUED

    def import_handoff(self, rec: Dict[str, Any]) -> None:
        """Adopt one exported record: requests with a cache snapshot seed
        the eviction-resume table (``_bind_slot`` restores the slot through
        ``_try_resume`` — the same code path preemption resume takes, so
        the continuation is bit-identical); records without one re-prefill
        prompt+history.  Codec state (mid-UTF-8 decoder, stop-sequence
        holdback) is installed ahead of admission; ``sample_key`` is
        already bound on the request and survives the hop untouched."""
        req = rec["req"]
        rid = req.request_id
        self._assign_sample_key(req)      # idempotent: keeps the key stream
        if rec.get("streamer") is not None:
            self._streamers[rid] = rec["streamer"]
        if rec.get("stopchk") is not None:
            self._stopchk[rid] = rec["stopchk"]
        if rec.get("cache") is not None:
            # the snapshot may live on the exporting replica's chip
            self._evicted[rid] = {"cache": self._on_device(rec["cache"]),
                                  "ctx_valid": rec.get("ctx_valid")}
        elif req.output_tokens:
            # mid-generation record without a snapshot: resume by
            # re-prefilling the whole history (the preemption fallback)
            req.preempt_count = max(1, req.preempt_count)
        req.status = RequestStatus.QUEUED
        self.scheduler.add(req)

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #
    def content_cache_stats(self) -> Dict[str, Any]:
        """Content-cache + media-pipeline counters for ``GET /stats``.
        Plain-int reads of engine-thread-owned counters, so handler threads
        may call this concurrently with the engine loop (same contract as
        ``scheduler.snapshot``).  Media counters exist even with the cache
        disabled — the singleflight dedup invariant is engine-level."""
        ms = self.media_stats
        out: Dict[str, Any] = {
            "enabled": self.content_cache is not None,
            "encoder_invocations": ms.encoder_invocations,
            "encode_waves": ms.encode_waves,
            "encode_queue_depth": len(self._encode_tasks),
            "dedup_joins": ms.dedup_joins,
            "embed_hits": ms.embed_hits,
            "embed_misses": ms.embed_misses,
            "xkv_hits": ms.xkv_hits,
            "xkv_misses": ms.xkv_misses,
            "xkv_lease_pages": ms.xkv_lease_pages,
            "xkv_publish_skipped": ms.xkv_publish_skipped,
        }
        if self.content_cache is not None:
            s = self.content_cache.stats
            out.update(bytes=self.content_cache.nbytes,
                       entries=len(self.content_cache),
                       insertions=s.insertions,
                       evictions=s.evictions,
                       bytes_evicted=s.bytes_evicted)
        return out

    def validate_request(self, req: Request) -> None:
        """Validate + normalise a request without enqueueing it: prompt
        -length policy (truncate or raise), stop-token / stop-sequence /
        logprob / sampler checks, and base-PRNG-key binding.  Idempotent.
        ``add_request`` calls this; the admission-queue path
        (:class:`~repro.serving.client.EngineClient` with an
        ``AdmissionController``) calls it at submit time so invalid
        requests raise to the caller instead of failing later on the
        engine loop."""
        n = len(req.prompt_tokens)
        if not self.cfg.sliding_window and n > self.pool.cache_len:
            if not self.truncate_long_prompts:
                raise PromptTooLongError(
                    f"prompt has {n} tokens but the KV cache holds "
                    f"{self.pool.cache_len}; raise cache_len or pass "
                    "truncate_long_prompts=True")
            req.metadata["truncated_prompt_from"] = n
            req.prompt_tokens = list(req.prompt_tokens[-self.pool.cache_len:])
        if len(req.sampling.stop_token_ids) + 1 > self.max_stop_tokens:
            raise ValueError(
                f"{len(req.sampling.stop_token_ids)} stop tokens exceed "
                f"max_stop_tokens={self.max_stop_tokens}")
        if any(not isinstance(s, str) or not s
               for s in req.sampling.stop_sequences):
            raise ValueError("stop sequences must be non-empty strings")
        if not 0 <= req.sampling.top_logprobs <= self.max_top_logprobs:
            raise ValueError(
                f"top_logprobs={req.sampling.top_logprobs} out of range "
                f"[0, max_top_logprobs={self.max_top_logprobs}]")
        # sampler hardening (mirrors the top_logprobs check): out-of-range
        # top_p/top_k/min_p/seed raise here — i.e. at EngineClient.submit —
        # before the request can reach a decode slot
        validate_sampling_params(req.sampling.top_p, req.sampling.top_k,
                                 req.sampling.min_p, req.sampling.seed)
        if req.sampling.echo and (req.images or req.video_frames
                                  or req.audio is not None):
            raise ValueError(
                "echo is supported for text-only prompts (prompt logprobs "
                "are teacher-forced over the token sequence alone)")
        self._assign_sample_key(req)
        # an n>1 group leader opens its group entry here (i.e. at
        # EngineClient.submit) so followers released later — possibly in a
        # different admission round — find it and wait for the shared value
        if (req.group_size > 1 and req.group_leader is None
                and req.request_id not in self._prefill_groups):
            self._prefill_groups[req.request_id] = {
                "value": None, "remaining": req.group_size - 1,
                "failed": False}
            self.group_stats["groups"] += 1

    def add_request(self, req: Request) -> None:
        self.validate_request(req)
        req.status = RequestStatus.QUEUED
        self.scheduler.add(req)

    # ------------------------------------------------------------------ #
    # speculative decoding rounds
    # ------------------------------------------------------------------ #
    def _plan_spec_lens(self, reclaim_queued: bool) -> Optional[np.ndarray]:
        """Host-side staging plan for one draft-verify round: per-slot draft
        lengths, or None to run a normal decode block instead.

        A slot stages zero drafts when (guards, in order): the scheduler is
        under pressure or acceptance is on probation (``plan_spec_k`` = 0);
        its ring would wrap inside the round (``pos + spec_k >= cache_len``
        — a wrapped validity mask would let a verify query attend to cells
        written for later queries in the same batched pass); its remaining
        budget cannot accept any draft; or (draft rung) its draft KV is not
        primed.  All-zero rounds return None so an unspeculable batch keeps
        the K-step amortisation of plain block decode."""
        acceptance = self.spec_controller.tick()
        k_cap = self.scheduler.plan_spec_k(self.spec_k, acceptance,
                                           reclaim_queued=reclaim_queued)
        if k_cap <= 0:
            return None
        lens = np.zeros((self.pool.max_batch,), np.int32)
        props: Dict[int, List[int]] = {}
        draft_rung = isinstance(self._draft_source, DraftModelSource)
        for slot, pos in self._live_positions().items():
            req = self.scheduler.active[slot]
            if pos + self.spec_k >= self.pool.cache_len:
                continue
            kmax = min(k_cap, req.sampling.max_tokens
                       - req.num_generated - 1)
            if kmax <= 0:
                continue
            if draft_rung:
                if self._draft_source.primed(slot):
                    lens[slot] = kmax
            else:
                p = self._draft_source.propose(
                    req.prompt_tokens + req.output_tokens, kmax)
                if p:
                    props[slot] = p
                    lens[slot] = len(p)
        if not lens.any():
            return None
        self._spec_props = props
        return lens

    def _dispatch_spec_round(self, lens: np.ndarray, want_lp: bool):
        """Stage drafts and dispatch one compiled verify round; returns the
        block plan + accounting arrays, or None on catastrophic failure
        (recovery already ran)."""
        fix = None
        q = None
        if self._paged:
            # the verify forward writes up to spec_k + 1 positions per slot
            self._ensure_paged_capacity(self.spec_k + 1)
        if isinstance(self._draft_source, DraftModelSource):
            snap, start_pos, drafts, q = \
                self._draft_source.draft_round(self.spec_k)
            fix = (snap, start_pos)
        else:
            host = np.zeros((self.pool.max_batch, self.spec_k), np.int32)
            for slot, p in self._spec_props.items():
                host[slot, :len(p)] = p
            drafts = jnp.asarray(host)
        self.state = stage_drafts(self.state, drafts,
                                  jnp.asarray(lens, dtype=jnp.int32))
        try:
            cache, state, toks, n_acc, n_emit, lps = self._spec_verify_fn(
                self.params, self.pool.cache, self.state, q,
                spec_k=self.spec_k, want_logprobs=want_lp,
                use_q=self._draft_source.uses_q)
        except Exception as e:      # catastrophic round failure
            self._recover_decode_block(e)
            return None
        self.pool.cache = cache
        self.state = state
        if fix is not None:
            self._draft_source.fixup(self.spec_k, *fix, state)
        return {"plan": (self.spec_k + 1, toks, lps),
                "lens": lens, "n_acc": n_acc, "n_emit": n_emit}

    def _account_spec_round(self, meta: Dict[str, Any]) -> None:
        lens = meta["lens"]
        n_acc = np.asarray(meta["n_acc"])
        n_emit = np.asarray(meta["n_emit"])
        st = self.spec_stats
        st.rounds += 1
        st.emitted += int(n_emit.sum())
        for slot in np.nonzero(lens)[0]:
            d = int(lens[slot])
            a = int(min(n_acc[slot], d))
            st.drafted += d
            st.accepted += a
            st.rejected += d - a
            self.spec_controller.observe(int(slot), d, a)

    def speculation_stats(self) -> Dict[str, Any]:
        """Speculation counter block for ``GET /stats`` (plain-int reads,
        same concurrency contract as ``scheduler.snapshot``)."""
        out: Dict[str, Any] = {"mode": self.spec_mode, "k": self.spec_k}
        out.update(self.spec_stats.snapshot())
        out["slot_acceptance_ewma"] = self.spec_controller.snapshot()
        out["draft_pool_bytes"] = (
            self._draft_source.nbytes
            if isinstance(self._draft_source, DraftModelSource) else 0)
        return out

    def step(self) -> List[StreamEvent]:
        """One scheduler iteration (paper Alg.1 loop body, K tokens).

        Async overlap: the decode block is dispatched first, the prefill
        wave's device work second, and only *then* does the host block on
        the decode block's token sync — so wave compute executes behind the
        host-sync window instead of stalling the decode loop.
        """
        events: List[StreamEvent] = []
        self._fault_tick += 1
        if (self.faults is not None
                and self.faults.fires("slow_step", self._fault_tick)):
            # injected wedged step (drives the EngineClient watchdog)
            time.sleep(self.faults.slow_step_s)

        # 1. bind pending requests to slots; open prefill jobs
        self._plan_admissions()

        # 2. dispatch one compiled block of K decode steps (no host block
        # yet); K collapses to 1 while requests, chunks, or — via the
        # client-installed reclaim hint — aborts wait at the boundary
        block_plan = None
        spec_meta = None
        if self._live_slots:
            reclaim_q = bool(self.reclaim_hint is not None
                             and self.reclaim_hint())
            want_lp = any(r.sampling.logprobs
                          for s, r in self.scheduler.active.items()
                          if s in self._live_slots)
            spec_lens = (self._plan_spec_lens(reclaim_q)
                         if self._spec_verify_fn is not None else None)
            if spec_lens is not None:
                # draft-verify round: one wider forward commits up to
                # spec_k + 1 tokens per slot in a single device dispatch
                spec_meta = self._dispatch_spec_round(spec_lens, want_lp)
                if spec_meta is not None:
                    block_plan = spec_meta["plan"]
            else:
                num_steps = self.scheduler.plan_decode_block(
                    self.max_decode_block, reclaim_queued=reclaim_q)
                if self._paged:
                    # the block's KV writes must land on exclusively-owned
                    # pages: allocate tails / COW-split shared pages now,
                    # under the page-pressure ladder (can shrink
                    # _live_slots)
                    self._ensure_paged_capacity(num_steps)
                try:
                    cache, state, toks, lps = self._decode_block_fn(
                        self.params, self.pool.cache, self.state,
                        num_steps=num_steps, want_logprobs=want_lp)
                except Exception as e:  # catastrophic block failure
                    self._recover_decode_block(e)
                else:
                    self.pool.cache = cache
                    self.state = state
                    block_plan = (num_steps, toks, lps)

        # 3. run an encode wave + dispatch the prefill wave behind the
        # in-flight decode block: both are host/new-device work that hides
        # in the block's host-sync window.  Encodes resolved here make
        # their requests admission-eligible next step
        self._dispatch_encode_wave()
        completed = self._dispatch_prefill_wave()

        # 4. sync the token block; emit + retire step-major
        if block_plan is not None:
            num_steps, toks, lps = block_plan
            block = np.asarray(toks)              # [K, B]: the block's one sync
            lp_c = lp_v = lp_i = None
            if lps is not None:
                lp_c, lp_v, lp_i = (np.asarray(a) for a in lps)
            self._step_count += 1
            self.scheduler.stats.steps += 1
            # one spec round is ONE device dispatch however many rows it
            # commits — that asymmetry is the whole point
            self.scheduler.stats.device_steps += \
                (1 if spec_meta is not None else num_steps)
            if spec_meta is not None:
                self._account_spec_round(spec_meta)
            live = {s: r for s, r in self.scheduler.active.items()
                    if s in self._live_slots}
            for k in range(num_steps):
                for slot in sorted(live):
                    req = live[slot]
                    if req.is_finished:
                        continue
                    tok = int(block[k, slot])
                    if tok < 0:
                        # frozen-slot sentinel: the device finish-mask fired
                        # but the host hasn't (belt and braces — the two
                        # conditions are equivalent by construction)
                        continue
                    if tok >= self.cfg.vocab_size or (
                            self.faults is not None
                            and self.faults.fires("decode", req.request_id,
                                                  req.num_generated)):
                        # corrupt sampled token (the NaN-in-logits scenario,
                        # or its injected stand-in): fail this request only;
                        # neighbour slots are independent (per-slot RNG,
                        # masked cache writes) and continue bit-identically
                        self._fault_events.extend(self._fail_request(
                            req.request_id,
                            f"corrupt token {tok} at position "
                            f"{req.num_generated}"))
                        continue
                    req.output_tokens.append(tok)
                    self.scheduler.stats.tokens_generated += 1
                    logprob = top = None
                    if lp_c is not None and req.sampling.logprobs:
                        logprob = float(lp_c[k, slot])
                        ntop = req.sampling.top_logprobs
                        top = list(zip(lp_i[k, slot, :ntop].tolist(),
                                       lp_v[k, slot, :ntop].tolist()))
                    try:
                        events.extend(
                            self._emit_token(slot, req, tok, logprob, top))
                    except Exception as e:  # per-request boundary (codec)
                        self._fault_events.extend(self._fail_request(
                            req.request_id, f"codec failure: {e}"))

        # 5. land finished prefills (next block picks the new slots up);
        # speculative jobs whose slot arrived this step commit in the same
        # batched call, their staged logits standing in for a wave row
        ready = [(j, j.logits) for j in self._ready_jobs]
        self._ready_jobs.clear()
        try:
            events.extend(self._commit_jobs(ready + completed))
        except Exception as e:  # commit-wave fault boundary
            log.warning("admission commit failed (%d jobs): %s",
                        len(ready) + len(completed), e)
            for job, _ in ready + completed:
                self._fault_events.extend(self._fail_request(
                    job.req.request_id, f"admission commit failed: {e}"))

        # drain terminal events raised at interior fault boundaries (every
        # failed request surfaces exactly one typed ERROR event)
        if self._fault_events:
            events.extend(self._fault_events)
            self._fault_events.clear()
        return events

    def run(self) -> List[StreamEvent]:
        events = []
        while self.scheduler.has_work:
            events.extend(self.step())
        return events

    def generate(self, requests: List[Request]) -> List[Request]:
        for r in requests:
            self.add_request(r)
        self.run()
        return requests
