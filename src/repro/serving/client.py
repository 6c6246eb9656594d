"""EngineClient: the async-first request-lifecycle API over the engine.

This is the layer between the single-threaded continuous-batching engine
and anything concurrent — HTTP handlers, asyncio apps, benchmark drivers.
``submit(GenerationRequest) -> RequestHandle`` returns immediately; the
handle exposes the whole lifecycle of one *logical* request (which fans out
to ``n`` engine requests for OpenAI-style multi-choice sampling):

* ``handle.stream()`` — typed :class:`TokenEvent` / :class:`FinishEvent`
  stream, consumable as a plain iterator **and** as an async iterator
  (``async for`` runs the blocking queue reads in a worker thread, so one
  event loop can multiplex many handles without starving the engine);
* ``handle.result()`` / ``await handle.result_async()`` — block until every
  choice finished, then return a :class:`GenerationResult`;
* ``handle.abort()`` — true cancellation: the abort propagates through the
  scheduler (pending queue, chunk queue, speculative jobs, preemption
  snapshots) and the engine (live slot freed, device row frozen) at the
  next block boundary — a disconnected client never holds a slot to budget
  exhaustion;
* ``handle.status`` — coarsest in-flight choice state
  (:class:`repro.core.request.RequestStatus`).

One dedicated loop thread owns the engine and drives ``engine.step()``
(the paper's Algorithm 1 outer loop); with block decode each step advances
up to ``max_decode_block`` tokens and the whole block's events fan out to
the per-handle queues in one critical section.  Submissions and aborts are
thread-safe and are applied at block boundaries: the engine collapses the
block size to 1 whenever requests or prefill chunks are pending, so a new
request waits at most one token for a free slot, and an abort frees its
slot within one decode block.

``OpenAIServer`` (serving/api.py) and ``ApiServer`` (serving/server.py)
are thin codecs over this client — they never touch engine internals.
"""
from __future__ import annotations

import asyncio
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple, Union

from repro import tracing
from repro.core.admission import (LEVEL_SHED_ALL, AdmissionController,
                                  Overloaded)
from repro.core.engine import InferenceEngine
from repro.core.request import (
    FinishReason,
    GenerationRequest,
    Request,
    RequestStatus,
    StreamEvent,
)

log = logging.getLogger("repro.client")

# lifecycle progress order used to aggregate a handle's per-choice states
_PROGRESS = {
    RequestStatus.QUEUED: 0,
    RequestStatus.PREFILLING: 1,
    RequestStatus.DECODING: 2,
    RequestStatus.FINISHED: 3,
    RequestStatus.ABORTED: 3,
    RequestStatus.FAILED: 3,
}


@dataclass
class TokenEvent:
    """One generated token for one choice of a handle."""

    index: int                    # choice index (0..n-1)
    token: int
    text: str                     # stop-sequence-filtered incremental text
    logprob: Optional[float] = None
    top_logprobs: Optional[List[Tuple[int, float]]] = None


@dataclass
class FinishEvent:
    """Terminal event for one choice; ``text`` carries any held-back tail
    (incomplete UTF-8 bytes / unmatched stop-sequence prefix)."""

    index: int
    finish_reason: str    # "stop" | "length" | "abort" | "timeout" | "error"
    text: str = ""


@dataclass
class ChoiceResult:
    index: int
    text: str
    tokens: List[int]
    finish_reason: Optional[str]
    logprobs: List[Tuple[float, List[Tuple[int, float]]]] = field(default_factory=list)
    # completions `echo`: the prompt token ids and — when the request also
    # asked for logprobs — their teacher-forced logprobs (first entry None)
    prompt_token_ids: List[int] = field(default_factory=list)
    prompt_logprobs: Optional[List[Optional[float]]] = None


@dataclass
class GenerationResult:
    """Aggregate of all ``n`` choices of one handle."""

    choices: List[ChoiceResult]
    prompt_tokens: int

    @property
    def text(self) -> str:
        return self.choices[0].text

    @property
    def completion_tokens(self) -> int:
        return sum(len(c.tokens) for c in self.choices)

    def usage(self) -> Dict[str, int]:
        return {
            "prompt_tokens": self.prompt_tokens,
            "completion_tokens": self.completion_tokens,
            "total_tokens": self.prompt_tokens + self.completion_tokens,
        }


@dataclass
class HandoffRecord:
    """One open request leaving a draining replica: the engine-level
    record (live request object + optional cache snapshot + streaming
    codec state) plus the client handle that migrates with it.  Produced
    by :meth:`EngineClient.handoff_export`, consumed by
    :meth:`EngineClient.handoff_import` — in-process only (the record
    carries live objects, not bytes)."""

    record: Dict[str, object]
    handle: Optional["RequestHandle"] = None

    @property
    def request(self) -> Request:
        return self.record["req"]  # type: ignore[return-value]


class HandleStream:
    """Single-consumer event stream of a handle: iterate synchronously or
    with ``async for``.  The async path is event-driven, not
    thread-bridged: the engine thread wakes a per-consumer
    ``asyncio.Event`` via ``call_soon_threadsafe``, so one event loop can
    hold hundreds of open streams without parking a worker thread per
    stream (the old ``asyncio.to_thread(q.get)`` bridge capped concurrent
    SSE streams at the default executor size)."""

    def __init__(self, q: "queue.Queue[Optional[object]]",
                 handle: Optional["RequestHandle"] = None) -> None:
        self._q = q
        self._handle = handle

    def __iter__(self) -> Iterator[object]:
        while True:
            ev = self._q.get()
            if ev is None:
                return
            yield ev

    def __aiter__(self):
        return self._agen()

    async def _agen(self):
        if self._handle is None:         # bare-queue stream (tests)
            while True:
                ev = await asyncio.to_thread(self._q.get)
                if ev is None:
                    return
                yield ev
        waker = self._handle._register_waker()
        try:
            while True:
                try:
                    ev = self._q.get_nowait()
                except queue.Empty:
                    waker.clear()
                    # re-check after clear: an event put between get_nowait
                    # and clear would otherwise be a lost wakeup
                    try:
                        ev = self._q.get_nowait()
                    except queue.Empty:
                        await waker.wait()
                        continue
                if ev is None:
                    return
                yield ev
        finally:
            self._handle._unregister_waker(waker)


class RequestHandle:
    """Lifecycle handle for one submitted :class:`GenerationRequest`."""

    def __init__(self, client: "EngineClient", requests: List[Request]):
        self._client = client
        self._requests = requests
        self._index = {r.request_id: i for i, r in enumerate(requests)}
        self._queue: "queue.Queue[Optional[object]]" = queue.Queue()
        self._done = threading.Event()
        self._open = len(requests)
        self._lock = threading.Lock()
        # asyncio consumers: (loop, Event) pairs woken from the engine
        # thread on every delivered event (see HandleStream._agen)
        self._wakers: List[Tuple[object, object]] = []

    # -- identity / introspection -------------------------------------- #
    @property
    def request_ids(self) -> List[int]:
        return [r.request_id for r in self._requests]

    @property
    def n(self) -> int:
        return len(self._requests)

    @property
    def prompt_tokens(self) -> int:
        return len(self._requests[0].prompt_tokens)

    @property
    def statuses(self) -> List[RequestStatus]:
        return [r.status for r in self._requests]

    @property
    def status(self) -> RequestStatus:
        """Aggregate state: the least-advanced unfinished choice; FINISHED
        only when every choice is terminal (ABORTED if any was aborted)."""
        states = self.statuses
        running = [s for s in states if _PROGRESS[s] < 3]
        if running:
            return min(running, key=lambda s: _PROGRESS[s])
        if RequestStatus.ABORTED in states:
            return RequestStatus.ABORTED
        if RequestStatus.FAILED in states:
            return RequestStatus.FAILED
        return RequestStatus.FINISHED

    @property
    def finished(self) -> bool:
        return self._done.is_set()

    # -- consumption ---------------------------------------------------- #
    def stream(self) -> HandleStream:
        """The handle's typed event stream (single consumer)."""
        return HandleStream(self._queue, self)

    def result(self, timeout: Optional[float] = None) -> GenerationResult:
        """Block until every choice finished (or aborted)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request not finished within {timeout}s")
        return self._result()

    async def result_async(self) -> GenerationResult:
        """Await completion without blocking a worker thread: the engine
        thread wakes us through the handle's waker list."""
        if not self._done.is_set():
            waker = self._register_waker()
            try:
                while not self._done.is_set():
                    waker.clear()
                    if self._done.is_set():
                        break
                    await waker.wait()
            finally:
                self._unregister_waker(waker)
        return self._result()

    # -- asyncio wakers (engine thread -> event loops) ------------------- #
    def _register_waker(self) -> "asyncio.Event":
        loop = asyncio.get_running_loop()
        waker = asyncio.Event()
        with self._lock:
            self._wakers.append((loop, waker))
            waker.set()                  # force an initial queue check
        return waker

    def _unregister_waker(self, waker: "asyncio.Event") -> None:
        with self._lock:
            self._wakers = [(lp, w) for lp, w in self._wakers
                            if w is not waker]

    def _wake(self) -> None:
        with self._lock:
            wakers = list(self._wakers)
        for loop, waker in wakers:
            try:
                loop.call_soon_threadsafe(waker.set)
            except RuntimeError:         # consumer's loop already closed
                pass

    def _result(self) -> GenerationResult:
        choices = [
            ChoiceResult(
                index=i,
                text=r.output_text,
                tokens=list(r.output_tokens),
                finish_reason=(r.finish_reason.value if r.finish_reason else None),
                logprobs=list(r.output_logprobs),
                prompt_token_ids=list(r.prompt_tokens),
                prompt_logprobs=(None if r.prompt_logprobs is None
                                 else list(r.prompt_logprobs)),
            )
            for i, r in enumerate(self._requests)
        ]
        return GenerationResult(choices=choices, prompt_tokens=self.prompt_tokens)

    def usage(self) -> Dict[str, int]:
        """OpenAI-style usage counts (prompt counted once across choices)."""
        return {
            "prompt_tokens": self.prompt_tokens,
            "completion_tokens": sum(r.num_generated for r in self._requests),
            "total_tokens": self.prompt_tokens + sum(r.num_generated for r in self._requests),
        }

    # -- cancellation --------------------------------------------------- #
    def abort(self, wait: bool = True, timeout: Optional[float] = 30.0) -> bool:
        """Cancel every unfinished choice.  The abort is applied by the
        engine thread at the next block boundary; with ``wait=True`` the
        call returns once the slots are actually reclaimed (the ABORT
        finish events arrived).  Aborting a finished handle is a no-op."""
        if self._done.is_set():
            return True
        self._client._request_abort(self.request_ids)
        if not wait:
            return True
        return self._done.wait(timeout)

    async def abort_async(self) -> bool:
        return await asyncio.to_thread(self.abort)

    # -- engine-thread side --------------------------------------------- #
    def _on_event(self, ev: StreamEvent) -> None:
        """Fan one engine event into the typed stream (engine thread)."""
        idx = self._index[ev.request_id]
        if ev.finished:
            reason = (ev.finish_reason or FinishReason.ABORT).value
            self._queue.put(FinishEvent(idx, reason, ev.text))
            with self._lock:
                self._open -= 1
                last = self._open == 0
            if last:
                self._queue.put(None)          # stream sentinel
                self._done.set()
        elif ev.token is not None:
            self._queue.put(TokenEvent(idx, ev.token, ev.text, ev.logprob, ev.top_logprobs))
        else:
            return
        self._wake()


class EngineClient:
    """Thread-safe request-lifecycle front end that owns the engine.

    Overload protection (PR 6, see DESIGN_overload_and_faults.md): with an
    :class:`AdmissionController` attached, ``submit`` goes through it —
    rate-limited / shed requests raise the typed 429/503
    :class:`~repro.core.admission.AdmissionError` to the caller, admitted
    ones wait in the fair queue and are *released* into the engine by the
    loop thread at block boundaries (queue-wait expirations surface as
    typed ``timeout`` finish events, never hangs).  A ``watchdog_timeout_s``
    arms a sidecar thread that flips readiness when one ``engine.step()``
    wedges; :meth:`drain` implements graceful shutdown.  The loop thread
    itself never dies: engine-internal failures are contained per-request
    at the engine's fault boundaries, and anything escaping them is logged
    and survived here."""

    def __init__(self, engine: InferenceEngine, *,
                 admission: Optional[AdmissionController] = None,
                 watchdog_timeout_s: Optional[float] = None,
                 auto_start: bool = True):
        self.engine = engine
        self._admission = admission
        self._cv = threading.Condition()
        self._handles: Dict[int, RequestHandle] = {}
        self._aborts: List[int] = []
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # graceful-drain state machine: _draining stops new admissions,
        # _drain_cutoff triggers the snapshot-and-abort path, _drained
        # signals the caller that the loop is empty and parked
        self._draining = False
        self._drain_cutoff = False
        self._drained = threading.Event()
        # rolling-restart handoff: the loop thread exports every open
        # request at a block boundary (engine state is quiescent there),
        # then terminates; see handoff_export()
        self._handoff_requested = False
        self._handoff_records: List[HandoffRecord] = []
        self._handoff_done = threading.Event()
        # watchdog: _step_started is (re)stamped around every loop body;
        # the sidecar thread flips _wedged when one body overruns
        self.watchdog_timeout_s = watchdog_timeout_s
        self._step_started: Optional[float] = None
        self._wedged = False
        self._watchdog_trips = 0
        self._watchdog_thread: Optional[threading.Thread] = None
        self._loop_errors = 0
        # collapse the decode block to K=1 while an abort waits at the
        # boundary, so its slot is reclaimed after one device step
        engine.reclaim_hint = lambda: bool(self._aborts)
        # default KV/capacity headroom probe for the degradation ladder:
        # fraction of (decode slots + one engine-queue's worth) still free
        if admission is not None and admission.headroom_fn is None:
            admission.headroom_fn = self._headroom
        if auto_start:
            self.start()

    def _headroom(self) -> float:
        sched = self.engine.scheduler
        cap = max(1, 2 * sched.max_batch)
        used = sched.num_active + len(sched.pending)
        slots = max(0.0, 1.0 - used / cap)
        # paged KV pool: bound headroom by *real* page occupancy, not just
        # slot count — long sequences can exhaust the arena while slots
        # remain free.  Pages held only by cache leases count as available
        # (the engine's pressure ladder reclaims them before shedding
        # matters).  Duck-typed: dense pools have no page_occupancy.
        probe = getattr(self.engine.pool, "page_occupancy", None)
        if probe is not None:
            occ = probe()
            pages = (occ["free"] + occ["reclaimable"]) / max(1, occ["total"])
            return min(slots, pages)
        return slots

    # ------------------------------------------------------------------ #
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        if self.watchdog_timeout_s is not None and self._watchdog_thread is None:
            self._watchdog_thread = threading.Thread(target=self._watchdog_run,
                                                     daemon=True)
            self._watchdog_thread.start()

    def submit(self, request: Union[GenerationRequest, Request]) -> RequestHandle:
        """Validate + enqueue; returns the lifecycle handle immediately.
        Invalid requests (prompt too long, bad stop sequences, ...) raise
        here, before anything is enqueued.  With admission control
        attached, rate-limited / shed requests raise the typed
        :class:`~repro.core.admission.AdmissionError` (429/503 +
        Retry-After) instead of queueing."""
        if isinstance(request, Request):
            reqs = [request]
        else:
            reqs = request.to_requests(self.engine.tokenizer)
        handle = RequestHandle(self, reqs)
        with self._cv:
            if self._stop:
                raise RuntimeError("EngineClient is stopped")
            if self._draining and self._admission is None:
                raise Overloaded("server is draining; retry against "
                                 "another replica", retry_after=1.0,
                                 code="draining")
            admitted: List[Request] = []
            try:
                if self._admission is not None:
                    # validation errors must raise here (not later on the
                    # loop thread), so validate before admission queues it
                    for r in reqs:
                        self.engine.validate_request(r)
                    for r in reqs:
                        self._admission.submit(r)
                        admitted.append(r)
                else:
                    for r in reqs:
                        self.engine.add_request(r)
                        admitted.append(r)
            except BaseException:
                # roll back the partial fan-out so no orphan choice decodes
                if self._admission is not None:
                    for r in admitted:
                        self._admission.drop(r.request_id)
                else:
                    for r in admitted:
                        self._aborts.append(r.request_id)
                self._cv.notify()
                raise
            for r in reqs:
                self._handles[r.request_id] = handle
            self._cv.notify()
        return handle

    def generate(self, request: Union[GenerationRequest, Request]) -> GenerationResult:
        """Blocking convenience: submit + wait."""
        return self.submit(request).result()

    def stats(self) -> Dict[str, object]:
        out = dict(self.engine.scheduler.snapshot())
        out["content_cache"] = self.engine.content_cache_stats()
        out["speculation"] = self.engine.speculation_stats()
        out["prefill_groups"] = dict(self.engine.group_stats)
        out["draining"] = self._draining
        out["loop_errors"] = self._loop_errors
        out["watchdog"] = {
            "timeout_s": self.watchdog_timeout_s,
            "wedged": self._wedged,
            "trips": self._watchdog_trips,
        }
        if self._admission is not None:
            out["admission"] = self._admission.snapshot()
        if self.engine.faults is not None:
            out["faults"] = self.engine.faults.snapshot()
        return out

    # -- health / readiness (the /healthz and /readyz payloads) --------- #
    @property
    def alive(self) -> bool:
        """Liveness: the loop thread exists and has not died."""
        return self._thread is not None and self._thread.is_alive()

    @property
    def ready(self) -> bool:
        """Readiness: alive, not wedged past the watchdog, not draining,
        and not shedding all traffic — load balancers stop routing here
        before the server falls over."""
        if not self.alive or self._wedged or self._draining:
            return False
        if (self._admission is not None
                and self._admission.level >= LEVEL_SHED_ALL):
            return False
        return True

    # ------------------------------------------------------------------ #
    def _request_abort(self, request_ids: List[int]) -> None:
        with self._cv:
            self._aborts.extend(request_ids)
            self._cv.notify()

    def _drain_aborts_locked(self) -> List[int]:
        out, self._aborts = self._aborts, []
        return out

    def _has_work_locked(self) -> bool:
        if self.engine.scheduler.has_work:
            return True
        return (self._admission is not None
                and self._admission.queue_depth > 0)

    def _run(self) -> None:
        engine = self.engine
        while True:
            with self._cv:
                while (not self._has_work_locked() and not self._aborts
                       and not self._stop and not self._drain_cutoff
                       and not self._draining):
                    self._cv.wait(timeout=0.5)
                if self._stop:
                    self._shutdown_locked()
                    self._drained.set()
                    self._handoff_done.set()
                    return
                if self._handoff_requested:
                    # block boundary: the engine is quiescent, so export
                    # every open request and terminate this loop.  Handles
                    # migrate with the records — no finish events here.
                    self._handoff_requested = False
                    self._handoff_export_locked()
                    self._stop = True
                    self._drained.set()
                    self._handoff_done.set()
                    return
                if (self._draining and not self._drain_cutoff
                        and not self._has_work_locked() and not self._aborts):
                    # drain complete: everything in flight finished and the
                    # admission queue is empty — park and signal drain()
                    self._drained.set()
                    self._cv.wait(timeout=0.5)
                    continue
                cutoff, self._drain_cutoff = self._drain_cutoff, False
                aborts = self._drain_aborts_locked()
            events: List[StreamEvent] = []
            self._step_started = time.monotonic()
            try:
                # aborts land at the block boundary, before the next
                # admission plan — the freed slot is reusable in this very
                # step; a request still waiting at admission is dropped
                # there instead
                for rid in aborts:
                    dropped = (self._admission.drop(rid)
                               if self._admission is not None else None)
                    if dropped is not None:
                        events.extend(self._finish_unstarted(
                            dropped, FinishReason.ABORT,
                            RequestStatus.ABORTED))
                    else:
                        events.extend(engine.abort(rid))
                if self._admission is not None:
                    with tracing.span("client.admission"):
                        events.extend(self._admission_round())
                if cutoff:
                    events.extend(self._drain_cutoff_events())
                elif engine.scheduler.has_work:
                    events.extend(engine.step())
            except Exception:
                # last-resort fault isolation: request-scoped failures are
                # already contained at the engine's own boundaries (typed
                # ERROR events); anything reaching here is a harness bug —
                # log it and keep the loop alive (liveness over silence)
                log.exception("engine loop error (loop survives)")
                self._loop_errors += 1
                time.sleep(0.05)        # no hot spin on persistent failure
            finally:
                self._step_started = None
            with self._cv, tracing.span("client.deliver"):
                for ev in events:
                    handle = self._handles.get(ev.request_id)
                    if handle is not None:
                        handle._on_event(ev)
                        if ev.finished:
                            del self._handles[ev.request_id]
                if cutoff:
                    self._drained.set()
            if cutoff:
                return

    @staticmethod
    def _finish_unstarted(req: Request, reason: FinishReason,
                          status: RequestStatus,
                          error: Optional[str] = None) -> List[StreamEvent]:
        """Terminal event for a request that never reached the engine
        (still in the admission queue): queue-wait timeout, abort-before
        -release, or drain cutoff."""
        req.finish_reason = reason
        req.status = status
        req.finish_time = time.monotonic()
        req.error = error
        return [StreamEvent(req.request_id, None, "", finished=True,
                            finish_reason=reason)]

    def _admission_round(self) -> List[StreamEvent]:
        """One fair-release round: expire overdue waiters (typed ``timeout``
        finish events) and release up to the engine's queue headroom in
        weighted-fair order."""
        sched = self.engine.scheduler
        capacity = max(0, sched.max_batch - len(sched.pending))
        ready, expired = self._admission.poll(capacity)
        events: List[StreamEvent] = []
        for req in expired:
            events.extend(self._finish_unstarted(
                req, FinishReason.TIMEOUT, RequestStatus.FAILED,
                error=(f"queue-wait timeout after "
                       f"{self._admission.queue_timeout_s:g}s")))
        for req in ready:
            try:
                self.engine.add_request(req)
            except Exception as e:   # pre-validated, so effectively dead code
                events.extend(self._finish_unstarted(
                    req, FinishReason.ERROR, RequestStatus.FAILED,
                    error=str(e)))
        return events

    def _drain_cutoff_events(self) -> List[StreamEvent]:
        """Drain timeout hit: snapshot + abort everything still in the
        engine, and terminate whatever is still waiting at admission."""
        events = list(self.engine.drain_snapshot())
        if self._admission is not None:
            ready, expired = self._admission.poll(1 << 30)
            for req in expired:
                events.extend(self._finish_unstarted(
                    req, FinishReason.TIMEOUT, RequestStatus.FAILED,
                    error="queue-wait timeout at drain"))
            for req in ready:
                events.extend(self._finish_unstarted(
                    req, FinishReason.ABORT, RequestStatus.ABORTED))
        return events

    def _watchdog_run(self) -> None:
        """Sidecar thread: detect a wedged ``engine.step()`` (a single loop
        body overrunning ``watchdog_timeout_s``).  A Python thread cannot
        be safely killed, so the watchdog's contract is *visibility*: flip
        readiness (load balancers route away), log loudly, and recover
        automatically when the step completes."""
        timeout = self.watchdog_timeout_s
        interval = max(0.005, min(0.5, timeout / 4))
        while not self._stop:
            t0 = self._step_started
            if t0 is not None and time.monotonic() - t0 > timeout:
                if not self._wedged:
                    self._wedged = True
                    self._watchdog_trips += 1
                    log.error(
                        "engine step wedged for > %.3fs (watchdog): "
                        "readiness flips until the step completes", timeout)
            else:
                self._wedged = False
            time.sleep(interval)

    def drain(self, timeout: Optional[float] = 30.0) -> bool:
        """Graceful drain (SIGTERM / ``POST /admin/drain``): stop admitting
        new work (``submit`` 503s with code ``draining``, ``/readyz``
        flips), let in-flight requests finish, then stop the loop.  If they
        have not finished within ``timeout`` seconds, every live slot is
        snapshotted to the prefix cache (exact-sequence entries — a warm
        restart resumes the work) and every open request is terminated with
        its typed event, so no client hangs across shutdown.  Returns True
        when the drain completed without the cutoff.  Idempotent."""
        with self._cv:
            if not self._draining:
                self._draining = True
                if self._admission is not None:
                    self._admission.start_drain()
            self._cv.notify_all()
        finished = self._drained.wait(timeout)
        if not finished:
            with self._cv:
                self._drain_cutoff = True
                self._cv.notify_all()
            self._drained.wait(10.0)
        self.stop()
        return finished

    @property
    def draining(self) -> bool:
        return self._draining

    # ------------------------------------------------------------------ #
    # rolling-restart handoff (DESIGN_router.md)
    # ------------------------------------------------------------------ #
    def handoff_export(self, timeout: Optional[float] = 30.0
                       ) -> List[HandoffRecord]:
        """Drain this replica *into records* instead of into the floor:
        stop admitting, let the loop thread reach its next block boundary,
        export every open request (live slots as exact cache snapshots,
        everything else as re-prefill queue records — see
        ``InferenceEngine.export_handoff``), and terminate the loop.  The
        returned records carry the live request objects AND their client
        handles; feeding them to a successor's :meth:`handoff_import`
        resumes every stream bit-identically, with consumers never seeing
        a finish event for the hop.  After this call the client is
        stopped (``submit`` raises; a router fails over)."""
        with self._cv:
            if self._stop:
                return []
            self._draining = True
            if self._admission is not None:
                self._admission.start_drain()
            self._handoff_requested = True
            self._cv.notify_all()
        if not self._handoff_done.wait(timeout):
            raise TimeoutError(f"handoff export not finished in {timeout}s")
        records, self._handoff_records = self._handoff_records, []
        return records

    def _handoff_export_locked(self) -> None:
        """Loop-thread half of :meth:`handoff_export` (holds ``_cv``)."""
        records: List[HandoffRecord] = []
        # admission-queue waiters first: overdue ones expire with their
        # usual typed timeout event; the rest become re-prefill records
        if self._admission is not None:
            ready, expired = self._admission.poll(1 << 30)
            for req in expired:
                for ev in self._finish_unstarted(
                        req, FinishReason.TIMEOUT, RequestStatus.FAILED,
                        error="queue-wait timeout at handoff"):
                    handle = self._handles.pop(ev.request_id, None)
                    if handle is not None:
                        handle._on_event(ev)
            for req in ready:
                records.append(HandoffRecord(
                    record={"req": req, "cache": None, "ctx_valid": None,
                            "streamer": None, "stopchk": None}))
        for rec in self.engine.export_handoff():
            records.append(HandoffRecord(record=rec))
        for hr in records:
            hr.handle = self._handles.pop(hr.request.request_id, None)
        self._handoff_records = records

    def handoff_import(self, records: List[HandoffRecord]) -> int:
        """Adopt a draining replica's exported requests: the engine seeds
        its resume tables (cache snapshots restore through the preemption
        -resume path, bit-identically), and each migrated handle re-binds
        to this client so its consumer keeps iterating the same stream.
        Admission control is bypassed — these requests were already
        admitted once at the source.  Returns the number adopted."""
        adopted = 0
        with self._cv:
            if self._stop:
                raise RuntimeError("EngineClient is stopped")
            for hr in records:
                req = hr.request
                if req.is_finished:
                    continue
                self.engine.import_handoff(hr.record)
                if hr.handle is not None:
                    hr.handle._client = self
                    self._handles[req.request_id] = hr.handle
                adopted += 1
            self._cv.notify_all()
        return adopted

    def _shutdown_locked(self) -> None:
        """Terminate every in-flight consumer with an ABORT finish event
        (the loop stops; their requests will never finish)."""
        for rid, handle in list(self._handles.items()):
            for r in handle._requests:
                if r.request_id == rid and not r.is_finished:
                    r.finish_reason = FinishReason.ABORT
                    r.status = RequestStatus.ABORTED
            handle._on_event(
                StreamEvent(rid, None, "", finished=True, finish_reason=FinishReason.ABORT)
            )
        self._handles.clear()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)

    close = stop

    def __enter__(self) -> "EngineClient":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
