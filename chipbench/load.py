"""Drive one run's traffic through ``EngineClient.submit`` from one asyncio
loop, and record what each request saw on the client's side.

Times are ``time.perf_counter()`` seconds.  A request's ``due`` is when the
schedule said it should be sent (open loop: the session's start or the end
of its think time; closed loop: the end of the caller's previous request),
so a late generator or a stalled server shows in its time to first token.
The load starts at ``t0``; the measured window is
``[t0 + ramp_s, t0 + ramp_s + window_s)``.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

from repro.core.admission import AdmissionError
from repro.core.request import GenerationRequest, SamplingParams
from repro.serving.client import FinishEvent, TokenEvent

from chipbench.traffic import Traffic

# after the window closes, requests due inside it get this long to show
# their first token before everything still open is aborted
FIRST_TOKEN_WAIT_S = 60.0


@dataclass
class Rec:
    key: tuple
    prompt: List[int]
    max_tokens: int
    greedy: bool
    due: float
    submit: float = 0.0
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finish: Optional[str] = None
    end: Optional[float] = None
    cached: int = 0             # prompt tokens served from the prefix cache
    admitted: bool = False      # submit accepted it
    handle: object = None

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None

    @property
    def ok(self) -> bool:
        return self.finish in ("length", "stop")


class Load:
    """One run's load: ``run()`` returns every request record."""

    def __init__(self, client, traffic: Traffic, *,
                 on_window: Optional[Callable[[str], None]] = None,
                 marks: Sequence[Tuple[float, str]] = ()):
        self.client = client
        self.t = traffic
        self.recs: List[Rec] = []
        # on_window(name) runs at "open", at each (seconds after open, name)
        # of ``marks``, and at "close"
        self.on_window = on_window or (lambda _: None)
        self.marks = sorted(marks)
        self.limit = traffic.context_limit or client.engine.pool.cache_len
        self._next_shape = 0
        self.lateness: List[float] = []

    # -- one request --------------------------------------------------------
    async def _request(self, key, prompt, max_tokens, greedy, due) -> Rec:
        rec = Rec(key, prompt, max_tokens, greedy, due)
        self.recs.append(rec)
        await _sleep_until(due)
        rec.submit = time.perf_counter()
        self.lateness.append(rec.submit - due)
        sp = SamplingParams(max_tokens=max_tokens,
                            **self.t.sampling_for(greedy))
        try:
            handle = self.client.submit(GenerationRequest(prompt=prompt,
                                                          sampling=sp))
        except AdmissionError as e:
            rec.finish, rec.end = f"refused:{e.code}", time.perf_counter()
            return rec
        rec.handle, rec.admitted = handle, True
        async for ev in handle.stream():
            now = time.perf_counter()
            if isinstance(ev, TokenEvent):
                rec.times.append(now)
                rec.tokens.append(ev.token)
            elif isinstance(ev, FinishEvent):
                rec.finish, rec.end = ev.finish_reason, now
        rec.cached = handle._requests[0].cached_prefix_len
        return rec

    # -- the two loops --------------------------------------------------------
    async def _session(self, s, t0: float, close: float) -> None:
        t = self.t
        history = t.system_prompt(s.system)
        due = t0 + s.start_s
        for j, turn in enumerate(s.turns):
            if due >= close:
                return
            prompt = history + t.user_message(s.index, j, turn.user_tokens)
            if len(prompt) + turn.output_tokens > self.limit:
                return
            rec = await self._request((s.index, j), prompt,
                                      turn.output_tokens, s.greedy, due)
            if not rec.ok:
                return
            history = prompt + rec.tokens
            due = rec.end + turn.think_s

    async def _caller(self, c: int, t0: float, close: float) -> None:
        t = self.t
        greedy = t.sampling.get("temperature", 0.0) == 0
        due = t0 + t.ramp_s * c / t.clients
        while due < close:
            j = self._next_shape
            self._next_shape += 1
            shape = t.shape(j)
            rec = await self._request((j,), t.prompt(j, shape.prompt_tokens),
                                      shape.output_tokens, greedy, due)
            if not rec.ok:
                return
            due = rec.end

    async def _marks(self, opens: float, closes: float) -> None:
        # in a thread: stopping the profiler takes seconds, and the loop
        # keeps sending requests meanwhile
        await _sleep_until(opens)
        await asyncio.to_thread(self.on_window, "open")
        for offset, name in self.marks:
            await _sleep_until(opens + offset)
            await asyncio.to_thread(self.on_window, name)
        await _sleep_until(closes)
        await asyncio.to_thread(self.on_window, "close")

    async def _run(self, t0: float) -> None:
        t = self.t
        opens = t0 + t.ramp_s
        closes = opens + t.window_s
        if t.loop == "open_sessions":
            work = [self._session(s, t0, closes) for s in t.sessions]
        else:
            work = [self._caller(c, t0, closes) for c in range(t.clients)]
        tasks = [asyncio.ensure_future(w) for w in work]
        marks = asyncio.ensure_future(self._marks(opens, closes))
        await marks
        # open loop: every request due in the window waits for its first
        # token; then whatever is still running is aborted
        deadline = time.perf_counter() + FIRST_TOKEN_WAIT_S
        while time.perf_counter() < deadline and any(
                r.due < closes and not r.times and r.finish is None
                for r in self.recs):
            await asyncio.sleep(0.05)
        for r in self.recs:
            if r.handle is not None and r.finish is None:
                r.handle.abort(wait=False)
        await asyncio.wait_for(asyncio.gather(*tasks), FIRST_TOKEN_WAIT_S)

    def run(self, t0: float) -> List[Rec]:
        asyncio.run(self._run(t0))
        return self.recs


async def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
