"""The one traffic generator: reads a mix's parameters from its data file.

A mix is ``traffic/<name>.json``.  ``loop`` says which of two shapes it is:

* ``open_sessions``: chat sessions start as a Poisson process at
  ``session_rate_per_s``; each picks one of ``system_prompts`` shared
  system prompts, then sends turns whose prompt is the whole history (system
  prompt, earlier user messages and the served replies) plus a new user
  message, with a think time after each reply.
* ``closed``: ``clients`` callers (a number, or ``"max_batch"``) each send
  their next request as soon as the previous one finishes.

Every seed gets the same set of sizes, think times and arrival gaps, drawn
from the file's ``pool_seed``; ``--seed`` only reorders them and draws the
token ids.  So two seeds differ in order, not in the amount of work.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

TOKENS = 2                      # seed-stream purpose tag (weights use 1)


def draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` values from a length/time distribution given as data."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    elif dist == "geometric":
        x = rng.geometric(1.0 / spec["mean"], n).astype(float)
    elif dist == "exponential":
        x = rng.exponential(spec["mean"], n)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec or "max" in spec:
        x = np.clip(x, spec.get("min", -np.inf), spec.get("max", np.inf))
    if spec.get("integer", dist != "exponential"):
        x = np.rint(x).astype(np.int64)
    return x


@dataclass
class Turn:
    user_tokens: int
    output_tokens: int
    think_s: float


@dataclass
class Session:
    index: int
    start_s: float              # offset from the start of the load
    system: int
    greedy: bool
    turns: List[Turn]


@dataclass
class Shape:
    prompt_tokens: int
    output_tokens: int


class Traffic:
    """A mix's schedule for one run: ``seed`` orders it and draws ids."""

    def __init__(self, params: dict, seed: int, vocab: int, window_s: float,
                 max_batch: int):
        self.p = params
        self.seed = int(seed)
        self.vocab = vocab
        self.loop = params["loop"]
        self.ramp_s = float(params["ramp_s"])
        self.window_s = float(window_s)
        self.sampling = dict(params.get("sampling", {}))
        self._order = np.random.default_rng([self.seed, TOKENS, 0])
        pool = np.random.default_rng(int(params["pool_seed"]))
        if self.loop == "open_sessions":
            self.sessions = self._sessions(pool)
        elif self.loop == "closed":
            c = params["clients"]
            self.clients = max_batch if c == "max_batch" else int(c)
            self.shapes = self._shapes(pool)
        else:
            raise ValueError(f"unknown loop {self.loop!r}")

    # -- schedules ----------------------------------------------------------
    def _sessions(self, pool: np.random.Generator) -> List[Session]:
        p = self.p
        rate = float(p["session_rate_per_s"])
        n = max(1, math.ceil(rate * (self.ramp_s + self.window_s)))
        gaps = draw(pool, {"dist": "exponential", "mean": 1.0 / rate}, n)
        turns = draw(pool, p["turns"], n)
        greedy = np.arange(n) < round(n * float(p["greedy_share"]))
        shapes = []
        for i in range(n):
            k = int(turns[i])
            shapes.append((greedy[i], [
                Turn(int(u), int(o), float(t)) for u, o, t in zip(
                    draw(pool, p["user_tokens"], k),
                    draw(pool, p["output_tokens"], k),
                    draw(pool, p["think_s"], k))]))
        # the run seed orders gaps and sessions, and picks system prompts
        gaps = self._order.permutation(gaps)
        order = self._order.permutation(n)
        systems = self._order.integers(0, int(p["system_prompts"]), n)
        starts = np.cumsum(gaps) - gaps[0]
        return [Session(i, float(starts[i]), int(systems[i]),
                        bool(shapes[order[i]][0]), shapes[order[i]][1])
                for i in range(n)]

    def _shapes(self, pool: np.random.Generator) -> List[Shape]:
        p = self.p
        n = int(p["pool_size"])
        prompts = draw(pool, p["prompt_tokens"], n)
        outputs = draw(pool, p["output_tokens"], n)
        order = self._order.permutation(n)
        return [Shape(int(prompts[i]), int(outputs[i])) for i in order]

    def shape(self, j: int) -> Shape:
        """The closed loop's ``j``-th request (the pool repeats)."""
        return self.shapes[j % len(self.shapes)]

    # -- token ids ----------------------------------------------------------
    def ids(self, n: int, *key: int) -> List[int]:
        """``n`` token ids, fixed by the run seed and ``key``."""
        rng = np.random.default_rng([self.seed, TOKENS, *key])
        return rng.integers(0, self.vocab, n).tolist()

    def system_prompt(self, s: int) -> List[int]:
        return self.ids(int(self.p["system_prompt_tokens"]), 1, s)

    def user_message(self, session: int, turn: int, n: int) -> List[int]:
        return self.ids(n, 2, session, turn)

    def prompt(self, j: int, n: int) -> List[int]:
        return self.ids(n, 3, j)

    def sampling_for(self, greedy: bool) -> Dict[str, float]:
        return {"temperature": 0.0} if greedy else dict(self.sampling)

    @property
    def context_limit(self) -> Optional[int]:
        return self.p.get("context_limit")
