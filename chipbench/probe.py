"""Traced runs only: host spans around the engine's layer calls, and the
host-side counts the per-layer metrics divide by.

The spans (``chipbench.<layer>``) go into the profiler's own trace through
``jax.profiler.TraceAnnotation``, so idle gaps on the device can be
labelled with what the host was doing.  Each decode-block dispatch and each
prefill wave group gets a numbered span (``chipbench.decode#<i>``,
``chipbench.prefill#<i>``); ``decode[i]`` records that block's size and
each live slot's context, ``prefill[i]`` the prompt tokens each row of that
group advanced.  ``trace.runs`` pairs the numbered spans with the device
runs of the programs they dispatched.  Nothing here runs when ``--trace 0``.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax

DECODE, PREFILL = "decode", "prefill"


class Probe:
    def __init__(self, client):
        # decode[i] = (K, [(position of the token fed in, steps it has left)
        # for each live slot]); prefill[i] = [(offset, tokens) per real row]
        self.decode: List[Tuple[int, List[Tuple[int, int]]]] = []
        self.prefill: List[List[Tuple[int, int]]] = []
        engine = self.engine = client.engine
        self._span(engine, "step", "engine.step")
        self._span(engine, "_plan_admissions", "scheduler.admit")
        self._span(engine, "_commit_jobs", "prefill.commit_first_tokens")
        self._span(client, "_admission_round", "admission.release")
        decode = engine._decode_block_fn
        wave = engine._run_wave_group

        @functools.wraps(decode)
        def decode_block(*args, num_steps, **kw):
            i = len(self.decode)
            self.decode.append((num_steps, self._live()))
            with jax.profiler.TraceAnnotation(f"chipbench.{DECODE}#{i}"):
                return decode(*args, num_steps=num_steps, **kw)

        @functools.wraps(wave)
        def run_wave_group(bucket, cross_cached, rows):
            i = len(self.prefill)
            self.prefill.append([(job.consumed, take) for job, take in rows])
            with jax.profiler.TraceAnnotation(f"chipbench.{PREFILL}#{i}"):
                return wave(bucket, cross_cached, rows)

        engine._decode_block_fn = decode_block
        engine._run_wave_group = run_wave_group

    @staticmethod
    def _span(obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def spanned(*args, **kw):
            with jax.profiler.TraceAnnotation("chipbench." + name):
                return fn(*args, **kw)

        setattr(obj, attr, spanned)

    def _live(self) -> List[Tuple[int, int]]:
        eng = self.engine
        out = []
        for slot, req in list(eng.scheduler.active.items()):
            if slot in eng._live_slots and not req.is_finished:
                out.append((len(req.prompt_tokens) + req.num_generated - 1,
                            req.sampling.max_tokens - req.num_generated))
        return out

    def release(self) -> None:
        """Drop the engine (the records stay) so its device state can go."""
        self.engine = None
