"""Host spans and device scopes for the profiler's trace.

``span`` marks host work in the profiler's host plane: a
``TraceAnnotation`` named ``repro.<name>`` whose keyword arguments land as
event stats (scalars only).  With no profiler running a span costs about a
microsecond, so spans wrap phases of a decode block, never single tokens.

``scope`` names device work: ``jax.named_scope`` puts the name into the op
metadata of everything traced inside it, which the device plane's op
events carry as their ``tf_op`` path.  It changes no computation.

The module imports nothing of ``repro``, so kernels, models and the engine
all use it without depending on one another.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


scope = jax.named_scope
