"""scheduler (core/scheduler.py): host syncs per generated token.

Delta of ``SchedulerStats.steps`` over delta of ``tokens_generated``
across the measured window (one host sync per decode block; block decode
of K tokens reads 1/K).  Moves ``itl_p95_ms``."""


def read(run):
    steps = run.counter_delta("steps")
    tokens = run.counter_delta("tokens_generated")
    return steps / tokens if tokens else None
