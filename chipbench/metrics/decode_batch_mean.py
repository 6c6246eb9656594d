"""scheduler (core/scheduler.py): mean live slots per decode step.

Delta of ``tokens_generated`` over delta of ``device_steps`` across the
measured window.  Moves ``output_tok_s``."""


def read(run):
    steps = run.counter_delta("device_steps")
    tokens = run.counter_delta("tokens_generated")
    return tokens / steps if steps else None
