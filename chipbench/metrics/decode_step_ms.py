"""model programs (core/engine.py decode_block): device time per decode
step.

Device time of the decode-block runs in the trace over the decode steps
those runs made (the sum of their block sizes).  Moves ``itl_p95_ms``."""

from chipbench.probe import DECODE

PROGRAMS = ("jit_decode_block",)


def read(run):
    runs = run.runs(PROGRAMS, DECODE)
    steps = sum(run.probe.decode[i][0] for i, _, _ in runs)
    if not steps:
        return None
    return 1e3 * sum(e - s for _, s, e in runs) / 1e9 / steps
