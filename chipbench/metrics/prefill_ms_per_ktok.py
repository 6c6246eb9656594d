"""model programs (core/engine.py prefill wave): device time per thousand
prompt tokens prefilled.

Device time of the prefill-wave runs in the trace over the prompt tokens
those runs advanced (cached prefix and row padding excluded).  Moves
``ttft_p90_ms``."""

from chipbench.probe import PREFILL

PROGRAMS = ("jit_prefill",)


def read(run):
    runs = run.runs(PROGRAMS, PREFILL)
    tokens = sum(take for i, _, _ in runs for _, take in run.probe.prefill[i])
    if not tokens:
        return None
    return 1e3 * sum(e - s for _, s, e in runs) / 1e9 / (tokens / 1e3)
