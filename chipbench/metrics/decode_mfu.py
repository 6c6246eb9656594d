"""whole decode step (core/engine.py decode_block): model FLOP/s
utilisation of the decode programs.

Operations the decoded tokens need (the family's ``decode_token_flops``:
every layer's matrices, attention over each token's live context, the output
head), over the device time of the decode-block runs that decoded them, over
the chip's bf16 peak.  Moves ``itl_p95_ms``."""

from chipbench.probe import DECODE

PROGRAMS = ("jit_decode_block",)


def read(run):
    runs = run.runs(PROGRAMS, DECODE)
    ops = 0
    for i, _, _ in runs:
        k, live = run.probe.decode[i]
        for step in range(k):
            for pos, left in live:
                if step < left:
                    ops += run.family.decode_token_flops(run.dims,
                                                         pos + 1 + step)
    if not ops:
        return None
    device_s = sum(e - s for _, s, e in runs) / 1e9
    return 100.0 * ops / device_s / run.peaks["bf16_flops"]
