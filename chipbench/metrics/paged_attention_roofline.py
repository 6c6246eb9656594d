"""kernels (kernels/paged_attention.py): the paged-attention kernel's share
of its roofline.

For each decode-block run in the trace, each of its steps and each layer,
the least time of one kernel call (the larger of its operations over peak
and its bytes over HBM bandwidth, for the live context each slot attends),
summed, over the device time of the kernel's operations inside those runs.
Moves ``itl_p95_ms``."""

from chipbench import flops
from chipbench import trace as T
from chipbench.probe import DECODE

PROGRAMS = ("jit_decode_block",)
KERNEL = "paged_attention"


def read(run):
    run.trace.require_op(KERNEL)
    least = kernel_ns = 0.0
    for i, s, e in run.runs(PROGRAMS, DECODE):
        k, live = run.probe.decode[i]
        for step in range(k):
            ctx = [pos + 1 + step for pos, left in live if step < left]
            if ctx:
                least += flops.least_time_s(
                    *flops.paged_attention_cost(run.dims, ctx), run.peaks)
        kernel_ns += T.ops_within(run.trace, KERNEL, s, e)
    if not least or not kernel_ns:
        return None
    least *= run.dims["num_hidden_layers"]
    return 100.0 * least / (kernel_ns / 1e9)
