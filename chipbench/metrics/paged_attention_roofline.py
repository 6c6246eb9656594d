"""kernels (kernels/paged_attention.py): the paged-attention kernel's share
of its roofline.

For each decode-block run in the trace and each of its steps, the least time
of the step's kernel calls (the larger of their operations over peak and
their bytes over HBM bandwidth, for the live context each slot attends in
each layer: the family's whole-stack ``paged_attention_cost``), summed, over
the device time of the kernel's operations inside those runs.  Moves
``itl_p95_ms``."""

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
                    *run.family.paged_attention_cost(run.dims, ctx),
                    run.peaks)
        kernel_ns += T.ops_within(run.trace, KERNEL, s, e)
    if not least or not kernel_ns:
        return None
    return 100.0 * least / (kernel_ns / 1e9)
