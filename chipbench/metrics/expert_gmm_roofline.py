"""kernels (kernels/expert_gmm.py): the grouped expert kernel's share of its
roofline.

Each ``expert_gmm`` call inside the traced decode-block runs is one expert
layer of one decode step.  Its least time is the larger of its operations
over peak and its bytes over HBM bandwidth, by the family's
``expert_gmm_cost`` for the mean call over the window: ``moe_rows`` rows and
``moe_experts`` held experts with any row (an expert with none is never
visited, so its weights are not read), each counter over the decode steps
times the expert layers.  The share is that least time times the calls,
over the device time of the calls and of the operations that stage their
weights: inside the layer scan the compiler copies each layer's slice of
the stacked expert matrices (an output shaped ``[held, d, f]`` or
``[held, f, d]``) before the kernel runs, one of them into on-chip memory,
so the kernel's own time leaves out part of its weight read.  The calls are
counted in the trace itself, so no dispatch is paired with its run.  Moves
``itl_p95_ms``.  None where the program keeps no such counters or the trace
holds no such kernel."""

from chipbench import flops
from chipbench import program_trace as PT

PROGRAMS = ("jit_decode_block",)
KERNEL = "expert_gmm"


def calls(trace, staged):
    """(calls, nanoseconds) inside the traced decode-block runs: the
    kernel's operations, counted by their own name and not by the name of
    an operand they read, and the time of those and of the operations whose
    output holds one of the ``staged`` weight shapes."""
    n = ns = 0
    for name, s, e in trace.modules:
        if name not in PROGRAMS:
            continue
        for op, s0, e0 in PT._leaves_in(trace, s, e):
            if KERNEL in op.split(" ", 1)[0]:
                n += 1
                ns += e0 - s0
            elif any(PT._holds(op.partition(" = ")[2], dims)
                     for dims in staged):
                ns += e0 - s0
    return n, ns


def read(run):
    if "moe_experts" not in run.counters.get("close", {}):
        return None
    dims = run.dims
    layers = dims["mlp_layer_types"][:dims["num_hidden_layers"]].count(
        "sparse")
    per_call = run.counter_delta("device_steps") * layers
    e, d, f = (dims["num_experts"], dims["hidden_size"],
               dims["moe_intermediate_size"])
    n, ns = calls(run.trace, ((e, d, f), (e, f, d)))
    if not (per_call and n and ns):
        return None
    cost = run.family.expert_gmm_cost(
        dims, run.counter_delta("moe_rows") / per_call,
        run.counter_delta("moe_experts") / per_call)
    return 100.0 * n * flops.least_time_s(*cost, run.peaks) / (ns / 1e9)
