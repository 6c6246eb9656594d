"""engine host loop (core/engine.py step): mean host time from one decode
block's token sync returning to the next block's dispatch, the time the
decode stream leaves the chip to the host.

Delta of ``SchedulerStats.host_gap_s`` over delta of ``host_gaps`` across
the measured window.  Moves ``output_tok_s``.  None where the program keeps
no such counter."""


def read(run):
    if "host_gap_s" not in run.counters.get("close", {}):
        return None
    gaps = run.counter_delta("host_gaps")
    return 1e3 * run.counter_delta("host_gap_s") / gaps if gaps else None
