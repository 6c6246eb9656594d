"""scheduler (core/scheduler.py): mean time from a request's slot bind to
its first token, the chunked prefill and the commit that samples it.

Delta of ``SchedulerStats.prefill_wait_s`` over delta of ``first_tokens``
across the measured window.  Moves ``output_tok_s``.  None where the
program keeps no such counter."""


def read(run):
    if "prefill_wait_s" not in run.counters.get("close", {}):
        return None
    firsts = run.counter_delta("first_tokens")
    return 1e3 * run.counter_delta("prefill_wait_s") / firsts if firsts else None
