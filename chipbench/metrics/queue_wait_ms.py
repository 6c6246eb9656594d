"""scheduler (core/scheduler.py): mean time a request waits for a slot.

Delta of ``SchedulerStats.queue_wait_s`` (slot bind less the time the
request joined the queue, summed over admissions) over delta of
``admitted`` across the measured window.  Moves ``output_tok_s``.  None
where the program keeps no such counter."""


def read(run):
    if "queue_wait_s" not in run.counters.get("close", {}):
        return None
    admitted = run.counter_delta("admitted")
    return 1e3 * run.counter_delta("queue_wait_s") / admitted if admitted else None
