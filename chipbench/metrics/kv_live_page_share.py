"""KV manager (core/paged_kv.py, kernels/paged_attention.py): share of the
pages ``paged_attention``'s grid visits that hold a live slot's context.

Delta of ``kv_live_pages`` over delta of ``kv_grid_pages`` across the
measured window, in percent; both are summed per decode step.  Moves
``itl_p95_ms``.  None where the program keeps no such counter."""


def read(run):
    if "kv_grid_pages" not in run.counters.get("close", {}):
        return None
    grid = run.counter_delta("kv_grid_pages")
    return 100.0 * run.counter_delta("kv_live_pages") / grid if grid else None
