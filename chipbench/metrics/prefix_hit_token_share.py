"""KV manager (core/prefix_cache.py, core/paged_kv.py): share of prompt
tokens served from the prefix cache.

Sum of ``Request.cached_prefix_len`` over the prompt tokens of every
request submitted in the measured window, in percent.  Moves
``ttft_p90_ms``."""


def read(run):
    recs = [r for r in run.recs
            if r.admitted and run.opens <= r.submit < run.closes]
    prompt = sum(len(r.prompt) for r in recs)
    return 100.0 * sum(r.cached for r in recs) / prompt if prompt else None
