"""model programs (core/engine.py prefill wave): share of the prefill
waves' token positions that are padding, from the power-of-two buckets and
row counts.

Delta of ``prefill_pad_tokens`` over the deltas of ``prefill_tokens`` and
``prefill_pad_tokens`` across the measured window, in percent.  Moves
``output_tok_s``.  None where the program keeps no such counter."""


def read(run):
    if "prefill_pad_tokens" not in run.counters.get("close", {}):
        return None
    pad = run.counter_delta("prefill_pad_tokens")
    total = run.counter_delta("prefill_tokens") + pad
    return 100.0 * pad / total if total else None
