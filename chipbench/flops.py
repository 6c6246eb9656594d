"""The roofline, and the rule every family's counts keep.

A family's counts (``families/<name>.py``: ``total_params``,
``kv_bytes_per_token``, ``decode_token_flops``, ``prefill_flops``,
``paged_attention_cost``) count what the algorithm needs, not what an
implementation happens to do: padded prefill rows, padded pages, logits at
positions nobody reads and frozen decode slots are not counted, so a later
kernel that skips such work does not make the count stale.  A matrix
multiply of ``m x k`` by ``k x n`` is ``2 m k n`` operations.
"""
from __future__ import annotations


def least_time_s(ops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of operations over peak and bytes over
    bandwidth (bf16 operands)."""
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
