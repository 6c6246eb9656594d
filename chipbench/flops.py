"""Operations and bytes the algorithm needs, from shapes and live lengths.

These count what a Qwen2 decoder layer stack needs, not what an
implementation happens to do: padded prefill rows, padded pages, logits at
positions nobody reads and frozen decode slots are not counted, so a later
kernel that skips such work does not make the count stale.  A matrix
multiply of ``m x k`` by ``k x n`` is ``2 m k n`` operations.

``dims`` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations

from typing import Iterable


def _shape(dims: dict):
    d = dims["hidden_size"]
    h = dims["num_attention_heads"]
    hkv = dims["num_key_value_heads"]
    hd = dims.get("head_dim") or d // h
    return d, h, hkv, hd, dims["intermediate_size"]


def layer_matmul_params(dims: dict) -> int:
    """Weight-matrix entries of one layer (q, k, v, o, gate, up, down)."""
    d, h, hkv, hd, dff = _shape(dims)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * dff


def layer_params(dims: dict) -> int:
    """All parameters of one layer: matrices, q/k/v biases, two norms."""
    d, h, hkv, hd, _ = _shape(dims)
    return layer_matmul_params(dims) + h * hd + 2 * hkv * hd + 2 * d


def embedding_params(dims: dict) -> int:
    return dims["vocab_size"] * dims["hidden_size"]


def total_params(dims: dict) -> int:
    """Embedding, layers, final norm, and the output head unless tied."""
    tables = 1 if dims["tie_word_embeddings"] else 2
    return (tables * embedding_params(dims)
            + dims["num_hidden_layers"] * layer_params(dims)
            + dims["hidden_size"])


def kv_bytes_per_token(dims: dict, bytes_per_el: int = 2) -> int:
    _, _, hkv, hd, _ = _shape(dims)
    return dims["num_hidden_layers"] * 2 * hkv * hd * bytes_per_el


def attention_flops(dims: dict, context: int) -> int:
    """One query token over ``context`` keys, one layer: QK^T and PV."""
    _, h, _, hd, _ = _shape(dims)
    return 4 * context * h * hd


def decode_token_flops(dims: dict, context: int) -> int:
    """One decoded token attending ``context`` positions (itself included):
    every layer's matrices, its attention, and the output head."""
    n_layers = dims["num_hidden_layers"]
    return (2 * n_layers * layer_matmul_params(dims)
            + n_layers * attention_flops(dims, context)
            + 2 * embedding_params(dims))


def prefill_flops(dims: dict, offset: int, tokens: int) -> int:
    """``tokens`` prompt tokens at positions ``offset..offset+tokens-1``,
    causal over everything before them, and the head at the last one."""
    n_layers = dims["num_hidden_layers"]
    # sum over queries of their context lengths (p + 1 for position p)
    ctx_sum = tokens * offset + tokens * (tokens + 1) // 2
    _, h, _, hd, _ = _shape(dims)
    return (2 * tokens * n_layers * layer_matmul_params(dims)
            + n_layers * 4 * ctx_sum * h * hd
            + 2 * embedding_params(dims))


def paged_attention_cost(dims: dict, contexts: Iterable[int],
                         bytes_per_el: int = 2):
    """(operations, bytes) of one ``paged_attention`` call: one query token
    per live slot over that slot's live context, one layer.  Bytes are the
    live K and V cells read plus each slot's query and output."""
    _, h, hkv, hd, _ = _shape(dims)
    contexts = list(contexts)
    ctx = sum(contexts)
    ops = 4 * ctx * h * hd
    nbytes = (2 * ctx * hkv * hd * bytes_per_el
              + 2 * len(contexts) * h * hd * bytes_per_el)
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of operations over peak and bytes over
    bandwidth (bf16 operands)."""
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
