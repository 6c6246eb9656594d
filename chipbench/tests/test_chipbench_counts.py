"""The Qwen2 family's operation and byte counts against hand counts, the
roofline, and the peaks table."""
import json
from pathlib import Path

import pytest

from chipbench import cell, flops, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


qwen2 = cell.family(conf("qwen2-7b"))


def test_qwen2_7b_per_layer_params_and_kv_bytes():
    c = conf("qwen2-7b")
    # q 3584x3584 + k, v 3584x512 each + o 3584x3584 + gate, up, down
    # 3584x18944 each = 233,046,016; biases 3584 + 2x512; norms 2x3584
    assert qwen2.layer_matmul_params(c) == 233_046_016
    assert qwen2.layer_params(c) == 233_057_792
    assert round(qwen2.layer_params(c) / 1e6, 2) == 233.06
    assert qwen2.embedding_params(c) == 152_064 * 3584 == 544_997_376
    # 14 layers x 2 (k, v) x 4 heads x 128 x 2 bytes
    assert qwen2.kv_bytes_per_token(c) == 28_672
    total = qwen2.total_params(c)
    assert total == 14 * 233_057_792 + 2 * 544_997_376 + 3584
    assert round(2 * total / 1e9, 2) == 8.71          # GB in bf16


def test_qwen2_0_5b_params_and_kv_bytes():
    c = conf("qwen2-0.5b")
    assert qwen2.layer_params(c) == 14_912_384
    assert qwen2.embedding_params(c) == 136_134_656
    assert qwen2.kv_bytes_per_token(c) == 12_288
    assert round(qwen2.total_params(c) * 2 / 1e9, 2) == 0.99
    # the KV arena the configuration states: 64 x 4096 x 12,288 B
    assert 64 * 4096 * qwen2.kv_bytes_per_token(c) == 3_221_225_472


@pytest.mark.parametrize("name", ["qwen2-0.5b", "qwen2-7b"])
def test_decode_token_flops_by_hand(name):
    c = conf(name)
    d, h, hd = c["hidden_size"], c["num_attention_heads"], \
        c["hidden_size"] // c["num_attention_heads"]
    n = c["num_hidden_layers"]
    ctx = 1000
    per_layer = 2 * qwen2.layer_matmul_params(c) + 4 * ctx * h * hd
    head = 2 * c["vocab_size"] * d
    assert qwen2.decode_token_flops(c, ctx) == n * per_layer + head


def test_prefill_flops_sums_causal_contexts():
    c = conf("qwen2-0.5b")
    h, hd = 14, 64
    # 3 tokens after 5 cached: contexts 6, 7, 8
    want = (2 * 3 * 24 * qwen2.layer_matmul_params(c)
            + 24 * 4 * (6 + 7 + 8) * h * hd + 2 * 151_936 * 896)
    assert qwen2.prefill_flops(c, 5, 3) == want


def test_paged_attention_cost_counts_live_cells_only():
    c = conf("qwen2-7b")
    ops, nbytes = qwen2.paged_attention_cost(c, [100, 300])
    # the whole stack: every one of the 14 layers attends the whole context
    assert ops == 14 * 4 * 400 * 28 * 128
    # live K and V cells, bf16, plus each slot's query and output
    assert nbytes == 14 * (2 * 400 * 4 * 128 * 2 + 2 * 2 * 28 * 128 * 2)
    p = peaks.peaks_for("TPU v5 lite")
    # attention at decode is bound by bytes, not operations
    assert flops.least_time_s(ops, nbytes, p) == nbytes / 819e9


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
