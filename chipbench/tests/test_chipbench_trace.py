"""The reduction from a profiler trace to per-layer numbers: on hand-made
events, and on a short trace recorded on a TPU v5e while the chat cell
served (``data/``)."""
import gzip
from pathlib import Path

import pytest

from chipbench import trace as T
from chipbench.probe import DECODE, PREFILL

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_keeps_gaps():
    got = T._union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert got == [(0, 3), (5, 8), (10, 12)]


def _reduced():
    r = T.Reduced(chips=1)
    # dispatch spans decode#5, prefill#2, decode#6; the first decode run
    # was dispatched before the trace began, and decode#6's run is cut
    # short by the trace's end: both drop out
    r.spans = [("decode#5", 100, 101), ("prefill#2", 102, 103),
               ("engine.step", 99, 130), ("decode#6", 140, 141)]
    r.modules = [("jit_decode_block", 90, 99),
                 ("jit_decode_block", 104, 110),
                 ("jit_prefill", 110, 125),
                 ("jit_decode_block", 141, 150)]
    r.op_events = [("fusion.1", 104, 105), ("paged_attention.3", 105, 108),
                   ("paged_attention.3", 111, 112)]
    r.gaps = [(125, 135)]
    return r


def test_runs_pair_with_their_dispatch_spans():
    r = _reduced()
    assert T.runs(r, ("jit_decode_block",), DECODE) == [(5, 104, 110)]
    assert T.runs(r, ("jit_prefill",), PREFILL) == [(2, 110, 125)]
    assert T.runs(r, ("jit_other",), DECODE) == []
    assert T.ops_within(r, "paged_attention", 104, 110) == 3
    assert T.attribute(r, r.gaps[0]) == "engine.step"
    with pytest.raises(KeyError):
        T.Reduced(programs={"jit_prefill": 1.0}).require_programs(
            ("jit_decode_block",))


def test_recorded_chip_trace():
    """4 s of ``qwen2-0.5b.chat_sessions``, KV pages of 16 tokens, on
    one TPU v5e (chip run of the benchmark's first version): one decode
    step took 245 ms, most of it in the paged-attention kernel."""
    raw = gzip.decompress((DATA / "chat_sessions_v5e.xplane.pb.gz")
                          .read_bytes())
    r = T.reduce(raw)
    assert r.chips == 1
    assert r.busy_ns == 3_215_129_773
    assert r.window_ns == 3_576_272_358
    assert len(r.modules) == 58 and len(r.spans) == 34
    decode = T.runs(r, ("jit_decode_block",), DECODE)
    prefill = T.runs(r, ("jit_prefill",), PREFILL)
    assert [i for i, _, _ in decode] == [46, 47, 48, 49, 50]
    assert [i for i, _, _ in prefill] == [49, 50, 51, 52]
    spans = {name: s for name, s, _ in r.spans}
    for i, s, e in decode:
        assert spans[f"{DECODE}#{i}"] < s < e
        kernel = T.ops_within(r, "paged_attention", s, e)
        assert 0.7 * (e - s) < kernel < e - s
    bd = T.breakdown(r)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0].startswith("%paged_attention")
    assert {name for name, _ in bd["idle_gaps"]} <= {
        "engine.step", "scheduler.admit", "prefill.commit_first_tokens",
        "admission.release", "unattributed"}
