"""The per-layer readers of the program's own counters, on hand-made window
counters, and ``program_trace``: scope paths read from a serialized XSpace
(hand-encoded, and the recorded v5e trace), innermost-scope and
innermost-span attribution, and decode runs paired with the program's
dispatch spans."""
import gzip
from pathlib import Path

import pytest

from chipbench import cell as C
from chipbench import program_trace as P
from chipbench import trace as T
from chipbench.run import RunData

DATA = Path(__file__).resolve().parent / "data"
CELL = "qwen2-7b.decode_heavy"
NEW = ("queue_wait_ms", "prefill_wait_ms", "host_gap_ms", "prefill_pad_share",
       "kv_live_page_share")


def _run(open_, close):
    return RunData({}, {}, [], 0.0, 1.0, {"open": open_, "close": close})


OPEN = {"admitted": 10, "queue_wait_s": 1.0, "prefill_wait_s": 2.0,
        "first_tokens": 10, "host_gap_s": 0.5, "host_gaps": 50,
        "prefill_tokens": 1000, "prefill_pad_tokens": 200,
        "kv_live_pages": 300, "kv_grid_pages": 800}
CLOSE = {"admitted": 14, "queue_wait_s": 1.2, "prefill_wait_s": 3.0,
         "first_tokens": 15, "host_gap_s": 0.8, "host_gaps": 80,
         "prefill_tokens": 4000, "prefill_pad_tokens": 1200,
         "kv_live_pages": 700, "kv_grid_pages": 1600}


@pytest.mark.parametrize("name, want", [
    ("queue_wait_ms", 1e3 * 0.2 / 4), ("prefill_wait_ms", 1e3 * 1.0 / 5),
    ("host_gap_ms", 1e3 * 0.3 / 30),
    ("prefill_pad_share", 100.0 * 1000 / 4000),
    ("kv_live_page_share", 100.0 * 400 / 800)])
def test_counter_readers(name, want):
    assert C.reader(name)(_run(OPEN, CLOSE)) == pytest.approx(want)
    # a window with nothing to divide by gives no number
    assert C.reader(name)(_run(OPEN, OPEN)) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_counters(name):
    old = {"admitted": 3, "steps": 2, "tokens_generated": 9}
    assert C.reader(name)(_run(old, dict(old, admitted=5))) is None


def test_new_metrics_are_declared_for_the_cell():
    entries = {m["name"]: m for m in C.metrics_for(C.benchmark(), CELL,
                                                   "per_layer")}
    for name in NEW:
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["workloads"] == [CELL]


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, ops):
    """An XPlane whose event metadata carry ``tf_op`` as a string or, for
    an op given a tuple, by reference to a stat metadata entry."""
    stat_meta = [(5, _msg((1, 7), (2, _msg((1, 7), (2, "tf_op"))))),
                 (5, _msg((1, 9), (2, _msg((1, 9), (2, "source")))))]
    events = []
    for i, (op, path) in enumerate(ops.items(), start=1):
        if isinstance(path, tuple):
            ref, text = path
            stat_meta.append((5, _msg((1, ref), (2, _msg((1, ref),
                                                        (2, text))))))
            stat = _msg((1, 7), (7, ref))
        else:
            stat = _msg((1, 7), (5, path))
        meta = _msg((1, i), (2, op), (5, _msg((1, 9), (5, "x.py:1"))),
                    (5, stat))
        events.append((4, _msg((1, i), (2, meta))))
    return _msg((1, 1), (2, name), (3, _msg((2, "XLA Ops"))), *events,
                *stat_meta)


def test_op_scopes_from_a_hand_encoded_xspace():
    ops = {"%copy.1 = bf16[4,8] copy(%p)": "jit(f)/kv_arena/while/copy:",
           "%fusion.2 = f32[4] fusion(%q)": (11, "jit(f)/while/mlp/dot:")}
    xspace = _msg((1, _plane("/host:CPU", {"%x = f32[] add()": "host/"})),
                  (1, _plane("/device:TPU:0", ops)))
    # the path by string and by reference; never the other stats (source)
    assert P.op_scopes(xspace) == {op: path if isinstance(path, str)
                                   else path[1] for op, path in ops.items()}


def test_op_scopes_of_the_recorded_chip_trace():
    """The v5e trace carries each op's scope path on its metadata; the run
    predates the program's scopes, so every op reads unscoped."""
    raw = gzip.decompress((DATA / "chat_sessions_v5e.xplane.pb.gz")
                          .read_bytes())
    scopes = P.op_scopes(raw)
    r = T.reduce(raw)
    kernel = [n for n in scopes if n.startswith("%paged_attention")]
    assert kernel and all(scopes[n].startswith("jit(decode_block)/")
                          and "pallas_call" in scopes[n] for n in kernel)
    assert sum(n in scopes for n, _, _ in r.op_events) > 0.9 * len(
        r.op_events)
    assert set(P.scope_ns(r, scopes)) == {P.UNSCOPED}
    arena = P.arena_ns(r, scopes, (8196, 16))      # pages, page size
    assert set(arena) == {P.UNSCOPED}
    assert 0 < arena[P.UNSCOPED] < r.busy_ns
    assert P.host_spans(raw) == []


def test_host_spans_keep_the_program_spans_and_their_stats(tmp_path):
    import jax
    from repro import tracing

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("engine.step"):
            with tracing.span("engine.decode_dispatch", block=3, k=8):
                pass
        with jax.profiler.TraceAnnotation("chipbench.decode#3"):
            pass
    finally:
        jax.profiler.stop_trace()
    spans = P.host_spans(T.find_xplane(str(tmp_path)))
    assert [(n, st) for n, _, _, st in spans] == [
        ("engine.step", {}), ("engine.decode_dispatch", {"block": 3, "k": 8})]
    (_, s0, e0, _), (_, s1, e1, _) = spans
    assert s0 <= s1 <= e1 <= e0


@pytest.mark.parametrize("path, want", [
    ("jit(decode_block)/while/body/closed_call/sampler/cond/argmax:",
     "sampler"),
    ("jit(decode_block)/while/body/closed_call/while/body/closed_call/"
     "kv_arena/scatter:", "kv_arena"),
    ("jit(prefill)/while/body/closed_call/attention_kernel/"
     "jit(paged_attention_pallas)/reshape:", "attention_kernel"),
    ("jit(decode_block)/while:", P.UNSCOPED), ("", P.UNSCOPED)])
def test_innermost_scope(path, want):
    assert P.innermost(path) == want


def test_innermost_span_labels_a_gap():
    spans = [("engine.step", 0, 100), ("engine.emit", 10, 40),
             ("decode#3", 12, 14), ("client.deliver", 100, 130)]
    assert P.attribute(spans, (15, 30)) == "engine.emit"
    assert P.attribute(spans, (12, 13)) == "decode"
    assert P.attribute(spans, (50, 60)) == "engine.step"
    # no span covers the gap: the one that overlaps it most
    assert P.attribute(spans, (90, 125)) == "client.deliver"
    assert P.attribute(spans, (200, 210)) == "unattributed"


def test_scope_time_per_decode_step():
    """Decode runs pair in order with the dispatch spans: a run that ended
    before the first span and a span whose run fell after the trace drop
    out.  Arena time is the ``kv_arena`` scope plus arena-shaped ops in any
    scope or none; weight-shaped ops outside every scope are not the
    arena's."""
    r = T.Reduced(chips=1)
    r.modules = [("jit_decode_block", 5, 9), ("jit_decode_block", 20, 40),
                 ("jit_prefill", 40, 45), ("jit_decode_block", 50, 70),
                 ("jit_decode_block", 80, 90)]
    restack = "%dus.1 = bf16[14,514,128,4,128]{4,3,2,1,0} fusion(%p)"
    weight = "%ds.2 = bf16[1,3584,514]{1,2,0} fusion(%w)"
    relayout = "%reshape.3 = bf16[514,128,512]{2,1,0} reshape(%c)"
    r.op_events = [(restack, 5, 8), ("%scatter", 20, 22), (weight, 22, 24),
                   (restack, 24, 30), ("%mlp", 30, 31), ("%kernel", 31, 39),
                   (relayout, 50, 53), ("%kernel", 53, 70)]
    scopes = {restack: "jit(decode_block)/while/body/closed_call/while/"
                       "body/dynamic_update_slice:",
              weight: "jit(decode_block)/while/body/closed_call/while/"
                      "body/dynamic_slice:",
              "%scatter": "jit(decode_block)/x/kv_arena/scatter:",
              "%mlp": "jit(decode_block)/x/mlp/dot:",
              "%kernel": "jit(decode_block)/x/attention_kernel/pallas_call:",
              relayout: "jit(decode_block)/x/attention_kernel/"
                        "jit(paged_attention_pallas)/reshape:"}
    spans = [("engine.decode_dispatch", 10, 11, {"block": 7, "k": 8}),
             ("engine.decode_dispatch", 45, 46, {"block": 8, "k": 4}),
             ("engine.decode_dispatch", 75, 76, {"block": 9, "k": 2})]
    assert P.decode_runs(r, spans) == [(8, 20, 40), (4, 50, 70)]
    assert P.scope_ns(r, scopes, 20, 40) == {
        "kv_arena": 2, P.UNSCOPED: 8, "mlp": 1, "attention_kernel": 8}
    assert P.arena_ns(r, scopes, (514, 128), 20, 40) == {
        "kv_arena": 2, P.UNSCOPED: 6}
    assert P.arena_ns(r, scopes, (514, 128), 50, 70) == {
        "attention_kernel": 3}
    assert P.arena_ms_per_step(r, spans, scopes, (514, 128)) == \
        pytest.approx(11 / 1e6 / 12)
    assert P.arena_ms_per_step(r, spans[:0], scopes, (514, 128)) is None


def test_decode_runs_pair_a_run_that_starts_before_its_span():
    """On the trace's clock a run can start a little before the span that
    dispatched it; it is still that span's run, not the previous block's."""
    r = T.Reduced(chips=1)
    r.modules = [("jit_decode_block", 0, 40), ("jit_decode_block", 99, 419),
                 ("jit_decode_block", 470, 510), ("jit_prefill", 520, 530)]
    spans = [("engine.decode_dispatch", 1, 2, {"block": 4, "k": 8}),
             ("engine.decode_dispatch", 100, 101, {"block": 5, "k": 8}),
             ("engine.decode_dispatch", 469, 470, {"block": 6, "k": 1})]
    assert P.decode_runs(r, spans[1:]) == [(8, 99, 419), (1, 470, 510)]
    with pytest.raises(ValueError):
        P.decode_runs(r, spans[::2])
