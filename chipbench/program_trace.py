"""What the program itself writes into a profiler trace, beside what
``trace.reduce`` keeps: its host spans (``repro.<phase>``, with their
stats) and each device operation's scope path.

A device op event names its HLO instruction; the instruction's metadata
sits on the event's *metadata* entry of the ``.xplane.pb``: stat ``tf_op``
holds the op_name path, the scopes it was traced under
(``jit(decode_block)/while/body/closed_call/while/body/closed_call/mlp/
dot_general:``), and ``source`` its line of code.  ``ProfileData`` exposes the events' own stats
only, so ``op_scopes`` reads the metadata from the serialized XSpace with a
small protobuf wire reader (XSpace.planes = 1; XPlane.name = 2,
event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2, stats = 5;
XStat.metadata_id = 1, str_value = 5, ref_value = 7; XStatMetadata.name =
2).

``trace.py`` keeps neither, and ``run.py`` deletes the trace before the
per-layer readers run, so no metric reads this module yet; PERF.md names
the two calls that would wire it in.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Sequence, Tuple

from chipbench import trace as T

PREFIX = "repro."
# the program's device scopes (repro/tracing.scope); an op belongs to the
# innermost of them on its path
SCOPES = ("kv_arena", "attention_kernel", "mlp", "head", "sampler")
ARENA, UNSCOPED = "kv_arena", "unscoped"
SCOPE_STAT = "tf_op"
DECODE_SPAN, DECODE_PROGRAM = "engine.decode_dispatch", "jit_decode_block"
_SHAPE = re.compile(r"\[([\d,]*)\]")

Span = Tuple[str, float, float, dict]       # (name, start_ns, end_ns, stats)


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized message: an int for varints,
    a memoryview for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """Op event name -> its ``tf_op`` metadata stat, the scope path, over
    the device planes of a serialized XSpace."""
    out: Dict[str, str] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(entry.get(2, b""))
        if not name.startswith(T.DEVICE_PREFIX):
            continue
        for entry in metas:                 # map entry: key = 1, value = 2
            value = dict(_fields(entry)).get(2)
            if value is None:
                continue
            op, path = "", None
            for f, v in _fields(value):
                if f == 2:
                    op = _text(v)
                elif f == 5:
                    s = dict(_fields(v))
                    if stat_names.get(s.get(1)) != SCOPE_STAT:
                        continue
                    path = (_text(s[5]) if 5 in s
                            else stat_names.get(s.get(7), ""))
            if path is not None:
                out[op] = path
    return out


def innermost(path: str) -> str:
    """The innermost program scope on an op's path, else ``unscoped``."""
    for part in reversed(path.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def host_spans(source) -> List[Span]:
    """The program's ``repro.*`` host spans, prefix dropped, with their
    stats, in time order."""
    out = []
    for plane in T.load(source).planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def attribute(spans: Sequence[Tuple], gap: Tuple[float, float]) -> str:
    """What the host was doing in a device gap: the innermost (shortest)
    span that covers the whole gap, else the span that overlaps it most,
    else ``unattributed``.  Numbered spans (``name#i``) lose the number."""
    covering = [(s[2] - s[1], s[0]) for s in spans
                if s[1] <= gap[0] and gap[1] <= s[2]]
    if covering:
        return min(covering)[1].split("#")[0]
    best, most = "unattributed", 0.0
    for s in spans:
        overlap = min(s[2], gap[1]) - max(s[1], gap[0])
        if overlap > most:
            best, most = s[0].split("#")[0], overlap
    return best


def _leaves_in(r: T.Reduced, start: float, end: float) -> List[T.Event]:
    """The first chip's leaf operations that start inside ``[start, end)``."""
    ev = r.op_events
    lo = bisect.bisect_left(ev, start, key=lambda e: e[1])
    hi = bisect.bisect_left(ev, end, key=lambda e: e[1])
    return T._leaves(ev[lo:hi])


def scope_ns(r: T.Reduced, scopes: Dict[str, str], start: float = -1.0,
             end: float = float("inf")) -> Dict[str, float]:
    """Nanoseconds of the first chip's leaf operations that start inside
    ``[start, end)``, by innermost scope."""
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in _leaves_in(r, start, end):
        out[innermost(scopes.get(name, ""))] += e - s
    return dict(out)


def decode_runs(r: T.Reduced, spans: Sequence[Span]
                ) -> List[Tuple[int, float, float]]:
    """``(K, start_ns, end_ns)`` of each traced decode-block run, paired in
    order with the ``engine.decode_dispatch`` spans, whose ``block`` stats
    must run without a gap.

    The engine syncs a block's tokens before it dispatches the next, so the
    first run that ends after the first span starts is that span's: a run
    that ends earlier was dispatched before the trace began.  (A run may
    start a millisecond before its own span on the trace's clock, which
    ``trace.runs``, pairing from the first run that *starts* after the first
    span, reads as a run of the previous block.)  The trace's last program
    run may be cut short by the trace's end, and a span whose run fell after
    it has none: both drop out."""
    disp = [(s, st) for name, s, _, st in spans if name == DECODE_SPAN]
    if not disp or not r.modules:
        return []
    blocks = [int(st["block"]) for _, st in disp]
    if blocks != list(range(blocks[0], blocks[0] + len(blocks))):
        raise ValueError(f"decode dispatch spans skip blocks: {blocks}")
    last_start = r.modules[-1][1]
    mods = [(s, e) for name, s, e in r.modules
            if name == DECODE_PROGRAM and e > disp[0][0]]
    return [(int(st["k"]), s, e) for (_, st), (s, e) in zip(disp, mods)
            if e <= last_start]


def _holds(shape: str, dims: Tuple[int, ...]) -> bool:
    """Whether the output shape at the head of an op's HLO text (``bf16[14,
    514,128,4,128]{...}``) holds ``dims`` as consecutive dimensions."""
    m = _SHAPE.search(shape)
    out = [int(d) for d in m.group(1).split(",") if d] if m else []
    n = len(dims)
    return any(tuple(out[i:i + n]) == dims for i in range(len(out) - n + 1))


def arena_ns(r: T.Reduced, scopes: Dict[str, str], dims: Tuple[int, ...],
             start: float = -1.0, end: float = float("inf")
             ) -> Dict[str, float]:
    """Nanoseconds of the paged arena's traffic among the first chip's leaf
    operations that start inside ``[start, end)``, by innermost scope: ops
    under ``kv_arena``, and ops whose output holds the arena's ``dims``
    (pages, page size) in any scope or none.  The second kind covers what
    no scope can name alone: the layer scan's slicing and restacking of the
    cache, which share the scan's op path with its slicing of the weights,
    the kernel's relayout of its operands, and the copies of the scan's
    carry that copy insertion adds without metadata."""
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in _leaves_in(r, start, end):
        scope = innermost(scopes.get(name, ""))
        if scope == ARENA or _holds(name.partition(" = ")[2], dims):
            out[scope] += e - s
    return dict(out)


def arena_ms_per_step(r: T.Reduced, spans: Sequence[Span],
                      scopes: Dict[str, str], dims: Tuple[int, ...]
                      ) -> float | None:
    """The arena's device time inside the traced decode-block runs
    (``arena_ns``), over the decode steps of those runs."""
    runs = decode_runs(r, spans)
    steps = sum(k for k, _, _ in runs)
    if not steps:
        return None
    ns = sum(sum(arena_ns(r, scopes, dims, s, e).values())
             for _, s, e in runs)
    return ns / 1e6 / steps
