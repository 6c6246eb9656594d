"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the union of device busy intervals, device time per program and per
operation, the longest idle gaps, and the benchmark's own host spans.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Modules`` line holds
one event per program run (``jit_<function>(<id>)``), their ``XLA Ops``
line one per operation; busy time is the union of the operation events.
Host spans are the ``TraceAnnotation`` events the harness writes
(``chipbench.*``), on any line of the ``/host:CPU`` plane.  Times are
nanoseconds on the trace's own clock, which the host and device planes
share.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "chipbench."
_SUFFIX = re.compile(r"\(\d+\)$")

Event = Tuple[str, float, float]            # (name, start_ns, end_ns)


def program_name(event_name: str) -> str:
    """``jit_decode_block(42)`` -> ``jit_decode_block``."""
    return _SUFFIX.sub("", event_name.strip())


@dataclass
class Reduced:
    chips: int = 0
    window_ns: float = 0.0            # first to last event of any plane
    busy_ns: float = 0.0              # union of op intervals, summed chips
    programs: Dict[str, float] = field(default_factory=dict)   # ns
    ops: Dict[str, float] = field(default_factory=dict)        # ns, leaf ops
    gaps: List[Tuple[float, float]] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)
    # the first chip's program runs and operations, in time order
    modules: List[Event] = field(default_factory=list)
    op_events: List[Event] = field(default_factory=list)

    def require_programs(self, names: Sequence[str]) -> None:
        """A program name missing from the trace is an error, not a zero."""
        if not any(k in names for k in self.programs):
            raise KeyError(f"none of the programs {list(names)} is in the "
                           f"trace; programs: {sorted(self.programs)}")

    def require_op(self, pattern: str) -> None:
        if not any(pattern in k for k in self.ops):
            raise KeyError(f"no operation matching {pattern!r} in the trace")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def load(source) -> "object":
    """``ProfileData`` from a path, or from the bytes of a serialized
    XSpace."""
    from jax.profiler import ProfileData
    if isinstance(source, (bytes, bytearray)):
        return ProfileData.from_serialized_xspace(bytes(source))
    return ProfileData.from_file(source)


def _leaves(events: List[Event]) -> List[Event]:
    """The events that contain no other event (an XLA ``while`` or
    ``conditional`` op spans the ops of its body on the same line)."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    return [ev for ev, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def reduce(source, gaps_kept: int = 10) -> Reduced:
    pd = load(source)
    r = Reduced()
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    all_gaps: List[Tuple[float, float]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            first = r.chips == 0
            r.chips += 1
            op_events: List[Event] = []
            for line in plane.lines:
                if line.name == MODULES:
                    for ev in line.events:
                        name = program_name(ev.name)
                        programs[name] += ev.duration_ns
                        if first:
                            r.modules.append((name, ev.start_ns,
                                              ev.start_ns + ev.duration_ns))
                elif line.name == OPS:
                    op_events.extend((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
                                     for ev in line.events)
            for name, s, e in _leaves(op_events):
                ops[name] += e - s
            if first:
                r.op_events = op_events
            merged = _union([(s, e) for _, s, e in op_events])
            r.busy_ns += sum(e - s for s, e in merged)
            all_gaps.extend((a[1], b[0]) for a, b in zip(merged, merged[1:]))
            if merged:
                lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    lo = min(lo, ev.start_ns)
                    hi = max(hi, ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        r.spans.append((ev.name[len(SPAN_PREFIX):],
                                        ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    if not r.chips:
        raise ValueError(f"{source if isinstance(source, str) else 'trace'}:"
                         f" no {DEVICE_PREFIX}* plane in the trace")
    r.window_ns = hi - lo if hi > lo else 0.0
    r.programs, r.ops = dict(programs), dict(ops)
    r.modules.sort(key=lambda e: e[1])
    r.op_events.sort(key=lambda e: e[1])
    r.spans.sort(key=lambda e: e[1])
    r.gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:gaps_kept]
    return r


def runs(r: Reduced, names: Sequence[str], span: str
         ) -> List[Tuple[int, float, float]]:
    """``(i, start_ns, end_ns)`` for each run of the programs ``names`` on
    the first chip, paired with the numbered host span ``<span>#<i>`` that
    dispatched it.

    One device runs its programs in the order the host dispatched them, so
    the runs that start after the first such span pair with the spans in
    order.  A run that starts earlier was dispatched before the trace began,
    a span left without a run had its run after the trace ended, and the
    trace's last program run may be cut short by the trace's end: all of
    them drop out, so a metric divides work and time of the same runs."""
    tag = span + "#"
    spans = [(s, int(name[len(tag):])) for name, s, _ in r.spans
             if name.startswith(tag)]
    if not spans or not r.modules:
        return []
    last_start = r.modules[-1][1]
    mods = [(s, e) for name, s, e in r.modules
            if name in names and s >= spans[0][0]]
    return [(i, s, e) for (_, i), (s, e) in zip(spans, mods)
            if e <= last_start]


def ops_within(r: Reduced, pattern: str, start: float, end: float) -> float:
    """Nanoseconds of the first chip's operations whose name contains
    ``pattern`` and that start inside ``[start, end)``."""
    ev = r.op_events
    lo = bisect.bisect_left(ev, start, key=lambda e: e[1])
    hi = bisect.bisect_left(ev, end, key=lambda e: e[1])
    return sum(e - s for name, s, e in ev[lo:hi] if pattern in name)


def attribute(r: Reduced, gap: Tuple[float, float]) -> str:
    """What the host was doing in a device gap: the span that overlaps it
    most (the innermost on a tie), else ``unattributed``."""
    best, best_key = "unattributed", (0.0, 0.0)
    for name, s, e in r.spans:
        key = (min(e, gap[1]) - max(s, gap[0]), s - e)
        if key[0] > 0 and key > best_key:
            best, best_key = name.split("#")[0], key
    return best


def breakdown(r: Reduced, top: int = 10) -> dict:
    ops = sorted(r.ops.items(), key=lambda kv: -kv[1])[:top]
    # an op's name is its HLO instruction; keep the head of it
    return {
        "device_ops": [[k[:160], v / 1e9] for k, v in ops],
        "idle_gaps": [[attribute(r, g), (g[1] - g[0]) / 1e9]
                      for g in r.gaps[:top]],
    }
