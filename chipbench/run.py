#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``: process start to the start of the load) makes the
seeded weights on the device, builds the replica the way
``repro.launch.serve`` does and warms every program the cell's traffic
reaches.  The load then ramps for the mix's ``ramp_s`` and is measured for
``--seconds``.  With ``--trace 0`` the last stdout line carries the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of part of the
window gives its per-layer metrics.  After the window the program is freed
and a sample of the finished requests is checked against the plain float32
reference of the configuration's family (``families/<name>.py``, with
``reference.py``); ``correct`` says whether every compared
number kept its limit (``limits/<cell>.json``), failed and refused requests
among them.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":             # run as a script from a checkout
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# part of a traced run's window that the profiler records: from 30% of the
# window, for 4 s at most (a trace of the whole window would be too large)
TRACE_FROM, TRACE_MAX_S = 0.3, 4.0
# the correctness sample, of greedy and of sampled requests each: the
# longest finished request, then others drawn from the seed until this many
# requests and served tokens are compared
SAMPLE_REQUESTS, SAMPLE_TOKENS = 6, 400


class NoChip(SystemExit):
    pass


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"chipbench: needs {chips} TPU chip(s), JAX found "
                     f"{len(devices)} {devices[0].platform} device(s); "
                     "no result")
    return devices[:chips]


def enable_compile_cache() -> str:
    """``serve.py``'s persistent compilation cache (``.jax_cache`` in the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), with every program
    cached, however quick its compile, so only a checkout's first run
    compiles."""
    import jax
    from repro.launch import serve
    path = serve.enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (as ``chip_smoke.py`` reads them)."""

    def __init__(self):
        import jax
        self.compiles: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.compiles[name] = self.compiles.get(name, 0) + 1
            self.seconds[name] = self.seconds.get(name, 0.0) + duration
            self.compile_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return sum(self.compiles.values())

    def top(self, n: int = 8) -> str:
        names = sorted(self.seconds, key=self.seconds.get, reverse=True)[:n]
        return ", ".join(f"{k} x{self.compiles[k]} {self.seconds[k]:.1f} s"
                         for k in names)


@dataclass
class RunData:
    """What the per-layer readers (``metrics/<name>.py``) read; ``family``
    is the configuration's ``families/<name>.py`` module, whose counts the
    readers divide by."""
    dims: dict
    peaks: dict
    recs: list
    opens: float
    closes: float
    counters: Dict[str, Dict[str, int]] = field(default_factory=dict)
    trace: object = None
    probe: object = None
    busy_s: float = 0.0         # device busy time in the trace, per chip
    window_s: float = 0.0       # first to last event of the trace
    family: object = None

    def counter_delta(self, key: str) -> int:
        return self.counters["close"][key] - self.counters["open"][key]

    def runs(self, programs, span: str):
        """The traced runs of ``programs`` paired with the probe's records
        (``trace.runs``); a program missing from the trace is an error."""
        from chipbench import trace as T
        self.trace.require_programs(programs)
        return T.runs(self.trace, programs, span)


def quantile(values: List[float], q: float) -> float:
    """Nearest-rank quantile (``q`` in [0, 1]) of all the values."""
    vs = sorted(values)
    return vs[min(len(vs) - 1, max(0, int(-(-q * len(vs) // 1)) - 1))]


def end_to_end(recs, opens: float, closes: float) -> dict:
    """The client-side numbers of one window."""
    from chipbench.load import FIRST_TOKEN_WAIT_S
    due = [r for r in recs if opens <= r.due < closes]
    # a request that never showed a token counts as waiting until the run
    # stopped waiting for it
    give_up = closes + FIRST_TOKEN_WAIT_S
    ttft = [((r.first if r.first is not None else give_up) - r.due) * 1e3
            for r in due]
    gaps, tokens = [], 0
    for r in recs:
        ts = r.times
        tokens += sum(opens <= t < closes for t in ts)
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                    if opens <= b < closes)
    def backlog(t):             # due by t, first token not shown by t
        return sum(r.due <= t and (r.first is None or r.first > t)
                   for r in recs)

    return {
        "requests_due": len(due),
        "backlog_open": backlog(opens),
        "backlog_close": backlog(closes),
        "ttft_p50_ms": statistics.median(ttft) if ttft else None,
        "ttft_p90_ms": quantile(ttft, 0.90) if ttft else None,
        "ttft_p95_ms": quantile(ttft, 0.95) if ttft else None,
        "itl_gaps": len(gaps),
        "itl_p50_ms": statistics.median(gaps) if gaps else None,
        "itl_p95_ms": quantile(gaps, 0.95) if gaps else None,
        "output_tokens": tokens,
        "output_tok_s": tokens / (closes - opens),
    }


def pick_sample(recs, seed: int, greedy: bool) -> list:
    """Finished greedy (or sampled) requests: the longest, then others drawn
    from the seed, until the sample holds ``SAMPLE_REQUESTS`` requests and
    ``SAMPLE_TOKENS`` served tokens, so it spans many slots."""
    import numpy as np
    done = [r for r in recs if r.greedy == greedy and r.ok and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.tokens), r.key))
    sample = [done.pop()]
    order = np.random.default_rng([int(seed), 3, int(greedy)]).permutation(
        len(done))
    for i in order:
        if (len(sample) >= SAMPLE_REQUESTS
                and sum(len(r.tokens) for r in sample) >= SAMPLE_TOKENS):
            break
        sample.append(done[i])
    return sample


def compare(family, dims: dict, seed: int, greedy: list, sampled: list,
            sampling: dict, control: Optional[str] = None
            ) -> Tuple[dict, Optional[dict]]:
    """The numbers that decide ``correct``, for the tokens the program
    served, against ``family.Reference``: ``logit_gap``, the widest gap of
    a served greedy token below the reference's best logit, and
    ``nucleus_excess``, the furthest a served sampled token lies outside
    the reference's top-p nucleus (``reference.nucleus_excess``).  With
    ``control`` (a precision of ``reference.ROUNDERS``) the same numbers
    come second for the control put in the program's place: the tokens it
    serves at the same positions (its best token for greedy requests, a
    draw from its nucleus for sampled ones)."""
    import numpy as np
    from chipbench import reference
    reqs = greedy + sampled
    n = len(greedy)
    seqs = [(r.prompt + r.tokens, len(r.tokens)) for r in reqs]
    ref = family.Reference(dims).logits(seed, seqs)
    temp, top_p = (float(sampling.get("temperature", 1.0)),
                   float(sampling.get("top_p", 1.0)))

    def numbers(served: list) -> dict:
        out = {}
        if greedy:
            gaps = [reference.served_gaps(lg, s)
                    for lg, s in zip(ref[:n], served[:n])]
            out["logit_gap"] = max(float(g.max()) for g in gaps)
            out["greedy_not_best"] = int(sum((g > 0).sum() for g in gaps))
        if sampled:
            ex = [reference.nucleus_excess(lg, s, temp, top_p)
                  for lg, s in zip(ref[n:], served[n:])]
            out["nucleus_excess"] = max(0.0, max(float(e.max()) for e in ex))
            out["sampled_outside"] = int(sum((e > 0).sum() for e in ex))
        return out

    program = numbers([r.tokens for r in reqs])
    if not control:
        return program, None
    low = family.Reference(dims, quant=control).logits(seed, seqs)
    served = [reference.greedy_tokens(lg) for lg in low[:n]]
    for i, lg in enumerate(low[n:]):
        u = np.random.default_rng([int(seed), 4, i]).random(lg.shape[0])
        served.append(reference.nucleus_tokens(lg, u, temp, top_p))
    del low
    return program, numbers(served)


# the numbers compared, each against its limit in ``limits/<cell>.json``
# (no request may fail or be refused, whatever the cell's file says):
# (name, limit key, True where the number must reach the limit)
CHECKS = (("logit_gap", "max_logit_gap", False),
          ("nucleus_excess", "max_nucleus_excess", False),
          ("served_tokens", "min_served_tokens", True),
          ("sampled_tokens", "min_sampled_tokens", True),
          ("failed_requests", "max_failed_requests", False))


def judge(numbers: dict, limits: dict) -> Dict[str, dict]:
    """Each number the cell's limits name, beside its limit; a number the
    run could not read is None and fails."""
    limits = dict(limits, max_failed_requests=0)
    checks = {}
    for name, key, at_least in CHECKS:
        if key not in limits:
            continue
        value, limit = numbers.get(name), limits[key]
        ok = value is not None and (value >= limit if at_least
                                    else value <= limit)
        checks[name] = {"value": value, "limit": limit, "ok": ok}
    return checks


def run_cell(wl: dict, conf: dict, mix: dict, limits: dict, metrics: list,
             seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, control: Optional[str] = None,
             log=print) -> dict:
    """One run of the cell ``wl`` (its ``BENCHMARK.json`` entry) with its
    configuration, traffic mix, limits and metric entries (per-layer ones
    when ``trace``) given as data."""
    from chipbench import cell as C
    cache_dir = enable_compile_cache()
    import jax
    devices = (check_devices(wl["chips"]) if require_chip
               else jax.devices()[:wl["chips"]])
    dev = devices[0]
    from chipbench import peaks as P
    from chipbench import replica
    from chipbench.load import Load
    from chipbench.traffic import Traffic
    peaks = P.peaks_for(dev.device_kind) if require_chip else {}
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")

    family = C.family(conf)
    clog = CompileLog()
    t = time.perf_counter()
    params = family.engine_params(seed, conf)
    client = replica.build(family.model_config(conf), conf["serve_flags"],
                           params)
    del params
    log(f"[setup] weights and replica {time.perf_counter() - t:.3f} s, "
        f"process start to here {time.perf_counter() - T_START:.3f} s")
    engine = client.engine
    warm_s = replica.warm(client, mix)
    traffic = Traffic(mix, seed, conf["vocab_size"], seconds,
                      engine.scheduler.max_batch)
    probe = None
    if trace:
        from chipbench.probe import Probe
        probe = Probe(client)
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f} s (warm-up {warm_s:.3f} s); compiles "
        f"{clog.count()} ({clog.compile_s:.1f} s), persistent-cache hits "
        f"{clog.cache_hits}")
    log(f"[setup] longest compiles: {clog.top()}")

    counters: Dict[str, Dict[str, int]] = {}
    compiles: Dict[str, int] = {}
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0    # the spans need the host tracer only

    def mark(what: str) -> None:
        if what in ("open", "close"):
            counters[what] = dict(vars(engine.scheduler.stats))
            compiles[what] = dict(clog.compiles)
        elif what == "trace_start":
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        elif what == "trace_stop":
            jax.profiler.stop_trace()

    marks = []
    if trace:
        start = TRACE_FROM * seconds
        marks = [(start, "trace_start"),
                 (start + min(TRACE_MAX_S, 0.4 * seconds), "trace_stop")]
    load = Load(client, traffic, on_window=mark, marks=marks)
    t0 = time.perf_counter()
    recs = load.run(t0)
    opens = t0 + traffic.ramp_s
    closes = opens + traffic.window_s
    e2e = end_to_end(recs, opens, closes)
    lateness = sorted(load.lateness)
    log(f"[window] {e2e}")
    in_window = {k: v - compiles["open"].get(k, 0)
                 for k, v in compiles["close"].items()
                 if v > compiles["open"].get(k, 0)}
    log(f"[window] compiles inside the window: {sum(in_window.values())} "
        f"{in_window}; generator late p50 "
        f"{1e3 * statistics.median(lateness):.3f} ms, max "
        f"{1e3 * lateness[-1]:.3f} ms")
    attempted = [r for r in recs if r.due < closes]
    failed = [r for r in attempted
              if r.finish is not None and r.finish not in
              ("length", "stop", "abort")]
    log(f"[window] attempted {len(attempted)}, failed {len(failed)}: "
        f"{sorted({r.finish for r in failed})}")

    log(f"[window] scheduler counters over the window: "
        f"{ {k: counters['close'][k] - counters['open'][k] for k in counters['close']} }")
    mem = dev.memory_stats() or {}
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0)) for d in devices)
    log(f"[memory] peak {peak_bytes} B of {mem.get('bytes_limit', 0)} B")
    # free the program's device state before the reference runs: handles
    # reach the client, and so the engine
    client.stop()
    for r in recs:
        r.handle = None
    del client, engine, load
    if probe is not None:
        probe.release()
    gc.collect()
    live = jax.live_arrays()
    log(f"[memory] {sum(a.nbytes for a in live)} B in {len(live)} arrays "
        "still live after the engine stopped: deleted before the reference")
    for a in live:
        a.delete()
    del live

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": len(attempted),
              "failed": len(failed), "metrics": {}, "device": device}
    run = RunData(conf, peaks, recs, opens, closes, counters, probe=probe,
                  family=family)
    if trace:
        from chipbench import trace as T
        run.trace = T.reduce(T.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.busy_s = run.trace.busy_ns / 1e9 / run.trace.chips
        run.window_s = run.trace.window_ns / 1e9
        device["busy_s"] = run.busy_s
        device["window_s"] = run.window_s
        log(f"[trace] {len(run.trace.modules)} program runs, "
            f"{len(run.trace.op_events)} operations, {len(run.trace.spans)} "
            f"host spans; device time by program: "
            f"{sorted(run.trace.programs.items(), key=lambda kv: -kv[1])[:12]}")
        for m in metrics:
            try:
                value = C.reader(m["name"])(run)
            except (KeyError, ValueError) as e:
                # left out of the line, so the run shows the metric missing
                log(f"[trace] metric {m['name']}: {e!r}", file=sys.stderr)
                continue
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = T.breakdown(run.trace)
    else:
        for m in metrics:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}

    greedy = pick_sample(recs, seed, greedy=True)
    sampled = (pick_sample(recs, seed, greedy=False)
               if "max_nucleus_excess" in limits else [])
    numbers = {"served_tokens": sum(len(r.tokens) for r in greedy),
               "sampled_tokens": sum(len(r.tokens) for r in sampled),
               "failed_requests": len(failed)}
    t_ref = time.perf_counter()
    program, stand_in = (compare(family, conf, seed, greedy, sampled,
                                 mix.get("sampling", {}), control)
                         if greedy or sampled else ({}, {}))
    log(f"[check] reference over {len(greedy)} greedy and {len(sampled)} "
        f"sampled requests in {time.perf_counter() - t_ref:.3f} s; "
        f"program: {program}")
    if control:
        # the control stands in for the program: its numbers are judged
        log(f"[check] control {control} in the program's place: {stand_in}")
        program = stand_in
    numbers.update(program)
    checks = judge(numbers, limits)
    result["correct"] = all(c["ok"] for c in checks.values())
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, choices=("fp8",),
                    help="judge the control instead of the program: the "
                         "reference in this precision put in the program's "
                         "place (the program's numbers are logged); for "
                         "setting limits, not for benchmark runs")
    a = ap.parse_args(argv)
    from chipbench import cell as C
    bench = C.benchmark()
    wl = C.workload(bench, a.workload)
    kind = "per_layer" if a.trace else "end_to_end"
    try:
        result = run_cell(wl, C.config(bench, wl["config"]),
                          C.traffic(wl["traffic"]), C.limits(a.workload),
                          C.metrics_for(bench, a.workload, kind), a.seed,
                          a.seconds, bool(a.trace), control=a.control)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
