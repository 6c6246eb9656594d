#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-0.5b at full width through the HTTP server.

    python chip_smoke.py                # one chip: kernels, dense + paged KV
    python chip_smoke.py --replicas 4   # four chips: router phase only

One process, one TPU host.  The default run

  1. checks every Pallas kernel against its ``kernels/ref.py`` reference
     at the model's widths, on the chip;
  2. builds the server the way ``repro.launch.serve`` does
     (``build_replica`` -> ``OpenAIServer`` -> ``AsgiServer``), once with
     ``--kv-layout dense`` and once with ``--kv-layout paged``, and sends
     chat, completions, streaming, echo, chunked-prefill and repeated
     (prefix-hit) requests over HTTP;
  3. fails on any non-200 reply, error envelope, finish other than
     ``length``/``stop``, usage that disagrees with the tokens returned,
     failed request in ``/stats``, a greedy request that answers twice
     differently, or a decode program that does not run its Pallas kernel.

``--replicas N`` builds N one-chip replicas behind ``serving/router.py``
(replica i on ``jax.devices()[i]``) and checks the router's answers
against one replica's.  Weights are random from ``--seed``.  Earlier
stdout lines report device kind, first-compile times, tokens served,
peak device memory and the attention implementation in each program; the
last line is one JSON object.  Exits non-zero, printing no result, when
no TPU is present or any check fails.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import gc
import json
import re
import sys
import time
import urllib.error
import urllib.request
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
# bf16 kernel-vs-reference tolerance: the one tests/test_kernels.py holds
# the interpret-mode kernels to
BF16_ATOL, BF16_RTOL = 2e-2, 1e-2
SERVE_FLAGS = ["--arch", ARCH, "--max-batch", "8", "--cache-len", "1024",
               "--prefill-chunk", "256"]
HTTP_TIMEOUT_S = 300
# which Pallas kernel each layout's decode program must contain
DECODE_KERNEL = {"dense": "decode_attention", "paged": "paged_attention"}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------- #
# compile observation (JAX monitoring events)
# --------------------------------------------------------------------------- #
class CompileLog:
    """First backend-compile time per program name, plus persistent-cache
    hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._counter)

    def reset(self) -> None:
        self.first: dict = {}
        self.count: dict = defaultdict(int)
        self.cache: dict = defaultdict(int)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.first.setdefault(name, duration)
            self.count[name] += 1

    def _counter(self, event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.cache[event.rsplit("/", 1)[1]] += 1

    def report(self, tag: str) -> None:
        for name in sorted(self.first, key=self.first.get, reverse=True)[:12]:
            print(f"[{tag}] first compile {name}: {self.first[name]:.2f} s "
                  f"({self.count[name]} compiles)")
        print(f"[{tag}] persistent compile cache: "
              f"{dict(self.cache) or 'no lookups'}")
        self.reset()


# --------------------------------------------------------------------------- #
# kernels against kernels/ref.py, on the chip
# --------------------------------------------------------------------------- #
def check_kernels(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.kernels.quant_matmul import (quant_matmul_pallas,
                                            quantize_int8, quantize_kv_int8)

    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    b, s, ps = 8, 1024, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=dt):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def compare(name, got, want_fn):
        with jax.default_matmul_precision("highest"):
            want = want_fn()
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == want.shape, f"{name}: shape {got.shape} != "
              f"{want.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = float(np.abs(got - want).max())
        ok = bool(np.allclose(got, want, atol=BF16_ATOL, rtol=BF16_RTOL))
        print(f"[kernels] {name} {tuple(got.shape)}: max |pallas - ref| = "
              f"{err:.3e} ({'ok' if ok else 'FAIL'})")
        check(ok, f"{name}: max error {err:.3e} over atol {BF16_ATOL} / "
              f"rtol {BF16_RTOL}")

    q, k, v = normal((2, 512, h, d)), normal((2, 512, hkv, d)), \
        normal((2, 512, hkv, d))
    compare("flash_attention", flash_attention_pallas(q, k, v, causal=True),
            lambda: ref.flash_attention_ref(q, k, v, causal=True))

    q, kc, vc = normal((b, h, d)), normal((b, s, hkv, d)), \
        normal((b, s, hkv, d))
    pos = jax.random.randint(next(keys), (b,), 0, 2 * s)      # some wrapped
    valid = (jnp.arange(s)[None] <= pos[:, None]) | (pos[:, None] >= s)
    compare("decode_attention", decode_attention_pallas(q, kc, vc, valid),
            lambda: ref.decode_attention_ref(q, kc, vc, valid))

    # a two-layer stacked arena, read at layer 1 in place
    pages = s // ps
    n = b * pages + 1
    kp, vp = normal((2, n, ps, hkv, d)), normal((2, n, ps, hkv, d))
    table = jax.random.permutation(next(keys), n)[:b * pages].reshape(
        b, pages).astype(jnp.int32)
    pos = jax.random.randint(next(keys), (b,), 0, s).astype(jnp.int32)
    layer = jnp.array([1], jnp.int32)

    def lanes(x):                  # [.., Hkv, D] -> the arena's [.., Hkv * D]
        return x.reshape(*x.shape[:-2], -1)

    compare("paged_attention",
            paged_attention_pallas(q, lanes(kp), lanes(vp), table, pos,
                                   layer),
            lambda: ref.paged_attention_ref(q, lanes(kp[1]), lanes(vp[1]),
                                            table, pos))
    (kq, ks), (vq, vs) = quantize_kv_int8(kp), quantize_kv_int8(vp)
    compare("paged_attention[int8]",
            paged_attention_pallas(q, lanes(kq), lanes(vq), table, pos,
                                   layer, k_scale=ks, v_scale=vs),
            lambda: ref.paged_attention_ref(q, lanes(kq[1]), lanes(vq[1]),
                                            table, pos, k_scale=ks[1],
                                            v_scale=vs[1]))

    x = normal((b, cfg.d_model))
    wq, sc = quantize_int8(normal((cfg.d_model, cfg.d_ff), jnp.float32))
    compare("quant_matmul", quant_matmul_pallas(x, wq, sc),
            lambda: ref.quant_matmul_ref(x, wq, sc))


# --------------------------------------------------------------------------- #
# HTTP client
# --------------------------------------------------------------------------- #
def http(port: int, path: str, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
            status, raw = r.status, r.read()
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"{path}: HTTP {e.code}: {e.read()[:500]!r}")
    check(status == 200, f"{path}: HTTP {status}")
    if body is not None and body.get("stream"):
        chunks = []
        for line in raw.decode().splitlines():
            if line.startswith("data: ") and line != "data: [DONE]":
                chunks.append(json.loads(line[len("data: "):]))
        for c in chunks:
            check("error" not in c, f"{path}: error chunk {c.get('error')}")
        return chunks
    out = json.loads(raw)
    check("error" not in out, f"{path}: error envelope {out.get('error')}")
    return out


def token_trace(reply) -> list:
    """(token text, logprob) per generated token of a 1-choice reply — the
    byte tokenizer renders most sampled ids as '', so the logprob is what
    tells two tokens apart."""
    lp = reply["choices"][0]["logprobs"]
    if "content" in lp:                                   # chat
        return [(e["token"], e["logprob"]) for e in lp["content"]]
    return list(zip(lp["tokens"], lp["token_logprobs"]))  # completions


def check_reply(name: str, reply, max_tokens: int, prompt_tokens: int = 0):
    """Clean finish, and usage that agrees with the returned tokens."""
    (choice,) = reply["choices"]
    reason = choice["finish_reason"]
    check(reason in ("length", "stop"), f"{name}: finish_reason {reason}")
    used = reply["usage"]["completion_tokens"]
    if reason == "length":
        check(used == max_tokens, f"{name}: {used} tokens, finish length "
              f"at max_tokens {max_tokens}")
    check(1 <= used <= max_tokens, f"{name}: completion_tokens {used}")
    if choice.get("logprobs"):
        n = len(token_trace(reply)) - prompt_tokens
        check(n == used, f"{name}: {n} tokens returned, usage says {used}")
    return used


def check_stream(name: str, chunks, max_tokens: int) -> int:
    finishes = [c["choices"][0]["finish_reason"] for c in chunks
                if c.get("choices") and c["choices"][0]["finish_reason"]]
    check(len(finishes) == 1 and finishes[0] in ("length", "stop"),
          f"{name}: finish reasons {finishes}")
    streamed = sum(len(c["choices"][0]["logprobs"]["content"])
                   for c in chunks if c.get("choices")
                   and c["choices"][0].get("logprobs"))
    usage = [c["usage"] for c in chunks if c.get("usage")]
    check(len(usage) == 1, f"{name}: {len(usage)} usage chunks")
    used = usage[0]["completion_tokens"]
    check(streamed == used, f"{name}: streamed {streamed} tokens, usage "
          f"says {used}")
    if finishes[0] == "length":
        check(used == max_tokens, f"{name}: {used} != max_tokens")
    return used


# --------------------------------------------------------------------------- #
# the served path
# --------------------------------------------------------------------------- #
def start_server(client, cfg):
    from repro.serving.api import OpenAIServer
    from repro.serving.asgi import AsgiServer
    server = AsgiServer(OpenAIServer(client, cfg.name), port=0)
    server.start()
    return server


def prompts(seed: int, vocab: int):
    """Token-id prompts from ``seed``: distinct first tokens, so no two
    share a prefix-cache block unless asked to."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return lambda n: [int(t) for t in rng.integers(300, vocab, n)]


def inspect_programs(engine) -> dict:
    """Pallas kernels in each compiled program the engine built, from the
    program's lowering (``kernel_name`` of every ``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp

    def kernels(lowered):
        return sorted({m for m in re.findall(r'kernel_name = "(\w+)"',
                                             lowered.as_text())})

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    out = {}
    params, cache, state = (sds(engine.params), sds(engine.pool.cache),
                            sds(engine.state))
    out["decode_block"] = kernels(engine._decode_block_fn.lower(
        params, cache, state, num_steps=1, want_logprobs=False))
    single = jax.eval_shape(engine.pool.single_cache_zeros)
    for (bucket, rows, xc), fn in sorted(engine._prefill_fns.items()):
        i32 = jax.ShapeDtypeStruct((rows, bucket), jnp.int32)
        out[f"prefill[bucket={bucket},rows={rows}]"] = kernels(fn.lower(
            params, i32, i32, (single,) * rows, None, None,
            jax.ShapeDtypeStruct((rows, bucket), jnp.bool_),
            jax.ShapeDtypeStruct((rows,), jnp.int32)))
    for bucket, fn in sorted(getattr(engine, "_echo_fns", {}).items()):
        from repro.models.model import cache_shapes
        out[f"echo_prefill[bucket={bucket}]"] = kernels(fn.lower(
            params, cache_shapes(engine.cfg, 1, bucket),
            jax.ShapeDtypeStruct((1, bucket), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)))
    return out


def report_memory(tag: str, devices) -> None:
    for d in devices:
        mem = d.memory_stats() or {}
        print(f"[{tag}] memory {d}: in use "
              f"{mem.get('bytes_in_use', 0) / 2**30:.3f} GiB, peak "
              f"{mem.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB of "
              f"{mem.get('bytes_limit', 0) / 2**30:.3f} GiB")


def serve_layout(layout: str, seed: int, log: CompileLog) -> int:
    """Serve one KV layout through HTTP; returns tokens generated."""
    import jax
    from repro.launch.serve import build_parser, build_replica, load_configs
    args = build_parser().parse_args(SERVE_FLAGS + ["--kv-layout", layout,
                                                    "--seed", str(seed)])
    cfg, _ = load_configs(args)
    tag = f"serve:{layout}"
    t0 = time.perf_counter()
    client = build_replica(args, cfg)
    print(f"[{tag}] {cfg.name}: {cfg.param_count() / 1e6:.1f}M params "
          f"{cfg.dtype}, built in {time.perf_counter() - t0:.1f} s")
    report_memory(tag, [jax.devices()[0]])
    server = start_server(client, cfg)
    port = server.port
    mk = prompts(seed, cfg.vocab_size)
    long_len = 3 * args.prefill_chunk - 68                 # 3 chunks
    try:
        t0 = time.perf_counter()
        first = http(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "Hello from the chip."}],
            "max_tokens": 16})
        check_reply("chat", first, 16)
        print(f"[{tag}] first request (compiles included): "
              f"{time.perf_counter() - t0:.1f} s")

        burst = {
            "chat": ("/v1/chat/completions", {
                "messages": [{"role": "system", "content": "Be brief."},
                             {"role": "user", "content": "Name a prime."}],
                "max_tokens": 16}, 16, 0),
            "completion": ("/v1/completions", {
                "prompt": mk(40), "max_tokens": 16, "logprobs": 2}, 16, 0),
            "stream": ("/v1/chat/completions", {
                "messages": [{"role": "user", "content": "Count to ten."}],
                "max_tokens": 24, "stream": True, "logprobs": True,
                "stream_options": {"include_usage": True}}, 24, 0),
            "long_prompt": ("/v1/completions", {
                "prompt": mk(long_len), "max_tokens": 8, "logprobs": 1},
                8, 0),
            "echo": ("/v1/completions", {
                "prompt": mk(64), "max_tokens": 4, "logprobs": 1,
                "echo": True}, 4, 64),
        }
        with concurrent.futures.ThreadPoolExecutor(len(burst)) as pool:
            futs = {name: pool.submit(http, port, path, body)
                    for name, (path, body, _, _) in burst.items()}
            for name, (_, _, mt, pt) in burst.items():
                reply = futs[name].result()
                if name == "stream":
                    check_stream(name, reply, mt)
                else:
                    check_reply(name, reply, mt, pt)

        # a repeated prompt: the first pass is cold, the next two resume
        # from the prefix cache (same path, so they must agree exactly)
        body = {"prompt": mk(100), "max_tokens": 16, "logprobs": 1}
        runs = []
        for i in range(3):
            reply = http(port, "/v1/completions", body)
            check_reply(f"repeat[{i}]", reply, 16)
            runs.append(token_trace(reply))
        check(runs[1] == runs[2], "the same greedy request (prefix hit) "
              f"answered differently: {runs[1]} vs {runs[2]}")
        # cold and hit run different prefill programs, so their logprobs
        # may differ in the last bits; a different token moves them by O(1)
        drift = max(abs(a[1] - b[1]) for a, b in zip(runs[0], runs[1]))
        print(f"[{tag}] repeated greedy prompt: hit == hit exactly; cold "
              f"vs hit: max |logprob difference| {drift:.3e} over 16 tokens")

        stats = http(port, "/stats")
        (rep,) = stats["replicas"]
        for key in ("failed", "aborted", "loop_errors"):
            check(rep[key] == 0, f"/stats {key} = {rep[key]}")
        hits = rep.get("prefix_cache", {}).get("hits", 0)
        check(hits >= 2, f"/stats prefix-cache hits = {hits}")
        print(f"[{tag}] served {rep['retired']} requests, "
              f"{rep['tokens_generated']} tokens; prefix hits {hits}; "
              f"prefill waves {rep['prefill_waves']}, chunks "
              f"{rep['prefill_chunks']}")

        programs = inspect_programs(client.engine)
        for name, found in programs.items():
            impl = ", ".join(found) if found else "jnp (no Pallas kernel)"
            print(f"[{tag}] {name}: {impl}")
        check(DECODE_KERNEL[layout] in programs["decode_block"],
              f"decode_block runs no {DECODE_KERNEL[layout]} kernel")
        echo = [v for k, v in programs.items() if k.startswith("echo")]
        check(bool(echo) and all("flash_attention" in v for v in echo),
              "the non-resumed (echo) prefill runs no flash kernel")
        log.report(tag)
        report_memory(tag, [jax.devices()[0]])
        return rep["tokens_generated"]
    finally:
        server.stop()
        client.stop()


def check_replica(i: int, rep: dict, engine, device) -> None:
    """Replica ``i`` holds its arrays on its own chip and served work."""
    import jax
    placed = {d for leaf in jax.tree.leaves(
        (engine.params, engine.pool.cache, engine.state))
        for d in leaf.devices()}
    check(placed == {device}, f"replica {i} arrays on {placed}")
    for key in ("failed", "aborted", "loop_errors"):
        check(rep[key] == 0, f"replica {i} /stats {key} = {rep[key]}")
    check(rep["retired"] > 0 and rep["tokens_generated"] > 0,
          f"replica {i} on {device} served nothing")
    print(f"[router] replica {i} on {device}: {rep['retired']} requests, "
          f"{rep['tokens_generated']} tokens")


def router_phase(n: int, seed: int, log: CompileLog) -> None:
    """N one-chip replicas behind the router against one replica: the same
    requests, one at a time, must give the same tokens; each replica's
    device must hold its engine and serve some of them."""
    import jax
    from repro.launch.serve import build_parser, build_replica, load_configs
    from repro.serving.router import Router
    devices = jax.devices()
    check(len(devices) >= n, f"--replicas {n} needs {n} devices, found "
          f"{len(devices)}")
    args = build_parser().parse_args(SERVE_FLAGS + ["--seed", str(seed)])
    cfg, _ = load_configs(args)
    mk = prompts(seed, cfg.vocab_size)
    bodies = [{"prompt": mk(24 + 8 * i), "max_tokens": 16, "logprobs": 1}
              for i in range(2 * n)]

    def run(client, tag):
        server = start_server(client, cfg)
        try:
            traces = []
            for i, body in enumerate(bodies):
                reply = http(server.port, "/v1/completions", body)
                check_reply(f"{tag}[{i}]", reply, 16)
                traces.append(token_trace(reply))
            with concurrent.futures.ThreadPoolExecutor(len(bodies)) as pool:
                for i, reply in enumerate(pool.map(
                        lambda b: http(server.port, "/v1/completions", b),
                        bodies)):
                    check_reply(f"{tag}:burst[{i}]", reply, 16)
            return traces, http(server.port, "/stats")
        finally:
            server.stop()

    t0 = time.perf_counter()
    replicas = [build_replica(args, cfg, index=i) for i in range(n)]
    router = Router(replicas, policy="round_robin", seed=seed)
    print(f"[router] {n} replicas built in {time.perf_counter() - t0:.1f} s")
    report_memory("router", devices[:n])
    try:
        routed, stats = run(router, "router")
        for i, rep in enumerate(stats["replicas"]):
            check_replica(i, rep, replicas[i].engine, devices[i])
    finally:
        router.stop()
    del replicas, router
    gc.collect()

    single = build_replica(args, cfg, index=0)
    try:
        alone, stats = run(single, "single")
        (rep,) = stats["replicas"]
        for key in ("failed", "aborted", "loop_errors"):
            check(rep[key] == 0, f"single replica /stats {key} = {rep[key]}")
    finally:
        single.stop()
    same = sum(a == b for a, b in zip(routed, alone))
    print(f"[router] router vs one replica: {same}/{len(bodies)} requests "
          "token-identical")
    check(same == len(bodies), "router and one replica disagree")
    log.report("router")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1: run only the router phase over N chips")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    from repro.launch.serve import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); refusing to run "
              "the smoke test elsewhere", file=sys.stderr)
        return 2
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")
    log = CompileLog()
    t0 = time.perf_counter()
    if opts.replicas > 1:
        router_phase(opts.replicas, opts.seed, log)
        count = opts.replicas
    else:
        from repro.configs import get_config
        check_kernels(get_config(ARCH), opts.seed)
        tokens = 0
        for layout in ("dense", "paged"):
            tokens += serve_layout(layout, opts.seed, log)
            gc.collect()
        print(f"[serve] tokens served: {tokens}")
        count = len(devices)
    report_memory("end", devices[:max(1, opts.replicas)])
    print(f"[total] {time.perf_counter() - t0:.1f} s (wall, compiles included)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
