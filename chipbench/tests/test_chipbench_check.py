"""The comparison that decides ``correct``, rehearsed on the CPU at a tiny
size: the served path agrees with the plain float32 reference, also for an
architecture that exists only as a family file, the control (the reference
in a lower precision, put in the program's place) fails the comparison, a
token altered where it is produced fails it, a request that fails in the
engine fails it, and the measured path refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from chipbench import cell as C
from chipbench import replica
from chipbench import run as R
from chipbench.traffic import Traffic
from repro.core.faults import FaultInjector

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 12345
# at the tiny size, on three seeds: served greedy tokens sit 0 to 0.019
# below the reference's best, the fp8 control's 0.33 to 0.35; served
# sampled tokens lie 0 to 0.0001 outside the reference's nucleus.  The fp8
# control serves a token outside the nucleus about once in 200 sampled
# tokens, so its reading needs a sample of fixed size (``fixed_sample``):
# over 1,000 greedy and 1,000 sampled tokens, on five seeds, 0.44 to 0.62
# and 0.0074 to 0.0148; over 400 of each, two of five seeds read 0 and
# 0.0005 outside the nucleus
TINY_LIMITS = {"max_logit_gap": 0.15, "min_served_tokens": 100,
               "max_nucleus_excess": 0.003, "min_sampled_tokens": 100}


@pytest.fixture(scope="module", autouse=True)
def own_compile_cache(tmp_path_factory):
    """The runs below turn JAX's persistent cache on: keep it out of the
    checkout, and give later tests in this process the settings back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    yield
    if env is None:
        del os.environ["JAX_COMPILATION_CACHE_DIR"]
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def tiny(kind, **conf_update):
    """A Qwen2 configuration with tied embeddings (qwen2-0.5b's file) and
    a mix cut to a size the CPU runs in seconds: two layers, d 64, a
    512-token vocabulary, four slots."""
    conf = json.loads((C.HERE / "configs" / "qwen2-0.5b.json").read_text())
    conf.update(name="tiny", hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=512,
                serve_flags=["--max-batch", "4", "--cache-len", "256",
                             "--kv-layout", "paged", "--prefill-chunk", "64"],
                **conf_update)
    mix = C.traffic(kind)
    if kind == "chat_sessions":
        mix.update(session_rate_per_s=1.5, ramp_s=2,
                   system_prompt_tokens=64, context_limit=256,
                   user_tokens={"dist": "lognormal", "median": 16,
                                "sigma": 1.0, "min": 4, "max": 64},
                   output_tokens={"dist": "lognormal", "median": 16,
                                  "sigma": 0.8, "min": 4, "max": 48},
                   think_s={"dist": "exponential", "mean": 0.5},
                   greedy_share=0.5, warm_rows=[1, 2], warm_first_tokens=2)
    else:
        mix.update(ramp_s=1, pool_size=16, warm_rows=[1, 2],
                   warm_first_tokens=2,
                   prompt_tokens={"dist": "lognormal", "median": 64,
                                  "sigma": 0.6, "min": 16, "max": 128},
                   output_tokens={"dist": "lognormal", "median": 16,
                                  "sigma": 0.5, "min": 4, "max": 48})
    wl = {"name": f"tiny.{kind}", "config": "tiny", "traffic": kind,
          "chips": 1}
    metrics = C.metrics_for(C.benchmark(), "qwen2-7b.decode_heavy",
                            "end_to_end")
    return wl, conf, mix, metrics


def tiny_run(kind, control=None, **conf_update):
    wl, conf, mix, metrics = tiny(kind, **conf_update)
    return R.run_cell(wl, conf, mix, TINY_LIMITS, metrics, SEED, 4.0, False,
                      require_chip=False, control=control,
                      log=lambda *a, **k: None)


@pytest.fixture(scope="module")
def chat():
    return tiny_run("chat_sessions")


def test_served_path_agrees_with_reference(chat):
    """Paged prefill (chunked, resumed from prefix-cache pages), paged
    decode blocks, the top-p sampler and the head, against one float32 pass
    per sequence."""
    assert chat["correct"] is True
    checks = chat["checks"]
    assert list(checks) == ["logit_gap", "nucleus_excess", "served_tokens",
                            "sampled_tokens", "failed_requests"]
    assert checks["served_tokens"]["value"] >= 100
    assert checks["sampled_tokens"]["value"] >= 100
    assert checks["logit_gap"]["value"] <= TINY_LIMITS["max_logit_gap"]
    assert (checks["nucleus_excess"]["value"]
            <= TINY_LIMITS["max_nucleus_excess"])
    assert checks["failed_requests"] == {"value": 0, "limit": 0}
    assert list(chat)[-1] == "checks"


# a family that exists only as a file: Qwen2's code under a new name
NEW_FAMILY = '''
from chipbench import cell

_qwen2 = cell.family({"architectures": ["Qwen2ForCausalLM"]}, here=cell.HERE)
model_config = _qwen2.model_config
engine_params = _qwen2.engine_params
Reference = _qwen2.Reference
total_params = _qwen2.total_params
kv_bytes_per_token = _qwen2.kv_bytes_per_token
decode_token_flops = _qwen2.decode_token_flops
prefill_flops = _qwen2.prefill_flops
paged_attention_cost = _qwen2.paged_attention_cost
'''


def test_family_file_alone_drives_the_served_path(tmp_path, monkeypatch):
    """A new architecture needs a file under ``families/`` and no edit to
    a harness file: the run finds it by ``architectures[0]``."""
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "tinyarch.py").write_text(NEW_FAMILY)
    found, real_family = [], C.family

    def family(conf, here=tmp_path):
        mod = real_family(conf, here=here)
        found.append(mod.__file__)
        return mod

    monkeypatch.setattr(C, "family", family)
    res = tiny_run("chat_sessions", architectures=["TinyArchForCausalLM"])
    assert str(tmp_path / "families" / "tinyarch.py") in found
    assert res["correct"] is True
    assert res["checks"]["served_tokens"]["value"] >= 100
    assert res["checks"]["sampled_tokens"]["value"] >= 100


def fixed_sample(conf, mix, tokens=1000):
    """Greedy and sampled requests of the chat mix, each turn's prompt the
    whole history, with fixed ids in place of the served tokens: about
    ``tokens`` served tokens of each kind, whatever a timed window would
    have finished."""
    t = Traffic(mix, SEED, conf["vocab_size"], 60.0, 4)
    reqs = {True: [], False: []}
    for s in t.sessions:
        history = t.system_prompt(s.system)
        for j, turn in enumerate(s.turns):
            prompt = history + t.user_message(s.index, j, turn.user_tokens)
            if len(prompt) + turn.output_tokens > t.context_limit:
                break
            served = t.ids(turn.output_tokens, 9, s.index, j)
            if sum(len(r.tokens) for r in reqs[s.greedy]) < tokens:
                reqs[s.greedy].append(SimpleNamespace(prompt=prompt,
                                                      tokens=served))
            history = prompt + served
    return reqs[True], reqs[False]


def test_control_fails_the_comparison(chat):
    """The fp8 reference put in the program's place, judged by the same
    rule, comes out not correct, on greedy and on sampled tokens alike:
    in a run, and on a fixed sample, so that how much a timed window on a
    busy CPU finished does not decide it."""
    assert tiny_run("chat_sessions", control="fp8")["correct"] is False
    wl, conf, mix, metrics = tiny("chat_sessions")
    greedy, sampled = fixed_sample(conf, mix)
    _, control = R.compare(C.family(conf), conf, SEED, greedy, sampled,
                           mix["sampling"], control="fp8")
    checks = R.judge(dict(control, failed_requests=0,
                          served_tokens=sum(len(r.tokens) for r in greedy),
                          sampled_tokens=sum(len(r.tokens) for r in sampled)),
                     TINY_LIMITS)
    assert not all(c["ok"] for c in checks.values())
    for name, limit in (("logit_gap", "max_logit_gap"),
                        ("nucleus_excess", "max_nucleus_excess")):
        value = control[name]
        assert value > TINY_LIMITS[limit]
        assert value >= 3 * chat["checks"][name]["value"]


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    real_build = replica.build

    def broken_build(*args, **kw):
        client = real_build(*args, **kw)
        eng = client.engine
        block = eng._decode_block_fn
        vocab = eng.cfg.vocab_size

        def altered(*a, **k):
            cache, state, toks, lps = block(*a, **k)
            return cache, state, jnp.where(toks >= 0, (toks + 1) % vocab,
                                           toks), lps

        eng._decode_block_fn = altered
        return client

    monkeypatch.setattr(replica, "build", broken_build)
    res = tiny_run("decode_heavy")
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMITS["max_logit_gap"]


def test_failed_request_is_not_correct(monkeypatch):
    """Requests that end in an error at the engine's fault boundary take
    load off the chip; the run must not count as correct however good the
    tokens of the others are."""
    real_warm = replica.warm

    def warm_then_fault(client, mix):
        seconds = real_warm(client, mix)
        client.engine.faults = FaultInjector(seed=3, rates={"prefill": 0.3})
        return seconds

    monkeypatch.setattr(replica, "warm", warm_then_fault)
    res = tiny_run("decode_heavy")
    assert res["failed"] > 0
    assert res["checks"]["failed_requests"]["value"] == res["failed"]
    assert res["correct"] is False


def _no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-7b.decode_heavy", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "needs 1 TPU chip" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-7b.decode_heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
