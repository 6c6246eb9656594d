"""Mellum 2 (``families/mellum.py``) on the CPU at a tiny size: the family
is found for the configuration file, its counts against hand counts, the
served path (``EngineClient``, paged KV, prefill then decode through the
cache past the window and past YaRN's original context) against the
float32 reference, the window and YaRN each seen by that comparison, the
expert shares adding up to the uncut layer, the fp8 control judged not
correct, and the two readers this configuration brought."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as C
from chipbench import flops, peaks, replica
from chipbench import run as R
from chipbench import trace as T
from chipbench.run import RunData
from repro.core.request import GenerationRequest, SamplingParams

NAME = "mellum2-12b-a2.5b"
CELL = f"{NAME}.decode_heavy"
SEED = 2**31 + 4242
# float32 on both sides, so routing is the same and only summation order
# differs: served log-probabilities sit within 1e-5 of the reference's at
# this size; without the window or without YaRN the reference moves by 2
# or more
LOGPROB_TOL = 1e-4
# the served tokens' gap reads 0 at this size; the fp8 control's 1 or more.
# A CPU window shared with the test suite's other workers finishes as few
# as 50 greedy tokens, so the sample is judged from 20
TINY_LIMITS = {"max_logit_gap": 0.05, "min_served_tokens": 20}


def conf(name=NAME):
    return json.loads((C.HERE / "configs" / f"{name}.json").read_text())


def tiny(**update):
    """Two periods of the layer pattern (so the layer scan and its stacked
    page arena run as at full depth), d 64, 4 / 2 heads of 16, a window of
    16, 4 of 8 experts held, top 2, YaRN over an original context of 32,
    a 512-token vocabulary, float32."""
    c = conf()
    c.update(name="tiny-mellum", hidden_size=64, head_dim=16,
             num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=8, vocab_size=512, moe_intermediate_size=32,
             num_experts=4, num_experts_per_tok=2,
             published={"num_experts": 8}, held_experts=[0, 4],
             sliding_window=16, torch_dtype="float32",
             layer_types=c["layer_types"][:8],
             mlp_layer_types=c["mlp_layer_types"][:8],
             serve_flags=["--max-batch", "4", "--cache-len", "256",
                          "--kv-layout", "paged", "--kv-page-size", "16",
                          "--prefill-chunk", "32"])
    c["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=4.0)
    c.update(update)
    return c


mellum = C.family(conf())


@pytest.fixture(scope="module", autouse=True)
def own_compile_cache(tmp_path_factory):
    """``run_cell`` turns JAX's persistent cache on: keep it out of the
    checkout, and give later tests in this process the settings back."""
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


def test_family_found_for_the_configuration():
    c = conf()
    assert mellum.__file__ == str(C.HERE / "families" / "mellum.py")
    cfg = mellum.model_config(c)
    from repro.models.model import find_pattern
    assert find_pattern(cfg.layer_kinds()) == (
        (), ("moe@1024", "moe@1024", "moe@1024", "moe"), 7)
    assert (cfg.moe.num_experts, cfg.moe.experts_per_token,
            cfg.moe.held, cfg.moe.expert_d_ff) == (64, 8, (0, 16), 896)
    assert cfg.rope_scaling.factor == 16.0
    assert cfg.rope_scaling.attention_factor == pytest.approx(1.27725887)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.vocab_size) == (2304, 32, 4, 128, 98_304)


def test_counts_by_hand():
    c = conf()
    d, f, v = 2304, 896, 98_304
    attn = 2 * d * 32 * 128 + 2 * d * 4 * 128          # q, o; k, v
    assert attn == 21_233_664
    expert = 3 * d * f                                   # gate, up, down
    layer = attn + d * 64 + 16 * expert + 2 * d          # router, 2 norms
    assert layer == 120_476_160
    assert mellum.total_params(c) == 28 * layer + 2 * v * d + d \
        == 3_826_319_616
    assert mellum.kv_bytes_per_token(c) == 28 * 2 * 4 * 128 * 2 == 57_344
    # one step: a slot at 3,000 and one at 500 positions; the 21 windowed
    # layers read at most 1,024 of them
    ops, nbytes = mellum.paged_attention_cost(c, [3000, 500])
    cells = 7 * (3000 + 500) + 21 * (1024 + 500)
    assert ops == 4 * cells * 32 * 128
    assert nbytes == 2 * cells * 4 * 128 * 2 + 28 * 2 * 2 * 32 * 128 * 2
    # one layer's call: 32 tokens give 32 x 8 x 16 / 64 = 64 rows on
    # average; the weights of the held experts with a row, once
    ops, nbytes = mellum.expert_gmm_cost(c, 64, 16)
    assert ops == 2 * 64 * expert
    assert nbytes == (16 * expert + 2 * 64 * d) * 2
    assert round(16 * expert * 2 * 28 / 1e9, 2) == 5.55   # GB a step
    # an expert no row reached is not read
    assert mellum.expert_gmm_cost(c, 64, 12)[1] == nbytes - 4 * expert * 2
    # a decoded token: matrices, router, 2 held experts expected, attention
    # over 1,500 positions (1,024 in the windowed layers), the head
    per_layer = 2 * (attn + d * 64 + 2 * expert)
    assert mellum.decode_token_flops(c, 1500) == (
        28 * per_layer + 4 * (7 * 1500 + 21 * 1024) * 32 * 128 + 2 * v * d)
    # 3 tokens after 1,022 cached: full layers see 1,023..1,025 keys, the
    # windowed ones 1,023, 1,024, 1,024
    assert mellum.prefill_flops(c, 1022, 3) == (
        3 * 28 * per_layer + 4 * (7 * 3072 + 21 * 3071) * 32 * 128
        + 2 * v * d)


@pytest.fixture(scope="module")
def served():
    """Three greedy requests through ``EngineClient`` on paged KV (pages of
    16, prefill in chunks of 32), their top-5 log-probabilities at every
    decoded position: prompts of 23 to 57 tokens and 30 decoded tokens
    each, so contexts pass the window (16) and YaRN's original context
    (32)."""
    c = tiny()
    client = replica.build(mellum.model_config(c), c["serve_flags"],
                           mellum.engine_params(SEED, c))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist() for n in (40, 23, 57)]
    handles = [client.submit(GenerationRequest(
        prompt=p, sampling=SamplingParams(max_tokens=30, temperature=0.0,
                                          logprobs=True, top_logprobs=5)))
        for p in prompts]
    choices = [h.result(timeout=600).choices[0] for h in handles]
    stats = client.engine.scheduler.stats
    client.stop()
    seqs = [(p + ch.tokens, len(ch.tokens)) for p, ch in zip(prompts, choices)]
    return c, seqs, choices, stats


def _logprob_gap(ref, seqs, choices):
    """Widest gap between a served top-5 log-probability and the
    reference's for the same token and position."""
    gap = 0.0
    for lg, ch in zip(ref.logits(SEED, seqs), choices):
        lp = np.asarray(jax.nn.log_softmax(lg, axis=-1))
        for i, (_, top) in enumerate(ch.logprobs):
            ids, vals = zip(*top)
            gap = max(gap, float(np.abs(lp[i, list(ids)] - vals).max()))
    return gap


def test_served_logits_agree_with_reference(served):
    c, seqs, choices, stats = served
    assert all(len(ch.tokens) == 30 for ch in choices)
    assert max(len(t) for t, _ in seqs) > 2 * 32
    assert _logprob_gap(mellum.Reference(c), seqs, choices) < LOGPROB_TOL
    # the held experts computed rows in the decode steps; each layer's step
    # visits at most the 4 held experts
    assert stats.moe_rows > 0 and stats.device_steps > 0
    assert 0 < stats.moe_experts <= stats.device_steps * 8 * 4


def test_window_is_seen(served):
    """A reference whose windowed layers see the whole context (the plain
    rotation kept) disagrees with what was served."""
    c, seqs, choices, _ = served
    ref = mellum.Reference(c)
    ref.windows = [10**6 if w else 0 for w in ref.windows]
    assert _logprob_gap(ref, seqs, choices) > 100 * LOGPROB_TOL


def test_yarn_is_seen(served):
    """A reference whose full layers take the plain rotation disagrees with
    what was served."""
    c, seqs, choices, _ = served
    ref = mellum.Reference(c)
    ref.yarn = (1.0 / ref.theta ** (np.arange(0, ref.hd, 2) / ref.hd), 1.0)
    assert _logprob_gap(ref, seqs, choices) > 100 * LOGPROB_TOL


def test_expert_shares_add_up_to_the_uncut_layer():
    """The program's expert layer holding experts [0, 4) and the one
    holding [4, 8), each routing over all 8, add up to the reference's
    layer holding all 8; right-padding tokens get nothing."""
    from repro.models import layers as L
    uncut = tiny(num_experts=8, held_experts=[0, 8])
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64), jnp.float32)
    valid = jnp.arange(24)[None, :] < jnp.asarray([[24], [17]])
    total = jnp.zeros_like(x)
    for lo, hi in ((0, 4), (4, 8)):
        share = tiny(held_experts=[lo, hi])
        p = mellum.layer_weights(key, share, jnp.float32)["moe"]
        y, (rows, experts) = L.apply_moe_held(
            p, x, mellum.model_config(share), seq_valid=valid)
        assert 0 < int(rows) < 41 * 2                  # real tokens x k
        assert 0 < int(experts) <= 4
        total = total + (y - x)
    ref = mellum.Reference(uncut)
    p = mellum.layer_weights(key, uncut, jnp.float32)["moe"]
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda r: ref._experts_fn(ref._rms(r, p["ln"]), p))(x)
    want = jnp.where(valid[..., None], want, 0.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1


def _tiny_run():
    c = tiny()
    mix = C.traffic("decode_heavy")
    mix.update(ramp_s=1, pool_size=16, warm_rows=[1, 2], warm_first_tokens=2,
               prompt_tokens={"dist": "lognormal", "median": 64,
                              "sigma": 0.6, "min": 16, "max": 128},
               output_tokens={"dist": "lognormal", "median": 16,
                              "sigma": 0.5, "min": 4, "max": 48})
    wl = {"name": "tiny.decode_heavy", "config": "tiny",
          "traffic": "decode_heavy", "chips": 1}
    metrics = C.metrics_for(C.benchmark(), CELL, "end_to_end")
    return R.run_cell(wl, c, mix, TINY_LIMITS, metrics, SEED, 8.0, False,
                      require_chip=False, log=lambda *a, **k: None)


def test_run_is_correct_and_the_fp8_control_is_not():
    """The cell's run on the tiny configuration keeps the limits; the fp8
    reference put in the program's place, judged by the same rule on a
    fixed sample of 400 greedy tokens (so that what a busy CPU's window
    finished does not decide it), does not."""
    run = _tiny_run()
    assert run["correct"] is True, run["checks"]
    c = tiny()
    rng = np.random.default_rng(3)
    greedy = [SimpleNamespace(prompt=rng.integers(0, 512, n).tolist(),
                              tokens=rng.integers(0, 512, 50).tolist())
              for n in (30, 45, 60, 75, 90, 105, 120, 135)]
    _, control = R.compare(mellum, c, SEED, greedy, [], {}, control="fp8")
    checks = R.judge(dict(control, failed_requests=0, served_tokens=400),
                     TINY_LIMITS)
    assert checks["logit_gap"]["ok"] is False
    assert control["logit_gap"] > 10 * TINY_LIMITS["max_logit_gap"]


def _counters(rows, steps, experts=None):
    out = {"device_steps": steps, "moe_rows": rows}
    if experts is not None:
        out["moe_experts"] = experts
    return out


def test_expert_rows_per_expert_reader():
    read = C.reader("expert_rows_per_expert")
    run = RunData(conf(), {}, [], 0.0, 1.0,
                  {"open": _counters(1000, 10), "close": _counters(
                      1000 + 28 * 16 * 4 * 50, 60)})
    assert read(run) == pytest.approx(4.0)
    # a program without the counter, or a configuration without experts
    old = {"device_steps": 10}
    assert read(RunData(conf(), {}, [], 0.0, 1.0,
                        {"open": old, "close": old})) is None
    assert read(RunData(conf("qwen2-7b"), {}, [], 0.0, 1.0,
                        {"open": _counters(0, 1),
                         "close": _counters(5, 2)})) is None


def _trace(op, staged=()):
    """A decode-block run holding one call of ``op`` (500 ns), an operation
    that reads its output and, before the call, one 50 ns operation for
    each output shape in ``staged``; a prefill run holding another call and
    operations of the same shapes."""
    r = T.Reduced(chips=1)
    r.spans = [("decode#0", 100.0, 200.0)]
    r.modules = [("jit_decode_block", 150.0, 1150.0),
                 ("jit_prefill", 2000.0, 2500.0)]
    r.programs = {"jit_decode_block": 1000.0, "jit_prefill": 500.0}
    name = op.split(" ", 1)[0]
    stage = [f"%dynamic-slice_bitcast_fusion.{i} = bf16[{shape}]{{2,1,0}} "
             f"fusion(%param.1)" for i, shape in enumerate(staged)]
    r.op_events = ([(o, 150.0 + 50 * i, 200.0 + 50 * i)
                    for i, o in enumerate(stage)]
                   + [(op, 400.0, 900.0),
                      (f"%fusion.1 = f32[4]{{0}} f({name})", 900.0, 1100.0),
                      (op.replace(".3", ".4"), 2000.0, 2050.0)]
                   + [(o, 2100.0 + 50 * i, 2150.0 + 50 * i)
                      for i, o in enumerate(stage)])
    r.ops = {o: e - s0 for o, s0, e in r.op_events}
    return r


def test_expert_gmm_roofline_reader():
    """One call in the decode-block run, after the copies that stage its
    expert matrices; the mean call over the window is 64 rows and 14 held
    experts with a row.  The time is the call's and the staging copies'
    (an operation of another shape, or one outside the decode-block run,
    does not count)."""
    read = C.reader("expert_gmm_roofline")
    c = conf()
    pk = peaks.peaks_for("TPU v5 lite")
    calls = 50 * 28                    # 50 decode steps of 28 expert layers
    counters = {"open": _counters(0, 10, 0),
                "close": _counters(64 * calls, 60, 14 * calls)}
    staged = ("16,2304,896", "16,2304,896", "16,896,2304", "32,2304")
    run = RunData(c, pk, [], 0.0, 1.0, counters,
                  trace=_trace("%expert_gmm.3 = bf16[256,2304]{1,0} x",
                               staged),
                  family=mellum)
    least = flops.least_time_s(*mellum.expert_gmm_cost(c, 64, 14), pk)
    assert read(run) == pytest.approx(100.0 * least / 650e-9)
    # without staging copies the call's own time is the whole of it
    run.trace = _trace("%expert_gmm.3 = bf16[256,2304]{1,0} x")
    assert read(run) == pytest.approx(100.0 * least / 500e-9)
    # a trace without the kernel (a program that has none) gives no number
    run.trace = _trace("%paged_attention.3 = y", staged)
    assert read(run) is None
    # nor does a program without the counter
    run.trace = _trace("%expert_gmm.3 = x", staged)
    run.counters = {"open": _counters(0, 10), "close": _counters(5, 60)}
    assert read(run) is None
