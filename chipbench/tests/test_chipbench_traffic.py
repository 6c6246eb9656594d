"""The traffic generator: seeded, the same work for every seed, lengths
where the parameters put them, and mixes found by file name."""
import json
import math
import shutil
from pathlib import Path

import numpy as np
import pytest

from chipbench import cell
from chipbench.traffic import Traffic, draw

HERE = Path(__file__).resolve().parents[1]
SEED = 2**31 + 977          # the driver's seeds pass 32 signed bits


def mix(name):
    return cell.traffic(name)


def schedule(t):
    if t.loop == "open_sessions":
        return [(s.start_s, s.system, s.greedy,
                 [(u.user_tokens, u.output_tokens, u.think_s)
                  for u in s.turns]) for s in t.sessions]
    return [(s.prompt_tokens, s.output_tokens) for s in t.shapes]


@pytest.mark.parametrize("name", ["chat_sessions", "decode_heavy"])
def test_same_seed_same_schedule_and_ids(name):
    a = Traffic(mix(name), SEED, 151_936, 30, 64)
    b = Traffic(mix(name), SEED, 151_936, 30, 64)
    assert schedule(a) == schedule(b)
    assert a.ids(50, 3, 7) == b.ids(50, 3, 7)
    c = Traffic(mix(name), SEED + 1, 151_936, 30, 64)
    assert schedule(a) != schedule(c)
    assert a.ids(50, 3, 7) != c.ids(50, 3, 7)


def test_seeds_reorder_the_same_sessions():
    a = Traffic(mix("chat_sessions"), 1, 151_936, 30, 64)
    b = Traffic(mix("chat_sessions"), 2, 151_936, 30, 64)
    key = lambda t: sorted((s.greedy, [(u.user_tokens, u.output_tokens,
                                        u.think_s) for u in s.turns])
                           for s in t.sessions)
    assert key(a) == key(b)
    assert [s.start_s for s in a.sessions] != [s.start_s for s in b.sessions]
    # the arrival gaps are one set, reordered: the spans differ by at most
    # the one gap each seed leaves out (the first)
    p = mix("chat_sessions")
    n = math.ceil(p["session_rate_per_s"] * (p["ramp_s"] + 30))
    assert len(a.sessions) == len(b.sessions) == n
    span = lambda t: t.sessions[-1].start_s
    gap = max(np.diff([s.start_s for s in a.sessions]).max(),
              np.diff([s.start_s for s in b.sessions]).max())
    assert abs(span(a) - span(b)) <= gap


def test_closed_loop_pool_is_a_permutation():
    a = Traffic(mix("decode_heavy"), 5, 152_064, 30, 32)
    b = Traffic(mix("decode_heavy"), 6, 152_064, 30, 32)
    assert a.clients == 32
    assert sorted(schedule(a)) == sorted(schedule(b))
    assert schedule(a) != schedule(b)
    assert a.shape(len(a.shapes)) == a.shape(0)


@pytest.mark.parametrize("spec,median", [
    ({"dist": "lognormal", "median": 96, "sigma": 1.0, "min": 8,
      "max": 1024}, 96),
    ({"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
      "max": 768}, 192),
    ({"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 128,
      "max": 2048}, 1024),
    ({"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 64,
      "max": 1024}, 512),
])
def test_lognormal_quantiles(spec, median):
    x = draw(np.random.default_rng(0), spec, 20_000)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert abs(np.median(x) / median - 1) < 0.03
    # the 84th percentile of a lognormal lies one sigma above the median,
    # unless the clip cuts it
    q84 = np.quantile(x, 0.8413)
    want = min(spec["max"], median * math.exp(spec["sigma"]))
    assert abs(q84 / want - 1) < 0.05


def test_turns_and_think_time():
    rng = np.random.default_rng(1)
    turns = draw(rng, {"dist": "geometric", "mean": 4, "min": 1, "max": 8},
                 20_000)
    assert turns.min() == 1 and turns.max() == 8
    assert 3.0 < turns.mean() < 3.6          # mean 4 before the clip at 8
    think = draw(rng, {"dist": "exponential", "mean": 3.0}, 20_000)
    assert abs(think.mean() / 3.0 - 1) < 0.03
    assert think.dtype.kind == "f"


def test_chat_history_fits_the_context_limit():
    t = Traffic(mix("chat_sessions"), 9, 151_936, 30, 64)
    assert t.context_limit == 4096
    assert len(t.system_prompt(0)) == 1024
    assert t.system_prompt(0) != t.system_prompt(1)
    assert t.user_message(3, 0, 17) == t.user_message(3, 0, 17)
    greedy = sum(s.greedy for s in t.sessions)
    assert greedy == round(len(t.sessions) * 0.25)


def test_a_new_mix_is_found_by_name(tmp_path):
    """A traffic file added under chipbench/traffic/ is found by its name,
    with no other file edited."""
    root = tmp_path / "chipbench"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    params = mix("decode_heavy")
    params.update(clients=3, pool_size=5)
    (root / "traffic" / "tiny_batch.json").write_text(json.dumps(params))
    found = cell.traffic("tiny_batch", here=root)
    t = Traffic(found, 4, 1000, 10, 64)
    assert t.clients == 3 and len(t.shapes) == 5
    with pytest.raises(FileNotFoundError):
        cell.traffic("tiny_batch")
