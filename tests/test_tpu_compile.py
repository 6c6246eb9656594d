"""Compile rehearsal: every Pallas kernel of the serving path, compiled by the
TPU compiler for a described (not attached) v5e chip at real model widths.

Interpret mode checks what a kernel computes, not whether Mosaic accepts
its block shapes, VMEM use or in-kernel ops; this file checks the latter
with no chip.  Widths: qwen2-0.5b (H=14, Hkv=2, head_dim 64, bf16, cache
512, page 16) and one head_dim-128 shape (H=16, Hkv=8); the windowed
paged kernel and the grouped expert kernel at Mellum 2 widths.  Two whole
programs are compiled too: the paged ``decode_block`` at Qwen2-7B attention
widths, to pin what the TPU compiler does with the stacked page arena, and
Mellum 2's at its published widths, depth and arena, to pin that it fits
and that its kernels read the arena the same way.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ModelConfig, MoEConfig, RopeScaling
from repro.core.engine import InferenceEngine
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.expert_gmm import expert_gmm_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.models import build_model

WIDTHS = {                       # name -> (heads, kv heads, head_dim)
    "qwen2-0.5b": (14, 2, 64),
    "hd128": (16, 8, 128),
}
BATCH, CACHE, PAGE = 8, 512, 16
LAYERS = 3                       # a stacked page arena's layers


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("seq", [512, 200])
def test_flash_attention_compiles(one_chip, width, seq):
    h, hkv, d = WIDTHS[width]
    q = _sds(one_chip, (2, seq, h, d))
    kv = _sds(one_chip, (2, seq, hkv, d))
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             q, kv, kv)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("cache", [CACHE, 1100])
def test_decode_attention_compiles(one_chip, width, cache):
    h, hkv, d = WIDTHS[width]
    q = _sds(one_chip, (BATCH, h, d))
    kv = _sds(one_chip, (BATCH, cache, hkv, d))
    valid = _sds(one_chip, (BATCH, cache), jnp.bool_)
    _compile(decode_attention_pallas, q, kv, kv, valid)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("int8", [False, True])
def test_paged_attention_compiles(one_chip, width, int8):
    h, hkv, d = WIDTHS[width]
    pages = CACHE // PAGE
    n = BATCH * pages + 1
    q = _sds(one_chip, (BATCH, h, d))
    arena = _sds(one_chip, (LAYERS, n, PAGE, hkv * d), jnp.int8 if int8 else
                 jnp.bfloat16)
    table = _sds(one_chip, (BATCH, pages), jnp.int32)
    pos = _sds(one_chip, (BATCH,), jnp.int32)
    layer = _sds(one_chip, (1,), jnp.int32)
    if int8:
        scale = _sds(one_chip, (LAYERS, n, PAGE, hkv), jnp.float32)
        _compile(lambda q, k, v, t, p, i, ks, vs: paged_attention_pallas(
            q, k, v, t, p, i, k_scale=ks, v_scale=vs),
            q, arena, arena, table, pos, layer, scale, scale)
    else:
        _compile(paged_attention_pallas, q, arena, arena, table, pos, layer)


@pytest.mark.parametrize("window", [1024, 1000])
def test_windowed_paged_attention_compiles(one_chip, window):
    """Mellum 2's windowed layers: 32 / 4 heads of 128, pages of 128, a
    window on a page boundary and one inside a page."""
    b, pages = 32, 24
    q = _sds(one_chip, (b, 32, 128))
    arena = _sds(one_chip, (LAYERS, b * pages + 2, 128, 4 * 128))
    table = _sds(one_chip, (b, pages), jnp.int32)
    pos = _sds(one_chip, (b,), jnp.int32)
    layer = _sds(one_chip, (1,), jnp.int32)
    _compile(lambda q, k, v, t, p, i: paged_attention_pallas(
        q, k, v, t, p, i, window=window), q, arena, arena, table, pos, layer)


@pytest.mark.parametrize("rows", [256, 4096])
def test_expert_gmm_compiles(one_chip, rows):
    """16 held experts of Mellum 2 (d 2,304, width 896): a decode step's
    32 x 8 token-expert pairs and a prefill wave's."""
    x = _sds(one_chip, (rows, 2304))
    wi = _sds(one_chip, (16, 2304, 896))
    wd = _sds(one_chip, (16, 896, 2304))
    sizes = _sds(one_chip, (16,), jnp.int32)
    _compile(expert_gmm_pallas, x, wi, wi, wd, sizes)


def test_quant_matmul_compiles(one_chip):
    x = _sds(one_chip, (BATCH, 896))
    w = _sds(one_chip, (896, 4864), jnp.int8)
    s = _sds(one_chip, (4864,), jnp.float32)
    _compile(quant_matmul_pallas, x, w, s)


def _instructions(hlo: str):
    """(computation, name, shape, opcode, line) of every instruction of an
    HLO module's text, and the names of the fused computations."""
    out, fused, comp = [], set(), None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\(", line)
        if ins:
            out.append((comp,) + ins.groups() + (line,))
            fused.update(re.findall(r"calls=%([\w.-]+)", line)
                         if ins.group(3) == "fusion" else ())
    return out, fused


def _arena_read_in_place(compiled, arena):
    """Pin what the TPU compiler does with a stacked arena leaf
    ``[L, N, ps, Hkv * D]`` in a compiled paged ``decode_block``: no op,
    fused or not, holds one layer of it (``[N, ps, ...]`` or
    ``[1, N, ps, ...]``: no slice, no relayout, no prefetch copy); outside
    fusions the stack is only a parameter, a tuple element, a bitcast or the
    output of an in-place cell scatter; and every ``paged_attention`` call
    reads the k and v stacks themselves.  Returns the number of calls."""
    lay, n, ps, lanes = arena["k"].shape
    stacked = f"bf16[{lay},{n},{ps},{lanes}]"
    ins, fused = _instructions(compiled.as_text())
    shapes = {name: shape.split("{")[0] for _, name, shape, _, _ in ins}
    roots = {c: op for c, _, _, op, line in ins if "ROOT" in line}
    calls = 0
    for comp, name, shape, op, line in ins:
        assert not re.match(rf"\w+\[(1,)?{n},{ps},", shape), line[:160]
        if comp in fused:
            continue
        kind = (roots[re.search(r"calls=%([\w.-]+)", line).group(1)]
                if op == "fusion" else op)
        if shape.split("{")[0] == stacked:
            assert op in ("parameter", "get-tuple-element", "bitcast") or \
                kind == "scatter", line[:160]
        if name.startswith("paged_attention"):
            args = re.search(r"custom-call\((.*?)\)", line).group(1)
            reads = [shapes.get(a.strip().lstrip("%"))
                     for a in re.sub(r"/\*.*?\*/", "", args).split(",")]
            assert reads.count(stacked) == 2, (reads, line[:200])
            calls += 1
    return calls


def test_paged_decode_block_writes_the_arena_in_place(one_chip, monkeypatch):
    """The paged ``decode_block`` at Qwen2-7B attention widths (28 / 4 heads,
    head_dim 128, pages of 128), compiled for a v5e with the Pallas kernels
    selected as on the chip.  The stacked arena ``[L, N, ps, Hkv * D]``
    rides the layer scan's carry: it is only ever written by the in-place
    cell scatters (no copy, no restacking dynamic-update-slice, no fresh
    buffer), and ``paged_attention`` reads it there, the layer index among
    its scalar operands, so no layer of it is sliced out or relaid out.
    Temporaries stay below 150 MB: four layers of the benchmark's 514
    pages, where one per-layer slice and relayout of a k / v leaf pair
    alone take 134 MB."""
    cfg = ModelConfig(name="qwen2-7b-attn", family="dense", num_layers=4,
                      d_model=3584, num_heads=28, num_kv_heads=4,
                      head_dim=128, d_ff=512, vocab_size=1024, qkv_bias=True,
                      dtype="bfloat16")
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params=params, max_batch=8, cache_len=1024,
                          kv_layout="paged", kv_page_size=128,
                          kv_num_pages=514)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    args = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        (params, eng.pool.cache, eng.state))
    compiled = eng._decode_block_fn.lower(
        *args, num_steps=2, want_logprobs=False).compile()
    arena = eng.pool.cache["block"]["pos0"]
    assert arena["k"].shape == (4, 514, 128, 4 * 128)
    assert _arena_read_in_place(compiled, arena) == 1   # one scan body
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 150e6, temps


MELLUM = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe", num_layers=28, d_model=2304,
    num_heads=32, num_kv_heads=4, head_dim=128, d_ff=7168,
    vocab_size=98_304, rope_theta=500_000.0, rms_eps=1e-6,
    layer_windows=(1024, 1024, 1024, 0),
    rope_scaling=RopeScaling(factor=16.0,
                             original_max_position_embeddings=8192,
                             beta_fast=32.0, beta_slow=1.0,
                             attention_factor=1.2772588722239782),
    moe=MoEConfig(num_experts=64, experts_per_token=8, expert_d_ff=896,
                  held=(0, 16)),
    dtype="bfloat16")


def test_mellum_decode_block_fits_one_chip(one_chip, monkeypatch):
    """Mellum 2's paged ``decode_block`` (K = 8) at published widths and
    depth, 16 held experts of 64, 32 slots over 514 pages of 128: both
    kernels are in it, and weights plus arena (11.43 GB of arguments) and
    temporaries (0.76 GB) leave a v5e room for a prefill wave; chipbench's
    configuration file records both (its 0.82 GB of temporaries predate
    the kernel's in-place read).  Built like the engine at full size
    but without one, so nothing is allocated on the CPU."""
    import types
    from repro.core.kv_cache import init_decode_state
    from repro.core.paged_kv import PagedKVPool
    params = jax.eval_shape(build_model(MELLUM).init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: PagedKVPool(
        MELLUM, 32, 3072, page_size=128, num_pages=514).cache)
    state = jax.eval_shape(lambda: init_decode_state(32, 0, 8,
                                                     moe_counts=True))
    engine = types.SimpleNamespace(model=build_model(MELLUM),
                                   media_kind="none", max_top_logprobs=5,
                                   _paged=True)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    args = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                        (params, cache, state))
    compiled = InferenceEngine._build_decode_block_fn(engine).lower(
        *args, num_steps=8, want_logprobs=False).compile()
    text = compiled.as_text()
    assert "expert_gmm" in text and "paged_attention" in text
    # one call per position of the 3:1 pattern (each position its own
    # stack of 7 layers), windowed and full, each reading its stack in place
    assert _arena_read_in_place(compiled, cache["block"]["pos0"]) == 4
    mem = compiled.memory_analysis()
    assert 11.0e9 < mem.argument_size_in_bytes < 11.6e9
    assert mem.temp_size_in_bytes < 1.2e9
