"""Compile rehearsal: every Pallas kernel of the serving path, compiled by the
TPU compiler for a described (not attached) v5e chip at real model widths.

Interpret mode checks what a kernel computes, not whether Mosaic accepts
its block shapes, VMEM use or in-kernel ops; this file checks the latter
with no chip.  Widths: qwen2-0.5b (H=14, Hkv=2, head_dim 64, bf16, cache
512, page 16) and one head_dim-128 shape (H=16, Hkv=8).

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas

WIDTHS = {                       # name -> (heads, kv heads, head_dim)
    "qwen2-0.5b": (14, 2, 64),
    "hd128": (16, 8, 128),
}
BATCH, CACHE, PAGE = 8, 512, 16


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
    arena = _sds(one_chip, (n, PAGE, hkv, d), jnp.int8 if int8 else
                 jnp.bfloat16)
    table = _sds(one_chip, (BATCH, pages), jnp.int32)
    pos = _sds(one_chip, (BATCH,), jnp.int32)
    if int8:
        scale = _sds(one_chip, (n, PAGE, hkv), jnp.float32)
        _compile(lambda q, k, v, t, p, ks, vs: paged_attention_pallas(
            q, k, v, t, p, k_scale=ks, v_scale=vs),
            q, arena, arena, table, pos, scale, scale)
    else:
        _compile(paged_attention_pallas, q, arena, arena, table, pos)


def test_quant_matmul_compiles(one_chip):
    x = _sds(one_chip, (BATCH, 896))
    w = _sds(one_chip, (896, 4864), jnp.int8)
    s = _sds(one_chip, (4864,), jnp.float32)
    _compile(quant_matmul_pallas, x, w, s)
