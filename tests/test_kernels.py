"""Kernel validation: every Pallas kernel (interpret=True on CPU) and every
production jnp path against the pure-jnp oracles in kernels/ref.py, swept
over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas, quantize_int8

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(key, b, sq, skv, h, hkv, d, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, sq, h, d), dtype)
    k = jax.random.normal(k2, (b, skv, hkv, d), dtype)
    v = jax.random.normal(k3, (b, skv, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("b,sq,skv,h,hkv,d", [
    (1, 16, 16, 4, 4, 32),      # MHA square
    (2, 32, 64, 8, 2, 16),      # GQA, kv longer
    (2, 24, 40, 6, 3, 64),      # non-power-of-two (padding path)
    (1, 128, 128, 2, 1, 64),    # multiple q/k blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 24])
def test_flash_pallas_vs_ref(b, sq, skv, h, hkv, d, dtype, window):
    q, k, v = _qkv(jax.random.PRNGKey(0), b, sq, skv, h, hkv, d, dtype)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("q_offset", [0, 7])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_jnp_vs_ref(q_offset, causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 24, 48, 8, 2, 32, jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, q_offset=q_offset)
    got = ops._flash_jnp(q, k, v, causal=causal, window=0,
                         q_offset=q_offset, chunk=16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-3)


def test_flash_causal_blocks_schedule():
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 64, 64, 4, 2, 32, jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    got = ops._flash_jnp_causal_blocks(q, k, v, window=0, q_offset=0, chunk=16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-3)
    # with sliding window
    want = ref.flash_attention_ref(q, k, v, causal=True, window=20)
    got = ops._flash_jnp_causal_blocks(q, k, v, window=20, q_offset=0,
                                       chunk=16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("b,s,h,hkv,d", [
    (2, 48, 8, 2, 32),
    (1, 16, 4, 4, 64),
    (3, 100, 6, 2, 16),         # padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_pallas_vs_ref(b, s, h, hkv, d, dtype):
    key = jax.random.PRNGKey(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (b, h, d), dtype)
    kc = jax.random.normal(k2, (b, s, hkv, d), dtype)
    vc = jax.random.normal(k3, (b, s, hkv, d), dtype)
    lengths = jax.random.randint(k4, (b,), 1, s + 1)
    valid = jnp.arange(s)[None] < lengths[:, None]
    want = ref.decode_attention_ref(q, kc, vc, valid)
    got = decode_attention_pallas(q, kc, vc, valid, block_k=16,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)
    got_jnp = ops._decode_jnp(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


def test_decode_ring_buffer_semantics():
    """Ring-valid mask: when pos >= cache_len every slot is live."""
    b, s, h, d = 1, 8, 2, 16
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (b, h, d))
    kc = jax.random.normal(key, (b, s, 1, d))
    vc = jax.random.normal(key, (b, s, 1, d))
    all_valid = jnp.ones((b, s), bool)
    want = ref.decode_attention_ref(q, kc, vc, all_valid)
    got = ops.decode_attention(q, kc, vc, all_valid)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("m,k,n", [(16, 64, 32), (24, 96, 40), (8, 128, 128)])
def test_quant_matmul(m, k, n):
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(6), (k, n)) * 0.2
    wq, sc = quantize_int8(w)
    want = ref.quant_matmul_ref(x, wq, sc)
    got = quant_matmul_pallas(x, wq, sc, block_m=8, block_n=128, block_k=32,
                              interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-3)
    # quantisation error itself is bounded
    dense = x @ w
    err = np.abs(np.asarray(want - dense)).max()
    assert err < 0.5, f"int8 quantisation error too large: {err}"


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 64, 4, 16, 2, 8, 16),
    (1, 32, 2, 8, 1, 4, 32),
    (2, 48, 4, 16, 4, 8, 16),   # padding path (48 % 32 != 0 with chunk 32)
])
def test_ssd_chunked_vs_ref(b, s, h, p, g, n, chunk):
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(keys[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)))
    a = -jnp.abs(jax.random.normal(keys[2], (h,)))
    bm = jax.random.normal(keys[3], (b, s, g, n))
    cm = jax.random.normal(keys[4], (b, s, g, n))
    st0 = jax.random.normal(keys[5], (b, h, p, n))
    want_y, want_s = ref.ssd_ref(x, dt, a, bm, cm, init_state=st0)
    got_y, got_s = ops.ssd(x, dt, a, bm, cm, init_state=st0, chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4, rtol=1e-3)


def test_ssd_decode_step_matches_scan():
    """Running T single decode steps == one chunked pass over T tokens."""
    b, s, h, p, g, n = 2, 12, 4, 8, 2, 4
    keys = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(keys[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)))
    a = -jnp.abs(jax.random.normal(keys[2], (h,)))
    bm = jax.random.normal(keys[3], (b, s, g, n))
    cm = jax.random.normal(keys[4], (b, s, g, n))
    want_y, want_s = ops.ssd(x, dt, a, bm, cm, chunk=4)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y, state = ops.ssd_decode_step(x[:, t], dt[:, t], a, bm[:, t],
                                       cm[:, t], state)
        ys.append(y)
    got_y = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(state, want_s, atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------- #
# grouped expert MLP
# --------------------------------------------------------------------------- #
def _experts(key, e, d, f, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (e, d, f), dtype) / d ** 0.5,
            jax.random.normal(k2, (e, d, f), dtype) / d ** 0.5,
            jax.random.normal(k3, (e, f, d), dtype) / f ** 0.5)


@pytest.mark.parametrize("sizes", [
    (37, 0, 150, 3, 0, 61),          # uneven, empty experts, groups
    (0, 0, 0, 0, 0, 0),              #   straddling 128-row tiles
    (300, 0, 0, 0, 0, 0),            # one expert takes every row
    (0, 0, 0, 0, 0, 1),              # a single row, last expert
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gmm_pallas_vs_dense(sizes, dtype):
    """Rows sorted by expert, then padding rows that no expert takes: the
    kernel (and the CPU path) give each grouped row its own expert's MLP,
    against a dense einsum of every expert on every row."""
    from repro.kernels.expert_gmm import expert_gmm_pallas
    m, d, f = 320, 256, 256
    key = jax.random.PRNGKey(sum(sizes))
    x = jax.random.normal(key, (m, d), dtype)
    w = _experts(jax.random.fold_in(key, 1), len(sizes), d, f, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    want = ref.expert_gmm_ref(x, *w, gs)
    rows = sum(sizes)                          # rows past them: undefined
    tol = ATOL[dtype] * (1 if dtype == jnp.float32 else 2)
    for got in (expert_gmm_pallas(x, *w, gs, interpret=True),
                ops.expert_gmm(x, *w, gs)):
        assert got.shape == (m, d) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                                   np.asarray(want[:rows]), atol=tol,
                                   rtol=tol)
    assert not np.asarray(want[rows:]).any()


def test_expert_gmm_visits_each_grouped_tile_once():
    """The visit list: one (expert, tile) per tile a group touches, in
    order, then repeats of the last real visit."""
    from repro.kernels.expert_gmm import visits
    expert, tile, start, end, count = visits(
        jnp.asarray([37, 0, 150, 3], jnp.int32), 3, 128)
    assert int(count[0]) == 4          # e0: t0; e2: t0 and t1; e3: t1
    assert expert.tolist() == [0, 2, 2, 3, 3, 3]
    assert tile.tolist() == [0, 0, 1, 1, 1, 1]
    assert start.tolist() == [0, 37, 37, 187]
    assert end.tolist() == [37, 37, 187, 190]
