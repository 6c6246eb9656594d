"""The held experts' SwiGLU MLP over rows sorted by expert — Pallas TPU
kernel (a grouped matmul: each group of rows meets its own expert's weights).

Rows ``x`` [M, D] arrive sorted by expert: ``group_sizes[e]`` rows for held
expert ``e``, in expert order, then rows no held expert takes.  A *visit* is
one (expert, row tile) pair the groups touch; visit ``v`` multiplies row
tile ``tiles[v]`` by expert ``experts[v]``'s weights, ``tf`` columns of the
expert's hidden width per grid step::

    acc += (silu(x @ w_gate[:, f]) * (x @ w_up[:, f])) @ w_down[f, :]

and at the last step stores the rows of the tile that belong to the expert.
So an expert whose rows fit one tile streams its weights once, however few
rows it has, an expert with no rows is never visited, and nothing is
computed for rows past the held groups.  The grid has a static number of
visits (``tiles + experts - 1``, the most the groups can touch); visits past
the last real one repeat the block indices of its last step, so the pipeline
copies nothing for them, and ``pl.when`` skips their compute.  Output rows outside every
group are left undefined: the caller selects the rows it routed.

Validated against ``ref.expert_gmm_ref`` (a dense einsum per expert) in
interpret mode on the CPU; compiles for TPU v5e (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

TILE_ROWS = 128        # rows per tile: one MXU pass, weights streamed once
TILE_HIDDEN = 128      # expert hidden columns per grid step


def visits(group_sizes: jax.Array, tiles: int, tm: int):
    """For each of the ``tiles + E - 1`` visits: its expert and row tile
    (past the last real visit: the last real one's), the row range of each
    expert's group, and how many visits are real."""
    e = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    n = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(n)
    count = visit_end[-1]
    v = jnp.minimum(jnp.arange(tiles + e - 1), jnp.maximum(count - 1, 0))
    expert = jnp.minimum(jnp.sum(visit_end[None, :] <= v[:, None], axis=1),
                         e - 1)
    tile = first[expert] + v - (visit_end - n)[expert]
    return (expert.astype(jnp.int32), tile.astype(jnp.int32),
            starts.astype(jnp.int32), ends.astype(jnp.int32),
            count.reshape(1).astype(jnp.int32))


def _kernel(expert_ref, tile_ref, start_ref, end_ref, count_ref, x_ref,
            wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm, nf):
    v = pl.program_id(0)
    j = pl.program_id(1)
    real = v < count_ref[0]

    @pl.when(real & (j == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real)
    def _accumulate():
        x = x_ref[...]                                         # [tm, D]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)               # [tm, tf]
        acc_ref[...] += jnp.dot(h, wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(real & (j == nf - 1))
    def _store():
        e = expert_ref[v]
        rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= start_ref[e]) & (rows < end_ref[e])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                               o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm_pallas(
    x: jax.Array,                   # [M, D] rows sorted by expert
    w_gate: jax.Array,              # [E, D, F]
    w_up: jax.Array,                # [E, D, F]
    w_down: jax.Array,              # [E, F, D]
    group_sizes: jax.Array,         # [E] int32, sum <= M
    *,
    interpret: bool = False,
) -> jax.Array:
    """[M, D] in ``x``'s dtype: each grouped row's expert MLP output (the
    rows past the groups undefined)."""
    m, d = x.shape
    e, _, f = w_gate.shape
    tm = min(TILE_ROWS, -(-m // 8) * 8)
    tf = min(TILE_HIDDEN, f)
    assert f % tf == 0, (f, tf)
    tiles = -(-m // tm)
    xp = x if m == tiles * tm else jnp.pad(x, ((0, tiles * tm - m), (0, 0)))
    meta = visits(group_sizes.astype(jnp.int32), tiles, tm)

    nf = f // tf

    # index maps see (grid idxs..., *scalar_prefetch_refs); a visit past the
    # last real one stays on the last real step's weight block
    def x_map(v, j, expert, tile, *_):
        return (tile[v], 0)

    def col(v, j, count):
        return jnp.where(v < count[0], j, nf - 1)

    def wi_map(v, j, expert, tile, start, end, count):
        return (expert[v], 0, col(v, j, count))

    def wd_map(v, j, expert, tile, start, end, count):
        return (expert[v], col(v, j, count), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(meta),
        grid=(tiles + e - 1, nf),
        in_specs=[pl.BlockSpec((tm, d), x_map),
                  pl.BlockSpec((1, d, tf), wi_map),
                  pl.BlockSpec((1, d, tf), wi_map),
                  pl.BlockSpec((1, tf, d), wd_map)],
        out_specs=pl.BlockSpec((tm, d), x_map),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],    # accumulator
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, nf=nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * tm, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="expert_gmm",
        interpret=interpret,
    )(*meta, xp, w_gate, w_up, w_down)
    return out if m == tiles * tm else out[:m]
