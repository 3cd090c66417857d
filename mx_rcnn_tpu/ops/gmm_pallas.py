"""Pallas TPU kernels for the grouped matrix product of the held experts.

``grouped_matmul(lhs, rhs, group_sizes)`` is ``lax.ragged_dot`` (the oracle
of ``tests/test_grouped_matmul.py``, and what ``ops/moe.py`` runs off the
TPU): the ``m`` rows of ``lhs`` are sorted by group, ``group_sizes[g]`` of
them belong to group ``g`` and are multiplied by ``rhs[g]``; rows past the
last group give zeros.  Operands in ``lhs``'s dtype (bfloat16 in the train
step), accumulation and result in float32.

The rows are cut into tiles of ``tm``.  A *visit* is one (row tile, group)
pair in which the group has rows — at most ``m / tm + groups - 1`` of them,
a static bound; the list is made outside the kernel (``_schedule``) and
reaches it through scalar prefetch, so each grid step's block indices are
read from it: the tile's rows, and the ``rhs`` block of the visit's group.
A tile that holds the boundary of two groups is visited once per group
with the other group's rows masked; a tile inside one group is stored
without a mask.  The contraction dimension is held whole in VMEM (no
accumulation across grid steps, and 1856 columns need no padding); the
outer grid dimension walks the output's column tiles, so ``rhs`` moves
once per column tile and group, not once per visit.  A weight the TPU
compiler keeps with ``k`` minor is handed over transposed (``_k_minor``).

Three products, one schedule:

* forward ``out = gmm(lhs, rhs)``;
* the rows' cotangent ``d lhs = gmm(d out, rhs^T)``, the same kernel
  contracting ``rhs``'s last dimension;
* the weights' cotangent ``d rhs[g] = lhs[rows of g]^T . d out[rows of g]``
  (``_tgmm``), whose float32 output block of a group stays in VMEM while
  the group's visits accumulate into it.  Every group is visited at least
  once, so an expert with no rows gets zeros, not unwritten memory.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The tiles follow the shapes the call sees.  Readings: one v5e chip, device
# ms a product over m = 12288 rows and 8 groups (PR 35; 0.62 ms is the MXU's
# peak for any of the three; ``lax.ragged_dot`` took 4.0-5.5).
#
# Rows (``_ROWS``), the forward's form at its best column tile, 128 / 256 /
# 512 / 1024 rows: k 2688 -> n 1856 (rhs transposed) 0.853 / 0.828 / 0.884 /
# 1.062; k 1856 -> n 2688 0.857 / 0.839 / 0.885 / 1.068.
_ROWS = 256
# Output columns (``_col_tile``): the fewest tiles of at most ``_COLS``,
# evened out and rounded up to 128 lanes, the last one partial: 640 for
# 1856 and 896 for 2688.  At 256 rows, n 1856 in tiles of 384 / 640 / 1024:
# 0.873 / 0.828 / 0.853 (whole, at 512 rows: 1.416); n 2688 in 384 / 896 /
# 1408: 0.949 / 0.839 / 0.851 (whole, at 512 rows: 1.444).
_COLS = 896
# The weights' cotangent keeps its (k, n) float32 block whole while it
# fits ``_BLOCK_BYTES``, else cuts its columns, then its rows, as above.
# 1856 x 2688 at 256 rows: whole (20 MB) 0.920; columns in 896 / 1408
# 0.981 / 0.979; rows in 640 1.005; 640 x 896 1.17 (at 512 rows; whole
# there 1.217).
_BLOCK_BYTES = 24 * 2 ** 20
# the blocks of one grid step twice (the next step's are fetched while
# this one runs) and the product before it is added: 65 MB for that whole
# block; a kernel gets 16 MiB unasked on the v5e, whose core has 128 MiB
_VMEM_LIMIT = 100 * 2 ** 20


def _col_tile(n: int) -> int:
    tiles = -(-n // _COLS)
    return -(-n // (tiles * 128)) * 128


def _block(k: int, n: int) -> Tuple[int, int]:
    """The (k, n) block of the weights' cotangent."""
    if 4 * k * n <= _BLOCK_BYTES:
        return k, n
    tn = _col_tile(n)
    return (k if 4 * k * tn <= _BLOCK_BYTES else _col_tile(k)), tn


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def _schedule(group_sizes: jax.Array, m: int, tm: int):
    """The visits, each (V,) int32 with V = m / tm + groups - 1: the group,
    the row tile, and the group's rows ``[lo, hi)``.  Groups in order, a
    group's tiles in order, so a tile's visits are consecutive and so are a
    group's.  A group with no rows has one visit with no rows in it; the
    last group's visits run on to the last tile, so every tile is visited
    whatever the sizes sum to.  Unused visits repeat the last tile and
    group with no rows."""
    e, tiles = group_sizes.shape[0], m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    reach = ends.at[-1].set(m)
    count = jnp.maximum(-(-reach // tm) - starts // tm, 1)
    v = tiles + e - 1
    group = jnp.repeat(jnp.arange(e, dtype=jnp.int32), count,
                       total_repeat_length=v)
    nth = jnp.arange(v, dtype=jnp.int32) - (jnp.cumsum(count) - count)[group]
    tile = jnp.minimum(starts[group] // tm + nth, tiles - 1)
    used = jnp.arange(v) < count.sum()
    return (group, tile.astype(jnp.int32),
            jnp.where(used, starts[group], 0).astype(jnp.int32),
            jnp.where(used, ends[group], 0).astype(jnp.int32))


def _rows_of_visit(tile_ref, lo_ref, hi_ref, v, tm):
    """(whole, some, mine): the visit's group owns the whole tile; owns
    some row of it; (tm, 1) bool, the rows it owns."""
    lo, hi = lo_ref[v], hi_ref[v]
    row0 = tile_ref[v] * tm
    whole = jnp.logical_and(lo <= row0, hi >= row0 + tm)
    some = jnp.maximum(lo, row0) < jnp.minimum(hi, row0 + tm)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return whole, some, jnp.logical_and(rows >= lo, rows < hi)


def _changed(ref, v):
    """The visit is the first of its tile (or group): ``ref[v]`` differs
    from the visit before."""
    return jnp.logical_or(v == 0, ref[jnp.maximum(v - 1, 0)] != ref[v])


def _gmm_kernel(group_ref, tile_ref, lo_ref, hi_ref, lhs_ref, rhs_ref,
                out_ref, *, tm: int, transpose_rhs: bool):
    """One visit: lhs_ref (tm, k) the tile's rows; rhs_ref (k, tn), or
    (tn, k) with ``transpose_rhs``, the group's block; out_ref (tm, tn),
    resident in VMEM across the tile's visits."""
    del group_ref
    v = pl.program_id(1)
    whole, some, mine = _rows_of_visit(tile_ref, lo_ref, hi_ref, v, tm)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def product():
        return jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                   preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _store():
        out_ref[...] = product().astype(out_ref.dtype)

    # the window holds nothing yet on a tile's first visit: rows that no
    # group owns (past the last group, or the wrapper's padding) are zeros
    @pl.when(jnp.logical_and(_changed(tile_ref, v), jnp.logical_not(whole)))
    def _clear():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_and(some, jnp.logical_not(whole)))
    def _merge():
        out_ref[...] = jnp.where(
            mine, product(), out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _tgmm_kernel(group_ref, tile_ref, lo_ref, hi_ref, lhs_ref, rhs_ref,
                 out_ref, *, tm: int):
    """One visit: lhs_ref (tm, tk), rhs_ref (tm, tn) the tile's rows of
    both operands; out_ref (tk, tn) float32, the group's block, resident
    across the group's visits."""
    v = pl.program_id(2)
    whole, some, mine = _rows_of_visit(tile_ref, lo_ref, hi_ref, v, tm)
    dims = (((0,), (0,)), ((), ()))

    @pl.when(_changed(group_ref, v))
    def _clear():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(whole)
    def _add():
        out_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(some, jnp.logical_not(whole)))
    def _add_masked():
        def own(ref):   # the select in float32: the v5e's vector unit has no bfloat16
            x = ref[...]
            return jnp.where(mine, x.astype(jnp.float32), 0.0).astype(x.dtype)

        out_ref[...] += jax.lax.dot_general(
            own(lhs_ref), own(rhs_ref), dims,
            preferred_element_type=jnp.float32)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=(
    "tm", "tn", "transpose_rhs", "out_dtype", "interpret"))
def _gmm(lhs, rhs, schedule, *, tm, tn, transpose_rhs, out_dtype, interpret):
    """lhs (m, k) with tm | m; rhs (e, k, n), or (e, n, k) with
    ``transpose_rhs``; -> (m, n) ``out_dtype``."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, k),
                                lambda j, v, g, t, lo, hi: (g[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tn),
                                lambda j, v, g, t, lo, hi: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), schedule[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, g, t, lo, hi: (t[v], 0)),
                rhs_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, lo, hi: (t[v], j))),
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * pl.cdiv(n, tn)
                            + rhs.size * rhs.dtype.itemsize
                            + m * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret, name="grouped_matmul",
    )(*schedule, lhs, rhs)


@functools.partial(jax.jit, static_argnames=(
    "groups", "tm", "tk", "tn", "interpret"))
def _tgmm(lhs, rhs, schedule, *, groups, tm, tk, tn, interpret):
    """lhs (m, k), rhs (m, n), tm | m -> (groups, k, n) float32: each
    group's rows of ``lhs``, transposed, times its rows of ``rhs``."""
    (m, k), n = lhs.shape, rhs.shape[1]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), schedule[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda i, j, v, g, t, lo, hi: (t[v], i)),
                pl.BlockSpec((tm, tn),
                             lambda i, j, v, g, t, lo, hi: (t[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda i, j, v, g, t, lo, hi: (g[v], i, j))),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * pl.cdiv(n, tn)
                            + rhs.size * rhs.dtype.itemsize * pl.cdiv(k, tk)
                            + groups * k * n * 4)),
        interpret=interpret, name="grouped_matmul_transposed",
    )(*schedule, lhs, rhs)


def _padded(x, tm):
    """``x`` with zero rows up to a multiple of ``tm`` (no group owns
    them)."""
    return jnp.pad(x, ((0, -x.shape[0] % tm), (0, 0)))


def _k_minor(rhs) -> bool:
    """The TPU compiler keeps a (groups, k, n) array whose ``n`` is no
    multiple of 128 lanes while ``k`` is one with ``k`` minor in memory
    (``experts_up`` (8, 2688, 1856): 1856 would be padded to 1920).  The
    kernels then take it as (groups, n, k), which is that memory read
    row-major, and return its cotangent the same way: both ``swapaxes``
    are relabelings.  Taken as (groups, k, n) it cost, compiled for a v5e,
    a relayout copy of the cast in each pass and, because the cotangent
    came out row-major, of the parameter and both Adam moments into and
    out of the update: 32 copies of 80-160 MB a step."""
    return rhs.shape[2] % 128 != 0 and rhs.shape[1] % 128 == 0


def _operands(lhs, rhs, group_sizes, tiling):
    """What both passes start from: the row tile; ``lhs`` padded to it;
    the weights in ``lhs``'s dtype, as (groups, n, k) where ``k_minor``;
    the schedule; the column tiles of ``k`` and ``n`` where each is a
    product's output (no wider than the dimension: a block as wide as the
    array need be no multiple of 128)."""
    m, (_, k, n) = lhs.shape[0], rhs.shape
    tm, tk, tn = tiling or (_ROWS, _col_tile(k), _col_tile(n))
    # no tile longer than the rows, rounded up to whole 128-row blocks
    tm = min(tm, -(-m // 128) * 128)
    rows = _padded(lhs, tm)
    k_minor = _k_minor(rhs)
    weights = rhs.astype(lhs.dtype)
    return (tm, rows, weights.swapaxes(1, 2) if k_minor else weights,
            k_minor, _schedule(group_sizes, rows.shape[0], tm),
            min(tk, k), min(tn, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes,
                   tiling: Optional[Tuple[int, int, int]] = None,
                   interpret: bool = False):
    """lhs (m, k); rhs (groups, k, n), cast to ``lhs``'s dtype for the
    product; group_sizes (groups,) int32, their sum at most ``m`` ->
    (m, n) float32.  ``d lhs`` in ``lhs``'s dtype, ``d rhs`` accumulated in
    float32 and returned in ``rhs``'s.  The tiles follow (m, k, n) by the
    rules above unless a test passes ``tiling``, the tiles of m, k and n
    wherever each is an output's dimension; ``interpret`` runs the kernels
    in the Pallas interpreter (off the TPU: tests only)."""
    return _forward(lhs, rhs, group_sizes, tiling, interpret)[0]


def _forward(lhs, rhs, group_sizes, tiling, interpret):
    tm, rows, weights, k_minor, schedule, _, tn = _operands(
        lhs, rhs, group_sizes, tiling)
    out = _gmm(rows, weights, schedule, tm=tm, tn=tn, transpose_rhs=k_minor,
               out_dtype=jnp.float32, interpret=interpret)
    return out[:lhs.shape[0]], (lhs, rhs, group_sizes)


def _backward(tiling, interpret, residuals, g):
    lhs, rhs, group_sizes = residuals
    groups, k, n = rhs.shape
    # the cast in ``_operands`` is the forward's own (one op after CSE):
    # the float32 parameter is the residual, not a second copy of it
    tm, rows, weights, k_minor, schedule, tk, tn = _operands(
        lhs, rhs, group_sizes, tiling)
    g_rows = _padded(g.astype(lhs.dtype), tm)
    d_lhs = _gmm(g_rows, weights, schedule, tm=tm, tn=tk,
                 transpose_rhs=not k_minor, out_dtype=lhs.dtype,
                 interpret=interpret)
    # the cotangent in the weights' orientation: (groups, n, k) where
    # ``k_minor``, its block cut by the same rule with the roles swapped
    a, b, ta, tb = (g_rows, rows, tn, tk) if k_minor else (rows, g_rows,
                                                           tk, tn)
    if not tiling:
        ta, tb = _block(a.shape[1], b.shape[1])
    d_rhs = _tgmm(a, b, schedule, groups=groups, tm=tm, tk=ta, tn=tb,
                  interpret=interpret)
    if k_minor:
        d_rhs = d_rhs.swapaxes(1, 2)
    return d_lhs[:lhs.shape[0]], d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_forward, _backward)
