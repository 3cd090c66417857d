"""Pallas TPU kernels of the delta rule (``ops/kda.py``): the two score
matrices of a chunk (``scores``, below) and the triangular inverses
(``inv_unit_lower``): ``T = (I + A)^-1`` for a batch of strictly lower
triangular ``A`` of ``n x n``, by forward substitution in float32 on the
vector unit, with the batch in the lanes.

The matrices are tiny (64 x 64 a chunk and head) and many (2048 a part of
the train step), so a matrix product per elimination step fills an eighth
of a lane tile and, in float32, takes six passes of the MXU.  Here the batch
is the minor dimension instead: the kernel sees ``A`` as ``(n, n, B)``, one
grid step takes the 128 matrices of a lane tile, and row ``i`` of all of
them at once is

    T[i, :] = e_i - sum_{j < i} A[i, j] T[j, :]

where ``T[j, :]`` is ``(n, 128)`` — ``n / 8`` vector registers, of which
those right of the diagonal are zero and skipped — and ``A[i, j]`` one
sublane row spread over them: multiply-subtract on full registers, no
padding, no product chain.  The blocks of 8 rows and 8 columns are static
(36 pairs at ``n = 64``), the 8 rows of a block run under a ``fori_loop``;
the block that holds the diagonal is taken whole, its entries at and above
the diagonal being zeros of ``A`` against rows of ``T`` that are still zero.

The backward needs no trace of the elimination: with ``T = M^-1``, ``dM = -T^T
dT T^T``, of which the strictly lower part is the input's cotangent.  Two
float32 products a matrix at the highest precision, left to XLA; ``T`` is
the one residual.

The scores ``A_qk[i, j] = sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])`` (``j <=
i``) and ``A_kk`` (the same with ``k_i``, ``j < i``) are formed a chunk-head
at a time, several chunk-heads a grid step, with every decay factor made in
VMEM from the float32 running sums ``G`` as ``ops/kda.py``'s module
docstring says: sub-chunk ``s``'s rows get ``exp(G_i - M_s)``, the keys at
or before it ``exp(M_s - G_j)`` (``M_s`` the sum at its middle), the keys
after it nothing; each sub-chunk is then one product of its ``q`` and
``k`` rows, stacked, against the chunk's factored keys, in the operands'
dtype with float32 accumulation.  Only the two masked matrices reach HBM.
The backward is the same factors again: with ``E_ij = exp(G_i - G_j)`` and
``P``, ``R`` the masked cotangents of ``A_qk``, ``A_kk``,

    dq_i = sum_j P_ij k_j E_ij        dk_i (as a row) = sum_j R_ij k_j E_ij
    dk_j (as a key) = sum_i (P_ij q_i + R_ij k_i) E_ij
    dG = q dq + k dk_row - k dk_key

(``A`` depends on ``G`` through differences only: the middles cancel); one
product a sub-chunk for the rows' side, one transposed for the keys'.
``dG``'s terms take the factored operands as the products took them,
rounded: a pair's term then leaves one side as the other takes it back, so
the diagonal's, which cancel and at the gate's bound outweigh the rest,
leave no rounding behind.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_HI = jax.lax.Precision.HIGHEST


def takes(n: int, dtype=jnp.float32) -> bool:
    """The kernel eliminates float32 matrices in blocks of 8 rows (a
    register's sublanes)."""
    return dtype == jnp.float32 and n >= 8 and n % 8 == 0


def _sum(terms):
    """Pairwise, so that no sum waits on a chain of all the others."""
    while len(terms) > 1:
        terms = [jax.lax.add(*terms[i:i + 2]) for i in range(0, len(terms), 2)]
    return terms[0]


def _substitute_kernel(a_ref, t_ref):
    """a_ref, t_ref (n, n, lanes): row, column, matrix.  Rows and columns
    in blocks of 8, a (8, lanes) float32 register each.  Row ``i`` of block
    ``ib`` has columns in blocks ``0 .. ib`` only, and an earlier row of
    block ``jb`` adds to blocks ``0 .. jb`` of it: the blocks are static, the
    row inside its block is the loop's.  ``lax`` primitives, not ``jnp``
    operators: the body is some 3000 statements at ``n = 64``, traced in
    0.4 s this way and in 1.2 s through ``jnp``'s dispatch."""
    n, _, lanes = a_ref.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0)
    zero = jnp.zeros((8, lanes), jnp.float32)
    for ib in range(n // 8):
        r0 = ib * 8
        # right of the diagonal nothing else writes the zeros, and left of
        # it the block's rows are read (against zeros of ``a``) before they
        # are written
        t_ref[r0:r0 + 8] = jnp.zeros((8, n, lanes), jnp.float32)

        def row(r, carry, ib=ib, r0=r0):
            i = r0 + r
            acc = [zero] * ib + [(sub == r).astype(jnp.float32)]
            for jb in range(ib + 1):
                a = a_ref[i, jb * 8:jb * 8 + 8, :]
                a = [jax.lax.broadcast_in_dim(
                    jax.lax.slice_in_dim(a, jj, jj + 1), (8, lanes), (0, 1))
                    for jj in range(8)]
                for kb in range(jb + 1):
                    acc[kb] = jax.lax.sub(acc[kb], _sum([jax.lax.mul(
                        a[jj], t_ref[jb * 8 + jj, kb * 8:kb * 8 + 8, :])
                        for jj in range(8)]))
            for kb in range(ib + 1):
                t_ref[i, kb * 8:kb * 8 + 8, :] = acc[kb]
            return carry

        jax.lax.fori_loop(0, 8, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _substitute(a, interpret: bool):
    """a (n, n, B) float32, strictly lower in its first two dimensions, B a
    multiple of ``LANES`` -> T (n, n, B).  A ``jit`` of its own: the train
    step calls the kernel twice a KDA block, and ten calls traced and
    lowered one by one were +13 s of ``setup_s`` (PERF.md section 6, PR
    39); through this ``jit``'s cache they are traced twice."""
    n, _, b = a.shape
    spec = pl.BlockSpec((n, n, LANES), lambda g: (0, 0, g))
    return pl.pallas_call(
        _substitute_kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        grid=(b // LANES,), in_specs=[spec], out_specs=spec,
        # both blocks twice (the next step's are fetched while this one
        # runs): 8 MiB at n = 64, and as much again beside them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=16 * n * n * LANES + 2 ** 23),
        cost_estimate=pl.CostEstimate(flops=b * n ** 3 // 3, transcendentals=0,
                                      bytes_accessed=8 * b * n * n),
        interpret=interpret, name="kda_inv_unit_lower",
    )(a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def inv_unit_lower(m, interpret: bool = False):
    """Inverse of unit-lower-triangular matrices (..., n, n) float32, ``n``
    a multiple of 8 (``takes``); only the part of ``m`` below the diagonal
    is read, and only it gets a cotangent.  ``interpret`` runs the kernel in
    the Pallas interpreter (off the TPU: tests only)."""
    return _forward(m, interpret)[0]


def _forward(m, interpret):
    n = m.shape[-1]
    if m.shape[-2] != n or not takes(n, m.dtype):
        raise ValueError(f"{m.dtype} matrices of {m.shape[-2:]}: the kernel "
                         "takes square float32 ones of a multiple of 8")
    b = math.prod(m.shape[:-2])
    # the batch goes into the lanes, padded to whole tiles with zero
    # matrices (which invert to the identity and are cut off again)
    a = jnp.tril(m, -1).reshape(b, n, n).transpose(1, 2, 0)
    a = jnp.pad(a, ((0, 0), (0, 0), (0, -b % LANES)))
    t = _substitute(a, interpret)[:, :, :b].transpose(2, 0, 1).reshape(m.shape)
    return t, t


def _backward(interpret, t, g):
    del interpret
    # the transpose is traced on its own: name its ops, so that their device
    # time is found under the solve's scope wherever it is traced from
    with jax.named_scope("kda_solve"):
        tt = jnp.swapaxes(t, -1, -2)
        x = jnp.matmul(jnp.matmul(tt, g, precision=_HI), tt, precision=_HI)
        return (-jnp.tril(x, -1),)


inv_unit_lower.defvjp(_forward, _backward)


# ---- the two score matrices of a chunk ------------------------------------

_NT = (((1,), (1,)), ((), ()))      # (m, d) x (n, d) -> (m, n)
_TN = (((0,), (0,)), ((), ()))      # (c, m) x (c, n) -> (m, n)
_STEP = 32                          # chunk-heads a grid step, at most


def scores_take(chunk: int, sub: int, dk: int, dtype) -> bool:
    """The scores kernel cuts sub-chunks of whole bfloat16 tiles (16 rows)
    from chunks of at most a lane tile, over keys a whole number of lane
    tiles wide."""
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))
            and sub % 16 == 0 and chunk % sub == 0 and chunk <= LANES
            and dk % LANES == 0)


def _factors(q, k, g, s, sub):
    """Sub-chunk ``s`` of one chunk-head; q, k, g (L, K) float32, ``g`` the
    running log-decay -> (its rows' factors exp(G_i - M) (sub, K), the keys'
    exp(M - G_j) (L, K), zero after the sub-chunk, its q and k rows
    factored and stacked (2 sub, K), the keys factored (L, K)); ``M`` the
    sum at the sub-chunk's middle."""
    length, width = g.shape
    lo, hi = s * sub, (s + 1) * sub
    mid = g[lo + sub // 2:lo + sub // 2 + 1]
    row = jnp.exp(g[lo:hi] - mid)
    key = jnp.exp(mid - g[:hi])
    if hi < length:
        key = jnp.concatenate(
            [key, jnp.zeros((length - hi, width), jnp.float32)])
    return (row, key, jnp.concatenate([q[lo:hi] * row, k[lo:hi] * row]),
            k * key)


def _masks(sub, length):
    """Row and column of a sub-chunk's rows against the chunk's keys."""
    return (jax.lax.broadcasted_iota(jnp.int32, (sub, length), 0),
            jax.lax.broadcasted_iota(jnp.int32, (sub, length), 1))


def _scores_kernel(q_ref, k_ref, g_ref, qk_ref, kk_ref, *, sub):
    """q_ref, k_ref, g_ref (T, L, K): T chunk-heads; qk_ref, kk_ref (T, L,
    L).  A ``fori_loop`` over the chunk-heads, the sub-chunks static."""
    steps, length, _ = q_ref.shape
    dtype, f32 = q_ref.dtype, jnp.float32
    i, j = _masks(sub, length)

    def one(t, carry):
        q, k = q_ref[t].astype(f32), k_ref[t].astype(f32)
        g = g_ref[t]
        for s in range(length // sub):
            _, _, rows, keys = _factors(q, k, g, s, sub)
            a = jax.lax.dot_general(rows.astype(dtype), keys.astype(dtype),
                                    _NT, preferred_element_type=f32)
            at = i + s * sub
            qk_ref[t, s * sub:(s + 1) * sub, :] = jnp.where(
                j <= at, a[:sub], 0.0).astype(qk_ref.dtype)
            kk_ref[t, s * sub:(s + 1) * sub, :] = jnp.where(
                j < at, a[sub:], 0.0)
        return carry

    jax.lax.fori_loop(0, steps, one, 0)


def _scores_bwd_kernel(q_ref, k_ref, g_ref, dqk_ref, dkk_ref, dq_ref, dk_ref,
                       dg_ref, *, sub):
    """The cotangents dqk_ref, dkk_ref (T, L, L) -> dq_ref, dk_ref, dg_ref
    (T, L, K); the module docstring's three sums."""
    steps, length, width = q_ref.shape
    dtype, f32 = q_ref.dtype, jnp.float32
    i, j = _masks(sub, length)

    def one(t, carry):
        q, k = q_ref[t].astype(f32), k_ref[t].astype(f32)
        g = g_ref[t]
        d_qk, d_kk = dqk_ref[t].astype(f32), dkk_ref[t]
        as_key = jnp.zeros((length, width), f32)
        dg = jnp.zeros((length, width), f32)
        dq, as_row, dg_row = [], [], []
        for s in range(length // sub):
            lo, hi = s * sub, (s + 1) * sub
            row, key, rows, keys = _factors(q, k, g, s, sub)
            rows, keys = rows.astype(dtype), keys.astype(dtype)
            at = i + lo
            cot = jnp.concatenate([jnp.where(j <= at, d_qk[lo:hi], 0.0),
                                   jnp.where(j < at, d_kk[lo:hi], 0.0)]
                                  ).astype(dtype)          # (2 sub, L)
            d_rows = jnp.dot(cot, keys, preferred_element_type=f32)
            d_keys = jax.lax.dot_general(cot, rows, _TN,
                                         preferred_element_type=f32)
            dq.append(row * d_rows[:sub])
            as_row.append(row * d_rows[sub:])
            as_key = as_key + key * d_keys
            # dG from the rounded operands (module docstring)
            by_row = rows.astype(f32) * d_rows
            dg_row.append(by_row[:sub] + by_row[sub:])
            dg = dg - keys.astype(f32) * d_keys
        dq, as_row = jnp.concatenate(dq), jnp.concatenate(as_row)
        dq_ref[t] = dq.astype(dq_ref.dtype)
        dk_ref[t] = (as_row + as_key).astype(dk_ref.dtype)
        dg_ref[t] = dg + jnp.concatenate(dg_row)
        return carry

    jax.lax.fori_loop(0, steps, one, 0)


def _scores_specs(n, length, width, io_bytes):
    """(chunk-heads a grid step, the rows' and the squares' block specs,
    compiler parameters): ``io_bytes`` the VMEM of one chunk-head's blocks,
    each taken twice (the next step's are fetched while one runs)."""
    step = math.gcd(n, _STEP)
    rows = pl.BlockSpec((step, length, width), lambda c: (c, 0, 0))
    square = pl.BlockSpec((step, length, length), lambda c: (c, 0, 0))
    params = pltpu.CompilerParams(dimension_semantics=("parallel",),
                                  vmem_limit_bytes=2 * step * io_bytes + 2 ** 23)
    return step, rows, square, params


def _exps(length, width, sub):
    """Exponentials a chunk-head: each sub-chunk's rows, and the keys at or
    before it."""
    n = length // sub
    return width * sub * (n + n * (n + 1) // 2)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _scores_call(q, k, g, *, sub, interpret):
    """q, k (N, L, K) in the products' dtype, g (N, L, K) float32 -> A_qk
    (N, L, L) in that dtype, A_kk (N, L, L) float32.  A ``jit`` of its own,
    as ``_substitute``."""
    n, length, width = q.shape
    size = q.dtype.itemsize
    # a square block's rows are padded to a lane tile in VMEM
    step, rows, square, params = _scores_specs(
        n, length, width, length * (width * (2 * size + 4)
                                    + LANES * (size + 4)))
    return pl.pallas_call(
        functools.partial(_scores_kernel, sub=sub),
        out_shape=(jax.ShapeDtypeStruct((n, length, length), q.dtype),
                   jax.ShapeDtypeStruct((n, length, length), jnp.float32)),
        grid=(n // step,), in_specs=[rows, rows, rows],
        out_specs=(square, square), compiler_params=params,
        cost_estimate=pl.CostEstimate(
            flops=4 * n * length * length * width,
            transcendentals=n * _exps(length, width, sub),
            bytes_accessed=n * length * (width * (2 * size + 4)
                                         + length * (size + 4))),
        interpret=interpret, name="kda_scores_fwd",
    )(q, k, g)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _scores_bwd_call(q, k, g, d_qk, d_kk, *, sub, interpret):
    """The cotangents of ``_scores_call``'s two results -> dq, dk in the
    products' dtype, dg float32, all (N, L, K)."""
    n, length, width = q.shape
    size = q.dtype.itemsize
    step, rows, square, params = _scores_specs(
        n, length, width, length * (width * (4 * size + 8)
                                    + LANES * (size + 4)))
    return pl.pallas_call(
        functools.partial(_scores_bwd_kernel, sub=sub),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, jnp.float32)),
        grid=(n // step,), in_specs=[rows, rows, rows, square, square],
        out_specs=(rows, rows, rows), compiler_params=params,
        cost_estimate=pl.CostEstimate(
            flops=8 * n * length * length * width,
            transcendentals=n * _exps(length, width, sub),
            bytes_accessed=n * length * (width * (4 * size + 8)
                                         + length * (size + 4))),
        interpret=interpret, name="kda_scores_bwd",
    )(q, k, g, d_qk, d_kk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def scores(q, k, g, sub: int, interpret: bool = False):
    """q, k (..., L, K) a chunk-head each, in the products' dtype; g (..., L,
    K) float32, the running log-decay from the chunk's start -> (A_qk (...,
    L, L) in q's dtype, ``j <= i``; A_kk (..., L, L) float32, ``j < i``);
    zeros elsewhere.  ``scores_take`` says which shapes; ``interpret`` runs
    the kernels in the Pallas interpreter (off the TPU: tests only)."""
    return _scores_forward(q, k, g, sub, interpret)[0]


def _flat(t):
    return t.reshape((-1,) + t.shape[-2:])


def _scores_forward(q, k, g, sub, interpret):
    length, width = q.shape[-2:]
    if (k.shape != q.shape or g.shape != q.shape or k.dtype != q.dtype
            or g.dtype != jnp.float32
            or not scores_take(length, sub, width, q.dtype)):
        raise ValueError(f"{q.dtype} {q.shape} / {k.dtype} {k.shape} / "
                         f"{g.dtype} {g.shape} in sub-chunks of {sub}: not "
                         "what the scores kernel takes")
    a_qk, a_kk = _scores_call(_flat(q), _flat(k), _flat(g), sub=sub,
                              interpret=interpret)
    square = q.shape[:-1] + (length,)
    return (a_qk.reshape(square), a_kk.reshape(square)), (q, k, g)


def _scores_backward(sub, interpret, res, cot):
    q, k, g = res
    # traced outside the forward's scopes: the kernel and the casts around
    # it carry the scope's name themselves
    with jax.named_scope("kda_scores"):
        dq, dk, dg = _scores_bwd_call(
            _flat(q), _flat(k), _flat(g), _flat(cot[0]).astype(q.dtype),
            _flat(cot[1]).astype(jnp.float32), sub=sub, interpret=interpret)
        return dq.reshape(q.shape), dk.reshape(q.shape), dg.reshape(g.shape)


scores.defvjp(_scores_forward, _scores_backward)
