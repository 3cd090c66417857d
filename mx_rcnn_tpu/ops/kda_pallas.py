"""Pallas TPU kernel for the delta rule's triangular inverses
(``ops/kda.py::inv_unit_lower``): ``T = (I + A)^-1`` for a batch of strictly
lower triangular ``A`` of ``n x n``, by forward substitution in float32 on the
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
