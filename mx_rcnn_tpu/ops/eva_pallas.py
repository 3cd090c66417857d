"""Pallas TPU kernels for EVA attention (``ops/eva.py``), flash style.

The keys of a query are two sets under one softmax: one pooled key a chunk
for every chunk wholly before the query's block (the summaries), then the
exact keys of its block up to itself.  Both sets arrive as one array, ``kx
= [kbar; k]`` (the ``N = S / chunk`` summaries first, then the ``S``
positions), and so do their values, ``vx``.  A grid step is one (query
block, key block) pair of that array: for a query block first the summary
blocks its block may read, then its own block's key blocks on or below the
diagonal.  The list is static and reaches the kernels by scalar prefetch
with a flag word a pair, so no pair outside a query's key sets is visited.
Two kinds of pair carry a mask: the last summary block of a window (lanes
past its summary count, ``lim``) and the tiles across the diagonal.  One
head a grid step (the family's heads are as many for keys as for queries):
head size 128 is the lane width, so a head is one ``(block, 128)`` column
block of the projection's ``(B, S, H * D)`` output, read in place.

Scores, statistics and accumulators are float32, every product takes
operands in ``q.dtype`` and accumulates in float32.  The forward kernel
(``eva_attend_fwd``) keeps a running maximum and sum a query (online
softmax) and writes ``o``, the log-sum-exp a row and the keys each row took
(all, and summaries), which the op turns into its counters.  The backward
kernel (``eva_attend_bwd``) is keys-major: ``s^T = k . q^T``, so that ``lse``
and ``delta = sum(o * d o)`` are lane-dense rows; ``d q^T`` of the query
block is resident while its pairs pass and ``d k, d v`` of the whole ``N +
S`` rows of a head stay in VMEM until the head's last pair.  ``o`` and the
log-sum-exp are named ``EVA_OUT`` and ``EVA_LSE``: a checkpoint under
``KEEP_EVA_RESIDUALS`` keeps them, and the backward does not run the
forward kernel again.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 512
BLOCK_K = 512
# ``d k, d v`` of one head's N + S rows, float32, in VMEM
_KV_BYTES = 32 * 2 ** 20
_VMEM_LIMIT = 96 * 2 ** 20
_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # (m, d) x (n, d) -> (m, n)
# a pair's flag word: the first and last pair of its query block; a tile
# across the diagonal; a summary block with lanes past the summary count
FIRST, LAST, DIAGONAL, PARTIAL = 1, 2, 4, 8
EVA_OUT = "eva_attention.o"
EVA_LSE = "eva_attention.lse"
KEEP_EVA_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    EVA_OUT, EVA_LSE)


def tiles(s: int, d: int, window: int, chunk: int,
          blocks: Optional[Tuple[int, int]] = None) -> bool:
    """The kernels take this sequence, head size, window and chunk."""
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    n = s // chunk
    return (d % _LANES == 0 and s % chunk == 0 and s % bq == 0
            and s % bk == 0 and window % bq == 0 and window % bk == 0
            and n % bk == 0 and 8 * (n + s) * d <= _KV_BYTES)


def pairs(s: int, window: int, chunk: int, bq: int, bk: int):
    """(query block, key block of ``kx``, flags, summary lanes) a grid
    step, four int32 arrays: for each query block in order, the summary
    blocks before its window's start, then its window's key blocks up to
    the one holding the block's last query."""
    nsb = s // chunk // bk
    rows = []
    for i in range(s // bq):
        start = window * (i * bq // window)
        count = start // chunk
        steps = [[i, j, PARTIAL if count - j * bk < bk else 0,
                  min(count - j * bk, bk)] for j in range(-(-count // bk))]
        for jl in range(start // bk, (i * bq + bq - 1) // bk + 1):
            steps.append([i, nsb + jl,
                          DIAGONAL if jl * bk + bk - 1 > i * bq else 0, bk])
        steps[0][2] |= FIRST
        steps[-1][2] |= LAST
        rows += steps
    # graphlint: disable=GL101 the list is of static ints, built at trace time
    return tuple(jnp.asarray(c) for c in np.asarray(rows, np.int32).T)


def _masks(i, j, lim, nsb, bq, bk, keys_major: bool):
    """(the diagonal tile's mask, the partial summary block's mask), keys
    down or across."""
    shape = (bk, bq) if keys_major else (bq, bk)
    kax, qax = (0, 1) if keys_major else (1, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, kax)
    query = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, qax)
    return (j - nsb) * bk + lane <= query, lane < lim


def _each_kind(tile, flags, diagonal, partial):
    """``tile(mask)`` with the mask the pair's flags name, or none."""
    on_diagonal = (flags & DIAGONAL) != 0
    part = (flags & PARTIAL) != 0

    @pl.when(on_diagonal)
    def _diag():
        tile(diagonal)

    @pl.when(part)
    def _part():
        tile(partial)

    @pl.when(jnp.logical_not(jnp.logical_or(on_diagonal, part)))
    def _full():
        tile(None)


def _lanes(x, n):
    """x (rows, 128), every lane the same -> (rows, n); fewer than 128 (a
    test's blocks, in the interpreter) are the first n."""
    if n <= _LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // _LANES))


def _fwd_kernel(qi_ref, kj_ref, fl_ref, lim_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, cnt_ref, m_ref, l_ref, acc_ref, n_ref,
                ns_ref, *, bq, bk, d, nsb):
    t = pl.program_id(2)
    i, j, flags, lim = qi_ref[t], kj_ref[t], fl_ref[t], lim_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        ns_ref[...] = jnp.zeros_like(ns_ref)

    def tile(mask):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32)
        s = s * (d ** -0.5)                                 # (bq, bk)
        if mask is None:
            took = jnp.full((bq, 1), bk, jnp.float32)
        else:
            s = jnp.where(mask, s, -jnp.inf)
            took = jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)
        n_ref[...] += took
        ns_ref[...] += jnp.where(j < nsb, took, 0.0)
        # every query's first pair holds a key it sees (summary 0, or its
        # window's first key): the maximum is finite from the first pair on
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    _each_kind(tile, flags, *_masks(i, j, lim, nsb, bq, bk, False))

    @pl.when((flags & LAST) != 0)
    def _store():
        o = acc_ref[...] / _lanes(l_ref[...], d)
        o_ref[...] = o.astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l_ref[...])).T[:1]  # (1, bq)
        cnt_ref[...] = jnp.concatenate([n_ref[...].T[:1],
                                        ns_ref[...].T[:1]], 0)  # (2, bq)


def _bwd_kernel(qi_ref, kj_ref, fl_ref, lim_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                dk_acc, dv_acc, *, bq, bk, d, nsb, last):
    t = pl.program_id(2)
    i, j, flags, lim = qi_ref[t], kj_ref[t], fl_ref[t], lim_ref[t]

    @pl.when(t == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((flags & FIRST) != 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k, v = k_ref[...], v_ref[...]
    k_t = k.T                                               # (d, bk)
    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)

    def tile(mask):
        q, do = q_ref[...], do_ref[...]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
        s = s * (d ** -0.5) - lse_ref[...]                  # (bk, bq)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        p = jnp.exp(s)
        dv_acc[rows, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[...])).astype(q.dtype)
        dk_acc[rows, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_acc[...] += jnp.dot(k_t, ds, preferred_element_type=jnp.float32)

    _each_kind(tile, flags, *_masks(i, j, lim, nsb, bq, bk, True))

    @pl.when((flags & LAST) != 0)
    def _store_q():
        dq_ref[...] = (dq_acc[...] * (d ** -0.5)).T.astype(dq_ref.dtype)

    @pl.when(t == last)
    def _store_kv():
        dk_ref[...] = (dk_acc[...] * (d ** -0.5)).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _specs(bq, bk, d):
    """The windows both kernels cut, by (b, head, step): a query block of a
    head (also ``o``, ``d o``, ``d q``), a key block of ``kx`` and the
    per-row statistics (b, heads, 1, s)."""
    rows_q = pl.BlockSpec((None, bq, d), lambda b, h, t, *p: (b, p[0][t], h))
    rows_k = pl.BlockSpec((None, bk, d), lambda b, h, t, *p: (b, p[1][t], h))
    stats = pl.BlockSpec((None, None, 1, bq),
                         lambda b, h, t, *p: (b, h, 0, p[0][t]))
    return rows_q, rows_k, stats


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _cost(b, h, d, steps, bq, bk, products, operands, rows):
    """``products`` tile products a visited pair, one exp a score,
    ``operands`` passes over an array of ``rows`` rows of every head."""
    return pl.CostEstimate(
        flops=2 * products * b * h * steps * bq * bk * d,
        transcendentals=b * h * steps * bq * bk,
        bytes_accessed=operands * b * rows * h * d * 2)


@functools.partial(jax.jit, static_argnames=("heads", "window", "chunk",
                                             "bq", "bk", "interpret"))
def _forward_call(q, kx, vx, *, heads, window, chunk, bq, bk, interpret):
    """q (B, S, H * D); kx, vx (B, N + S, H * D) -> o as q, lse (B, H, 1,
    S) float32, keys taken (B, H, 2, S) float32 (all, summaries)."""
    b, s, hd = q.shape
    d = hd // heads
    prefetch = pairs(s, window, chunk, bq, bk)
    rows_q, rows_k, stats = _specs(bq, bk, d)
    counts = pl.BlockSpec((None, None, 2, bq),
                          lambda b, h, t, *p: (b, h, 0, p[0][t]))
    steps = prefetch[0].shape[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, d=d,
                          nsb=s // chunk // bk),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32),
                   jax.ShapeDtypeStruct((b, heads, 2, s), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, heads, steps),
            in_specs=[rows_q, rows_k, rows_k],
            out_specs=(rows_q, stats, counts),
            scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32)]),
        compiler_params=_params(),
        cost_estimate=_cost(b, heads, d, steps, bq, bk, 2, 3, s),
        interpret=interpret, name="eva_attend_fwd",
    )(*prefetch, q, kx, vx)


@functools.partial(jax.jit, static_argnames=("heads", "window", "chunk",
                                             "bq", "bk", "interpret"))
def _backward_call(q, kx, vx, do, lse, delta, *, heads, window, chunk, bq,
                   bk, interpret):
    """The cotangents of q, kx and vx, each as its operand."""
    b, s, hd = q.shape
    d = hd // heads
    prefetch = pairs(s, window, chunk, bq, bk)
    rows_q, rows_k, stats = _specs(bq, bk, d)
    whole_k = pl.BlockSpec((None, kx.shape[1], d),
                           lambda b, h, t, *p: (b, 0, h))
    steps = prefetch[0].shape[0]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, d=d,
                          nsb=s // chunk // bk, last=steps - 1),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(kx.shape, kx.dtype),
                   jax.ShapeDtypeStruct(vx.shape, vx.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, heads, steps),
            in_specs=[rows_q, rows_k, rows_k, rows_q, stats, stats],
            out_specs=(rows_q, whole_k, whole_k),
            scratch_shapes=[pltpu.VMEM((d, bq), jnp.float32),
                            pltpu.VMEM((kx.shape[1], d), jnp.float32),
                            pltpu.VMEM((kx.shape[1], d), jnp.float32)]),
        compiler_params=_params(),
        cost_estimate=_cost(b, heads, d, steps, bq, bk, 5, 4, s),
        interpret=interpret, name="eva_attend_bwd",
    )(*prefetch, q, kx, vx, do, lse, delta)


def _flat(x):
    return x.reshape(x.shape[0], x.shape[1], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def eva_flash(q, kx, vx, window: int, chunk: int,
              blocks: Optional[Tuple[int, int]] = None,
              interpret: bool = False):
    """q (B, S, H, D); kx, vx (B, S / chunk + S, H, D), the summaries
    first (``tiles`` holds) -> (o (B, S, H, D) in ``q.dtype``, keys each
    (row, head) took (B, H, 2, S) float32: all, and of them summaries).
    ``blocks`` is a test's (block_q, block_k) in place of the file's;
    ``interpret`` runs the kernels in the Pallas interpreter (off the TPU:
    tests only)."""
    return _forward(q, kx, vx, window, chunk, blocks, interpret)[0]


def _forward(q, kx, vx, window, chunk, blocks, interpret):
    b, s, h, d = q.shape
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    if kx.shape[1] != s // chunk + s or not tiles(s, d, window, chunk,
                                                  blocks):
        raise ValueError(f"sequence {s}, head size {d}, window {window} and "
                         f"chunk {chunk} do not tile ({bq}, {bk}, 128)")
    o, lse, cnt = _forward_call(_flat(q), _flat(kx), _flat(vx), heads=h,
                                window=window, chunk=chunk, bq=bq, bk=bk,
                                interpret=interpret)
    o, lse = checkpoint_name(o, EVA_OUT), checkpoint_name(lse, EVA_LSE)
    return (o.reshape(q.shape), cnt), (q, kx, vx, o, lse)


def _backward(window, chunk, blocks, interpret, residuals, g):
    q, kx, vx, o, lse = residuals
    b, s, h, d = q.shape
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    g = g[0]
    # the transpose is traced on its own: name it, so that its device time
    # is found under the op's scope wherever it is traced from
    with jax.named_scope("eva_attend"):
        delta = jnp.sum(o.reshape(q.shape).astype(jnp.float32)
                        * g.astype(jnp.float32), axis=-1)    # (B, S, H)
        delta = delta.transpose(0, 2, 1)[:, :, None, :]
        dq, dk, dv = _backward_call(
            _flat(q), _flat(kx), _flat(vx), _flat(g.astype(q.dtype)), lse,
            delta, heads=h, window=window, chunk=chunk, bq=bq, bk=bk,
            interpret=interpret)
    return dq.reshape(q.shape), dk.reshape(kx.shape), dv.reshape(vx.shape)


eva_flash.defvjp(_forward, _backward)
