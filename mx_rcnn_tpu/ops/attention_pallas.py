"""Pallas TPU kernels for causal grouped-query attention (flash style).

``flash_causal_gqa(q, k, v)`` is ``softmax(q . k^T / sqrt(D) + causal mask)
. v`` of ``ops/attention.py`` with no (queries, keys) array in HBM, forward
or backward: a tile of scores, its probabilities, the running maximum and
sum of each query and the output's accumulator live in VMEM (online
softmax).  Scores, statistics and accumulators are float32; every product
takes operands in ``q.dtype`` and accumulates in float32, as the blocked
path's do.

No operand is transposed or copied on the way in or out.  Head size 128 is
the lane width, so the ``R = Hq / Hkv`` query heads of a key-value head are
one ``(block_q, R * D)`` column block of the projection's ``(B, S, Hq * D)``
output, a key-value head one ``(block_k, D)`` column block of its own, and
a ``BlockSpec`` reads each in place.  A grid step is one (query block, key
block) pair on or below the diagonal, by query block and its key blocks in
order — the list is static and reaches the kernels by scalar prefetch, so
the pairs above the diagonal are never visited — and walks the R query
heads inside, all against the one fetched K/V tile; only the tiles that
cross the diagonal pay for the mask.

Two kernels behind one ``custom_vjp`` whose residuals are ``q, k, v, o``
and the log-sum-exp of each row.  The last two are the forward kernel's
own results, named ``FLASH_OUT`` and ``FLASH_LSE``: a ``jax.checkpoint``
around a call under ``KEEP_FLASH_RESIDUALS`` saves that pair and
recomputes the rest, so that its backward reads ``o`` and ``lse`` and does
not run the forward kernel a second time (``q, k, v``, padded, are what a
checkpoint exists not to hold; a policy finds no name where ``causal_gqa``
took the blocked path and saves nothing there):

* forward, keys in the lanes: ``s = q . k^T (block_q, block_k)``, the
  statistics ``(block_q, 128)`` with every lane the same, ``acc += p . v``;
  both products stream a block of queries past each latched tile.  ``o``
  and ``lse = m + log(l)``, the latter turned into a lane-dense row once a
  query block and head;
* backward, keys-major: ``s^T = k . q^T (block_k, block_q)``, so that ``lse``
  and ``delta = sum(o * d o)`` (formed once, outside) are rows ``(1,
  block_q)`` that broadcast down the sublanes, and four of its five
  products are plain: ``p^T = exp(s^T - lse)``, ``d v += p^T . d o``,
  ``d s^T = p^T * (v . d o^T - delta)``, ``d k += d s^T . q``, ``d q^T += k^T
  . d s^T``.  One kernel forms each tile once for all three cotangents:
  ``d q^T`` of the query block's heads is resident while its key blocks
  pass, and ``d k, d v`` of the *whole* sequence of the key-value head —
  ``(S, D)`` float32 each, which is what sixteen query heads sharing one
  key-value head buy: 4 MB at 8192 positions — stay in VMEM until the
  group's last pair.
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

# Queries and keys a tile; the sequence has to be a multiple of both.
# Readings at the cell's shape, (2, 8192, 32 over 2, 128) bfloat16, on one
# v5e chip, ms a call on the host's clock, forward / backward (PR 37; 5.9 /
# 14.8 at the MXU's peak): 512 x 512 8.40 / 18.75; 1024 x 512 8.78 / 18.86;
# 512 x 1024 9.23 / 18.95; 1024 x 1024 9.19 / 18.50; 2048 x 512 9.79 /
# 20.25.  With keys-major tiles in the forward too 10.32 at 1024 x 1024
# (11.06 at 512 x 512, 22.9 at 256 x 256); with the backward as a ``d k, d
# v`` kernel and a ``d q`` kernel (seven products) 25.2.
BLOCK_Q = 512
BLOCK_K = 512
# ``d k, d v`` of one key-value head's whole sequence, float32, in VMEM
_KV_BYTES = 32 * 2 ** 20
_VMEM_LIMIT = 96 * 2 ** 20
_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # (m, d) x (n, d) -> (m, n)
# the forward kernel's two results, which the backward kernel reads as
# residuals, and the checkpoint policy that keeps exactly those
FLASH_OUT = "flash_causal_gqa.o"
FLASH_LSE = "flash_causal_gqa.lse"
KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    FLASH_OUT, FLASH_LSE)


def tiles(s: int, d: int, blocks: Optional[Tuple[int, int]] = None) -> bool:
    """The kernels take this sequence length and head size."""
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    return (d % _LANES == 0 and s % bq == 0 and s % bk == 0
            and 8 * s * d <= _KV_BYTES)


def _pairs(s: int, bq: int, bk: int):
    """The (query block, key block) pairs that hold a key at or before a
    query, by query block and its key blocks in order: two int32 arrays."""
    qi, kj = np.asarray([(i, j) for i in range(s // bq)
                         for j in range(s // bk) if j * bk < (i + 1) * bq],
                        np.int32).T
    return jnp.asarray(qi), jnp.asarray(kj)


def _visible(i, j, bq, bk, keys_major: bool):
    """The tile's (key at or before query), keys down or across."""
    shape = (bk, bq) if keys_major else (bq, bk)
    key = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape,
                                            0 if keys_major else 1)
    query = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape,
                                              1 if keys_major else 0)
    return key <= query


def _last_key_block(i, bq, bk):
    """The last key block that holds a key at or before a query of block
    ``i``: its pair closes the query block."""
    return (i * bq + bq - 1) // bk


def _each_head(body, r: int):
    """``body(h)`` for each of the group's ``r`` heads."""
    def step(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, r, step, 0)


def _each_head_of_tile(body, r: int, i, j, bq, bk):
    """``body(h, masked)`` for each head, with the mask only where the pair
    holds a key after a query (the tile crosses the diagonal)."""
    crosses = j * bk + bk - 1 > i * bq
    for masked in (True, False):
        @pl.when(crosses if masked else jnp.logical_not(crosses))
        def _run():
            _each_head(functools.partial(body, masked=masked), r)


def _head(h, d):
    """Head ``h``'s lane block of a (rows, r * d) window."""
    return slice(None), pl.ds(pl.multiple_of(h * d, d), d)


def _lanes(x, n):
    """x (rows, 128), every lane the same -> (rows, n)."""
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, bq, bk, d, r):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k, v = k_ref[...], v_ref[...]

    def head(h, masked):
        s = jax.lax.dot_general(q_ref[_head(h, d)], k, _NT,
                                preferred_element_type=jnp.float32)
        s = s * (d ** -0.5)                                 # (bq, bk)
        if masked:
            s = jnp.where(_visible(i, j, bq, bk, False), s, -jnp.inf)
        # key 0 is at or before every query and block 0 comes first: the
        # maximum is finite from the first tile on
        m_prev = m_ref[h]                                   # (bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_next
        acc_ref[h] = _lanes(alpha, d) * acc_ref[h] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _each_head_of_tile(head, r, i, j, bq, bk)

    @pl.when(j == _last_key_block(i, bq, bk))
    def _store():
        def one(h):
            o = acc_ref[h] / _lanes(l_ref[h], d)
            o_ref[_head(h, d)] = o.astype(o_ref.dtype)
            lse_ref[h] = (m_ref[h] + jnp.log(l_ref[h])).T[:1]   # (1, bq)

        _each_head(one, r)


def _bwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, bq, bk, d, r, last):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(t == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k, v = k_ref[...], v_ref[...]
    k_t = k.T                                               # (d, bk)
    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)

    def head(h, masked):
        q_r, do_r = q_ref[_head(h, d)], do_ref[_head(h, d)]
        s = jax.lax.dot_general(k, q_r, _NT,
                                preferred_element_type=jnp.float32)
        s = s * (d ** -0.5) - lse_ref[h]                    # (bk, bq)
        if masked:
            s = jnp.where(_visible(i, j, bq, bk, True), s, -jnp.inf)
        p = jnp.exp(s)
        dv_acc[rows, :] += jnp.dot(p.astype(do_r.dtype), do_r,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do_r, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[h])).astype(q_r.dtype)
        dk_acc[rows, :] += jnp.dot(ds, q_r,
                                   preferred_element_type=jnp.float32)
        dq_acc[h] += jnp.dot(k_t, ds, preferred_element_type=jnp.float32)

    _each_head_of_tile(head, r, i, j, bq, bk)

    @pl.when(j == _last_key_block(i, bq, bk))
    def _store_q():
        def one(h):
            dq = dq_acc[h] * (d ** -0.5)                    # (d, bq)
            dq_ref[_head(h, d)] = dq.T.astype(dq_ref.dtype)

        _each_head(one, r)

    @pl.when(t == last)
    def _store_kv():
        dk_ref[...] = (dk_acc[...] * (d ** -0.5)).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _specs(q, k, hkv, bq, bk):
    """(b, s, d, r) of q (B, S, Hq * D) and k (B, S, Hkv * D), and the
    windows both kernels cut: a query block of a group's heads (also ``o``,
    ``d o``, ``d q``), a key block of a key-value head, and the group's
    per-row statistics (b, hkv, r, 1, s)."""
    b, s, hd = q.shape
    d = k.shape[2] // hkv
    r = hd // d // hkv
    rows_q = pl.BlockSpec((None, bq, r * d),
                          lambda b, g, t, qi, kj: (b, qi[t], g))
    rows_k = pl.BlockSpec((None, bk, d),
                          lambda b, g, t, qi, kj: (b, kj[t], g))
    stats = pl.BlockSpec((None, None, r, 1, bq),
                         lambda b, g, t, qi, kj: (b, g, 0, 0, qi[t]))
    return (b, s, d, r), rows_q, rows_k, stats


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _cost(b, s, hq, d, products, operands):
    """``products`` tile products over the lower triangle, one exp a
    score, ``operands`` passes over an array of q's size."""
    return pl.CostEstimate(
        flops=products * b * hq * s * (s + 1) * d,
        transcendentals=b * hq * s * (s + 1) // 2,
        bytes_accessed=operands * b * s * hq * d * 2)


@functools.partial(jax.jit, static_argnames=("hkv", "bq", "bk", "interpret"))
def _forward_call(q, k, v, *, hkv, bq, bk, interpret):
    """q (B, S, Hq * D); k, v (B, S, Hkv * D) -> o as q, lse
    (B, Hkv, R, 1, S) float32."""
    (b, s, d, r), rows_q, rows_k, stats = _specs(q, k, hkv, bq, bk)
    qi, kj = _pairs(s, bq, bk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, d=d, r=r),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, hkv, r, 1, s), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, hkv, qi.shape[0]),
            in_specs=[rows_q, rows_k, rows_k],
            out_specs=(rows_q, stats),
            scratch_shapes=[pltpu.VMEM((r, bq, _LANES), jnp.float32),
                            pltpu.VMEM((r, bq, _LANES), jnp.float32),
                            pltpu.VMEM((r, bq, d), jnp.float32)]),
        compiler_params=_params(), cost_estimate=_cost(b, s, hkv * r, d, 2, 3),
        interpret=interpret, name="flash_causal_gqa",
    )(qi, kj, q, k, v)


@functools.partial(jax.jit, static_argnames=("hkv", "bq", "bk", "interpret"))
def _backward_call(q, k, v, do, lse, delta, *, hkv, bq, bk, interpret):
    """The cotangents of q, k and v, each as its operand."""
    (b, s, d, r), rows_q, rows_k, stats = _specs(q, k, hkv, bq, bk)
    whole_k = pl.BlockSpec((None, s, d), lambda b, g, t, qi, kj: (b, 0, g))
    qi, kj = _pairs(s, bq, bk)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, d=d, r=r,
                          last=qi.shape[0] - 1),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, hkv, qi.shape[0]),
            in_specs=[rows_q, rows_k, rows_k, rows_q, stats, stats],
            out_specs=(rows_q, whole_k, whole_k),
            scratch_shapes=[pltpu.VMEM((r, d, bq), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32)]),
        compiler_params=_params(), cost_estimate=_cost(b, s, hkv * r, d, 5, 4),
        interpret=interpret, name="flash_causal_gqa_bwd",
    )(qi, kj, q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_causal_gqa(q, k, v, blocks: Optional[Tuple[int, int]] = None,
                     interpret: bool = False):
    """q (B, S, Hq, D); k, v (B, S, Hkv, D), Hq a multiple of Hkv (query
    head j reads key-value head j // (Hq // Hkv)), D a multiple of 128 and
    S of both blocks (``tiles``) -> (B, S, Hq, D) in ``q.dtype``.  Scale
    D^-1/2, full causal mask, no positional term.  ``blocks`` is a test's
    (block_q, block_k) in place of the file's; ``interpret`` runs the
    kernels in the Pallas interpreter (off the TPU: tests only)."""
    return _forward(q, k, v, blocks, interpret)[0]


def _flat(x):
    return x.reshape(x.shape[0], x.shape[1], -1)


def _forward(q, k, v, blocks, interpret):
    (b, s, hq, d), hkv = q.shape, k.shape[2]
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    if hq % hkv or not tiles(s, d, blocks):
        raise ValueError(f"{hq} query heads over {hkv}, sequence {s} and "
                         f"head size {d} do not tile ({bq}, {bk}, 128)")
    o, lse = _forward_call(_flat(q), _flat(k), _flat(v), hkv=hkv, bq=bq,
                           bk=bk, interpret=interpret)
    o, lse = checkpoint_name(o, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return o.reshape(q.shape), (q, k, v, o, lse)


def _backward(blocks, interpret, residuals, g):
    q, k, v, o, lse = residuals
    (b, s, hq, d), hkv = q.shape, k.shape[2]
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    # the transpose is traced on its own: name it, so that its device time
    # is found under attention's scope wherever it is traced from
    with jax.named_scope("attention"):
        do = _flat(g.astype(q.dtype))
        delta = jnp.sum(o.reshape(q.shape).astype(jnp.float32)
                        * g.astype(jnp.float32), axis=-1)    # (B, S, Hq)
        delta = delta.transpose(0, 2, 1).reshape(b, hkv, hq // hkv, 1, s)
        dq, dk, dv = _backward_call(
            _flat(q), _flat(k), _flat(v), do, lse, delta, hkv=hkv, bq=bq,
            bk=bk, interpret=interpret)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


flash_causal_gqa.defvjp(_forward, _backward)
