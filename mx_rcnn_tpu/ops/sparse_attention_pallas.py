"""Pallas TPU kernels for learned sparse attention (``ops/sparse_attention.py``).

Four Mosaic kernels, each a walk over (key block, query block) tiles with
the keys down the sublanes and the queries across the lanes (keys-major,
as ``ops/attention_pallas.py``'s backward), so that everything a query owns
— its threshold, its softmax statistics, its indexer weights — is a row
``(1, block_q)`` that broadcasts down the tile.  Three kernels form the
indexer's scores of a tile from the padded operands (``_index_tile``: an
indexer head's 64 channels padded to the 128 lanes, one product a head), in
one order of operations, so that a score is the same number in each:
``dsa_select``, ``dsa_attend_fwd`` and ``dsa_kl``.  The backward reads the
forward's choice, packed 32 keys to an int32 word:

* ``dsa_select``: one block of queries against all the keys before it;
  its scores, as order-preserving int32 keys, stay in VMEM while the
  ``top_k``-th largest of each column is found bit by bit (31 counting
  passes after the sign), then the last tied key that is still chosen
  (``cut``, by halving the index range), then the log-sum-exp of the
  chosen scores and the chosen keys a (query block, key tile) pair holds;
* ``dsa_attend_fwd``: attention over the chosen keys, every head of a tile
  in one grid step, online softmax; ``o``, each row's log-sum-exp, how many
  keys each query attended to (the choice holds ``min(t + 1, top_k)``: a
  different count says that this kernel's scores are not the choice's) and
  each tile's choice as a packed mask (``_pack``: a ``(block_k // 32,
  block_q)`` int32 block a (query block, key block) pair of the pair list,
  68.2 MB a layer at 32768 positions and blocks of 512);
* ``dsa_attend_bwd``: its backward (``ops/attention_pallas.py``'s five
  products, under the forward's mask, unpacked once a grid step for the
  query heads of one key-value head), ``d k, d v`` of the group's whole
  sequence resident in VMEM;
* ``dsa_kl``: all heads of a tile at once, with the forward's log-sum-exp:
  the target (the heads' probabilities summed over the head count), the
  indexer's loss a query (``sum p log p - sum p I + lse_I``) and its
  gradient with respect to the indexer's queries, key and weights, which
  the ``custom_vjp`` hands back times the loss's cotangent; a checkpoint
  under ``KEEP_CHOICE`` keeps that gradient (``KL_GRAD``), so that its
  backward does not run this kernel again.

This first form visits every tile on or below the diagonal and masks by
the threshold: correct, with the work of dense causal attention.  A tile
that holds no chosen key could be skipped (``tiles_touched_pct`` says how
many do).
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

from mx_rcnn_tpu.ops.attention_pallas import _pairs

BLOCK_Q = 512
BLOCK_K = 512
# queries a block of the choice: its scores against the whole sequence,
# int32, stay in VMEM (16 MB at 32768 keys)
SELECT_Q = 128
_LANES = 128
_VMEM_LIMIT = 100 * 2 ** 20
# the largest sequence whose resident arrays fit: ``d k, d v`` of a
# key-value head and ``d ki`` float32, the choice's scores int32
_RESIDENT_BYTES = 32 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))      # (m, d) x (n, d) -> (m, n)
_TN = (((0,), (0,)), ((), ()))      # (k, m) x (k, n) -> (m, n)
_INT_MIN = np.int32(-2 ** 31)
# the chosen scores' log-sum-exp a query, kept by a checkpoint as the choice
# is (``sparse_attention.KEEP_CHOICE``)
LSE_I = "sparse_attention.lse_i"
# ``dsa_kl``'s gradient of the indexer's queries, weights and key, kept by
# the same checkpoint at the indexer's width, so that its backward does not
# run the kernel again
KL_GRAD = "sparse_attention.kl_grad"
_LOW = np.int32(0x7FFFFFFF)


def tiles(s: int, d: int, di: int,
          blocks: Optional[Tuple[int, int]] = None) -> bool:
    """The kernels take this sequence, head size and indexer head size."""
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    return (d % _LANES == 0 and di <= _LANES and s % bq == 0
            and s % bk == 0 and s % SELECT_Q == 0
            and 8 * s * d <= _RESIDENT_BYTES
            and 4 * s * _LANES <= _RESIDENT_BYTES)


def _index_tile(ki, qi_ref, w_ref, scale, heads):
    """The indexer's scores of a tile, keys down: ki (bk, 128); qi_ref
    (bq, heads * 128); w_ref (heads, bq) -> (bk, bq) float32."""
    total = None
    for j in range(heads):
        dots = jax.lax.dot_general(
            ki, qi_ref[:, j * _LANES:(j + 1) * _LANES], _NT,
            preferred_element_type=jnp.float32) * scale
        part = jnp.maximum(dots, 0.0) * w_ref[j:j + 1, :]
        total = part if total is None else total + part
    return total


def _causal(i, j, bq, bk):
    key = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    query = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    return key, key <= query


def _chosen(scores, key, causal, theta, cut):
    """theta, cut (1, bq): key chosen where it is at or before the query and
    its score is above the threshold, or on it and not after the cut."""
    return causal & ((scores > theta)
                     | ((scores == theta) & (key.astype(jnp.float32) <= cut)))


def _pack(chosen):
    """(bk, bq) bool -> (bk // 32, bq) int32: bit ``b`` of word row ``w``
    holds key row ``w + (bk // 32) * b``, so that each bit is a whole block
    of rows."""
    rows = chosen.shape[0] // 32
    bits = chosen.astype(jnp.int32)
    words = bits[:rows]
    for b in range(1, 32):
        words = words | (bits[b * rows:(b + 1) * rows] << b)
    return words


def _unpack(words):
    """``_pack``'s inverse: (bk // 32, bq) int32 -> (bk, bq) bool."""
    return jnp.concatenate([(words >> b) & 1 for b in range(32)], axis=0) != 0


def _ordered(x):
    """float32 -> int32 with the same order (no NaN)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ _LOW, b)


def _unordered(b):
    return jax.lax.bitcast_convert_type(jnp.where(b < 0, b ^ _LOW, b),
                                        jnp.float32)


# ---- the choice ----------------------------------------------------------------

def _select_kernel(ki_ref, qi_ref, w_ref, theta_ref, cut_ref, lse_ref,
                   hits_ref, keys_ref, *, bq, bk, n_k, top_k, scale, heads,
                   index_bits):
    i = pl.program_id(0)
    last = (i * bq + bq - 1) // bk

    def each_tile(body, init):
        """``body(j, carry)`` over the key tiles up to the diagonal."""
        def step(j, carry):
            return jax.lax.cond(j <= last, lambda c: body(j, c),
                                lambda c: c, carry)
        return jax.lax.fori_loop(0, n_k, step, init)

    def fill(j, carry):
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        scores = _index_tile(ki_ref[rows, :], qi_ref, w_ref, scale, heads)
        _, causal = _causal(i, j, bq, bk)
        keys_ref[rows, :] = _ordered(jnp.where(causal, scores, -jnp.inf))
        return carry

    each_tile(fill, 0)
    zero = jnp.zeros((1, bq), jnp.float32)

    def count(pred):
        """sum over the key tiles of ``pred(j, keys)`` (bk, bq) bool."""
        def body(j, acc):
            rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
            hit = pred(j, keys_ref[rows, :]).astype(jnp.float32)
            return acc + jnp.sum(hit, axis=0, keepdims=True)
        return each_tile(body, zero)

    # the top_k-th largest key of each column, bit by bit from the sign
    prefix = jnp.where(count(lambda j, kb: kb >= 0) >= top_k, 0, _INT_MIN)

    def bit(n, carry):
        prefix, step = carry
        cand = prefix | step
        prefix = jnp.where(count(lambda j, kb: kb >= cand) >= top_k, cand,
                           prefix)
        return prefix, jax.lax.shift_right_logical(step, np.int32(1))

    theta, _ = jax.lax.fori_loop(0, 31, bit,
                                 (prefix.astype(jnp.int32), np.int32(1 << 30)))
    # of the keys equal to it, the first (top_k - keys above it) are chosen:
    # the cut is the index of the last of them
    need = top_k - count(lambda j, kb: kb > theta)

    def index_bit(n, carry):
        cut, step = carry
        cand = cut | step

        def pred(j, kb):
            key = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            return (kb == theta) & (key < cand)
        cut = jnp.where(count(pred) < need, cand, cut)
        return cut, jax.lax.shift_right_logical(step, np.int32(1))

    top_bit = np.int32(1 << (index_bits - 1))  # graphlint: disable=GL101 a static int, not a traced value
    cut, _ = jax.lax.fori_loop(0, index_bits, index_bit,
                               (jnp.zeros((1, bq), jnp.int32), top_bit))
    query = i * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    # a query with no more than top_k keys before it chooses them all
    every = query < top_k
    theta_f = jnp.where(every, -jnp.inf, _unordered(theta))
    cut_f = jnp.where(every, np.float32(2 ** 30), cut.astype(jnp.float32))

    def top(j, acc):
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        return jnp.maximum(acc, jnp.max(_unordered(keys_ref[rows, :]),
                                        axis=0, keepdims=True))

    m = each_tile(top, jnp.full((1, bq), -jnp.inf, jnp.float32))
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, hits_ref.shape[-1]), 1)

    def total(j, carry):
        acc, hits = carry
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        scores = _unordered(keys_ref[rows, :])
        key, causal = _causal(i, j, bq, bk)
        chosen = _chosen(scores, key, causal, theta_f, cut_f)
        acc = acc + jnp.sum(jnp.where(chosen, jnp.exp(scores - m), 0.0),
                            axis=0, keepdims=True)
        n = jnp.sum(chosen.astype(jnp.float32), axis=(0, 1), keepdims=True)
        return acc, jnp.where(lanes == j, n, hits)

    acc, hits = each_tile(total, (zero, jnp.zeros(hits_ref.shape,
                                                  jnp.float32)))
    theta_ref[...] = theta_f
    cut_ref[...] = cut_f
    lse_ref[...] = m + jnp.log(acc)
    hits_ref[...] = hits


@functools.partial(jax.jit, static_argnames=("top_k", "heads", "scale",
                                             "bk", "interpret"))
def select(qi, ki, w_t, *, top_k, heads, scale, bk, interpret):
    """qi (S, heads * 128), ki (S, 128) padded; w_t (heads, S) float32 ->
    (theta, cut as float32, lse of the chosen scores) each (1, S), and the
    chosen keys of each (SELECT_Q queries, bk keys) pair (S / SELECT_Q, 1,
    S / bk)."""
    s = ki.shape[0]
    bq, n_k = SELECT_Q, s // bk
    row = pl.BlockSpec((1, bq), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_select_kernel, bq=bq, bk=bk, n_k=n_k,
                          top_k=top_k, scale=scale, heads=heads,
                          index_bits=max(1, (s - 1).bit_length())),
        out_shape=(jax.ShapeDtypeStruct((1, s), jnp.float32),
                   jax.ShapeDtypeStruct((1, s), jnp.float32),
                   jax.ShapeDtypeStruct((1, s), jnp.float32),
                   jax.ShapeDtypeStruct((s // bq, 1, n_k), jnp.float32)),
        grid=(s // bq,),
        in_specs=[pl.BlockSpec((s, _LANES), lambda i: (0, 0)),
                  pl.BlockSpec((bq, qi.shape[1]), lambda i: (i, 0)),
                  pl.BlockSpec((heads, bq), lambda i: (0, i))],
        out_specs=(row, row, row,
                   pl.BlockSpec((None, 1, n_k), lambda i: (i, 0, 0))),
        scratch_shapes=[pltpu.VMEM((s, bq), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="dsa_select",
    )(ki, qi, w_t)


# ---- attention over the chosen keys ------------------------------------------

def _each_head(body, r):
    def step(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, r, step, 0)


def _head(h, d):
    """Head ``h``'s lane block of a (rows, r * d) window."""
    return slice(None), pl.ds(pl.multiple_of(h * d, d), d)


def _tile_mask(i, j, bq, bk, ki_ref, qi_ref, w_ref, theta_ref, cut_ref,
               scale, heads):
    """(the tile's indexer scores, its chosen keys), both (bk, bq)."""
    scores = _index_tile(ki_ref[...], qi_ref, w_ref, scale, heads)
    key, causal = _causal(i, j, bq, bk)
    return scores, _chosen(scores, key, causal, theta_ref[...], cut_ref[...])


def _fwd_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref,
                theta_ref, cut_ref, o_ref, lse_ref, n_ref, mask_ref, m_ref,
                l_ref, acc_ref, n_acc, *, bq, bk, d, r, hq, scale, heads):
    t = pl.program_id(0)
    i, j = qb_ref[t], kb_ref[t]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        n_acc[...] = jnp.zeros_like(n_acc)

    # the tile's choice, once for every head, and for the backward
    _, chosen = _tile_mask(i, j, bq, bk, ki_ref, qi_ref, w_ref, theta_ref,
                           cut_ref, scale, heads)
    n_acc[...] += jnp.sum(chosen.astype(jnp.float32), axis=0, keepdims=True)
    mask_ref[...] = _pack(chosen)

    def head(h):
        group = _head(h // r, d)
        k, v = k_ref[group], v_ref[group]
        s = jax.lax.dot_general(k, q_ref[_head(h, d)], _NT,
                                preferred_element_type=jnp.float32)
        s = jnp.where(chosen, s * (d ** -0.5), -jnp.inf)    # (bk, bq)
        m_prev = m_ref[h]                                   # (1, bq)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        # a column with no chosen key yet keeps -inf: exp against 0
        m_use = jnp.where(m_next == -jnp.inf, 0.0, m_next)
        p = jnp.exp(s - m_use)
        alpha = jnp.exp(m_prev - m_use)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=0, keepdims=True)
        m_ref[h] = m_next
        acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
            v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)

    _each_head(head, hq)

    @pl.when(j == (i * bq + bq - 1) // bk)
    def _store():
        def one(h):
            o = acc_ref[h] / l_ref[h]                       # (d, bq)
            o_ref[_head(h, d)] = o.T.astype(o_ref.dtype)
            lse_ref[h] = m_ref[h] + jnp.log(l_ref[h])

        _each_head(one, hq)
        n_ref[...] = n_acc[...]


def _bwd_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc,
                dv_acc, *, bq, bk, d, r, last):
    t = pl.program_id(1)
    i, j = qb_ref[t], kb_ref[t]

    @pl.when(t == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    chosen = _unpack(mask_ref[...])
    k, v = k_ref[...], v_ref[...]
    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)

    def head(h):
        q_r, do_r = q_ref[_head(h, d)], do_ref[_head(h, d)]
        s = jax.lax.dot_general(k, q_r, _NT,
                                preferred_element_type=jnp.float32)
        p = jnp.where(chosen, jnp.exp(s * (d ** -0.5) - lse_ref[h]), 0.0)
        dv_acc[rows, :] += jnp.dot(p.astype(do_r.dtype), do_r,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do_r, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[h])).astype(q_r.dtype)
        dk_acc[rows, :] += jnp.dot(ds, q_r,
                                   preferred_element_type=jnp.float32)
        dq_acc[h] += jax.lax.dot_general(k, ds, _TN,
                                         preferred_element_type=jnp.float32)

    _each_head(head, r)

    @pl.when(j == (i * bq + bq - 1) // bk)
    def _store_q():
        def one(h):
            dq = dq_acc[h] * (d ** -0.5)                    # (d, bq)
            dq_ref[_head(h, d)] = dq.T.astype(dq_ref.dtype)

        _each_head(one, r)

    @pl.when(t == last)
    def _store_kv():
        dk_ref[...] = (dk_acc[...] * (d ** -0.5)).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _kl_kernel(qb_ref, kb_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref,
               theta_ref, cut_ref, lse_i_ref, kl_ref, dqi_ref, dw_ref,
               dki_ref, pa_acc, pi_acc, dqi_acc, dw_acc, dki_acc, *, bq, bk,
               d, r, hkv, last, scale, heads):
    t = pl.program_id(0)
    i, j = qb_ref[t], kb_ref[t]

    @pl.when(t == 0)
    def _init_k():
        dki_acc[...] = jnp.zeros_like(dki_acc)

    @pl.when(j == 0)
    def _init_q():
        pa_acc[...] = jnp.zeros_like(pa_acc)
        pi_acc[...] = jnp.zeros_like(pi_acc)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    scores, chosen = _tile_mask(i, j, bq, bk, ki_ref, qi_ref, w_ref,
                                theta_ref, cut_ref, scale, heads)
    # the target: every head's probabilities over the chosen keys, summed
    total = jnp.zeros((bk, bq), jnp.float32)
    for g in range(hkv):
        k = k_ref[:, g * d:(g + 1) * d]
        for h in range(g * r, (g + 1) * r):
            s = jax.lax.dot_general(k, q_ref[:, h * d:(h + 1) * d], _NT,
                                    preferred_element_type=jnp.float32)
            total += jnp.exp(s * (d ** -0.5) - lse_ref[h])
    p = jnp.where(chosen, total * (1.0 / (hkv * r)), 0.0)
    live = p > 0
    pa_acc[...] += jnp.sum(jnp.where(live, p * jnp.log(
        jnp.where(live, p, 1.0)), 0.0), axis=0, keepdims=True)
    pi_acc[...] += jnp.sum(p * jnp.where(chosen, scores, 0.0), axis=0,
                           keepdims=True)
    # the loss's gradient with respect to the scores: softmax - target
    d_scores = jnp.where(chosen, jnp.exp(scores - lse_i_ref[...]), 0.0) - p
    ki = ki_ref[...]
    ki_t = ki.T                                             # (128, bk)
    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
    for n in range(heads):
        qi = qi_ref[:, n * _LANES:(n + 1) * _LANES]          # (bq, 128)
        dots = jax.lax.dot_general(ki, qi, _NT,
                                   preferred_element_type=jnp.float32) * scale
        dw_acc[n:n + 1, :] += jnp.sum(d_scores * jnp.maximum(dots, 0.0),
                                      axis=0, keepdims=True)
        dd = jnp.where(dots > 0, d_scores * w_ref[n:n + 1, :] * scale,
                       0.0).astype(qi.dtype)                # (bk, bq)
        dqi_acc[n] += jnp.dot(ki_t, dd, preferred_element_type=jnp.float32)
        dki_acc[rows, :] += jnp.dot(dd, qi,
                                    preferred_element_type=jnp.float32)

    @pl.when(j == (i * bq + bq - 1) // bk)
    def _store_q():
        kl_ref[...] = pa_acc[...] - pi_acc[...] + lse_i_ref[...]
        dw_ref[...] = dw_acc[...]
        for n in range(heads):
            dqi_ref[:, n * _LANES:(n + 1) * _LANES] = dqi_acc[n].T.astype(
                dqi_ref.dtype)

    @pl.when(t == last)
    def _store_k():
        dki_ref[...] = dki_acc[...].astype(dki_ref.dtype)


def _params(parallel: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * parallel + ("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT)


def _indexer_specs(bq, bk, heads, at):
    """Windows of the indexer's padded queries, key, weights and of the
    choice's rows; ``at(t, qb, kb) -> (query block, key block)``."""
    def q_block(*a):
        return (at(*a)[0], 0)

    def k_block(*a):
        return (at(*a)[1], 0)

    def row(*a):
        return (0, at(*a)[0])

    return [pl.BlockSpec((bq, heads * _LANES), q_block),
            pl.BlockSpec((bk, _LANES), k_block),
            pl.BlockSpec((heads, bq), row),
            pl.BlockSpec((1, bq), row), pl.BlockSpec((1, bq), row)]


@functools.partial(jax.jit, static_argnames=("hkv", "heads", "scale", "bq",
                                             "bk", "interpret"))
def attend_fwd(q, k, v, qi, ki, w_t, theta, cut, *, hkv, heads, scale, bq,
               bk, interpret):
    """q (S, Hq * D); k, v (S, Hkv * D) -> o as q, lse (Hkv, R, 1, S), the
    keys each query attended to (1, S) float32, and the choice of each pair
    of ``_pairs(S, bq, bk)`` packed (``_pack``) as (pairs, bk // 32, bq)
    int32: (2080, 16, 512), 68.2 MB, at 32768 positions and blocks of 512.
    Every head of a tile in one grid step, so that the tile's choice is
    formed once; the backward reads it from the mask."""
    s, hd = q.shape
    d = k.shape[1] // hkv
    hq = hd // d
    qb, kb = _pairs(s, bq, bk)
    at = lambda t, qb, kb: (qb[t], kb[t])   # noqa: E731
    rows_q = pl.BlockSpec((bq, hd), lambda t, qb, kb: (qb[t], 0))
    rows_k = pl.BlockSpec((bk, k.shape[1]), lambda t, qb, kb: (kb[t], 0))
    o, lse, attended, mask = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, d=d, r=hq // hkv, hq=hq,
                          scale=scale, heads=heads),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((hq, 1, s), jnp.float32),
                   jax.ShapeDtypeStruct((1, s), jnp.float32),
                   jax.ShapeDtypeStruct((qb.shape[0], bk // 32, bq),
                                        jnp.int32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(qb.shape[0],),
            in_specs=[rows_q, rows_k, rows_k]
            + _indexer_specs(bq, bk, heads, at),
            out_specs=(rows_q, pl.BlockSpec(
                (hq, 1, bq), lambda t, qb, kb: (0, 0, qb[t])),
                pl.BlockSpec((1, bq), lambda t, qb, kb: (0, qb[t])),
                pl.BlockSpec((None, bk // 32, bq),
                             lambda t, qb, kb: (t, 0, 0))),
            scratch_shapes=[pltpu.VMEM((hq, 1, bq), jnp.float32),
                            pltpu.VMEM((hq, 1, bq), jnp.float32),
                            pltpu.VMEM((hq, d, bq), jnp.float32),
                            pltpu.VMEM((1, bq), jnp.float32)]),
        compiler_params=_params(0), interpret=interpret,
        name="dsa_attend_fwd",
    )(qb, kb, q, k, v, qi, ki, w_t, theta, cut)
    return o, lse.reshape(hkv, hq // hkv, 1, s), attended, mask


@functools.partial(jax.jit, static_argnames=("hkv", "bq", "bk", "interpret"))
def attend_bwd(q, k, v, do, lse, delta, mask, *, hkv, bq, bk, interpret):
    """The cotangents of q, k and v, each as its operand; ``mask`` the
    forward's packed choice (``attend_fwd``), one block a pair, read by each
    key-value head's pass: the indexer's scores are not formed here."""
    s, hd = q.shape
    d = k.shape[1] // hkv
    r = hd // d // hkv
    qb, kb = _pairs(s, bq, bk)
    rows_q = pl.BlockSpec((bq, r * d), lambda g, t, qb, kb: (qb[t], g))
    rows_k = pl.BlockSpec((bk, d), lambda g, t, qb, kb: (kb[t], g))
    whole_k = pl.BlockSpec((s, d), lambda g, t, qb, kb: (0, g))
    stats = pl.BlockSpec((None, r, 1, bq),
                         lambda g, t, qb, kb: (g, 0, 0, qb[t]))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, d=d, r=r,
                          last=qb.shape[0] - 1),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(hkv, qb.shape[0]),
            in_specs=[rows_q, rows_k, rows_k, rows_q, stats, stats,
                      pl.BlockSpec((None, bk // 32, bq),
                                   lambda g, t, qb, kb: (t, 0, 0))],
            out_specs=(rows_q, whole_k, whole_k),
            scratch_shapes=[pltpu.VMEM((r, d, bq), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32)]),
        compiler_params=_params(1), interpret=interpret,
        name="dsa_attend_bwd",
    )(qb, kb, q, k, v, do, lse, delta, mask)


@functools.partial(jax.jit, static_argnames=("hkv", "heads", "scale", "bq",
                                             "bk", "interpret"))
def index_loss(q, k, lse, qi, ki, w_t, theta, cut, lse_i, *, hkv, heads,
               scale, bq, bk, interpret):
    """(the indexer's loss a query (1, S), its gradient with respect to qi
    (as qi), w_t (as w_t, float32) and ki (as ki)); lse (Hq, 1, S) the
    attention's log-sum-exp, lse_i (1, S) the chosen scores'."""
    s, hd = q.shape
    d = k.shape[1] // hkv
    r = hd // d // hkv
    qb, kb = _pairs(s, bq, bk)
    at = lambda t, qb, kb: (qb[t], kb[t])   # noqa: E731
    row = pl.BlockSpec((1, bq), lambda t, qb, kb: (0, qb[t]))
    return pl.pallas_call(
        functools.partial(_kl_kernel, bq=bq, bk=bk, d=d, r=r, hkv=hkv,
                          last=qb.shape[0] - 1, scale=scale, heads=heads),
        out_shape=(jax.ShapeDtypeStruct((1, s), jnp.float32),
                   jax.ShapeDtypeStruct(qi.shape, qi.dtype),
                   jax.ShapeDtypeStruct(w_t.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ki.shape, ki.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(qb.shape[0],),
            in_specs=[pl.BlockSpec((bq, hd), lambda t, qb, kb: (qb[t], 0)),
                      pl.BlockSpec((bk, k.shape[1]),
                                   lambda t, qb, kb: (kb[t], 0)),
                      pl.BlockSpec((hd // d, 1, bq),
                                   lambda t, qb, kb: (0, 0, qb[t]))]
            + _indexer_specs(bq, bk, heads, at) + [row],
            out_specs=(row,
                       pl.BlockSpec((bq, qi.shape[1]),
                                    lambda t, qb, kb: (qb[t], 0)),
                       pl.BlockSpec((heads, bq), lambda t, qb, kb: (0, qb[t])),
                       pl.BlockSpec((s, _LANES), lambda t, qb, kb: (0, 0))),
            scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32),
                            pltpu.VMEM((1, bq), jnp.float32),
                            pltpu.VMEM((heads, _LANES, bq), jnp.float32),
                            pltpu.VMEM((heads, bq), jnp.float32),
                            pltpu.VMEM((s, _LANES), jnp.float32)]),
        compiler_params=_params(0), interpret=interpret, name="dsa_kl",
    )(qb, kb, q, k, lse, qi, ki, w_t, theta, cut, lse_i)


# ---- the op ------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attend(static, q, k, v, qi, ki, w_t, theta, cut):
    return _attend_fwd(static, q, k, v, qi, ki, w_t, theta, cut)[0]


def _attend_fwd(static, q, k, v, qi, ki, w_t, theta, cut):
    """The forward kernel; its residuals are q, k, v, o, lse and the packed
    choice, which the backward reads in place of the indexer's operands.
    The mask is not named: a checkpoint under ``KEEP_CHOICE`` runs this
    again before the backward, and one layer's mask is live at a time."""
    hkv, heads, scale, bq, bk, interpret = static
    o, lse, attended, mask = attend_fwd(q, k, v, qi, ki, w_t, theta, cut,
                                        hkv=hkv, heads=heads, scale=scale,
                                        bq=bq, bk=bk, interpret=interpret)
    return (o, lse, attended), (q, k, v, mask, o, lse)


def _attend_bwd(static, res, g):
    hkv, _, _, bq, bk, interpret = static
    q, k, v, mask, o, lse = res
    do = g[0].astype(q.dtype)
    s, hd = q.shape
    d = k.shape[1] // hkv
    # the transpose is traced on its own: name it, so that its device time
    # is found under the block's scopes wherever it is traced from
    with jax.named_scope("dsa"), jax.named_scope("dsa_attend"):
        delta = jnp.sum((o.astype(jnp.float32) * g[0].astype(jnp.float32))
                        .reshape(s, hd // d, d), -1)           # (S, Hq)
        delta = delta.T.reshape(hkv, -1, 1, s)
        dq, dk, dv = attend_bwd(q, k, v, do, lse, delta, mask, hkv=hkv,
                                bq=bq, bk=bk, interpret=interpret)
    # the choice and the indexer's operands take no gradient from here
    return dq, dk, dv, None, None, None, None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kl(static, q, k, lse, qi, ki, w_t, theta, cut, lse_i):
    return _kl_fwd(static, q, k, lse, qi, ki, w_t, theta, cut, lse_i)[0]


def _kl_fwd(static, q, k, lse, qi, ki, w_t, theta, cut, lse_i):
    hkv, heads, scale, bq, bk, interpret, di = static
    kl, dqi, dw, dki = index_loss(q, k, lse, qi, ki, w_t, theta, cut, lse_i,
                                  hkv=hkv, heads=heads, scale=scale, bq=bq,
                                  bk=bk, interpret=interpret)
    # lanes di.. of a head's block are zeros (the other side's operand is
    # padded with zeros): keep the gradient without them
    dqi = dqi.reshape(qi.shape[0], heads, _LANES)[..., :di]
    return jnp.sum(kl), checkpoint_name((dqi, dw, dki[:, :di]), KL_GRAD)


def _kl_bwd(static, res, g):
    dqi, dw, dki = res
    # the target is held constant and the choice takes no gradient: the
    # loss reaches the indexer's queries, key and weights alone
    with jax.named_scope("dsa"), jax.named_scope("dsa_index_loss"):
        dqi = _padded(dqi).reshape(dqi.shape[0], -1)
        dki = _padded(dki)
        return (None, None, None, (g * dqi).astype(dqi.dtype),
                (g * dki).astype(dki.dtype), g * dw, None, None, None)


_kl.defvjp(_kl_fwd, _kl_bwd)


def _padded(x):
    """(..., Di) -> (..., 128): an indexer head in a lane block of its own."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, _LANES - x.shape[-1])])


def sparse_attention(q, k, v, qi, w, ki, top_k: int, sample=(),
                     interpret: bool = False,
                     blocks: Optional[Tuple[int, int]] = None):
    """``ops/sparse_attention.py::sparse_attention`` by the kernels: the
    same arguments and results.  ``blocks`` is a test's (block_q, block_k)
    in place of the file's."""
    from mx_rcnn_tpu.ops.sparse_attention import (CUT, THETA, chosen_mask,
                                                  count_misses,
                                                  indexer_scores)
    s, hq, d = q.shape
    hkv, (heads, di) = k.shape[1], qi.shape[1:]
    bq, bk = blocks or (BLOCK_Q, BLOCK_K)
    scale = float(di) ** -0.5
    # (hkv, heads, scale, bq, bk, interpret): the custom_vjps' static part,
    # the loss's with the indexer's head size after it
    static = (hkv, heads, scale, bq, bk, interpret)
    qi_p = _padded(qi).reshape(s, heads * _LANES)
    ki_p = _padded(ki.astype(qi.dtype))
    w_t = w.T.astype(jnp.float32)
    with jax.named_scope("dsa_index"):
        theta, cut, lse_i, hits = select(
            *jax.lax.stop_gradient((qi_p, ki_p, w_t)), top_k=top_k,
            heads=heads, scale=scale, bk=bk, interpret=interpret)
        theta = checkpoint_name(theta, THETA)
        cut = checkpoint_name(cut, CUT)
        lse_i = checkpoint_name(lse_i, LSE_I)
    q2, k2, v2 = (q.reshape(s, hq * d), k.reshape(s, hkv * d),
                  v.reshape(s, hkv * d))
    with jax.named_scope("dsa_attend"):
        o, lse, attended = _attend(static, q2, k2, v2, qi_p, ki_p, w_t, theta,
                                   cut)
        misses = count_misses(attended[0], jnp.arange(s), top_k)
    with jax.named_scope("dsa_index_loss"):
        kl = _kl(static + (di,), q2, k2,
                 jax.lax.stop_gradient(lse).reshape(hq, 1, s), qi_p, ki_p,
                 w_t, theta, cut, lse_i)
    # the chosen keys a (tile x tile) pair holds, from the choice's
    # (SELECT_Q x bk) counts
    n = s // bk
    per = hits.reshape(n, -1, n).sum(1) > 0
    touched = jnp.sum(per & jnp.tril(jnp.ones((n, n), bool))) / (
        n * (n + 1) // 2)
    if sample:
        rows = jnp.asarray(sample, jnp.int32)
        with jax.named_scope("dsa_index"):
            scores = indexer_scores(*jax.lax.stop_gradient(
                (qi[rows], w[rows], ki)))
            picks = chosen_mask(scores, theta[0, rows],
                                cut[0, rows].astype(jnp.int32), rows,
                                jnp.arange(s)).astype(jnp.int8)
    else:
        picks = jnp.zeros((0, s), jnp.int8)
    return o.reshape(s, hq, d), kl, touched, picks, misses
