"""Learned sparse attention: DeepSeek Sparse Attention (DSA,
DeepSeek-V3.2-Exp's technical report), one sequence of one layer.

An indexer scores every causal (query, key) pair,

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s] / sqrt(Di)),   s <= t,

with ``J`` query heads ``qi`` of width ``Di``, one key head ``ki`` shared by
all and a weight a query head ``w`` (the caller's projections, scaled as it
states them).  Query ``t`` attends to the ``top_k`` keys of largest
``I[t, .]`` among ``s <= t`` (all of them while ``t < top_k``), ties to the
lower index as ``lax.top_k`` breaks them; one set serves every attention
head.  The choice is exact: it is held as a threshold a query, ``theta[t]``
(the ``top_k``-th largest score) and ``cut[t]`` (the last key whose score
equals ``theta`` that is still chosen), so that key ``s`` is chosen where
``s <= t`` and ``I > theta`` or ``I == theta and s <= cut``.  Nothing but
these two numbers a query is kept between the choice and its use.

Attention over the chosen keys is grouped-query softmax attention, scale
``D^-1/2``, scores and softmax in float32, products on operands in
``q.dtype`` accumulated in float32.  Beside the output the op returns the
indexer's training signal of the report's sparse stage, summed over the
sequence's queries: ``KL(p[t] || softmax_{s in S_t}(I[t, s]))`` with the
target ``p[t, s]`` the main attention's probabilities summed over heads
over the head count, held constant (its gradient is not taken).  Its
gradient reaches ``qi``, ``w`` and ``ki`` alone; the output's reaches ``q``,
``k`` and ``v`` alone (the choice is not differentiable).

Two ways to the same result, chosen at trace time as ``causal_gqa``
chooses: on a TPU where the shapes tile, the Mosaic kernels of
``ops/sparse_attention_pallas.py``; anywhere else the blocked path below,
which never forms the whole (S, S) matrix: queries in blocks of ``block_q``,
and the sequence in ``GROUPS`` groups of queries, a group's blocks seeing
the keys up to the group's end only.  The first form visits every causal
tile and masks by the threshold: correct, not yet sparse in its work.

Also returned, for the step's counters: the share of (``TILE`` x ``TILE``)
causal tiles that hold at least one chosen key of their query tile (what a
kernel that skips empty tiles could save), the chosen keys of a few query
rows (``sample``), which the benchmark compares with its reference's
choice, and how many queries attention took another number of keys than
the choice holds (``count_misses``: 0 where the attention's mask is the
choice, whose scores it forms again).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# what a checkpoint around a call keeps: the choice, a threshold and a cut
# a query, and on the kernels' path the chosen scores' log-sum-exp a query
# (``sparse_attention_pallas.LSE_I``) and the indexer loss's gradient
# (``sparse_attention_pallas.KL_GRAD``)
THETA, CUT = "sparse_attention.theta", "sparse_attention.cut"
KEEP_CHOICE = jax.checkpoint_policies.save_only_these_names(
    THETA, CUT, "sparse_attention.lse_i", "sparse_attention.kl_grad")
# the (queries, keys) tile ``tiles_touched`` counts, where the sequence's
# groups are at least that long
TILE = 512
# groups of queries the blocked path cuts the sequence into
GROUPS = 8


def indexer_scores(qi, w, ki):
    """qi (Q, J, Di); w (Q, J) float32; ki (K, Di) -> I (Q, K) float32: the
    products on operands in ``qi.dtype`` accumulated in float32, the head
    sum in float32."""
    dots = jnp.einsum("qjd,kd->qjk", qi, ki.astype(qi.dtype),
                      preferred_element_type=jnp.float32)
    dots = dots * (qi.shape[-1] ** -0.5)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)


def choose(scores, top_k: int):
    """scores (Q, K) float32, the keys after a query -inf -> (theta, cut),
    each (Q,): the ``top_k``-th largest score of a row (``lax.top_k``'s),
    and the last index among the chosen whose score equals it."""
    vals, idx = jax.lax.top_k(scores, min(top_k, scores.shape[-1]))
    theta = vals[:, -1]
    cut = jnp.max(jnp.where(vals == theta[:, None], idx, -1), axis=-1)
    return theta, cut.astype(jnp.int32)


def chosen_mask(scores, theta, cut, qpos, kpos):
    """The chosen keys of each row: (Q, K) bool."""
    return (kpos[None, :] <= qpos[:, None]) & (
        (scores > theta[:, None])
        | ((scores == theta[:, None]) & (kpos[None, :] <= cut[:, None])))


def count_misses(attended, qpos, top_k: int):
    """The queries at ``qpos`` whose keys attended to, ``attended``, are
    not ``min(t + 1, top_k)``: () int32."""
    return jnp.sum(attended != jnp.minimum(qpos + 1, top_k)).astype(
        jnp.int32)


def _barrier_sum(x):
    return jax.lax.optimization_barrier(jnp.sum(x, axis=-1, keepdims=True))


def _barrier_max(x):
    return jax.lax.optimization_barrier(jnp.max(x, axis=-1, keepdims=True))


@jax.custom_vjp
def _softmax_rows(s):
    """Softmax along the last axis with its row reductions behind an
    ``optimization_barrier`` (``ops/attention.py::softmax_rows`` says why),
    the transpose under this op's scope."""
    return _softmax_fwd(s)[0]


def _softmax_fwd(s):
    e = jnp.exp(s - _barrier_max(s))
    p = e / _barrier_sum(e)
    return p, p


def _softmax_bwd(p, g):
    with jax.named_scope("dsa_attend"):
        return (p * (g - _barrier_sum(g * p)),)


_softmax_rows.defvjp(_softmax_fwd, _softmax_bwd)


@jax.custom_vjp
def _log_softmax_rows(x):
    """Log-softmax along the last axis, -inf entries left out, the row
    reductions behind barriers."""
    return _log_softmax_fwd(x)[0]


def _log_softmax_fwd(x):
    m = _barrier_max(x)
    y = x - (m + jnp.log(_barrier_sum(jnp.exp(x - m))))
    return y, jnp.exp(y)


def _log_softmax_bwd(p, g):
    with jax.named_scope("dsa_index_loss"):
        return (g - p * _barrier_sum(g),)


_log_softmax_rows.defvjp(_log_softmax_fwd, _log_softmax_bwd)


def _attend_and_kl(q, k, v, qi, w, ki, theta, cut, lo, top_k):
    """One block of queries at positions ``lo ..`` against the keys
    ``0 .. K``: (o (Q, Hq, D), sum of the block's KL, ``count_misses`` of
    the keys it attended to)."""
    nq, hq, d = q.shape
    nk, hkv = k.shape[0], k.shape[1]
    qpos = lo + jnp.arange(nq)
    kpos = jnp.arange(nk)
    with jax.named_scope("dsa_attend"):
        scores = indexer_scores(qi, w, ki)
        chosen = chosen_mask(jax.lax.stop_gradient(scores), theta, cut, qpos,
                             kpos)
        s = jnp.einsum("qgrd,kgd->qgrk", q.reshape(nq, hkv, hq // hkv, d), k,
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        p = _softmax_rows(jnp.where(chosen[:, None, None, :], s, -jnp.inf))
        o = jnp.einsum("qgrk,kgd->qgrd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        misses = count_misses(jnp.sum(chosen, -1), qpos, top_k)
    with jax.named_scope("dsa_index_loss"):
        target = jax.lax.stop_gradient(p.sum((1, 2)) / hq)
        logq = _log_softmax_rows(jnp.where(chosen, scores, -jnp.inf))
        live = chosen & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0))
                            - jnp.where(live, logq, 0.0)), 0.0))
    return o.reshape(nq, hq, d).astype(q.dtype), kl, misses


def _layout(s: int, block_q: int):
    """(queries a group, the tile): ``GROUPS`` groups where whole blocks
    divide them, fewer otherwise."""
    if s % block_q:
        raise ValueError(f"sequence {s} is no multiple of the block {block_q}")
    groups = math.gcd(s // block_q, GROUPS)
    size = s // groups
    tile = math.gcd(min(TILE, size), size)
    if tile % block_q and block_q % tile:
        raise ValueError(f"block {block_q} and tile {tile} do not nest")
    return size, tile


def _tiles_touched(hits, s: int, tile: int):
    """hits (query tiles, key tiles) bool -> the share of causal tiles
    that hold a chosen key."""
    n = s // tile
    causal = jnp.tril(jnp.ones((n, n), bool))
    return jnp.sum(hits & causal) / (n * (n + 1) // 2)


def _blocked(q, k, v, qi, w, ki, top_k: int, block_q: int,
             sample: Sequence[int]):
    s = q.shape[0]
    size, tile = _layout(s, block_q)
    nb = size // block_q

    def blocks(x, g):
        part = x[g * size:(g + 1) * size]
        return part.reshape((nb, block_q) + x.shape[1:])

    def starts(g):
        return g * size + jnp.arange(nb) * block_q

    thetas, cuts, hits, picks = [], [], [], []
    rows_s = jnp.asarray(sample, jnp.int32)
    for g in range(s // size):
        end = (g + 1) * size
        # the choice: no gradient reaches it
        with jax.named_scope("dsa_index"):
            qi_s, w_s, ki_s = jax.lax.stop_gradient((qi, w, ki[:end]))

            def select(a):
                qib, wb, lo = a
                scores = indexer_scores(qib, wb, ki_s)
                qpos, kpos = lo + jnp.arange(block_q), jnp.arange(end)
                scores = jnp.where(kpos[None, :] <= qpos[:, None], scores,
                                   -jnp.inf)
                theta, cut = choose(scores, top_k)
                picked = chosen_mask(scores, theta, cut, qpos, kpos)
                rows = min(block_q, tile)
                hit = picked.reshape(block_q // rows, rows, end // tile,
                                     tile).any((1, 3))
                # the sample's rows that lie in this block, as chosen here
                local = rows_s - lo
                inside = (local >= 0) & (local < block_q)
                pick = picked[jnp.clip(local, 0, block_q - 1)] & inside[:,
                                                                      None]
                return theta, cut, jnp.pad(hit, ((0, 0), (0, (s - end)
                                                          // tile))), pick

            theta, cut, hit, pick = jax.lax.map(
                select, (blocks(qi_s, g), blocks(w_s, g), starts(g)))
        thetas.append(theta.reshape(-1))
        cuts.append(cut.reshape(-1))
        hits.append(hit.reshape(-1, s // tile))
        picks.append(jnp.pad(pick.any(0), ((0, 0), (0, s - end))))
    theta = checkpoint_name(jnp.concatenate(thetas), THETA)
    cut = checkpoint_name(jnp.concatenate(cuts), CUT)
    hits = jnp.concatenate(hits)
    if tile > block_q:
        hits = hits.reshape(s // tile, -1, s // tile).any(1)

    outs, kls, misses = [], [], []
    part = jax.checkpoint(_attend_and_kl, static_argnums=(9,))
    for g in range(s // size):
        end = (g + 1) * size
        k_g, v_g, ki_g = k[:end], v[:end], ki[:end]
        o, kl, miss = jax.lax.map(
            lambda a: part(a[0], k_g, v_g, a[1], a[2], ki_g, a[3], a[4],
                           a[5], top_k),
            (blocks(q, g), blocks(qi, g), blocks(w, g),
             blocks(theta, g), blocks(cut, g), starts(g)))
        outs.append(o.reshape((size,) + q.shape[1:]))
        kls.append(jnp.sum(kl))
        misses.append(jnp.sum(miss))
    return (jnp.concatenate(outs), sum(kls), _tiles_touched(hits, s, tile),
            functools.reduce(jnp.logical_or, picks).astype(jnp.int8),
            sum(misses))


def sparse_attention(q, k, v, qi, w, ki, top_k: int, block_q: int = 256,
                     sample: Sequence[int] = (), interpret: bool = False):
    """One sequence: q (S, Hq, D); k, v (S, Hkv, D), Hq a multiple of Hkv;
    qi (S, J, Di); w (S, J) float32; ki (S, Di) -> (o (S, Hq, D) in
    ``q.dtype``, the sum over queries of the indexer's KL () float32, the
    share of causal tiles touched () float32, the chosen keys of the rows
    ``sample`` (len, S) int8, the queries whose attention took another
    number of keys than the choice holds () int32).  ``block_q``: queries
    a block of the blocked path; ``interpret`` runs the TPU kernels in the
    Pallas interpreter (tests only).  A ``jax.checkpoint`` around a call
    under ``KEEP_CHOICE`` keeps the choice and, on the kernels' path, the
    indexer loss's gradient, and recomputes everything else."""
    if interpret or jax.default_backend() == "tpu":  # graphlint: disable=GL203 the platform name is a host string, fixed at trace time
        from mx_rcnn_tpu.ops import sparse_attention_pallas as kernels

        if kernels.tiles(q.shape[0], q.shape[-1], qi.shape[-1]) and (
                q.shape[0] >= top_k):
            return kernels.sparse_attention(q, k, v, qi, w, ki, top_k,
                                            sample, interpret)
    return _blocked(q, k, v, qi, w, ki, top_k, block_q, sample)
