"""EVA attention (EvaByte's chunked linearized attention), a layer's core
after its projections.

Positions fall in blocks of ``window`` (``s(t) = window * floor(t /
window)``) and in chunks of ``chunk`` (a divisor of the window).  Query
``t`` sees two key sets under one softmax:

* the exact keys of its own block up to itself, ``s(t) <= j <= t``;
* one pooled key and value for every chunk wholly before its block, ``c <
  s(t) / chunk``:

      kbar_c = sum_{j in c} softmax_j(mu_h . k_j / sqrt(D)) k_j
      vbar_c = sum_{j in c} softmax_j(phi_h . k_j / sqrt(D)) v_j

  with ``mu_h`` and ``phi_h`` learned vectors a head (``pool_chunks``).

So ``o_t = sum_{j in L(t)} p_tj v_j + sum_{c in P(t)} p_tc vbar_c``, ``p``
the softmax over ``q_t . k / sqrt(D)`` of both sets together; scores and
softmax in float32, the products on operands in ``q.dtype`` accumulated in
float32.  With ``window >= S`` there are no summaries and this is causal
attention.

Two ways to the same result, chosen at trace time as ``causal_gqa``
chooses: on a TPU where the shapes tile, the Mosaic kernels of
``ops/eva_pallas.py`` over ``[kbar; k]``; anywhere else the blocked path
below (a block of queries against its window's summaries and keys, a
``jax.checkpoint`` each).  Beside ``o`` the op returns the share of the
softmax's (query, key) pairs that were summaries, in percent, and how many
(query, head) rows took another number of keys than ``t - s(t) + 1 +
s(t) / chunk``: both read from the keys each row's mask let through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.ops import eva_pallas
from mx_rcnn_tpu.ops.attention import softmax_rows
from mx_rcnn_tpu.ops.eva_pallas import KEEP_EVA_RESIDUALS  # noqa: F401


def pool_chunks(k, v, mu, phi, chunk: int):
    """k, v (B, S, H, D); mu, phi (H, D) -> (kbar, vbar) (B, S / chunk, H,
    D) in ``k.dtype``: each chunk's keys and values weighted by a softmax
    over its positions of ``mu . k / sqrt(D)`` and ``phi . k / sqrt(D)``,
    the weights and sums in float32."""
    b, s, h, d = k.shape
    kc = k.reshape(b, s // chunk, chunk, h, d)
    vc = v.reshape(b, s // chunk, chunk, h, d)

    def pooled(vec, vals):
        logits = jnp.einsum("bnchd,hd->bnch", kc, vec.astype(k.dtype),
                            preferred_element_type=jnp.float32) * d ** -0.5
        w = jax.nn.softmax(logits, axis=2)
        return jnp.einsum("bnch,bnchd->bnhd", w, vals.astype(jnp.float32)
                          ).astype(k.dtype)

    return pooled(mu, kc), pooled(phi, vc)


def expected_keys(s: int, window: int, chunk: int):
    """(keys, summaries) query ``t`` takes, (S,) each."""
    t = np.arange(s)
    start = window * (t // window)
    return t - start + 1 + start // chunk, start // chunk


def _counters(took, summaries, s, window, chunk):
    """took, summaries (..., S): keys and summary keys each row took ->
    (summary share in percent, rows whose count is not the expected)."""
    want = jnp.asarray(expected_keys(s, window, chunk)[0], jnp.float32)
    pct = 100.0 * jnp.sum(summaries) / jnp.sum(took)
    return pct, jnp.sum((took != want).astype(jnp.int32))


def _kernels_take(q, window, chunk, blocks, interpret) -> bool:
    _, s, _, d = q.shape
    return ((interpret or jax.default_backend() == "tpu")
            and eva_pallas.tiles(s, d, window, chunk, blocks))


def eva_attention(q, k, v, kbar, vbar, window: int, chunk: int,
                  block_q: int = 256, blocks=None, interpret: bool = False):
    """q, k, v (B, S, H, D); kbar, vbar (B, S / chunk, H, D) -> (o (B, S,
    H, D) in ``q.dtype``, summary share of the pairs in percent, rows that
    took another number of keys).  ``window`` and ``chunk`` divide S (a
    window of S or more: no summaries).  On a TPU (``interpret``: the
    kernels in the Pallas interpreter, on any platform), with shapes the
    kernels tile, ``eva_pallas.eva_flash`` (``blocks``: a test's (block_q,
    block_k)); else the blocked path in blocks of ``block_q`` queries."""
    s = q.shape[1]
    window = min(window, s)
    if s % window or window % chunk:
        raise ValueError(f"window {window} does not tile sequence {s} in "
                         f"chunks of {chunk}")
    if _kernels_take(q, window, chunk, blocks, interpret):
        o, cnt = eva_pallas.eva_flash(
            q, jnp.concatenate([kbar, k], 1), jnp.concatenate([vbar, v], 1),
            window, chunk, blocks, interpret)
        return (o, *_counters(cnt[:, :, 0], cnt[:, :, 1], s, window, chunk))
    return _blocked(q, k, v, kbar, vbar, window, chunk, block_q)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _block(qb, keys, vals, lo: int, n: int):
    """qb (B, H, Q, D) at window offsets lo..lo+Q; keys, vals (B, H, n + K,
    D): the window's ``n`` summaries, then its positions 0..K with K = lo +
    Q.  Returns (o, keys and summaries each row took (B, H, Q) twice)."""
    d = qb.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", qb, keys,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    qpos = lo + jnp.arange(qb.shape[2])
    kpos = jnp.arange(keys.shape[2]) - n
    seen = kpos[None, :] <= qpos[:, None]
    p = softmax_rows(jnp.where(seen, s, -jnp.inf))
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vals.dtype), vals,
                   preferred_element_type=jnp.float32).astype(qb.dtype)
    took = jnp.broadcast_to(jnp.sum(seen, -1).astype(jnp.float32),
                            qb.shape[:3])
    return o, took, jnp.full(qb.shape[:3], float(n))


def _blocked(q, k, v, kbar, vbar, window, chunk, block_q):
    """Heads go major before the blocks are cut; a window's blocks of
    queries see its summaries and its own keys up to the block's end."""
    b, s, h, d = q.shape
    block_q = min(block_q, window)
    if window % block_q:
        raise ValueError(f"window {window} is no multiple of block_q "
                         f"{block_q}")
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    kb, vb = kbar.transpose(0, 2, 1, 3), vbar.transpose(0, 2, 1, 3)
    outs, took, summaries = [], [], []
    for start in range(0, s, window):
        n = start // chunk
        for lo in range(0, window, block_q):
            end = start + lo + block_q
            o, t_all, t_sum = _block(
                qh[:, :, start + lo:end],
                jnp.concatenate([kb[:, :, :n], kh[:, :, start:end]], 2),
                jnp.concatenate([vb[:, :, :n], vh[:, :, start:end]], 2),
                lo, n)
            outs.append(o)
            took.append(t_all)
            summaries.append(t_sum)
    o = jnp.concatenate(outs, 2).transpose(0, 2, 1, 3)
    return (o, *_counters(jnp.concatenate(took, -1),
                          jnp.concatenate(summaries, -1), s, window, chunk))
