"""Causal grouped-query attention: ``softmax(q . k^T / sqrt(D) + causal
mask) . v``, scores and softmax in float32, the two products on operands in
``q.dtype`` accumulated in float32.  One result, two ways to it, and
``causal_gqa`` takes the one the platform and the shapes allow, read at
trace time:

* on a TPU, where head size and sequence tile (``attention_pallas.tiles``:
  head size a multiple of 128 lanes, sequence a multiple of the kernels'
  blocks), the Mosaic kernels of ``ops/attention_pallas.py``: score tiles,
  running maximum and sum and the accumulator stay in VMEM, forward and
  backward; heads of another width, or with values narrower than their
  queries (latent attention), enter padded with zeros;
* anywhere else (the CPU tests; ``nemotron_h_tiny``'s head size 16 on any
  platform) the blocked path below.  A block of ``block_q`` queries sees
  only the keys up to its own end, so the products above the diagonal are
  never formed and the score matrix of a block is (block_q, keys so far):
  the whole (S, S) matrix per head never exists.  Each block is a
  ``jax.checkpoint``: the backward pass recomputes a block's scores
  instead of keeping every block's.

``KEEP_FLASH_RESIDUALS`` is the kernels' checkpoint policy: a
``jax.checkpoint`` around a call under it keeps the forward kernel's ``o``
and log-sum-exp for the backward and recomputes everything else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.attention_pallas import KEEP_FLASH_RESIDUALS  # noqa: F401


@jax.custom_vjp
def softmax_rows(s):
    """Softmax along the last axis, forward and transpose, with every row
    reduction (the maximum, the sum, the transpose's dot) behind an
    ``optimization_barrier``.  Without it the TPU compiler rewrites
    ``s - max(s)`` as a reduce-window whose window is the whole row, at a
    cost quadratic in the row: 47 ms for one block of 256 queries against
    8192 keys, where the pass over its 0.5 GB takes 1.3 ms (PERF.md section
    6, PR 34)."""
    return _softmax_fwd(s)[0]


def _softmax_fwd(s):
    m = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    p = e / jax.lax.optimization_barrier(jnp.sum(e, axis=-1, keepdims=True))
    return p, p


def _softmax_bwd(p, g):
    # the transpose is traced on its own: name the op, so that its device
    # time is found under attention's scope wherever it is traced from
    with jax.named_scope("attention"):
        t = jax.lax.optimization_barrier(
            jnp.sum(g * p, axis=-1, keepdims=True))
        return (p * (g - t),)


softmax_rows.defvjp(_softmax_fwd, _softmax_bwd)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _block(qb, kb, vb, lo: int):
    """qb (B, G, R, Q, D) at positions lo..lo+Q; kb, vb (B, G, K, D) at
    positions 0..K with K = lo + Q."""
    d = qb.shape[-1]
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qb, kb,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    qpos = lo + jnp.arange(qb.shape[3])
    kpos = jnp.arange(kb.shape[2])
    s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
    p = softmax_rows(s)
    return jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(vb.dtype), vb,
                      preferred_element_type=jnp.float32).astype(qb.dtype)


def _kernel_width(q, k, v, interpret: bool) -> int:
    """The head size at which the Mosaic kernels run these operands, or 0
    where they do not: a TPU (or the interpreter) and, with query/key and
    value widths rounded up together to the 128 lanes, shapes the kernels
    tile.  Platform and shapes are read at trace time."""
    from mx_rcnn_tpu.ops import attention_pallas

    _, s, hq, d = q.shape
    wide = -(-max(d, v.shape[-1]) // 128) * 128
    takes = ((interpret or jax.default_backend() == "tpu")
             and hq % k.shape[2] == 0 and attention_pallas.tiles(s, wide))
    return wide if takes else 0


def _widened(t, wide: int):
    return jnp.pad(t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),))


def causal_gqa(q, k, v, block_q: int = 256, interpret: bool = False):
    """q (B, S, Hq, D); k (B, S, Hkv, D); v (B, S, Hkv, Dv) with Hq a
    multiple of Hkv (query head j reads key-value head j // (Hq // Hkv)).
    Scale D^-1/2, no positional term.  Returns (B, S, Hq, Dv) in
    ``q.dtype``.  ``Dv`` may differ from ``D`` (latent attention: 192
    against 128).

    On a TPU (``interpret`` runs the kernels in the Pallas interpreter
    instead: what a test passes, on any platform), with shapes the kernels
    tile, ``attention_pallas.flash_causal_gqa``; heads that are no multiple
    of the 128 lanes, or whose value width is not their query/key width,
    enter it padded with zeros to one width ``W`` (zeros add nothing to a
    score, and the padded value columns are cut off again), the queries
    times ``sqrt(W / D)`` so that the kernels' ``W^-1/2`` is ``D^-1/2``.
    Else the blocked path in blocks of ``block_q`` queries, which the
    kernels take no notice of."""
    wide = _kernel_width(q, k, v, interpret)
    if wide:
        from mx_rcnn_tpu.ops.attention_pallas import flash_causal_gqa

        d, dv = q.shape[-1], v.shape[-1]
        if wide == d == dv:
            return flash_causal_gqa(q, k, v, None, interpret)
        scaled = (q.astype(jnp.float32) * (wide / d) ** 0.5).astype(q.dtype)
        out = flash_causal_gqa(_widened(scaled, wide), _widened(k, wide),
                               _widened(v, wide), None, interpret)
        return out[..., :dv]
    return _blocked_causal_gqa(q, k, v, block_q)


def _blocked_causal_gqa(q, k, v, block_q: int):
    """Heads go major and positions next to the head size before the blocks
    are cut, so that a block's scores are plain batched (rows, D) x (D,
    keys) products whose keys are the minor axis: the softmax then reduces
    along the minor axis (``softmax_rows``)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} key-value heads")
    block_q = min(block_q, s)
    if s % block_q:
        raise ValueError(f"sequence {s} is no multiple of block_q {block_q}")
    qg = q.reshape(b, s, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)
    kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    outs = [_block(qg[:, :, :, lo:lo + block_q], kg[:, :, :lo + block_q],
                   vg[:, :, :lo + block_q], lo)
            for lo in range(0, s, block_q)]
    out = jnp.concatenate(outs, axis=3)            # (B, G, R, S, Dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, v.shape[-1])
