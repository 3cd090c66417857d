"""Pallas TPU kernel for the greedy-NMS suppression sweep.

Reference: ``rcnn/cython/nms_kernel.cu`` — the classic triangular-bitmask
CUDA NMS (64-box blocks, device-wide bitmask, host-side final reduction).

This is the Pallas counterpart of ``ops/nms.py — _suppression_sweep`` (the
jnp fallback, which stays as the oracle): boxes arrive score-sorted, the
kernel walks tiles of T boxes through a sequential 1-D grid, and for each
tile (a) suppresses by the finalized survivors of all earlier tiles, then
(b) resolves the within-tile greedy chain by fixed-point iteration —
bit-identical decisions to sequential greedy NMS.

Only the pairs that can suppress are formed.  Step (a) of tile i needs the
columns before the tile and no others, so it walks ``[0, i*T)`` in chunks
of at most ``_CHUNK`` columns, each a (T, chunk) IoU block folded into a
(T, T) running max, in rolled loops whose trip counts come from the grid
index; with the (T, T) self-block of step (b) that is T²·n·(n+1)/2 pairs
for n = K/T tiles, half of the n·T·K a full (T, K) slab a tile would form.

Why a kernel helps on TPU: the whole sweep runs out of VMEM — the box
coordinates and the keep mask never round-trip to HBM between tiles, the
IoU blocks never leave the core, and the keep mask accumulates in place
across grid steps (constant-index output block + input/output aliasing),
where the XLA version re-materializes masks per fori_loop iteration.

Numerics mirror ``ops/boxes.py — bbox_overlaps`` exactly (+1 pixel areas,
``union > 0`` guard, ``iou > threshold`` suppression), so the two backends
agree decision-for-decision, not just approximately.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Widest column chunk of step (a).  The pairs are the time (7.4-7.7 ps
# each over step (a), the vector units busy), so what the width buys is
# independent work inside one loop pass to hide the IoU chain's latency
# behind, until the block's temporaries outgrow the registers' reach.  One
# v5e chip, 16 images under vmap as the train step runs it, ms a call at
# K = 6144 / 12032 (PR 32; the (T, K) slab this replaced: 4.94 / 20.62).
# One width, the rest in T-wide passes: 128 5.71 / 20.20, 256 4.03 /
# 13.61, 512 3.43 / 11.06, 768 3.41 / 10.72, 1024 3.46 / 10.71, 1536
# 3.67 / 11.08, 2048 3.89 / 11.44.  The rest in halving widths instead:
# from 512 3.38 / 10.95, from 1024 3.19 / 10.19 (chosen); 2048, 512, 128:
# 3.27 / 10.25.  Of the chosen one's time 0.92 / 1.77 is not step (a).
_CHUNK = 1024


def _sweep_kernel(boxes_ref, boxes_t_ref, keep_in_ref, keep_ref, *,
                  tile: int, iou_threshold: float):
    """One grid step = one tile of ``tile`` sorted boxes.

    boxes_ref: (K, 4) fp32 score-sorted boxes (VMEM).
    boxes_t_ref: (4, K) the same boxes transposed (broadcast-friendly rows).
    keep_in_ref / keep_ref: (1, K) fp32 alive mask.  The input is aliased
      onto the output HBM buffer, but the output VMEM window is NOT
      guaranteed to hold the aliased input's contents before the first
      write — so program 0 explicitly seeds the output block from the input
      block; later grid steps read/write only ``keep_ref`` (constant-index
      block, resident in VMEM across the sequential grid).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _seed():
        keep_ref[:, :] = keep_in_ref[:, :]

    t = tile
    start = pl.multiple_of(i * t, t)

    tile_boxes = boxes_ref[pl.ds(start, t), :]          # (T, 4)
    tx1 = tile_boxes[:, 0:1]                            # (T, 1)
    ty1 = tile_boxes[:, 1:2]
    tx2 = tile_boxes[:, 2:3]
    ty2 = tile_boxes[:, 3:4]
    area_t = (tx2 - tx1 + 1.0) * (ty2 - ty1 + 1.0)      # (T, 1)

    def over_block(off, width):
        """(T, width) 1.0/0.0: tile row overlaps column ``off + j`` by more
        than the threshold — semantics of bbox_overlaps.  The columns come
        from ref slices (Mosaic does not lower dynamic_slice of a computed
        value)."""
        x1 = boxes_t_ref[0:1, pl.ds(off, width)]        # (1, width)
        y1 = boxes_t_ref[1:2, pl.ds(off, width)]
        x2 = boxes_t_ref[2:3, pl.ds(off, width)]
        y2 = boxes_t_ref[3:4, pl.ds(off, width)]
        iw = jnp.maximum(jnp.minimum(tx2, x2) - jnp.maximum(tx1, x1) + 1.0,
                         0.0)
        ih = jnp.maximum(jnp.minimum(ty2, y2) - jnp.maximum(ty1, y1) + 1.0,
                         0.0)
        inter = iw * ih
        area_a = (x2 - x1 + 1.0) * (y2 - y1 + 1.0)      # (1, width)
        union = area_t + area_a - inter
        iou = jnp.where(union > 0, inter / jnp.maximum(union, 1e-12), 0.0)
        return (iou > iou_threshold).astype(jnp.float32)

    # (a) suppression by the finalized survivors of earlier tiles.  Only
    # columns before the tile can suppress it, so only those pairs are
    # formed: tile i walks the columns [0, i*T) in chunks and folds each
    # chunk's ``over * keep`` into a (T, T) running max (elementwise; the
    # one cross-lane reduction comes after the loops).  The trip counts
    # depend on the grid index and the loops stay rolled.  The widest
    # chunk takes all the columns it divides; what is left, always a
    # multiple of T, goes to widths that halve down to T — each of those
    # loops runs at most once, and no pair at or after the tile is formed.
    def fold(width):
        def body(c, acc):
            off = pl.multiple_of(c * width, width)
            hit = over_block(off, width) * keep_ref[0:1, pl.ds(off, width)]
            for g in range(width // t):
                acc = jnp.maximum(acc, hit[:, g * t:(g + 1) * t])
            return acc
        return body

    # no chunk wider than the columns before the last tile: none could run,
    # and a slice wider than the ref does not trace
    width = t
    while 2 * width <= min(_CHUNK, boxes_t_ref.shape[1] - t):
        width *= 2
    acc = jnp.zeros((t, t), jnp.float32)
    lo = 0
    while width >= t:
        hi = start // width
        acc = jax.lax.fori_loop(lo, hi, fold(width), acc)
        lo = 2 * hi
        width //= 2
    sup_prev = jnp.max(acc, axis=1, keepdims=True)      # (T, 1)
    tile_alive0 = keep_ref[0, pl.ds(start, t)].reshape(t, 1)
    alive0 = tile_alive0 * (1.0 - sup_prev)             # (T, 1)

    # (b) within-tile greedy chain: strictly-earlier suppressors only
    over_self = over_block(start, t)                    # (T, T)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    colt = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    tri = (row < colt).astype(jnp.float32)
    chain = over_self * tri                             # chain[s, j]

    def fix_cond(state):
        alive, prev, it = state
        return jnp.logical_and(jnp.any(alive != prev), it < t)

    def fix_body(state):
        alive, _, it = state
        sup = jnp.max(chain * alive, axis=0).reshape(t, 1)  # (T, 1)
        return alive0 * (1.0 - sup), alive, it + 1

    alive, _, _ = jax.lax.while_loop(
        fix_cond, fix_body, (alive0, jnp.zeros_like(alive0), 0))
    keep_ref[0, pl.ds(start, t)] = alive.reshape(t)


@functools.partial(jax.jit, static_argnames=("iou_threshold", "tile_size",
                                             "interpret"))
def suppression_sweep_pallas(
    boxes: jnp.ndarray,
    alive_init: jnp.ndarray,
    iou_threshold: float,
    tile_size: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in Pallas replacement for ``ops/nms.py — _suppression_sweep``.

    Args:
      boxes: (K, 4) fp32 boxes sorted by descending score; K must be a
        multiple of ``tile_size`` (the callers pad).
      alive_init: (K,) bool candidate mask (padding slots False).
      iou_threshold: suppression threshold.
      interpret: run the kernel in interpreter mode (CPU testing).
    Returns:
      (K,) bool keep mask — exact sequential-greedy-NMS survivors.
    """
    k = boxes.shape[0]
    t = tile_size
    if k % t != 0:
        raise ValueError(f"padded box count {k} must be a multiple of {t}")
    boxes = boxes.astype(jnp.float32)
    keep0 = alive_init.reshape(1, k).astype(jnp.float32)
    kernel = functools.partial(_sweep_kernel, tile=t,
                               iou_threshold=float(iou_threshold))
    keep = pl.pallas_call(
        kernel,
        grid=(k // t,),
        in_specs=[
            pl.BlockSpec((k, 4), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(boxes, boxes.T, keep0)
    return keep.reshape(k) > 0.5
