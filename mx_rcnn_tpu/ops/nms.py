"""Greedy non-maximum suppression with static shapes, jit-safe.

Reference: ``rcnn/cython/cpu_nms.pyx``, ``rcnn/cython/gpu_nms.pyx`` +
``rcnn/cython/nms_kernel.cu`` (the classic triangular-bitmask CUDA kernel,
64-box blocks) and the wrapper selection in ``rcnn/processing/nms.py``
(``py_nms_wrapper`` / ``cpu_nms_wrapper`` / ``gpu_nms_wrapper``).

TPU-native design: the reference returns a *variable-length* keep list,
which XLA cannot express.  Here NMS is reformulated as a fixed-shape
computation:

1. sort boxes by score (descending; invalid boxes sink to the end),
2. tile-wise suppression sweep — for each tile of T sorted boxes, first
   suppress by the *final* survivors of earlier tiles, then resolve the
   within-tile greedy chain by fixed-point iteration (the suppressor of a
   suppressed box does not count).  This reproduces exact sequential greedy
   NMS semantics while doing O(K/T) vectorized (T, K) IoU sweeps on the VPU
   instead of a length-K sequential loop,
3. compact the survivors into a fixed-size index buffer with a cumsum
   scatter (padded with -1).

The whole thing lives inside the same XLA program as the network — no
device→host bounce like the reference's Python ``proposal`` CustomOp.

Batched variants (r6): :func:`nms_batch` / :func:`nms_mask_batch` run the
sweep for B images in ONE loop nest — each tile step is a single
(B·T, K) IoU sweep with per-image-blocked keep-mask updates instead of B
vmap-sliced (T, K) sweeps — decision-exact per image vs the per-image
sweep (the oracle), same auto-selection guards.  ``ops/proposal.py`` and
the eval postprocess (``core/tester.py``) feed these.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.boxes import bbox_overlaps

# plain float, NOT jnp.float32: a module-level jnp constant would
# initialize the XLA backend at import time, breaking the
# jax.distributed.initialize ordering multi-host needs
_NEG = -1e10

# Suppression-sweep backends: the Pallas kernel (ops/nms_pallas.py) keeps
# the whole sweep in VMEM; the jnp sweep below is the oracle the tests
# compare it against.  ``backend=None`` or 'auto' = the kernel on a TPU, the
# jnp sweep on any other platform (see _resolve_backend — on a TPU it never
# gives way quietly).

# the kernel's tile: whole 128-lane registers, independent of the padding
# tile (greedy NMS is exact at any tile size)
_KERNEL_TILE = 128
# what the kernel holds in VMEM grows with K through its (K, 4) box block
# alone: padded to whole 128-lane rows it is 512 B a box, twice that under
# vmap (the next image's block is fetched while this one runs) — the IoU
# blocks are (tile, 1024) at most whatever K is.  K=12032 (the 12000-box
# recipe) compiles and matches the jnp sweep on a v5e (chip_smoke.py checks
# it); compiled for a described v5e, under the train step's vmap K=14336
# fits the 16 MiB scoped-VMEM default and K=15360 does not (alone, 24576
# fits).  Nothing above 12032 has run, so the bound stays where it was: an
# input between them fails in the compiler, loudly
_KERNEL_MAX_K = 16384


def _resolve_backend(backend: Optional[str], k: int, tile: int) -> str:
    """'auto' → the sweep that runs for ``k`` padded boxes in tiles of
    ``tile``.  Off-TPU that is the jnp sweep.  On a TPU it is the kernel
    for every input of at least one tile; an input the kernel cannot take
    (a tile that is not whole 128-lane registers, or more boxes than its
    VMEM blocks hold) raises instead of quietly running the slower sweep —
    pass ``backend='jnp'`` to choose that one.  Inputs smaller than one
    tile (k < tile_size, so tile == k) have no tiling for the kernel to
    do and run the jnp single-tile sweep."""
    b = backend or "auto"
    if b != "auto":
        return b
    if jax.default_backend() != "tpu":  # graphlint: disable=GL203 the platform name is a host string, fixed at trace time
        return "jnp"
    if tile % _KERNEL_TILE == 0 and k <= _KERNEL_MAX_K:
        return "pallas"
    if k == tile:
        return "jnp"
    raise ValueError(
        f"NMS of {k} boxes in tiles of {tile} cannot run in the Pallas "
        f"kernel on this TPU (needs a tile that is a multiple of "
        f"{_KERNEL_TILE} and at most {_KERNEL_MAX_K} boxes); pass "
        f"backend='jnp' to run the XLA sweep instead")


def _chain_fixed_point(iou_self: jnp.ndarray, alive0: jnp.ndarray,
                       t: int) -> jnp.ndarray:
    """Resolve the within-tile greedy chain by fixed-point iteration: the
    suppressor of a suppressed box does not count.  ``iou_self`` is the
    (..., t, t) strictly-upper-triangular suppressor relation, ``alive0``
    the (..., t) candidates after suppression by earlier tiles.  Works
    batched: extra iterations past one row's fixed point leave that row
    unchanged (``alive0 & ~sup(alive)`` is stationary at a fixed point),
    so a joint loop over many images makes per-image decisions exactly.
    """

    def fix_cond(state):
        alive, prev, it = state
        return jnp.logical_and(jnp.any(alive != prev), it < t)

    def fix_body(state):
        alive, _, it = state
        sup = jnp.any(iou_self & alive[..., :, None], axis=-2)
        return alive0 & ~sup, alive, it + 1

    alive, _, _ = jax.lax.while_loop(
        fix_cond, fix_body, (alive0, jnp.zeros_like(alive0), 0)
    )
    return alive


def _suppression_sweep(
    boxes: jnp.ndarray,
    alive_init: jnp.ndarray,
    iou_threshold: float,
    tile_size: int,
) -> jnp.ndarray:
    """Exact greedy NMS over score-sorted ``boxes``; returns the keep mask.

    ``alive_init`` marks candidate boxes (invalid/padded boxes False).
    """
    k = boxes.shape[0]
    t = tile_size
    if k % t != 0:
        raise ValueError(f"padded box count {k} must be a multiple of tile {t}")
    num_tiles = k // t
    # Within-tile suppressor relation: strictly-earlier boxes only.
    tri = jnp.arange(t)[:, None] < jnp.arange(t)[None, :]  # tri[s, j]: s before j

    # Tile 0 is peeled out of the loop: it has no earlier tiles, so the
    # suppress-by-earlier-survivors term would be a (t, k) all-False
    # CONSTANT — XLA constant-folds the reduction over it at compile time,
    # which stalled >1 s per compile at eval-postprocess shapes
    # (MULTICHIP_r05 slow-operation alarms).  Peeling also skips the
    # useless (t, k−t) IoU block when k fits one tile.
    iou0 = bbox_overlaps(boxes[:t], boxes[:t]) > iou_threshold
    alive_first = _chain_fixed_point(iou0 & tri, alive_init[:t], t)
    keep = jax.lax.dynamic_update_slice(alive_init, alive_first, (0,))

    def tile_body(i, keep):
        start = i * t
        tile_boxes = jax.lax.dynamic_slice(boxes, (start, 0), (t, 4))
        tile_alive0 = jax.lax.dynamic_slice(keep, (start,), (t,))
        iou = bbox_overlaps(tile_boxes, boxes)  # (t, k)
        overlaps = iou > iou_threshold
        # (a) suppression by final survivors of earlier tiles
        earlier = (jnp.arange(k) < start) & keep
        sup_prev = jnp.any(overlaps & earlier[None, :], axis=1)
        alive0 = tile_alive0 & ~sup_prev
        # (b) within-tile greedy chain, fixed-point iteration
        iou_self = jax.lax.dynamic_slice(overlaps, (0, start), (t, t)) & tri
        alive = _chain_fixed_point(iou_self, alive0, t)
        return jax.lax.dynamic_update_slice(keep, alive, (start,))

    return jax.lax.fori_loop(1, num_tiles, tile_body, keep)


def _suppression_sweep_batched(
    boxes: jnp.ndarray,
    alive_init: jnp.ndarray,
    iou_threshold: float,
    tile_size: int,
) -> jnp.ndarray:
    """Exact greedy NMS over B images at once: boxes (B, K, 4) score-sorted
    per image, alive_init (B, K) → keep (B, K).

    The per-image sweep under ``vmap`` turns into B loop *states* advancing
    through one batched ``fori_loop`` × ``while_loop`` chain whose per-tile
    work is a stack of small (T, K) slabs; here the batch axis is folded
    into the sweep itself, so every tile step issues ONE (B·T, K) IoU
    sweep + keep-mask update on the VPU (blocked per image — cross-image
    IoUs are never formed) and the within-tile fixed point iterates
    jointly.  Decisions are exact per image (see ``_chain_fixed_point``);
    ``tests/test_nms.py`` pins equality against the per-image sweep.
    """
    b, k = alive_init.shape
    t = tile_size
    if k % t != 0:
        raise ValueError(f"padded box count {k} must be a multiple of tile {t}")
    num_tiles = k // t
    tri = jnp.arange(t)[:, None] < jnp.arange(t)[None, :]
    overlaps_of = jax.vmap(bbox_overlaps)  # (B, q, 4) x (B, k, 4) → (B, q, k)

    # tile 0 peeled, exactly like the per-image sweep (no all-False
    # constant term, no constant-folding stall)
    iou0 = overlaps_of(boxes[:, :t], boxes[:, :t]) > iou_threshold
    alive_first = _chain_fixed_point(iou0 & tri[None], alive_init[:, :t], t)
    keep = jnp.concatenate([alive_first, alive_init[:, t:]], axis=1)

    def tile_body(i, keep):
        start = i * t
        tile_boxes = jax.lax.dynamic_slice(boxes, (0, start, 0), (b, t, 4))
        tile_alive0 = jax.lax.dynamic_slice(keep, (0, start), (b, t))
        overlaps = overlaps_of(tile_boxes, boxes) > iou_threshold  # (B, t, k)
        earlier = (jnp.arange(k)[None, :] < start) & keep  # (B, k)
        sup_prev = jnp.any(overlaps & earlier[:, None, :], axis=2)
        alive0 = tile_alive0 & ~sup_prev
        iou_self = jax.lax.dynamic_slice(
            overlaps, (0, 0, start), (b, t, t)) & tri[None]
        alive = _chain_fixed_point(iou_self, alive0, t)
        return jax.lax.dynamic_update_slice(keep, alive, (0, start))

    return jax.lax.fori_loop(1, num_tiles, tile_body, keep)


def _mask_pad_sort(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    valid: Optional[jnp.ndarray],
    tile_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int, int]:
    """Rank-generic sweep preamble shared by the per-image and batched
    paths: mask invalid scores, pad the box axis to a tile multiple, sort
    by descending score.  boxes (..., K, 4) / scores (..., K) →
    (boxes_sorted, order, alive0, pad, tile)."""
    k = scores.shape[-1]
    boxes = boxes.astype(jnp.float32)
    scores = scores.astype(jnp.float32)
    if valid is not None:
        scores = jnp.where(valid, scores, _NEG)
    t = min(tile_size, max(k, 1))
    pad = (-k) % t
    if pad:
        boxes = jnp.pad(boxes,
                        [(0, 0)] * (boxes.ndim - 2) + [(0, pad), (0, 0)])
        scores = jnp.pad(scores,
                         [(0, 0)] * (scores.ndim - 1) + [(0, pad)],
                         constant_values=_NEG)
    order = jnp.argsort(-scores, axis=-1)
    boxes_sorted = jnp.take_along_axis(boxes, order[..., None], axis=-2)
    alive0 = jnp.take_along_axis(scores, order, axis=-1) > _NEG / 2
    return boxes_sorted, order, alive0, pad, t


def _run_sweep(
    boxes_sorted: jnp.ndarray,
    alive0: jnp.ndarray,
    iou_threshold: float,
    t: int,
    backend: Optional[str],
    interpret: bool,
) -> jnp.ndarray:
    """Backend resolution + sweep dispatch, shared by the per-image and
    batched paths (rank-dispatched: (K, 4) runs the per-image sweep,
    (B, K, 4) the cross-image batched one; the Pallas kernel is per-image
    either way, vmapped over the batch — the shape the chip runs
    validated).  ``interpret`` runs the kernel in the Pallas interpreter
    and is only ever what the caller passed: off-TPU, ``backend='pallas'``
    without it fails to lower ("Only interpret mode is supported on CPU
    backend") instead of silently interpreting.
    """
    k = alive0.shape[-1]
    # one name for the sweep whatever implements it (metadata only): the
    # benchmark's nms.device_ms reads the device time under it
    with jax.named_scope("nms_sweep"):
        if _resolve_backend(backend, k, t) == "pallas":
            from mx_rcnn_tpu.ops.nms_pallas import suppression_sweep_pallas

            # the kernel runs at its own tile whatever the padding tile is
            # (greedy NMS is exact at any tile): 128 is what the chip runs
            # proved, and keeps the fixed point's (T, T) chain block at 16
            # vector registers
            tp = _KERNEL_TILE if t % _KERNEL_TILE == 0 else t

            def pallas_one(bx, al):
                return suppression_sweep_pallas(bx, al, iou_threshold, tp,
                                                interpret=interpret)

            if boxes_sorted.ndim == 3:
                return jax.vmap(pallas_one)(boxes_sorted, alive0)
            return pallas_one(boxes_sorted, alive0)
        if boxes_sorted.ndim == 3:
            return _suppression_sweep_batched(boxes_sorted, alive0,
                                              iou_threshold, t)
        return _suppression_sweep(boxes_sorted, alive0, iou_threshold, t)


def _sorted_survivors(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    valid: Optional[jnp.ndarray],
    iou_threshold: float,
    tile_size: int,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, int, int]:
    """Shared preamble of all four entry points: mask invalid scores, pad
    to a tile multiple, sort by score, run the suppression sweep.

    Rank-generic — (K, ·) serves nms/nms_mask, (B, K, ·) serves
    nms_batch/nms_mask_batch (the sweep dispatch is per rank, see
    ``_run_sweep``).  Returns (order, keep, pad, tile) over the padded
    arrays, both in sorted order.  Keeping this in ONE place keeps the
    per-image and cross-image paths — and the training (nms) and eval
    (nms_mask) paths — identical by construction.
    """
    boxes_sorted, order, alive0, pad, t = _mask_pad_sort(
        boxes, scores, valid, tile_size)
    keep = _run_sweep(boxes_sorted, alive0, iou_threshold, t, backend,
                      interpret)
    return order, keep, pad, t


@functools.partial(jax.jit, static_argnames=("iou_threshold", "max_output",
                                             "tile_size", "backend",
                                             "interpret"))
def nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    max_output: int,
    valid: Optional[jnp.ndarray] = None,
    tile_size: int = 256,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy NMS; returns up to ``max_output`` surviving indices by score.

    Args:
      boxes: (K, 4) in (x1, y1, x2, y2).
      scores: (K,).
      iou_threshold: suppression threshold (ref NMS_THRESH).
      max_output: static output size.
      valid: optional (K,) bool mask of real (non-padding) boxes.
    Returns:
      (indices, out_valid): indices (max_output,) int32 into the input arrays
      ordered by descending score, padded with -1; out_valid (max_output,)
      bool marks real outputs.
    """
    if boxes.shape[0] == 0:
        return (jnp.full((max_output,), -1, jnp.int32),
                jnp.zeros((max_output,), bool))
    order, keep, _, t = _sorted_survivors(boxes, scores, valid,
                                          iou_threshold, tile_size, backend,
                                          interpret)
    # Compact survivors (in score order) into a fixed buffer.
    pos = jnp.cumsum(keep) - 1
    emit = keep & (pos < max_output)
    out_idx = jnp.full((max_output,), -1, dtype=jnp.int32)
    out_idx = out_idx.at[jnp.where(emit, pos, max_output)].set(
        order.astype(jnp.int32), mode="drop"
    )
    out_valid = out_idx >= 0
    return out_idx, out_valid


@functools.partial(jax.jit, static_argnames=("iou_threshold", "tile_size",
                                             "backend", "interpret"))
def nms_mask(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: Optional[jnp.ndarray] = None,
    tile_size: int = 256,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Greedy NMS returning a keep mask in the *original* box order.

    Used by the eval path (per-class NMS, ref ``rcnn/core/tester.py —
    pred_eval``) where all candidates are postprocessed host-side.
    """
    k = boxes.shape[0]
    if k == 0:
        return jnp.zeros((0,), bool)
    order, keep_sorted, pad, _ = _sorted_survivors(
        boxes, scores, valid, iou_threshold, tile_size, backend, interpret)
    keep = jnp.zeros((k + pad,), dtype=bool).at[order].set(keep_sorted)
    return keep[:k]


@functools.partial(jax.jit, static_argnames=("iou_threshold", "max_output",
                                             "tile_size", "backend",
                                             "interpret"))
def nms_batch(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    max_output: int,
    valid: Optional[jnp.ndarray] = None,
    tile_size: int = 256,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-image batched :func:`nms`: boxes (B, K, 4), scores (B, K) →
    ((B, max_output) indices, (B, max_output) valid).

    Decision-exact per image against ``vmap(nms)`` (pinned by
    ``tests/test_nms.py``) but runs ONE tile-sweep loop nest whose steps
    process all images together — the per-image serialized
    ``fori_loop``×``while_loop`` chains under vmap become a single (B·T, K)
    sweep per tile step (see :func:`_suppression_sweep_batched`).
    """
    b, k = scores.shape
    if k == 0:
        return (jnp.full((b, max_output), -1, jnp.int32),
                jnp.zeros((b, max_output), bool))
    order, keep, _, t = _sorted_survivors(
        boxes, scores, valid, iou_threshold, tile_size, backend, interpret)
    pos = jnp.cumsum(keep, axis=1) - 1
    emit = keep & (pos < max_output)

    def compact(order_i, pos_i, emit_i):
        out = jnp.full((max_output,), -1, dtype=jnp.int32)
        return out.at[jnp.where(emit_i, pos_i, max_output)].set(
            order_i.astype(jnp.int32), mode="drop")

    out_idx = jax.vmap(compact)(order, pos, emit)
    return out_idx, out_idx >= 0


@functools.partial(jax.jit, static_argnames=("iou_threshold", "tile_size",
                                             "backend", "interpret"))
def nms_mask_batch(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: Optional[jnp.ndarray] = None,
    tile_size: int = 256,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Cross-image batched :func:`nms_mask`: (B, K, ...) → (B, K) keep
    mask in original box order.  The eval postprocess flattens its
    (images × classes) double vmap into one (N·C, R) call so every
    per-class NMS in the batch shares a single sweep loop nest."""
    b, k = scores.shape
    if k == 0:
        return jnp.zeros((b, 0), bool)
    order, keep_sorted, pad, _ = _sorted_survivors(
        boxes, scores, valid, iou_threshold, tile_size, backend, interpret)
    keep = jax.vmap(
        lambda o, ks: jnp.zeros((k + pad,), dtype=bool).at[o].set(ks)
    )(order, keep_sorted)
    return keep[:, :k]
