"""Fused RPN proposal generation: decode + clip + min-size + top-k + NMS + pad.

Reference: ``mx.symbol.Proposal`` (MXNet contrib C++/CUDA op) and its Python
twin ``rcnn/symbol/proposal.py — ProposalOperator`` — in the reference this
is a mid-graph CustomOp that copies RPN scores/deltas to the host, runs
NumPy + Cython NMS, and copies the ROIs back (the biggest per-step sync in
the reference hot loop, see SURVEY.md §3.1).

TPU-native design: a single jit-compatible function with **static shapes
end to end** — the variable-length survivor set of the reference becomes a
fixed ``(post_nms_top_n, 4)`` buffer plus a validity mask.  Invalid slots are
filled with the top surviving box so downstream ROI pooling always sees a
well-formed box; ``proposal_target`` masks them out via the validity flags
(padding boxes are never sampled as fg/bg — if they reach the sampled batch
as filler they are labelled -1/ignore and excluded from every loss).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.nms import nms, nms_batch


def _decode_filter_topk(scores, bbox_deltas, anchors, im_info,
                        pre_nms_top_n: int, min_size: int):
    """Stages 1–3 of the proposal op for ONE image: decode + clip,
    min-size filter, pre-NMS top-k.  Shared by the per-image and batched
    paths so their pre-NMS candidate sets are identical by construction.

    Returns (top_boxes (pre, 4), top_scores (pre,), top_valid (pre,))."""
    n = scores.shape[0]
    scores = scores.astype(jnp.float32)
    # 1. decode + clip to the real image extent
    proposals = bbox_pred(anchors, bbox_deltas.astype(jnp.float32))
    proposals = clip_boxes(proposals, (im_info[0], im_info[1]))
    # 2. min-size filter at input scale (ref: min_size * im_info[2])
    ws = proposals[:, 2] - proposals[:, 0] + 1.0
    hs = proposals[:, 3] - proposals[:, 1] + 1.0
    min_sz = min_size * im_info[2]
    size_ok = (ws >= min_sz) & (hs >= min_sz)
    scores = jnp.where(size_ok, scores, -jnp.inf)
    # 3. pre-NMS top-k (cap at N — small images have fewer anchors)
    pre = min(pre_nms_top_n, n)
    top_scores, top_idx = jax.lax.top_k(scores, pre)
    return proposals[top_idx], top_scores, jnp.isfinite(top_scores)


def _compact_rois(top_boxes, top_scores, keep_idx, keep_valid):
    """Stage 5 for ONE image: gather NMS survivors into the fixed buffer,
    filling padded slots with the best surviving box (slot 0 survives NMS
    by construction whenever any valid proposal exists)."""
    safe_idx = jnp.maximum(keep_idx, 0)
    rois = top_boxes[safe_idx]
    roi_scores = jnp.where(keep_valid, top_scores[safe_idx], 0.0)
    rois = jnp.where(keep_valid[:, None], rois, rois[0][None, :])
    return rois, roi_scores, keep_valid


@functools.partial(
    jax.jit,
    static_argnames=("pre_nms_top_n", "post_nms_top_n", "nms_thresh", "min_size"),
)
def propose(
    scores: jnp.ndarray,
    bbox_deltas: jnp.ndarray,
    anchors: jnp.ndarray,
    im_info: jnp.ndarray,
    pre_nms_top_n: int = 6000,
    post_nms_top_n: int = 300,
    nms_thresh: float = 0.7,
    min_size: int = 16,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Generate ROIs from one image's RPN outputs.

    Args:
      scores: (N,) foreground probabilities, N = H*W*A (framework HWA order).
      bbox_deltas: (N, 4) RPN regression output.
      anchors: (N, 4) shifted anchors for this feature grid (constant).
      im_info: (3,) = (img_height, img_width, im_scale) of the real image
        content inside the padded bucket (ref ``im_info`` blob).
      pre_nms_top_n / post_nms_top_n / nms_thresh / min_size: ref Proposal op
        attrs (TRAIN: 12000/2000/0.7/16; TEST: 6000/300/0.7/16).

    Returns:
      rois: (post_nms_top_n, 4) clipped proposal boxes.
      roi_scores: (post_nms_top_n,) their fg scores.
      roi_valid: (post_nms_top_n,) bool — False for padded slots.
    """
    # stages 1–3 (decode+clip, min-size, top-k) shared with the batched path
    top_boxes, top_scores, top_valid = _decode_filter_topk(
        scores, bbox_deltas, anchors, im_info, pre_nms_top_n, min_size)
    # 4. NMS + fixed-size compaction
    keep_idx, keep_valid = nms(
        top_boxes, top_scores, nms_thresh, post_nms_top_n, valid=top_valid
    )
    # 5. fill padded slots (see _compact_rois)
    return _compact_rois(top_boxes, top_scores, keep_idx, keep_valid)


def propose_batch(
    scores: jnp.ndarray,
    bbox_deltas: jnp.ndarray,
    anchors: jnp.ndarray,
    im_info: jnp.ndarray,
    *,
    pre_nms_top_n: int = 6000,
    post_nms_top_n: int = 300,
    nms_thresh: float = 0.7,
    min_size: int = 16,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched :func:`propose` over a leading batch axis.

    scores (B, N), bbox_deltas (B, N, 4), im_info (B, 3); anchors shared.

    The per-image stages (decode/top-k/compaction) run under vmap but the
    NMS sweep runs as ONE cross-image batched pass (:func:`nms_batch`),
    decision-exact vs ``vmap(propose)`` (pinned by
    ``tests/test_proposal.py``).
    """
    top_boxes, top_scores, top_valid = jax.vmap(
        lambda s, d, i: _decode_filter_topk(s, d, anchors, i,
                                            pre_nms_top_n, min_size)
    )(scores, bbox_deltas, im_info)
    keep_idx, keep_valid = nms_batch(
        top_boxes, top_scores, nms_thresh, post_nms_top_n, valid=top_valid)
    return jax.vmap(_compact_rois)(top_boxes, top_scores, keep_idx,
                                   keep_valid)
