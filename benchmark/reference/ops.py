"""Plain detection ops of the reference: anchors, box arithmetic, target
assignment, proposals with greedy NMS, ROIAlign and the losses.

Written from the published method (Ren et al. 2015; the py-faster-rcnn /
mx-rcnn conventions: inclusive corners, width = x2 - x1 + 1) in float32
``jax.numpy`` with no kernels, tiles or batching tricks.  It imports
nothing of the program.  Random subsampling draws ``jax.random.uniform`` on
the key the caller hands in and keeps the smallest draws, which is the
rule the recipe states; both sides of a comparison therefore see the same
draw when they derive the same keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIG = 3.4e38


def base_anchors(stride, ratios, scales):
    """(A, 4) windows around the [0, 0, stride-1, stride-1] cell: ratios
    enumerated first (rounded), then scales."""
    size = float(stride * stride)
    ctr = 0.5 * (stride - 1)
    out = []
    for r in ratios:
        w = np.round(np.sqrt(size / r))
        h = np.round(w * r)
        for s in scales:
            ws, hs = w * s, h * s
            out.append([ctr - 0.5 * (ws - 1), ctr - 0.5 * (hs - 1),
                        ctr + 0.5 * (ws - 1), ctr + 0.5 * (hs - 1)])
    return np.asarray(out, np.float64)


def grid_anchors(fh, fw, stride, ratios, scales):
    """All anchors of an (fh, fw) grid, row-major over (y, x, anchor)."""
    base = base_anchors(stride, ratios, scales)
    ys, xs = np.meshgrid(np.arange(fh) * stride, np.arange(fw) * stride,
                         indexing="ij")
    shift = np.stack([xs, ys, xs, ys], -1)[:, :, None, :]
    return (shift + base[None, None]).reshape(-1, 4).astype(np.float32)


def area(b):
    return (b[..., 2] - b[..., 0] + 1.0) * (b[..., 3] - b[..., 1] + 1.0)


def iou(a, b):
    """(N, 4) x (K, 4) -> (N, K)."""
    iw = (jnp.minimum(a[:, None, 2], b[None, :, 2])
          - jnp.maximum(a[:, None, 0], b[None, :, 0]) + 1.0)
    ih = (jnp.minimum(a[:, None, 3], b[None, :, 3])
          - jnp.maximum(a[:, None, 1], b[None, :, 1]) + 1.0)
    inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)
    union = area(a)[:, None] + area(b)[None, :] - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1e-12), 0.0)


def _cwh(b):
    w = b[..., 2] - b[..., 0] + 1.0
    h = b[..., 3] - b[..., 1] + 1.0
    return b[..., 0] + 0.5 * (w - 1.0), b[..., 1] + 0.5 * (h - 1.0), w, h


def encode(ex, gt):
    ecx, ecy, ew, eh = _cwh(ex)
    gcx, gcy, gw, gh = _cwh(gt)
    return jnp.stack([(gcx - ecx) / (ew + 1e-14), (gcy - ecy) / (eh + 1e-14),
                      jnp.log(jnp.maximum(gw, 1.0) / jnp.maximum(ew, 1.0)),
                      jnp.log(jnp.maximum(gh, 1.0) / jnp.maximum(eh, 1.0))],
                     -1)


def decode(boxes, d):
    cx, cy, w, h = _cwh(boxes)
    cap = np.log(1000.0 / 16.0)
    pcx, pcy = d[..., 0] * w + cx, d[..., 1] * h + cy
    pw = jnp.exp(jnp.minimum(d[..., 2], cap)) * w
    ph = jnp.exp(jnp.minimum(d[..., 3], cap)) * h
    return jnp.stack([pcx - 0.5 * (pw - 1.0), pcy - 0.5 * (ph - 1.0),
                      pcx + 0.5 * (pw - 1.0), pcy + 0.5 * (ph - 1.0)], -1)


def rank_among(key, mask):
    """Random 0-based rank of every True element among the True ones (the
    smallest uniform draw ranks 0); False elements rank after them."""
    r = jnp.where(mask, jax.random.uniform(key, mask.shape), BIG)
    return jnp.argsort(jnp.argsort(r))


def anchor_targets(anchors, gt, gt_valid, im_info, key, tr):
    """RPN labels {1, 0, -1}, regression targets and weights of one image."""
    inside = ((anchors[:, 0] >= 0) & (anchors[:, 1] >= 0)
              & (anchors[:, 2] < im_info[1]) & (anchors[:, 3] < im_info[0]))
    ov = jnp.where(gt_valid[None, :], iou(anchors, gt), 0.0)
    best = ov.max(1)
    ov_in = jnp.where(inside[:, None], ov, -1.0)
    gt_best = ov_in.max(0)
    is_gt_best = ((ov_in == gt_best[None]) & gt_valid[None]
                  & (gt_best[None] > 0)).any(1)
    pos = (inside & (is_gt_best | (best >= tr["rpn_positive_overlap"]))
           & gt_valid.any())
    neg = inside & (best < tr["rpn_negative_overlap"]) & ~pos
    kf, kb = jax.random.split(key)
    batch = tr["rpn_batch_size"]
    quota = int(tr["rpn_fg_fraction"] * batch)
    pos_kept = pos & (rank_among(kf, pos) < quota)
    neg_kept = neg & (rank_among(kb, neg) < batch - pos_kept.sum())
    labels = jnp.where(pos_kept, 1, jnp.where(neg_kept, 0, -1))
    targets = encode(anchors, gt[ov.argmax(1)])
    w = pos_kept[:, None].astype(jnp.float32)
    return labels, targets * w, w * jnp.ones((1, 4), jnp.float32)


def greedy_nms(boxes, alive, thresh):
    """Sequential greedy suppression over score-sorted boxes: a live box
    suppresses every later box that overlaps it by more than ``thresh``."""
    k = boxes.shape[0]
    later = jnp.arange(k)
    areas = area(boxes)

    def body(i, keep):
        b = boxes[i]
        iw = jnp.minimum(b[2], boxes[:, 2]) - jnp.maximum(b[0], boxes[:, 0]) + 1.0
        ih = jnp.minimum(b[3], boxes[:, 3]) - jnp.maximum(b[1], boxes[:, 1]) + 1.0
        inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)
        union = areas[i] + areas - inter
        ov = jnp.where(union > 0, inter / jnp.maximum(union, 1e-12), 0.0)
        return keep & ~(keep[i] & (ov > thresh) & (later > i))

    return jax.lax.fori_loop(0, k, body, alive)


def proposals(fg, deltas, anchors, im_info, tr):
    """One image's ROIs: decode, clip, drop small, top pre-NMS by score,
    greedy NMS, first post-NMS survivors.  Unfilled slots repeat the best
    box and are marked invalid."""
    boxes = decode(anchors, deltas)
    hi_x, hi_y = im_info[1] - 1.0, im_info[0] - 1.0
    boxes = jnp.stack([jnp.clip(boxes[:, 0], 0, hi_x),
                       jnp.clip(boxes[:, 1], 0, hi_y),
                       jnp.clip(boxes[:, 2], 0, hi_x),
                       jnp.clip(boxes[:, 3], 0, hi_y)], -1)
    small = tr["rpn_min_size"] * im_info[2]
    ok = ((boxes[:, 2] - boxes[:, 0] + 1.0 >= small)
          & (boxes[:, 3] - boxes[:, 1] + 1.0 >= small))
    score = jnp.where(ok, fg, -jnp.inf)
    pre = min(tr["rpn_pre_nms_top_n"], score.shape[0])
    top, idx = jax.lax.top_k(score, pre)
    cand = boxes[idx]
    keep = greedy_nms(cand, jnp.isfinite(top), tr["rpn_nms_thresh"])
    post = tr["rpn_post_nms_top_n"]
    slot = jnp.cumsum(keep) - 1
    src = jnp.full((post,), -1, jnp.int32).at[
        jnp.where(keep & (slot < post), slot, post)].set(
            jnp.arange(pre, dtype=jnp.int32), mode="drop")
    valid = src >= 0
    rois = cand[jnp.maximum(src, 0)]
    return jnp.where(valid[:, None], rois, rois[0][None]), valid


def sample_rois(rois, valid, gt, gt_cls, gt_valid, key, tr, num_classes):
    """The RCNN minibatch of one image: ground truth joins the pool, at
    most fg_fraction of the slots are foreground (IoU >= fg_thresh), the
    rest background; targets are class-specific and normalised."""
    pool = jnp.concatenate([rois, gt], 0)
    pvalid = jnp.concatenate([valid, gt_valid], 0)
    ov = jnp.where(gt_valid[None], iou(pool, gt), 0.0)
    best, arg = ov.max(1), ov.argmax(1)
    fg = pvalid & (best >= tr["fg_thresh"])
    bg = pvalid & (best < tr["bg_thresh_hi"]) & (best >= tr["bg_thresh_lo"])
    kf, kb = jax.random.split(key)
    n = tr["batch_rois"]
    fg_rank, bg_rank = rank_among(kf, fg), rank_among(kb, bg)
    fg_sel = fg & (fg_rank < int(round(tr["fg_fraction"] * n)))
    bg_sel = bg & (bg_rank < n - fg_sel.sum())
    size = pool.shape[0]
    prio = jnp.where(fg_sel, 3 * size - fg_rank,
                     jnp.where(bg_sel, 2 * size - bg_rank,
                               size - jnp.arange(size)))
    pick = jnp.argsort(-prio)[:n]
    box, is_fg, is_bg, g = pool[pick], fg_sel[pick], bg_sel[pick], arg[pick]
    labels = jnp.where(is_fg, gt_cls[g], jnp.where(is_bg, 0, -1))
    t = ((encode(box, gt[g]) - jnp.asarray(tr["bbox_means"], jnp.float32))
         / jnp.asarray(tr["bbox_stds"], jnp.float32))
    hot = jax.nn.one_hot(labels, num_classes) * (labels > 0)[:, None]
    targets = (hot[:, :, None] * t[:, None, :]).reshape(n, -1)
    weights = jnp.repeat(hot, 4, axis=1)
    return box, labels, targets, weights


def _bin_weights(start, length, bins, size, ratio):
    """(bins, size) averaged bilinear weights of one axis of one ROI:
    ``ratio`` sample points a bin, each a hat function over pixel centres."""
    k = jnp.arange(bins * ratio, dtype=jnp.float32)
    pos = jnp.clip(start + (k + 0.5) * (length / (bins * ratio)) - 0.5,
                   0.0, size - 1.0)
    hat = jnp.maximum(
        0.0, 1.0 - jnp.abs(pos[:, None] - jnp.arange(size, dtype=jnp.float32)))
    return hat.reshape(bins, ratio, size).mean(1)


def roi_align(feat, rois, out_hw, scale, mm, ratio=2):
    """ROIAlign of one image: (H, W, C) x (R, 4) -> (R, ph, pw, C).  The
    mean over a bin's sample grid factorises into the two axes."""
    ph, pw = out_hw
    h, w, _ = feat.shape
    x1, y1, x2, y2 = (rois[:, i] * scale for i in range(4))
    wy = jax.vmap(lambda s, l: _bin_weights(s, l, ph, h, ratio))(
        y1, jnp.maximum(y2 - y1, 1.0))
    wx = jax.vmap(lambda s, l: _bin_weights(s, l, pw, w, ratio))(
        x1, jnp.maximum(x2 - x1, 1.0))
    rows = mm("rsh,hwc->rswc", wy, feat)
    return mm("rswc,rtw->rstc", rows, wx)


def nll_sum(logits, labels):
    """Sum of softmax cross-entropy over labels != -1, and their count."""
    keep = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return jnp.where(keep, nll, 0.0).sum(), keep.sum()


def smooth_l1_sum(pred, target, weight, sigma):
    d = jnp.abs(pred - target)
    s2 = sigma * sigma
    return (jnp.where(d < 1.0 / s2, 0.5 * s2 * d * d, d - 0.5 / s2)
            * weight).sum()
