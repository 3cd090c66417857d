"""Fused proposal-op tests (ref mx.symbol.Proposal / rcnn/symbol/proposal.py)."""

import numpy as np
import jax.numpy as jnp

from mx_rcnn_tpu.ops.anchors import generate_shifted_anchors
from mx_rcnn_tpu.ops.proposal import propose, propose_batch


def setup_inputs(h=8, w=8, seed=0):
    anchors = jnp.array(generate_shifted_anchors(h, w, 16))
    n = anchors.shape[0]
    rng = np.random.RandomState(seed)
    scores = jnp.array(rng.uniform(0, 1, (n,)).astype(np.float32))
    deltas = jnp.array((rng.randn(n, 4) * 0.1).astype(np.float32))
    im_info = jnp.array([128.0, 128.0, 1.0])
    return anchors, scores, deltas, im_info


def test_propose_shapes_and_validity():
    anchors, scores, deltas, im_info = setup_inputs()
    rois, rs, valid = propose(scores, deltas, anchors, im_info,
                              pre_nms_top_n=200, post_nms_top_n=50)
    assert rois.shape == (50, 4)
    assert rs.shape == (50,)
    assert bool(valid[0])
    r = np.asarray(rois)
    # clipped to image bounds
    assert (r[:, 0] >= 0).all() and (r[:, 2] <= 127).all()
    assert (r[:, 1] >= 0).all() and (r[:, 3] <= 127).all()


def test_propose_min_size_filter():
    anchors, scores, deltas, im_info = setup_inputs()
    # shrink every box below min_size by predicting a huge negative dw/dh
    deltas = jnp.zeros_like(deltas).at[:, 2:].set(-5.0)
    rois, rs, valid = propose(scores, deltas, anchors, im_info,
                              pre_nms_top_n=200, post_nms_top_n=50, min_size=16)
    assert not bool(np.asarray(valid).any())


def test_propose_scores_sorted_and_nms_applied():
    anchors, scores, deltas, im_info = setup_inputs()
    rois, rs, valid = propose(scores, deltas, anchors, im_info,
                              pre_nms_top_n=576, post_nms_top_n=100,
                              nms_thresh=0.7)
    rs = np.asarray(rs)[np.asarray(valid)]
    assert (np.diff(rs) <= 1e-6).all()  # descending
    # surviving boxes must have pairwise IoU <= 0.7
    from mx_rcnn_tpu.ops.boxes import bbox_overlaps
    r = rois[valid]
    iou = np.array(bbox_overlaps(r, r))  # copy: np.asarray of a jax array is read-only
    np.fill_diagonal(iou, 0)
    assert (iou <= 0.7 + 1e-5).all()


def test_propose_batch_vmap():
    anchors, scores, deltas, im_info = setup_inputs()
    b_scores = jnp.stack([scores, scores * 0.5])
    b_deltas = jnp.stack([deltas, deltas])
    b_info = jnp.stack([im_info, im_info])
    rois, rs, valid = propose_batch(b_scores, b_deltas, anchors, b_info,
                                    pre_nms_top_n=200, post_nms_top_n=30)
    assert rois.shape == (2, 30, 4)
    np.testing.assert_allclose(np.asarray(rois[0]), np.asarray(rois[1]), rtol=1e-5)


def test_propose_batch_batched_nms_decision_exact_vs_vmap():
    """The r6 cross-image batched NMS path must equal the vmap-of-propose
    composition on EVERY output — jitted whole (the production context;
    eager dispatch can differ by 1 ulp in fused decode arithmetic, which
    is a dispatch artifact, not a decision difference)."""
    import jax

    anchors, scores, deltas, im_info = setup_inputs()
    rng = np.random.RandomState(3)
    b = 4
    b_scores = jnp.stack([scores * float(s)
                          for s in rng.uniform(0.2, 1.0, b)])
    b_deltas = jnp.stack([deltas + float(d)
                          for d in rng.uniform(-0.1, 0.1, b)])
    b_info = jnp.tile(im_info[None], (b, 1))
    kw = dict(pre_nms_top_n=200, post_nms_top_n=30, nms_thresh=0.7,
              min_size=4)

    per_image = jax.jit(lambda s, d, i: jax.vmap(
        lambda s1, d1, i1: propose(s1, d1, anchors, i1, **kw))(s, d, i))
    batched = jax.jit(lambda s, d, i: propose_batch(
        s, d, anchors, i, **kw))
    a = per_image(b_scores, b_deltas, b_info)
    g = batched(b_scores, b_deltas, b_info)
    for x, y, name in zip(a, g, ("rois", "scores", "valid")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)
