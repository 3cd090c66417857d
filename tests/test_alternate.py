"""Alternate (4-stage) training tests — VERDICT r1 item 6.

Runs the miniature full schedule on the synthetic set and checks the
stage artifacts plus two sharp invariants: stage 3 (RPN retrain with
FIXED_PARAMS_SHARED) must leave the shared convs bit-identical to its
rcnn1 init, and stage 4 likewise vs rpn2 — that is the property that makes
the final combine valid (ref ``train_alternate.py`` stages 3/4 freeze
shared convs so RPN and RCNN agree on features).
"""



import os
import pickle

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.train import RCNNBatch
from mx_rcnn_tpu.data import load_gt_roidb
from mx_rcnn_tpu.data.loader import ROIIter
from mx_rcnn_tpu.tools.test import test_rcnn as eval_rcnn
from mx_rcnn_tpu.tools.train_alternate import alternate_train
from mx_rcnn_tpu.utils.checkpoint import load_param


def _cfg(tmp_path):
    cfg = generate_config(
        "tiny", "synthetic",
        dataset__root_path=str(tmp_path),
        dataset__dataset_path=str(tmp_path / "synthetic"),
        dataset__num_classes=4,
    )
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=512,
                         rpn_post_nms_top_n=128, batch_rois=64,
                         max_gt_boxes=8, flip=False)
    cfg = cfg.replace_in("test", rpn_pre_nms_top_n=512,
                         rpn_post_nms_top_n=64,
                         proposal_pre_nms_top_n=512,
                         proposal_post_nms_top_n=96)
    cfg = cfg.replace_in("bucket", scale=128, max_size=160,
                         shapes=((128, 160), (160, 128)))
    return cfg


KW = dict(num_images=24, image_size=(128, 160), max_objects=3)


def test_roiiter_packs_scaled_padded_proposals(tmp_path):
    cfg = _cfg(tmp_path)
    _, roidb = load_gt_roidb(cfg, training=True, **KW)
    rng = np.random.RandomState(0)
    proposals = []
    for rec in roidb:
        k = rng.randint(1, 6)
        x1 = rng.uniform(0, 60, k)
        y1 = rng.uniform(0, 60, k)
        p = np.stack([x1, y1, x1 + 20, y1 + 20,
                      np.sort(rng.uniform(size=k))[::-1]], axis=1)
        proposals.append(p.astype(np.float32))
    it = ROIIter(roidb, cfg, proposals, batch_images=2, shuffle=False,
                 max_rois=8)
    batch = next(iter(it))
    assert isinstance(batch, RCNNBatch)
    assert batch.rois.shape == (2, 8, 4)
    assert batch.rois_valid.shape == (2, 8)
    # valid count matches the proposal count, padding is invalid
    # (loader is unshuffled: batch j=0 is roidb[0] of its bucket)
    j = 0
    n_valid = int(batch.rois_valid[j].sum())
    assert 1 <= n_valid <= 8
    # rois are scaled into input coordinates by im_scale
    scale = batch.im_info[j, 2]
    assert batch.rois[j, 0, 2] - batch.rois[j, 0, 0] == pytest.approx(
        20 * scale, rel=1e-5)
    # mismatched lengths are rejected
    with pytest.raises(ValueError):
        ROIIter(roidb, cfg, proposals[:-1])


def test_alternate_four_stages_and_combine(tmp_path):
    cfg = _cfg(tmp_path)
    prefix = str(tmp_path / "model" / "alt")
    final = alternate_train(cfg, prefix=prefix, rpn_epoch=4, rcnn_epoch=4,
                            rpn_lr=3e-3, rcnn_lr=3e-3, rpn_lr_step="3",
                            rcnn_lr_step="3", frequent=1000, seed=0,
                            dataset_kw=KW)
    # all stage artifacts exist
    for stage in ("rpn1", "rcnn1", "rpn2", "rcnn2"):
        assert os.path.exists(f"{prefix}-{stage}-0004.ckpt"), stage
    for pkl in ("rpn1-proposals.pkl", "rpn2-proposals.pkl"):
        with open(f"{prefix}-{pkl}", "rb") as f:
            props = pickle.load(f)
        assert len(props) == KW["num_images"]
    assert final == f"{prefix}-final"
    assert os.path.exists(f"{prefix}-final-0001.ckpt")

    # frozen-shared-conv invariants: stage3 backbone == rcnn1 backbone,
    # stage4 backbone == rpn2 backbone (bit-identical)
    p_rcnn1, _ = load_param(f"{prefix}-rcnn1", 4)
    p_rpn2, _ = load_param(f"{prefix}-rpn2", 4)
    p_rcnn2, _ = load_param(f"{prefix}-rcnn2", 4)
    for a, b in zip(jax.tree.leaves(p_rcnn1["backbone"]),
                    jax.tree.leaves(p_rpn2["backbone"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(p_rpn2["backbone"]),
                    jax.tree.leaves(p_rcnn2["backbone"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # but stage3 DID train the RPN head (it must differ from rcnn1's)
    moved = [not np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree.leaves(p_rcnn1["rpn"]),
                             jax.tree.leaves(p_rpn2["rpn"]))]
    assert any(moved)

    # the combined model is evaluable end to end
    results = eval_rcnn(cfg, prefix=final, epoch=1, verbose=False,
                        dataset_kw=dict(num_images=8, image_size=(128, 160),
                                        max_objects=3))
    assert "mAP" in results and np.isfinite(results["mAP"])
    # final params: rpn from rpn2, head from rcnn2 (combine semantics)
    p_final, _ = load_param(final, 1)
    for a, b in zip(jax.tree.leaves(p_final["rpn"]),
                    jax.tree.leaves(p_rpn2["rpn"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(p_final["cls_score"]),
                    jax.tree.leaves(p_rcnn2["cls_score"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # VERDICT r03 item 4: the stage-4 RCNN checkpoint evaluated on dumped
    # rpn2 proposals (the tools/test_rcnn path) must match the combined
    # model's mAP within noise — the combine IS rpn2's RPN + rcnn2's head,
    # so with the dump pinned to the test-time proposal params the only
    # differences are the raw-coordinate roundtrip of the pkl format
    from mx_rcnn_tpu.core.tester import generate_proposals
    from mx_rcnn_tpu.data import TestLoader
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.tools.test_rcnn import test_rcnn_stage

    kw_eval = dict(num_images=8, image_size=(128, 160), max_objects=3)
    cfg_dump = cfg.replace_in(
        "test", proposal_pre_nms_top_n=cfg.test.rpn_pre_nms_top_n,
        proposal_post_nms_top_n=cfg.test.rpn_post_nms_top_n)
    _, test_roidb = load_gt_roidb(cfg_dump, training=False, **kw_eval)
    params, bs = load_param(f"{prefix}-rpn2", 4)
    props = generate_proposals(
        build_model(cfg_dump), {"params": params, "batch_stats": bs},
        TestLoader(test_roidb, cfg_dump), cfg_dump)
    stage = test_rcnn_stage(cfg_dump, prefix=f"{prefix}-rcnn2", epoch=4,
                            proposals=props, verbose=False,
                            dataset_kw=kw_eval)
    assert stage["mAP"] == pytest.approx(results["mAP"], abs=0.05)


def test_stage2_init_knob(tmp_path):
    """stage2_init='rpn1' must seed stage 2 from the rpn1 backbone;
    the default 'fresh' must not (script/ablate_alternate.py)."""
    cfg = _cfg(tmp_path)
    prefix = str(tmp_path / "model" / "alt2")
    alternate_train(cfg, prefix=prefix, rpn_epoch=1, rcnn_epoch=1,
                    rpn_lr=3e-3, rcnn_lr=0.0, frequent=1000, seed=0,
                    dataset_kw=KW, stage2_init="rpn1")
    # rpn trains (lr>0) so rpn1 != the seed-0 init; rcnn lr 0 keeps stage-2
    # weights at their init → rcnn1 backbone == the TRAINED rpn1 backbone
    p_rpn1, _ = load_param(f"{prefix}-rpn1", 1)
    p_rcnn1, _ = load_param(f"{prefix}-rcnn1", 1)
    for a, b in zip(jax.tree.leaves(p_rpn1["backbone"]),
                    jax.tree.leaves(p_rcnn1["backbone"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    prefix = str(tmp_path / "model" / "alt3")
    alternate_train(cfg, prefix=prefix, rpn_epoch=1, rcnn_epoch=1,
                    rpn_lr=3e-3, rcnn_lr=0.0, frequent=1000, seed=0,
                    dataset_kw=KW)  # default: fresh
    p_rpn1, _ = load_param(f"{prefix}-rpn1", 1)
    p_rcnn1, _ = load_param(f"{prefix}-rcnn1", 1)
    same = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_rpn1["backbone"]),
                        jax.tree.leaves(p_rcnn1["backbone"])))
    assert not same
