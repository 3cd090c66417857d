"""Four-stage alternate training (the original Faster R-CNN paper schedule).

Reference: ``train_alternate.py — alternate_train`` with the stage tools
``rcnn/tools/train_rpn.py``, ``test_rpn.py`` (proposal generation),
``train_rcnn.py`` and ``rcnn/utils/combine_model.py`` (SURVEY.md §3.3):

  1. train RPN from the pretrained backbone            → <prefix>-rpn1
  1.5 dump proposals for the train roidb from rpn1
  2. train Fast R-CNN on those proposals               → <prefix>-rcnn1
  3. retrain RPN from rcnn1 with shared convs frozen   → <prefix>-rpn2
  3.5 dump proposals from rpn2
  4. retrain Fast R-CNN on them, shared convs frozen   → <prefix>-rcnn2
  ∪  combine rpn2 (RPN + shared convs) with rcnn2 (head) → <prefix>-final

Deviation from the reference, documented: the reference always initializes
stage 2 from ImageNet weights; with no ``--pretrained`` checkpoint
available (this machine cannot download one), stage 2 initializes FRESH by
default — closer in spirit to the reference (stage 2 starts from generic
weights, never from the stage-1 RPN-specialized ones) than round 2's
rpn1-checkpoint fallback.  Round-3 ablations
(``script/ablate_alternate.py``) found the two inits
statistically indistinguishable across seeds (means 0.87 both) and showed
the round-2 "alternate vs e2e mAP gap" was run-to-run seed variance of the
small synthetic eval, not a schedule defect; ``--stage2_init rpn1`` keeps
the old behavior.
"""

from __future__ import annotations

import argparse
import logging
import pickle

import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.core.tester import generate_proposals
from mx_rcnn_tpu.core.train import TrainState
from mx_rcnn_tpu.data import TestLoader, load_gt_roidb
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.train import (add_set_arg, config_from_args,
                                     train_net)
from mx_rcnn_tpu.utils.checkpoint import (combine_model, load_param,
                                          save_checkpoint)

logger = logging.getLogger("mx_rcnn_tpu")


def _dump_proposals(cfg: Config, roidb, prefix: str, epoch: int,
                    out_path: str):
    """Stage 1.5/3.5: RPN proposal dump over the (flip-augmented) train
    roidb (ref ``test_rpn.py — generate_proposals`` writes rpn_data pkl)."""
    model = build_model(cfg)
    params, batch_stats = load_param(prefix, epoch)
    loader = TestLoader(roidb, cfg)  # single pass per stage: no cache
    props = generate_proposals(
        model, {"params": params, "batch_stats": batch_stats}, loader, cfg)
    with open(out_path, "wb") as f:
        pickle.dump(props, f, pickle.HIGHEST_PROTOCOL)
    sizes = [len(p) for p in props]
    logger.info("dumped proposals for %d images (mean %.1f/img) to %s",
                len(props), float(np.mean(sizes)), out_path)
    return props


def alternate_train(cfg: Config, *, prefix: str,
                    pretrained: str = None, pretrained_epoch: int = 0,
                    rpn_epoch: int = None, rpn_lr: float = None,
                    rpn_lr_step: str = None,
                    rcnn_epoch: int = None, rcnn_lr: float = None,
                    rcnn_lr_step: str = None,
                    num_devices: int = 1, frequent: int = None,
                    seed: int = 0, dataset_kw: dict = None,
                    stage2_init: str = "fresh") -> str:
    """Run the full 4-stage schedule; returns the final combined prefix
    (checkpoint saved as ``<prefix>-final-0001.ckpt``)."""
    d = cfg.default
    # 'is None' (not 'or'): explicit zeros are meaningful (lr 0 = sanity
    # check, epoch 0 = skip a stage) and must not fall back to defaults
    rpn_epoch = d.rpn_epoch if rpn_epoch is None else rpn_epoch
    rcnn_epoch = d.rcnn_epoch if rcnn_epoch is None else rcnn_epoch
    rpn_lr = d.rpn_lr if rpn_lr is None else rpn_lr
    rcnn_lr = d.rcnn_lr if rcnn_lr is None else rcnn_lr
    rpn_lr_step = d.rpn_lr_step if rpn_lr_step is None else rpn_lr_step
    rcnn_lr_step = d.rcnn_lr_step if rcnn_lr_step is None else rcnn_lr_step
    shared = cfg.network.fixed_params_shared

    _, roidb = load_gt_roidb(cfg, training=True, **(dataset_kw or {}))
    common = dict(num_devices=num_devices, frequent=frequent, seed=seed,
                  roidb=roidb)

    logger.info("=== Stage 1: train RPN ===")
    train_net(cfg, mode="rpn", prefix=f"{prefix}-rpn1",
              end_epoch=rpn_epoch, lr=rpn_lr, lr_step=rpn_lr_step,
              pretrained=pretrained, pretrained_epoch=pretrained_epoch,
              **common)

    logger.info("=== Stage 1.5: generate proposals from rpn1 ===")
    props1 = _dump_proposals(cfg, roidb, f"{prefix}-rpn1", rpn_epoch,
                             f"{prefix}-rpn1-proposals.pkl")

    logger.info("=== Stage 2: train RCNN on rpn1 proposals ===")
    # with pretrained weights the ref semantics apply (ImageNet init);
    # without, 'fresh' (default, ablation-backed) or 'rpn1' (r2 behavior)
    init2 = ((f"{prefix}-rpn1", rpn_epoch)
             if not pretrained and stage2_init == "rpn1" else None)
    train_net(cfg, mode="rcnn", prefix=f"{prefix}-rcnn1",
              end_epoch=rcnn_epoch, lr=rcnn_lr, lr_step=rcnn_lr_step,
              pretrained=pretrained, pretrained_epoch=pretrained_epoch,
              proposals=props1, init_from=init2, **common)

    logger.info("=== Stage 3: retrain RPN, shared convs frozen ===")
    train_net(cfg, mode="rpn", prefix=f"{prefix}-rpn2",
              end_epoch=rpn_epoch, lr=rpn_lr, lr_step=rpn_lr_step,
              init_from=(f"{prefix}-rcnn1", rcnn_epoch),
              frozen_prefixes=shared, **common)

    logger.info("=== Stage 3.5: generate proposals from rpn2 ===")
    props2 = _dump_proposals(cfg, roidb, f"{prefix}-rpn2", rpn_epoch,
                             f"{prefix}-rpn2-proposals.pkl")

    logger.info("=== Stage 4: retrain RCNN, shared convs frozen ===")
    train_net(cfg, mode="rcnn", prefix=f"{prefix}-rcnn2",
              end_epoch=rcnn_epoch, lr=rcnn_lr, lr_step=rcnn_lr_step,
              init_from=(f"{prefix}-rpn2", rpn_epoch),
              frozen_prefixes=shared, proposals=props2, **common)

    logger.info("=== Combine rpn2 + rcnn2 → final ===")
    p_rpn, s_rpn = load_param(f"{prefix}-rpn2", rpn_epoch)
    p_rcnn, s_rcnn = load_param(f"{prefix}-rcnn2", rcnn_epoch)
    # RPN weights and shared convs from the rpn2 lineage; per-ROI head,
    # cls_score and bbox_pred from rcnn2 (ref combine_model)
    params = combine_model(p_rpn, p_rcnn, from_a=("rpn", "backbone"))
    stats = combine_model(s_rpn, s_rcnn, from_a=("backbone",))
    final = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats=stats, opt_state={})
    path = save_checkpoint(f"{prefix}-final", 1, final)
    logger.info('saved combined model to "%s"', path)
    return f"{prefix}-final"


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="4-stage alternate training (ref train_alternate.py)")
    p.add_argument("--network", default="resnet101",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="PascalVOC",
                   choices=["PascalVOC", "coco", "synthetic", "synthetic_hard"])
    p.add_argument("--image_set", default=None)
    p.add_argument("--root_path", default=None)
    p.add_argument("--dataset_path", default=None)
    p.add_argument("--prefix", default="model/alt")
    p.add_argument("--pretrained", default=None)
    p.add_argument("--pretrained_epoch", type=int, default=0)
    p.add_argument("--rpn_epoch", type=int, default=None)
    p.add_argument("--rcnn_epoch", type=int, default=None)
    p.add_argument("--rpn_lr", type=float, default=None)
    p.add_argument("--rcnn_lr", type=float, default=None)
    p.add_argument("--rpn_lr_step", default=None)
    p.add_argument("--rcnn_lr_step", default=None)
    p.add_argument("--num_devices", type=int, default=1)
    p.add_argument("--frequent", type=int, default=None)
    p.add_argument("--no_flip", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--stage2_init", choices=["fresh", "rpn1"],
                   default="fresh",
                   help="stage-2 init when --pretrained is absent (fresh "
                        "mirrors the ref's generic-weights semantics; "
                        "measured equivalent to rpn1 across seeds)")
    add_set_arg(p)
    return p.parse_args(argv)


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    args = parse_args(argv)
    cfg = config_from_args(args)
    alternate_train(cfg, prefix=args.prefix, pretrained=args.pretrained,
                    pretrained_epoch=args.pretrained_epoch,
                    rpn_epoch=args.rpn_epoch, rpn_lr=args.rpn_lr,
                    rpn_lr_step=args.rpn_lr_step,
                    rcnn_epoch=args.rcnn_epoch, rcnn_lr=args.rcnn_lr,
                    rcnn_lr_step=args.rcnn_lr_step,
                    num_devices=args.num_devices, frequent=args.frequent,
                    seed=args.seed, stage2_init=args.stage2_init)


if __name__ == "__main__":
    main()
