"""Detection evaluation entry point: checkpoint → mAP.

Reference: ``test.py — test_rcnn`` (SURVEY.md §3.2): generate_config →
test symbol → TestLoader → Predictor → ``pred_eval`` → per-class NMS →
``imdb.evaluate_detections``.
"""

from __future__ import annotations

import argparse
import logging
from typing import Dict

from mx_rcnn_tpu import runtime
from mx_rcnn_tpu.config import Config, generate_config
from mx_rcnn_tpu.core.tester import Predictor, pred_eval
from mx_rcnn_tpu.data import TestLoader, load_gt_roidb
from mx_rcnn_tpu.tools.train import add_set_arg, parse_set_overrides
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.utils.checkpoint import load_param

logger = logging.getLogger("mx_rcnn_tpu")


def test_rcnn(cfg: Config, *, prefix: str, epoch: int,
              image_set: str = None, out_dir: str = None,
              verbose: bool = True, dataset_kw: dict = None,
              save_dets: str = None, num_devices: int = 1
              ) -> Dict[str, float]:
    """Evaluate checkpoint ``prefix``@``epoch``; returns the metric dict
    (includes ``mAP`` for VOC-style evaluators).

    ``num_devices > 1`` shards the eval batch over a data mesh (multi-chip
    evaluation — the reference evals on a single GPU).
    """
    imdb, roidb = load_gt_roidb(cfg, image_set=image_set, training=False,
                                **(dataset_kw or {}))
    mesh = None
    if num_devices > 1:
        import jax

        from mx_rcnn_tpu.parallel.dp import device_mesh

        available = len(jax.devices())
        if num_devices > available:
            raise ValueError(
                f"--num_devices {num_devices} but only {available} "
                f"device(s) available")
        mesh = device_mesh(num_devices)
    # no decoded-image cache: eval reads each image exactly once, so
    # caching would only add RSS (the cache pays off on multi-epoch reads)
    loader = TestLoader(roidb, cfg,
                        batch_images=cfg.test.batch_images * num_devices)
    params, batch_stats = load_param(prefix, epoch)
    if cfg.quant.enabled:
        # quantized-inference eval (docs/PERF.md "Quantized inference"):
        # calibrate activation scales on a held-out training sweep, then
        # evaluate through the quantized forward — the mAP this returns
        # against an fp run of the same checkpoint IS the accuracy gate
        # (tools/gauntlet.py quant mode; make quant-smoke)
        from mx_rcnn_tpu.core.tester import quant_predictor

        logger.info("quant eval: %s/%s estimator=%s bits=%d",
                    cfg.quant.dtype, cfg.quant.mode, cfg.quant.estimator,
                    cfg.quant.weight_bits)
        predictor = quant_predictor(cfg, params, batch_stats, mesh=mesh,
                                    dataset_kw=dataset_kw)
        logger.info("quant calibration fingerprint: %s",
                    predictor.quant_fingerprint)
    else:
        model = build_model(cfg)
        predictor = Predictor(
            model, {"params": params, "batch_stats": batch_stats}, cfg,
            mesh=mesh)
    results = pred_eval(predictor, loader, imdb, cfg, out_dir=out_dir,
                        verbose=verbose, save_dets=save_dets)
    for k, v in sorted(results.items()):
        logger.info("%s AP = %.4f", k, v)
    if "mAP" in results:
        print(f"mAP = {results['mAP']:.4f}")
    return results


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="Evaluate a Faster R-CNN checkpoint (ref test.py)")
    p.add_argument("--network", default="resnet101",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="PascalVOC",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard", "synthetic_stream"])
    p.add_argument("--image_set", default=None,
                   help="defaults to the dataset's test_image_set")
    p.add_argument("--root_path", default=None)
    p.add_argument("--dataset_path", default=None)
    p.add_argument("--prefix", default="model/e2e")
    p.add_argument("--epoch", type=int, required=True)
    p.add_argument("--out_dir", default=None,
                   help="write detection files here (VOC comp4 / COCO json)")
    p.add_argument("--save_dets", default=None,
                   help="pickle raw detections here for tools/reeval.py")
    p.add_argument("--num_devices", type=int, default=1,
                   help="shard eval batches over this many devices")
    add_set_arg(p)
    return p.parse_args(argv)


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    args = parse_args(argv)
    overrides = {}
    if args.root_path:
        overrides["dataset__root_path"] = args.root_path
    if args.dataset_path:
        overrides["dataset__dataset_path"] = args.dataset_path
    overrides.update(parse_set_overrides(args))
    cfg = generate_config(args.network, args.dataset, **overrides)
    runtime.log_runtime(runtime.enable_compile_cache())
    test_rcnn(cfg, prefix=args.prefix, epoch=args.epoch,
              image_set=args.image_set, out_dir=args.out_dir,
              save_dets=args.save_dets, num_devices=args.num_devices)


if __name__ == "__main__":
    main()
