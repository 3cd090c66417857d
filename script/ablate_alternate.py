"""Ablation driver for the alternate-training mAP gap (VERDICT r02 item 5).

Round-2 hardware runs on the full-size synthetic recipe scored e2e 0.84
vs alternate 0.68.  This script reruns the 4-stage schedule under
controlled variants to localize the loss:

  e2e        — end-to-end baseline (10 epochs).
  alt        — alternate with stage2_init='rpn1' (the round-2 default,
               the 0.68 configuration).
  alt-nofreeze — stages 3/4 train the shared convs instead of freezing
               them.  The paper freezes ImageNet-initialized shared convs;
               with no pretrained weights (this machine), the frozen
               features are whatever 8 epochs of from-scratch RPN+RCNN
               produced — hypothesis: freezing THOSE is the gap.
  alt-fresh2 — stage 2 initializes fresh instead of from rpn1 (now the
               tool's default, adopted FROM this ablation).
  alt-long   — stages run e2e-length (10 epochs each).

Each variant trains, combines, and evaluates with tools.test; 'alt'
additionally evaluates the mid-schedule rpn1+rcnn1 combination so
stage-3/4 regressions are visible separately.

Usage:  python script/ablate_alternate.py [--variants alt,e2e,...]
        [--root data/ablate_alt]
Writes <root>/results.json and prints one line per variant.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys

# run on CPU unless told otherwise: ablations must not contend with
# benchmarks for the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.tester import Predictor, pred_eval
from mx_rcnn_tpu.core.train import TrainState
from mx_rcnn_tpu.data import TestLoader, load_gt_roidb
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.train import train_net
from mx_rcnn_tpu.tools.train_alternate import alternate_train
from mx_rcnn_tpu.utils.checkpoint import (combine_model, load_param,
                                          save_checkpoint)

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
log = logging.getLogger("ablate")


def evaluate(cfg, prefix: str, epoch: int) -> float:
    imdb, roidb = load_gt_roidb(cfg, training=False)
    loader = TestLoader(roidb, cfg)
    model = build_model(cfg)
    params, batch_stats = load_param(prefix, epoch)
    predictor = Predictor(model, {"params": params,
                                  "batch_stats": batch_stats}, cfg)
    results = pred_eval(predictor, loader, imdb, cfg, verbose=False)
    return float(results["mAP"])


def combine_eval(cfg, rpn_prefix, rpn_epoch, rcnn_prefix, rcnn_epoch,
                 out_prefix) -> float:
    p_rpn, s_rpn = load_param(rpn_prefix, rpn_epoch)
    p_rcnn, s_rcnn = load_param(rcnn_prefix, rcnn_epoch)
    params = combine_model(p_rpn, p_rcnn, from_a=("rpn", "backbone"))
    stats = combine_model(s_rpn, s_rcnn, from_a=("backbone",))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats=stats, opt_state={})
    save_checkpoint(out_prefix, 1, state)
    return evaluate(cfg, out_prefix, 1)


def run_variant(name: str, root: str, seed: int = 0) -> dict:
    cfg = generate_config("tiny", "synthetic")
    cfg = cfg.replace_in("dataset", root_path=root)
    prefix = os.path.join(root, f"model/{name}-s{seed}")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    out = {"variant": name, "seed": seed}

    if name == "e2e":
        train_net(cfg, prefix=prefix, end_epoch=10, seed=seed)
        out["mAP"] = evaluate(cfg, prefix, 10)
        return out

    kw = {}
    if name == "alt":
        kw = dict(stage2_init="rpn1")  # the round-2 default under test
    elif name == "alt-nofreeze":
        # stages 3/4 keep training the shared convs: replace the shared
        # freeze set with the ordinary FIXED_PARAMS set
        cfg = cfg.replace_in("network",
                             fixed_params_shared=cfg.network.fixed_params,
                             )
        kw = dict(stage2_init="rpn1")
    elif name == "alt-long":
        kw = dict(rpn_epoch=10, rcnn_epoch=10, stage2_init="rpn1")
    # alt-fresh2: the tool default (stage2_init='fresh'), no kw needed

    d = cfg.default
    rpn_ep = kw.get("rpn_epoch", d.rpn_epoch)
    rcnn_ep = kw.get("rcnn_epoch", d.rcnn_epoch)
    final = alternate_train(cfg, prefix=prefix, seed=seed, **kw)
    out["mAP"] = evaluate(cfg, final, 1)
    if name == "alt":
        # mid-schedule diagnostic: rpn1 + rcnn1 combined
        out["mAP_rpn1_rcnn1"] = combine_eval(
            cfg, f"{prefix}-rpn1", rpn_ep, f"{prefix}-rcnn1", rcnn_ep,
            f"{prefix}-mid")
    return out







def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--root", default="data/ablate_alt")
    p.add_argument("--variants",
                   default="e2e,alt,alt-nofreeze,alt-fresh2,alt-long")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    results = []
    res_path = os.path.join(args.root, "results.json")
    if os.path.exists(res_path):
        results = json.load(open(res_path))
    done = {(r["variant"], r.get("seed", 0)) for r in results}
    for name in args.variants.split(","):
        if (name, args.seed) in done:
            log.info("skip %s (already in results.json)", name)
            continue
        log.info("=== variant %s ===", name)
        r = run_variant(name, args.root, seed=args.seed)
        results.append(r)
        os.makedirs(args.root, exist_ok=True)
        with open(res_path, "w") as f:
            json.dump(results, f, indent=1)
        log.info("RESULT %s", r)
    for r in results:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
