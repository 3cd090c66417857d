"""A cell cut to a size a CPU test run can hold: ResNet-50 or VGG16 at full
width, a 96x128 bucket, two images a step.  Built from the real cell's own
files, so the harness under test is the one the chip runs."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def tiny_cell(name: str = "r101-coco.train") -> dict:
    cell = bench_run.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    net = config["network"]
    if net["family"] == "resnet":
        config["program"]["network"] = "resnet50"
        net["depth"] = 50
    config["program"]["overrides"].update({
        "bucket__scale": 96, "bucket__max_size": 128,
        "bucket__shapes": [[96, 128]], "network__anchor_scales": [2, 4, 8],
        "default__frequent": 2})
    config["bucket"] = [96, 128]
    net["anchor_scales"] = [2, 4, 8]
    traffic.update({"image_hw": [96, 128], "images_per_chip": 8,
                    "per_chip_batch": 2, "box_side": [16, 60],
                    "warmup_steps": 4, "epoch_steps": 100})
    cell["check"]["block"] = 2
    return cell
