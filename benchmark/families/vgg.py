"""The VGG16 family (mx-rcnn ``symbol_vgg.py``): conv1_1 .. conv5_3 are the
backbone, fc6/fc7 with dropout the per-ROI head.  Everything the benchmark
knows of the family is here: its parameter rows, its plain forward passes,
its layer table for the operation count and (``STAGES``) its step's scopes."""

from __future__ import annotations

import jax

from benchmark import flops
from benchmark.families import _detector
from benchmark.reference.nets import conv_rows, max_pool

BLOCKS = (("conv1", 2, 64), ("conv2", 2, 128), ("conv3", 3, 256),
          ("conv4", 3, 512), ("conv5", 3, 512))
FEAT_CHANNELS, HEAD_CHANNELS = 512, 4096
STAGES = _detector.STAGES


def param_rows(net):
    rows, cin = [], 3
    for name, n, f in BLOCKS:
        for j in range(n):
            rows += conv_rows(("backbone", f"{name}_{j + 1}"), 3, cin, f, "he")
            cin = f
    ph, pw = net["pooled_size"]
    return rows + [
        (("head", "fc6", "kernel"), (ph * pw * cin, HEAD_CHANNELS), "lecun"),
        (("head", "fc6", "bias"), (HEAD_CHANNELS,), "zeros"),
        (("head", "fc7", "kernel"), (HEAD_CHANNELS, HEAD_CHANNELS), "lecun"),
        (("head", "fc7", "bias"), (HEAD_CHANNELS,), "zeros")]


def backbone(net, params, x, mm):
    p = params["backbone"]
    for i, (name, n, _) in enumerate(BLOCKS):
        for j in range(n):
            layer = p[f"{name}_{j + 1}"]
            x = jax.nn.relu(mm.conv(x, layer["kernel"]) + layer["bias"])
        if i < 4:
            x = max_pool(x, 2, 2, 0)
    return x


def head(net, params, pooled, mm, drop_masks=None):
    """pooled (R, ph, pw, C) -> one feature row a ROI."""
    p = params["head"]
    x = pooled.reshape(pooled.shape[0], -1)
    for i, name in enumerate(("fc6", "fc7")):
        x = jax.nn.relu(mm.dense(x, p[name]["kernel"]) + p[name]["bias"])
        if drop_masks is not None:
            x = x * drop_masks[i]
    return x


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one image of the
    traffic.  conv1 and conv2 are frozen (forward only); conv3_1, the first
    trainable layer, needs no gradient for its input."""
    net, rois = config["network"], _detector.rois(config)
    rows, cin, hw = [], 3, _detector.image_hw(traffic)
    for i, (name, n, f) in enumerate(BLOCKS):
        for j in range(n):
            grad = ("none" if i < 2 else
                    "weight" if (i, j) == (2, 0) else "both")
            rows.append(flops.conv(f"{name}_{j + 1}", "backbone", cin, f, 3,
                                   1, hw, 1, grad))
            cin = f
        if i < 4:
            hw = (hw[0] // 2, hw[1] // 2)
    ph, pw = net["pooled_size"]
    head_rows = [flops.dense("fc6", "rcnn_losses", ph * pw * cin,
                             HEAD_CHANNELS, rois),
                 flops.dense("fc7", "rcnn_losses", HEAD_CHANNELS,
                             HEAD_CHANNELS, rois)]
    return _detector.table(config, rows, hw, head_rows, FEAT_CHANNELS,
                           HEAD_CHANNELS)
