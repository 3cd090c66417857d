"""Operations and bytes of a configuration's training step, counted from
its layer table, and the chip's peaks.

The table is built when it is asked for, from the family's own rule
(``benchmark/families/<family>.py::layers``) at the extent of the images
the traffic sends, not of the bucket they are padded into: convolving the
padding is work the algorithm does not require.

A table row is one layer: ``kind`` 'conv' | 'dense' | 'roialign', its
shapes, the named ``scope`` its device time is found under, ``per``
'image' | 'roi' (how often it runs), and ``grad``: 'none' (frozen and
nothing trainable before it: forward only), 'weight', 'input' or 'both'.
Only operations the algorithm requires are counted: a multiply-add is two,
recomputation counts nothing, and a backward pass costs one forward's worth
for each of the input gradient and the weight gradient that exists.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
PASSES = {"none": 1, "weight": 2, "input": 2, "both": 3}


def peaks(device_kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def _conv(name, scope, cin, cout, k, stride, out_hw, per, grad):
    return {"name": name, "scope": scope, "kind": "conv", "cin": cin,
            "cout": cout, "k": k, "stride": stride, "out_hw": list(out_hw),
            "per": per, "grad": grad}


def _dense(name, scope, cin, cout, per, grad="both"):
    return {"name": name, "scope": scope, "kind": "dense", "cin": cin,
            "cout": cout, "per": per, "grad": grad}


def layer_table(config: Dict, image_hw) -> List[Dict]:
    """Every layer of ``config``'s training step on an image of
    ``image_hw``: the family's backbone rows, the RPN head on its
    features, ROIAlign, the family's per-ROI head, the two output layers."""
    net = config["network"]
    fam = importlib.import_module(f"benchmark.families.{net['family']}")
    rows, feat_hw, head_rows = fam.layers(net, image_hw, _conv, _dense)
    anchors, classes = net["num_anchors"], net["num_classes"]
    rows += [
        _conv("rpn_conv_3x3", "rpn_head", fam.FEAT_CHANNELS, 512, 3, 1,
              feat_hw, "image", "both"),
        _conv("rpn_cls_score", "rpn_head", 512, 2 * anchors, 1, 1, feat_hw,
              "image", "both"),
        _conv("rpn_bbox_pred", "rpn_head", 512, 4 * anchors, 1, 1, feat_hw,
              "image", "both"),
        {"name": "roialign", "scope": "rcnn_losses", "kind": "roialign",
         "out_hw": list(net["pooled_size"]), "c": fam.FEAT_CHANNELS,
         "ratio": 2, "per": "roi", "grad": "input"}]
    rows += head_rows
    rows += [_dense("cls_score", "rcnn_losses", fam.HEAD_CHANNELS, classes,
                    "roi"),
             _dense("bbox_pred", "rcnn_losses", fam.HEAD_CHANNELS,
                    4 * classes, "roi")]
    return rows


def forward_flops(layer: Dict) -> float:
    """One forward application of ``layer``."""
    kind = layer["kind"]
    if kind == "conv":
        oh, ow = layer["out_hw"]
        k = layer["k"]
        return 2.0 * k * k * layer["cin"] * layer["cout"] * oh * ow
    if kind == "dense":
        return 2.0 * layer["cin"] * layer["cout"]
    if kind == "roialign":
        ph, pw = layer["out_hw"]
        # 4 taps, a multiply-add each, at ratio^2 sample points a bin
        return 2.0 * 4 * layer["ratio"] ** 2 * ph * pw * layer["c"]
    raise ValueError(f"unknown layer kind {kind!r}")


def forward_bytes(layer: Dict, width: int = 2) -> float:
    """Bytes one forward application has to move: input, output, weights,
    at ``width`` bytes an element (bf16 activations)."""
    kind = layer["kind"]
    if kind == "conv":
        oh, ow = layer["out_hw"]
        s, k = layer["stride"], layer["k"]
        return width * (oh * s * ow * s * layer["cin"]
                        + oh * ow * layer["cout"]
                        + k * k * layer["cin"] * layer["cout"])
    if kind == "dense":
        return width * (layer["cin"] + layer["cout"]
                        + layer["cin"] * layer["cout"])
    ph, pw = layer["out_hw"]
    return width * 5 * layer["ratio"] ** 2 * ph * pw * layer["c"]


def _times(layer: Dict, rois_per_image: int) -> int:
    return rois_per_image if layer["per"] == "roi" else 1


def step_flops_per_image(layers: List[Dict], rois_per_image: int,
                         scope: str = None) -> float:
    """Forward + backward operations for one image (its ROIs with it),
    of every layer or of those under ``scope``."""
    return sum(forward_flops(l) * PASSES[l["grad"]] * _times(l, rois_per_image)
               for l in layers if scope is None or l["scope"] == scope)


def least_seconds_per_image(layers: List[Dict], rois_per_image: int,
                            peak: Dict, scope: str) -> Tuple[float, str]:
    """Least time the chip could take for ``scope``'s layers of one image,
    layer by layer the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two bounds most of it.  Weights shared
    by the images of a batch are counted once per image (an upper count of
    bytes; these layers are compute-bound all the same)."""
    by_flops = by_bytes = total = 0.0
    for l in layers:
        if l["scope"] != scope:
            continue
        n = PASSES[l["grad"]] * _times(l, rois_per_image)
        tf = forward_flops(l) * n / peak["flops_per_s"]
        tb = forward_bytes(l) * n / peak["bytes_per_s"]
        total += max(tf, tb)
        by_flops += tf
        by_bytes += tb
    return total, ("compute" if by_flops >= by_bytes else "memory")
