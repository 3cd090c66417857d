"""What the single-level C4 detector families (``resnet.py``, ``vgg.py``)
share of the operation count: the RPN head on the one feature level,
ROIAlign, the two output layers, and the train step's named scopes.  No
family of its own: a file here whose name starts with ``_`` is a helper."""

from __future__ import annotations

from benchmark import flops

# the stages a device op can lie under (core/train.py); what lies under none
# is step.unscoped_ms
STAGES = ("backbone", "rpn_head", "rpn_losses", "proposal", "rcnn_losses",
          "optimizer", "grad_sync")


def image_hw(traffic):
    """The extent of the images the traffic sends (not of the bucket they
    are padded into)."""
    return tuple(traffic["image_hw"])


def rois(config):
    """How often a per-ROI row runs an image."""
    return config["train"]["batch_rois"]


def table(config, backbone_rows, feat_hw, head_rows, feat_channels,
          head_channels):
    """The whole table of one image: the family's backbone rows, the RPN
    head on its features, ROIAlign, the family's per-ROI head, the two
    output layers."""
    net = config["network"]
    anchors, classes, n = net["num_anchors"], net["num_classes"], rois(config)
    rows = list(backbone_rows) + [
        flops.conv("rpn_conv_3x3", "rpn_head", feat_channels, 512, 3, 1,
                   feat_hw, 1, "both"),
        flops.conv("rpn_cls_score", "rpn_head", 512, 2 * anchors, 1, 1,
                   feat_hw, 1, "both"),
        flops.conv("rpn_bbox_pred", "rpn_head", 512, 4 * anchors, 1, 1,
                   feat_hw, 1, "both"),
        {"name": "roialign", "scope": "rcnn_losses", "kind": "roialign",
         "out_hw": list(net["pooled_size"]), "c": feat_channels,
         "ratio": 2, "times": n, "grad": "input"}]
    rows += head_rows
    rows += [flops.dense("cls_score", "rcnn_losses", head_channels, classes,
                         n),
             flops.dense("bbox_pred", "rcnn_losses", head_channels,
                         4 * classes, n)]
    return rows
