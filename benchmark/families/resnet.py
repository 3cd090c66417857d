"""The ResNet C4 family (mx-rcnn ``symbol_resnet.py``): stages 1-3 are the
backbone, stage 4 is the per-ROI head.  Everything the benchmark knows of
the family is here: its parameter rows, its plain forward passes, its
layer table for the operation count and (``STAGES``) its step's scopes."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops
from benchmark.families import _detector
from benchmark.reference.nets import bn_rows, max_pool

BN_EPS = 2e-5
UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}
FILTERS = (256, 512, 1024, 2048)
FEAT_CHANNELS, HEAD_CHANNELS = 1024, 2048
STAGES = _detector.STAGES
# the residual branch's last convolution starts small and random: at zero
# (the program's own init) nothing flows back through the branch in the
# first step and conv1/conv2 of every unit would go uncompared; at full
# scale, with identity batch norm, every unit doubles the variance
BRANCH_INIT = "he0.2"


# ---- parameter rows ---------------------------------------------------------

def _unit_rows(path, cin, filters, first):
    mid = filters // 4
    rows = bn_rows(path + ("bn1",), cin)
    rows.append((path + ("conv1", "kernel"), (1, 1, cin, mid), "he"))
    rows += bn_rows(path + ("bn2",), mid)
    rows.append((path + ("conv2", "kernel"), (3, 3, mid, mid), "he"))
    rows += bn_rows(path + ("bn3",), mid)
    rows.append((path + ("conv3", "kernel"), (1, 1, mid, filters),
                 BRANCH_INIT))
    if first:
        rows.append((path + ("sc", "kernel"), (1, 1, cin, filters), "he"))
    return rows


def _stage_rows(top, stage, cin, filters, units):
    rows = []
    for u in range(units):
        rows += _unit_rows((top, f"stage{stage}_unit{u + 1}"),
                           cin if u == 0 else filters, filters, u == 0)
    return rows


def param_rows(net):
    units = UNITS[net["depth"]]
    rows = bn_rows(("backbone", "bn_data"), 3)
    rows.append((("backbone", "conv0", "kernel"), (7, 7, 3, 64), "he"))
    rows += bn_rows(("backbone", "bn0"), 64)
    cin = 64
    for s in (1, 2, 3):
        rows += _stage_rows("backbone", s, cin, FILTERS[s - 1], units[s - 1])
        cin = FILTERS[s - 1]
    rows += _stage_rows("head", 4, cin, FILTERS[3], units[3])
    return rows + bn_rows(("head", "bn1"), FILTERS[3])


# ---- forward passes ---------------------------------------------------------

def _bn(p, x):
    # running mean 0 and variance 1: y = scale * x / sqrt(1 + eps) + bias
    return x * (p["scale"] / np.sqrt(1.0 + BN_EPS)) + p["bias"]


def _unit(p, x, stride, mm):
    a1 = jax.nn.relu(_bn(p["bn1"], x))
    c1 = mm.conv(a1, p["conv1"]["kernel"])
    c2 = mm.conv(jax.nn.relu(_bn(p["bn2"], c1)), p["conv2"]["kernel"], stride)
    c3 = mm.conv(jax.nn.relu(_bn(p["bn3"], c2)), p["conv3"]["kernel"])
    short = mm.conv(a1, p["sc"]["kernel"], stride) if "sc" in p else x
    return mm.keep(c3 + short)


def _stage(p, x, stage, units, stride, mm):
    """Unit 1 changes the shape; units 2.. are alike, so they run as one
    scanned body over their stacked weights (the same arithmetic as a loop,
    a fraction of the compiled code)."""
    x = _unit(p[f"stage{stage}_unit1"], x, stride, mm)
    rest = [p[f"stage{stage}_unit{u + 1}"] for u in range(1, units)]
    if rest and not mm.scan:
        for q in rest:
            x = _unit(q, x, 1, mm)
    elif rest:
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *rest)
        x, _ = jax.lax.scan(lambda y, q: (_unit(q, y, 1, mm), None), x,
                            stacked)
    return x


def backbone(net, params, x, mm):
    p = params["backbone"]
    units = UNITS[net["depth"]]
    x = mm.conv(_bn(p["bn_data"], x), p["conv0"]["kernel"], 2)
    x = max_pool(jax.nn.relu(_bn(p["bn0"], x)), 3, 2, 1)
    x = _stage(p, x, 1, units[0], 1, mm)
    x = _stage(p, x, 2, units[1], 2, mm)
    return _stage(p, x, 3, units[2], 2, mm)


def head(net, params, pooled, mm, drop_masks=None):
    """pooled (R, ph, pw, C) -> one feature row a ROI."""
    p = params["head"]
    x = _stage(p, pooled, 4, UNITS[net["depth"]][3], 2, mm)
    return jax.nn.relu(_bn(p["bn1"], x)).mean((1, 2))


# ---- layer table --------------------------------------------------------------

def _half(hw, s):
    return (-(-hw[0] // s), -(-hw[1] // s))


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one image of the
    traffic.  conv0 and stage 1 are frozen with nothing trainable before
    them (forward only); the first trainable layers need no gradient for
    their input."""
    net, image_hw = config["network"], _detector.image_hw(traffic)
    conv, rois = flops.conv, _detector.rois(config)
    units = UNITS[net["depth"]]
    rows = [conv("conv0", "backbone", 3, 64, 7, 2, _half(image_hw, 2),
                 1, "none")]
    hw, cin = _half(image_hw, 4), 64
    for stage in (1, 2, 3):
        f = FILTERS[stage - 1]
        grad = "none" if stage == 1 else "both"
        for u in range(units[stage - 1]):
            s = 2 if (u == 0 and stage > 1) else 1
            out = _half(hw, s)
            pre = f"stage{stage}_unit{u + 1}"
            first = "weight" if (stage == 2 and u == 0) else grad
            rows.append(conv(f"{pre}/conv1", "backbone", cin, f // 4, 1, 1,
                             hw, 1, first))
            rows.append(conv(f"{pre}/conv2", "backbone", f // 4, f // 4, 3, s,
                             out, 1, grad))
            rows.append(conv(f"{pre}/conv3", "backbone", f // 4, f, 1, 1, out,
                             1, grad))
            if u == 0:
                rows.append(conv(f"{pre}/sc", "backbone", cin, f, 1, s, out,
                                 1, first))
            hw, cin = out, f
    feat_hw = hw
    phw = tuple(net["pooled_size"])
    head_rows = []
    for u in range(units[3]):
        s = 2 if u == 0 else 1
        out = _half(phw, s)
        pre = f"stage4_unit{u + 1}"
        f = FILTERS[3]
        head_rows.append(conv(f"{pre}/conv1", "rcnn_losses", cin, f // 4, 1,
                              1, phw, rois, "both"))
        head_rows.append(conv(f"{pre}/conv2", "rcnn_losses", f // 4, f // 4,
                              3, s, out, rois, "both"))
        head_rows.append(conv(f"{pre}/conv3", "rcnn_losses", f // 4, f, 1, 1,
                              out, rois, "both"))
        if u == 0:
            head_rows.append(conv(f"{pre}/sc", "rcnn_losses", cin, f, 1, s,
                                  out, rois, "both"))
        phw, cin = out, f
    return _detector.table(config, rows, feat_hw, head_rows, FEAT_CHANNELS,
                           HEAD_CHANNELS)
