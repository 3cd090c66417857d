"""Plain forward passes and parameter tables of the reference networks.

A network is a list of ``(path, shape, init)`` rows (its parameter table,
from which the benchmark makes the weights) and three pure functions over
the nested parameter dict: ``backbone`` (image -> stride-16 features),
``rpn`` and ``head`` (pooled ROI features -> class logits, box deltas).
What differs between families (ResNet C4, VGG16) is one file a family,
``benchmark/families/<family>.py``, found by the configuration's
``network.family``; what they share (the RPN, the two output layers, the
precision policies) is here.  Paths follow the layer names of the published
symbols (mx-rcnn ``symbol_resnet.py`` / ``symbol_vgg.py``), which are also
the names the program's checkpoints use; that shared naming is how weights
are handed over.  Batch norm runs on fixed statistics, as the recipe states.

``mm`` is the contraction policy: every convolution and matrix product
goes through it, so the precision the reference computes in is one
argument (``policy`` below).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np


# ---- precision policies ---------------------------------------------------

def _straight_through(rnd):
    """x -> rnd(x) whose gradient is that of the identity."""
    @jax.custom_vjp
    def f(x):
        return rnd(x)

    f.defvjp(lambda x: (rnd(x), None), lambda _, g: (g,))
    return f


def _round_cotangent(rnd):
    """The identity, whose incoming cotangent is rounded: placed on a
    contraction's output, it rounds what the backward contractions get."""
    @jax.custom_vjp
    def f(y):
        return y

    f.defvjp(lambda y: (y, None), lambda _, g: (rnd(g),))
    return f


def _scaled(e, m, top):
    """Round to ``e`` exponent and ``m`` mantissa bits on a per-tensor scale
    (the largest magnitude lands on ``top``)."""
    def rnd(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return jax.lax.reduce_precision(x / scale, e, m) * scale
    return rnd


def policy(name):
    """(operand, kernel, cotangent, kept activation) roundings of a
    precision.  Rounding is ``lax.reduce_precision``, which the compiler
    may not fold away; every contraction itself runs in float32 at
    ``highest``.

    'float32' rounds nothing and is the reference proper.  'float8' is the
    lower-precision control: the configuration keeps every activation in
    bfloat16 by a plain cast, so the control keeps every activation in
    float8 by a plain cast (E5M2, the 8-bit format with the range for it):
    contraction operands, kernels, contraction outputs, residual sums and
    the cotangents the backward contractions get.  'float8_scaled' is the
    careful 8-bit training recipe (E4M3 operands and kernels, E5M2
    cotangents, each on a per-tensor scale, at the contractions alone),
    kept because one limit's upper reading comes from it (PERF.md)."""
    same = lambda x: x  # noqa: E731
    if name == "float32":
        return same, same, same, same
    if name == "float8":
        e5m2 = lambda x: jax.lax.reduce_precision(x, 5, 2)  # noqa: E731
        cast = _straight_through(e5m2)
        return cast, cast, _round_cotangent(e5m2), cast
    if name == "float8_scaled":
        e4m3 = _straight_through(_scaled(4, 3, 224.0))
        return e4m3, e4m3, _round_cotangent(_scaled(5, 2, 2.0 ** 14)), same
    raise ValueError(f"unknown precision policy {name!r}")


class Contract:
    """Convolutions, dense layers and einsums under one precision policy."""

    def __init__(self, name="float32", scan=True):
        self.name = name
        self.act, self.kernel, self.cot, self.keep = policy(name)
        self.precision = jax.lax.Precision.HIGHEST
        # alike units as one scanned body: the TPU compiler takes minutes
        # less; XLA's CPU backend runs convolutions in a loop very slowly,
        # so CPU tests pass scan=False
        self.scan = scan

    def _out(self, y):
        """A contraction's output: kept in the activation format, its
        cotangent rounded before the backward contractions get it."""
        return self.keep(self.cot(y))

    def conv(self, x, kernel, stride=1):
        return self._out(jax.lax.conv_general_dilated(
            self.act(x), self.kernel(kernel), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.precision))

    def dense(self, x, kernel):
        return self._out(jnp.matmul(self.act(x), self.kernel(kernel),
                                    precision=self.precision))

    def __call__(self, spec, a, b):
        """An einsum of two activations (ROIAlign's interpolation)."""
        return self._out(jnp.einsum(spec, self.act(a), self.act(b),
                                    precision=self.precision))


# ---- parameter tables -----------------------------------------------------

def family(net):
    """The module ``benchmark/families/<family>.py`` of ``net``'s family:
    its ``param_rows``, ``backbone``, ``head`` and ``layers``."""
    return importlib.import_module(f"benchmark.families.{net['family']}")


def bn_rows(path, c):
    return [(path + ("scale",), (c,), "ones"), (path + ("bias",), (c,), "zeros")]


def conv_rows(path, k, cin, cout, init):
    return [(path + ("kernel",), (k, k, cin, cout), init),
            (path + ("bias",), (cout,), "zeros")]


def _shared_rows(feat_c, head_c, num_classes, anchors):
    rows = conv_rows(("rpn", "rpn_conv_3x3"), 3, feat_c, 512, "normal0.01")
    rows += conv_rows(("rpn", "rpn_cls_score"), 1, 512, 2 * anchors,
                      "normal0.01")
    rows += conv_rows(("rpn", "rpn_bbox_pred"), 1, 512, 4 * anchors,
                      "normal0.01")
    rows += [(("cls_score", "kernel"), (head_c, num_classes), "normal0.01"),
             (("cls_score", "bias"), (num_classes,), "zeros"),
             (("bbox_pred", "kernel"), (head_c, 4 * num_classes),
              "normal0.001"),
             (("bbox_pred", "bias"), (4 * num_classes,), "zeros")]
    return rows


def param_table(net):
    """Rows ``(path, shape, init)`` of every trainable-or-frozen parameter
    of ``net`` (the 'network' group of a configuration file): the family's
    own, then the RPN and the two output layers every family shares."""
    fam = family(net)
    return fam.param_rows(net) + _shared_rows(
        fam.FEAT_CHANNELS, fam.HEAD_CHANNELS, net["num_classes"],
        net["num_anchors"])


def make_weights(net, seed):
    """The nested parameter dict, made on the device in one jitted call
    from ``seed``: row ``i`` of the table draws from ``fold_in(key, i)``.
    ``init``: 'ones', 'zeros', 'normal<std>', 'lecun', or 'he' with an
    optional factor ('he0.2' is a fifth of He's deviation)."""
    table = param_table(net)

    def draw(key):
        out = {}
        for i, (path, shape, init) in enumerate(table):
            k = jax.random.fold_in(key, i)
            if init == "ones":
                v = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                v = jnp.zeros(shape, jnp.float32)
            elif init.startswith("normal"):
                v = float(init[6:]) * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = int(np.prod(shape[:-1]))
                gain = 1.0 if init == "lecun" else 2.0
                factor = float(init[2:] or 1) if init.startswith("he") else 1.0
                v = factor * np.sqrt(gain / fan_in) * jax.random.normal(
                    k, shape, jnp.float32)
            node = out
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = v
        return out

    return jax.jit(draw)(jax.random.PRNGKey(seed % (2 ** 31)))


def fixed_stats(params):
    """Identity running statistics for every batch-norm scope of ``params``
    (the recipe trains on frozen statistics; random-weight runs have none
    to load)."""
    def walk(node):
        out = {}
        for name, sub in node.items():
            if isinstance(sub, dict):
                if set(sub) == {"scale", "bias"}:
                    out[name] = {"mean": jnp.zeros_like(sub["bias"]),
                                 "var": jnp.ones_like(sub["scale"])}
                else:
                    inner = walk(sub)
                    if inner:
                        out[name] = inner
        return out
    return walk(params)


# ---- forward passes -------------------------------------------------------

def max_pool(x, k, s, pad):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def backbone(net, params, x, mm):
    """image (N, H, W, 3) -> stride-16 features."""
    return family(net).backbone(net, params, x, mm)


def rpn(params, feat, mm):
    """feat (N, H, W, C) -> logits (N, H*W*A, 2), deltas (N, H*W*A, 4)."""
    p = params["rpn"]
    x = jax.nn.relu(mm.conv(feat, p["rpn_conv_3x3"]["kernel"])
                    + p["rpn_conv_3x3"]["bias"])
    cls = mm.conv(x, p["rpn_cls_score"]["kernel"]) + p["rpn_cls_score"]["bias"]
    box = mm.conv(x, p["rpn_bbox_pred"]["kernel"]) + p["rpn_bbox_pred"]["bias"]
    n = feat.shape[0]
    return cls.reshape(n, -1, 2), box.reshape(n, -1, 4)


def head(net, params, pooled, mm, drop_masks=None):
    """pooled (R, ph, pw, C) -> class logits (R, classes), deltas."""
    x = family(net).head(net, params, pooled, mm, drop_masks)
    cls = mm.dense(x, params["cls_score"]["kernel"]) + params["cls_score"]["bias"]
    box = mm.dense(x, params["bbox_pred"]["kernel"]) + params["bbox_pred"]["bias"]
    return cls, box
