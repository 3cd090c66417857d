"""The composite Faster R-CNN model.

Reference: the train/test symbol builders ``get_vgg_train/test`` and
``get_resnet_train/test`` (``rcnn/symbol/``).  The reference builds a
separate static graph per phase; here ONE flax module exposes the pieces —
``features`` (shared backbone), ``rpn_raw`` (RPN head), ``roi_head``
(per-ROI classifier/regressor) — and the phase pipelines are pure
functions: the training step (``core/train.py``) wires targets + losses, the
predictor (``core/tester.py``) wires proposal + detection decoding, both
around the same weights.

``__call__`` implements the full test-mode forward (the equivalent of the
reference test symbol): images → features → RPN → proposal → ROIAlign →
head → (rois, cls_prob, bbox_deltas), entirely inside one XLA program.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.resnet import ResNetBackbone, ResNetHead
from mx_rcnn_tpu.models.rpn import RPNHead
from mx_rcnn_tpu.models.vgg import VGGBackbone, VGGHead
from mx_rcnn_tpu.ops.anchors import generate_shifted_anchors
from mx_rcnn_tpu.ops.normalize import normalize_images
from mx_rcnn_tpu.ops.proposal import propose_batch
from mx_rcnn_tpu.ops.quant import QuantSpec
from mx_rcnn_tpu.ops.roi_pool import roi_align_batched

Dtype = Any


class FasterRCNN(nn.Module):
    """Backbone + RPN + RCNN head with reference-matching hyperparameters.

    Fields mirror the per-network config block (ref ``rcnn/config.py``).
    """

    network: str = "resnet101"          # 'vgg' | 'resnet50' | 'resnet101'
    num_classes: int = 21
    anchor_scales: Tuple[int, ...] = (8, 16, 32)
    anchor_ratios: Tuple[float, ...] = (0.5, 1.0, 2.0)
    feat_stride: int = 16
    pooled_size: Tuple[int, int] = (14, 14)
    # test-time proposal params (ref config.TEST)
    test_pre_nms_top_n: int = 6000
    test_post_nms_top_n: int = 300
    test_nms_thresh: float = 0.7
    test_min_size: int = 16
    # ref PIXEL_MEANS — applied ON DEVICE when the loader ships raw uint8
    # batches (ops/normalize.py); fp32 host-normalized input passes through
    pixel_means: Tuple[float, ...] = (123.68, 116.779, 103.939)
    dtype: Dtype = jnp.float32
    # inference-only quantization recipe (ops/quant.py — cfg.quant):
    # covers the backbone convs and the head trunk; the RPN head and the
    # final cls_score/bbox_pred projections stay fp (first/last-layer
    # exemption, standard PTQ playbook).  None = the unchanged fp model.
    quant: Optional[QuantSpec] = None
    # backbone layout lever (docs/PERF.md "Quantized inference" —
    # layout levers): zero-pad the stem's input channels 3 -> this many
    # before conv0, aligning the channel axis for lane-friendly layouts.
    # Padded channels are exactly zero so every conv sum is unchanged —
    # output BIT-identical to the 3-channel model given the same first-3
    # kernel channels (pinned by tests/test_quant.py); param shapes DO
    # change (conv0 kernel grows an input channel), so this is not a
    # checkpoint-compatible default; no cell has measured it (ROADMAP D5).
    # 0 = off.
    stem_channel_pad: int = 0

    @property
    def num_anchors(self) -> int:
        return len(self.anchor_scales) * len(self.anchor_ratios)

    def setup(self):
        if self.network == "vgg":
            self.backbone = VGGBackbone(dtype=self.dtype, quant=self.quant)
            self.head = VGGHead(dtype=self.dtype, quant=self.quant)
        elif self.network in ("resnet50", "resnet101"):
            depth = int(self.network.replace("resnet", ""))
            self.backbone = ResNetBackbone(depth=depth, dtype=self.dtype,
                                           quant=self.quant)
            self.head = ResNetHead(depth=depth, dtype=self.dtype,
                                   quant=self.quant)
        elif self.network == "tiny":  # test-only miniature (models/tiny.py)
            from mx_rcnn_tpu.models.tiny import TinyBackbone, TinyHead
            self.backbone = TinyBackbone(dtype=self.dtype, quant=self.quant)
            self.head = TinyHead(dtype=self.dtype, quant=self.quant)
        else:
            raise ValueError(f"unknown network {self.network!r}")
        head_out_init = nn.initializers.normal(0.01)
        self.rpn = RPNHead(num_anchors=self.num_anchors, dtype=self.dtype)
        # ref: cls_score Normal(0.01), bbox_pred Normal(0.001)
        self.cls_score = nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=head_out_init, name="cls_score")
        self.bbox_pred = nn.Dense(
            4 * self.num_classes, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.001), name="bbox_pred")

    # ---- pieces (used by the train step) ----------------------------------

    def features(self, images: jnp.ndarray,
                 im_info: jnp.ndarray = None) -> jnp.ndarray:
        """(N, H, W, 3) RGB → (N, H/16, W/16, C) backbone features.

        ``images`` is either fp32 mean-subtracted (host-normalized path) or
        raw uint8 (TPU-native path) — uint8 needs ``im_info`` so the
        on-device normalization masks padding back to exact zeros."""
        images = normalize_images(images, im_info, self.pixel_means)
        pad = self.stem_channel_pad - images.shape[-1]
        if pad > 0:  # layout lever: zero channels add exactly 0 per sum
            images = jnp.pad(images, [(0, 0)] * 3 + [(0, pad)])
        return self.backbone(images)

    def rpn_raw(self, feat: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """feat → ((N, H*W*A, 2) cls logits, (N, H*W*A, 4) deltas)."""
        return self.rpn(feat)

    def roi_head(self, pooled: jnp.ndarray, train: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(R, ph, pw, C) pooled ROI features → ((R, classes) cls logits,
        (R, 4*classes) bbox deltas)."""
        x = self.head(pooled, train) if self.network == "vgg" else self.head(pooled)
        return self.cls_score(x), self.bbox_pred(x)

    def anchors_for(self, feat_h: int, feat_w: int) -> jnp.ndarray:
        """Constant (H*W*A, 4) anchor grid for a static feature shape."""
        return jnp.asarray(
            generate_shifted_anchors(
                feat_h, feat_w, self.feat_stride,
                self.anchor_ratios, self.anchor_scales,
            )
        )

    def rpn_proposals(self, images: jnp.ndarray, im_info: jnp.ndarray,
                      pre_nms_top_n: int = 6000, post_nms_top_n: int = 300
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """RPN-only forward (ref ``get_*_rpn_test`` symbol): images →
        (rois, fg scores, valid) — used by generate_proposals in alternate
        training and by test_rpn."""
        with jax.named_scope("backbone"):
            feat = self.features(images, im_info)
        with jax.named_scope("rpn_head"):
            rpn_cls, rpn_box = self.rpn_raw(feat)
        _, fh, fw, _ = feat.shape
        anchors = self.anchors_for(fh, fw)
        fg = jax.nn.softmax(rpn_cls.astype(jnp.float32), axis=-1)[..., 1]
        with jax.named_scope("proposal"):
            return propose_batch(
                fg, rpn_box.astype(jnp.float32), anchors, im_info,
                pre_nms_top_n=pre_nms_top_n,
                post_nms_top_n=post_nms_top_n,
                nms_thresh=self.test_nms_thresh,
                min_size=self.test_min_size,
            )

    def _pool_and_classify(self, feat: jnp.ndarray, rois: jnp.ndarray):
        """ROIAlign + the per-ROI head of the two test forwards, under the
        train step's scope names; returns (cls_logits, deltas, R)."""
        with jax.named_scope("roi_align"):
            pooled = roi_align_batched(
                feat, rois, self.pooled_size,
                1.0 / self.feat_stride)  # (N, R, ph, pw, C)
        n, r = pooled.shape[:2]
        flat = pooled.reshape((n * r,) + pooled.shape[2:])
        with jax.named_scope("roi_head"):
            cls_logits, deltas = self.roi_head(flat, train=False)
        return cls_logits, deltas, r

    def detect_rois(self, images: jnp.ndarray, im_info: jnp.ndarray,
                    rois: jnp.ndarray, roi_valid: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, ...]:
        """RCNN-only test forward on PRECOMPUTED proposals (ref the
        HAS_RPN=False test symbol consumed by ``rcnn/tools/test_rcnn.py``):
        skips the RPN entirely and classifies the given ROIs.

        Args:
          images: (N, H, W, 3) as in ``__call__``.
          im_info: (N, 3).
          rois: (N, R, 4) proposal boxes in INPUT (scaled) coordinates.
          roi_valid: (N, R) bool mask for padded proposal slots.
        Returns the same tuple as ``__call__`` so the eval postprocess is
        shared: (rois, roi_valid, cls_prob, bbox_deltas).
        """
        with jax.named_scope("backbone"):
            feat = self.features(images, im_info)
        n = feat.shape[0]
        cls_logits, deltas, r = self._pool_and_classify(feat, rois)
        cls_prob = jax.nn.softmax(cls_logits.astype(jnp.float32), axis=-1)
        return (
            rois,
            roi_valid,
            cls_prob.reshape(n, r, self.num_classes),
            deltas.astype(jnp.float32).reshape(n, r, 4 * self.num_classes),
        )

    # ---- full test-mode forward (ref get_*_test symbol) -------------------

    def __call__(self, images: jnp.ndarray, im_info: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, ...]:
        """Test forward for a batch.

        Args:
          images: (N, H, W, 3) mean-subtracted RGB, static bucket shape.
          im_info: (N, 3) = (real_h, real_w, scale) per image.
        Returns:
          rois (N, R, 4), roi_valid (N, R), cls_prob (N, R, classes),
          bbox_deltas (N, R, 4*classes) — R = test_post_nms_top_n.
        """
        # the train step's scope names (core/train.py), so a trace of the
        # test forward reads by the same stages
        with jax.named_scope("backbone"):
            feat = self.features(images, im_info)
        with jax.named_scope("rpn_head"):
            rpn_cls, rpn_box = self.rpn_raw(feat)
        n, fh, fw, _ = feat.shape
        anchors = self.anchors_for(fh, fw)
        fg_scores = jax.nn.softmax(rpn_cls.astype(jnp.float32), axis=-1)[..., 1]
        with jax.named_scope("proposal"):
            rois, _, roi_valid = propose_batch(
                fg_scores, rpn_box, anchors, im_info,
                pre_nms_top_n=self.test_pre_nms_top_n,
                post_nms_top_n=self.test_post_nms_top_n,
                nms_thresh=self.test_nms_thresh,
                min_size=self.test_min_size,
            )
        cls_logits, deltas, r = self._pool_and_classify(feat, rois)
        cls_prob = jax.nn.softmax(cls_logits.astype(jnp.float32), axis=-1)
        return (
            rois,
            roi_valid,
            cls_prob.reshape(n, r, self.num_classes),
            deltas.astype(jnp.float32).reshape(n, r, 4 * self.num_classes),
        )


def build_model(cfg: Config, quant_phase: str = "apply"):
    """The model of ``cfg.network.family``, by that family's builder in the
    family table (``families.py``): :func:`build_detector` for the
    detectors, a sequence family's own stack otherwise."""
    from mx_rcnn_tpu import families

    return families.of(cfg).get("build")(cfg, quant_phase)


def build_detector(cfg: Config, quant_phase: str = "apply") -> FasterRCNN:
    """Construct the detector from a Config (ref generate_config wiring).

    ``quant_phase`` only matters when ``cfg.quant.enabled``:
    ``'apply'`` builds the quantized-inference model (needs the
    calibrated ``quant`` variables collection — ``core/tester.py —
    quant_predictor``), ``'calib'`` builds the statistics-recording
    calibration twin.  With quant disabled (the default) the returned
    model is the UNCHANGED fp model, bit-identical to a build that
    predates the quant subsystem (pinned by tests/test_quant.py)."""
    from mx_rcnn_tpu.config import validate_dtype_string
    from mx_rcnn_tpu.ops.quant import spec_from_config

    validate_dtype_string(cfg.network.compute_dtype,
                          "network__compute_dtype")
    quant = (spec_from_config(cfg.quant, phase=quant_phase)
             if cfg.quant.enabled else None)
    return FasterRCNN(
        network=cfg.network.name,
        num_classes=cfg.num_classes,
        anchor_scales=cfg.network.anchor_scales,
        anchor_ratios=cfg.network.anchor_ratios,
        feat_stride=cfg.network.rpn_feat_stride,
        pooled_size=cfg.network.rcnn_pooled_size,
        test_pre_nms_top_n=cfg.test.rpn_pre_nms_top_n,
        test_post_nms_top_n=cfg.test.rpn_post_nms_top_n,
        test_nms_thresh=cfg.test.rpn_nms_thresh,
        test_min_size=cfg.test.rpn_min_size,
        pixel_means=tuple(cfg.network.pixel_means),
        dtype=jnp.bfloat16 if cfg.network.compute_dtype == "bfloat16" else jnp.float32,
        quant=quant,
        stem_channel_pad=cfg.network.stem_channel_pad,
    )
