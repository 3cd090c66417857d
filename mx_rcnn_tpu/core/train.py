"""The end-to-end training step: ONE XLA program per iteration.

Reference hot loop (SURVEY.md §3.1): ``MutableModule.fit`` →
``forward_backward`` with two device→host→device CustomOp bounces
(proposal, proposal_target) and host-side anchor assignment in
``AnchorLoader``; gradients synced per-array through KVStore.

TPU-native: everything from the (image, gt) batch onward — anchor targets,
RPN losses, proposal NMS, ROI sampling, ROIAlign, RCNN losses, backward,
SGD update — is traced into a single jitted function.  Data parallelism
wraps this same function (see ``mx_rcnn_tpu/parallel``), with gradient
``psum`` over ICI fused into the step.

Loss layout matches the reference train symbol (§3.5):
  rpn_cls:  softmax CE, ignore -1, normalized by valid anchors,
  rpn_bbox: smooth_l1(sigma=3) · weights / RPN_BATCH_SIZE,
  rcnn_cls: softmax CE over sampled ROIs (normalization='batch'),
  rcnn_bbox: smooth_l1(sigma=1) · weights / BATCH_ROIS,
all summed; the six training metrics of ``rcnn/core/metric.py`` are
returned per step from the same activations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Tuple

import flax
import jax
import jax.numpy as jnp
import optax

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.faster_rcnn import FasterRCNN
from mx_rcnn_tpu.ops.losses import (
    accuracy_with_ignore,
    softmax_cross_entropy_with_ignore,
    weighted_smooth_l1,
)
from mx_rcnn_tpu.ops.proposal import propose_batch
from mx_rcnn_tpu.ops.roi_pool import roi_align_batched
from mx_rcnn_tpu.ops.targets import anchor_target, proposal_target


class TrainState(NamedTuple):
    """Weights + optimizer slots (the analog of the Module's arg/aux params +
    optimizer state; see ref ``rcnn/core/module.py``)."""

    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any


class Batch(NamedTuple):
    """Static-shape training batch (built host-side by the loader).

    images: (N, H, W, 3) padded into the bucket — uint8 raw RGB (the
      TPU-native default; normalized on device, see ops/normalize.py) or
      fp32 mean-subtracted (host-normalized path).
    im_info: (N, 3) — (real_h, real_w, scale) of the resized image.
    gt_boxes: (N, G, 4) padded gt boxes in input coordinates.
    gt_classes: (N, G) int32 class ids (1..C-1; 0 is background).
    gt_valid: (N, G) bool.
    """

    images: jnp.ndarray
    im_info: jnp.ndarray
    gt_boxes: jnp.ndarray
    gt_classes: jnp.ndarray
    gt_valid: jnp.ndarray


class RCNNBatch(NamedTuple):
    """Batch for RCNN-only training from PRECOMPUTED proposals (alternate
    training stages 2/4; ref ``ROIIter`` feeds ``get_rcnn_batch``).

    Same fields as :class:`Batch` plus the proposal buffer:
    rois: (N, R, 4) proposal boxes in input (scaled) coordinates.
    rois_valid: (N, R) bool.
    """

    images: jnp.ndarray
    im_info: jnp.ndarray
    gt_boxes: jnp.ndarray
    gt_classes: jnp.ndarray
    gt_valid: jnp.ndarray
    rois: jnp.ndarray
    rois_valid: jnp.ndarray


class TokenBatch(NamedTuple):
    """Batch of a sequence family (``data/tokens.py``): ids (N, S) int32,
    every row a full sequence of the vocabulary held here."""

    ids: jnp.ndarray


def _rpn_losses(model: FasterRCNN, rpn_cls, rpn_box, anchors, batch,
                key: jax.Array, cfg: Config):
    """Anchor targets + the two RPN losses (shared by e2e and RPN-only
    training so the objectives cannot drift apart).

    Returns (cls_loss, bbox_loss, metrics dict).
    """
    tr = cfg.train
    n = batch.images.shape[0]
    with jax.named_scope("anchor_target"):
        at = jax.vmap(
            functools.partial(
                anchor_target,
                rpn_batch_size=tr.rpn_batch_size,
                rpn_fg_fraction=tr.rpn_fg_fraction,
                positive_overlap=tr.rpn_positive_overlap,
                negative_overlap=tr.rpn_negative_overlap,
                clobber_positives=tr.rpn_clobber_positives,
                allowed_border=tr.rpn_allowed_border,
                bbox_weights=tr.rpn_bbox_weights,
            ),
            in_axes=(None, 0, 0, 0, 0),
        )(anchors, batch.gt_boxes, batch.gt_valid, batch.im_info,
          jax.random.split(key, n))

    rpn_cls32 = rpn_cls.astype(jnp.float32)
    cls_loss = softmax_cross_entropy_with_ignore(
        rpn_cls32.reshape(-1, 2), at.labels.reshape(-1), -1, "valid")
    bbox_loss = weighted_smooth_l1(
        rpn_box.astype(jnp.float32), at.bbox_targets, at.bbox_weights,
        sigma=3.0, grad_norm=tr.rpn_batch_size * n)
    metrics = {
        "rpn_acc": accuracy_with_ignore(rpn_cls32.reshape(-1, 2),
                                        at.labels.reshape(-1)),
        "rpn_logloss": cls_loss,
        "rpn_l1loss": bbox_loss,
    }
    return cls_loss, bbox_loss, metrics


def _rcnn_losses(model: FasterRCNN, variables, feat, rois, rois_valid,
                 batch, key: jax.Array, cfg: Config):
    """ROI sampling + pooled head + the two RCNN losses (shared by e2e and
    RCNN-only training).  ``rois`` come either from the in-graph proposal
    op (e2e) or from a precomputed buffer (alternate stages 2/4).

    Returns (cls_loss, bbox_loss, metrics dict).
    """
    tr = cfg.train
    n = batch.images.shape[0]
    k_prop, k_drop = jax.random.split(key)

    def one_img(rois_i, valid_i, gt_b, gt_c, gt_v, key_i):
        return proposal_target(
            rois_i, valid_i, gt_b, gt_c, gt_v, key_i,
            num_classes=model.num_classes,
            batch_rois=tr.batch_rois,
            fg_fraction=tr.fg_fraction,
            fg_thresh=tr.fg_thresh,
            bg_thresh_hi=tr.bg_thresh_hi,
            bg_thresh_lo=tr.bg_thresh_lo,
            bbox_means=tr.bbox_means,
            bbox_stds=tr.bbox_stds,
            gt_append=tr.gt_append)

    with jax.named_scope("proposal_target"):
        pt = jax.vmap(one_img)(
            rois, rois_valid, batch.gt_boxes, batch.gt_classes,
            batch.gt_valid, jax.random.split(k_prop, n))

    with jax.named_scope("roi_align"):
        pooled = roi_align_batched(
            feat, pt.rois, model.pooled_size,
            1.0 / model.feat_stride)  # (N, B, ph, pw, C)
    flat = pooled.reshape((-1,) + pooled.shape[2:])
    with jax.named_scope("roi_head"):
        cls_logits, bbox_deltas = model.apply(
            variables, flat, True, method=model.roi_head,
            rngs={"dropout": k_drop})
    cls_logits = cls_logits.astype(jnp.float32)
    bbox_deltas = bbox_deltas.astype(jnp.float32)

    labels = pt.labels.reshape(-1)
    # ref RCNN loss is normalization='batch', but the reference never emits
    # filler ROIs (sample_rois fills all BATCH_ROIS slots), so its batch
    # denominator always equals the valid count; 'valid' is the faithful
    # generalization when the proposal pool is too small to fill every slot
    cls_loss = softmax_cross_entropy_with_ignore(
        cls_logits, labels, -1, "valid")
    bbox_loss = weighted_smooth_l1(
        bbox_deltas, pt.bbox_targets.reshape(bbox_deltas.shape),
        pt.bbox_weights.reshape(bbox_deltas.shape),
        sigma=1.0, grad_norm=tr.batch_rois * n)
    metrics = {
        "rcnn_acc": accuracy_with_ignore(cls_logits, labels),
        "rcnn_logloss": cls_loss,
        "rcnn_l1loss": bbox_loss,
        "num_fg": pt.fg_mask.sum().astype(jnp.float32),
    }
    return cls_loss, bbox_loss, metrics


def loss_and_metrics(  # graphlint: jit (traced via LOSS_FNS inside the step)
    model: FasterRCNN,
    params,
    batch_stats,
    batch: Batch,
    key: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full train-mode forward; returns (total_loss, metrics)."""
    tr = cfg.train
    variables = {"params": params, "batch_stats": batch_stats}
    k_anchor, k_rcnn = jax.random.split(key)

    # named_scope on each stage: jax.profiler traces then attribute device
    # time per stage (benchmark/metrics/<scope>.device_ms.py).  Scopes are
    # metadata: no jit boundary, the compiled step keeps its instructions
    # and fusions.
    # Inside them: anchor_target (rpn_losses), nms_sweep (proposal, in
    # ops/nms.py), proposal_target / roi_align / roi_head (rcnn_losses);
    # beside them in make_train_step: grad_sync, optimizer
    with jax.named_scope("backbone"):
        feat = model.apply(variables, batch.images, batch.im_info,
                           method=model.features)
    with jax.named_scope("rpn_head"):
        rpn_cls, rpn_box = model.apply(variables, feat,
                                       method=model.rpn_raw)
    _, fh, fw, _ = feat.shape
    anchors = model.anchors_for(fh, fw)

    with jax.named_scope("rpn_losses"):
        rpn_cls_loss, rpn_bbox_loss, rpn_metrics = _rpn_losses(
            model, rpn_cls, rpn_box, anchors, batch, k_anchor, cfg)

    # ---- proposals (no gradient; ref Proposal/proposal_target CustomOps
    # define no backward) ---------------------------------------------------
    rpn_cls32 = jax.lax.stop_gradient(rpn_cls.astype(jnp.float32))
    fg_scores = jax.nn.softmax(rpn_cls32, axis=-1)[..., 1]
    rpn_box_sg = jax.lax.stop_gradient(rpn_box.astype(jnp.float32))

    with jax.named_scope("proposal"):
        # cross-image batched NMS sweep (r6): one tile-sweep loop nest for
        # the whole batch instead of B serialized chains under vmap —
        # decision-exact vs vmap(propose), pinned by tests/test_proposal.py
        rois, _, rois_valid = propose_batch(
            fg_scores, rpn_box_sg, anchors, batch.im_info,
            pre_nms_top_n=tr.rpn_pre_nms_top_n,
            post_nms_top_n=tr.rpn_post_nms_top_n,
            nms_thresh=tr.rpn_nms_thresh,
            min_size=tr.rpn_min_size)
    with jax.named_scope("rcnn_losses"):
        rcnn_cls_loss, rcnn_bbox_loss, rcnn_metrics = _rcnn_losses(
            model, variables, feat, rois, rois_valid, batch, k_rcnn, cfg)

    total = rpn_cls_loss + rpn_bbox_loss + rcnn_cls_loss + rcnn_bbox_loss
    # the six reference metrics (rcnn/core/metric.py)
    metrics = {**rpn_metrics, **rcnn_metrics, "loss": total}
    return total, metrics


def loss_and_metrics_rpn(  # graphlint: jit (traced via LOSS_FNS)
    model: FasterRCNN,
    params,
    batch_stats,
    batch: Batch,
    key: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """RPN-only training loss (alternate stages 1/3; ref ``get_vgg_rpn`` /
    ``train_rpn.py``): backbone → RPN heads → anchor targets → two losses.
    Shares ``_rpn_losses`` with the e2e objective."""
    variables = {"params": params, "batch_stats": batch_stats}
    with jax.named_scope("backbone"):
        feat = model.apply(variables, batch.images, batch.im_info,
                           method=model.features)
    with jax.named_scope("rpn_head"):
        rpn_cls, rpn_box = model.apply(variables, feat,
                                       method=model.rpn_raw)
    _, fh, fw, _ = feat.shape
    anchors = model.anchors_for(fh, fw)
    with jax.named_scope("rpn_losses"):
        cls_loss, bbox_loss, metrics = _rpn_losses(
            model, rpn_cls, rpn_box, anchors, batch, key, cfg)
    total = cls_loss + bbox_loss
    return total, {**metrics, "loss": total}


def loss_and_metrics_rcnn(  # graphlint: jit (traced via LOSS_FNS)
    model: FasterRCNN,
    params,
    batch_stats,
    batch: RCNNBatch,
    key: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """RCNN-only training loss from precomputed proposals (alternate stages
    2/4; ref ``train_rcnn.py`` + host-side ``sample_rois``).  Shares
    ``_rcnn_losses`` with the e2e objective."""
    variables = {"params": params, "batch_stats": batch_stats}
    with jax.named_scope("backbone"):
        feat = model.apply(variables, batch.images, batch.im_info,
                           method=model.features)
    with jax.named_scope("rcnn_losses"):
        cls_loss, bbox_loss, metrics = _rcnn_losses(
            model, variables, feat, batch.rois, batch.rois_valid, batch,
            key, cfg)
    total = cls_loss + bbox_loss
    return total, {**metrics, "loss": total}


def loss_and_metrics_lm(  # graphlint: jit (traced via LOSS_FNS)
    model,
    params,
    batch_stats,
    batch: TokenBatch,
    key: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The loss of a sequence family's stack (``models/nemotron_h.py``,
    ``models/ling_flash.py``: the next-token loss; ``models/joyai_flash.py``:
    that plus its multi-token-prediction module's, weighted) on
    ``(N, S)`` ids, and the routed-expert counters of the step: mean
    assignments per token that fell on held experts (mean over the expert
    layers), the worst layer's largest expert load over its mean,
    the rows not computed (must be 0), and the rows of every held expert
    (layers x held; a log line shows its mean); beside them whatever
    further scalar counters the family's stack returns, under their own
    names (``models/ling_flash.py``: ``kda_chunk_log_decay_min``;
    ``models/joyai_flash.py``: ``loss_main``, ``mtp_loss``).  Nothing here
    knows a family."""
    loss, aux = model.apply({"params": params}, batch.ids)
    sizes = aux.pop("sizes").astype(jnp.float32)
    overflow = aux.pop("overflow")
    per_layer = sizes.sum(-1)
    return loss, {
        **aux,
        "loss": loss,
        "moe_assignments_per_token": per_layer.mean() / batch.ids.size,
        "moe_load_max_over_mean": jnp.max(
            sizes.max(-1) * sizes.shape[-1] / jnp.maximum(per_layer, 1.0)),
        "moe_overflow": overflow.sum().astype(jnp.float32),
        "moe_expert_rows": sizes,
    }


LOSS_FNS = {
    "e2e": loss_and_metrics,
    "rpn": loss_and_metrics_rpn,
    "rcnn": loss_and_metrics_rcnn,
    "lm": loss_and_metrics_lm,
}


def init_variables(
    model: FasterRCNN,
    key: jax.Array,
    image_shape: Tuple[int, int, int, int],
):
    """Initialize all model variables in ONE compiled program.

    Returns (params, batch_stats).  (Ref analog: ``load_param`` + the
    Normal-init of new layers in ``train_end2end.py``; pretrained weights
    are grafted on top via ``utils/pretrained.py``.)"""
    if not isinstance(model, FasterRCNN):
        # a sequence family traces itself on a shape of its own
        # graphlint: disable=GL302 one-shot init program
        return jax.jit(model.init_variables)(key)

    def _init(key):
        images = jnp.zeros(image_shape, jnp.float32)
        variables = model.init(key, images, method=model.features)
        # also materialize RPN + head params with dummy shapes
        feat = model.apply(variables, images, method=model.features)
        k1, k2 = jax.random.split(key)
        v_rpn = model.init(k1, feat, method=model.rpn_raw)
        pooled = jnp.zeros((8,) + model.pooled_size + (feat.shape[-1],),
                           jnp.float32)
        v_head = model.init(k2, pooled, False, method=model.roi_head)
        params = {**variables["params"], **v_rpn["params"], **v_head["params"]}
        batch_stats = {
            **variables.get("batch_stats", {}),
            **v_rpn.get("batch_stats", {}),
            **v_head.get("batch_stats", {}),
        }
        return flax.core.freeze(params).unfreeze(), batch_stats

    # one compiled program instead of thousands of eager op dispatches;
    # init runs once per process so discarding the jit cache is the point
    return jax.jit(_init)(key)  # graphlint: disable=GL302 one-shot init program


def init_state(
    model: FasterRCNN,
    key: jax.Array,
    tx: optax.GradientTransformation,
    image_shape: Tuple[int, int, int, int],
) -> TrainState:
    """init_variables + optimizer slots as a TrainState."""
    params, batch_stats = init_variables(model, key, image_shape)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
    )


def setup_training(
    model: FasterRCNN,
    cfg: Config,
    key: jax.Array,
    image_shape: Tuple[int, int, int, int],
    steps_per_epoch: int,
    **optimizer_kw,
):
    """One-stop builder: init variables ONCE, build the optimizer from the
    resulting param tree (no throwaway second init), assemble the state.

    Returns (state, tx).
    """
    from mx_rcnn_tpu.core.optim import make_optimizer

    params, batch_stats = init_variables(model, key, image_shape)
    tx = make_optimizer(cfg, params, steps_per_epoch, **optimizer_kw)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
    )
    return state, tx


def make_train_step(model: FasterRCNN, cfg: Config,
                    tx: optax.GradientTransformation,
                    axis_name: str | None = None, mode: str = "e2e",
                    grad_accum: int = 1):
    """Build the jittable train step.  When ``axis_name`` is set the step is
    meant to run under shard_map/pmap-style SPMD and gradients/metrics are
    psum-averaged over that mesh axis (the TPU replacement for MXNet
    ``kvstore='device'``).

    ``mode`` selects the loss: 'e2e' (full Faster R-CNN), 'rpn' (alternate
    stages 1/3, expects :class:`Batch`), 'rcnn' (stages 2/4, expects
    :class:`RCNNBatch` with precomputed proposals).

    ``grad_accum > 1`` builds the ACCUMULATING step the elastic controller
    (ft/elastic.py) uses to keep the effective global batch on-recipe on a
    shrunken mesh: the batch arrives with a leading microbatch axis
    (leaves shaped ``(grad_accum, N, ...)``), gradients and metrics are
    computed per microbatch under ``lax.map`` (serialized — peak
    activation memory stays that of ONE microbatch) and averaged, then
    ONE optimizer update applies.  ``state.step`` counts optimizer steps
    in both paths, so the LR schedule and step↔epoch mapping are
    accumulation-invariant by construction.  The per-microbatch RNG folds
    in the microbatch index on top of the step fold, so microbatches
    sample independently (``grad_accum=1`` keeps the exact pre-elastic
    key derivation — resume streams stay bit-identical).
    """
    loss_and_metrics_fn = LOSS_FNS[mode]

    # graphlint: jit (jitted by fit/parallel.dp after construction)
    def step(state: TrainState, batch, key: jax.Array
             ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        key = jax.random.fold_in(key, state.step)

        if grad_accum <= 1:
            def loss_fn(params):
                return loss_and_metrics_fn(model, params, state.batch_stats,
                                           batch, key, cfg)

            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params)
        else:
            def micro(idx_and_batch):
                idx, mb = idx_and_batch
                mkey = jax.random.fold_in(key, idx)

                def loss_fn(params):
                    return loss_and_metrics_fn(
                        model, params, state.batch_stats, mb, mkey, cfg)

                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params)
                return g, m

            grads, metrics = jax.lax.map(
                micro, (jnp.arange(grad_accum, dtype=jnp.int32), batch))
            # mean over microbatches = the gradient of the mean loss over
            # the full effective batch (each microbatch is equal-sized)
            grads = jax.tree.map(lambda g: g.mean(axis=0), grads)
            metrics = jax.tree.map(lambda m: m.mean(axis=0), metrics)
        if axis_name is not None:
            with jax.named_scope("grad_sync"):
                grads = jax.lax.pmean(grads, axis_name)
                metrics = jax.lax.pmean(metrics, axis_name)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(state.step + 1, params, state.batch_stats,
                               opt_state)
        return new_state, metrics

    return step
