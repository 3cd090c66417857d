"""Immutable configuration system.

Reference: ``rcnn/config.py`` — the reference keeps a global mutable easydict
singleton (``config``/``default``) mutated by ``generate_config(network,
dataset)`` and argparse overrides.  A hidden mutable global is hostile to XLA
tracing and to reproducibility, so here the same three-level precedence
(hardcoded defaults < network/dataset presets < CLI overrides) is realized
with **frozen dataclasses**: ``generate_config`` returns a new immutable
``Config`` that is threaded explicitly through every function.

Key names and default values mirror the reference 1:1 wherever a reference
key exists (``config.TRAIN.*``, ``config.TEST.*``, per-network and
per-dataset dicts, ``default.*``) so they can be audited side by side.
TPU-specific additions (shape buckets, compute dtype, padded sizes) are
grouped at the bottom of each dataclass and commented as such.
"""

from __future__ import annotations

import dataclasses
import types
import typing
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence, Tuple


@dataclass(frozen=True)
class TrainConfig:
    """Mirrors reference ``config.TRAIN``."""

    # -- whole-pipeline switches --------------------------------------------
    batch_images: int = 1          # images per device (ref: BATCH_IMAGES, per GPU)
    # configlint: disable=CL201 ref TRAIN.END2END mirrored 1:1 for side-by-side audit; this port is statically end-to-end (alternate training is its own CLI)
    end2end: bool = True           # ref: END2END
    flip: bool = True              # ref: FLIP — append horizontally flipped roidb
    shuffle: bool = True           # ref: SHUFFLE
    # sequence families (``families.py``: row "sequence"): tokens a row of
    # the batch; ``batch_images`` then counts sequences per device
    seq_len: int = 0
    # configlint: disable=CL201 ref ASPECT_GROUPING mirrored 1:1; grouping is realized structurally by the landscape/portrait buckets (BucketConfig)
    aspect_grouping: bool = True   # ref: ASPECT_GROUPING — group wide/tall images

    # -- R-CNN ROI sampling (ref rcnn/io/rcnn.py — sample_rois) --------------
    batch_rois: int = 128          # ref: BATCH_ROIS — ROIs per image
    fg_fraction: float = 0.25      # ref: FG_FRACTION — max fg fraction
    fg_thresh: float = 0.5         # ref: FG_THRESH — fg IoU threshold
    bg_thresh_hi: float = 0.5      # ref: BG_THRESH_HI
    bg_thresh_lo: float = 0.0      # ref: BG_THRESH_LO

    # -- bbox regression target normalization (ref: BBOX_* keys) -------------
    # configlint: disable=CL201 ref BBOX_REGRESSION_THRESH mirrored 1:1 for audit; the fused proposal-target op keys fg on fg_thresh alone, as the ref e2e path does
    bbox_regression_thresh: float = 0.5            # ref: BBOX_REGRESSION_THRESH
    bbox_means: Tuple[float, ...] = (0.0, 0.0, 0.0, 0.0)   # ref: BBOX_MEANS
    bbox_stds: Tuple[float, ...] = (0.1, 0.1, 0.2, 0.2)    # ref: BBOX_STDS

    # -- RPN anchor target assignment (ref rcnn/io/rpn.py — assign_anchor) ---
    rpn_batch_size: int = 256          # ref: RPN_BATCH_SIZE — anchors per image
    rpn_fg_fraction: float = 0.5       # ref: RPN_FG_FRACTION
    rpn_positive_overlap: float = 0.7  # ref: RPN_POSITIVE_OVERLAP
    rpn_negative_overlap: float = 0.3  # ref: RPN_NEGATIVE_OVERLAP
    rpn_clobber_positives: bool = False  # ref: RPN_CLOBBER_POSITIVES
    rpn_allowed_border: int = 0        # ref: assign_anchor(allowed_border=0)
    rpn_bbox_weights: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0)  # ref: RPN_BBOX_WEIGHTS

    # -- RPN proposal generation at TRAIN time (ref mx.symbol.Proposal args) -
    rpn_pre_nms_top_n: int = 12000  # ref: RPN_PRE_NMS_TOP_N
    rpn_post_nms_top_n: int = 2000  # ref: RPN_POST_NMS_TOP_N
    rpn_nms_thresh: float = 0.7     # ref: RPN_NMS_THRESH
    rpn_min_size: int = 16          # ref: RPN_MIN_SIZE (pixels, at input scale)

    # -- TPU additions -------------------------------------------------------
    max_gt_boxes: int = 100        # static pad for per-image gt boxes
    gt_append: bool = True         # append gt boxes to sampled ROI pool (ref does)


@dataclass(frozen=True)
class TestConfig:
    """Mirrors reference ``config.TEST``."""

    # configlint: disable=CL201 ref TEST.HAS_RPN mirrored 1:1; every model in this port carries an RPN
    has_rpn: bool = True            # ref: HAS_RPN (True for end2end models)
    batch_images: int = 1           # ref: BATCH_IMAGES
    nms: float = 0.3                # ref: NMS — per-class NMS threshold at eval
    score_thresh: float = 1e-3      # ref: pred_eval thresh
    max_per_image: int = 100        # ref: pred_eval max_per_image
    # RPN proposal generation at TEST time
    rpn_pre_nms_top_n: int = 6000   # ref: RPN_PRE_NMS_TOP_N
    rpn_post_nms_top_n: int = 300   # ref: RPN_POST_NMS_TOP_N
    rpn_nms_thresh: float = 0.7     # ref: RPN_NMS_THRESH
    rpn_min_size: int = 16          # ref: RPN_MIN_SIZE
    # proposal-generation mode for alternate training (ref tools/test_rpn.py)
    # configlint: disable=CL201 ref key mirrored for audit; the alternate-training proposal dump reads the pre/post top_n pair and shares rpn_nms_thresh
    proposal_nms_thresh: float = 0.7
    proposal_pre_nms_top_n: int = 20000
    proposal_post_nms_top_n: int = 2000


@dataclass(frozen=True)
class NetworkConfig:
    """Per-network preset. Mirrors the reference's per-network dict in
    ``rcnn/config.py`` (pretrained prefix, anchor geometry, strides,
    FIXED_PARAMS)."""

    name: str = "resnet101"
    # configlint: disable=CL201 ref per-network dict keys mirrored 1:1; the live values come from the --pretrained/--pretrained_epoch CLI flags
    pretrained: str = ""                 # path prefix of pretrained backbone
    pretrained_epoch: int = 0  # configlint: disable=CL201 see pretrained above
    pixel_means: Tuple[float, ...] = (123.68, 116.779, 103.939)  # RGB; ref: PIXEL_MEANS
    # configlint: disable=CL201 ref IMAGE_STRIDE mirrored 1:1; stride padding is realized by the static buckets (multiples of 32)
    image_stride: int = 0                # ref: IMAGE_STRIDE (VGG 0, pad multiple)
    rpn_feat_stride: int = 16            # ref: RPN_FEAT_STRIDE
    # configlint: disable=CL201 ref RCNN_FEAT_STRIDE mirrored 1:1; both stages share one stride here and code derives from rpn_feat_stride
    rcnn_feat_stride: int = 16           # ref: RCNN_FEAT_STRIDE
    anchor_scales: Tuple[int, ...] = (8, 16, 32)       # ref: ANCHOR_SCALES
    anchor_ratios: Tuple[float, ...] = (0.5, 1.0, 2.0)  # ref: ANCHOR_RATIOS
    rcnn_pooled_size: Tuple[int, int] = (14, 14)  # ref: VGG 7x7, ResNet 14x14
    # Parameter-name prefixes frozen during training (ref: FIXED_PARAMS) and
    # the larger set frozen in alternate-training shared-conv stages
    # (ref: FIXED_PARAMS_SHARED).  'gamma'/'beta' are the reference's
    # freeze-every-BN-affine tokens (see core/optim.frozen_mask); stage4 is
    # the per-ROI head and must stay trainable in shared-conv stages.
    fixed_params: Tuple[str, ...] = (
        "conv0", "stage1", "bn0", "bn_data", "gamma", "beta")
    fixed_params_shared: Tuple[str, ...] = (
        "conv0", "stage1", "stage2", "stage3", "bn0", "bn_data",
        "gamma", "beta")
    # -- TPU additions -------------------------------------------------------
    # configlint: disable=CL201 preset documentation; faster_rcnn.setup derives depth from the network NAME so name and depth cannot disagree
    depth: int = 101                     # resnet depth (50 / 101 / 152)
    compute_dtype: str = "bfloat16"      # MXU-friendly activation dtype
    # backbone layout lever (docs/PERF.md "Quantized inference"):
    # zero-pad the stem's 3 input channels up to this count before conv0
    # (4 aligns the channel axis; padded channels are exact zeros so the
    # output is bit-identical — pinned by test).  Changes the conv0
    # kernel's param shape, so it is not a checkpoint-compatible default;
    # no cell has measured it (ROADMAP D5).  0 = off.
    stem_channel_pad: int = 0
    # -- sequence-model families (models/nemotron_h.py, models/ling_flash.py,
    # models/joyai_flash.py, models/keye_vl2.py, models/evabyte.py)
    # "detector" = the Faster R-CNN families above; "nemotron_h" = a hybrid
    # stack of Mamba-2 ('M'), attention ('*') and routed-expert ('E')
    # blocks, one letter a block in ``layer_pattern`` (the published
    # hybrid_override_pattern, or the part of it this chip runs);
    # "ling_flash" = layers of a mixer, 'K' a gated-delta-rule (KDA) or 'L'
    # a latent-attention (MLA) one, one letter a layer, each followed by a
    # dense SwiGLU (the first ``first_k_dense_replace`` layers kept here)
    # or a routed-expert MLP; "joyai_flash" = layers of a latent-attention
    # mixer with a low-rank query alone (``layer_pattern`` all 'L'), the same
    # two kinds of MLP, and after the last layer ``num_nextn_predict_layers``
    # multi-token-prediction modules that share the stack's embedding and
    # head; "keye_vl2" = layers of grouped-query attention over the keys a
    # learned indexer chooses ('S' each), every one followed by a
    # softmax-routed expert MLP with no shared expert; "evabyte" = layers of
    # EVA attention ('V' each: exact keys inside a window, one pooled key
    # and value for each earlier chunk) and a dense SwiGLU on a float32
    # residual stream, then several byte-prediction heads.  Widths keep
    # their published names.  The
    # family also chooses the builder, the loss, the loader and the
    # optimizer: the one table of ``families.py``.
    family: str = "detector"
    layer_pattern: str = ""
    hidden_size: int = 0
    vocab_size: int = 0                 # rows of the vocabulary held here
    norm_eps: float = 1e-5
    # residual writers start at 0.02 / sqrt(2 * init_layers): the published
    # depth, whatever part of it ``layer_pattern`` keeps
    init_layers: int = 0
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 0                 # published n_groups
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 0
    num_key_value_heads: int = 0
    head_dim: int = 0
    # queries a block of ops/attention.py's blocked path (off the TPU, or
    # shapes its kernels do not tile); the TPU kernels' blocks are constants
    # of ops/attention_pallas.py and read no field
    attn_block_q: int = 256
    n_routed_experts: int = 0           # the router's width
    # (first, count): the experts this chip holds of n_routed_experts
    experts_held: Tuple[int, int] = (0, 0)
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # rows kept for the held experts, over the assignments expected under
    # even routing (ops/moe.py — row_capacity); beyond it rows overflow
    moe_capacity_factor: float = 2.0
    # -- ling_flash and joyai_flash ----------------------------------------------
    # leading layers kept here whose MLP is dense (SwiGLU ``intermediate_size``)
    first_k_dense_replace: int = 0
    intermediate_size: int = 0
    # group-limited routing: the router's outputs in ``n_group`` groups, a
    # group's score the sum of its two best, ``topk_group`` groups kept
    # (0, or one group of all = plain top-k over all experts, as nemotron_h
    # and joyai_flash route)
    n_group: int = 0
    topk_group: int = 0
    # latent attention: width of the compressed key-value latent, and a
    # head's query/key widths without and with the rotary term, its value
    # width; ``head_dim`` is the KDA head's (keys and values alike)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    # -- joyai_flash alone ------------------------------------------------------
    # width of the low-rank query (q_a, RMSNorm, q_b); the rotary term pairs
    # adjacent channels (2j, 2j + 1) where ``rope_interleave``, else
    # (j, j + R/2)
    q_lora_rank: int = 0
    rope_interleave: bool = False
    # multi-token-prediction modules after the last layer (0 or 1), and the
    # weight of their loss beside the next-token loss
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.0
    # -- keye_vl2 alone ----------------------------------------------------------
    # the indexer of DeepSeek Sparse Attention (ops/sparse_attention.py): its
    # query heads and their width (one key head of that width), the channels
    # of each that take the rotary term, and the keys a query attends to
    indexer_num_heads: int = 0
    indexer_head_dim: int = 0
    indexer_rope_dim: int = 0
    indexer_topk: int = 0
    # the router's scores over all experts: "sigmoid" or "softmax"
    router_scoring: str = "sigmoid"
    # tokens a part of the expert layer, run one after the other, each with
    # its own row capacity (0: the whole sequence at once)
    moe_token_chunk: int = 0
    # each held expert's sorted rows start on a multiple of this many rows,
    # in at least one such block (0: packed); at the grouped products' row
    # tile every tile is one expert's, so that their work is the same on
    # every step whatever the routing (ops/moe.py — held_assignments)
    moe_group_rows: int = 0
    # -- evabyte alone -----------------------------------------------------------
    # EVA attention (ops/eva.py): exact keys inside blocks of ``eva_window``
    # positions, one pooled key and value for each earlier chunk of
    # ``eva_chunk`` positions; every matrix starts normal with ``init_std``
    eva_window: int = 0
    eva_chunk: int = 0
    init_std: float = 0.02
    # heads after the last layer: head i predicts the token 1 + i ahead
    num_pred_heads: int = 1
    # -- ling_flash alone ------------------------------------------------------
    # the KDA gate's lower bound a position (kda_safe_gate): the log-decay
    # is ``kda_lower_bound * sigmoid(.)``, so a sub-chunk of 16 positions
    # decays by exp(16 * kda_lower_bound) at most, inside float32's range
    kda_lower_bound: float = -5.0

    @property
    def num_anchors(self) -> int:
        """Ref NUM_ANCHORS — derived, so it can never desynchronize from the
        scale/ratio presets."""
        return len(self.anchor_scales) * len(self.anchor_ratios)


@dataclass(frozen=True)
class DatasetConfig:
    """Per-dataset preset. Mirrors the reference's per-dataset dict."""

    name: str = "PascalVOC"
    image_set: str = "2007_trainval"
    test_image_set: str = "2007_test"
    root_path: str = "data"
    dataset_path: str = "data/VOCdevkit"
    num_classes: int = 21                # ref: NUM_CLASSES (VOC 21 / COCO 81)


@dataclass(frozen=True)
class DefaultConfig:
    """Mirrors reference ``default.*`` (training-schedule defaults)."""

    frequent: int = 20            # ref: default.frequent — Speedometer period
    # configlint: disable=CL201 ref default.kvstore kept for CLI parity; DP-over-ICI (parallel/dp.py) replaces the kvstore concept wholesale
    kvstore: str = "device"       # kept for CLI parity; maps to DP-over-ICI
    # configlint: disable=CL201 ref default.prefix/begin_epoch mirrored 1:1; the --prefix/--begin_epoch CLI flags own the live values
    prefix: str = "model/e2e"
    begin_epoch: int = 0  # configlint: disable=CL201 see prefix above
    e2e_epoch: int = 10           # ref: default.e2e_epoch
    e2e_lr: float = 0.001         # ref: default.e2e_lr
    e2e_lr_step: str = "7"        # ref: default.e2e_lr_step (epoch for x0.1)
    # alternate training stage schedules (ref: default.rpn_*/rcnn_*)
    rpn_epoch: int = 8
    rpn_lr: float = 0.001
    rpn_lr_step: str = "6"
    rcnn_epoch: int = 8
    rcnn_lr: float = 0.001
    rcnn_lr_step: str = "6"
    # optimizer constants (ref train_end2end.py — train_net: sgd with
    # momentum 0.9, wd 5e-4, elementwise clip_gradient=5)
    momentum: float = 0.9
    wd: float = 0.0005
    lr_factor: float = 0.1
    clip_gradient: float = 5.0
    # linear LR warmup (upstream WarmupMultiFactorScheduler; off by default
    # to match the reference scripts — enable at large DP batch)
    warmup_step: int = 0
    warmup_lr: float = 0.0
    # TPU addition: SGD momentum accumulator dtype.  Default ADOPTED as
    # "bfloat16" from the r5 on-chip A/B (25.66 ms vs 25.77 ms fp32 —
    # speed-neutral — with momentum HBM and its per-step read/write
    # bandwidth halved; docs/PERF.md "Lever A/Bs" + adoption note).
    # "float32" restores the reference-exact accumulator
    # (``--set default__momentum_dtype=float32``).  Params themselves
    # always stay float32.
    momentum_dtype: str = "bfloat16"
    # host input pipeline (TPU addition; the ref loader is synchronous —
    # SURVEY.md §7 "Hard parts": cv2 decode must overlap device steps)
    num_workers: int = 4
    prefetch: int = 4
    # ship uint8 batches and normalize on device (ops/normalize.py) —
    # bit-identical to host normalization, 4x less host bandwidth
    raw_images: bool = True
    # decoded-uint8 image cache (data/cache.py): RAM-tier budget in MiB
    # (0 disables), plus an optional disk tier directory
    image_cache_mb: int = 2048
    image_cache_dir: str = ""
    # process-parallel decode pool (data/decode_pool.py): worker process
    # count, 0 = decode in-thread.  Workers share image_cache_dir's disk
    # tier; pointless on a 1-core host (docs/PERF.md scaling table) but
    # the lever for feeding multiple chips from a many-core host
    decode_procs: int = 0


@dataclass(frozen=True)
class BucketConfig:
    """TPU addition (no reference equivalent — replaces the dynamic-shape
    rebinding of ref ``rcnn/core/module.py — MutableModule``).

    The reference resizes short side to SCALES[0][0]=600 capped at 1000 and
    rebinds executors per batch shape.  XLA requires static shapes, so images
    are resized the same way then padded into one of a small set of static
    buckets; aspect-ratio grouping (ref ASPECT_GROUPING) maps each image to
    the landscape or portrait bucket.

    Sublane note (r6): the default 608×1024 bucket yields a 38×64 stride-16
    feature grid, and 38 rows is hostile to the 8-sublane VPU register
    shape (38 = 4×8 + 6 — every (H-minor) retile pads ~5%).  The
    sublane-friendly alternative is 640×1024 (40×64 grid, 40 = 5×8) at
    +5.3% pixels — select it per run with
    ``--set bucket__shapes='[[640,1024],[1024,640]]'`` (anchors and bucket
    padding regenerate from the feature shape automatically; pinned by
    tests/test_anchors.py).  Whether the alignment win beats the pixel tax
    has not been measured: no cell runs the 640 bucket (ROADMAP D5).
    """

    scale: int = 600            # ref: SCALES[0][0] — target short side
    max_size: int = 1000        # ref: SCALES[0][1] — cap on long side
    # (H, W) static buckets, multiples of 32 to keep feature grids aligned.
    shapes: Tuple[Tuple[int, int], ...] = ((608, 1024), (1024, 608))


@dataclass(frozen=True)
class DataConfig:
    """TPU addition (no reference equivalent — the reference loader is a
    synchronous in-process iterator over a fully-materialized roidb):
    policy knobs for the STREAMING input plane (docs/DATA.md) — sharded
    loaders, bounded-memory decode windows, and double-buffered
    host→device staging.

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set data__field=value`` CLI overrides).
    """

    # use the topology-invariant streaming loader (``data/loader.py —
    # StreamLoader``) for training: image-granular epoch plan that is a
    # pure function of (seed, epoch), so shard unions and mid-epoch
    # resumes stay exactly-once across ANY worker/process/accum
    # topology.  DEFAULT FLIPPED to true at PR 11 after the soak leg
    # (train data-smoke + elastic-smoke green with streaming on —
    # docs/DATA.md "Streaming by default"); ``--set
    # data__streaming=false`` is the escape hatch back to the classic
    # AnchorLoader plan (bit-pinned by the pre-r7 resume tests).
    # Multi-process worlds shard the plan by batch ROWS either way, so
    # N processes decode 1/N of the data in both modes.
    streaming: bool = True
    # double-buffered host→device staging (``data/staging.py``): a
    # background thread assembles + device_puts the NEXT batch(es)
    # while the in-flight step runs, so the fit loop's data_wait gauge
    # goes to ~0 even when the dataset does not fit in HBM.  The
    # device-cache path (``--device_cache``) stays the small-set fast
    # path and bypasses staging entirely.
    staging: bool = True
    # device-resident batches kept in flight by the stager (>= 1;
    # each costs one batch of HBM — 2 = classic double buffering)
    stage_depth: int = 2
    # RAM ceiling for the host input plane in MiB (0 = unlimited): the
    # decoded-image cache budget and the per-worker shares are clamped
    # so cache + prefetch window + process floor fit under it
    # (``data/loader.py — stream_cache_budget``, logged once at loader
    # build), and ``tools/data_bench.py --check`` fails if measured RSS
    # exceeds it.
    ram_ceiling_mb: int = 0
    # NOTE deliberately NO shard_id/num_shards knobs here: training
    # derives loader-shard ownership from the process topology alone
    # (``tools/train.py`` — a lone training process given a shard would
    # silently train on 1/N of every batch), and bench rigs pass shard
    # ownership explicitly (``tools/data_bench.py --shard_id/--num_shards``
    # CLI, ``StreamLoader(shard=...)`` API).


@dataclass(frozen=True)
class ServeConfig:
    """TPU addition (no reference equivalent — the reference has no online
    inference path at all): policy knobs for the ``mx_rcnn_tpu/serve/``
    request/response engine (docs/SERVING.md).

    The engine coalesces single-image requests into per-bucket micro-batches
    and ALWAYS pads the batch to ``batch_size`` rows before dispatch, so one
    XLA program per (bucket, dtype) serves all traffic — the serving analog
    of the static train/eval buckets.
    """

    batch_size: int = 4         # static micro-batch rows per dispatch
    max_delay_ms: float = 10.0  # max wait to fill a micro-batch before
                                # dispatching it partial (tail-latency cap)
    queue_depth: int = 64       # hard per-bucket admission cap
    shed_watermark: int = 32    # shed (HTTP 429) once a bucket queue holds
                                # this many waiting requests (<= queue_depth)
    default_timeout_ms: float = 2000.0  # per-request deadline; 0 disables.
                                # Expired requests are cancelled BEFORE
                                # dispatch so dead work never occupies a
                                # batch slot
    score_thresh: float = 0.05  # serving detection floor (eval's 1e-3
                                # keeps near-zero boxes the AP sweep needs;
                                # a response wants confident boxes only)
    # request-body admission cap (MB): a claimed Content-Length above
    # this is refused 413 BEFORE any body byte is read; an absent one
    # (incl. chunked transfer) is 411 (netio.read_request_body)
    max_body_mb: float = 64.0


@dataclass(frozen=True)
class FleetConfig:
    """TPU addition (no reference equivalent): policy knobs for the
    ``mx_rcnn_tpu/serve/fleet.py`` serving fleet — N replica engines over
    device subsets behind a join-shortest-queue router, warmed from
    AOT-exported programs (``serve/export.py``) so a cold replica joins
    in seconds instead of paying trace+compile (docs/SERVING.md "Fleet
    tier").

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set fleet__field=value`` CLI overrides).
    """

    # replica engines in the fleet (each one full ServingEngine over its
    # own Predictor; tools/fleet.py serve --replicas overrides)
    replicas: int = 1
    # AOT export store directory ("" = trace-warm: every replica pays
    # the classic trace+compile warmup).  Written by
    # ``tools/fleet.py export``; holds serialized per-bucket programs +
    # manifest + the bundled XLA persistent cache.
    export_dir: str = ""
    # devices per replica (0 = divide jax.devices() evenly; replicas
    # beyond the device supply share the remainder round-robin).  A
    # subset of size > 1 becomes that replica's 1-D data mesh — the
    # mesh-sharded Predictor math from core/tester.py, per replica.
    devices_per_replica: int = 0
    # replica health monitor cadence: dead/unhealthy replicas are
    # ejected from the routing set and (when ``relaunch``) rebuilt via
    # the ft/supervisor.py RestartPolicy backoff schedule
    health_interval_s: float = 1.0
    # how many times the router re-dispatches a request whose replica
    # died before serving it (0 = fail straight to the client); reroutes
    # never extend the request's deadline
    reroute_retries: int = 1
    # relaunch crashed replicas (RestartPolicy paces retries and turns
    # repeated identical failures into a crash-loop verdict)
    relaunch: bool = True


@dataclass(frozen=True)
class CrosshostConfig:
    """TPU addition (no reference equivalent — the reference is strictly
    single-process): policy knobs for the cross-host serving plane
    (``serve/remote.py`` + ``serve/agent.py`` + ``serve/scheduler.py``,
    docs/SERVING.md "Cross-host tier") — per-host replica agents behind
    the fleet's ``Replica`` seam, dispatched over persistent keep-alive
    HTTP with a binary prepared-path wire format, an export-store
    distribution plane (one sha-verified resumable pull per joining
    host), and a gauge-driven scheduler that adds/drains replicas
    against traffic and re-places capacity when a host dies.

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set crosshost__field=value`` CLI overrides).
    """

    # comma-separated agent base URLs ("host:port,host:port" or
    # "name=url"); non-empty = tools/fleet.py serve builds a cross-host
    # router of RemoteReplicas instead of in-process engines
    agents: str = ""
    # persistent keep-alive HTTP connections per remote replica — each
    # one an independent request pipeline to the agent, so a remote
    # replica serves up to connections x pipeline_depth frames in flight
    connections: int = 2
    # in-flight frames admitted per connection (the bounded pipeline:
    # the frame that would exceed connections x pipeline_depth sheds at
    # the head instead of queueing unboundedly toward a slow host)
    pipeline_depth: int = 4
    # frames coalesced into one count-prefixed MXE1 envelope per send
    # (1 = every frame ships alone, the PR-15 behavior).  A wire worker
    # that finds several binary frames queued packs up to this many
    # into one vectored sendmsg / one HTTP round trip / one agent
    # wakeup — the burst-rate header+syscall amortization
    # tools/loadgen.py --wire_bench measures (serve/remote.py)
    frames_per_send: int = 1
    # adaptive per-connection pipelining: 0 keeps the fixed
    # pipeline_depth above; >= 1 lets each RemoteEngine self-tune its
    # depth in [1, pipeline_depth_max] by AIMD over windowed wire-RTT
    # samples (serve/remote.py PipelineController) — a slow or skewed
    # agent stops accumulating in-flight frames instead of inflating
    # fleet p99
    pipeline_depth_max: int = 0
    # socket-level I/O timeout for agent RPCs — a transport backstop
    # strictly above any request deadline (deadlines are enforced by the
    # agent's own admission path; this catches dead-host half-opens)
    io_timeout_s: float = 60.0
    # backlog-feed scrape cadence: the head polls each agent's /metrics
    # this often for bucket-lane depths (the JSQ routing signal) and
    # fleet gauges (the scheduler signal)
    scrape_interval_s: float = 0.25
    # consecutive transport/scrape failures before a remote replica
    # reads not-alive and the manager ejects it (single blips — one lost
    # frame, one slow scrape — must not eject a healthy host)
    dead_after_failures: int = 3
    # export-store distribution endpoint ("" = agents expect a local
    # fleet.export_dir already in place).  Set to the head's StoreServer
    # URL: a joining agent pulls the store ONCE (sha-verified,
    # resumable), then every local replica export-warms from disk.
    store_url: str = ""
    # replica engines each agent starts locally
    agent_replicas: int = 1
    # wire-body cap (MB), both directions: the agent refuses request
    # bodies claiming more (413), the head caps what it will buffer of
    # an agent response (RemoteTransportError past it).  Sized well
    # above the largest legitimate frame (a 1024x1024x3 fp32 prepared
    # canvas is 12 MB) and well below harm
    max_body_mb: float = 64.0
    # scheduler actuation RPC deadline: a hung agent costs one resize
    # call this much, surfaced as the typed AgentAdminTimeout — it can
    # never wedge the scheduler tick (serve/scheduler.py)
    admin_timeout_s: float = 5.0
    # per-request deadline on every store-pull HTTP call (/index and
    # each /f/<rel>); expiry surfaces as the typed StorePullError so a
    # dead store endpoint fails the join loudly instead of hanging it
    pull_timeout_s: float = 30.0
    # --- scheduler (serve/scheduler.py) ----------------------------------
    # fleet-wide ready-replica target (0 = adopt hosts x agent_replicas
    # at scheduler start); the host-death re-place signal: ready < target
    target_replicas: int = 0
    min_replicas: int = 1            # never drain below
    max_replicas: int = 8            # never add above
    # scale-up triggers, judged over window_s: shed ratio
    # (delta shed / delta submitted) above this...
    up_shed_ratio: float = 0.05
    # ...or mean bucket-lane backlog per ready replica above this many
    # images
    up_backlog: float = 2.0
    # hysteresis (the obs/health.py idiom): a trigger must hold for this
    # many consecutive decide() ticks to act...
    for_samples: int = 2
    # ...and the fleet must be fully idle (no backlog, no shed, ready >
    # min) for this many consecutive ticks before a drain
    idle_samples: int = 8
    # post-action cooldown: no further add/drain until the last action
    # is this old (lets the fleet absorb the resize before re-judging)
    cooldown_s: float = 5.0
    interval_s: float = 0.5          # scheduler tick cadence
    window_s: float = 10.0           # rate/ratio judgment window


@dataclass(frozen=True)
class BulkConfig:
    """TPU addition (no reference equivalent — the reference scores a
    corpus through a synchronous single-GPU eval loop): policy knobs for
    the offline bulk-inference plane (``serve/bulk.py``,
    docs/SERVING.md "Bulk tier") — a StreamLoader-fed corpus driven
    through the serving fleet's bucket lanes with backpressure-bounded
    in-flight depth and exactly-once sink accounting.

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set bulk__field=value`` CLI overrides).
    """

    # in-flight images admitted to the fleet at once (the backpressure
    # bound: the feeder blocks once this many images are between
    # submit_prepared and their terminal state).  0 = auto:
    # 2 x serve.batch_size x fleet.replicas, clamped under the per-lane
    # shed watermark so steady-state bulk traffic never sheds.
    max_inflight: int = 0
    # plan batches per committed sink shard — the atomicity AND resume
    # unit: a shard lands via tmp → fsync → rename (all-or-nothing under
    # SIGKILL) and the resume cursor is the contiguous committed-shard
    # prefix, so a killed run restarts exactly-once at the first
    # uncommitted shard.
    shard_batches: int = 16
    # resubmit budget per image for replica-death / shed transients (the
    # fleet router's own reroute_retries sit BELOW this — a resubmit is
    # a fresh fleet request).  Exhausting it aborts the whole run loudly:
    # bulk never silently drops an image (N in = N accounted).
    retries: int = 8


@dataclass(frozen=True)
class FTConfig:
    """TPU addition (no reference equivalent — the reference dies on
    preemption and restarts at the last epoch boundary): policy knobs for
    the ``mx_rcnn_tpu/ft/`` fault-tolerance layer (docs/FT.md).

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set ft__field=value`` CLI overrides).
    """

    # serialize+write+fsync checkpoints on a background writer thread so
    # the training step only pays the device_get; False restores the
    # fully synchronous write-on-the-training-thread path
    async_snapshots: bool = True
    # the writer admits one snapshot being written + one queued (at most
    # two fetched host copies alive); the request that would make a third
    # blocks up to this long, then fails loudly (never an unbounded
    # backlog of multi-hundred-MB serializations)
    slot_timeout_s: float = 120.0
    # retention GC (ft/integrity.py — gc_checkpoints): keep the newest
    # keep_last epoch checkpoints plus every keep_every-th epoch.  The
    # DEFAULT keep_every=1 marks every epoch as a keeper, i.e. nothing is
    # ever deleted — reference parity (the reference keeps all per-epoch
    # params files); raise it (e.g. ``--set ft__keep_every=5``) to thin
    # long runs.  keep_last=0 disables GC entirely.
    keep_last: int = 3
    keep_every: int = 1
    # ``--resume auto`` HARD-FAILS when the checkpoint manifest records a
    # different effective global batch (device count x batch_images x
    # grad_accum) than this run would train with — a silent batch change
    # alters the LR-schedule semantics and the experiment.  True downgrades
    # the error to a WARNING; the elastic controller (ft/elastic.py) sets
    # it for its own supervised restores, where the resize is the point.
    allow_resize_resume: bool = False


@dataclass(frozen=True)
class ElasticConfig:
    """TPU addition (no reference equivalent — the reference assumes a
    fixed device set for the whole run): policy knobs for the
    ``mx_rcnn_tpu/ft/elastic.py`` elastic run controller (docs/FT.md
    "Elasticity"), which turns preemption into a mesh resize: drain →
    restore the latest valid checkpoint onto the new mesh → rescale
    grad accumulation so the effective global batch stays on-recipe →
    keep stepping.

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set elastic__field=value`` CLI overrides).
    """

    # master switch (tools/train.py --elastic): wrap training in the
    # generation loop that watches topology directives and resizes live
    enabled: bool = False
    # the RECIPE's reference device count: effective global batch =
    # base_devices x batch_images (x process count folded in).  A mesh of
    # K devices trains with grad_accum = base_devices / K so the
    # optimizer-step cadence and LR schedule never leave the recipe.
    # 0 = adopt the first generation's device count as the base.
    base_devices: int = 0
    # where topology directives land ("" = <prefix>.topology.json); the
    # supervisor (or any scheduler) atomically writes
    # {"generation": G, "num_devices": D, "num_processes": P} here and
    # optionally SIGUSR1s the process to poll immediately
    topology_path: str = ""
    # directive poll cadence in optimizer steps (a stat() per poll; 1 =
    # every step — detection latency is bounded by one step either way
    # because SIGUSR1 forces an immediate poll)
    poll_steps: int = 1
    # runaway guard: a generation loop that resizes more than this many
    # times in one run aborts loudly instead of thrashing forever
    max_generations: int = 64


@dataclass(frozen=True)
class QuantConfig:
    """TPU addition (no reference equivalent — the reference serves
    fp32): policy knobs for the post-training quantized INFERENCE
    forward (``ops/quant.py``, docs/PERF.md "Quantized inference").
    Applies the Jacob et al. 2018 PTQ playbook to the serving/eval
    forward: per-output-channel symmetric weight quantization +
    per-tensor activation scales from an offline calibration sweep.

    OFF by default; with ``enabled=False`` every fp serving/eval output
    is BIT-identical to a build without the subsystem (pinned by
    ``tests/test_quant.py``).  Training always runs fp — this section
    is deliberately OUTSIDE the config fingerprint (like ``serve``/
    ``test``), and the export-store manifest records the knobs plus the
    calibration fingerprint instead, so a fleet replica can never mix
    quantized and fp programs unknowingly (``serve/export.py``).

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set quant__field=value`` CLI overrides).
    """

    # master switch: quantize the inference forward (eval Predictor,
    # serving engine, AOT exports); training is never quantized
    enabled: bool = False
    # container dtype: 'int8' (the int32-accumulate integer path) or
    # 'fp8' (e4m3, fp32-accumulate)
    dtype: str = "int8"
    # 'native' runs the real low-precision program (int8×int8 →
    # int32-accumulate dot/conv); 'sim' runs the same quantized integer
    # values in fp32 arithmetic (the fake-quant proxy — pinned
    # tile-level-equivalent to native by test)
    mode: str = "native"
    # activation-scale estimator over the calibration sweep: 'absmax'
    # (running max of |x|) or 'percentile' (mean of the per-batch
    # ``percentile``-th percentile of |x| — clips outlier tails)
    estimator: str = "absmax"
    percentile: float = 99.9
    # effective integer bits of the int8 container, SHARED by the weight
    # channels and the activation grid (both quantize to
    # qmax = 2^(b-1)-1).  8 = production; lower values are the red-team
    # over-quantization arm the accuracy gate must catch
    # (tools/gauntlet.py quant_redteam)
    weight_bits: int = 8
    # calibration sweep: how many held-out TRAINING batches feed the
    # activation statistics, and the seed of the deterministic
    # subsample of the training roidb they are drawn from
    calibration_batches: int = 2
    calibration_seed: int = 0
    # accuracy gate: |paired mAP delta| bound for the quantized arm,
    # consumed by make quant-smoke.  The full gauntlet takes its own
    # --budget flag (default 0.02) and the parity runbook its
    # QUANT_TOLERANCE env — pass matching values there when gating
    # `--compare e2e quant` on real data
    map_delta_budget: float = 0.05


@dataclass(frozen=True)
class ObsConfig:
    """TPU addition (no reference equivalent — the reference's only
    instrument is the Speedometer stdout line): policy knobs for the
    ``mx_rcnn_tpu/obs/`` unified observability layer
    (docs/OBSERVABILITY.md).

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set obs__field=value`` CLI overrides).  Everything is
    OFF by default; the disabled hot-path cost is pinned near zero by
    ``tests/test_obs.py``.
    """

    # master switch: wire the process metrics registry into the fit
    # loop, data loaders and snapshotter, and have the CLIs write a
    # runs/<id>/ run record (events.jsonl + BENCH summary.json)
    enabled: bool = False
    # base directory for run records
    run_dir: str = "runs"
    # serve the unified registry as JSON GET /metrics on this port from
    # tools/train.py (0 = off; tools/serve.py always exposes /metrics on
    # its own HTTP front end)
    metrics_port: int = 0
    # export the collected host-side spans (obs/trace.py) as a chrome
    # trace into the run record on exit.  tools/train.py — train_net
    # collects them under ``enabled`` alone; tools/serve.py collects them
    # under this flag
    trace: bool = False
    # span ring size: the newest trace_cap events are kept (older ones
    # fall off, counted)
    trace_cap: int = 100_000
    # on-demand profiler window (obs/profiler.py): capture a
    # profile_steps-step jax.profiler window starting at this GLOBAL
    # step (0 = never), rolled up into per-scope device-time tables
    profile_at_step: int = 0
    profile_steps: int = 3
    # where the window lands ("" = <run record dir>/profile)
    profile_dir: str = ""
    # arm SIGUSR2 as a live profiler toggle in the CLIs (kill -USR2 PID
    # starts a window, a second signal stops + rolls it up)
    sigusr2: bool = False
    # smoothing factor for the train.loss_ema gauge (per log window)
    loss_ema: float = 0.9
    # --- time-series plane (obs/timeseries.py) ---------------------------
    # ring-buffer sampler over the registry: counters→windowed rates,
    # gauges, exact windowed histogram percentiles; the substrate the
    # health engine and flight recorder read
    timeseries: bool = False
    sample_interval_s: float = 1.0   # sampler cadence
    ts_capacity: int = 600           # ring depth (samples)
    # --- SLO/health engine (obs/health.py) -------------------------------
    # evaluate the default rule set after every sample; publish
    # health.* gauges + runrec transitions + enriched /healthz
    health: bool = False
    health_window_s: float = 30.0    # default rule window (docs only —
    # the stock rules carry per-rule windows; kept as the knob custom
    # rule sets read)
    # --- flight recorder (obs/flightrec.py) ------------------------------
    # black-box dumps (runs/<id>/flight/) on crash / SIGTERM /
    # lock-watchdog trip / health-critical transition
    flight: bool = False
    flight_window_s: float = 120.0   # how much sample history a dump keeps
    flight_events: int = 512         # bounded event ring fed by runrec
    # --- cross-process collection (obs/collect.py) -----------------------
    # comma-separated /metrics URLs (host:port or full URL, optionally
    # name=url) merged into the labeled fleet view by tools/obs.py
    collect_urls: str = ""
    # --- distributed tracing (obs/trace.py distributed plane) ------------
    # head sampling probability for cross-host traces (0 = off: the
    # serve hot path pays one None-check and wire frames stay
    # bit-identical to the untraced layout — pinned by
    # tests/test_trace_distributed.py).  Deterministic fraction
    # accumulator, not a coin flip.
    trace_sample: float = 0.0
    trace_ring: int = 256            # kept span trees per process
    # forced tail retention: SERVED traces in the slowest percentile of
    # the recent window are kept alongside every non-SERVED/rerouted one
    trace_slow_pct: float = 99.0
    # obs.skew_ms.max drift alarm threshold (obs/health.py skew rule)
    skew_alarm_ms: float = 50.0


@dataclass(frozen=True)
class SimConfig:
    """TPU addition (no reference equivalent): policy knobs for the
    ``mx_rcnn_tpu/sim/`` fleet-at-scale simulator (docs/SIM.md) — a
    discrete-event virtual-time harness that runs the SHIPPED
    scheduler/health/router decision code over hundreds of simulated
    hosts.  Request-level semantics (batch size, shed watermark,
    deadline) are deliberately NOT duplicated here: the simulator reads
    ``cfg.serve`` and ``cfg.crosshost`` so a policy is gauntleted under
    the exact knobs it ships with.

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set sim__field=value`` CLI overrides).
    """

    hosts: int = 100            # simulated agent hosts (one registry each)
    duration_s: float = 240.0   # trace length in VIRTUAL seconds
    seed: int = 0               # root seed for every sim RNG substream
    # collector scrape / health / scheduler cadence in virtual seconds
    # (the sim analog of crosshost.scrape_interval_s, which is tuned for
    # wall-clock HTTP scraping and would be needless event pressure here)
    scrape_interval_s: float = 1.0
    # per-dispatch service time at the SMALLEST bucket (ms).  The engine
    # pads every micro-batch to serve.batch_size rows, so service cost
    # depends on the bucket, not the occupancy — 430 ms/batch-of-4
    # reproduces the ~9.3 img/s per-host rate CROSSHOST_r15 measured.
    # Larger buckets scale by pixel ratio.
    service_ms: float = 430.0
    service_jitter: float = 0.10   # lognormal sigma on service draws
    warmup_s: float = 5.0          # resize(+1) cold-join delay (vt)
    relaunch_s: float = 8.0        # host drain->relaunch dark time (vt)
    util: float = 0.65             # generators' base demand, as a
                                   # fraction of boot fleet capacity
    settle_s: float = 60.0         # post-trace drain budget before any
                                   # still-queued request counts lost


@dataclass(frozen=True)
class RolloutConfig:
    """TPU addition (no reference equivalent): knobs for the live-ops
    rollout plane (``serve/rollout.py``) — versioned export stores,
    per-host rolling updates, canary routing with the online paired
    gate, and first-class rollback (docs/SERVING.md "Rollout tier").

    Same 3-level precedence as every section (hardcoded defaults <
    presets < ``--set rollout__field=value`` CLI overrides).
    """

    # fraction of traffic the JSQ router sends down the canary version
    # lane while the gate observes (deterministic fraction accumulator,
    # not a coin flip — byte-reproducible under the simulator)
    canary_fraction: float = 0.25
    # online paired gate: equivalence budget on the shadow-score scale
    # (same CI-inside-±budget TOST judgment as tools/gauntlet.py
    # paired_compare) and the minimum paired samples before judging
    gate_budget: float = 0.02
    gate_min_pairs: int = 12
    # shadow-score every Nth sampled canary opportunity (live: every Nth
    # controller tick; sim: every Nth virtual gate tick)
    gate_sample_every: int = 4
    # canary dwell before rolling: the gate and HealthEngine observe at
    # least this long even if min_pairs is reached earlier
    bake_s: float = 10.0
    # per-host swap step bound: a host that stops answering mid-step is
    # skipped after this long and re-checked during FINALIZE (the
    # kill-mid-rollout convergence path)
    step_timeout_s: float = 60.0
    # hosts rolled concurrently (the wave width).  1 = strictly serial
    # per-host rolling (the live default); the 100-host sim scenario
    # overrides this to a wave, as a real fleet runbook would
    wave: int = 1
    # cadence of controller re-checks while waiting on pulls / warms /
    # drains (virtual seconds under the sim, wall seconds live)
    settle_s: float = 1.0
    # simulated store-pull latency (virtual seconds) for the sim port
    pull_s: float = 3.0
    # red-team arm: deterministic shadow-score damage applied to the v2
    # arm (sim + bench only; 0.0 = healthy).  The online-gate analog of
    # the gauntlet's _REDTEAM_NMS damaged arm.
    redteam_damage: float = 0.0


@dataclass(frozen=True)
class Config:
    train: TrainConfig = field(default_factory=TrainConfig)
    test: TestConfig = field(default_factory=TestConfig)
    network: NetworkConfig = field(default_factory=NetworkConfig)
    dataset: DatasetConfig = field(default_factory=DatasetConfig)
    default: DefaultConfig = field(default_factory=DefaultConfig)
    bucket: BucketConfig = field(default_factory=BucketConfig)
    data: DataConfig = field(default_factory=DataConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    crosshost: CrosshostConfig = field(default_factory=CrosshostConfig)
    bulk: BulkConfig = field(default_factory=BulkConfig)
    ft: FTConfig = field(default_factory=FTConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)
    quant: QuantConfig = field(default_factory=QuantConfig)
    sim: SimConfig = field(default_factory=SimConfig)
    rollout: RolloutConfig = field(default_factory=RolloutConfig)

    @property
    def num_classes(self) -> int:
        return self.dataset.num_classes

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)

    def replace_in(self, section: str, **kw: Any) -> "Config":
        """Return a new Config with fields replaced inside one section,
        e.g. ``cfg.replace_in('train', batch_images=2)``."""
        return dataclasses.replace(
            self, **{section: dataclasses.replace(getattr(self, section), **kw)})


# ---------------------------------------------------------------------------
# Network / dataset presets (ref rcnn/config.py — generate_config)
# ---------------------------------------------------------------------------

_NETWORKS: Mapping[str, Mapping[str, Any]] = {
    "vgg": dict(
        name="vgg",
        depth=16,
        rcnn_pooled_size=(7, 7),
        # ref: VGG FIXED_PARAMS = ['conv1', 'conv2'] — freeze first two blocks
        fixed_params=("conv1", "conv2"),
        fixed_params_shared=("conv1", "conv2", "conv3", "conv4", "conv5"),
        image_stride=0,
    ),
    "resnet50": dict(name="resnet50", depth=50, rcnn_pooled_size=(14, 14)),
    "resnet101": dict(name="resnet101", depth=101, rcnn_pooled_size=(14, 14)),
    # test-only miniature network (see models/tiny.py); small anchors so
    # tiny test images still contain in-image anchors
    "tiny": dict(
        name="tiny", depth=0, rcnn_pooled_size=(7, 7),
        # 32/64/128-px anchors: cover both the 128-px unit-test canvases and
        # the synthetic dataset's 320x400 canvases (objects span 1/5..1/2 of
        # the canvas in data/synthetic.py)
        anchor_scales=(2, 4, 8), fixed_params=(),
        # tiny's whole backbone is conv1+conv2 — the alternate-training
        # shared-conv freeze must cover it for the combine to be valid
        fixed_params_shared=("conv1", "conv2"),
        compute_dtype="float32",
    ),
    # NVIDIA-Nemotron-3-Nano-30B-A3B (model_type nemotron_h) at its
    # published widths, whole: 52 blocks, all 128 experts, the whole
    # vocabulary.  A chip's share of it is this preset with layer_pattern,
    # experts_held and vocab_size overridden (benchmark/configs/).
    "nemotron_h": dict(
        name="nemotron_h", family="nemotron_h", fixed_params=(),
        layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        init_layers=52, hidden_size=2688, vocab_size=131072, norm_eps=1e-5,
        mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
        ssm_groups=8, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        n_routed_experts=128, experts_held=(0, 128), num_experts_per_tok=6,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        routed_scaling_factor=2.5, norm_topk_prob=True,
    ),
    # test-only miniature of the same family: every mechanism, CPU-sized
    "nemotron_h_tiny": dict(
        name="nemotron_h_tiny", family="nemotron_h", fixed_params=(),
        layer_pattern="ME*E", init_layers=4, hidden_size=64, vocab_size=256,
        mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
        ssm_groups=2, conv_kernel=4, chunk_size=16,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        attn_block_q=32,
        n_routed_experts=8, experts_held=(2, 2), num_experts_per_tok=2,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        # 4 x the even share is every assignment a token can make to the
        # two held experts: a bound that cannot be exceeded
        moe_capacity_factor=4.0,
        compute_dtype="float32",
    ),
    # Ling-3.0-flash-VL's language stack (inclusionAI; config.json's keys)
    # at its published widths, whole: 42 layers, five KDA to one MLA in
    # every six (MLA where (i + 1) % 6 == 0), two leading dense layers, 512
    # experts in 8 groups.  A chip's share is this preset with
    # layer_pattern, first_k_dense_replace, experts_held and vocab_size
    # overridden (benchmark/configs/ling3-flash-6l-ep64.json).
    "ling_flash": dict(
        name="ling_flash", family="ling_flash", fixed_params=(),
        layer_pattern="".join("L" if (i + 1) % 6 == 0 else "K"
                              for i in range(42)),
        first_k_dense_replace=2, init_layers=42, hidden_size=2560,
        vocab_size=157184, norm_eps=1e-6, intermediate_size=6144,
        num_attention_heads=32, head_dim=128, conv_kernel=4, chunk_size=64,
        kda_lower_bound=-5.0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=6e6,
        n_routed_experts=512, experts_held=(0, 512), num_experts_per_tok=8,
        n_group=8, topk_group=4, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, routed_scaling_factor=2.5,
        norm_topk_prob=True,
    ),
    # test-only miniature of the same family: every mechanism, CPU-sized
    # (dense-KDA, KDA-E, MLA-E, KDA-E; 16 experts in 4 groups, 2 kept, 2 held)
    "ling_flash_tiny": dict(
        name="ling_flash_tiny", family="ling_flash", fixed_params=(),
        layer_pattern="KKLK", first_k_dense_replace=1, init_layers=4,
        hidden_size=64, vocab_size=256, norm_eps=1e-6, intermediate_size=96,
        num_attention_heads=4, head_dim=16, conv_kernel=4, chunk_size=16,
        kda_lower_bound=-5.0, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=6e6, attn_block_q=32,
        n_routed_experts=16, experts_held=(4, 2), num_experts_per_tok=2,
        n_group=4, topk_group=2, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, routed_scaling_factor=2.5,
        norm_topk_prob=True,
        # 8 x the even share is every assignment a token can make to the
        # two held experts: a bound that cannot be exceeded
        moe_capacity_factor=8.0,
        compute_dtype="float32",
    ),
    # JoyAI-LLM-Flash (jdopensource; config.json's keys, DeepSeek-V3's
    # modelling) at its published widths, whole: 40 layers of latent
    # attention with a low-rank query, one leading dense layer, 256 experts
    # routed ungrouped (n_group 1), one multi-token-prediction module.  A
    # chip's share is this preset with layer_pattern, experts_held and
    # vocab_size overridden (benchmark/configs/joyai-flash-5l-mtp-ep16.json).
    "joyai_flash": dict(
        name="joyai_flash", family="joyai_flash", fixed_params=(),
        layer_pattern="L" * 40, first_k_dense_replace=1, init_layers=40,
        hidden_size=2048, vocab_size=129280, norm_eps=1e-6,
        intermediate_size=7168, num_attention_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=32e6, rope_interleave=True,
        n_routed_experts=256, experts_held=(0, 256), num_experts_per_tok=8,
        n_group=1, topk_group=1, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, routed_scaling_factor=2.5,
        norm_topk_prob=True, num_nextn_predict_layers=1,
        # DeepSeek-V3's weight for most of its pre-training (0.1 after)
        mtp_loss_weight=0.3,
    ),
    # test-only miniature of the same family: every mechanism, CPU-sized
    # (MLA+dense, MLA+E, MLA+E and the module; 16 experts, 4 held, top 2)
    "joyai_flash_tiny": dict(
        name="joyai_flash_tiny", family="joyai_flash", fixed_params=(),
        layer_pattern="LLL", first_k_dense_replace=1, init_layers=3,
        hidden_size=64, vocab_size=256, norm_eps=1e-6, intermediate_size=96,
        num_attention_heads=4, q_lora_rank=40, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=32e6, rope_interleave=True, attn_block_q=32,
        n_routed_experts=16, experts_held=(4, 4), num_experts_per_tok=2,
        n_group=1, topk_group=1, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, routed_scaling_factor=2.5,
        norm_topk_prob=True, num_nextn_predict_layers=1, mtp_loss_weight=0.3,
        # 4 x the even share is every assignment a token can make to held
        # experts (top 2 of them): a bound that cannot be exceeded
        moe_capacity_factor=4.0,
        compute_dtype="float32",
    ),
    # Keye-VL-2.0-30B-A3B's language stack (Kwai-Keye; config.json's keys
    # with sa_config's) at its published widths, whole: 48 layers of
    # grouped-query attention (32 over 4 heads of 128) over the 2048 keys a
    # query an indexer of 16 heads of 64 chooses, each followed by 128
    # experts routed by softmax, top 8, no shared expert.  A chip's share is
    # this preset with layer_pattern, experts_held and vocab_size
    # overridden (benchmark/configs/keye-vl2-a3b-6l-ep8.json).
    "keye_vl2": dict(
        name="keye_vl2", family="keye_vl2", fixed_params=(),
        layer_pattern="S" * 48, init_layers=48, hidden_size=2048,
        vocab_size=151936, norm_eps=1e-6, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, rope_theta=1e7,
        indexer_num_heads=16, indexer_head_dim=64, indexer_rope_dim=32,
        indexer_topk=2048, n_routed_experts=128, experts_held=(0, 128),
        num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True, router_scoring="softmax",
        # ops/gmm_pallas.py's row tile
        moe_group_rows=256,
    ),
    # test-only miniature of the same family: every mechanism, CPU-sized
    # (two layers; 4 over 2 heads of 16, an indexer of 4 heads of 16
    # choosing 16 keys; 16 experts, 4 held, top 2)
    "keye_vl2_tiny": dict(
        name="keye_vl2_tiny", family="keye_vl2", fixed_params=(),
        layer_pattern="SS", init_layers=2, hidden_size=64, vocab_size=256,
        norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=1e7, attn_block_q=16, indexer_num_heads=4,
        indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=16,
        n_routed_experts=16, experts_held=(4, 4), num_experts_per_tok=2,
        moe_intermediate_size=32, norm_topk_prob=True,
        router_scoring="softmax", moe_token_chunk=32, moe_group_rows=8,
        # 4 x the even share is every assignment a token can make to held
        # experts (top 2 of them): a bound that cannot be exceeded
        moe_capacity_factor=4.0,
        compute_dtype="float32",
    ),
    # EvaByte (config.json's keys) at its published widths, whole: 32 layers
    # of EVA attention (32 heads of 128 for queries, keys and values alike,
    # windows of 2048 bytes, chunks of 16) and a dense SwiGLU of 11008, a
    # vocabulary of 320 byte ids, 8 prediction heads.  A chip's share is
    # this preset with layer_pattern overridden
    # (benchmark/configs/evabyte-4l-pp8.json).
    "evabyte": dict(
        name="evabyte", family="evabyte", fixed_params=(),
        layer_pattern="V" * 32, hidden_size=4096, vocab_size=320,
        norm_eps=1e-5, init_std=0.01275, num_attention_heads=32,
        num_key_value_heads=32, head_dim=128, rope_theta=1e5,
        eva_window=2048, eva_chunk=16, intermediate_size=11008,
        num_pred_heads=8,
    ),
    # test-only miniature of the same family: every mechanism, CPU-sized
    # (two layers; 2 heads of 128, so that the kernels' interpreter takes
    # them; windows of 64, chunks of 16; 4 heads)
    "evabyte_tiny": dict(
        name="evabyte_tiny", family="evabyte", fixed_params=(),
        layer_pattern="VV", hidden_size=256, vocab_size=320, norm_eps=1e-5,
        init_std=0.0625, num_attention_heads=2, num_key_value_heads=2,
        head_dim=128, rope_theta=1e5, eva_window=64, eva_chunk=16,
        attn_block_q=32, intermediate_size=384, num_pred_heads=4,
        compute_dtype="float32",
    ),
}

# every preset ``generate_config`` builds a network from (the train CLI's
# ``--network`` choices)
NETWORK_NAMES = tuple(_NETWORKS)

# What a network preset fixes outside its own section: the sequence family
# trains with AdamW (core/optim.py reads momentum as beta1, clip_gradient as
# the global-norm clip, wd as the decoupled decay of matrices), a constant
# lr after the optional warm-up, and rows that are sequences.
_NETWORK_SECTIONS: Mapping[str, Mapping[str, Mapping[str, Any]]] = {
    "nemotron_h": {
        # lr: what a linear warm-up over a few thousand steps to a peak of
        # a few 1e-4 gives in its first steps, which is where a run from
        # random weights is; Adam moves every weight by lr a step whatever
        # the gradient, and at 1e-4 the router's logits shift by tenths a
        # step (PERF.md section 6, PR 34)
        "default": dict(e2e_lr=1e-6, e2e_lr_step="", wd=0.1,
                        clip_gradient=1.0, momentum=0.9, e2e_epoch=1),
        "train": dict(seq_len=8192, batch_images=2, flip=False),
    },
    "nemotron_h_tiny": {
        "default": dict(e2e_lr=3e-3, e2e_lr_step="", wd=0.1,
                        clip_gradient=1.0, momentum=0.9, e2e_epoch=1,
                        frequent=4),
        "train": dict(seq_len=64, batch_images=2, flip=False),
    },
}
# ling_flash, joyai_flash, keye_vl2 and evabyte train by the recipe
# nemotron_h states, for the reasons it states
_NETWORK_SECTIONS = {**_NETWORK_SECTIONS,
                     "ling_flash": _NETWORK_SECTIONS["nemotron_h"],
                     "ling_flash_tiny": _NETWORK_SECTIONS["nemotron_h_tiny"],
                     "joyai_flash": _NETWORK_SECTIONS["nemotron_h"],
                     "joyai_flash_tiny": _NETWORK_SECTIONS["nemotron_h_tiny"],
                     "keye_vl2": _NETWORK_SECTIONS["nemotron_h"],
                     "keye_vl2_tiny": _NETWORK_SECTIONS["nemotron_h_tiny"],
                     "evabyte": _NETWORK_SECTIONS["nemotron_h"],
                     "evabyte_tiny": _NETWORK_SECTIONS["nemotron_h_tiny"]}

_DATASETS: Mapping[str, Mapping[str, Any]] = {
    "PascalVOC": dict(
        name="PascalVOC",
        image_set="2007_trainval",
        test_image_set="2007_test",
        dataset_path="data/VOCdevkit",
        num_classes=21,
    ),
    "coco": dict(
        name="coco",
        image_set="train2017",
        test_image_set="val2017",
        dataset_path="data/coco",
        num_classes=81,
    ),
    # download-free generated dataset (data/synthetic.py) — the end-to-end
    # train→eval gate runs on it; no reference equivalent
    "synthetic": dict(
        name="synthetic",
        image_set="train",
        test_image_set="test",
        dataset_path="data/synthetic",
        num_classes=4,
    ),
    # the accuracy gauntlet (data/synthetic.py — HardSyntheticDataset):
    # 8 fg classes, 200/100 images, scale/occlusion/crowding + distractors
    "synthetic_hard": dict(
        name="synthetic_hard",
        image_set="train",
        test_image_set="test",
        dataset_path="data/synthetic_hard",
        num_classes=9,
    ),
    # COCO-cardinality rehearsal set (data/synthetic.py —
    # StreamSyntheticDataset): 80 fg classes, 10k/1k images — sized so
    # input-plane claims are tested at production cardinality, not on
    # sets that fit in HBM (docs/DATA.md)
    "synthetic_stream": dict(
        name="synthetic_stream",
        image_set="train",
        test_image_set="test",
        dataset_path="data/synthetic_stream",
        num_classes=81,
    ),
    # token sources for the sequence families (data/tokens.py): a file of
    # ids (<dataset_path>/<image_set>.npy), or ids drawn from the seed
    "tokens": dict(
        name="tokens", image_set="train", test_image_set="test",
        dataset_path="data/tokens", num_classes=0,
    ),
    "synthetic_tokens": dict(
        name="synthetic_tokens", image_set="train", test_image_set="test",
        dataset_path="data/synthetic_tokens", num_classes=0,
    ),
}

# Per-dataset bucket presets (TPU addition): synthetic canvases are
# 320x400 (hard: 240x320), so resizing them to the VOC 600/1000 scale
# would only waste compute on interpolated pixels.
_DATASET_BUCKETS: Mapping[str, Mapping[str, Any]] = {
    "synthetic": dict(scale=320, max_size=416,
                      shapes=((320, 416), (416, 320))),
    "synthetic_hard": dict(scale=240, max_size=320,
                           shapes=((240, 320), (320, 240))),
    "synthetic_stream": dict(scale=240, max_size=320,
                             shapes=((240, 320), (320, 240))),
}


def generate_config(network: str = "resnet101", dataset: str = "PascalVOC",
                    **overrides: Any) -> Config:
    """Build an immutable Config from network+dataset presets.

    Reference: ``rcnn/config.py — generate_config(network, dataset)`` which
    mutates the global singleton; here a fresh Config is returned.
    ``overrides`` may address nested fields with a ``section__field`` key,
    e.g. ``generate_config('vgg', 'PascalVOC', train__batch_images=2)``.
    """
    if network not in _NETWORKS:
        raise KeyError(f"unknown network {network!r}; have {sorted(_NETWORKS)}")
    if dataset not in _DATASETS:
        raise KeyError(f"unknown dataset {dataset!r}; have {sorted(_DATASETS)}")
    cfg = Config(
        network=NetworkConfig(**_NETWORKS[network]),
        dataset=DatasetConfig(**_DATASETS[dataset]),
    )
    if dataset in _DATASET_BUCKETS:
        cfg = cfg.replace_in("bucket", **_DATASET_BUCKETS[dataset])
    for section, kw in _NETWORK_SECTIONS.get(network, {}).items():
        cfg = cfg.replace_in(section, **kw)
    by_section: dict = {}
    for key, val in overrides.items():
        if "__" not in key:
            raise KeyError(f"override {key!r} must be 'section__field'")
        section, fname = key.split("__", 1)
        by_section.setdefault(section, {})[fname] = val
    for section, kw in by_section.items():
        node = getattr(cfg, section, None)
        if node is not None:
            # resolved type objects (not strings): get_type_hints evaluates
            # the `from __future__ import annotations` strings against the
            # module namespace, so every Optional/Union spelling works
            try:
                declared = typing.get_type_hints(type(node))
            except Exception:  # unresolvable forward ref: fall back to cur
                declared = {}
            kw = {f: _coerce_override(getattr(node, f, None), v,
                                      f"{section}__{f}", declared.get(f))
                  for f, v in kw.items()}
        cfg = cfg.replace_in(section, **kw)
    return cfg


_BOOL_STRINGS = {"true": True, "yes": True, "1": True,
                 "false": False, "no": False, "0": False}


_DTYPE_STRINGS = ("float32", "bfloat16")


def validate_dtype_string(val: str, key: str) -> str:
    """Dtype-string config fields (``compute_dtype``, ``momentum_dtype``)
    accept exactly two spellings; anything else must FAIL loudly — a typo
    like 'bf16' silently falling back to float32 would erase the memory
    saving the user asked for with no signal."""
    if val not in _DTYPE_STRINGS:
        raise ValueError(
            f"{key} must be one of {_DTYPE_STRINGS}, got {val!r}")
    return val


def _synthetic_exemplar(tp: Any) -> Any:
    """An exemplar value of a field's RESOLVED declared type, used to drive
    coercion when the field's *current* value is None (advisor r3: keying
    coercion off a None value silently skipped all type checks).  ``tp``
    comes from ``typing.get_type_hints``, so Optional[X], Union[X, None]
    and ``X | None`` all arrive as unions and unwrap uniformly.  Returns
    None for types coercion doesn't handle."""
    origin = typing.get_origin(tp)
    union_kinds = (typing.Union, getattr(types, "UnionType", ()))
    if origin in union_kinds:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) != 1:
            return None  # genuinely multi-typed field: store as-is
        tp = args[0]
        origin = typing.get_origin(tp)
    if tp is tuple or origin is tuple:
        return ()
    return {bool: False, int: 0, float: 0.0, str: ""}.get(tp)


def _coerce_override(cur: Any, val: Any, key: str,
                     annotation: Any = None) -> Any:
    """Coerce a config override to the field's declared type.

    Frozen dataclasses do no type checking, and CLI ``--set`` values may
    arrive as strings (``--set train__shuffle=false``) — without coercion
    the string "false" would be stored and read as truthy.  Unknown fields
    (cur is None AND no annotation, because getattr missed) pass through so
    replace_in can raise its own error.  A known field whose current value
    is None coerces against its declared annotation instead, so None
    defaults still get type errors on bad literals.
    """
    if val is None:
        return val
    if cur is None:
        if annotation is None:
            return val
        cur = _synthetic_exemplar(annotation)
        if cur is None:  # un-coercible declared type: store as-is
            return val
    if isinstance(cur, bool):
        if isinstance(val, bool):
            return val
        if isinstance(val, int) and val in (0, 1):
            return bool(val)
        if isinstance(val, str) and val.lower() in _BOOL_STRINGS:
            return _BOOL_STRINGS[val.lower()]
        raise TypeError(f"{key} expects a bool, got {val!r}")
    if isinstance(cur, int):
        if isinstance(val, bool) or (isinstance(val, float)
                                     and not val.is_integer()):
            raise TypeError(f"{key} expects an int, got {val!r}")
        try:
            return int(val)
        except (TypeError, ValueError):
            raise TypeError(f"{key} expects an int, got {val!r}")
    if isinstance(cur, float):
        if isinstance(val, bool):
            raise TypeError(f"{key} expects a float, got {val!r}")
        try:
            return float(val)
        except (TypeError, ValueError):
            raise TypeError(f"{key} expects a float, got {val!r}")
    if isinstance(cur, tuple):
        if isinstance(val, (list, tuple)):
            # deep-convert so no mutable list nests inside the frozen
            # config (shapes etc. are tuples of tuples)
            return tuple(tuple(v) if isinstance(v, (list, tuple)) else v
                         for v in val)
        raise TypeError(f"{key} expects a tuple/list, got {val!r}")
    if isinstance(cur, str) and not isinstance(val, str):
        raise TypeError(f"{key} expects a string, got {val!r}")
    return val
