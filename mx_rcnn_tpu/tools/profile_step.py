"""Train-step time breakdown: where do the milliseconds go?

Reference analog: none — the reference has no profiler wiring beyond
``Speedometer`` (SURVEY.md §5.1).  On TPU the jitted step is one opaque XLA
program, so this tool attributes time by *ablation*: it compiles and times
each stage of the step (backbone, RPN losses, proposal NMS, targets,
ROIAlign, ROI head, full step) on the real device and reports per-stage
milliseconds.

Measurement methodology: each stage is chained N times inside ONE XLA
program with an unfoldable data dependency (carry · 1e-30 injected into
the stage input, carry re-derived from the stage output), then timed with
a single dispatch + fetch; per-iteration time = wall / N, so the fixed
dispatch cost is amortized N-fold.

The chain is UNROLLED at trace time, not a ``lax.fori_loop``: loop bodies
at ResNet-101 size hit a compile pathology on the round-3 stack (the
loop-wrapped program ran ~12× slower than the flat one; not re-tested on
jax 0.9.0).  Unrolling sidesteps the loop
op entirely at the cost of compile time linear in N — hence the default
N of 8; raise ``--iters`` on fast-compiling devices for tighter numbers.

r6 additions: the lever A/B switches (``--roi_backend blocked
--roi_chunk 64`` for the ROI-chunked blocked ROIAlign, ``--nms_mode
per_image`` for the pre-batched proposal sweep, ``--shape 640x1024`` for
the sublane-friendly bucket arm — `script/perf_r6.sh` drives the full
battery incl. the batch-8 stage table), per-stage gauges into the obs
registry (``profile/stage_ms/*`` — stage tables land in the unified
/metrics view and runrec summaries), and ``--check`` (the `make
perf-smoke` self-test: finite stages, ZERO timed-pass recompiles, chain
self-check).

Usage:
  python -m mx_rcnn_tpu.tools.profile_step --network resnet101 --iters 8
"""

from __future__ import annotations

import argparse
import functools
import sys
import time

import numpy as np


def make_batch(cfg, batch_images, h, w, seed=0, raw=False):
    """Synthetic training batch; ``raw=True`` emits the uint8 image layout
    the production loader ships (device-side normalization path)."""
    import jax.numpy as jnp

    from mx_rcnn_tpu.core.train import Batch

    rng = np.random.RandomState(seed)
    g = cfg.train.max_gt_boxes
    n_gt = 8
    gt_boxes = np.zeros((batch_images, g, 4), np.float32)
    gt_classes = np.zeros((batch_images, g), np.int32)
    gt_valid = np.zeros((batch_images, g), bool)
    for i in range(batch_images):
        xy = rng.uniform(0, [w * 0.8, h * 0.8], (n_gt, 2))
        wh = rng.uniform(0.05, 0.4, (n_gt, 2)) * [w, h]
        gt_boxes[i, :n_gt, :2] = xy
        gt_boxes[i, :n_gt, 2:] = np.minimum(xy + wh, [w - 1, h - 1])
        gt_classes[i, :n_gt] = rng.randint(1, cfg.dataset.num_classes, n_gt)
        gt_valid[i, :n_gt] = True
    if raw:
        images = jnp.asarray(
            rng.randint(0, 256, (batch_images, h, w, 3)), jnp.uint8)
    else:
        images = jnp.asarray(rng.randn(batch_images, h, w, 3), jnp.float32)
    return Batch(
        images=images,
        im_info=jnp.tile(jnp.array([[float(h), float(w), 1.0]]),
                         (batch_images, 1)),
        gt_boxes=jnp.asarray(gt_boxes),
        gt_classes=jnp.asarray(gt_classes),
        gt_valid=jnp.asarray(gt_valid),
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--network", default="resnet101")
    p.add_argument("--dataset", default="coco")
    p.add_argument("--batch_images", type=int, default=2)
    p.add_argument("--shape", default="608x1024")
    p.add_argument("--iters", type=int, default=8,
                   help="unrolled chain length inside the timed XLA "
                        "program (compile time grows with it)")
    p.add_argument("--trace_dir", default=None,
                   help="also dump a jax.profiler trace here")
    p.add_argument("--trace_summary", action="store_true",
                   help="parse the dumped trace (utils/xplane.py) and "
                        "print device time by named scope and op class")
    p.add_argument("--prenms", type=int, default=None,
                   help="override TRAIN rpn_pre_nms_top_n (the adopted "
                        "recipe is 6000; the config ships the ref 12000)")
    p.add_argument("--roi_backend", default="auto",
                   choices=("auto", "jnp", "blocked", "pallas"),
                   help="ROIAlign backend for the roi_align stage AND the "
                        "full step (cfg.train.roi_align_backend) — the r6 "
                        "blocked-vs-einsum A/B arm switch")
    p.add_argument("--roi_chunk", type=int, default=64,
                   help="ROI block size for --roi_backend blocked")
    p.add_argument("--nms_mode", default="batched",
                   choices=("batched", "per_image"),
                   help="proposal-stage NMS composition: 'batched' (one "
                        "cross-image tile sweep when the jnp backend is "
                        "selected) or 'per_image' (vmap of per-image "
                        "sweeps — the pre-r6 composition)")
    p.add_argument("--nms_backend", default="auto",
                   choices=("auto", "jnp", "pallas"),
                   help="suppression-sweep backend (ops/nms.py "
                        "set_nms_backend).  NOTE on TPU 'auto' resolves "
                        "to the per-image Pallas kernel in BOTH "
                        "--nms_mode arms (lane/VMEM guards permitting), "
                        "so the batched-sweep A/B must force 'jnp' to "
                        "engage the cross-image sweep — script/perf_r6.sh "
                        "leg 3 runs the 3-arm comparison")
    p.add_argument("--check", action="store_true",
                   help="perf-smoke self-test: assert the chain "
                        "self-check (sum of stages ~ full step), zero "
                        "recompiles on every timed pass, and the stage "
                        "gauges landing in the obs registry; exits "
                        "non-zero on violation")
    p.add_argument("--quant", action="store_true",
                   help="ALSO time the quantized INFERENCE forward vs "
                        "the fp one per bucket (docs/PERF.md 'Quantized "
                        "inference'): calibrates on the synthetic batch, "
                        "then chains the full test-mode forward in both "
                        "arms — the r9 quant A/B switch")
    p.add_argument("--quant_dtype", default="int8",
                   choices=("int8", "fp8"))
    p.add_argument("--quant_mode", default="native",
                   choices=("native", "sim"))
    p.add_argument("--pad_stem", type=int, default=0,
                   help="backbone layout lever: zero-pad the stem's "
                        "input channels 3 -> N before conv0 "
                        "(cfg.network.stem_channel_pad; output pinned "
                        "bit-identical, param shapes change).  The A/B "
                        "is two invocations, 0 vs 4, like the other "
                        "lever switches")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.train import make_train_step, setup_training
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.obs.metrics import LoweringCounter, registry
    from mx_rcnn_tpu.ops.nms import set_nms_backend
    from mx_rcnn_tpu.ops.proposal import propose_batch
    from mx_rcnn_tpu.ops.roi_pool import roi_align_batched
    from mx_rcnn_tpu.ops.targets import anchor_target, proposal_target

    set_nms_backend(args.nms_backend)

    h, w = (int(v) for v in args.shape.split("x"))
    n = args.batch_images
    N = args.iters
    cfg = generate_config(args.network, args.dataset)
    cfg = cfg.replace_in("train", batch_images=n,
                         roi_align_backend=args.roi_backend,
                         roi_align_chunk=args.roi_chunk,
                         nms_batched=args.nms_mode == "batched")
    if args.prenms is not None:
        cfg = cfg.replace_in("train", rpn_pre_nms_top_n=args.prenms)
    if args.pad_stem:
        cfg = cfg.replace_in("network", stem_channel_pad=args.pad_stem)
    model = build_model(cfg)
    tr = cfg.train
    key = jax.random.PRNGKey(0)
    batch = make_batch(cfg, n, h, w)

    print(f"device: {jax.devices()[0].platform} "
          f"({jax.devices()[0].device_kind}); loop N={N}", file=sys.stderr)
    state, tx = setup_training(model, cfg, key, (n, h, w, 3),
                               steps_per_epoch=10_000)
    variables = {"params": state.params, "batch_stats": state.batch_stats}

    def fetch(x):
        return np.asarray(jax.tree_util.tree_leaves(x)[0]).ravel()[:1]

    # stage table accounting: per-stage ms land in the process obs
    # registry (gauges under profile/stage_ms/* — the unified /metrics
    # view and runrec summaries pick them up) and in ``stage_ms`` for the
    # --check self-test; ``relowerings`` counts jit cache misses on the
    # TIMED pass of each stage (the warm pass must never retrace).
    stage_ms: dict = {}
    relowerings: dict = {}

    def record_stage(label, per_s, lowerings=0):
        ms = per_s * 1e3
        slug = "".join(ch if ch.isalnum() else "_" for ch in label.lower())
        slug = "_".join(filter(None, slug.split("_")))
        stage_ms[label] = ms
        relowerings[label] = lowerings
        registry().set_gauge(f"profile/stage_ms/{slug}", round(ms, 4))

    def timed_loop(stage, label, note=""):
        """stage: carry (f32 scalar) -> carry.  Runs N reps in one program,
        UNROLLED (no fori_loop — see module docstring); the carry chain is
        an unfoldable data dependence, so XLA cannot CSE the copies."""

        def chain(c):
            for _ in range(N):
                c = stage(c)
            return c

        looped = jax.jit(chain)
        fetch(looped(jnp.float32(0)))  # compile+warm
        with LoweringCounter() as lc:
            t0 = time.perf_counter()
            fetch(looped(jnp.float32(0)))
            per = (time.perf_counter() - t0) / N
        print(f"{label:<34s} {per * 1e3:9.2f} ms  {note}", flush=True)
        record_stage(label, per, lc.n)
        return per

    def carry_of(x):
        return jax.tree_util.tree_leaves(x)[0].ravel()[0].astype(jnp.float32)

    eps = jnp.float32(1e-30)

    # --- stages ------------------------------------------------------------
    def feat_of(images):
        return model.apply(variables, images, method=model.features)

    t_feat = timed_loop(
        lambda c: carry_of(feat_of(batch.images + c * eps)),
        "backbone fwd")

    feat = jax.jit(feat_of)(batch.images)
    _, fh, fw, fc = feat.shape
    anchors = jnp.asarray(model.anchors_for(fh, fw))

    def feat_bwd(c):
        def f(p):
            y = model.apply({**variables, "params": p},
                            batch.images + c * eps, method=model.features)
            return (y.astype(jnp.float32) ** 2).mean()
        g = jax.grad(f)(variables["params"])
        return carry_of(g)

    t_feat_bwd = timed_loop(feat_bwd, "backbone fwd+bwd (dummy loss)")

    rpn_cls, rpn_box = jax.jit(
        lambda v, f: model.apply(v, f, method=model.rpn_raw))(variables, feat)
    fg = jax.nn.softmax(rpn_cls.astype(jnp.float32), axis=-1)[..., 1]
    box32 = rpn_box.astype(jnp.float32)

    prop_fn = functools.partial(
        propose_batch, batched_nms=tr.nms_batched,
        pre_nms_top_n=tr.rpn_pre_nms_top_n,
        post_nms_top_n=tr.rpn_post_nms_top_n,
        nms_thresh=tr.rpn_nms_thresh, min_size=tr.rpn_min_size)

    def prop_stage(c):
        rois, _, _ = prop_fn(fg + c * eps, box32, anchors, batch.im_info)
        return carry_of(rois)

    t_prop = timed_loop(prop_stage, "proposal (decode+topk+NMS)",
                        f"pre={tr.rpn_pre_nms_top_n} "
                        f"post={tr.rpn_post_nms_top_n} "
                        f"nms={args.nms_mode}/{args.nms_backend}")

    rois, _, rois_valid = jax.jit(
        lambda s, d, i: prop_fn(s, d, anchors, i))(fg, box32, batch.im_info)

    at_one = functools.partial(
        anchor_target, rpn_batch_size=tr.rpn_batch_size,
        rpn_fg_fraction=tr.rpn_fg_fraction,
        positive_overlap=tr.rpn_positive_overlap,
        negative_overlap=tr.rpn_negative_overlap,
        clobber_positives=tr.rpn_clobber_positives,
        allowed_border=tr.rpn_allowed_border,
        bbox_weights=tr.rpn_bbox_weights)
    keys = jax.random.split(key, n)

    def at_stage(c):
        at = jax.vmap(at_one, in_axes=(None, 0, 0, 0, 0))(
            anchors, batch.gt_boxes + c * eps, batch.gt_valid,
            batch.im_info, keys)
        return carry_of(at.bbox_targets)

    t_at = timed_loop(at_stage, "anchor_target",
                      f"anchors={anchors.shape[0]}")

    pt_one = functools.partial(
        proposal_target, num_classes=model.num_classes,
        batch_rois=tr.batch_rois, fg_fraction=tr.fg_fraction,
        fg_thresh=tr.fg_thresh, bg_thresh_hi=tr.bg_thresh_hi,
        bg_thresh_lo=tr.bg_thresh_lo, bbox_means=tr.bbox_means,
        bbox_stds=tr.bbox_stds, gt_append=tr.gt_append)

    def pt_stage(c):
        pt = jax.vmap(pt_one)(rois + c * eps, rois_valid, batch.gt_boxes,
                              batch.gt_classes, batch.gt_valid, keys)
        return carry_of(pt.rois)

    t_pt = timed_loop(pt_stage, "proposal_target")

    pt = jax.jit(jax.vmap(pt_one))(rois, rois_valid,
                       batch.gt_boxes, batch.gt_classes, batch.gt_valid,
                       keys)

    # the stage runs whatever backend the full step will run
    # (cfg.train.roi_align_backend — 'auto' resolves like core/train)
    ra_backend = None if tr.roi_align_backend == "auto" \
        else tr.roi_align_backend
    ra_fn = functools.partial(
        roi_align_batched, output_size=model.pooled_size,
        spatial_scale=1.0 / model.feat_stride, backend=ra_backend,
        chunk=tr.roi_align_chunk)

    def ra_stage(c):
        pooled = ra_fn(feat + c * eps.astype(feat.dtype), pt.rois)
        return carry_of(pooled)

    t_ra = timed_loop(ra_stage, "roi_align",
                      f"rois={pt.rois.shape[0] * pt.rois.shape[1]} "
                      f"backend={tr.roi_align_backend}")

    pooled = jax.jit(ra_fn)(feat, pt.rois)
    flat = pooled.reshape((-1,) + pooled.shape[2:])

    def head_stage(c):
        def f(p):
            cl, b = model.apply(
                {**variables, "params": p},
                flat + c * eps.astype(flat.dtype), True,
                method=model.roi_head, rngs={"dropout": jax.random.PRNGKey(0)})
            return (cl.astype(jnp.float32) ** 2).mean() + \
                   (b.astype(jnp.float32) ** 2).mean()
        return carry_of(jax.grad(f)(variables["params"]))

    t_head = timed_loop(head_stage, "roi head fwd+bwd (dummy loss)",
                        f"rois={flat.shape[0]}")

    # --- aggregate ablations ----------------------------------------------
    from mx_rcnn_tpu.core.train import Batch, loss_and_metrics

    def loss_fwd_stage(c):
        b = Batch(batch.images + c * eps, batch.im_info, batch.gt_boxes,
                  batch.gt_classes, batch.gt_valid)
        total, _ = loss_and_metrics(model, variables["params"],
                                    variables["batch_stats"], b, key, cfg)
        return total

    t_loss_fwd = timed_loop(loss_fwd_stage, "full loss fwd (no bwd)")

    def loss_bwd_stage(c):
        b = Batch(batch.images + c * eps, batch.im_info, batch.gt_boxes,
                  batch.gt_classes, batch.gt_valid)

        def f(p):
            total, _ = loss_and_metrics(model, p, variables["batch_stats"],
                                        b, key, cfg)
            return total

        return carry_of(jax.grad(f)(variables["params"]))

    t_loss_bwd = timed_loop(loss_bwd_stage, "full loss fwd+bwd (no update)")

    grads = jax.jit(lambda: jax.grad(
        lambda p: loss_and_metrics(model, p, variables["batch_stats"],
                                   batch, key, cfg)[0]
    )(variables["params"]))()

    def opt_stage(c):
        g = jax.tree_util.tree_map(lambda x: x + c * eps.astype(x.dtype),
                                   grads)
        updates, _ = tx.update(g, state.opt_state, variables["params"])
        return carry_of(updates)

    t_opt = timed_loop(opt_stage, "optimizer update")

    # --- full step (natural chaining through the state) --------------------
    step = jax.jit(make_train_step(model, cfg, tx), donate_argnums=(0,))
    # the step donates its state: run it on a copy (``variables`` shares
    # the original's buffers)
    s = step(jax.tree.map(jnp.copy, state), batch, key)[0]
    s, metrics = step(s, batch, key)
    fetch(metrics["loss"])
    with LoweringCounter() as lc_full:
        t0 = time.perf_counter()
        for _ in range(N):
            s, metrics = step(s, batch, key)
        fetch(metrics["loss"])
        t_full = (time.perf_counter() - t0) / N
    print(f"{'FULL train step (donated)':<34s} {t_full * 1e3:9.2f} ms  "
          f"imgs/s/chip={n / t_full:.1f}", flush=True)
    record_stage("FULL train step (donated)", t_full, lc_full.n)

    acct = t_feat_bwd + t_prop + t_at + t_pt + t_ra + t_head
    print(f"{'sum of pieces (approx)':<34s} {acct * 1e3:9.2f} ms", flush=True)
    record_stage("sum of pieces (approx)", acct)
    registry().set_gauge("profile/self_check_ratio",
                         round(acct / t_full, 4) if t_full > 0 else -1.0)

    # --- quantized inference A/B (--quant; docs/PERF.md "Quantized
    # inference"): the full TEST-MODE forward — the program serving and
    # eval run — fp vs quantized, same chain methodology.  Calibration
    # sweeps the synthetic batch (deterministic), so the arm is
    # self-contained; accuracy is gated elsewhere (gauntlet/quant-smoke),
    # this measures pure step time.
    if args.quant:
        from mx_rcnn_tpu.core.tester import calibrate_quant

        test_images = jnp.asarray(np.asarray(batch.images, np.float32))
        test_info = jnp.asarray(batch.im_info)

        def fp_fwd_stage(c):
            out = model.apply(variables, test_images + c * eps, test_info)
            return carry_of(out[2])

        timed_loop(fp_fwd_stage, "inference fwd (fp)",
                   f"batch={n} post={model.test_post_nms_top_n}")

        qcfg = cfg.replace_in("quant", enabled=True,
                              dtype=args.quant_dtype, mode=args.quant_mode)
        quant_col = calibrate_quant(
            qcfg, variables["params"], variables["batch_stats"],
            batches=[(np.asarray(test_images), np.asarray(batch.im_info))])
        qmodel = build_model(qcfg)
        qvars = {**variables, "quant": quant_col}

        def q_fwd_stage(c):
            out = qmodel.apply(qvars, test_images + c * eps, test_info)
            return carry_of(out[2])

        timed_loop(q_fwd_stage,
                   f"inference fwd ({args.quant_dtype}/{args.quant_mode})",
                   f"batch={n}")

    if args.check:
        _run_check(stage_ms, relowerings, acct, t_full)

    if args.trace_dir:
        import jax.profiler

        with jax.profiler.trace(args.trace_dir):
            for _ in range(3):
                s, metrics = step(s, batch, key)
            fetch(metrics["loss"])
        print(f"trace written to {args.trace_dir}", file=sys.stderr)
        if args.trace_summary:
            summarize_trace(args.trace_dir)


def _run_check(stage_ms: dict, relowerings: dict, acct: float,
               t_full: float) -> None:
    """`--check` (make perf-smoke): assert the profiler's own invariants —
    every stage measured finite, NO stage retraced on its timed pass, the
    chain self-check holds (sum of the six component stages lands in the
    same ballpark as the full step; wide band because a contended CPU box
    adds multiplicative noise and XLA overlaps stages in-program), and the
    per-stage gauges landed in the obs registry.  Raises SystemExit(1) on
    the first violation so `make test-gate` fails loudly."""
    import math

    from mx_rcnn_tpu.obs.metrics import registry

    failures = []
    for label, ms in stage_ms.items():
        if not math.isfinite(ms):
            failures.append(f"stage {label!r} not finite: {ms}")
    for label, lows in relowerings.items():
        if lows:
            failures.append(
                f"stage {label!r} lowered {lows} new program(s) on its "
                f"timed pass (jit cache miss — the chain retraced)")
    if t_full <= 0:
        failures.append(f"full step non-positive: {t_full * 1e3:.3f} ms")
    elif not 0.1 <= acct / t_full <= 10.0:
        # an order of magnitude each way: the check catches structural
        # breakage (a stage timing garbage, a chain folded away),
        # not noise — a contended 1-core box was measured swinging the
        # ratio 0.28–0.42 run to run on the tiny model
        failures.append(
            f"chain self-check failed: sum of stages {acct * 1e3:.2f} ms "
            f"vs full step {t_full * 1e3:.2f} ms (ratio "
            f"{acct / t_full:.2f} outside [0.1, 10])")
    snap = registry().snapshot()
    gauges = snap.get("gauges", {})
    missing = [k for k in ("profile/stage_ms/full_train_step_donated",
                           "profile/self_check_ratio") if k not in gauges]
    if missing:
        failures.append(f"obs registry gauges missing: {missing}")
    if failures:
        for f in failures:
            print(f"CHECK FAIL: {f}", file=sys.stderr)
        raise SystemExit(1)
    print(f"CHECK OK: {len(stage_ms)} stages, zero timed-pass recompiles, "
          f"self-check ratio {acct / t_full:.2f}", flush=True)


def summarize_trace(trace_dir: str, top: int = 15) -> None:
    """Parse the newest xplane.pb under ``trace_dir`` and print device
    time grouped by named scope AND by HLO op category — the loop-free
    attribution path (works wherever the profiler captures a device
    timeline; no TensorFlow dependency)."""
    import glob
    import os

    from mx_rcnn_tpu.utils.xplane import (category_of, parse_xspace,
                                          summarize_device_time)

    pbs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                        "*", "*.xplane.pb")),
                 key=os.path.getmtime)
    if not pbs:
        print("no xplane.pb found under trace dir", file=sys.stderr)
        return
    # parse once: the pure-Python protobuf walk dominates, and both
    # groupings read the same planes
    planes = parse_xspace(pbs[-1])
    for title, key in (("named scope", None), ("HLO op class", category_of)):
        summary = summarize_device_time(planes, key=key)
        for plane, groups in summary.items():
            total = sum(groups.values())
            if not groups or not total:
                continue
            if title == "named scope" and set(groups) == {"(unscoped)"}:
                # XLA:CPU events carry no op_name/tf_op metadata — scope
                # attribution is a device-plane (TPU) feature; the op-class
                # table below always works
                print(f"-- {plane}: no scope metadata in this trace "
                      f"(XLA:CPU); see the op-class table")
                continue
            print(f"-- {plane} by {title} (total {total:.2f} ms over the "
                  f"traced steps)")
            for g, ms in list(groups.items())[:top]:
                print(f"   {g:<42s} {ms:9.3f} ms  {100 * ms / total:5.1f}%")


if __name__ == "__main__":
    main()
