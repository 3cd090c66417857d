"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the training main path once, through the entry points a
user calls, at the full width of the flagship configuration: ResNet-101, 81
classes, per-chip batch 2, the 608x1024 bucket, bf16 activations, pre-NMS
6000 -> post-NMS 2000, 128 ROIs/image (BASELINE.json config 5).  Weights
are random, made from a seed; the dataset is generated from a seed.

  ``tools.train`` (parse_args -> config_from_args -> train_net) on the
  default input plane (streaming loader + staging thread) for one epoch of
  8 optimizer steps, every step's loss finite
  -> the snapshotter's epoch checkpoint, verified by manifest + SHA-256
  -> that checkpoint loaded into a ``Predictor``; ONE test-mode batch
     through ``Predictor.raw`` + the jitted postprocess; finite scores
  -> evidence the NMS kernel ran compiled: 'auto' resolves to 'pallas' at
     the recipe's proposal shape, the compiled kernel's keep masks equal the
     jnp sweep's at K=6144 and K=12032, and the compiled train step's HLO
     holds a ``tpu_custom_call``
  -> the grouped products' kernels (``ops/gmm_pallas.py``), compiled at the
     language-model cell's shapes, against ``lax.ragged_dot``: the held
     experts' output and the cotangents of ``x``, ``w_up``, ``w_down``.

Every phase failure is fatal.  With no TPU the script exits non-zero before
compiling anything and prints no result.  Stdout is two JSON lines: the
run's record (``{"smoke_record": {...}}`` — argv, versions, losses, cache
directory, ``peak_bytes_in_use``, kernel evidence; its timings are smoke
timings, one run with compile included, not metrics), then as the LAST
line exactly ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py          # through the chip tool; ~3 min cold
"""

from __future__ import annotations

import ast
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Sequence


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class _Events:
    """The ``run_record`` hook of ``train_net``: keeps the fit loop's
    structured events with the host clock at which each arrived."""

    def __init__(self):
        self.rows: List = []

    def event(self, kind: str, **fields) -> None:
        self.rows.append((time.perf_counter(), kind, fields))

    def of(self, kind: str) -> List:
        return [(t, f) for t, k, f in self.rows if k == kind]


def chip_train_argv(workdir: str, num_devices: int = 1) -> List[str]:
    """The trainer command line of the chip run: the flagship recipe on a
    generated 81-class set sized for 8 optimizer steps in one epoch."""
    root = os.path.join(workdir, "data")
    return [
        "--network", "resnet101", "--dataset", "synthetic_stream",
        "--root_path", root,
        "--dataset_path", os.path.join(root, "synthetic_stream"),
        "--dataset_kw",
        repr({"num_images": 16 * num_devices, "image_size": (600, 1000)}),
        "--prefix", os.path.join(workdir, "model", "smoke"),
        "--end_epoch", "1", "--frequent", "1", "--no_flip", "--seed", "0",
        "--batch_images", "2", "--num_devices", str(num_devices),
        # random ResNet-101 weights under frozen identity BN start at a
        # loss in the hundreds; the recipe's 1e-3 with its elementwise
        # clip of 5 diverges from there within ten steps
        "--lr", "1e-5",
        "--set", "bucket__scale=600", "--set", "bucket__max_size=1000",
        "--set", "bucket__shapes=((608, 1024), (1024, 608))",
        "--set", "train__rpn_pre_nms_top_n=6000",
    ]


def _sweep_parity(k: int, compiled: bool) -> Dict:
    """Keep masks of the Pallas sweep vs the jnp sweep (the oracle) on ``k``
    seeded score-sorted boxes whose last slots are padding, one image and a
    vmapped pair (the train step's form).  ``compiled`` runs the kernel
    through Mosaic; otherwise the caller asked for the interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops.nms import _suppression_sweep
    from mx_rcnn_tpu.ops.nms_pallas import suppression_sweep_pallas

    def boxes(seed):
        rng = np.random.RandomState(seed)
        cx, cy = rng.uniform(0, 1024, k), rng.uniform(0, 608, k)
        w = rng.choice([32, 64, 128, 256, 512], k) * rng.uniform(.7, 1.4, k)
        h = rng.choice([32, 64, 128, 256, 512], k) * rng.uniform(.7, 1.4, k)
        b = np.stack([np.clip(cx - w / 2, 0, 1023),
                      np.clip(cy - h / 2, 0, 607),
                      np.clip(cx + w / 2, 0, 1023),
                      np.clip(cy + h / 2, 0, 607)], axis=1)
        return b.astype(np.float32), np.arange(k) < k - k // 43

    def kernel(b, a):
        return suppression_sweep_pallas(b, a, 0.7, 128,
                                        interpret=not compiled)

    def oracle(b, a):
        return _suppression_sweep(b, a, 0.7, 256)

    b, a = (jnp.asarray(np.stack(x)) for x in zip(boxes(k), boxes(k + 1)))
    got_one = np.asarray(jax.jit(kernel)(b[0], a[0]))
    got_two = np.asarray(jax.jit(jax.vmap(kernel))(b, a))
    want = np.asarray(jax.jit(jax.vmap(oracle))(b, a))
    _check(np.array_equal(got_one, want[0]) and np.array_equal(got_two, want),
           f"Pallas NMS sweep disagrees with the jnp sweep at K={k} "
           f"({int((got_two != want).sum())} of {want.size} decisions)")
    _check(0 < want.sum() < a.sum(), f"degenerate NMS parity input at K={k}")
    return {"k": k, "kept": int(want.sum()), "equal": True}


def _grouped_parity(compiled: bool) -> Dict:
    """The held experts' layer with the grouped products' Pallas kernels
    against the same layer over ``lax.ragged_dot``: output and the
    cotangents of ``x``, ``w_up`` and ``w_down``, bfloat16 rows, largest
    gap over the oracle's largest value.  ``compiled`` runs the kernels
    through Mosaic at the language-model cell's shapes (16384 tokens,
    12288 rows, 2688 x 1856, 8 of 128 experts held); otherwise in the
    interpreter at a small size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops import moe

    tokens, hidden, width, count, experts, top_k = (
        (16384, 2688, 1856, 8, 128, 6) if compiled else (256, 80, 48, 4, 16, 2))
    keys = jax.random.split(jax.random.PRNGKey(35), 5)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
    w_up = 0.02 * jax.random.normal(keys[1], (count, hidden, width))
    w_down = 0.02 * jax.random.normal(keys[2], (count, width, hidden))
    ct = jax.random.normal(keys[3], (tokens, hidden))
    idx, weight = moe.route(
        x, 0.02 * jax.random.normal(keys[4], (hidden, experts)), 0.0, top_k,
        2.5, True)
    routed = moe.held_assignments(
        idx, weight, (0, count),
        moe.row_capacity(tokens, top_k, experts, count, 2.0))

    def kernels(*a):
        return moe.held_experts(a[0], routed, a[1], a[2],
                                interpret=not compiled)

    def oracle(x, w_up, w_down):
        keep = routed.valid[:, None]

        def product(rows, w):
            return jax.lax.ragged_dot(rows, w.astype(rows.dtype),
                                      routed.group_sizes,
                                      preferred_element_type=jnp.float32)

        h = product(jnp.where(keep, x[routed.token], 0), w_up)
        y = product(jnp.square(jax.nn.relu(h)).astype(x.dtype), w_down)
        y = jnp.where(keep, y * routed.weight[:, None], 0.0)
        return jnp.zeros(x.shape, jnp.float32).at[routed.token].add(y)

    def both(fn):
        out, pull = jax.vjp(fn, x, w_up, w_down)
        return (out,) + pull(ct)

    got, want = jax.jit(lambda: both(kernels))(), jax.jit(
        lambda: both(oracle))()
    gaps = {}
    for name, g, w in zip(("y", "d_x", "d_w_up", "d_w_down"), got, want):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        gaps[name] = float(np.abs(g - w).max() / np.abs(w).max())
    _check(0 < int(routed.valid.sum()) < routed.valid.size
           and int(routed.overflow) == 0, "degenerate grouped parity input")
    _check(max(gaps.values()) < 0.02,
           f"grouped product kernels disagree with lax.ragged_dot: {gaps}")
    return {"rows": int(routed.valid.size), "held": int(routed.valid.sum()),
            "relative_gap": gaps}


def run_smoke(train_argv: Sequence[str], *, expect_platform: str,
              parity_sizes: Sequence[int]) -> Dict:
    """Drive the main path once and return the result record.

    ``train_argv``: the ``tools.train`` command line (its ``--prefix`` and
    data paths say where the run writes).  ``expect_platform``: the JAX
    platform this run is for — anything else found is refused before the
    first compile.  It also decides what the kernel evidence must show:
    on ``'tpu'`` the NMS kernel runs compiled and the step holds a Mosaic
    custom call; on any other platform (the tier-1 rehearsal) the kernel
    runs interpreted at ``parity_sizes`` and 'auto' must be the jnp sweep.
    """
    t_start = time.perf_counter()
    from mx_rcnn_tpu import native, runtime

    # host library first: its build is the one child process this program
    # starts (g++), and it has exited before JAX touches the device
    native_backend = native.backend()

    import jax

    dev = runtime.device_summary()
    if dev["platform"] != expect_platform:
        raise SystemExit(
            f"chip_smoke: this run is for platform {expect_platform!r} but "
            f"JAX found {dev['platform']!r} ({dev['device_kind']} x"
            f"{dev['device_count']}) — refusing before any compile")
    on_tpu = expect_platform == "tpu"
    cache_dir = runtime.enable_compile_cache()
    runtime.log_runtime(cache_dir)

    from importlib import metadata

    import numpy as np

    versions = {pkg: metadata.version(pkg)
                for pkg in ("jax", "jaxlib", "libtpu")}

    # ---- kernel: compiled parity against the oracle -----------------------
    parity = [_sweep_parity(k, compiled=on_tpu) for k in parity_sizes]
    grouped = _grouped_parity(compiled=on_tpu)

    # ---- train: the normal entry points -----------------------------------
    from mx_rcnn_tpu.ft.integrity import latest_valid_checkpoint
    from mx_rcnn_tpu.ops.nms import _resolve_backend
    from mx_rcnn_tpu.tools import train as train_tool

    args = train_tool.parse_args(list(train_argv))
    cfg = train_tool.config_from_args(args)
    dataset_kw = ast.literal_eval(args.dataset_kw) if args.dataset_kw else {}
    pre = cfg.train.rpn_pre_nms_top_n
    nms_backend = _resolve_backend(None, pre + (-pre) % 256, 256)
    _check(nms_backend == ("pallas" if on_tpu else "jnp"),
           f"NMS 'auto' resolved to {nms_backend!r} on {expect_platform} at "
           f"the recipe's {pre} pre-NMS boxes")

    events = _Events()
    t_train = time.perf_counter()
    state = train_tool.train_net(
        cfg, prefix=args.prefix, end_epoch=args.end_epoch, lr=args.lr,
        lr_step=args.lr_step, num_devices=args.num_devices,
        frequent=args.frequent, seed=args.seed, dataset_kw=dataset_kw,
        run_record=events)
    t_train_end = time.perf_counter()
    logs = events.of("log")
    losses = [f["loss"] for _, f in logs]
    _check(len(losses) >= 8, f"trainer ran {len(losses)} steps, need >= 8")
    _check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    _check(int(jax.device_get(state.step)) == len(losses),
           "state.step disagrees with the logged steps")
    t_epoch = events.of("epoch_start")[0][0]
    state_devices = max(len(leaf.sharding.device_set)
                        for leaf in jax.tree.leaves(state.params))
    _check(state_devices == args.num_devices,
           f"state spans {state_devices} device(s), asked for "
           f"{args.num_devices}")

    ref = latest_valid_checkpoint(args.prefix)
    _check(ref is not None and ref.kind == "epoch"
           and ref.epoch == args.end_epoch and ref.step == len(losses),
           f"no verified epoch-{args.end_epoch} checkpoint under "
           f"{args.prefix}: {ref}")

    # ---- test mode: the checkpoint through Predictor.raw + postprocess ----
    import jax.numpy as jnp

    from mx_rcnn_tpu.core.tester import (Predictor, _postprocess_batch,
                                         tiled_bbox_stats)
    from mx_rcnn_tpu.data import TestLoader, load_gt_roidb
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.utils.checkpoint import load_param

    t0 = time.perf_counter()
    model = build_model(cfg)
    params, batch_stats = load_param(args.prefix, ref.epoch)
    predictor = Predictor(model, {"params": params,
                                  "batch_stats": batch_stats}, cfg)
    _, test_roidb = load_gt_roidb(cfg, training=False, **dataset_kw)
    batch, _, scales = next(iter(TestLoader(
        test_roidb, cfg, batch_images=cfg.test.batch_images, num_workers=0)))
    rois, roi_valid, cls_prob, deltas = predictor.raw(batch.images,
                                                      batch.im_info)
    stds, means = tiled_bbox_stats(cfg, cfg.num_classes)
    boxes_b, scores_b, keep_b = map(np.asarray, _postprocess_batch(
        rois, roi_valid, cls_prob, deltas, jnp.asarray(batch.im_info),
        jnp.asarray(scales), stds, means, nms_thresh=cfg.test.nms,
        score_thresh=cfg.test.score_thresh))
    n, r, c = (cfg.test.batch_images, cfg.test.rpn_post_nms_top_n,
               cfg.num_classes)
    _check(scores_b.shape == (n, r, c) and boxes_b.shape == (n, r, 4 * c)
           and keep_b.shape == (n, c, r),
           f"test-mode shapes {scores_b.shape} {boxes_b.shape} "
           f"{keep_b.shape}, expected ({n}, {r}, {c})")
    _check(bool(np.isfinite(scores_b).all() and np.isfinite(boxes_b).all()),
           "non-finite test-mode scores or boxes")
    _check(bool(np.asarray(roi_valid).any()), "test mode: no valid proposal")
    test_s = time.perf_counter() - t0

    # ---- the compiled single-device step holds the Mosaic call ------------
    # the same step fit() jitted, lowered again for the same shapes: the
    # compile is a read from the cache the run just wrote
    custom_calls = None
    hlo_s = None
    if args.num_devices == 1:
        from mx_rcnn_tpu.core.optim import make_optimizer
        from mx_rcnn_tpu.core.train import make_train_step
        from mx_rcnn_tpu.data.synthetic import make_batch

        t0 = time.perf_counter()
        tx = make_optimizer(cfg, state.params, len(losses), base_lr=args.lr,
                            lr_step=args.lr_step)
        like_loader = make_batch(cfg, cfg.train.batch_images,
                                 *cfg.bucket.shapes[0], raw=True)
        step = jax.jit(make_train_step(model, cfg, tx), donate_argnums=(0,))
        hlo = step.lower(state, like_loader,
                         jax.random.PRNGKey(args.seed)).compile().as_text()
        custom_calls = hlo.count("tpu_custom_call")
        hlo_s = time.perf_counter() - t0
        _check((custom_calls > 0) == on_tpu,
               f"compiled train step holds {custom_calls} tpu_custom_call(s) "
               f"on {expect_platform}")

    mem = [d.memory_stats() for d in jax.local_devices()]
    peak = [m["peak_bytes_in_use"] if m else None for m in mem]
    in_use = [m["bytes_in_use"] if m else None for m in mem]
    if on_tpu:
        _check(all(b and b > 0 for b in in_use[:args.num_devices])
               and bool(peak[0]),
               f"memory_stats per device: in use {in_use}, peak {peak}")

    return {
        "ok": True,
        "device": {"platform": dev["platform"], "kind": dev["device_kind"],
                   "count": dev["device_count"]},
        **dev,
        "versions": versions,
        "native_backend": native_backend,
        "compile_cache_dir": cache_dir,
        "train_argv": list(train_argv),
        "steps": len(losses),
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "checkpoint": {"epoch": ref.epoch, "step": ref.step,
                       "verified": True},
        "state_devices": state_devices,
        "nms_backend": nms_backend,
        "nms_parity": parity,
        "grouped_parity": grouped,
        "tpu_custom_calls_in_step": custom_calls,
        "test_mode": {"scores_shape": list(scores_b.shape),
                      "detections_kept": int(keep_b.sum()),
                      "max_score": float(scores_b.max())},
        # smoke timings — one run, first step = trace + compile (or cache
        # read) + first batch + run; not metrics
        "compile_s": round(logs[0][0] - t_epoch, 2),
        "rest_steps_wall_s": round(logs[-1][0] - logs[0][0], 2),
        "train_total_s": round(t_train_end - t_train, 2),
        "test_mode_s": round(test_s, 2),
        "step_hlo_check_s": None if hlo_s is None else round(hlo_s, 2),
        "total_s": round(time.perf_counter() - t_start, 2),
        "peak_bytes_in_use": peak[0],
        "peak_bytes_in_use_per_device": peak,
        "bytes_in_use_per_device": in_use,
    }


def verdict(result: Dict) -> Dict:
    """The object of the last stdout line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count`` as JAX reports them) — the shape the
    driver's chip check parses.  Everything else the run learned is the
    record printed on the line before it."""
    dev = result["device"]
    return {"ok": bool(result["ok"]),
            "device": {"platform": str(dev["platform"]),
                       "kind": str(dev["kind"]), "count": int(dev["count"])}}


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        result = run_smoke(chip_train_argv(workdir), expect_platform="tpu",
                           parity_sizes=(6144, 12032))
    print(json.dumps({"smoke_record": result}), flush=True)
    print(json.dumps(verdict(result)), flush=True)
    return 0


if __name__ == "__main__":
    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    sys.exit(main())
