"""The training driver: epochs of the jitted step + logging + checkpoints.

Reference: ``rcnn/core/module.py — MutableModule.fit`` (the train loop:
forward_backward → update → metric update → batch_end_callback →
epoch_end_callback) plus ``rcnn/core/callback.py — Speedometer`` (imgs/sec
every ``frequent`` batches) and ``do_checkpoint`` (per-epoch save).

TPU-native: the loop body is ONE jitted XLA program (single device) or one
SPMD program over a mesh (``parallel/dp.py``); metrics come back as device
scalars and are only synced to host at log time so the async dispatch
pipeline stays full between logs.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.core.train import Batch, TrainState, make_train_step
from mx_rcnn_tpu.models.faster_rcnn import FasterRCNN
from mx_rcnn_tpu.obs import trace as obs_trace

logger = logging.getLogger("mx_rcnn_tpu")


class Speedometer:
    """imgs/sec + running metric means every ``frequent`` batches
    (ref ``rcnn/core/callback.py — Speedometer``).

    Call once per batch; pass the averaged metrics on log batches (the fit
    loop aligns those with its metric window) and it prints samples/sec over
    the batches elapsed since the previous log line.

    ``registry`` (an ``obs/metrics.py`` Registry): each log window also
    publishes ``train.samples_per_sec`` and the windowed metric means
    (``train.metric.<name>`` gauges) into the shared registry — the
    stdout line itself stays byte-identical to the reference port
    (pinned by ``tests/test_obs.py``).
    """

    def __init__(self, batch_size: int, frequent: int = 20,
                 log: Callable[[str], None] = None, registry=None):
        self.batch_size = batch_size
        self.frequent = frequent
        self.log = log or logger.info
        self.registry = registry
        self._tic = time.perf_counter()
        self._since = 0

    def reset(self) -> None:
        """Call at epoch start so the first window excludes checkpoint-save
        and summary time from the previous epoch."""
        self._tic = time.perf_counter()
        self._since = 0

    def __call__(self, epoch: int, nbatch: int,
                 metrics: Dict[str, float]) -> None:
        self._since += 1
        if not metrics:
            return
        elapsed = time.perf_counter() - self._tic
        speed = self._since * self.batch_size / max(elapsed, 1e-9)
        if self.registry is not None:
            self.registry.set_gauge("train.samples_per_sec", speed)
            for k, v in metrics.items():
                self.registry.set_gauge(f"train.metric.{k}", float(v))
        parts = ", ".join(f"{k}={v:.4f}" for k, v in metrics.items())
        self.log(f"Epoch[{epoch}] Batch [{nbatch}] "
                 f"Speed: {speed:.2f} samples/sec, {parts}")
        self._tic = time.perf_counter()
        self._since = 0


# unique loop sentinel: the device-cache path legitimately yields None
# batches, so exhaustion cannot be signalled with None
_END = object()


def _accum_iter(batch_iter, grad_accum: int):
    """Group a loader iterator into accumulation batches: every yield
    stacks ``grad_accum`` consecutive loader batches into leaves shaped
    ``(grad_accum, N, ...)``; a partial trailing group is dropped (the
    epoch holds ``len(loader) // grad_accum`` optimizer steps)."""
    from mx_rcnn_tpu.parallel.dp import stack_microbatches

    while True:
        group = []
        for _ in range(grad_accum):
            b = next(batch_iter, _END)
            if b is _END:
                return
            group.append(b)
        yield stack_microbatches(group)


def _mean_metrics(window: List[Dict]) -> Dict[str, float]:
    """Host-side mean of a window of device metric dicts (one sync)."""
    if not window:
        return {}
    window = jax.device_get(window)
    keys = window[0].keys()
    return {k: float(np.mean([m[k] for m in window])) for k in keys}


def _sync_metrics(window: List[Dict], step: int) -> Dict[str, float]:
    """The loop's one sync, under a ``train.sync`` span that carries the
    global ``step`` it follows and the count ``n`` of steps it fetched.
    One path whether spans are collected or not: start every value's copy
    to the host (as ``jax.device_get`` does first, so the earlier steps'
    values cross while the last step still runs), wait for the last step,
    take the means.  With spans collected the span also carries
    ``fetch_us``, its time after the last step's result was ready: the
    span's end less the fetch is the moment the host learned that step
    ``step`` had left the device, the one point at which the host's clock
    and the device's are tied (``benchmark/hostspans.py``)."""
    with obs_trace.span("train.sync", step=step, n=len(window)) as sp:
        for leaf in jax.tree.leaves(window):
            leaf.copy_to_host_async()
        jax.block_until_ready(window[-1])
        if sp is None:
            return _mean_metrics(window)
        t_ready = time.monotonic_ns()
        avg = _mean_metrics(window)
        sp.args["fetch_us"] = (time.monotonic_ns() - t_ready) / 1e3
        return avg


def fit(
    model: FasterRCNN,
    cfg: Config,
    state: TrainState,
    tx,
    train_loader,
    num_epochs: int,
    key: jax.Array,
    begin_epoch: int = 0,
    prefix: Optional[str] = None,
    frequent: Optional[int] = None,
    mesh=None,
    mode: str = "e2e",
    epoch_end_callback: Optional[Callable[[int, TrainState], None]] = None,
    stop_flag: Optional[Callable[[], bool]] = None,
    device_cache: bool = False,
    step_callback: Optional[Callable[[int], None]] = None,
    run_record=None,
    grad_accum: int = 1,
    multiproc: bool = False,
    data_cursor: Optional[Dict] = None,
) -> TrainState:
    """Run ``begin_epoch .. num_epochs`` epochs; checkpoint per epoch.

    ``mesh``: a ``jax.sharding.Mesh`` (1-D ``('data',)`` or hierarchical
    ``('dcn', 'ici')`` — see ``parallel.dp.device_mesh``) enables
    data-parallel SPMD (the kvstore='device' replacement); None =
    single-device jit.
    ``mode``: 'e2e' | 'rpn' | 'rcnn' (alternate-training stages).
    ``key`` is the base RNG; the step folds in ``state.step`` so resuming
    from a checkpoint replays the identical sample stream.
    ``stop_flag``: polled after every step; when it returns True the loop
    saves a mid-epoch interrupt checkpoint (``<prefix>-interrupt.ckpt``)
    and returns — the preemption path (SIGTERM on preemptible TPUs).
    ``step_callback``: host-side hook called with the global step after
    every executed step (fault injection — ``ft/faults.py`` — and test
    instrumentation; adds no device sync).
    Checkpoints go through the ``ft/snapshot.py`` snapshotter: the
    training thread pays only the ``jax.device_get``; serialization +
    durable write + manifest commit + retention GC happen on a background
    writer thread (``cfg.ft.async_snapshots=false`` restores inline
    writes).  The interrupt save is flushed before the loop returns.
    ``run_record``: an ``obs/runrec.py`` RunRecord — the loop appends
    epoch/log/snapshot events to its ``events.jsonl`` (None = no record).
    With ``cfg.obs.enabled`` the loop also records step time, data-wait
    fraction, loss EMA and lowering counts into the process metrics
    registry, and ``cfg.obs.profile_at_step`` opens an on-demand
    profiler window (``obs/profiler.py``); all of it is absent from the
    hot path when disabled (the default — overhead pinned by
    ``tests/test_obs.py``).  The loop body is covered end to end by
    ``obs/trace.py`` spans on this thread — ``train.data_wait``,
    ``train.dispatch``, ``train.hooks`` (profiler window,
    ``step_callback``, ``stop_flag``), and at a log step ``train.sync``
    and ``train.log`` — each with ``step=``, the global step it belongs
    to; the first step's ``train.dispatch`` holds the step's trace,
    lowering and compile or cache read (``setup.first_step_s``); before
    the loop ``setup.fit`` covers the prologue (the jit wrap, the state's
    copy to the device, the snapshotter, the stager's start).  With span
    collection off each is one module-flag read.
    ``device_cache``: stage the loader's epoch in HBM once and gather each
    step's batch on device (``data/device_cache.py``) — for RAM/HBM-scale
    datasets on hosts or links too slow to stream per step.  Shuffling is
    IMAGE-granular (r5): each epoch re-groups images into new batches via
    an on-device permutation, matching the streaming loader's in-bucket
    semantics (deterministic from ``key`` and the epoch number, so resume
    stays step-exact; ``shuffle=False`` loaders run bit-identical to
    streaming).  Composes with a mesh: the epoch shards over the data
    axes and each device regroups within its own shard (disclosed
    residual: images don't migrate across devices between epochs —
    ``parallel.dp.make_dp_cached_step``).  Limit: requires a
    single-bucket dataset.
    Mid-epoch RESUME is driven by ``state.step`` alone: if the incoming
    state is ``skip`` steps past ``begin_epoch``'s start, the first epoch
    skips its first ``skip`` batches; the deterministic per-epoch shuffle
    (``set_epoch``) plus the step-folded RNG make the continued run
    bit-identical to an uninterrupted one.
    ``grad_accum``: microbatches accumulated per optimizer step (the
    elastic shrink lever — ft/elastic.py): each step consumes
    ``grad_accum`` consecutive loader batches, ``steps_per_epoch`` and
    ``state.step`` count OPTIMIZER steps, so the LR schedule, the
    step↔epoch mapping and the resume math are accumulation-invariant.
    Not composable with ``device_cache`` (the HBM epoch cache gathers one
    batch per step by construction).
    ``multiproc``: the mesh spans multiple ``jax.distributed`` processes
    (``parallel/multihost.py``): state replication and batch assembly go
    through ``multihost_utils`` (every process feeds only its local image
    slice of the deterministic global batch — decoded by the loader's own
    row shard when one is set, sliced host-side otherwise), and only
    process 0 writes checkpoints (state is replicated, so host 0 holds
    the full values).
    ``data_cursor``: the checkpoint manifest's data-shard cursor (PR 6
    recorded it; ``tools/train.py --resume auto`` now consumes it) —
    ``{"loader_batch_images": N}`` names the batch size of the run that
    WROTE the checkpoint, so a loader with ``resume_at`` (the streaming
    loader) can replay that run's plan and continue the epoch
    exactly-once even across a topology change.
    With ``cfg.data.staging`` (the default), batches are double-buffered
    host→device by a background thread (``data/staging.py``): the next
    batch's assembly + ``device_put`` overlap the in-flight step, so
    ``train.data_wait_frac`` goes to ~0 without requiring the dataset to
    fit in HBM.  Skipped automatically for the device-cache path (no
    host batches) and multiproc (global-array assembly is collective and
    stays on the step thread).
    """
    # ``setup.fit``: from here to the loop's first ``train.data_wait``
    t_fit = time.perf_counter() if obs_trace.enabled() else None
    frequent = cfg.default.frequent if frequent is None else frequent
    # -- observability wiring (cfg.obs.enabled; docs/OBSERVABILITY.md) --
    # rec stays None when disabled, and every obs touch below hides
    # behind a `rec is None` branch — the disabled hot path is a local
    # None-check (cost pinned by tests/test_obs.py)
    rec = None
    prof = None
    lowerings = None
    loss_ema = None
    if getattr(cfg, "obs", None) is not None and cfg.obs.enabled:
        from mx_rcnn_tpu.obs.metrics import LoweringCounter, registry

        rec = registry()
        lowerings = LoweringCounter()
        lowerings.__enter__()
        if cfg.obs.profile_at_step > 0:
            from mx_rcnn_tpu.obs.profiler import StepProfiler

            pdir = cfg.obs.profile_dir or os.path.join(
                run_record.dir if run_record is not None else "obs_trace",
                "profile")
            prof = StepProfiler(pdir, cfg.obs.profile_at_step,
                                cfg.obs.profile_steps)
    grad_accum = max(int(grad_accum), 1)
    if device_cache and (grad_accum > 1 or multiproc):
        raise ValueError(
            "device_cache composes with neither grad_accum nor multiproc "
            "(the HBM epoch cache gathers exactly one batch per step, "
            "single process) — use the streaming loader for elastic runs")
    cache = None
    # host→device staging placement (data/staging.py): set by the two
    # single-process branches below; stays None for the device-cache
    # path (no host batches to stage) and multiproc (collective global
    # assembly must run on the step thread)
    stage_place = None
    if device_cache:
        import jax.numpy as jnp

        from mx_rcnn_tpu.data.device_cache import (build_caches,
                                                   make_cached_step)

        on_mesh = mesh is not None and mesh.size > 1
        caches = build_caches(train_loader,
                              mesh=mesh if on_mesh else None)
        if len(caches) != 1:
            raise ValueError(
                f"device_cache needs a single-bucket dataset "
                f"(got {len(caches)} buckets); use the streaming loader")
        cache = caches[0]
        shuffle = getattr(train_loader, "shuffle", True)
        logger.info("device cache: %d batches staged in HBM (%.0f MB%s)",
                    cache.num_batches, cache.nbytes / 1e6,
                    f", sharded over {mesh.size} devices" if on_mesh else "")
        if on_mesh:
            from mx_rcnn_tpu.parallel.dp import (make_dp_cached_step,
                                                 replicate)

            cstep = make_dp_cached_step(model, cfg, tx, mesh,
                                        cache.num_batches, shuffle=shuffle,
                                        mode=mode)
            state = replicate(state, mesh)
        else:
            cstep = jax.jit(
                make_cached_step(
                    make_train_step(model, cfg, tx, mode=mode),
                    cache.num_batches, shuffle=shuffle),
                donate_argnums=(0, 2))
        # the gather index IS the global step: restores (incl. mid-epoch
        # interrupts) resume the exact batch sequence with no bookkeeping.
        # int() is LOAD-BEARING: on CPU, device_get returns a zero-copy
        # view of the step buffer and jnp.asarray keeps sharing it — the
        # idx would alias state.step, and cstep donates BOTH (argnums 0
        # and 2), double-donating one buffer → nondeterministic training
        # (found by the ft crashloop; pinned by
        # test_cached_fit_is_deterministic in tests/test_ft.py)
        idx_box = [jnp.asarray(int(jax.device_get(state.step)), jnp.int32)]

        def run_step(state, batch: Batch):
            state, idx_box[0], metrics = cstep(state, cache.data,
                                               idx_box[0], key)
            return state, metrics
    elif mesh is not None and mesh.size > 1:
        from mx_rcnn_tpu.parallel.dp import (
            make_dp_train_step, replicate, shard_accum_batch, shard_batch)

        step_fn = make_dp_train_step(model, cfg, tx, mesh, mode=mode,
                                     grad_accum=grad_accum)
        if multiproc:
            # the mesh spans processes: device_put cannot address remote
            # devices, so replication and batch assembly go through
            # multihost_utils (parallel/multihost.py).  Every process
            # contributes only its own image slice (rows [pid*per,
            # (pid+1)*per) of the image axis) — identical math to
            # single-process DP.  With a loader row shard (the r7
            # sharded input plane — tools/train.py sets it from the
            # process topology) the batch IS the local slice already
            # and each process decoded only 1/N of the epoch; without
            # one, every process decodes the full batch and slices it
            # host-side (the pre-r7 fallback).
            from mx_rcnn_tpu.parallel import multihost

            state = multihost.replicate_global(jax.device_get(state), mesh)

            def run_step(state, batch: Batch):
                # read the shard LIVE (set_shard may remap between
                # epochs): a sharded loader already yields local rows
                local = (batch
                         if getattr(train_loader, "shard", None) is not None
                         else multihost.local_image_slice(
                             batch, accum=grad_accum > 1))
                gbatch = multihost.global_batch(local, mesh,
                                                accum=grad_accum > 1)
                return step_fn(state, gbatch, key)
        else:
            state = replicate(state, mesh)
            place = (shard_batch if grad_accum <= 1 else shard_accum_batch)
            stage_place = lambda b: place(b, mesh)  # noqa: E731

            def run_step(state, batch: Batch):
                # a staged batch is already mesh-placed; device_put with
                # an identical sharding is a no-op, so one place() serves
                # both the staged and unstaged paths
                return step_fn(state, place(batch, mesh), key)
    else:
        from mx_rcnn_tpu.parallel.dp import own_leaves

        base = jax.jit(make_train_step(model, cfg, tx, mode=mode,
                                       grad_accum=grad_accum),
                       donate_argnums=(0,))
        # a restored state arrives with numpy leaves (views of one
        # msgpack buffer); the jitted step DONATES arg 0 — force
        # private jax-owned copies first (parallel/dp.py — own_leaves)
        state = own_leaves(state)
        stage_place = jax.device_put

        def run_step(state, batch: Batch):
            return base(state, batch, key)

    n_dev = mesh.size if mesh is not None else 1
    speedo = Speedometer(cfg.train.batch_images * n_dev * grad_accum,
                         frequent, registry=rec)
    # OPTIMIZER steps per epoch: with accumulation each step consumes
    # grad_accum loader batches (a partial trailing group is dropped —
    # the effective batch of every optimizer step stays on-recipe)
    if grad_accum > 1 and len(train_loader) < grad_accum:
        raise ValueError(
            f"grad_accum={grad_accum} exceeds the loader's "
            f"{len(train_loader)} batches/epoch — every epoch would run "
            f"ZERO optimizer steps (and 'complete' without training); "
            f"the dataset is too small for this topology")
    steps_per_epoch = (len(train_loader) // grad_accum if grad_accum > 1
                       else len(train_loader))
    done_steps = int(jax.device_get(state.step))
    data_cfg = getattr(cfg, "data", None)
    if data_cfg is None or not data_cfg.staging:
        stage_place = None
    stager = None
    snap = None
    if prefix is not None and not (multiproc and jax.process_index() != 0):
        from mx_rcnn_tpu.ft.snapshot import make_snapshotter
        from mx_rcnn_tpu.utils.checkpoint import make_topology

        topo = make_topology(
            n_dev, num_processes=jax.process_count() if multiproc else 1,
            grad_accum=grad_accum, batch_images=cfg.train.batch_images)
        snap = make_snapshotter(prefix, cfg, steps_per_epoch, topology=topo)
    try:
        for epoch in range(begin_epoch, num_epochs):
            if hasattr(train_loader, "set_epoch"):
                train_loader.set_epoch(epoch)  # resume-exact shuffle order
            # mid-epoch (preemption) resume: skip batches the restored state
            # already consumed; the deterministic shuffle replays the same
            # order
            skip = 0
            if epoch == begin_epoch and steps_per_epoch:
                skip = min(max(done_steps - epoch * steps_per_epoch, 0),
                           steps_per_epoch)
                if skip:
                    logger.info("Epoch[%d] resuming mid-epoch: skipping %d "
                                "consumed batches", epoch, skip)
            speedo.reset()
            window: List[Dict] = []
            epoch_metrics: List[Dict] = []
            t0 = time.perf_counter()
            nbatch = skip
            stop_requested = False
            if cache is not None:
                # batches gather on device from the staged epoch; the
                # resumed idx (== state.step) already accounts for the
                # skipped prefix
                batch_iter = iter([None] * (steps_per_epoch - skip))
            else:
                skip_b = skip * grad_accum  # loader batches, not opt steps
                loader_skips = hasattr(train_loader, "skip_next_batches")
                if skip_b and hasattr(train_loader, "resume_at"):
                    # streaming loader: position by the data cursor —
                    # the recording run's batch size lets the loader
                    # replay ITS plan, so the continued epoch is
                    # exactly-once even across an elastic topology
                    # change (docs/DATA.md; plain skip trims the
                    # CURRENT plan, which is only identical for the
                    # same topology).  images_consumed_in_epoch comes
                    # from state.step under the OLD topology
                    # (tools/train.py); the new-topology product below
                    # is the fallback for direct fit() callers and is
                    # only equal when the global batch is unchanged.
                    cur = data_cursor or {}
                    images = cur.get("images_consumed_in_epoch")
                    if images is None:
                        images = skip_b * train_loader.batch_images
                    train_loader.resume_at(
                        images, cur.get("loader_batch_images"))
                elif skip_b and loader_skips:
                    train_loader.skip_next_batches(skip_b)  # trims the order
                batch_iter = iter(train_loader)
                if skip_b and not loader_skips:
                    for _ in range(skip_b):  # fallback: decode-and-discard
                        next(batch_iter, None)
                if grad_accum > 1:
                    batch_iter = _accum_iter(batch_iter, grad_accum)
                if stage_place is not None:
                    from mx_rcnn_tpu.data.staging import DeviceStager

                    # double-buffer host→device: assembly + device_put of
                    # batch k+1 overlap step k (docs/DATA.md)
                    stager = DeviceStager(batch_iter, stage_place,
                                          depth=data_cfg.stage_depth,
                                          rec=rec, first_seq=skip + 1)
                    batch_iter = iter(stager)
            if run_record is not None:
                run_record.event("epoch_start", epoch=epoch, skip=skip,
                                 steps_per_epoch=steps_per_epoch)
            if t_fit is not None:
                obs_trace.complete("setup.fit",
                                   (time.perf_counter() - t_fit) * 1e3)
                t_fit = None
            while True:
                # the global step this iteration produces: every span of
                # the iteration carries it
                gstep = epoch * steps_per_epoch + nbatch + 1
                if rec is None:
                    batch = next(batch_iter, _END)
                else:
                    lowerings.mark_step(gstep)
                    t_wait = time.perf_counter()
                    with obs_trace.span("train.data_wait", step=gstep):
                        batch = next(batch_iter, _END)
                    wait_s = time.perf_counter() - t_wait
                if batch is _END:
                    break
                if rec is None:
                    state, metrics = run_step(state, batch)
                else:
                    with obs_trace.span("train.dispatch", step=gstep):
                        state, metrics = run_step(state, batch)
                    step_s = time.perf_counter() - t_wait
                    rec.inc("train.steps")
                    rec.observe("train.step_ms", step_s * 1e3)
                    rec.observe("train.data_wait_ms", wait_s * 1e3)
                    frac = wait_s / max(step_s, 1e-9)
                    rec.set_gauge("train.data_wait_frac", frac)
                    # the PER-STEP fraction as a distribution (percent
                    # scale for the log-bucket range): p50 of this is
                    # the honest data_wait_frac statistic — a ratio of
                    # independent wait/step percentiles is not
                    rec.observe("train.data_wait_frac_pct", 100.0 * frac,
                                lo=0.01, hi=1000.0)
                window.append(metrics)
                nbatch += 1
                with obs_trace.span("train.hooks", step=gstep):
                    if prof is not None:
                        m = metrics  # bind: the lambda must sync THIS step
                        prof.on_step(gstep,
                                     sync=lambda: jax.block_until_ready(m))
                    if step_callback is not None:
                        step_callback(gstep)
                    if stop_flag is not None and stop_flag():
                        stop_requested = True
                        # mid-epoch: save the step-exact interrupt state
                        # and leave.  On the epoch's LAST batch, fall
                        # through instead — the normal epoch end writes
                        # the (superseding) epoch checkpoint and the run
                        # stops cleanly at the boundary.
                        if nbatch < steps_per_epoch:
                            if snap is not None:
                                with obs_trace.span("train.snapshot",
                                                    kind="interrupt",
                                                    step=gstep):
                                    path = snap.save_interrupt(state)
                                if run_record is not None:
                                    run_record.event(
                                        "interrupt", epoch=epoch,
                                        nbatch=nbatch, path=path)
                                logger.info(
                                    "stop requested: saved interrupt "
                                    'checkpoint to "%s" (step %d) — rerun '
                                    "with --resume to continue", path,
                                    int(jax.device_get(state.step)))
                            else:
                                logger.info("stop requested: no prefix, "
                                            "state not saved")
                            return state
                if nbatch % frequent == 0:
                    avg = _sync_metrics(window, gstep)
                    with obs_trace.span("train.log", step=gstep) as sp:
                        # dropping the window frees n steps' device
                        # scalars, a millisecond of the log step's own
                        epoch_metrics.append(avg)
                        window = []
                        speedo(epoch, nbatch, avg)
                        if rec is not None:
                            loss = avg.get("loss")
                            if loss is not None:
                                a = cfg.obs.loss_ema
                                loss_ema = (
                                    loss if loss_ema is None
                                    else a * loss_ema + (1 - a) * loss)
                                rec.set_gauge("train.loss_ema", loss_ema)
                            rec.set_gauge("train.lowerings_total",
                                          lowerings.n)
                            if sp is not None:
                                # the registry's compile seconds as they
                                # stand at this edge: the first edge's
                                # value is what the start paid
                                sp.args["backend_compile_s"] = rec.counter(
                                    "compile.backend_s")
                        if run_record is not None:
                            run_record.event(
                                "log", epoch=epoch, nbatch=nbatch,
                                samples_per_sec=(
                                    None if rec is None
                                    else rec.gauge("train.samples_per_sec")),
                                **avg)
                else:
                    speedo(epoch, nbatch, {})
            if stager is not None:  # epoch drained: join the stage thread
                stager.close()
                stager = None
            if window:
                epoch_metrics.append(_mean_metrics(window))
            epoch_s = time.perf_counter() - t0
            if epoch_metrics:
                keys = epoch_metrics[0].keys()
                summary = ", ".join(
                    f"{k}={np.mean([m[k] for m in epoch_metrics]):.4f}"
                    for k in keys)
                logger.info("Epoch[%d] Train summary: %s  (%.1fs)", epoch,
                            summary, epoch_s)
            if rec is not None:
                rec.inc("train.epochs")
                rec.set_gauge("train.epoch_s", epoch_s)
            if run_record is not None:
                run_record.event(
                    "epoch_end", epoch=epoch, nbatch=nbatch,
                    epoch_s=round(epoch_s, 3),
                    **{k: float(np.mean([m[k] for m in epoch_metrics]))
                       for k in (epoch_metrics[0].keys()
                                 if epoch_metrics else ())})
            if snap is not None:
                # device_get here, serialize+write+manifest+GC in the
                # background; the interrupt file is cleared by the writer
                # only after this epoch checkpoint commits
                with obs_trace.span("train.snapshot", kind="epoch",
                                    step=(epoch + 1) * steps_per_epoch):
                    path = snap.save_epoch(epoch + 1, state)
                if run_record is not None:
                    run_record.event("snapshot", epoch=epoch, path=path)
                logger.info('Epoch[%d] Snapshotting checkpoint to "%s"',
                            epoch, path)
            if epoch_end_callback is not None:
                if snap is not None:
                    snap.flush()  # callbacks may read the checkpoint file
                epoch_end_callback(epoch, state)
            if stop_requested:
                logger.info("stop requested at epoch boundary — stopping "
                            "after epoch %d", epoch)
                return state
        return state
    finally:
        if lowerings is not None:
            lowerings.mark_step(None)  # a later lowering is no step's
        if stager is not None:
            stager.close()  # early return/error: release the stage thread
        if prof is not None:
            prof.close()  # run shorter than the window: close it cleanly
        if snap is not None:
            snap.close()  # flush pending writes before the process moves on
