"""End-to-end Faster R-CNN training entry point.

Reference: ``train_end2end.py — parse_args / train_net`` (SURVEY.md §3.1):
argparse → generate_config → load_gt_roidb(flip) → AnchorLoader → pretrained
init → MutableModule.fit(sgd, Speedometer, do_checkpoint).

TPU-native: same CLI surface and flow, but the fit loop runs ONE jitted XLA
program per step (``core/fit.py``) and multi-device training is a
``shard_map`` mesh instead of a ctx list + kvstore: ``--num-devices N``
replaces ``--gpus 0,..,N-1`` (``kvstore='device'`` ≙ in-step pmean over
ICI, see ``parallel/dp.py``).
"""

from __future__ import annotations

import argparse
import functools
import json
import logging
import time

import jax

from mx_rcnn_tpu import families, runtime
from mx_rcnn_tpu.config import NETWORK_NAMES, Config, generate_config
from mx_rcnn_tpu.core.fit import fit
from mx_rcnn_tpu.core.train import setup_training
from mx_rcnn_tpu.data import (AnchorLoader, cache_from_config,
                              decode_pool_from_config, load_gt_roidb)
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.obs import trace as obs_trace
from mx_rcnn_tpu.utils.checkpoint import restore_state

logger = logging.getLogger("mx_rcnn_tpu")


def _legacy_resume(state, prefix: str, steps_per_epoch: int):
    """Unverified auto-resume (plain ``--resume``, and the ``--resume
    auto`` fallback for pre-manifest run dirs): a SIGTERM interrupt
    checkpoint (step-exact) wins over epoch checkpoints; missing/corrupt
    files fail loudly at restore — never a silent from-scratch run when
    checkpoints exist.  Returns (state, begin_epoch)."""
    import os

    from mx_rcnn_tpu.utils.checkpoint import (interrupt_path,
                                              latest_checkpoint,
                                              restore_interrupt,
                                              restore_state)

    if os.path.exists(interrupt_path(prefix)):
        state, saved_spe = restore_interrupt(state, prefix)
        _check_spe(saved_spe, steps_per_epoch, prefix)
        step = int(state.step)
        begin_epoch = step // steps_per_epoch
        logger.info("resumed mid-epoch from %s (step %d → epoch %d)",
                    interrupt_path(prefix), step, begin_epoch)
        return state, begin_epoch
    found = latest_checkpoint(prefix)
    if found:
        begin_epoch = found[0]
        state = restore_state(state, prefix, begin_epoch)
        logger.info("resumed from %s epoch %d", prefix, begin_epoch)
        return state, begin_epoch
    logger.info("--resume: nothing under %s, starting fresh", prefix)
    return state, 0


def _check_topology(manifest: dict, cfg, num_devices: int, grad_accum: int,
                    path: str) -> None:
    """Restore-on-a-different-mesh admission check (docs/FT.md
    "Elasticity").  The manifest's ``topology`` record (written since the
    elastic era — ``utils/checkpoint.py — make_topology``) names the
    effective global batch the checkpoint was trained with; a resume that
    would SILENTLY change it changes the LR-schedule semantics and the
    experiment, so the old fingerprint-style WARNING is a hard error here.
    ``cfg.ft.allow_resize_resume`` downgrades it back to a warning — the
    elastic controller sets that for its supervised resizes, where the
    grad-accum rescale (or an explicit operator decision) makes the
    change principled instead of accidental."""
    topo = (manifest or {}).get("topology")
    if not topo or not topo.get("global_batch"):
        return  # pre-topology manifest: nothing to check against
    now = num_devices * cfg.train.batch_images * grad_accum
    then = int(topo["global_batch"])
    if then == now:
        return
    msg = (f"checkpoint {path} was trained with effective global batch "
           f"{then} ({topo.get('devices')} devices x batch_images x "
           f"grad_accum {topo.get('grad_accum')}) but this run would "
           f"train with {now} ({num_devices} devices x "
           f"{cfg.train.batch_images} images x grad_accum {grad_accum}) "
           f"— the LR schedule and step↔epoch mapping would silently "
           f"change")
    if cfg.ft.allow_resize_resume:
        logger.warning("resume: %s (ft.allow_resize_resume is set — "
                       "continuing anyway)", msg)
        return
    raise ValueError(
        msg + "; rescale grad_accum to preserve the global batch, or set "
        "ft.allow_resize_resume=true to accept the resize")


def _check_spe(saved_spe, steps_per_epoch: int, prefix: str) -> None:
    """Interrupt checkpoints are step-exact only under the same
    batches-per-epoch; mismatch must fail loudly (shared by the legacy and
    verified resume paths so the validation cannot diverge)."""
    from mx_rcnn_tpu.utils.checkpoint import interrupt_path

    if saved_spe is not None and saved_spe != steps_per_epoch:
        raise ValueError(
            f"interrupt checkpoint was written with "
            f"{saved_spe} steps/epoch but this run has "
            f"{steps_per_epoch} (different batch size, device "
            f"count, or dataset) — step-exact resume is impossible; "
            f"delete {interrupt_path(prefix)} to resume from the "
            f"last epoch checkpoint instead")


def _collects_spans(fn):
    """With ``cfg.obs.enabled`` the wrapped entry collects ``obs/trace.py``
    spans while it runs, whoever calls it, and counts compile seconds from
    its start (``obs/metrics.py — LoweringCounter``); the spans stay in
    ``obs_trace.events()`` afterwards.  Collection that was already on
    (the CLI's ``obs.trace``) is left as it was found."""
    @functools.wraps(fn)
    def wrapper(cfg, **kw):
        if not cfg.obs.enabled:
            return fn(cfg, **kw)
        from mx_rcnn_tpu.obs.metrics import LoweringCounter

        LoweringCounter._ensure_listener()
        if obs_trace.enabled():
            return fn(cfg, **kw)
        obs_trace.enable(cfg.obs.trace_cap)
        try:
            return fn(cfg, **kw)
        finally:
            obs_trace.disable()

    return wrapper


@_collects_spans
def train_net(cfg: Config, *, prefix: str, begin_epoch: int = 0,
              end_epoch: int = None, lr: float = None, lr_step: str = None,
              num_devices: int = 1, frequent: int = None, seed: int = 0,
              pretrained: str = None, pretrained_epoch: int = 0,
              roidb=None, dataset_kw: dict = None,
              frozen_prefixes=None, mode: str = "e2e", proposals=None,
              init_from=None, dcn_size: int = 1,
              resume=False, stop_flag=None,
              device_cache: bool = False, fault_plan: str = None,
              run_record=None, step_callback=None,
              epoch_end_callback=None, grad_accum: int = 1,
              multiproc: bool = False, post_restore_callback=None):
    """Train; returns the final TrainState.

    ``mode``: 'e2e' | 'rpn' | 'rcnn' — the alternate-training stage drivers
    reuse this function (ref ``rcnn/tools/train_rpn.py``/``train_rcnn.py``
    are thin variations of ``train_net`` the same way).
    ``proposals``: per-roidb-record proposal arrays (required for 'rcnn').
    ``init_from``: (prefix, epoch) checkpoint to initialize params and
    batch_stats from (stage chaining; optimizer state starts fresh), or the
    weights themselves handed over in memory: a mapping with ``params`` (and
    ``batch_stats`` where the model keeps any) whose leaves may live on the
    device already — no file is written or read.
    ``roidb`` may be injected (the alternate driver does); when None it is
    loaded from ``cfg.dataset``.  The family table (``families.py``) says
    what else differs by ``cfg.network.family``: for a sequence family
    (the table's rows of ``row`` 'sequence') ``roidb`` is the token source, an
    ``(n, S)`` array of ids (``data/tokens.py``), the loader is a
    ``TokenLoader``, ``mode`` is ``'lm'`` whatever was passed, and
    everything from the stager and ``fit`` on is the detectors' code.
    ``resume``: restore the newest state under ``prefix`` — a SIGTERM
    interrupt checkpoint (mid-epoch, step-exact) if present, else the
    highest epoch checkpoint.  ``resume="auto"`` additionally VERIFIES
    candidates (manifest + SHA-256, ``ft/integrity.py``) and falls back
    past corrupt/truncated/manifest-less files instead of crashing on the
    first bad one — the crash-loop supervisor's resume mode.
    ``stop_flag``: polled per step; True ⇒ save an interrupt checkpoint
    and return (see ``core.fit.fit``).
    ``fault_plan``: a ``ft/faults.py`` plan spec this process executes
    against itself (crash-loop certification; never set in production).
    ``run_record``: an ``obs/runrec.py`` RunRecord the fit loop appends
    structured events to (docs/OBSERVABILITY.md; None = off).
    ``grad_accum``: microbatches accumulated per optimizer step — the
    elastic mesh-shrink lever (ft/elastic.py): ``num_devices x
    batch_images x grad_accum`` images feed every optimizer step, and
    ``steps_per_epoch`` / the LR schedule count optimizer steps, so a
    shrunken mesh with a rescaled ``grad_accum`` trains the SAME recipe.
    ``multiproc``: ``num_devices`` spans every ``jax.distributed``
    process (call ``parallel.multihost.initialize`` first); the mesh is
    the global ``(dcn, ici)`` mesh, each process feeds its local image
    slice, and only process 0 writes checkpoints.
    ``post_restore_callback(state, ref, steps_per_epoch)``: invoked after
    a VERIFIED resume restored ``state`` from ``ref`` (a
    ``ft/integrity.py — CheckpointRef``), before training starts — the
    elastic controller's restore-bit-identity audit hook.
    ``step_callback`` / ``epoch_end_callback``: forwarded to
    ``core.fit.fit`` (instrumentation hooks — ``tools/obs_smoke.py`` uses
    them to time steps and count per-epoch lowerings); a ``fault_plan``'s
    injector chains in front of a caller ``step_callback``.
    With ``cfg.obs.enabled`` the start is laid out in spans
    (``obs/trace.py``, collected for any caller): ``setup.loader`` (roidb
    to loader), ``setup.model`` (``build_model``), ``setup.init`` (the
    init program and the optimizer's slots), ``setup.load`` (``init_from``
    / the pretrained graft), ``setup.resume``, then the ``train.*`` spans
    of ``core.fit.fit``; the ``setup.entry`` instant before them all
    carries ``process_s``, the process's age at this entry (what ran before
    it: interpreter, imports, the backend's start, the caller's own work).
    """
    if obs_trace.enabled():
        obs_trace.instant("setup.entry", process_s=obs_trace.process_age_s())
    if cfg.quant.enabled:
        # quantization is inference-only (docs/PERF.md "Quantized
        # inference"): the quantized model needs the calibrated 'quant'
        # collection a train step never carries.  Refuse up front
        # instead of crashing deep inside flax.
        raise ValueError(
            "quant__enabled=true is inference-only — train with the fp "
            "config and enable quant at test/serve/export time")
    if end_epoch is None:
        end_epoch = cfg.default.e2e_epoch
    t_loader = time.perf_counter()
    family = families.of(cfg)
    images = family.row == "image"
    if family.mode is not None:
        mode = family.mode
    if roidb is None and family.source is not None:
        roidb = family.get("source")(cfg, seed)
    elif roidb is None:
        _, roidb = load_gt_roidb(cfg, training=True, **(dataset_kw or {}))
    logger.info("[%s] training on %d roidb images", mode, len(roidb))

    grad_accum = max(int(grad_accum), 1)
    n_total = cfg.train.batch_images * num_devices
    # cache budgets derive from the bounded streaming window, not the
    # raw config number (loader.py — stream_cache_budget; logged once)
    bh0, bw0 = cfg.bucket.shapes[0]
    image_bytes = bh0 * bw0 * 3
    batch_bytes = n_total * image_bytes
    decode_pool = None if not images else decode_pool_from_config(
        cfg, n_images=len(roidb), image_bytes=image_bytes,
        batch_bytes=batch_bytes)
    # with a decode pool the cache lives IN the workers (loader.py —
    # decode_pool_from_config splits the RAM budget across them); a
    # parent-side cache would be dead weight the pool path never consults
    cache = (None if decode_pool is not None or not images
             else cache_from_config(cfg, n_images=len(roidb),
                                    image_bytes=image_bytes,
                                    batch_bytes=batch_bytes))
    # loader-shard ownership (docs/DATA.md): each process of a
    # multi-process world decodes only its row slice of every batch
    # (1/N of the epoch).  ONLY the process topology shards here —
    # explicit shard ownership is a bench-rig concept
    # (tools/data_bench.py --shard_id/--num_shards), where sibling
    # processes consume the other shards; sharding a lone training
    # process would silently train on 1/N of every batch.
    shard = None
    if multiproc and jax.process_count() > 1:
        shard = (jax.process_index(), jax.process_count())
    loader_kw = dict(batch_images=n_total, shuffle=cfg.train.shuffle,
                     seed=seed, cache=cache, decode_pool=decode_pool,
                     shard=shard)
    if family.loader is not None:
        loader = family.get("loader")(roidb, cfg, batch_images=n_total,
                                      shuffle=cfg.train.shuffle, seed=seed)
    elif mode == "rcnn":
        from mx_rcnn_tpu.data.loader import ROIIter

        if proposals is None:
            raise ValueError("mode='rcnn' requires precomputed proposals")
        if cfg.data.streaming:
            logger.warning(
                "data.streaming=true is not implemented for mode='rcnn' "
                "(proposal-fed ROIIter keeps the classic plan) — "
                "mid-epoch resume across a topology change falls back "
                "to same-topology skip semantics")
        loader = ROIIter(roidb, cfg, proposals, **loader_kw)
    elif cfg.data.streaming:
        # the topology-invariant streaming plan: shard unions and
        # mid-epoch cursors stay exactly-once across resizes
        from mx_rcnn_tpu.data.loader import StreamLoader

        loader = StreamLoader(roidb, cfg, **loader_kw)
    else:
        loader = AnchorLoader(roidb, cfg, **loader_kw)
    obs_trace.complete("setup.loader",
                       (time.perf_counter() - t_loader) * 1e3)
    if shard is not None:
        logger.info("loader shard %d/%d: this process decodes %d of %d "
                    "rows per batch", shard[0], shard[1],
                    n_total // shard[1], n_total)
    # OPTIMIZER steps per epoch (== loader batches unless accumulating);
    # the LR schedule and the step↔epoch resume math count these
    steps_per_epoch = max(len(loader) // grad_accum, 1)
    logger.info("%d optimizer steps/epoch (global batch %d = %d devices x "
                "%d images x accum %d)", steps_per_epoch,
                n_total * grad_accum, num_devices, cfg.train.batch_images,
                grad_accum)

    # the family's model modules are imported on first use: 1-2 s of a
    # sequence family's start
    with obs_trace.span("setup.model"):
        model = build_model(cfg)
    bh, bw = cfg.bucket.shapes[0]
    key = jax.random.PRNGKey(seed)
    with obs_trace.span("setup.init"):
        state, tx = setup_training(
            model, cfg, key, (cfg.train.batch_images, bh, bw, 3),
            steps_per_epoch, base_lr=lr, lr_step=lr_step,
            frozen_prefixes=frozen_prefixes)

    if pretrained:
        from mx_rcnn_tpu.utils.pretrained import load_pretrained_into

        with obs_trace.span("setup.load", source="pretrained"):
            state = load_pretrained_into(state, pretrained,
                                         pretrained_epoch, cfg)
        logger.info("grafted pretrained backbone from %s", pretrained)
    if init_from is not None:
        from mx_rcnn_tpu.utils.checkpoint import load_param

        with obs_trace.span("setup.load", source="init_from"):
            if isinstance(init_from, dict):
                state = state._replace(
                    params=init_from["params"],
                    batch_stats=init_from.get("batch_stats", {}))
            else:
                p, s = load_param(*init_from)
                state = state._replace(params=p, batch_stats=s)
        logger.info("initialized params from %s",
                    "memory" if isinstance(init_from, dict) else init_from)
    data_cursor = None
    t_resume = time.perf_counter()
    if resume == "auto" and begin_epoch == 0:
        # integrity-verified resume (ft/integrity.py): scan candidates
        # newest→oldest by manifest step, verify checksums, fall back past
        # corrupt/truncated/manifest-less files with a loud log — the
        # crash-loop supervisor's resume mode (docs/FT.md)
        import os

        from mx_rcnn_tpu.ft.integrity import latest_valid_checkpoint
        from mx_rcnn_tpu.utils.checkpoint import (config_fingerprint,
                                                  interrupt_path,
                                                  latest_checkpoint,
                                                  restore_interrupt)

        ref = latest_valid_checkpoint(prefix)
        if ref is None and (os.path.exists(interrupt_path(prefix))
                            or latest_checkpoint(prefix)):
            # checkpoints exist but none VERIFIES — e.g. a pre-manifest
            # run directory.  Starting from scratch here would silently
            # overwrite them; fall back to the legacy UNVERIFIED resume
            # (a genuinely corrupt file then fails loudly at restore).
            logger.warning(
                "--resume auto: checkpoints exist under %s but none has a "
                "verifying manifest (pre-manifest run?) — falling back to "
                "UNVERIFIED legacy resume instead of starting over", prefix)
            state, begin_epoch = _legacy_resume(state, prefix,
                                                steps_per_epoch)
        elif ref is None:
            logger.info("--resume auto: nothing restorable under %s, "
                        "starting fresh", prefix)
        else:
            fp_now = config_fingerprint(cfg)
            fp_ckpt = ref.manifest.get("config_fingerprint")
            if fp_ckpt and fp_ckpt != fp_now:
                logger.warning(
                    "resume: checkpoint %s was written under config "
                    "fingerprint %s but this run is %s — the recipe "
                    "changed; the continued run is NOT the same experiment",
                    ref.path, fp_ckpt, fp_now)
            # effective-global-batch admission: a silent change is a hard
            # error (ft.allow_resize_resume downgrades — elastic path)
            _check_topology(ref.manifest, cfg, num_devices, grad_accum,
                            ref.path)
            if ref.kind == "interrupt":
                state, saved_spe = restore_interrupt(state, prefix)
                _check_spe(saved_spe, steps_per_epoch, prefix)
                step = int(state.step)
                begin_epoch = step // steps_per_epoch
                logger.info("resumed mid-epoch from verified %s "
                            "(step %d → epoch %d)", ref.path, step,
                            begin_epoch)
                # data-shard cursor (PR 6 recorded it, r7 consumes it):
                # the writing run's loader batch size lets a streaming
                # loader replay THAT run's plan and continue the epoch
                # exactly-once — even when this run's topology (and so
                # its batch size) differs (core/fit.py — resume_at)
                topo = ref.manifest.get("topology") or {}
                cur = ref.manifest.get("data_cursor") or {}
                if topo.get("global_batch") and topo.get("grad_accum"):
                    old_bi = (int(topo["global_batch"])
                              // int(topo["grad_accum"]))
                    # images consumed IN THIS EPOCH, computed from the
                    # authoritative state.step under the topology that
                    # WROTE the checkpoint — correct even when the
                    # effective global batch changed across the resume
                    # (ft.allow_resize_resume), where the new-topology
                    # skip math would reposition the loader wrongly
                    images = ((step % steps_per_epoch)
                              * int(topo["global_batch"]))
                    data_cursor = {"loader_batch_images": old_bi,
                                   "images_consumed_in_epoch": images}
                    want = cur.get("batches_consumed")
                    if want is not None and int(want) * old_bi != images:
                        # manifest/state disagreement about how much
                        # data was consumed — the state is what training
                        # resumes from, so it wins; say so loudly
                        logger.warning(
                            "resume: manifest data_cursor says %s "
                            "batches x %d images consumed but "
                            "state.step implies %d images — using the "
                            "step-derived position", want, old_bi,
                            images)
            else:
                begin_epoch = ref.epoch
                state = restore_state(state, prefix, begin_epoch)
                logger.info("resumed from verified %s (epoch %d, step %d)",
                            ref.path, ref.epoch, ref.step)
            if post_restore_callback is not None:
                post_restore_callback(state, ref, steps_per_epoch)
    elif resume and begin_epoch == 0:
        state, begin_epoch = _legacy_resume(state, prefix, steps_per_epoch)
    elif begin_epoch > 0:
        state = restore_state(state, prefix, begin_epoch)
        logger.info("resumed from %s epoch %d", prefix, begin_epoch)
    if resume or begin_epoch > 0:
        obs_trace.complete("setup.resume",
                           (time.perf_counter() - t_resume) * 1e3)

    mesh = None
    if multiproc:
        from mx_rcnn_tpu.parallel import multihost

        mesh = multihost.global_mesh()
        if mesh.size != num_devices:
            raise ValueError(
                f"multiproc mesh spans {mesh.size} global devices but "
                f"num_devices={num_devices} was requested — pass the "
                f"GLOBAL device count (jax.device_count())")
    elif num_devices > 1:
        from mx_rcnn_tpu.parallel.dp import device_mesh

        mesh = device_mesh(num_devices, dcn_size=dcn_size)
    elif dcn_size > 1:
        raise ValueError(
            f"dcn_size={dcn_size} requires num_devices > 1 (got "
            f"{num_devices}) — the (dcn, ici) mesh only exists in "
            "multi-device training")
    if fault_plan:
        from mx_rcnn_tpu.ft.faults import FaultInjector, parse_plan

        injector = FaultInjector(parse_plan(fault_plan), prefix)
        if step_callback is None:
            step_callback = injector.on_step
        else:
            user_cb = step_callback

            def step_callback(step, _inj=injector.on_step, _cb=user_cb):
                _inj(step)
                _cb(step)
        logger.warning("fault injection ACTIVE: %s", fault_plan)
    try:
        state = fit(model, cfg, state, tx, loader, end_epoch, key,
                    begin_epoch=begin_epoch, prefix=prefix,
                    frequent=frequent, mesh=mesh, mode=mode,
                    stop_flag=stop_flag,
                    device_cache=device_cache, step_callback=step_callback,
                    run_record=run_record,
                    epoch_end_callback=epoch_end_callback,
                    grad_accum=grad_accum, multiproc=multiproc,
                    data_cursor=data_cursor)
    finally:
        if decode_pool is not None:
            decode_pool.close()
    return state


def add_set_arg(p) -> None:
    """Register the generic config-override flag (shared by every CLI)."""
    p.add_argument("--set", action="append", metavar="SEC__FIELD=VAL",
                   help="override any config field, e.g. "
                        "--set train__rpn_pre_nms_top_n=6000 (repeatable); "
                        "values parse as Python literals (strings/bools "
                        "coerced to the field's type)")


def parse_set_overrides(args) -> dict:
    """--set section__field=value items → generate_config overrides."""
    import ast

    overrides = {}
    for item in getattr(args, "set", None) or []:
        key, sep, val = item.partition("=")
        if not sep or "__" not in key:
            raise ValueError(
                f"--set expects section__field=value, got {item!r}")
        try:
            overrides[key] = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            overrides[key] = val
    return overrides


def config_from_args(args) -> Config:
    """Build the config from common dataset/train CLI flags.

    Shared by every training-family CLI (train, train_alternate,
    train_rpn/train_rcnn/test_rpn); absent attributes are treated as unset
    so tools only expose the flags that apply to them.
    """
    overrides = {}
    if getattr(args, "image_set", None):
        overrides["dataset__image_set"] = args.image_set
    if getattr(args, "root_path", None):
        overrides["dataset__root_path"] = args.root_path
    if getattr(args, "dataset_path", None):
        overrides["dataset__dataset_path"] = args.dataset_path
    if getattr(args, "batch_images", None):
        overrides["train__batch_images"] = args.batch_images
    if getattr(args, "no_flip", False):
        overrides["train__flip"] = False
    if getattr(args, "no_shuffle", False):
        overrides["train__shuffle"] = False
    overrides.update(parse_set_overrides(args))
    return generate_config(args.network, args.dataset, **overrides)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="Train Faster R-CNN end-to-end (ref train_end2end.py)")
    p.add_argument("--network", default="resnet101",
                   choices=NETWORK_NAMES)
    p.add_argument("--dataset", default="PascalVOC",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard", "synthetic_stream",
                            "tokens", "synthetic_tokens"])
    p.add_argument("--image_set", default=None,
                   help="e.g. 2007_trainval or 2007_trainval+2012_trainval")
    p.add_argument("--root_path", default=None)
    p.add_argument("--dataset_path", default=None)
    p.add_argument("--prefix", default="model/e2e")
    p.add_argument("--pretrained", default=None,
                   help="pretrained backbone checkpoint prefix/path")
    p.add_argument("--pretrained_epoch", type=int, default=0)
    p.add_argument("--begin_epoch", type=int, default=0)
    p.add_argument("--end_epoch", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--lr_step", default=None)
    p.add_argument("--frequent", type=int, default=None,
                   help="Speedometer logging period (batches)")
    p.add_argument("--batch_images", type=int, default=None,
                   help="images per device (ref BATCH_IMAGES)")
    p.add_argument("--num_devices", type=int, default=1,
                   help="data-parallel devices (ref --gpus)")
    p.add_argument("--dcn_size", type=int, default=1,
                   help="hosts/slices: >1 builds a (dcn, ici) mesh with "
                        "hierarchical gradient all-reduce (multi-host DP)")
    p.add_argument("--no_flip", action="store_true")
    p.add_argument("--no_shuffle", action="store_true")
    p.add_argument("--resume", nargs="?", const=True, default=False,
                   choices=[True, "auto"], metavar="auto",
                   help="resume from the newest state under --prefix: a "
                        "SIGTERM interrupt checkpoint (step-exact) if "
                        "present, else the highest epoch checkpoint.  "
                        "'--resume auto' additionally verifies manifests + "
                        "SHA-256 and falls back past corrupt/truncated "
                        "files (docs/FT.md)")
    p.add_argument("--fault_plan", default=None,
                   help="fault-injection plan this process executes against "
                        "itself, e.g. 'kill@step=7@sig=KILL' — crash-loop "
                        "certification only (mx_rcnn_tpu/ft/faults.py)")
    p.add_argument("--dataset_kw", default=None,
                   help="Python-literal dict of extra dataset-constructor "
                        "kwargs, e.g. \"{'num_images': 32}\" (synthetic "
                        "sizing for smokes and the crash-loop driver)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--elastic", action="store_true",
                   help="elastic training (ft/elastic.py, docs/FT.md "
                        "'Elasticity'): watch topology directives at "
                        "<prefix>.topology.json (+ SIGUSR1), drain and "
                        "resize the mesh live on device loss/return, "
                        "rescale grad accumulation to keep the global "
                        "batch on-recipe")
    p.add_argument("--grad_accum", type=int, default=1,
                   help="microbatches accumulated per optimizer step "
                        "(effective global batch = num_devices x "
                        "batch_images x grad_accum); the elastic "
                        "controller manages this itself")
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator HOST:PORT — makes "
                        "this process one worker of a multi-process "
                        "world (requires --num_processes/--process_id)")
    p.add_argument("--num_processes", type=int, default=1)
    p.add_argument("--process_id", type=int, default=0)
    p.add_argument("--local_devices", type=int, default=None,
                   help="pin the per-process CPU device count (the "
                        "multi-host-without-a-cluster rig; leave unset "
                        "on real TPU hosts)")
    add_set_arg(p)
    p.add_argument("--device_cache", action="store_true",
                   help="stage the epoch in HBM and gather batches on "
                        "device (single-bucket datasets; for hosts/links "
                        "too slow to stream per step — see "
                        "data/device_cache.py)")
    p.add_argument("--export_train_step", default=None, metavar="DIR",
                   help="AOT-export the jitted train step for this "
                        "recipe into DIR (serve/export.py — "
                        "export_train_step: jax.export program + "
                        "manifest, verified bit-equal to the live "
                        "trace) and exit.  The export's verify pass "
                        "also pre-warms the persistent cache the next "
                        "(re)start reads — docs/FT.md 'Recovery time'")
    return p.parse_args(argv)


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    # opt-in lock sanitizer, FIRST — the crashloop/elastic smokes arm it
    # via MXRCNN_THREAD_SANITIZER in the child env, and every lock the
    # snapshotter/loader/elastic controller builds must be born wrapped
    # (docs/ANALYSIS.md "threadlint")
    from mx_rcnn_tpu.analysis import sanitizer

    sanitizer.maybe_install_from_env()
    args = parse_args(argv)
    multiproc = args.coordinator is not None
    if multiproc:
        # distributed init must precede ANY backend initialization —
        # before config_from_args touches nothing device-side, but keep
        # the ordering airtight by initializing first thing
        from mx_rcnn_tpu.parallel import multihost

        multihost.initialize(args.coordinator, args.num_processes,
                             args.process_id,
                             local_devices=args.local_devices)
        logger.info("jax.distributed: process %d/%d, %d local / %d global "
                    "devices", jax.process_index(), jax.process_count(),
                    jax.local_device_count(), jax.device_count())
    cfg = config_from_args(args)
    # persistent XLA compile cache, armed BEFORE any compile: a restart
    # (elastic EXIT_RESIZE relaunch, crash-loop attempt, the next chip
    # call) reads the step instead of recompiling it (docs/FT.md
    # "Recovery time").  Not for jax.distributed workers: ranks that hit
    # the cache race ahead of ranks that compile and the collective-init
    # barrier times the stragglers out — there every rank compiles.
    cache_dir = ("(off: multi-process)" if multiproc
                 else runtime.enable_compile_cache())
    runtime.log_runtime(cache_dir)
    if args.export_train_step:
        from mx_rcnn_tpu.serve.export import export_train_step

        report = export_train_step(
            cfg, out_dir=args.export_train_step,
            num_devices=args.num_devices, grad_accum=args.grad_accum,
            seed=args.seed)
        print(json.dumps(report))
        return 0
    dataset_kw = None
    if args.dataset_kw:
        import ast

        dataset_kw = ast.literal_eval(args.dataset_kw)

    # graceful preemption: first SIGTERM finishes the in-flight step, saves
    # a step-exact interrupt checkpoint and exits; --resume picks it up
    import signal

    stop = {"flag": False}

    def _on_sigterm(signum, frame):
        logger.info("SIGTERM received — checkpointing and stopping")
        stop["flag"] = True

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread (embedded use) — no handler
        pass

    # observability (docs/OBSERVABILITY.md): run record + unified
    # /metrics exporter + host-span trace + SIGUSR2 profiler toggle —
    # all OFF unless cfg.obs asks (e.g. --set obs__enabled=true).
    # CliObs owns the wiring AND the fail-soft teardown, shared with
    # tools/serve.py
    from mx_rcnn_tpu.obs.runrec import cli_obs

    obs_sess = cli_obs(cfg, "train")
    if obs_sess is not None and obs_sess.flight is not None:
        # a train-side flight record should carry where the loop was:
        # the step/epoch gauges are already in the samples, but the
        # registry view at dump time pins the exact last-published state
        from mx_rcnn_tpu.obs.metrics import registry as _reg

        obs_sess.flight.add_context(
            "train", lambda: {"step": _reg().counter("train.steps"),
                              "epochs_done": _reg().counter(
                                  "train.epochs"),
                              "samples_per_sec": _reg().gauge(
                                  "train.samples_per_sec")})
    exit_code = 0
    try:
        if args.elastic or cfg.elastic.enabled:
            from mx_rcnn_tpu.ft.elastic import run_elastic

            exit_code = run_elastic(
                cfg, prefix=args.prefix, end_epoch=args.end_epoch,
                lr=args.lr, lr_step=args.lr_step, frequent=args.frequent,
                seed=args.seed, dataset_kw=dataset_kw,
                pretrained=args.pretrained,
                pretrained_epoch=args.pretrained_epoch,
                stop_flag=lambda: stop["flag"],
                run_record=obs_sess.record if obs_sess else None,
                multiproc=multiproc, fault_plan=args.fault_plan)
        else:
            train_net(cfg, prefix=args.prefix, begin_epoch=args.begin_epoch,
                      end_epoch=args.end_epoch, lr=args.lr,
                      lr_step=args.lr_step,
                      num_devices=args.num_devices, frequent=args.frequent,
                      seed=args.seed, pretrained=args.pretrained,
                      pretrained_epoch=args.pretrained_epoch,
                      dcn_size=args.dcn_size,
                      resume=args.resume, stop_flag=lambda: stop["flag"],
                      device_cache=args.device_cache,
                      fault_plan=args.fault_plan,
                      dataset_kw=dataset_kw, grad_accum=args.grad_accum,
                      multiproc=multiproc,
                      run_record=obs_sess.record if obs_sess else None)
    finally:
        if obs_sess is not None:
            from mx_rcnn_tpu.obs.metrics import registry

            obs_sess.close(metric="train_samples_per_sec",
                           value=registry().gauge("train.samples_per_sec"),
                           unit="imgs/s",
                           steps=registry().counter("train.steps"))
    if exit_code:
        import sys

        sys.exit(exit_code)


if __name__ == "__main__":
    main()
