"""Bulk-inference CLI: score a corpus through the serving fleet with
exactly-once sink accounting (docs/SERVING.md "Bulk tier").

No reference equivalent.  Drives a :class:`~mx_rcnn_tpu.data.loader.
StreamTestLoader` corpus through an export-warmed replica fleet
(``serve/bulk.py — BulkRunner``) and emits ONE BENCH-style JSON record
with ``--check`` invariants:

* **N in = N accounted** — every planned corpus image reaches the sink
  exactly once (``lost == 0``; an unservable image ABORTS the run, it
  is never dropped);
* **0 post-warm recompiles** — the whole corpus serves through the
  export-warmed programs (``LoweringCounter``);
* **bounded RSS** — peak RSS stays under ``data.ram_ceiling_mb``;
* **rate floor** — sustained imgs/s >= ``--min_ratio_vs_serve`` x the
  closed-loop serve baseline (the same fleet scored by closed-loop
  clients that read each PNG and POST it raw — the honest alternative
  workload the bulk plane replaces).

``--protocol kill_resume`` (the measured acceptance protocol and
``make bulk-smoke``): an uninterrupted CONTROL run, a run SIGKILLed
after committing its mid-corpus shard (``--fault kill@shard=K``), and a
RESUME of the killed sink — then asserts the killed+resumed shard set
is BYTE-identical to the control's (the exactly-once restart claim,
stated in bytes).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import List

logger = logging.getLogger("mx_rcnn_tpu")


def _sha256_file(path: str) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def parse_fault(spec: str):
    """``kill@shard=K`` → a fault hook that SIGKILLs this process right
    after shard K commits (the ft/faults.py idiom pointed at the sink:
    the committed prefix is the only trace the run leaves)."""
    if not spec:
        return None
    if not spec.startswith("kill@shard="):
        raise ValueError(f"unknown fault spec {spec!r} "
                         "(expected kill@shard=K)")
    k = int(spec.split("=", 1)[1])

    def fault(shard: int) -> None:
        if shard == k:
            logging.getLogger("mx_rcnn_tpu").warning(
                "FAULT: SIGKILL after shard %d commit", shard)
            sys.stdout.flush()
            os.kill(os.getpid(), signal.SIGKILL)

    return fault


def _model_ident(args) -> str:
    """The weights identity recorded in the sink manifest: a resume
    must score with the SAME model it started with.  For a checkpoint
    the identity is the ckpt file's sha256 (a retrain that overwrites
    the same path is DIFFERENT weights and must be refused), not the
    path string."""
    if args.prefix:
        from mx_rcnn_tpu.utils.checkpoint import checkpoint_path

        path = checkpoint_path(args.prefix, args.epoch)
        return f"sha256:{_sha256_file(path)[:16]}@{args.epoch}"
    return f"random-init@seed={args.seed}"


def _corpus(cfg, args):
    """The scoring corpus roidb — the TRAIN image set loaded with EVAL
    semantics (``training=False`` + explicit ``image_set``): no flip
    augmentation and, critically, no gt filter — inference must score
    unannotated images too, and ``filter_roidb`` would silently drop
    them from the plan (the 10k rehearsal set is already on disk from
    the data-plane bench).  NOTE deliberately no decoded-image cache: a
    bulk pass touches every image exactly once, so a cache can only
    retain gigabytes it will never hit and pay per-image bookkeeping —
    the bounded window here is the in-flight depth, not a cache."""
    from mx_rcnn_tpu.data import load_gt_roidb

    _, roidb = load_gt_roidb(cfg, image_set=cfg.dataset.image_set,
                             training=False,
                             num_images=args.num_images)
    return roidb


def _serve_baseline(router, roidb, duration_s: float, concurrency: int,
                    out_dir: str) -> dict:
    """The closed-loop serve baseline: N workers each read one corpus
    PNG from disk, POST it raw (``router.detect``) and append the
    serialized result to a per-worker file — exactly what scoring this
    corpus through the ONLINE path would take.  Decode, preprocess AND
    result persistence are paid per request (a corpus-scoring client
    that discards its results scores nothing); what the baseline does
    NOT pay is the bulk plane's ordering/atomicity/cursor machinery —
    per-worker appends, no exactly-once, no resume."""
    from mx_rcnn_tpu.data.image import imread_rgb
    from mx_rcnn_tpu.serve.bulk import detections_line
    from mx_rcnn_tpu.serve.queue import (DeadlineExceeded, RequestFailed,
                                         ShedError)

    os.makedirs(out_dir, exist_ok=True)
    paths = [r["image"] for r in roidb]
    # per-image model time is content-dependent (the NMS fixed point —
    # docs/PERF.md), so a window over the corpus HEAD would compare a
    # biased sample against bulk's full-corpus rate: sample uniformly
    import numpy as np

    order = np.random.RandomState(0).permutation(len(paths))
    paths = [paths[i] for i in order]
    stop = time.monotonic() + duration_s
    outcomes = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
    lock = threading.Lock()

    def worker(wid: int):
        i = wid
        with open(os.path.join(out_dir, f"client{wid}.jsonl"), "w") as f:
            while time.monotonic() < stop:
                img = imread_rgb(paths[i % len(paths)])
                try:
                    dets = router.detect(img, timeout_ms=60_000.0)
                    # persist under the CORPUS index (paths was
                    # permuted), per detections_line's contract
                    f.write(detections_line(int(order[i % len(order)]),
                                            dets) + "\n")
                    key = "ok"
                except ShedError:
                    key = "shed"
                except DeadlineExceeded:
                    key = "expired"
                except (RequestFailed, TimeoutError):
                    key = "failed"
                i += concurrency
                with lock:
                    outcomes[key] += 1

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    served = outcomes["ok"]
    return {"imgs_per_sec": round(served / max(wall, 1e-9), 2),
            "duration_s": round(wall, 2), "client": outcomes,
            "concurrency": concurrency}


def run_single(args, cfg) -> int:
    """One bulk pass (fresh or resuming) in THIS process; prints the
    BENCH record and returns the --check exit code."""
    from mx_rcnn_tpu.data.loader import StreamTestLoader
    from mx_rcnn_tpu.obs.metrics import LoweringCounter, registry
    from mx_rcnn_tpu.obs.runrec import cli_obs
    from mx_rcnn_tpu.serve.bulk import (BulkRunner, BulkSink, auto_inflight,
                                        make_sink_manifest)
    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import (CACHE_SUBDIR, ExportStore,
                                          export_serve_programs)
    from mx_rcnn_tpu.serve.fleet import build_fleet
    from mx_rcnn_tpu.tools.data_bench import _vm_peak_mb
    from mx_rcnn_tpu.tools.loadgen import init_predictor

    roidb = _corpus(cfg, args)
    store_root = args.export_dir
    if store_root:
        enable_compile_cache(os.path.join(store_root, CACHE_SUBDIR))
        predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
    else:
        store_root = os.path.join(args.workdir, "store")
        enable_compile_cache(os.path.join(store_root, CACHE_SUBDIR))
        predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
        if not os.path.exists(os.path.join(store_root, "manifest.json")):
            logger.info("[bulk] exporting serving programs → %s",
                        store_root)
            export_serve_programs(predictor, cfg, store_root)
    ExportStore(store_root).check(
        cfg, quant_fingerprint=getattr(predictor, "quant_fingerprint",
                                       None))

    # obs (off by default): run record under runs/<id>/ like every
    # other entry point, plus — when enabled — the time-series sampler,
    # health engine and flight recorder (docs/OBSERVABILITY.md)
    obs_sess = cli_obs(cfg, "bulk")
    record = obs_sess.record if obs_sess else None

    logger.info("[bulk] launching %d export-warmed replica(s) ...",
                cfg.fleet.replicas)
    router = build_fleet(cfg, predictor.model, predictor.variables,
                         export_root=store_root, record=record)
    rec = {
        "metric": "bulk_imgs_per_sec",
        "unit": "imgs/s",
        "measured": True,
        "network": args.network,
        "dataset": args.dataset,
        "corpus_images": len(roidb),
        "replicas": cfg.fleet.replicas,
        "batch_images": args.batch_images,
        "serve_batch_size": cfg.serve.batch_size,
        "max_inflight": auto_inflight(cfg),
        "shard_batches": cfg.bulk.shard_batches,
        "quant": (f"{cfg.quant.dtype}/{cfg.quant.mode}"
                  if cfg.quant.enabled else None),
        "smoke": bool(args.smoke),
        "host": {"physical_cores": os.cpu_count()},
    }
    problems: List[str] = []
    try:
        replicas_ready = router.healthz()["ready"]
        rec["replicas_ready"] = replicas_ready
        if replicas_ready < cfg.fleet.replicas:
            problems.append(f"only {replicas_ready}/{cfg.fleet.replicas} "
                            "replicas joined")
        if not args.skip_baseline:
            logger.info("[bulk] closed-loop serve baseline "
                        "(clients read + POST each PNG) ...")
            rec["serve_baseline"] = _serve_baseline(
                router, roidb, args.baseline_s,
                concurrency=2 * cfg.serve.batch_size * cfg.fleet.replicas,
                out_dir=os.path.join(args.workdir, "baseline_out"))
            router.metrics.reset()

        loader = StreamTestLoader(roidb, cfg,
                                  batch_images=args.batch_images,
                                  shuffle=False, seed=args.seed,
                                  raw_images=False)
        sink = BulkSink(args.out_dir,
                        make_sink_manifest(cfg, roidb, args.seed,
                                           args.batch_images,
                                           model=_model_ident(args)))
        runner = BulkRunner(router, loader, sink, cfg,
                            registry=registry(),
                            fault=parse_fault(args.fault),
                            record=record)
        logger.info("[bulk] scoring %d images → %s (resume cursor: %d "
                    "shard(s))", len(roidb), args.out_dir,
                    sink.committed_shards())
        with LoweringCounter() as lc:
            stats = runner.run()
        rec["bulk"] = stats
        # per-replica micro-batch occupancy: <batch_size means lanes ran
        # dry and dispatchers padded — the first thing to look at when
        # the rate trails the serve baseline
        rec["batch_occupancy_mean"] = [
            r.engine.metrics.snapshot()["batch_occupancy"]["mean_rows"]
            for r in router.manager.replicas
            if r.engine is not None]
        rec["value"] = stats["imgs_per_sec"]
        rec["recompiles_after_warm"] = lc.n
        rec["peak_rss_mb"] = round(_vm_peak_mb(), 1)
        rec["ram_ceiling_mb"] = cfg.data.ram_ceiling_mb

        checks = {
            "n_in_equals_n_accounted": (stats["accounted_images"]
                                        == stats["planned_images"]),
            "zero_lost": stats["lost"] == 0,
            "zero_recompiles_after_warm": lc.n == 0,
        }
        if cfg.data.ram_ceiling_mb > 0:
            checks["rss_under_ceiling"] = (rec["peak_rss_mb"]
                                           <= cfg.data.ram_ceiling_mb)
        if "serve_baseline" in rec and stats["scored_images"]:
            base = rec["serve_baseline"]["imgs_per_sec"]
            rec["ratio_vs_serve_baseline"] = (
                round(stats["imgs_per_sec"] / base, 3) if base else None)
            checks["rate_vs_serve_baseline"] = (
                base == 0 or stats["imgs_per_sec"]
                >= args.min_ratio_vs_serve * base)
        rec["checks"] = checks
        problems += [k for k, v in checks.items() if not v]
    finally:
        router.close()
        if obs_sess is not None:
            obs_sess.close(metric=rec["metric"], value=rec.get("value"),
                           unit=rec.get("unit"),
                           checks=rec.get("checks"))

    print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    if args.check and problems:
        for p in problems:
            logger.error("CHECK FAILED: %s", p)
        return 1
    if args.check:
        logger.info("CHECK OK: %s", ", ".join(rec.get("checks", {})))
    return 0


def _child_cmd(args, out_dir: str, store: str, fault: str = None,
               baseline: bool = False) -> List[str]:
    cmd = [sys.executable, "-m", "mx_rcnn_tpu.tools.bulk",
           "--protocol", "single", "--network", args.network,
           "--dataset", args.dataset, "--root_path", args.root_path,
           "--num_images", str(args.num_images),
           "--batch_images", str(args.batch_images),
           "--replicas", str(args.replicas),
           "--seed", str(args.seed),
           "--out_dir", out_dir, "--export_dir", store,
           "--workdir", args.workdir,
           "--baseline_s", str(args.baseline_s),
           "--min_ratio_vs_serve", str(args.min_ratio_vs_serve),
           "--check"]
    if args.dataset_path:
        cmd += ["--dataset_path", args.dataset_path]
    if args.prefix:
        cmd += ["--prefix", args.prefix, "--epoch", str(args.epoch)]
    if not baseline:
        cmd += ["--skip_baseline"]
    if fault:
        cmd += ["--fault", fault]
    for s in args.set or []:
        cmd += ["--set", s]
    return cmd


def _run_child(cmd, timeout_s: float = 3600.0):
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=timeout_s,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    record = None
    for ln in out.stdout.strip().splitlines():
        if ln.startswith("{"):
            record = json.loads(ln)
    return out.returncode, record, out


def run_kill_resume(args, cfg) -> int:
    """The acceptance protocol: control → kill-at-mid-shard → resume →
    byte-compare.  Children are REAL processes (SIGKILL must be real);
    they share one export store and one materialized corpus."""
    from mx_rcnn_tpu.obs.runrec import cli_obs
    from mx_rcnn_tpu.serve.bulk import BulkSink
    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import (CACHE_SUBDIR,
                                          export_serve_programs)
    from mx_rcnn_tpu.tools.loadgen import init_predictor

    # the parent orchestrator gets its own run record (children write
    # theirs): the three phase events + the final byte-identity verdict
    # make the protocol's runs/<id>/ self-describing
    obs_sess = cli_obs(cfg, "bulk_kill_resume")

    def _phase(name: str, **kw) -> None:
        if obs_sess is not None:
            obs_sess.record.event("bulk_protocol_phase", phase=name, **kw)

    # materialize corpus + export store ONCE, in the parent, so children
    # never race the PNG writes or the export verify pass
    roidb = _corpus(cfg, args)
    store = args.export_dir or os.path.join(args.workdir, "store")
    if not os.path.exists(os.path.join(store, "manifest.json")):
        enable_compile_cache(os.path.join(store, CACHE_SUBDIR))
        predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
        logger.info("[bulk] exporting serving programs → %s", store)
        export_serve_programs(predictor, cfg, store)

    import math

    from mx_rcnn_tpu.data.loader import StreamTestLoader

    # the ACTUAL plan geometry (per-bucket tails make it sum-of-ceils
    # over buckets, not ceil over the corpus) — a dims-only loader
    # build, no pixels decoded
    plan = StreamTestLoader(roidb, cfg, batch_images=args.batch_images,
                            shuffle=False, seed=args.seed,
                            num_workers=0)._plan(0, args.batch_images)
    n_batches = len(plan)
    n_shards = math.ceil(n_batches / max(cfg.bulk.shard_batches, 1))
    kill_shard = max(n_shards // 2 - 1, 0)
    ctrl_dir = os.path.join(args.workdir, "sink_control")
    kill_dir = args.out_dir or os.path.join(args.workdir, "sink_kill")

    rec = {"metric": "bulk_kill_resume", "measured": True,
           "corpus_images": len(roidb), "shards": n_shards,
           "kill_after_shard": kill_shard, "smoke": bool(args.smoke)}
    problems: List[str] = []

    logger.info("[bulk] CONTROL run (uninterrupted, with serve "
                "baseline) → %s", ctrl_dir)
    _phase("control", out_dir=ctrl_dir)
    rc, ctrl, out = _run_child(_child_cmd(args, ctrl_dir, store,
                                          baseline=True))
    rec["control"] = ctrl
    if rc != 0 or ctrl is None:
        problems.append(f"control run failed rc={rc}")
        print(out.stdout[-4000:], file=sys.stderr)
        print(out.stderr[-4000:], file=sys.stderr)

    logger.info("[bulk] KILL run (SIGKILL after shard %d) → %s",
                kill_shard, kill_dir)
    _phase("kill", out_dir=kill_dir, kill_after_shard=kill_shard)
    rc, _, out = _run_child(_child_cmd(
        args, kill_dir, store, fault=f"kill@shard={kill_shard}"))
    killed_by_signal = rc in (-signal.SIGKILL, 128 + signal.SIGKILL, 137)
    try:
        committed_at_kill = BulkSink(kill_dir).committed_shards()
    except ValueError:
        # child died before writing the sink manifest (startup failure,
        # not the planned mid-corpus kill) — report it as a check
        # failure with the child's tail, never a raw traceback
        committed_at_kill = 0
        print(out.stdout[-2000:], file=sys.stderr)
        print(out.stderr[-2000:], file=sys.stderr)
    rec["kill"] = {"rc": rc, "killed_by_signal": killed_by_signal,
                   "committed_shards": committed_at_kill}
    if not killed_by_signal:
        problems.append(f"kill run exited rc={rc}, not by SIGKILL")
    if not 0 < committed_at_kill < n_shards:
        problems.append(f"kill left {committed_at_kill}/{n_shards} "
                        "shards — not a mid-corpus kill")

    logger.info("[bulk] RESUME run (same sink) ...")
    _phase("resume", out_dir=kill_dir,
           committed_at_kill=committed_at_kill)
    rc, resume, out = _run_child(_child_cmd(args, kill_dir, store))
    rec["resume"] = resume
    if rc != 0 or resume is None:
        problems.append(f"resume run failed rc={rc}")
        print(out.stdout[-4000:], file=sys.stderr)
        print(out.stderr[-4000:], file=sys.stderr)
    elif resume["bulk"]["resumed_shards"] != committed_at_kill:
        problems.append("resume did not start at the killed run's cursor")

    # byte-identity: every shard of the killed+resumed sink equals the
    # control's — shards before the kill came from run 1, after from
    # run 2, and the union must not show the seam
    sink_c, sink_k = BulkSink(ctrl_dir), BulkSink(kill_dir)
    nc, nk = sink_c.committed_shards(), sink_k.committed_shards()
    identical = nc == nk == n_shards and all(
        _sha256_file(sink_c.shard_path(k))
        == _sha256_file(sink_k.shard_path(k)) for k in range(nc))
    rec["union_bit_identical"] = identical
    if not identical:
        problems.append(f"killed+resumed union differs from control "
                        f"({nk} vs {nc} shards of {n_shards})")

    checks = {
        "control_check_ok": bool(ctrl and ctrl.get("checks")
                                 and all(ctrl["checks"].values())),
        "killed_mid_corpus": killed_by_signal
        and 0 < committed_at_kill < n_shards,
        "resume_check_ok": bool(resume and resume.get("checks")
                                and all(resume["checks"].values())),
        "union_bit_identical": identical,
    }
    rec["checks"] = checks
    if ctrl:
        rec["value"] = ctrl.get("value")
        rec["unit"] = "imgs/s"
    problems += [k for k, v in checks.items() if not v]
    if obs_sess is not None:
        obs_sess.close(metric=rec["metric"], value=rec.get("value"),
                       unit=rec.get("unit"), checks=checks)

    print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    if args.check and problems:
        for p in problems:
            logger.error("CHECK FAILED: %s", p)
        return 1
    if args.check:
        logger.info("CHECK OK: %s", ", ".join(checks))
    return 0


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    from mx_rcnn_tpu.analysis import sanitizer

    sanitizer.maybe_install_from_env()
    p = argparse.ArgumentParser(
        description="Bulk-inference plane: StreamLoader-fed fleet "
                    "scoring with exactly-once accounting "
                    "(docs/SERVING.md 'Bulk tier')")
    from mx_rcnn_tpu.tools.train import add_set_arg, parse_set_overrides

    p.add_argument("--network", default="tiny",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="synthetic_stream",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard", "synthetic_stream"])
    p.add_argument("--root_path", default="data")
    p.add_argument("--dataset_path", default=None)
    p.add_argument("--prefix", default=None,
                   help="checkpoint prefix (default: random init — "
                        "deterministic across the protocol's processes)")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--num_images", type=int, default=10_000)
    p.add_argument("--batch_images", type=int, default=0,
                   help="loader batch rows (0 = serve.batch_size)")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--export_dir", default=None,
                   help="existing AOT export store (default: build one "
                        "under --workdir)")
    p.add_argument("--workdir", default=None)
    p.add_argument("--out_dir", default=None, help="result sink dir")
    p.add_argument("--protocol", default="single",
                   choices=["single", "kill_resume"])
    p.add_argument("--fault", default=None,
                   help="fault plan: kill@shard=K (SIGKILL after shard "
                        "K commits)")
    p.add_argument("--baseline_s", type=float, default=10.0,
                   help="closed-loop serve-baseline window")
    p.add_argument("--skip_baseline", action="store_true")
    p.add_argument("--min_ratio_vs_serve", type=float, default=1.0,
                   help="--check floor for bulk/serve-baseline rate "
                        "(the smoke uses 0.4: a contended 1-core box "
                        "shares every stage)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="gate scale: tiny canvas, 48-image corpus, "
                        "2 replicas, kill+resume protocol")
    p.add_argument("--check", action="store_true")
    p.add_argument("--out", default=None)
    add_set_arg(p)
    args = p.parse_args(argv)

    overrides = {}
    if args.smoke:
        from mx_rcnn_tpu.tools.loadgen import _smoke_overrides

        overrides.update(_smoke_overrides())
        overrides.update({"bulk__shard_batches": 4,
                          "data__ram_ceiling_mb": 3072})
        args.dataset = "synthetic"
        args.num_images = min(args.num_images, 48)
        if args.dataset_path is None:
            # own directory (the data_bench --smoke rule): a 48-image
            # spec regenerating inside data/synthetic would invalidate
            # the 64-image set every other smoke/test shares
            args.dataset_path = os.path.join(args.root_path,
                                             "synthetic_bulk_smoke")
        args.baseline_s = min(args.baseline_s, 5.0)
        if args.min_ratio_vs_serve == 1.0:
            args.min_ratio_vs_serve = 0.4
        if args.protocol == "single" and not args.fault \
                and not args.out_dir:
            args.protocol = "kill_resume"
    overrides.update(parse_set_overrides(args))
    overrides.setdefault("fleet__replicas", args.replicas)
    overrides.setdefault("data__streaming", True)
    if args.dataset_path:
        overrides["dataset__dataset_path"] = args.dataset_path
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config(args.network, args.dataset,
                          dataset__root_path=args.root_path, **overrides)
    if args.batch_images <= 0:
        args.batch_images = cfg.serve.batch_size
    if args.workdir is None:
        import tempfile

        args.workdir = tempfile.mkdtemp(prefix="bulk_")
    os.makedirs(args.workdir, exist_ok=True)
    if args.protocol == "kill_resume":
        # children rebuild the config from flags alone: ship the MERGED
        # override set (smoke presets included), not just the user's
        args.set = [f"{k}={v!r}" for k, v in overrides.items()]
        return run_kill_resume(args, cfg)
    if args.out_dir is None:
        args.out_dir = os.path.join(args.workdir, "sink")
    return run_single(args, cfg)


if __name__ == "__main__":
    sys.exit(main())
