"""Load generator for the serving engine AND the serving fleet:
closed/open loop, BENCH JSON, the FLEET_r08 measurement protocol.

No reference equivalent.  Replays synthetic images against an IN-PROCESS
:class:`~mx_rcnn_tpu.serve.engine.ServingEngine` (no network in the
measurement path — the HTTP front end is exercised by its own tests) and
emits ONE BENCH-style JSON line so serving performance enters the
measured-evidence pipeline:

    {"metric": "serve_imgs_per_sec", "value": ..., "measured": true,
     "offline_imgs_per_sec": ..., "ratio_vs_offline": ...,
     "p50_ms"/"p90_ms"/"p99_ms": ..., "shed_rate": ..., "lost": 0,
     "recompiles_after_warmup": 0, ...}

Two load models:

* ``--mode closed`` — ``--concurrency`` workers each keep exactly one
  request in flight (submit → wait → repeat): measures sustainable
  throughput and in-system latency without overload.
* ``--mode open``   — requests are submitted on a fixed ``--qps``
  schedule regardless of completions (the arrival process real traffic
  has): driving QPS past capacity exercises deadline expiry and the shed
  watermark, and the emitted shed/expired rates show overload degrading
  gracefully instead of collapsing latency.

The offline baseline is the same Predictor forward + postprocess batch
loop WITHOUT the serving machinery (queues, threads, per-request demux),
at the identical bucket/batch size — ``ratio_vs_offline`` is the serving
overhead acceptance metric (ISSUE 2: >= 0.8).  ``--check`` turns the
invariants (zero lost requests, zero post-warmup recompiles, ratio
floor) into the exit code for ``make serve-smoke``.

Fleet tier (ISSUE 8, docs/SERVING.md "Fleet tier"): ``--fleet N`` runs
the same closed/open loops through an N-replica
:class:`~mx_rcnn_tpu.serve.fleet.FleetRouter`; ``--fleet_bench`` /
``--fleet_smoke`` run the full fleet measurement protocol and emit a
``FLEET_r08.json``-style record:

* **cold join** — one replica's time-to-serving in a FRESH process,
  trace-warm (today's path, persistent cache off) vs export-warm (AOT
  store + bundled cache), on the production-representative backbone;
* **export integrity** — every AOT program pinned bit-equal to the live
  trace, and zero post-join recompiles under mixed-bucket traffic;
* **router scaling** — closed-loop throughput at 1/2/4 replicas with a
  DEVICE-COMPUTE SIMULATOR (``--stub_ms`` sleep per dispatched batch,
  GIL released — exactly what an on-chip replica does to the host
  thread).  On this 1-core CPU box every real-model replica shares the
  same silicon, so real-model N-replica throughput is flat BY PHYSICS;
  the stub leg is the honest way to validate that the ROUTER (routing,
  queues, accounting, coalescing) sustains N-replica rates — the
  record carries both legs, labeled;
* **kill-mid-burst** — one replica killed under load: zero lost
  requests fleet-wide, stranded work rerouted, replica relaunched and
  rejoined.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import threading
import time
from typing import List

import numpy as np

from mx_rcnn_tpu.analysis import sanitizer
from mx_rcnn_tpu.config import Config, generate_config
from mx_rcnn_tpu.core.tester import Predictor
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.metrics import LoweringCounter
from mx_rcnn_tpu.serve.queue import (DeadlineExceeded, RequestFailed,
                                     ShedError)
from mx_rcnn_tpu.tools.train import add_set_arg, parse_set_overrides

logger = logging.getLogger("mx_rcnn_tpu")


def synthetic_images(cfg: Config, n: int, seed: int = 0
                     ) -> List[np.ndarray]:
    """``n`` random uint8 RGB images alternating landscape/portrait at
    the bucket canvas sizes, so mixed traffic exercises EVERY shape
    bucket (the recompile guard is only meaningful over mixed shapes)."""
    rng = np.random.RandomState(seed)
    buckets = [tuple(b) for b in cfg.bucket.shapes]
    return [rng.randint(0, 256,
                        size=buckets[i % len(buckets)] + (3,),
                        dtype=np.uint8)
            for i in range(n)]


def init_predictor(cfg: Config, prefix: str = None, epoch: int = 0,
                   seed: int = 0) -> Predictor:
    """Predictor from a checkpoint when given one, else from random init
    — serving throughput does not depend on the weight values.  With
    ``cfg.quant.enabled`` the returned predictor is the quantized one
    (held-out calibration sweep + tagged program keys — docs/PERF.md
    "Quantized inference"), so every serving CLI gains the quant mode
    through one ``--set quant__enabled=true``."""
    import jax

    from mx_rcnn_tpu.core.train import init_variables

    model = build_model(cfg)
    if prefix:
        from mx_rcnn_tpu.utils.checkpoint import load_param

        params, batch_stats = load_param(prefix, epoch)
    else:
        params, batch_stats = init_variables(
            model, jax.random.PRNGKey(seed),
            (1,) + tuple(cfg.bucket.shapes[0]) + (3,))
    if cfg.quant.enabled:
        from mx_rcnn_tpu.core.tester import quant_predictor

        return quant_predictor(cfg, params, batch_stats)
    return Predictor(model, {"params": params, "batch_stats": batch_stats},
                     cfg)


def offline_rate(engine: ServingEngine, reps: int = 12) -> float:
    """The comparison bar: full-batch Predictor forward + postprocess in
    a plain loop, no serving machinery, same bucket/batch size.  Buckets
    alternate like the serving traffic does."""
    b = engine.cfg.serve.batch_size
    batches = []
    for bucket in engine.buckets:
        bh, bw = bucket
        images = np.zeros((b, bh, bw, 3), np.float32)
        im_info = np.tile(np.array([bh, bw, 1.0], np.float32), (b, 1))
        batches.append((images, im_info))
    engine._run(*batches[0])  # ensure warm before timing
    t0 = time.perf_counter()
    for i in range(reps):
        engine._run(*batches[i % len(batches)])
    dt = time.perf_counter() - t0
    return reps * b / dt


def run_closed_loop(engine: ServingEngine, images, duration_s: float,
                    concurrency: int, timeout_ms: float) -> dict:
    """``concurrency`` workers, one request in flight each."""
    stop = time.monotonic() + duration_s
    outcomes = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
    lock = threading.Lock()

    def worker(wid: int):
        i = wid
        while time.monotonic() < stop:
            img = images[i % len(images)]
            i += concurrency
            try:
                engine.detect(img, timeout_ms=timeout_ms)
                key = "ok"
            except ShedError:
                key = "shed"
            except DeadlineExceeded:
                key = "expired"
            except (RequestFailed, TimeoutError):
                key = "failed"
            with lock:
                outcomes[key] += 1

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"wall_s": time.perf_counter() - t0, "client": outcomes}


def run_open_loop(engine: ServingEngine, images, duration_s: float,
                  qps: float, timeout_ms: float) -> dict:
    """Submit on a fixed schedule (no back-pressure from completions);
    collect every handle so no request outcome is dropped."""
    period = 1.0 / qps
    handles = []
    t0 = time.perf_counter()
    start = time.monotonic()
    k = 0
    while True:
        target = start + k * period
        now = time.monotonic()
        if now - start >= duration_s:
            break
        if now < target:
            time.sleep(target - now)
        handles.append(engine.submit(images[k % len(images)],
                                     timeout_ms=timeout_ms))
        k += 1
    outcomes = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
    for h in handles:
        try:
            h.wait(timeout=30.0)
            outcomes["ok"] += 1
        except ShedError:
            outcomes["shed"] += 1
        except DeadlineExceeded:
            outcomes["expired"] += 1
        except (RequestFailed, TimeoutError):
            outcomes["failed"] += 1
    return {"wall_s": time.perf_counter() - t0, "client": outcomes,
            "submitted": k}


# ---------------------------------------------------------------------------
# fleet tier (docs/SERVING.md "Fleet tier")
# ---------------------------------------------------------------------------

def make_stub_run_fn(cfg: Config, model_ms: float, seed: int = 0):
    """Device-compute simulator for the router-scaling legs: sleeps
    ``model_ms`` per dispatched batch with the GIL RELEASED (what an
    on-chip replica does to the host) and returns canned
    postprocess-shaped outputs, so the full engine path — preprocess,
    queues, coalescing, demux, accounting — runs for real while the
    model time parallelizes across replicas the way per-chip compute
    does.  Every use is labeled in the emitted record."""
    n = cfg.serve.batch_size
    r = cfg.test.rpn_post_nms_top_n
    c = cfg.num_classes
    rng = np.random.RandomState(seed)
    boxes = (rng.rand(n, r, 4 * c) * 100.0).astype(np.float32)
    scores = rng.rand(n, r, c).astype(np.float32)
    keep = np.zeros((n, c, r), bool)
    keep[:, 1:, :3] = True  # a few detections per class → real demux work

    def run_fn(images, im_info):
        time.sleep(model_ms / 1000.0)
        return boxes, scores, keep

    return run_fn


def make_content_stub_run_fn(cfg: Config, model_ms: float = 0.0):
    """Deterministic CONTENT-DEPENDENT stub (tests/test_bulk.py + the
    bulk sink's SIGKILL rig): every output row is a pure function of
    that row's pixels alone, so (a) identical images score identically
    regardless of micro-batch composition or replica — the property the
    bulk plane's byte-identity invariant rests on — and (b) two
    different images produce different lines, so a mis-ordered or
    mis-slotted sink cannot pass the bit-identity comparison."""
    r = cfg.test.rpn_post_nms_top_n
    c = cfg.num_classes

    def run_fn(images, im_info):
        if model_ms:
            time.sleep(model_ms / 1000.0)
        n = images.shape[0]
        boxes = np.zeros((n, r, 4 * c), np.float32)
        scores = np.zeros((n, r, c), np.float32)
        keep = np.zeros((n, c, r), bool)
        for j in range(n):
            m = np.float32(np.abs(images[j]).sum())
            x = np.float32(m % np.float32(37.0))
            boxes[j, 0, 4:8] = [x, x + 1.0, x + 5.0, x + 7.0]
            scores[j, 0, 1] = np.float32(0.5) + x / np.float32(100.0)
            keep[j, 1, 0] = True
        return boxes, scores, keep

    return run_fn


def _build_fleet(cfg: Config, replicas: int, model, variables, *,
                 export_root: str = None, stub_ms: float = None,
                 record=None):
    from mx_rcnn_tpu.serve.fleet import build_fleet

    fcfg = cfg.replace_in("fleet", replicas=replicas)
    factory = (None if stub_ms is None
               else (lambda rid: make_stub_run_fn(fcfg, stub_ms)))
    return build_fleet(fcfg, model, variables, export_root=export_root,
                       run_fn_factory=factory, record=record)


def _drain(target, timeout_s: float = 30.0) -> None:
    deadline = time.monotonic() + timeout_s
    while (target.metrics.snapshot()["in_flight"] > 0
           and time.monotonic() < deadline):
        time.sleep(0.05)


def _fleet_leg_record(run: dict, snap: dict) -> dict:
    c = snap["counters"]
    return {
        "imgs_per_sec": round(c["served"] / run["wall_s"], 2),
        "duration_s": round(run["wall_s"], 2),
        "p50_ms": snap["total_ms"]["p50"],
        "p99_ms": snap["total_ms"]["p99"],
        "served": c["served"], "shed": c["shed"],
        "expired": c["expired"], "failed": c["failed"],
        "submitted": c["submitted"],
        "shed_rate": round(c["shed"] / max(c["submitted"], 1), 4),
        "lost": c["submitted"] - snap["terminated"],
    }


def _run_join_bench(mode: str, network: str, dataset: str,
                    overrides: dict, export_dir: str = None,
                    timeout_s: float = 900.0) -> dict:
    """One cold-join measurement in a FRESH interpreter (imports and
    backend init excluded by the child's own timers; the record keeps
    ``total_s`` for the full picture)."""
    import subprocess
    import sys

    cmd = [sys.executable, "-m", "mx_rcnn_tpu.tools.fleet", "join_bench",
           "--mode", mode, "--network", network, "--dataset", dataset]
    for k, v in overrides.items():
        cmd += ["--set", f"{k}={v!r}" if isinstance(v, str) else
                f"{k}={v}"]
    if export_dir:
        cmd += ["--export_dir", export_dir]
    env = dict(os.environ)
    if mode == "trace":
        # the baseline must pay the full compile: strip any inherited
        # persistent-cache env (the child also clears the live config)
        for k in list(env):
            if k.startswith("JAX_COMPILATION_CACHE") \
                    or k.startswith("JAX_PERSISTENT_CACHE"):
                env.pop(k)
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=timeout_s, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"join_bench {mode} failed rc={out.returncode}:"
                           f"\n{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
    last = [ln for ln in out.stdout.strip().splitlines()
            if ln.startswith("{")][-1]
    return json.loads(last)


def _kill_mid_burst_leg(cfg: Config, model, variables, export_root: str,
                        duration_s: float, timeout_ms: float,
                        images) -> dict:
    """2-replica export-warm fleet, closed-loop burst, replica 0 killed
    mid-burst: the fleet-wide terminate-exactly-once + reroute +
    relaunch + rejoin leg."""
    kcfg = cfg.replace_in("fleet", health_interval_s=0.2)
    router = _build_fleet(kcfg, 2, model, variables,
                          export_root=export_root)
    try:
        concurrency = 2 * cfg.serve.batch_size * 2
        stop = time.monotonic() + duration_s
        kill_at = time.monotonic() + duration_s / 3.0
        outcomes = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
        lock = threading.Lock()

        def worker(wid: int):
            i = wid
            while time.monotonic() < stop:
                try:
                    router.detect(images[i % len(images)],
                                  timeout_ms=timeout_ms)
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
        for t in threads:
            t.start()
        while time.monotonic() < kill_at:
            time.sleep(0.02)
        victim = router.manager.replicas[0]
        served_before_kill = router.metrics.snapshot()["counters"]["served"]
        eng = victim.engine
        eng.kill()
        kill_t = time.monotonic()
        for t in threads:
            t.join()
        _drain(router)
        # wait for the relaunch to rejoin (RestartPolicy resets on
        # progress, so the delay is ~one health tick + the join itself)
        rejoin_s = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if victim.ready() and victim.generation >= 2:
                # stamp from the replica's OWN ready transition: the
                # drain above may have finished long after the rejoin
                rejoin_s = round(victim.joins[-1]["ready_t"] - kill_t, 2)
                break
            time.sleep(0.05)
        snap = router.metrics.snapshot()
        c = snap["counters"]
        return {
            "submitted": c["submitted"], "served": c["served"],
            "shed": c["shed"], "expired": c["expired"],
            "failed": c["failed"],
            "lost": c["submitted"] - snap["terminated"],
            "served_after_kill": c["served"] - served_before_kill,
            "rerouted": router.rerouted(),
            "ejects": router.manager.ejects,
            "relaunched": victim.generation >= 2,
            "rejoin_s": rejoin_s,
            "client_outcomes": outcomes,
        }
    finally:
        router.close()


def run_fleet_bench(args) -> int:
    """The FLEET_r08 measurement protocol (module docstring); emits one
    BENCH-style record and, under ``--check``, turns the fleet
    acceptance invariants into the exit code for ``make fleet-smoke``."""
    import tempfile

    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import (CACHE_SUBDIR,
                                          export_serve_programs)
    from mx_rcnn_tpu.serve.metrics import LoweringCounter

    smoke = args.fleet_smoke
    overrides = dict(_smoke_overrides()) if smoke else {}
    overrides.update(parse_set_overrides(args))
    cfg = generate_config(args.network, args.dataset, **overrides)
    workdir = args.workdir or tempfile.mkdtemp(prefix="fleet_bench_")
    os.makedirs(workdir, exist_ok=True)
    store_root = os.path.join(workdir, "store")
    # None → bounded 20 s bench default; explicit 0 keeps the engine
    # contract's no-deadline mode (same distinction as the single-engine
    # path below)
    timeout_ms = 20_000.0 if args.timeout_ms is None else args.timeout_ms
    dur = min(args.duration, 6.0) if smoke else args.duration
    rec: dict = {
        "metric": "fleet_scaling_x_at_2_replicas",
        "unit": "x",
        "measured": True,
        "smoke": smoke,
        "network": args.network,
        "bucket_shapes": [list(b) for b in cfg.bucket.shapes],
        "batch_size": cfg.serve.batch_size,
        "host": {"physical_cores": os.cpu_count()},
    }
    problems: List[str] = []

    # -- 1. export store (traffic model) + bit-equality pin -------------
    logger.info("[fleet] exporting serving programs → %s", store_root)
    enable_compile_cache(os.path.join(store_root, CACHE_SUBDIR))
    predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
    t0 = time.perf_counter()
    report = export_serve_programs(predictor, cfg, store_root)
    rec["export"] = {"bit_equal": report["bit_equal"],
                     "programs": len(report["programs"]),
                     "bytes": report["bytes"],
                     "export_s": round(time.perf_counter() - t0, 2)}
    if not report["bit_equal"]:
        problems.append("exported programs not bit-equal to live trace")

    # -- 2. cold join: trace-warm vs export-warm, fresh processes -------
    # the production-representative backbone for the full bench (compile
    # cost is what the export machinery exists to skip); the smoke stays
    # on the traffic model to fit the gate budget
    join_net = args.join_network if not smoke else args.network
    join_overrides = dict(overrides)
    if join_net != args.network:
        join_overrides = {"serve__batch_size": cfg.serve.batch_size}
    join_store = store_root
    if join_net != args.network:
        import subprocess
        import sys

        join_store = os.path.join(workdir, f"store_{join_net}")
        logger.info("[fleet] exporting %s join store → %s", join_net,
                    join_store)
        cmd = [sys.executable, "-m", "mx_rcnn_tpu.tools.fleet", "export",
               "--network", join_net, "--dataset", args.dataset,
               "--out", join_store]
        for k, v in join_overrides.items():
            cmd += ["--set", f"{k}={v}"]
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=1800)
        if out.returncode != 0:
            raise RuntimeError(f"join-store export failed:\n"
                               f"{out.stdout[-2000:]}\n"
                               f"{out.stderr[-2000:]}")
    logger.info("[fleet] cold-join leg: trace-warm (fresh process, "
                "no cache) ...")
    trace_join = _run_join_bench("trace", join_net, args.dataset,
                                 join_overrides)
    logger.info("[fleet] cold-join leg: export-warm (fresh process, "
                "AOT store) ...")
    export_join = _run_join_bench("export", join_net, args.dataset,
                                  join_overrides, export_dir=join_store)
    # the ratio compares JOIN OVERHEAD (warm_s minus the pure execution
    # of the dummy warmup batches, measured by a second all-resident
    # warmup pass): trace+compile vs deserialize+cache-read.  The
    # execution term is identical in both modes and, on a CPU backbone,
    # dwarfs both overheads (~30 s/bucket of raw conv math a TPU does in
    # ms) — comparing raw warm_s would measure the backbone, not the
    # store
    ratio = (export_join["overhead_s"] / trace_join["overhead_s"]
             if trace_join.get("overhead_s") else None)
    rec["cold_join"] = {
        "network": join_net,
        "trace_warm_s": trace_join["warm_s"],
        "trace_exec_s": trace_join["exec_s"],
        "trace_overhead_s": trace_join["overhead_s"],
        "trace_total_s": trace_join["total_s"],
        "export_warm_s": export_join["warm_s"],
        "export_exec_s": export_join["exec_s"],
        "export_overhead_s": export_join["overhead_s"],
        "export_total_s": export_join["total_s"],
        "ratio": round(ratio, 4) if ratio is not None else None,
        "note": "overhead_s = warm_s - exec_s: the trace+compile "
                "(resp. deserialize+cache-read) stage the AOT store "
                "addresses; exec_s is the dummy-batch model execution, "
                "identical in both modes (ms on a TPU, dominant on "
                "this CPU box); total_s additionally includes model "
                "build",
    }
    if ratio is None or ratio > args.max_join_ratio:
        problems.append(f"export-warm/trace-warm join-overhead ratio "
                        f"{ratio} > {args.max_join_ratio}")

    model, variables = predictor.model, predictor.variables
    images = synthetic_images(cfg, args.images, args.seed)

    # -- 3. real-model fleet legs (export-warm) + zero-recompile pin ----
    real: dict = {}
    for n_rep in ([1, 2] if not smoke else [2]):
        router = _build_fleet(cfg, n_rep, model, variables,
                              export_root=store_root)
        try:
            with LoweringCounter() as lc:
                run = run_closed_loop(
                    router, images, dur,
                    concurrency=4 * cfg.serve.batch_size * n_rep,
                    timeout_ms=timeout_ms)
                _drain(router)
            leg = _fleet_leg_record(run, router.metrics.snapshot())
            leg["recompiles_after_join"] = lc.n
            real[str(n_rep)] = leg
            if leg["lost"]:
                problems.append(f"real {n_rep}-replica leg lost "
                                f"{leg['lost']} requests")
            if lc.n:
                problems.append(f"real {n_rep}-replica leg recompiled "
                                f"{lc.n}x after join")
        finally:
            router.close()
    if "1" in real and "2" in real and real["1"]["imgs_per_sec"]:
        real["scaling_2r"] = round(real["2"]["imgs_per_sec"]
                                   / real["1"]["imgs_per_sec"], 3)
    real["note"] = ("all replicas share this host's "
                    f"{os.cpu_count()} CPU core(s): real-model scaling "
                    "here validates fleet overhead, not silicon — "
                    "per-chip scaling is the stub leg's subject")
    rec["real_model"] = real

    # -- 4. router-scaling legs (device-compute simulator) --------------
    stub: dict = {"mode": "stub-device-compute",
                  "stub_model_ms": args.stub_ms,
                  "note": "per-batch device compute simulated by a "
                          "GIL-releasing sleep, so replica 'chips' run "
                          "concurrently like real device subsets; "
                          "everything else (preprocess, routing, "
                          "queues, coalescing, demux, accounting) is "
                          "the production path"}
    sweep = [int(s) for s in args.fleet_sweep.split(",")]
    thr: dict = {}
    for n_rep in sweep:
        router = _build_fleet(cfg, n_rep, model, variables,
                              stub_ms=args.stub_ms)
        try:
            # 4x batch-per-replica keeps every (replica, bucket) lane a
            # spare full batch deep — at 2x the closed loop runs with
            # zero slack and measures its own resubmit latency, not the
            # router (observed: 1.0-1.4x "scaling" at 2 replicas)
            run = run_closed_loop(
                router, images, dur,
                concurrency=4 * cfg.serve.batch_size * n_rep,
                timeout_ms=timeout_ms)
            _drain(router)
            leg = _fleet_leg_record(run, router.metrics.snapshot())
            thr[str(n_rep)] = leg
            if leg["lost"]:
                problems.append(f"stub {n_rep}-replica leg lost "
                                f"{leg['lost']} requests")
        finally:
            router.close()
    stub["replicas"] = thr
    base = thr[str(sweep[0])]["imgs_per_sec"]
    for n_rep in sweep[1:]:
        if base:
            stub[f"scaling_{n_rep}r"] = round(
                thr[str(n_rep)]["imgs_per_sec"] / base, 3)
    rec["router_scaling"] = stub
    scalings = [k for k in stub if k.startswith("scaling_")]
    rec["value"] = stub.get("scaling_2r") or (
        stub[scalings[0]] if scalings else None)
    if "scaling_2r" in stub:
        if stub["scaling_2r"] < args.min_scaling:
            problems.append(f"router scaling at 2 replicas "
                            f"{stub['scaling_2r']} < {args.min_scaling}")
    else:
        # scaling keys are relative to the sweep's first entry; a custom
        # --fleet_sweep without the 1→2 pair has no 2-replica claim to gate
        logger.warning("--fleet_sweep %s has no 1→2 pair — the "
                       "min-scaling gate is skipped", args.fleet_sweep)

    # -- 5. shed-rate curve (overdriven open loop, 2-replica stub) ------
    # per-replica capacity: each bucket's dispatcher pipelines its own
    # batches, so capacity = buckets x batch / stub_ms per replica
    capacity = (2 * len(cfg.bucket.shapes) * cfg.serve.batch_size
                / (args.stub_ms / 1000.0))
    curve = []
    # smoke overdrive is 2.5x: at 1.5x the short window ends before the
    # backlog (excess qps spread over 4 lanes) reaches the watermark
    for factor in ([0.6, 2.5] if smoke else [0.6, 1.0, 1.5, 2.5]):
        router = _build_fleet(cfg, 2, model, variables,
                              stub_ms=args.stub_ms)
        try:
            run = run_open_loop(router, images, max(dur / 2, 2.0),
                                qps=capacity * factor,
                                timeout_ms=timeout_ms)
            _drain(router)
            leg = _fleet_leg_record(run, router.metrics.snapshot())
            curve.append({"qps_target": round(capacity * factor, 1),
                          "load_factor": factor, **leg})
            if leg["lost"]:
                problems.append(f"shed-curve leg x{factor} lost "
                                f"{leg['lost']} requests")
        finally:
            router.close()
    rec["shed_curve"] = {"stub_capacity_imgs_per_sec": round(capacity, 1),
                         "legs": curve}
    over = [l for l in curve if l["load_factor"] > 1.0]
    if over and all(l["shed_rate"] == 0 for l in over):
        problems.append("overdriven legs shed nothing — watermark "
                        "shedding not composing at fleet level")

    # -- 6. kill-mid-burst: reroute + relaunch + exactly-once -----------
    logger.info("[fleet] kill-mid-burst leg ...")
    kill = _kill_mid_burst_leg(cfg, model, variables, store_root,
                               duration_s=max(dur, 4.0),
                               timeout_ms=timeout_ms, images=images)
    rec["kill_mid_burst"] = kill
    if kill["lost"]:
        problems.append(f"kill leg lost {kill['lost']} requests")
    if not kill["relaunched"]:
        problems.append("killed replica did not relaunch+rejoin")
    if kill["served_after_kill"] <= 0:
        problems.append("no requests served after the kill")

    print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    if args.check:
        problems += sanitizer.check_problems()
        for msg in problems:
            logger.error("CHECK FAILED: %s", msg)
        return 1 if problems else 0
    return 0


def _smoke_overrides() -> dict:
    """The `make serve-smoke` canvas: the quick-tier 128x160 tiny-model
    buckets (compiles in seconds on one CPU core) with eval-scale ROI
    counts shrunk to keep the smoke under a minute."""
    return {
        "bucket__scale": 128, "bucket__max_size": 160,
        "bucket__shapes": ((128, 160), (160, 128)),
        "test__rpn_pre_nms_top_n": 512, "test__rpn_post_nms_top_n": 64,
        "serve__batch_size": 2, "serve__max_delay_ms": 20.0,
    }


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    # opt-in lock sanitizer (make threadlint-smoke runs the serve legs
    # with MXRCNN_THREAD_SANITIZER=strict; docs/ANALYSIS.md "threadlint")
    sanitizer.maybe_install_from_env()
    p = argparse.ArgumentParser(
        description="Serving load generator + BENCH JSON "
                    "(docs/SERVING.md)")
    p.add_argument("--network", default="tiny",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="synthetic",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard"])
    p.add_argument("--prefix", default=None,
                   help="checkpoint prefix (default: random init — "
                        "throughput does not depend on weights)")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--mode", default="closed", choices=["closed", "open"])
    p.add_argument("--duration", type=float, default=20.0,
                   help="measurement window, seconds")
    p.add_argument("--concurrency", type=int, default=None,
                   help="closed-loop workers (default: 2x batch_size — "
                        "enough to keep every micro-batch full)")
    p.add_argument("--qps", type=float, default=20.0,
                   help="open-loop arrival rate")
    p.add_argument("--timeout_ms", type=float, default=None,
                   help="per-request deadline (default: "
                        "cfg.serve.default_timeout_ms)")
    p.add_argument("--images", type=int, default=16,
                   help="distinct synthetic images to cycle through")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None,
                   help="also write the JSON record to this path")
    p.add_argument("--check", action="store_true",
                   help="exit 1 unless: zero lost requests, zero "
                        "post-warmup recompiles, ratio_vs_offline >= "
                        "--min_ratio")
    p.add_argument("--min_ratio", type=float, default=0.5,
                   help="--check floor for serving/offline throughput "
                        "(0.5 for the contended-smoke gate; the "
                        "acceptance measurement in docs/SERVING.md "
                        "records the real ratio)")
    p.add_argument("--smoke", action="store_true",
                   help="small-canvas preset for `make serve-smoke` "
                        "(tiny net, 128x160 buckets, short window)")
    # fleet tier (docs/SERVING.md "Fleet tier")
    p.add_argument("--fleet", type=int, default=0,
                   help="run the closed/open loop through an N-replica "
                        "FleetRouter instead of a single engine")
    p.add_argument("--export_dir", default=None,
                   help="--fleet: warm replicas from this AOT export "
                        "store (default: trace-warm)")
    p.add_argument("--fleet_bench", action="store_true",
                   help="run the full FLEET_r08 measurement protocol "
                        "(cold join, bit-equality, router scaling, shed "
                        "curves, kill-mid-burst) and emit one record")
    p.add_argument("--fleet_smoke", action="store_true",
                   help="gate-scale --fleet_bench for `make fleet-smoke` "
                        "(tiny canvas, short windows, lenient join "
                        "ratio)")
    p.add_argument("--fleet_sweep", default="1,2,4",
                   help="replica counts for the router-scaling legs")
    p.add_argument("--stub_ms", type=float, default=150.0,
                   help="simulated per-batch device-compute time for "
                        "the router-scaling legs (GIL-releasing sleep)."
                        "  Sized so simulated device time dominates the "
                        "host-side per-request work this 1-core box "
                        "serializes — the leg measures the ROUTER, not "
                        "the GIL")
    p.add_argument("--join_network", default="resnet50",
                   help="backbone for the cold-join legs of the full "
                        "bench (compile cost is the quantity under "
                        "test; the smoke reuses --network)")
    p.add_argument("--max_join_ratio", type=float, default=None,
                   help="--check ceiling for export-warm/trace-warm "
                        "cold-join time (default 0.10 bench / 0.50 "
                        "smoke — tiny-model smoke programs compile in "
                        "seconds, so fixed per-program costs dominate)")
    p.add_argument("--min_scaling", type=float, default=1.8,
                   help="--check floor for router-leg throughput "
                        "scaling at 2 replicas")
    p.add_argument("--workdir", default=None,
                   help="fleet bench working directory (export stores; "
                        "default: a fresh temp dir)")
    p.add_argument("--crosshost_bench", action="store_true",
                   help="run the cross-host battery (subprocess agents "
                        "+ binary wire + store pull + live scheduler — "
                        "tools/crosshost.py) and emit CROSSHOST_r15-"
                        "style JSON")
    p.add_argument("--crosshost_smoke", action="store_true",
                   help="gate-scale --crosshost_bench for `make "
                        "crosshost-smoke` (2 hosts, short bursts)")
    p.add_argument("--crosshost_sweep", default="1,2,4",
                   help="host counts for the cross-host scaling legs")
    p.add_argument("--min_wire_ratio", type=float, default=1.05,
                   help="--check floor for binary/JSON prepared-wire "
                        "throughput (at CPU saturation the ratio is "
                        "total-cost-per-request, so the codec tax is "
                        "diluted by the shared HTTP/dispatch cost — "
                        "the per-request p50 gap in the record is the "
                        "sharper signal)")
    p.add_argument("--min_crosshost_scaling", type=float, default=1.9,
                   help="--check floor for 2-host stub scaling (the "
                        "4-host floor is 2x this)")
    p.add_argument("--wire_bench", action="store_true",
                   help="run the wire data-plane battery (v1-fp32 / "
                        "v2-u8 / +coalesce / +adaptive arms + SIGKILL-"
                        "mid-envelope — tools/wire_bench.py) and emit "
                        "WIRE_r20-style JSON")
    p.add_argument("--wire_smoke", action="store_true",
                   help="gate-scale --wire_bench for `make wire-smoke` "
                        "(short windows, same arms and kill leg)")
    p.add_argument("--max_wire_bytes_ratio", type=float, default=0.30,
                   help="--check ceiling for v2-u8/v1-fp32 bytes per "
                        "image (measured counters AND production-"
                        "bucket codec math)")
    p.add_argument("--min_wire_speedup", type=float, default=1.8,
                   help="--check floor for the coalesced-arm/v1-arm "
                        "wire-leg throughput ratio")
    add_set_arg(p)
    args = p.parse_args(argv)

    if args.wire_bench or args.wire_smoke:
        from mx_rcnn_tpu.tools.wire_bench import run_wire_bench

        return run_wire_bench(args)

    if args.crosshost_bench or args.crosshost_smoke:
        from mx_rcnn_tpu.tools.crosshost import run_crosshost_bench

        return run_crosshost_bench(args)

    if args.fleet_bench or args.fleet_smoke:
        if args.max_join_ratio is None:
            args.max_join_ratio = 0.5 if args.fleet_smoke else 0.10
        if args.fleet_smoke and args.fleet_sweep == "1,2,4":
            args.fleet_sweep = "1,2"  # gate budget: the scaling floor
        return run_fleet_bench(args)

    overrides = {}
    if args.smoke:
        overrides.update(_smoke_overrides())
        args.duration = min(args.duration, 12.0)
    overrides.update(parse_set_overrides(args))
    cfg = generate_config(args.network, args.dataset, **overrides)
    concurrency = args.concurrency or 2 * cfg.serve.batch_size
    timeout_ms = (cfg.serve.default_timeout_ms if args.timeout_ms is None
                  else args.timeout_ms)

    # obs (off by default): the loadgen is an entry point like any
    # other — a bench run with obs on gets a runs/<id>/ record and,
    # with the time-series plane on, sampled windows over the window
    # it measures (docs/OBSERVABILITY.md)
    from mx_rcnn_tpu.obs.runrec import cli_obs

    obs_sess = cli_obs(cfg, "loadgen")

    predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
    images = synthetic_images(cfg, args.images, args.seed)

    if args.fleet:
        logger.info("building %d-replica fleet (%s) ...", args.fleet,
                    f"export-warm from {args.export_dir}"
                    if args.export_dir else "trace-warm")
        engine = _build_fleet(cfg, args.fleet, predictor.model,
                              predictor.variables,
                              export_root=args.export_dir,
                              record=obs_sess.record if obs_sess
                              else None)
        off = None  # offline baseline is a single-engine concept
    else:
        engine = ServingEngine(predictor, cfg)
        logger.info("warmup: compiling %d bucket program(s) at batch %d "
                    "...", len(engine.buckets), cfg.serve.batch_size)
        t0 = time.perf_counter()
        engine.warmup()
        logger.info("warmup done in %.1fs", time.perf_counter() - t0)
        logger.info("offline baseline (no serving machinery) ...")
        off = offline_rate(engine)
        logger.info("offline: %.2f imgs/s at batch %d", off,
                    cfg.serve.batch_size)

    # fresh metrics for the measured window (warmup batches excluded)
    engine.metrics.reset()
    logger.info("load: mode=%s duration=%.0fs %s", args.mode,
                args.duration,
                f"concurrency={concurrency}" if args.mode == "closed"
                else f"qps={args.qps}")
    with LoweringCounter() as lc:
        if args.mode == "closed":
            run = run_closed_loop(engine, images, args.duration,
                                  concurrency, timeout_ms)
        else:
            run = run_open_loop(engine, images, args.duration, args.qps,
                                timeout_ms)
        # drain: every submitted request must reach a terminal state
        deadline = time.monotonic() + 30.0
        while (engine.metrics.snapshot()["in_flight"] > 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
    snap = engine.metrics.snapshot()
    engine.close()

    c = snap["counters"]
    lost = c["submitted"] - snap["terminated"]
    served_rate = c["served"] / run["wall_s"]
    rec = {
        "metric": "serve_imgs_per_sec",
        "value": round(served_rate, 2),
        "unit": "imgs/s",
        "measured": True,
        "mode": args.mode,
        "network": args.network,
        "bucket_shapes": [list(b) for b in cfg.bucket.shapes],
        "batch_size": cfg.serve.batch_size,
        "max_delay_ms": cfg.serve.max_delay_ms,
        "duration_s": round(run["wall_s"], 2),
        "concurrency": concurrency if args.mode == "closed" else None,
        "qps_target": args.qps if args.mode == "open" else None,
        "fleet_replicas": args.fleet or None,
        "offline_imgs_per_sec": round(off, 2) if off else None,
        "ratio_vs_offline": round(served_rate / off, 3) if off else None,
        "p50_ms": snap["total_ms"]["p50"],
        "p90_ms": snap["total_ms"]["p90"],
        "p99_ms": snap["total_ms"]["p99"],
        "queue_wait_p99_ms": snap["queue_wait_ms"]["p99"],
        "model_ms_p50": snap["model_ms"]["p50"],
        "batch_occupancy_mean": snap["batch_occupancy"]["mean_rows"],
        "served": c["served"], "shed": c["shed"],
        "expired": c["expired"], "failed": c["failed"],
        "submitted": c["submitted"],
        "shed_rate": round(c["shed"] / max(c["submitted"], 1), 4),
        "expired_rate": round(c["expired"] / max(c["submitted"], 1), 4),
        "lost": lost,
        "recompiles_after_warmup": lc.n,
        "client_outcomes": run["client"],
    }
    if obs_sess is not None:
        obs_sess.close(metric=rec["metric"], value=rec["value"],
                       unit=rec["unit"], mode=args.mode)
    print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    if args.check:
        problems = sanitizer.check_problems()
        if lost != 0:
            problems.append(f"{lost} requests lost (no terminal state)")
        if lc.n != 0:
            problems.append(f"{lc.n} recompiles after warmup")
        if rec["ratio_vs_offline"] is not None \
                and rec["ratio_vs_offline"] < args.min_ratio:
            problems.append(
                f"serving/offline ratio {rec['ratio_vs_offline']} < "
                f"{args.min_ratio}")
        if c["served"] == 0:
            problems.append("zero requests served")
        for msg in problems:
            logger.error("CHECK FAILED: %s", msg)
        return 1 if problems else 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
