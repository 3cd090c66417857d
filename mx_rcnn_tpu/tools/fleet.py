"""Fleet serving CLI: AOT export store + N-replica HTTP service.

No reference equivalent.  Three subcommands (docs/SERVING.md "Fleet
tier"):

* ``export`` — trace + ``jax.export``-serialize every per-bucket serving
  program (and the eval forward) into an export store, verify each
  program BIT-EQUAL to the live trace, and populate the store's bundled
  persistent XLA cache — the artifact a cold replica joins from in
  seconds::

      python -m mx_rcnn_tpu.tools.fleet export --network resnet101 \\
          --prefix model/e2e --epoch 10 --out model/export

* ``serve`` — replica manager + join-shortest-queue router behind the
  same stdlib HTTP front end as ``tools/serve.py`` (``POST /detect``,
  ``GET /healthz`` now reporting per-replica state, ``GET /metrics``
  with fleet-level accounting)::

      python -m mx_rcnn_tpu.tools.fleet serve --replicas 4 \\
          --export_dir model/export --prefix model/e2e --epoch 10

* ``join_bench`` — measure ONE replica's cold-join in a fresh process
  (``--mode trace``: today's trace+compile path, persistent cache off;
  ``--mode export``: AOT store + bundled cache) and print the timing
  JSON.  ``tools/loadgen.py --fleet_bench`` drives both modes and
  records the ratio in ``FLEET_r08.json``.
"""

from __future__ import annotations

import argparse
import json
import logging
import time

logger = logging.getLogger("mx_rcnn_tpu")


def _add_model_args(p: argparse.ArgumentParser) -> None:
    from mx_rcnn_tpu.tools.train import add_set_arg

    p.add_argument("--network", default="tiny",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="synthetic",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard", "synthetic_stream"])
    p.add_argument("--prefix", default=None,
                   help="checkpoint prefix (default: random init)")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    add_set_arg(p)


def _config(args):
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.tools.train import parse_set_overrides

    return generate_config(args.network, args.dataset,
                           **parse_set_overrides(args))


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="Fleet serving: AOT export + replica manager + "
                    "router (docs/SERVING.md 'Fleet tier')")
    sub = p.add_subparsers(dest="cmd", required=True)

    pe = sub.add_parser("export", help="write + verify an export store")
    _add_model_args(pe)
    pe.add_argument("--out", required=True, help="export store directory")
    pe.add_argument("--eval_batch", type=int, default=None,
                    help="also export the eval Predictor forward at this "
                         "batch size")
    pe.add_argument("--no_verify", action="store_true",
                    help="skip the bit-equality pin (also skips "
                         "populating the bundled XLA cache — joins then "
                         "pay the compile once)")

    ps = sub.add_parser("serve", help="N-replica fleet HTTP service")
    _add_model_args(ps)
    ps.add_argument("--replicas", type=int, default=None,
                    help="replica count (default cfg.fleet.replicas)")
    ps.add_argument("--export_dir", default=None,
                    help="warm replicas from this export store "
                         "(default cfg.fleet.export_dir; empty = "
                         "trace-warm)")
    ps.add_argument("--host", default="127.0.0.1")
    ps.add_argument("--port", type=int, default=8080)
    ps.add_argument("--class_names", default=None)
    ps.add_argument("--no_warmup", action="store_true",
                    help=argparse.SUPPRESS)  # parity with tools/serve.py

    pj = sub.add_parser("join_bench",
                        help="time one replica's cold join (fresh "
                             "process) and print JSON")
    _add_model_args(pj)
    pj.add_argument("--mode", required=True, choices=["trace", "export"])
    pj.add_argument("--export_dir", default=None,
                    help="store for --mode export")
    return p.parse_args(argv)


def _init_predictor(cfg, args):
    from mx_rcnn_tpu.tools.loadgen import init_predictor

    return init_predictor(cfg, args.prefix, args.epoch, args.seed)


def cmd_export(args) -> int:
    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import (CACHE_SUBDIR,
                                          export_serve_programs)

    cfg = _config(args)
    import os

    from mx_rcnn_tpu.obs.runrec import cli_obs

    # the verify pass compiles every exported program — pointing the
    # persistent cache INTO the store makes those compiles the cache
    # entries a joining replica will read
    enable_compile_cache(os.path.join(args.out, CACHE_SUBDIR))
    obs_sess = cli_obs(cfg, "fleet_export")
    report = None
    try:
        predictor = _init_predictor(cfg, args)
        t0 = time.perf_counter()
        report = export_serve_programs(predictor, cfg, args.out,
                                       eval_batch=args.eval_batch,
                                       verify=not args.no_verify)
        report["export_s"] = round(time.perf_counter() - t0, 2)
    finally:
        if obs_sess is not None:
            obs_sess.close(metric="fleet_export_s",
                           value=(report or {}).get("export_s"),
                           unit="s", store=args.out)
    print(json.dumps(report))
    return 0


def cmd_serve(args) -> int:
    cfg = _config(args)
    if args.replicas:
        cfg = cfg.replace_in("fleet", replicas=args.replicas)
    export_dir = (cfg.fleet.export_dir if args.export_dir is None
                  else args.export_dir)
    import os

    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import CACHE_SUBDIR

    # an export store bundles its own cache; without one the fixed
    # in-checkout cache serves restarts (runtime.py has the rule)
    enable_compile_cache(os.path.join(export_dir, CACHE_SUBDIR)
                         if export_dir else None)

    from mx_rcnn_tpu.obs.runrec import cli_obs

    obs_sess = cli_obs(cfg, "fleet")
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.fleet import build_fleet
    from mx_rcnn_tpu.serve.server import make_server

    model = build_model(cfg)
    if args.prefix:
        from mx_rcnn_tpu.utils.checkpoint import load_param

        params, batch_stats = load_param(args.prefix, args.epoch)
    else:
        import jax

        from mx_rcnn_tpu.core.train import init_variables

        params, batch_stats = init_variables(
            model, jax.random.PRNGKey(args.seed),
            (1,) + tuple(cfg.bucket.shapes[0]) + (3,))
    variables = {"params": params, "batch_stats": batch_stats}
    if cfg.quant.enabled:
        # quantized fleet (docs/PERF.md "Quantized inference"): every
        # replica's Predictor is built from these shared variables, so
        # the calibration sweep runs ONCE here; the export-store
        # admission check (serve/export.py) refuses a store whose quant
        # knobs/fingerprint disagree with what this derives
        from mx_rcnn_tpu.core.tester import calibrate_quant

        variables["quant"] = calibrate_quant(cfg, params, batch_stats)
        logger.info("quant fleet: %s/%s calibrated", cfg.quant.dtype,
                    cfg.quant.mode)
    logger.info("launching %d replica(s), %s ...", cfg.fleet.replicas,
                f"export-warm from {export_dir}" if export_dir
                else "trace-warm")
    router = build_fleet(cfg, model, variables,
                         export_root=export_dir or None,
                         record=obs_sess.record if obs_sess else None)
    if obs_sess is not None and obs_sess.flight is not None:
        # a flight record from this process should carry the fleet's
        # shape at dump time, not just its metrics
        obs_sess.flight.add_context("fleet", router.healthz)
    names = args.class_names.split(",") if args.class_names else None
    srv = make_server(router, args.host, args.port, class_names=names,
                      max_body_mb=cfg.serve.max_body_mb)
    host, port = srv.server_address[:2]
    logger.info("fleet serving on http://%s:%d  (%d replicas ready; "
                "POST /detect, GET /healthz, GET /metrics)", host, port,
                router.healthz()["ready"])
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        srv.server_close()
        router.close()
        if obs_sess is not None:
            snap = router.metrics.snapshot()
            obs_sess.close(metric="fleet_requests_served",
                           value=snap["counters"]["served"],
                           unit="requests")
    return 0


def cmd_join_bench(args) -> int:
    """One replica's cold join, timed in THIS (fresh) process: build the
    predictor, then warm it — trace mode re-traces and re-compiles with
    the persistent cache OFF (today's path); export mode loads the AOT
    store through its bundled cache.  Prints one JSON line."""
    import jax

    cfg = _config(args)
    if args.mode == "export":
        if not args.export_dir:
            raise SystemExit("--mode export requires --export_dir")
        import os

        from mx_rcnn_tpu.runtime import enable_compile_cache
        from mx_rcnn_tpu.serve.export import CACHE_SUBDIR

        enable_compile_cache(os.path.join(args.export_dir, CACHE_SUBDIR))
    else:
        # the trace-warm baseline measures tracing + compiling: it must
        # not read a cache some earlier run populated
        jax.config.update("jax_enable_compilation_cache", False)

    from mx_rcnn_tpu.obs.runrec import cli_obs
    from mx_rcnn_tpu.serve.engine import ServingEngine

    obs_sess = cli_obs(cfg, "join_bench")
    t_start = time.perf_counter()
    predictor = _init_predictor(cfg, args)
    t_build = time.perf_counter() - t_start
    engine = ServingEngine(predictor, cfg, start=False)
    t0 = time.perf_counter()
    if args.mode == "export":
        from mx_rcnn_tpu.serve.export import ExportStore

        join = engine.warm_from_export(ExportStore(args.export_dir))
    else:
        engine.warmup()
        join = {}
    warm_s = time.perf_counter() - t0
    first = list(engine.last_warmup_run_s)
    # second warmup pass: every program is resident, so each bucket's
    # run times the pure MODEL EXECUTION of its dummy batch — identical
    # in both modes and huge on a CPU backbone (a TPU executes it in
    # ms).  Pairing each bucket's first call with its ADJACENT second
    # call splits join overhead (trace+compile, resp.
    # deserialize+cache-read — the stage the AOT store addresses) from
    # execution without cross-minute load drift.
    engine.warmup()
    second = engine.last_warmup_run_s
    exec_s = sum(second)
    overhead_s = sum(max(a - b, 0.0) for a, b in zip(first, second)) \
        + join.get("load_s", 0.0)
    doc = {
        "mode": args.mode,
        "build_s": round(t_build, 3),
        "warm_s": round(warm_s, 3),
        "exec_s": round(exec_s, 3),
        "overhead_s": round(max(overhead_s, 0.001), 3),
        "total_s": round(time.perf_counter() - t_start, 3),
        "programs": engine.program_count(),
        **{k: v for k, v in join.items() if k in ("load_s",)},
    }
    if obs_sess is not None:
        obs_sess.close(metric="join_total_s", value=doc["total_s"],
                       unit="s", mode=args.mode)
    print(json.dumps(doc))
    return 0


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    # opt-in lock sanitizer (MXRCNN_THREAD_SANITIZER; docs/ANALYSIS.md)
    from mx_rcnn_tpu.analysis import sanitizer

    sanitizer.maybe_install_from_env()
    args = parse_args(argv)
    return {"export": cmd_export, "serve": cmd_serve,
            "join_bench": cmd_join_bench}[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
