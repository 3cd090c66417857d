"""Online detection serving entry point: checkpoint → warmed HTTP service.

No reference equivalent (the reference has no online inference path).
Builds the model from a training checkpoint, wraps it in the
micro-batching :class:`~mx_rcnn_tpu.serve.engine.ServingEngine`,
pre-compiles every shape-bucket program (so no client ever pays an XLA
compile), and serves ``/detect`` / ``/healthz`` / ``/metrics`` over
stdlib HTTP (``serve/server.py``).  Policy knobs live in
``cfg.serve`` — override any of them with
``--set serve__batch_size=8`` etc.  Architecture and measured numbers:
``docs/SERVING.md``.
"""

from __future__ import annotations

import argparse
import logging

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.server import make_server
from mx_rcnn_tpu.tools.train import add_set_arg, parse_set_overrides

logger = logging.getLogger("mx_rcnn_tpu")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="Serve a Faster R-CNN checkpoint over HTTP "
                    "(docs/SERVING.md)")
    p.add_argument("--network", default="resnet101",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="PascalVOC",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard"])
    p.add_argument("--prefix", default="model/e2e")
    p.add_argument("--epoch", type=int, required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--class_names", default=None,
                   help="comma-separated class names (index 0 = "
                        "background); default labels are cls<N>")
    p.add_argument("--no_warmup", action="store_true",
                   help="skip the startup pre-compile pass (first "
                        "request per bucket then pays the compile)")
    add_set_arg(p)
    return p.parse_args(argv)


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    # opt-in lock sanitizer (MXRCNN_THREAD_SANITIZER; docs/ANALYSIS.md
    # "threadlint") — a live server can run with real-order recording on
    from mx_rcnn_tpu.analysis import sanitizer

    sanitizer.maybe_install_from_env()
    args = parse_args(argv)
    cfg = generate_config(args.network, args.dataset,
                          **parse_set_overrides(args))
    # persistent XLA cache: a restarted server's warmup pays tracing
    # only (docs/FT.md "Recovery-time levers"; the fleet CLI's export
    # stores bundle their own cache instead)
    from mx_rcnn_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    # observability (docs/OBSERVABILITY.md): publish serving metrics into
    # the PROCESS registry (so /metrics is the unified scrape), write a
    # runs/<id>/ record, optionally collect spans / arm SIGUSR2.  CliObs
    # owns the wiring AND the fail-soft teardown, shared with
    # tools/train.py
    from mx_rcnn_tpu.obs.runrec import cli_obs

    obs_sess = cli_obs(cfg, "serve")
    metrics = None
    if obs_sess is not None:
        from mx_rcnn_tpu.obs.metrics import ServeMetrics, registry

        metrics = ServeMetrics(registry=registry())
    # checkpoint → predictor, quantized when cfg.quant.enabled (the
    # shared serving-CLI bootstrapping — docs/PERF.md "Quantized
    # inference"; one --set quant__enabled=true away)
    from mx_rcnn_tpu.tools.loadgen import init_predictor

    predictor = init_predictor(cfg, args.prefix, args.epoch)
    if cfg.quant.enabled:
        logger.info("quant serving: %s/%s fingerprint=%s", cfg.quant.dtype,
                    cfg.quant.mode, predictor.quant_fingerprint)
    engine = ServingEngine(predictor, cfg, metrics=metrics)
    if not args.no_warmup:
        logger.info("warming %d bucket(s) at batch %d ...",
                    len(engine.buckets), cfg.serve.batch_size)
        engine.warmup()
    if obs_sess is not None and obs_sess.flight is not None:
        # a flight record from this process should carry the engine's
        # queue/warmup state at dump time, not just its metrics
        obs_sess.flight.add_context("engine", engine.healthz)
    names = args.class_names.split(",") if args.class_names else None
    srv = make_server(engine, args.host, args.port, class_names=names,
                      max_body_mb=cfg.serve.max_body_mb)
    host, port = srv.server_address[:2]
    logger.info("serving on http://%s:%d  (POST /detect, GET /healthz, "
                "GET /metrics)", host, port)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        srv.server_close()
        engine.close()
        if obs_sess is not None:
            snap = engine.metrics.snapshot()
            obs_sess.record.event("serve_stats", **snap["counters"])
            obs_sess.close(metric="serve_requests_served",
                           value=snap["counters"]["served"],
                           unit="requests")


if __name__ == "__main__":
    main()
