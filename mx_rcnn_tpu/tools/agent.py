"""Per-host replica agent CLI: the cross-host fleet's host-side entry
point (docs/SERVING.md "Cross-host tier").

Runs one :class:`~mx_rcnn_tpu.serve.agent.ReplicaAgent` — pull the
export store (when ``--store_url`` / ``crosshost.store_url`` points at
a head's store server), build ``crosshost.agent_replicas`` local
replicas, and serve the agent HTTP surface the head consumes
(``/healthz``, ``/metrics``, binary ``/prepared``, ``/detect``,
``POST /replicas``)::

    python -m mx_rcnn_tpu.tools.agent --port 9201 \\
        --store_url http://head:9200 --export_dir /tmp/store \\
        --replicas 2

``--stub_ms`` / ``--stub content`` swap the model path for the loadgen
stubs — the multi-process bench rig (``tools/loadgen.py
--crosshost_bench``) launches its "hosts" this way so router/wire/
scheduler behavior measures without N copies of model compute fighting
for one CPU core.  One JSON ready-line goes to stdout once the server
is bound (the rig's subprocess handshake); logs go to stderr.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys

logger = logging.getLogger("mx_rcnn_tpu")


def parse_args(argv=None) -> argparse.Namespace:
    from mx_rcnn_tpu.tools.fleet import _add_model_args

    p = argparse.ArgumentParser(
        description="Per-host replica agent (docs/SERVING.md "
                    "'Cross-host tier')")
    _add_model_args(p)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 binds a free port (reported in the ready "
                        "line)")
    p.add_argument("--replicas", type=int, default=None,
                   help="local replica count (default "
                        "cfg.crosshost.agent_replicas)")
    p.add_argument("--store_url", default=None,
                   help="head store server to pull the export store "
                        "from (default cfg.crosshost.store_url; empty "
                        "= no pull)")
    p.add_argument("--export_dir", default=None,
                   help="local export-store path: pull target and/or "
                        "warm source (default cfg.fleet.export_dir)")
    p.add_argument("--class_names", default=None)
    p.add_argument("--stub_ms", type=float, default=None,
                   help="replace the model with a GIL-releasing sleep "
                        "stub of this many ms per batch (bench rig)")
    p.add_argument("--stub", default="plain",
                   choices=["plain", "content"],
                   help="stub flavor for --stub_ms: 'content' is the "
                        "deterministic content-dependent stub the bulk "
                        "byte-identity leg needs")
    return p.parse_args(argv)


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    from mx_rcnn_tpu.analysis import sanitizer

    sanitizer.maybe_install_from_env()
    args = parse_args(argv)

    from mx_rcnn_tpu.tools.fleet import _config

    cfg = _config(args)
    if args.replicas:
        cfg = cfg.replace_in("crosshost", agent_replicas=args.replicas)
    if args.store_url is not None:
        cfg = cfg.replace_in("crosshost", store_url=args.store_url)
    if args.export_dir is not None:
        cfg = cfg.replace_in("fleet", export_dir=args.export_dir)
    if cfg.fleet.export_dir and args.stub_ms is None:
        import os

        from mx_rcnn_tpu.runtime import enable_compile_cache
        from mx_rcnn_tpu.serve.export import CACHE_SUBDIR

        # warm through the store's bundled XLA cache — the pulled store
        # carries it, so the join pays deserialize + cache read, not a
        # compile (the 0-post-warm-recompiles acceptance)
        enable_compile_cache(os.path.join(cfg.fleet.export_dir,
                                          CACHE_SUBDIR))

    run_fn_factory = None
    if args.stub_ms is not None:
        from mx_rcnn_tpu.tools.loadgen import (make_content_stub_run_fn,
                                               make_stub_run_fn)

        if args.stub == "content":
            run_fn_factory = (lambda rid:
                              make_content_stub_run_fn(cfg, args.stub_ms))
        else:
            run_fn_factory = (lambda rid:
                              make_stub_run_fn(cfg, args.stub_ms,
                                               seed=rid))

    from mx_rcnn_tpu.obs.runrec import cli_obs
    from mx_rcnn_tpu.serve.agent import ReplicaAgent, make_agent_server
    from mx_rcnn_tpu.tools.loadgen import init_predictor

    obs_sess = cli_obs(cfg, "agent")
    if run_fn_factory is not None:
        # stub agents skip the model build entirely (the bench launches
        # several per box; Predictor(None, {}) is the test-rig idiom)
        model, variables = None, {}
    else:
        predictor = init_predictor(cfg, args.prefix, args.epoch,
                                   args.seed)
        model, variables = predictor.model, predictor.variables
    agent = ReplicaAgent(
        cfg, model, variables,
        run_fn_factory=run_fn_factory,
        record=obs_sess.record if obs_sess else None,
        class_names=(args.class_names.split(",")
                     if args.class_names else None))
    srv = make_agent_server(agent, args.host, args.port)
    host, port = srv.server_address[:2]
    h = agent.healthz()
    ready = {"ready": bool(h.get("ok")), "host": host, "port": port,
             "replicas": h.get("ready"), "warm_s": h.get("warm_s"),
             "store_pull": h.get("store_pull")}
    print(json.dumps(ready), flush=True)
    logger.info("agent serving on http://%s:%d (%s replicas ready)",
                host, port, h.get("ready"))
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        srv.server_close()
        agent.close()
        if obs_sess is not None:
            obs_sess.close(metric="agent_warm_s", value=agent.warm_s,
                           unit="s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
