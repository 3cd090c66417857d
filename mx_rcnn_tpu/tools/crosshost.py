"""Cross-host bench rig: the CROSSHOST_r15 measurement protocol.

Driven through ``tools/loadgen.py --crosshost_bench`` (full battery →
``docs/CROSSHOST_r15.json``) and ``--crosshost_smoke`` (`make
crosshost-smoke`, ~2 min gate scale).  Every "host" is a real separate
PROCESS (``tools/agent.py`` subprocess on a loopback port) so the wire,
the store pull, the scrape plane and the SIGKILL legs all cross a true
process boundary; the honesty caveat is that every process shares this
box's CPU core(s), so absolute throughput validates the PLANE, not
silicon — the same posture as the fleet bench's stub legs
(docs/SERVING.md "Cross-host tier").

Legs:

1. **join** — export a store in the parent, serve it from a
   :func:`~mx_rcnn_tpu.serve.agent.make_store_server`, launch one REAL
   (tiny-model) agent that joins via ``--store_url``: the store-server
   request log must show each file shipped exactly once, and after a
   mixed-bucket burst the agent's ``agent.lowered_after_warm`` gauge
   must read 0 — one transfer + export-warm, never N checkpoint pulls
   and never a post-warm compile;
2. **wire A/B** — the same prepared burst through one stub agent over
   the binary frame vs the base64-JSON control arm
   (``RemoteEngine(wire=...)``);
3. **scaling** — 1/2(/4) stub-model hosts behind the cross-host
   router, closed-loop prepared traffic, throughput vs the 1-host leg;
4. **host-kill** — 2 stub hosts + the LIVE gauge-driven scheduler;
   SIGKILL one agent process mid-burst: every admitted request must
   account (0 lost), every non-shed request must serve within its
   ORIGINAL deadline (reroute never extends it), and the scheduler
   must restore capacity on the survivor without operator input;
5. **bulk 2-host** — the PR-13 bulk plane over two content-stub
   hosts: an uninterrupted control vs an aborted-and-resumed run must
   commit byte-identical shards (exactly-once across the wire).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

import numpy as np

from mx_rcnn_tpu.config import Config, generate_config
from mx_rcnn_tpu.netio import read_limited
from mx_rcnn_tpu.serve.queue import (DeadlineExceeded, RequestFailed,
                                     ShedError)
from mx_rcnn_tpu.tools.loadgen import (_drain, _fleet_leg_record,
                                       _smoke_overrides)

logger = logging.getLogger("mx_rcnn_tpu")


# ---------------------------------------------------------------------------
# rig plumbing
# ---------------------------------------------------------------------------

def _free_ports(n: int) -> List[int]:
    """n distinct free loopback ports, held concurrently so the kernel
    can't hand the same port out twice."""
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


class AgentProc:
    """One ``tools/agent.py`` subprocess: launch, ready-line handshake,
    teardown.  stderr (logs) goes to a per-agent file the bench quotes
    on failure; stdout carries exactly the one ready-line JSON."""

    def __init__(self, workdir: str, name: str, port: int,
                 overrides: Dict, *, network: str = "tiny",
                 dataset: str = "synthetic", replicas: int = 1,
                 store_url: str = None, export_dir: str = None,
                 stub_ms: float = None, stub: str = "plain"):
        self.name = name
        self.port = port
        self.url = f"http://127.0.0.1:{port}"
        self.log_path = os.path.join(workdir, f"{name}.log")
        cmd = [sys.executable, "-m", "mx_rcnn_tpu.tools.agent",
               "--network", network, "--dataset", dataset,
               "--host", "127.0.0.1", "--port", str(port),
               "--replicas", str(replicas)]
        for k, v in overrides.items():
            cmd += ["--set", f"{k}={v!r}" if isinstance(v, str)
                    else f"{k}={v}"]
        if store_url:
            cmd += ["--store_url", store_url]
        if export_dir:
            cmd += ["--export_dir", export_dir]
        if stub_ms is not None:
            cmd += ["--stub_ms", str(stub_ms), "--stub", stub]
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                     stderr=self._log, text=True,
                                     env=_child_env())
        self.ready: Dict = {}

    def wait_ready(self, timeout_s: float = 300.0) -> Dict:
        box: Dict = {}

        def read():
            box["line"] = self.proc.stdout.readline()

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout_s)
        line = box.get("line")
        if not line:
            self.kill()
            tail = ""
            try:
                with open(self.log_path) as f:
                    tail = f.read()[-2000:]
            except OSError:
                pass
            raise RuntimeError(f"agent {self.name} not ready within "
                               f"{timeout_s}s:\n{tail}")
        self.ready = json.loads(line)
        if not self.ready.get("ready"):
            raise RuntimeError(f"agent {self.name} reported unready: "
                               f"{self.ready}")
        return self.ready

    def sigkill(self) -> None:
        """The host-death lever: no shutdown path runs, sockets go
        half-dead — exactly what a powered-off host looks like."""
        try:
            self.proc.send_signal(signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


def _scrape(url: str, timeout_s: float = 10.0) -> Dict:
    with urllib.request.urlopen(url.rstrip("/") + "/metrics",
                                timeout=timeout_s) as r:
        snap = json.loads(read_limited(r).decode())
    return snap.get("registry", snap)


def _healthz(url: str, timeout_s: float = 10.0) -> Dict:
    with urllib.request.urlopen(url.rstrip("/") + "/healthz",
                                timeout=timeout_s) as r:
        return json.loads(read_limited(r).decode())


def _prepared_set(cfg: Config, n: int, seed: int = 0) -> List[Tuple]:
    """n (canvas, im_info, bucket) triples alternating over the shape
    buckets — the prepared-path analogue of ``synthetic_images`` (mixed
    buckets keep the recompile pin and the lane-JSQ path honest)."""
    rng = np.random.RandomState(seed)
    buckets = [tuple(b) for b in cfg.bucket.shapes]
    out = []
    for i in range(n):
        b = buckets[i % len(buckets)]
        out.append((rng.rand(*b, 3).astype(np.float32) * 255.0,
                    np.array([b[0], b[1], 1.0], np.float32), b))
    return out


def _run_prepared_closed(target, prepared, duration_s: float,
                         concurrency: int, timeout_ms: float) -> dict:
    """``run_closed_loop`` over the prepared/binary hot path —
    ``target`` is anything with ``submit_prepared`` (cross-host router
    or a bare RemoteEngine)."""
    stop = time.monotonic() + duration_s
    outcomes = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
    lock = threading.Lock()

    def worker(wid: int):
        i = wid
        while time.monotonic() < stop:
            data, im_info, bucket = prepared[i % len(prepared)]
            i += concurrency
            try:
                req = target.submit_prepared(data, im_info, bucket,
                                             timeout_ms=timeout_ms)
                req.wait(timeout=timeout_ms / 1000.0 + 30.0)
                key = "ok"
            except ShedError:
                key = "shed"
                time.sleep(0.005)  # a real client backs off; a tight
                # resubmit spin would just burn the shared core
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


# ---------------------------------------------------------------------------
# the bench
# ---------------------------------------------------------------------------

def run_crosshost_bench(args) -> int:
    from mx_rcnn_tpu.analysis import sanitizer
    from mx_rcnn_tpu.serve.agent import make_store_server
    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import (CACHE_SUBDIR,
                                          export_serve_programs)
    from mx_rcnn_tpu.serve.remote import (RemoteEngine,
                                          build_crosshost_router)
    from mx_rcnn_tpu.serve.scheduler import AgentAdmin, FleetScheduler
    from mx_rcnn_tpu.tools.loadgen import init_predictor
    from mx_rcnn_tpu.tools.train import parse_set_overrides

    smoke = args.crosshost_smoke
    overrides = dict(_smoke_overrides())  # both tiers use the tiny rig:
    # every "host" shares one box, so the production canvas would only
    # measure core contention; the full tier differs in durations/sweep
    overrides.update(parse_set_overrides(args))
    cfg = generate_config(args.network, args.dataset, **overrides)
    # agent subprocesses must build the identical config (the prepared
    # frames' bucket shapes are part of the wire contract)
    agent_overrides = dict(overrides)
    workdir = args.workdir or tempfile.mkdtemp(prefix="crosshost_")
    os.makedirs(workdir, exist_ok=True)
    timeout_ms = 20_000.0 if args.timeout_ms is None else args.timeout_ms
    dur = min(args.duration, 4.0) if smoke else max(args.duration, 8.0)
    batch = cfg.serve.batch_size
    # keep-alive pipeline sized so the closed loop never sheds at the
    # head: per-agent capacity (connections x depth) >= its share
    ch_over = {"connections": 2, "pipeline_depth": 4 * batch,
               "scrape_interval_s": 0.2, "io_timeout_s": 30.0}
    rec: dict = {
        "metric": "crosshost_scaling_x_at_2_hosts",
        "unit": "x",
        "measured": True,
        "smoke": smoke,
        "network": args.network,
        "bucket_shapes": [list(b) for b in cfg.bucket.shapes],
        "batch_size": batch,
        "host": {"physical_cores": os.cpu_count()},
        "note": "every 'host' is a separate local process sharing this "
                "box's core(s): ratios validate the cross-host plane "
                "(wire, store pull, scheduler), not multi-machine "
                "silicon",
    }
    problems: List[str] = []
    prepared = _prepared_set(cfg, args.images, args.seed)

    # -- 1. store export + one-transfer join (real tiny model) ----------
    store_root = os.path.join(workdir, "store")
    logger.info("[crosshost] exporting store -> %s", store_root)
    enable_compile_cache(os.path.join(store_root, CACHE_SUBDIR))
    predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
    report = export_serve_programs(predictor, cfg, store_root)
    store_srv = make_store_server(store_root)
    threading.Thread(target=store_srv.serve_forever,
                     daemon=True).start()
    sp = store_srv.server_address[1]
    logger.info("[crosshost] join leg: real agent pulling store from "
                ":%d ...", sp)
    # join(1) + wire(1) + sweep(sum) + kill(2) + bulk(2), worst case
    ports = _free_ports(16)
    a0 = AgentProc(workdir, "join-agent", ports[0], agent_overrides,
                   network=args.network, dataset=args.dataset,
                   replicas=1, store_url=f"http://127.0.0.1:{sp}",
                   export_dir=os.path.join(workdir, "agent_store"))
    try:
        ready = a0.wait_ready()
        pull = ready.get("store_pull") or {}
        router, feed = build_crosshost_router(
            cfg.replace_in("crosshost", **ch_over), [a0.url])
        try:
            run = _run_prepared_closed(router, prepared,
                                       min(dur, 3.0),
                                       concurrency=2 * batch,
                                       timeout_ms=timeout_ms)
            _drain(router)
        finally:
            feed.close()
            router.close()
        snap = _scrape(a0.url)
        lowered = snap["gauges"].get("agent.lowered_after_warm")
        with store_srv.stats_lock:
            reqs = list(store_srv.requests)
        files_in_store = len(store_srv.index)
        rec["join"] = {
            "store_files": files_in_store,
            "store_bytes": report["bytes"],
            "pull": pull,
            "store_requests": len(reqs),
            "warm_s": ready.get("warm_s"),
            "burst_ok": run["client"]["ok"],
            "recompiles_after_warm": lowered,
        }
        if pull.get("files") != files_in_store or pull.get("refused"):
            problems.append(f"join pull incomplete or refused: {pull}")
        if len(reqs) != files_in_store or any(r["start"] for r in reqs):
            problems.append(
                f"join was not ONE whole transfer per file: "
                f"{len(reqs)} requests for {files_in_store} files")
        if run["client"]["ok"] == 0:
            problems.append("join burst served nothing")
        if lowered is None or lowered > 0:
            problems.append(f"agent recompiled {lowered} time(s) after "
                            f"export-warm")
    finally:
        a0.kill()

    # -- 2. wire A/B: binary frame vs base64-JSON control ---------------
    logger.info("[crosshost] wire A/B leg ...")
    # near-zero batching delay on the agent and concurrency pinned to
    # the connection count: every request ships immediately and waits
    # only on encode/wire/decode, so the A/B isolates the frame cost
    # instead of measuring a shared 20ms batch-delay floor on both arms
    aw = AgentProc(workdir, "wire-agent", ports[1],
                   dict(agent_overrides, serve__max_delay_ms=2.0),
                   network=args.network, dataset=args.dataset,
                   replicas=1, stub_ms=0.0)
    wire: dict = {}
    try:
        aw.wait_ready()
        wcfg = cfg.replace_in("crosshost", **ch_over)
        for arm in ("json", "binary"):
            eng = RemoteEngine(f"wire-{arm}", aw.url, wcfg, wire=arm)
            try:
                # warm the arm's whole path (connections, agent lanes,
                # codec code) before the measured window, then zero the
                # counters — otherwise whichever arm runs FIRST pays
                # every first-touch cost and the A/B skews
                _run_prepared_closed(eng, prepared, 0.5,
                                     concurrency=ch_over["connections"],
                                     timeout_ms=timeout_ms)
                _drain(eng)
                eng.metrics.reset()
                run = _run_prepared_closed(eng, prepared,
                                           max(dur / 2, 2.0),
                                           concurrency=ch_over[
                                               "connections"],
                                           timeout_ms=timeout_ms)
                _drain(eng)
                snap = eng.metrics.snapshot()
                wire[arm] = {
                    "imgs_per_sec": round(run["client"]["ok"]
                                          / run["wall_s"], 2),
                    "p50_ms": snap["total_ms"]["p50"],
                    "p99_ms": snap["total_ms"]["p99"],
                    "client": run["client"],
                }
            finally:
                eng.close()
        ratio = (wire["binary"]["imgs_per_sec"]
                 / max(wire["json"]["imgs_per_sec"], 1e-9))
        wire["binary_over_json"] = round(ratio, 3)
        wire["note"] = ("identical burst, identical agent; the arms "
                        "differ ONLY in prepared-frame encoding — the "
                        "ratio is the b64+JSON tax on a shared-core "
                        "box")
        if ratio < args.min_wire_ratio:
            problems.append(f"binary wire {ratio:.3f}x JSON < "
                            f"{args.min_wire_ratio}")
    finally:
        aw.kill()
    rec["wire_ab"] = wire

    # -- 3. host scaling (stub model, 1/2/4 agent processes) ------------
    sweep = [1, 2] if smoke else [int(s) for s in
                                  args.crosshost_sweep.split(",")]
    stub_ms = min(args.stub_ms, 60.0) if smoke else args.stub_ms
    thr: dict = {}
    port_i = 2
    for n_hosts in sweep:
        logger.info("[crosshost] scaling leg: %d host(s) ...", n_hosts)
        agents = [AgentProc(workdir, f"scale{n_hosts}-{i}",
                            ports[port_i + i], agent_overrides,
                            network=args.network, dataset=args.dataset,
                            replicas=1, stub_ms=stub_ms)
                  for i in range(n_hosts)]
        port_i += n_hosts
        try:
            for a in agents:
                a.wait_ready()
            router, feed = build_crosshost_router(
                cfg.replace_in("crosshost", **ch_over),
                [a.url for a in agents])
            try:
                run = _run_prepared_closed(
                    router, prepared, dur,
                    concurrency=4 * batch * n_hosts,
                    timeout_ms=timeout_ms)
                _drain(router)
                leg = _fleet_leg_record(run, router.metrics.snapshot())
                thr[str(n_hosts)] = leg
                if leg["lost"]:
                    problems.append(f"{n_hosts}-host leg lost "
                                    f"{leg['lost']} requests")
            finally:
                feed.close()
                router.close()
        finally:
            for a in agents:
                a.kill()
    scaling: dict = {"stub_model_ms": stub_ms, "hosts": thr}
    base = thr[str(sweep[0])]["imgs_per_sec"]
    for n_hosts in sweep[1:]:
        if base:
            s = round(thr[str(n_hosts)]["imgs_per_sec"] / base, 3)
            scaling[f"scaling_{n_hosts}h"] = s
            floor = args.min_crosshost_scaling * (n_hosts / 2.0)
            if s < floor:
                problems.append(f"scaling at {n_hosts} hosts {s} < "
                                f"{floor}")
    rec["host_scaling"] = scaling
    rec["value"] = scaling.get("scaling_2h")

    # -- 4. host-kill + live scheduler ----------------------------------
    logger.info("[crosshost] host-kill leg (live scheduler) ...")
    # up_shed_ratio near 1: the closed loop DELIBERATELY overdrives the
    # head so its capacity gate sheds as backpressure — that is client
    # load, not missing replicas, and the leg measures the DEFICIT path
    # (the overload path is pinned on synthetic traces in
    # tests/test_remote.py)
    kcfg = cfg.replace_in("crosshost", **dict(
        ch_over, dead_after_failures=2, for_samples=2,
        cooldown_s=1.0, interval_s=0.2, window_s=5.0,
        up_shed_ratio=0.9))
    kcfg = kcfg.replace_in("fleet", reroute_retries=2,
                           health_interval_s=0.2)
    agents = [AgentProc(workdir, f"kill-{i}", ports[port_i + i],
                        agent_overrides, network=args.network,
                        dataset=args.dataset, replicas=1,
                        stub_ms=stub_ms)
              for i in range(2)]
    port_i += 2
    try:
        for a in agents:
            a.wait_ready()
        urls = [a.url for a in agents]
        router, feed = build_crosshost_router(kcfg, urls)
        sched = FleetScheduler(feed.store,
                               AgentAdmin.from_config(urls, kcfg),
                               kcfg).start()
        try:
            kdur = max(dur, 6.0)
            stop_box = {}

            def burst():
                stop_box["run"] = _run_prepared_closed(
                    router, prepared, kdur,
                    concurrency=4 * batch * 2,
                    timeout_ms=timeout_ms)

            bt = threading.Thread(target=burst, daemon=True)
            bt.start()
            time.sleep(kdur / 3.0)
            served_before = router.metrics.snapshot()["counters"]["served"]
            agents[1].sigkill()
            kill_t = time.monotonic()
            bt.join()
            _drain(router)
            run = stop_box["run"]
            # capacity restore: the scheduler must grow the SURVIVOR
            # to cover the dead host's replica, with no operator input
            restore_s = None
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                try:
                    if _healthz(urls[0]).get("ready", 0) >= 2:
                        restore_s = round(time.monotonic() - kill_t, 2)
                        break
                except OSError:
                    pass
                time.sleep(0.2)
            snap = router.metrics.snapshot()
            c = snap["counters"]
            leg = {
                "submitted": c["submitted"], "served": c["served"],
                "shed": c["shed"], "expired": c["expired"],
                "failed": c["failed"],
                "lost": c["submitted"] - snap["terminated"],
                "served_after_kill": c["served"] - served_before,
                "rerouted": router.rerouted(),
                "ejects": router.manager.ejects,
                "client": run["client"],
                "capacity_restore_s": restore_s,
                "scheduler_actions": [
                    {k: a[k] for k in ("action", "source", "reason")}
                    for a in sched.actions],
            }
            rec["host_kill"] = leg
            if leg["lost"]:
                problems.append(f"host-kill leg lost {leg['lost']} "
                                f"requests")
            if run["client"]["failed"] or run["client"]["expired"]:
                problems.append(
                    "host-kill leg had client failures/expiries — "
                    "reroute did not complete within the original "
                    f"deadline: {run['client']}")
            if leg["served_after_kill"] <= 0:
                problems.append("nothing served after the host kill")
            if restore_s is None:
                problems.append("scheduler did not restore capacity "
                                "on the survivor within 60s")
            if not any(a["action"] == "add" for a in sched.actions):
                problems.append("scheduler recorded no add action "
                                "after the host kill")
        finally:
            sched.close()
            feed.close()
            router.close()
    finally:
        for a in agents:
            a.kill()

    # -- 5. bulk over 2 hosts: exactly-once + byte-identical resume -----
    logger.info("[crosshost] bulk 2-host leg ...")
    rec["bulk_2host"] = _bulk_leg(cfg, agent_overrides, args, workdir,
                                  [ports[port_i], ports[port_i + 1]],
                                  ch_over, problems)

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


class _PlannedAbort(RuntimeError):
    """The bulk leg's mid-run failure: raised from the fault hook after
    a shard commit, so the resume starts from a durably committed
    prefix (the in-process analogue of the SIGKILL protocol)."""


def _bulk_leg(cfg: Config, agent_overrides: Dict, args, workdir: str,
              ports: List[int], ch_over: Dict,
              problems: List[str]) -> dict:
    from mx_rcnn_tpu.data import load_gt_roidb
    from mx_rcnn_tpu.data.loader import StreamTestLoader
    from mx_rcnn_tpu.serve.bulk import (BulkRunner, BulkSink,
                                        make_sink_manifest)
    from mx_rcnn_tpu.serve.remote import build_crosshost_router

    data_root = os.path.join(workdir, "bulk_data")
    bcfg = cfg.replace_in("dataset", root_path=data_root,
                          dataset_path=os.path.join(data_root,
                                                    "synthetic"))
    bcfg = bcfg.replace_in("bulk", shard_batches=2)
    bcfg = bcfg.replace_in("data", streaming=True)
    bcfg = bcfg.replace_in("crosshost", **ch_over)
    h, w = bcfg.bucket.shapes[0]
    _, roidb = load_gt_roidb(bcfg, training=True, flip=False,
                             num_images=16, image_size=(h, w),
                             max_objects=2)
    agents = [AgentProc(workdir, f"bulk-{i}", ports[i],
                        agent_overrides, network=args.network,
                        dataset=args.dataset, replicas=1,
                        stub_ms=0.0, stub="content")
              for i in range(2)]
    try:
        for a in agents:
            a.wait_ready()
        router, feed = build_crosshost_router(
            bcfg, [a.url for a in agents])
        try:
            def run_bulk(sink_dir, fault=None):
                loader = StreamTestLoader(roidb, bcfg, batch_images=2,
                                          shuffle=False, seed=0,
                                          raw_images=False,
                                          num_workers=0)
                sink = BulkSink(sink_dir,
                                make_sink_manifest(bcfg, roidb, 0, 2))
                return BulkRunner(router, loader, sink, bcfg,
                                  fault=fault,
                                  total_replicas=2).run()

            ctrl_dir = os.path.join(workdir, "bulk_ctrl")
            kill_dir = os.path.join(workdir, "bulk_resume")
            ctrl = run_bulk(ctrl_dir)

            def fault(shard_i: int):
                if shard_i == 1:
                    raise _PlannedAbort(f"planned abort @shard="
                                        f"{shard_i}")

            aborted = False
            try:
                run_bulk(kill_dir, fault=fault)
            except _PlannedAbort:
                aborted = True
            resumed = run_bulk(kill_dir)
            names = sorted(f for f in os.listdir(ctrl_dir)
                           if f.startswith("shard-"))
            k_names = sorted(f for f in os.listdir(kill_dir)
                             if f.startswith("shard-"))
            identical = names == k_names and all(
                open(os.path.join(ctrl_dir, n), "rb").read()
                == open(os.path.join(kill_dir, n), "rb").read()
                for n in names)
            leg = {
                "corpus_images": len(roidb),
                "control": {k: ctrl[k] for k in
                            ("planned_images", "shards")},
                "aborted_mid_run": aborted,
                "resumed_shards": resumed["resumed_shards"],
                "resumed_images": resumed["resumed_images"],
                "byte_identical": identical,
            }
            if not aborted:
                problems.append("bulk leg: planned abort never fired")
            if not resumed["resumed_shards"]:
                problems.append("bulk resume re-scored everything — "
                                "committed prefix was not honored")
            if not identical:
                problems.append("bulk resume shards differ from the "
                                "uninterrupted control")
            return leg
        finally:
            feed.close()
            router.close()
    finally:
        for a in agents:
            a.kill()
