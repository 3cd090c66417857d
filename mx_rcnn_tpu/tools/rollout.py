"""Rollout-plane measurement protocol: ROLLOUT_r18
(docs/SERVING.md "Rollout tier").

Drives the REAL :class:`~mx_rcnn_tpu.serve.rollout.RolloutController`
over real ``tools/agent.py`` subprocesses on loopback ports — the same
rig (and the same honesty caveat: shared CPU core(s), so the numbers
validate the PLANE, not silicon) as the cross-host bench.  Legs:

1. **lineage** — the admission truth table over real exported stores:
   a v2 child admits against its recorded parent, an unknown parent and
   an unrooted version refuse, a ``train_fingerprint`` mismatch
   refuses, and a legacy version-less store still admits (back-compat);
2. **live swap** — two REAL tiny-model agents booted from a v1 store,
   a v2 store (same weights — an equivalence rollout) rolled out
   MID-BURST through pull → canary (online paired gate) → per-host
   rolling swap → finalize: every request terminates exactly once
   (0 lost), a post-swap mixed-bucket burst lowers ZERO new programs
   (0 unexpected recompiles — v2 serves from exported programs; the
   engine warm's own wrapper lowerings are recorded, not judged), each
   host pulled v2 exactly ONCE, and both hosts finish all-v2;
3. **red-team refusal** — a v2d store whose BUNDLED WEIGHTS are
   damaged (large additive noise): it passes lineage admission (the
   lineage is genuine — only behavior is wrong), the online paired
   gate refuses it on shadow-scored deltas, the controller
   auto-rollbacks, and every host ends base-only with 0 lost; a second
   rollback on top must be a recorded no-op (idempotence);
4. **kill mid-rollout** (full tier) — one agent SIGKILLed while its
   rolling swap is in flight: the controller defers it, finishes the
   fleet, and FINALIZE re-converges the relaunched (clean-disk) host —
   re-pull, re-swap — ending DONE with every host on v2;
5. **sim 100-host** (full tier) — the virtual-time canary-rollout
   scenario over the same controller at fleet scale, shipped and
   damaged-model arms (the gauntlet's rubric, summarized here so one
   JSON carries the whole protocol).

``--smoke`` runs legs 1-3 at 2 hosts (`make rollout-smoke`, ~2-3 min);
the full battery adds legs 4-5 and writes ``ROLLOUT_r18.json``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from mx_rcnn_tpu.config import Config, generate_config
from mx_rcnn_tpu.tools.crosshost import (AgentProc, _free_ports,
                                         _prepared_set,
                                         _run_prepared_closed, _scrape)
from mx_rcnn_tpu.tools.loadgen import _drain, _smoke_overrides

logger = logging.getLogger("mx_rcnn_tpu")


def _damaged_variables(variables, scale: float, seed: int = 1):
    """The red-team arm's weights: every matrix/conv leaf gains
    additive noise ``scale`` x its own mean magnitude.  The lineage
    stays genuine (the damaged store records the true parent and its
    own true fingerprint) — only the model's BEHAVIOR is wrong, so
    nothing but the online paired gate can catch it."""
    rng = np.random.RandomState(seed)

    def walk(x):
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        a = np.asarray(x)
        if a.ndim >= 2:
            noise = rng.standard_normal(a.shape).astype(a.dtype)
            return a + scale * (np.abs(a).mean() + 1e-3) * noise
        return a

    return walk(variables)


def _store_server(root: str):
    from mx_rcnn_tpu.serve.agent import make_store_server

    srv = make_store_server(root)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _lineage_leg(cfg: Config, predictor, workdir: str, v1_root: str,
                 v2_root: str, problems: List[str]) -> Dict:
    """Leg 1: the admission truth table (satellite: lineage manifest
    fields + refusals + version-less back-compat)."""
    from mx_rcnn_tpu.serve.export import (ExportMismatch, ExportStore,
                                          export_serve_programs,
                                          manifest_sha,
                                          variables_fingerprint)

    legacy_root = os.path.join(workdir, "store_legacy")
    export_serve_programs(predictor, cfg, legacy_root, verify=False)

    sha1 = manifest_sha(v1_root)
    v1s, v2s = ExportStore(v1_root), ExportStore(v2_root)
    table: Dict[str, Dict] = {}

    def case(name: str, fn, expect_refused: bool):
        try:
            lineage = fn()
            table[name] = {"refused": False, "lineage": lineage}
        except ExportMismatch as e:
            table[name] = {"refused": True, "error": str(e)[:160]}
        if table[name]["refused"] != expect_refused:
            problems.append(
                f"lineage case {name}: expected refused="
                f"{expect_refused}, got {table[name]}")

    case("child_admits",
         lambda: v2s.check_lineage(known_parents={sha1}), False)
    case("unknown_parent_refused",
         lambda: v2s.check_lineage(known_parents={"0" * 64}), True)
    case("unrooted_refused",
         lambda: v1s.check_lineage(known_parents={sha1}), True)
    case("fingerprint_mismatch_refused",
         lambda: v2s.check_lineage(
             known_parents={sha1},
             expect_train_fingerprint="deadbeef"), True)
    case("fingerprint_match_admits",
         lambda: v2s.check_lineage(
             known_parents={sha1},
             expect_train_fingerprint=variables_fingerprint(
                 predictor.variables)), False)
    case("legacy_versionless_admits",
         lambda: ExportStore(legacy_root).check_lineage(
             known_parents={sha1}), False)
    return {"parent_sha": sha1[:16], "cases": table}


def _ver_counters(url: str) -> Dict[str, float]:
    """The per-version accounting series one agent exports
    (``fleet.ver.<label>.*`` — the canary health rules' inputs)."""
    try:
        snap = _scrape(url)
    except OSError:
        return {}
    return {k: v for k, v in (snap.get("counters") or {}).items()
            if k.startswith("fleet.ver.")}


def _host_state(port, admin, urls: List[str]) -> Dict[str, Dict]:
    out = {}
    for i, source in enumerate(sorted(admin.by_source)):
        versions = port.versions(source)
        rec = {"versions": versions}
        try:
            snap = _scrape(admin.by_source[source])
            rec["recompiles_after_warm"] = (
                snap["gauges"].get("agent.lowered_after_warm"))
        except OSError:
            rec["recompiles_after_warm"] = None
        out[source] = rec
    return out


def _burst(router, prepared, duration_s: float, concurrency: int,
           timeout_ms: float) -> Dict:
    box: Dict = {}

    def run():
        box["run"] = _run_prepared_closed(router, prepared, duration_s,
                                          concurrency, timeout_ms)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    box["thread"] = t
    return box


def _controller(port, cfg: Config, version: str, store_url: str):
    from mx_rcnn_tpu.serve.rollout import RolloutController

    return RolloutController(port, cfg, version=version,
                             store_url=store_url)


def _swap_leg_record(ctrl, run: Dict, snap: Dict, hosts: Dict) -> Dict:
    c = snap["counters"]
    return {
        "phase": ctrl.phase,
        "submitted": c["submitted"], "served": c["served"],
        "shed": c["shed"], "expired": c["expired"],
        "failed": c["failed"],
        "lost": c["submitted"] - snap["terminated"],
        "client": run["client"],
        "gate": ctrl.gate.verdict(),
        "events": [e["kind"] for e in ctrl.events],
        "hosts": hosts,
    }


def _check_exactly_once(name: str, leg: Dict,
                        problems: List[str]) -> None:
    if leg["lost"]:
        problems.append(f"{name}: lost {leg['lost']} requests")
    if leg["failed"] or leg["expired"]:
        problems.append(f"{name}: {leg['failed']} failed / "
                        f"{leg['expired']} expired mid-rollout — the "
                        "graceful drain path dropped work")
    if leg["served"] <= 0:
        problems.append(f"{name}: burst served nothing")


def run_rollout_bench(args) -> int:
    from mx_rcnn_tpu.analysis import sanitizer
    from mx_rcnn_tpu.runtime import enable_compile_cache
    from mx_rcnn_tpu.serve.export import (CACHE_SUBDIR,
                                          export_serve_programs)
    from mx_rcnn_tpu.serve.remote import build_crosshost_router
    from mx_rcnn_tpu.serve.rollout import (DONE, ROLLED_BACK,
                                           AgentRolloutPort)
    from mx_rcnn_tpu.serve.scheduler import AgentAdmin
    from mx_rcnn_tpu.tools.loadgen import init_predictor
    from mx_rcnn_tpu.tools.train import parse_set_overrides

    smoke = args.smoke
    overrides = dict(_smoke_overrides())  # tiny rig on both tiers: all
    # "hosts" share one box; the full tier differs in legs, not canvas
    overrides.update(parse_set_overrides(args))
    cfg = generate_config(args.network, args.dataset, **overrides)
    agent_overrides = dict(overrides)
    workdir = args.workdir or tempfile.mkdtemp(prefix="rollout_")
    os.makedirs(workdir, exist_ok=True)
    timeout_ms = 20_000.0 if args.timeout_ms is None else args.timeout_ms
    batch = cfg.serve.batch_size
    ch_over = {"connections": 2, "pipeline_depth": 4 * batch,
               "scrape_interval_s": 0.2, "io_timeout_s": 30.0,
               "admin_timeout_s": 30.0}  # a pull RPC blocks while the
    # agent downloads the store; refused sockets still fail instantly
    # controller cadence for a 2-host wall-clock rig: sample every tick,
    # judge after 6 pairs, 2s bake; one step timeout covers a clean-disk
    # agent relaunch (leg 4) so FINALIZE re-converges instead of
    # abandoning
    rcfg = cfg.replace_in("rollout", gate_min_pairs=6,
                          gate_sample_every=1, bake_s=2.0,
                          settle_s=0.25, step_timeout_s=45.0)
    rcfg = rcfg.replace_in("crosshost", **ch_over)
    rec: Dict = {
        "metric": "rollout_live_swap_exactly_once",
        "unit": "invariant",
        "measured": True,
        "smoke": smoke,
        "network": args.network,
        "bucket_shapes": [list(b) for b in cfg.bucket.shapes],
        "batch_size": batch,
        "host": {"physical_cores": os.cpu_count()},
        "note": "every 'host' is a separate agent process sharing this "
                "box's core(s): the invariants (exactly-once, 0 "
                "recompiles, gate refusal, re-convergence) validate "
                "the rollout PLANE, not multi-machine silicon",
    }
    problems: List[str] = []
    prepared = _prepared_set(cfg, args.images, args.seed)
    dur = min(args.duration, 4.0) if smoke else max(args.duration, 8.0)

    # -- stores: v1 (boot), v2 (same weights: equivalence), v2d (damaged)
    v1_root = os.path.join(workdir, "store_v1")
    v2_root = os.path.join(workdir, "store_v2")
    v2d_root = os.path.join(workdir, "store_v2d")
    logger.info("[rollout] exporting v1/v2/v2d stores -> %s", workdir)
    enable_compile_cache(os.path.join(v1_root, CACHE_SUBDIR))
    predictor = init_predictor(cfg, args.prefix, args.epoch, args.seed)
    export_serve_programs(predictor, cfg, v1_root, version="v1",
                          bundle_variables=True)
    export_serve_programs(predictor, cfg, v2_root, version="v2",
                          parent=v1_root, bundle_variables=True)
    damaged = type(predictor)(predictor.model,
                              _damaged_variables(predictor.variables,
                                                 scale=10.0), cfg)
    export_serve_programs(damaged, cfg, v2d_root, version="v2d",
                          parent=v1_root, verify=False,
                          bundle_variables=True)

    # -- 1. lineage admission truth table -------------------------------
    logger.info("[rollout] lineage leg ...")
    rec["lineage"] = _lineage_leg(cfg, predictor, workdir, v1_root,
                                  v2_root, problems)

    srv1, url1 = _store_server(v1_root)
    srv2, url2 = _store_server(v2_root)
    srv2d, url2d = _store_server(v2d_root)

    ports = _free_ports(4)
    logger.info("[rollout] launching 2 real agents ...")
    agents = [AgentProc(workdir, f"roll-{i}", ports[i], agent_overrides,
                        network=args.network, dataset=args.dataset,
                        replicas=1, store_url=url1,
                        export_dir=os.path.join(workdir,
                                                f"agent{i}_store"))
              for i in range(2)]
    try:
        for a in agents:
            a.wait_ready()
        urls = [a.url for a in agents]
        admin = AgentAdmin.from_config(urls, rcfg)
        port = AgentRolloutPort(admin)
        router, feed = build_crosshost_router(rcfg, urls)
        try:
            # -- 2. live v1 -> v2 swap mid-burst ------------------------
            logger.info("[rollout] live swap leg (v2 mid-burst) ...")
            ctrl = _controller(port, rcfg, "v2", url2)
            router.metrics.reset()  # per-leg accounting (bulk idiom)
            box = _burst(router, prepared, max(dur * 3, 12.0),
                         concurrency=2 * batch * 2,
                         timeout_ms=timeout_ms)
            phase = ctrl.run(timeout_s=300.0)
            box["thread"].join()
            _drain(router)
            hosts = _host_state(port, admin, urls)
            # the 0-unexpected-recompiles bar: v2 replicas warm from
            # EXPORTED programs, so a post-swap mixed-bucket burst must
            # lower NOTHING new (the warm itself pays the same few
            # wrapper lowerings any post-boot replica add pays — that
            # cost is recorded per host above, not judged)
            post = _run_prepared_closed(router, prepared,
                                        max(dur, 3.0),
                                        concurrency=2 * batch * 2,
                                        timeout_ms=timeout_ms)
            _drain(router)
            hosts_after = _host_state(port, admin, urls)
            leg = _swap_leg_record(ctrl, box["run"],
                                   router.metrics.snapshot(), hosts)
            leg["post_swap_client"] = post["client"]
            leg["recompiles_during_post_swap_burst"] = {
                s: (None
                    if hosts_after[s]["recompiles_after_warm"] is None
                    or hosts[s]["recompiles_after_warm"] is None
                    else hosts_after[s]["recompiles_after_warm"]
                    - hosts[s]["recompiles_after_warm"])
                for s in hosts}
            # one-transfer-per-host: a re-pull must be a recorded no-op
            leg["repull_already"] = [
                bool((port.pull(s, url2, "v2") or {}).get("already"))
                for s in sorted(admin.by_source)]
            leg["per_version_counters"] = {
                a.name: _ver_counters(a.url) for a in agents}
            rec["live_swap"] = leg
            if phase != DONE:
                problems.append(f"live swap ended {phase}, not done "
                                f"(events: {leg['events']})")
            _check_exactly_once("live swap", leg, problems)
            for src, h in hosts.items():
                if h["versions"] != {"v2": 1}:
                    problems.append(f"live swap: {src} ended "
                                    f"{h['versions']}, not all-v2")
            for src, delta in (
                    leg["recompiles_during_post_swap_burst"].items()):
                if delta != 0:
                    problems.append(
                        f"live swap: {src} lowered {delta} program(s) "
                        "during the post-swap burst — v2 is not serving "
                        "from its exported/warmed programs")
            if not (post["client"].get("ok", 0) > 0):
                problems.append("live swap: post-swap burst served "
                                "nothing — recompile delta is vacuous")
            if not all(leg["repull_already"]):
                problems.append("live swap: a re-pull was not a no-op "
                                "— one-transfer-per-host broken")
            if not leg["gate"]["judged"] or leg["gate"]["refused"]:
                problems.append(f"live swap gate did not pass: "
                                f"{leg['gate']}")
            if not any(k.endswith(".dispatched") for k in
                       {c for d in leg["per_version_counters"].values()
                        for c in d}):
                problems.append("no fleet.ver.* counters appeared — "
                                "per-version accounting never engaged")

            # -- 3. red-team: damaged weights, gate refusal -------------
            logger.info("[rollout] red-team leg (damaged v2d) ...")
            ctrl2 = _controller(port, rcfg, "v2d", url2d)
            router.metrics.reset()
            box = _burst(router, prepared, max(dur * 3, 12.0),
                         concurrency=2 * batch * 2,
                         timeout_ms=timeout_ms)
            phase = ctrl2.run(timeout_s=300.0)
            box["thread"].join()
            _drain(router)
            hosts = _host_state(port, admin, urls)
            leg = _swap_leg_record(ctrl2, box["run"],
                                   router.metrics.snapshot(), hosts)
            leg["rollback_reason"] = ctrl2.status()["rollback_reason"]
            leg["rollback_s"] = ctrl2.rollback_s
            leg["rollback_noop"] = ctrl2.rollback("operator")
            rec["redteam"] = leg
            if phase != ROLLED_BACK:
                problems.append(f"red-team ended {phase}, not "
                                f"rolled_back ({leg['events']})")
            if leg["rollback_reason"] != "gate_refused":
                problems.append(f"red-team rollback reason "
                                f"{leg['rollback_reason']!r}, not the "
                                "gate")
            if not leg["gate"]["refused"]:
                problems.append(f"gate did not refuse the damaged "
                                f"model: {leg['gate']}")
            _check_exactly_once("red-team", leg, problems)
            for src, h in hosts.items():
                if h["versions"] != {"base": 1}:
                    problems.append(f"red-team: {src} ended "
                                    f"{h['versions']}, not base-only")
            if not leg["rollback_noop"].get("noop"):
                problems.append("second rollback was not a no-op — "
                                "rollback is not idempotent")

            # -- 4. SIGKILL mid-rollout, relaunch, re-converge ----------
            if not smoke:
                logger.info("[rollout] kill-mid-rollout leg ...")
                rec["kill_rollout"] = _kill_leg(
                    args, agent_overrides, workdir, agents, ports,
                    port, admin, rcfg, url1, url2, problems)
        finally:
            feed.close()
            router.close()
    finally:
        for a in agents:
            a.kill()
        for srv in (srv1, srv2, srv2d):
            srv.shutdown()

    # -- 5. fleet-scale virtual-time arm --------------------------------
    if not smoke:
        logger.info("[rollout] sim 100-host leg ...")
        rec["sim_100h"] = _sim_leg(args.seed, problems)

    print(json.dumps(rec))
    if args.out:
        from mx_rcnn_tpu.tools.sim import _atomic_json

        _atomic_json(args.out, rec)
    if args.check:
        problems += sanitizer.check_problems()
        for msg in problems:
            logger.error("CHECK FAILED: %s", msg)
        return 1 if problems else 0
    return 0


def _kill_leg(args, agent_overrides: Dict, workdir: str,
              agents: List[AgentProc], ports: List[int], port, admin,
              rcfg: Config, url1: str, url2: str,
              problems: List[str]) -> Dict:
    """Leg 4: SIGKILL one agent while its rolling swap is in flight.
    The relaunch gets a CLEAN export dir (a replaced host, not a
    rebooted one): FINALIZE must re-pull v2 onto it and re-swap."""
    from mx_rcnn_tpu.serve.rollout import DONE, ROLLING, _TERMINAL

    ctrl = _controller(port, rcfg, "v2", url2)
    ctrl.start()
    killed_source = sorted(admin.by_source)[1]
    killed = False
    deadline = time.monotonic() + 420.0
    while ctrl.phase not in _TERMINAL and time.monotonic() < deadline:
        ctrl.step()
        if (not killed and ctrl.phase == ROLLING
                and any(e["kind"] == "host_rolling"
                        and e.get("source") == killed_source
                        for e in ctrl.events)):
            agents[1].sigkill()
            killed = True
            # immediate replacement on the same port, fresh disk; it
            # warms concurrently with the rest of the rollout
            agents[1] = AgentProc(
                workdir, "roll-1b", ports[1], agent_overrides,
                network=args.network, dataset=args.dataset, replicas=1,
                store_url=url1,
                export_dir=os.path.join(workdir, "agent1b_store"))
            threading.Thread(target=agents[1].wait_ready,
                             daemon=True).start()
        time.sleep(rcfg.rollout.settle_s)
    hosts = _host_state(port, admin, [a.url for a in agents])
    leg = {
        "phase": ctrl.phase,
        "killed": killed,
        "killed_source": killed_source,
        "events": [e["kind"] for e in ctrl.events],
        "deferred": ctrl.status()["deferred"],
        "hosts": hosts,
    }
    if not killed:
        problems.append("kill leg: never reached the kill point")
    if ctrl.phase != DONE:
        problems.append(f"kill leg ended {ctrl.phase}, not done "
                        f"({leg['events']})")
    if "host_deferred" not in leg["events"]:
        problems.append("kill leg: the killed host was never deferred "
                        "— the kill did not land mid-swap")
    for src, h in hosts.items():
        if h["versions"] != {"v2": 1}:
            problems.append(f"kill leg: {src} ended {h['versions']} — "
                            "FINALIZE did not re-converge the fleet")
    return leg


def _sim_leg(seed: int, problems: List[str]) -> Dict:
    """Leg 5: the 100-host virtual-time canary rollout, both arms —
    the same rubric the sim gauntlet pins (tools/sim.py)."""
    from mx_rcnn_tpu.sim.traffic import generate
    from mx_rcnn_tpu.tools.sim import MISTUNED_BY_SCENARIO, _arm

    cfg = generate_config("tiny", "synthetic")
    trace = generate("canary_rollout", cfg, 100, max(seed, 0))
    shipped = _arm(trace, cfg, "shipped")
    mistuned = _arm(trace, cfg, "mistuned",
                    MISTUNED_BY_SCENARIO["canary_rollout"])

    def summary(s: Dict) -> Dict:
        r = s.get("rollout") or {}
        return {"lost": s["lost"], "served": s["served"],
                "phase": r.get("phase"), "reason": r.get("reason"),
                "final_versions": r.get("final_versions"),
                "gate": r.get("gate"), "wall_s": s["wall_s"]}

    leg = {"hosts": trace["hosts"], "seed": trace["seed"],
           "shipped": summary(shipped), "mistuned": summary(mistuned)}
    sh, mi = leg["shipped"], leg["mistuned"]
    if sh["phase"] != "done" or sh["final_versions"] != {"v2": 100}:
        problems.append(f"sim shipped arm: {sh}")
    if sh["lost"] or mi["lost"]:
        problems.append(f"sim lost requests: shipped {sh['lost']}, "
                        f"mistuned {mi['lost']}")
    if (mi["phase"] != "rolled_back" or mi["reason"] != "gate_refused"
            or set(mi["final_versions"] or {}) != {"base"}):
        problems.append(f"sim mistuned arm not refused+rolled back: "
                        f"{mi}")
    return leg


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    from mx_rcnn_tpu.analysis import sanitizer
    from mx_rcnn_tpu.tools.train import add_set_arg

    sanitizer.maybe_install_from_env()
    p = argparse.ArgumentParser(
        description="Rollout-plane bench: ROLLOUT_r18 protocol "
                    "(docs/SERVING.md 'Rollout tier')")
    p.add_argument("--network", default="tiny",
                   choices=["vgg", "resnet50", "resnet101", "tiny"])
    p.add_argument("--dataset", default="synthetic",
                   choices=["PascalVOC", "coco", "synthetic",
                            "synthetic_hard"])
    p.add_argument("--prefix", default=None,
                   help="checkpoint prefix (default: random init — "
                        "the invariants do not depend on weights)")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--duration", type=float, default=4.0,
                   help="per-leg burst window, seconds")
    p.add_argument("--timeout_ms", type=float, default=None)
    p.add_argument("--images", type=int, default=16)
    p.add_argument("--workdir", default=None,
                   help="scratch dir for stores/agent logs (default: "
                        "mkdtemp)")
    p.add_argument("--out", default=None,
                   help="write the protocol record here "
                        "(ROLLOUT_r18.json)")
    p.add_argument("--smoke", action="store_true",
                   help="gate scale: legs 1-3 at 2 hosts, ~2-3 min "
                        "(make rollout-smoke)")
    p.add_argument("--check", action="store_true",
                   help="exit nonzero on any violated invariant")
    add_set_arg(p)
    args = p.parse_args(argv)
    return run_rollout_bench(args)


if __name__ == "__main__":
    sys.exit(main())
