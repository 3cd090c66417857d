"""Fleet tier tests (ISSUE 8): AOT export round-trip bit-equality,
batch-aware JSQ routing, deadline/shed composition at fleet scope,
replica crash → eject → relaunch → rejoin, and the fleet-wide
terminate-exactly-once accounting invariant.

Routing/lifecycle tests run stub-model fleets (``make_stub_run_fn``
gated by an event — no compiles, millisecond launches); the export
tests use the module-scoped tiny Predictor so the whole file traces a
handful of quick-tier programs once.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.export import (ExportMismatch, ExportStore,
                                      export_serve_programs,
                                      serve_fwd_name)
from mx_rcnn_tpu.serve.fleet import (R_DEAD, R_READY, R_RELAUNCHING,
                                     FleetRouter, ReplicaManager,
                                     build_fleet, partition_devices)
from mx_rcnn_tpu.serve.queue import (EXPIRED, FAILED, PENDING, SERVED,
                                     SHED, ServeRequest)
from mx_rcnn_tpu.tools.loadgen import init_predictor, make_stub_run_fn


def _fleet_cfg(replicas=2, **kw):
    cfg = generate_config(
        "tiny", "synthetic",
        bucket__scale=128, bucket__max_size=160,
        bucket__shapes=((128, 160), (160, 128)),
        test__rpn_pre_nms_top_n=512, test__rpn_post_nms_top_n=64,
        serve__batch_size=2, serve__max_delay_ms=20.0,
        fleet__replicas=replicas, fleet__health_interval_s=30.0)
    for sec in ("serve", "fleet"):
        sub = {k.split("__", 1)[1]: v for k, v in kw.items()
               if k.startswith(sec + "__")}
        if sub:
            cfg = cfg.replace_in(sec, **sub)
    return cfg


@pytest.fixture(scope="module")
def predictor():
    return init_predictor(_fleet_cfg())


def _img(landscape=True, seed=0):
    rng = np.random.RandomState(seed)
    h, w = (128, 160) if landscape else (160, 128)
    return rng.randint(0, 256, size=(h, w, 3), dtype=np.uint8)


class _Gate:
    """Per-fleet stub gate: replicas serve instantly while ``open``;
    ``close()`` makes every subsequent batch block until reopened —
    the controlled-backlog knob for routing tests."""

    def __init__(self):
        self._ev = threading.Event()
        self._ev.set()

    def close(self):
        self._ev.clear()

    def open(self):
        self._ev.set()

    def factory(self, cfg):
        def make(rid):
            inner = make_stub_run_fn(cfg, model_ms=1.0)

            def run_fn(images, im_info):
                self._ev.wait(timeout=30.0)
                return inner(images, im_info)

            return run_fn

        return make


def _stub_fleet(predictor, cfg, gate=None):
    gate = gate or _Gate()
    router = build_fleet(cfg, predictor.model, predictor.variables,
                         run_fn_factory=gate.factory(cfg))
    return router, gate


def _drain(router, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while (router.metrics.snapshot()["in_flight"] > 0
           and time.monotonic() < deadline):
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# config + device partitioning
# ---------------------------------------------------------------------------

def test_fleet_config_section_and_overrides():
    cfg = generate_config("tiny", "synthetic", fleet__replicas=4,
                          fleet__reroute_retries=3,
                          fleet__export_dir="/tmp/x")
    assert cfg.fleet.replicas == 4
    assert cfg.fleet.reroute_retries == 3
    assert cfg.fleet.export_dir == "/tmp/x"
    with pytest.raises(ValueError):
        ReplicaManager(lambda rid: None,
                       generate_config("tiny", "synthetic",
                                       fleet__replicas=0))


def test_partition_devices_shares_scarce_supply():
    devs = ["d0"]
    subsets = partition_devices(3, devices=devs)
    assert subsets == [["d0"], ["d0"], ["d0"]]
    subsets = partition_devices(2, devices=["d0", "d1", "d2", "d3"])
    assert subsets == [["d0", "d1"], ["d2", "d3"]]
    with pytest.raises(ValueError):
        partition_devices(0, devices=devs)


def test_partition_devices_logs_when_replicas_share(caplog):
    """Wrapping onto shared devices is said out loud; disjoint subsets
    are silent."""
    import logging

    with caplog.at_level(logging.WARNING, logger="mx_rcnn_tpu"):
        partition_devices(2, devices=["d0", "d1", "d2", "d3"])
        assert "SHARE devices" not in caplog.text
        partition_devices(3, devices=["d0", "d1"])
        assert "SHARE devices" in caplog.text


def test_one_device_replicas_live_on_their_own_devices(predictor):
    """Four one-device replicas on a four-device host: each replica's
    variables AND its batch placement sit on ITS device, not all on the
    default device (a one-device subset used to get mesh=None, and the
    Predictor then committed everything to device 0)."""
    import jax

    from mx_rcnn_tpu.serve.fleet import make_engine_build_fn

    devices = jax.devices()[:4]
    cfg = _fleet_cfg(replicas=4, fleet__devices_per_replica=1)
    build = make_engine_build_fn(
        cfg, predictor.model, predictor.variables, devices=devices,
        run_fn_factory=_Gate().factory(cfg))
    var_devs, batch_devs = [], []
    for rid in range(4):
        engine, join = build(rid)
        try:
            p = engine.predictor
            on = {d for leaf in jax.tree.leaves(p.variables)
                  for d in leaf.devices()}
            assert len(on) == 1
            var_devs.append(on.pop())
            batch_devs.append(next(iter(p._batch_sharding.device_set)))
            assert join["devices"] == 1
        finally:
            engine.close()
    assert var_devs == list(devices)
    assert batch_devs == list(devices)


# ---------------------------------------------------------------------------
# AOT export: round trip, admission checks, corruption
# ---------------------------------------------------------------------------

def test_export_round_trip_bit_equal_and_warm_start(predictor, tmp_path):
    """The tentpole pin: exported programs verify bit-equal at export
    time, AND an export-warmed engine's end-to-end detections are
    bit-identical to a trace-warmed engine's on the same images."""
    cfg = _fleet_cfg()
    root = str(tmp_path / "store")
    report = export_serve_programs(predictor, cfg, root)
    assert report["bit_equal"] is True
    assert sorted(e["name"] for e in report["programs"]) == sorted(
        [serve_fwd_name(tuple(b), cfg.serve.batch_size)
         for b in cfg.bucket.shapes] + ["serve_post"])

    live = ServingEngine(predictor, cfg)
    live.warmup()
    from mx_rcnn_tpu.core.tester import Predictor
    cold_pred = Predictor(predictor.model, predictor.variables, cfg)
    warm = ServingEngine(cold_pred, cfg, start=True)
    join = warm.warm_from_export(ExportStore(root))
    assert join["programs"] == len(cfg.bucket.shapes)
    try:
        for seed in range(3):
            for landscape in (True, False):
                img = _img(landscape, seed)
                a = live.detect(img, timeout_ms=30_000)
                b = warm.detect(img, timeout_ms=30_000)
                assert set(a) == set(b)
                for cls in a:
                    np.testing.assert_array_equal(a[cls], b[cls])
    finally:
        live.close()
        warm.close()


def test_export_store_refuses_mismatched_config(predictor, tmp_path):
    cfg = _fleet_cfg()
    root = str(tmp_path / "store")
    export_serve_programs(predictor, cfg, root, verify=False)
    other = generate_config(
        "tiny", "synthetic", bucket__scale=96, bucket__max_size=128,
        bucket__shapes=((96, 128),))
    store = ExportStore(root)
    with pytest.raises(ExportMismatch):
        store.check(other)
    store.check(other, allow_mismatch=True)  # explicit downgrade only
    # serving-semantics knobs sit OUTSIDE the train-config fingerprint
    # but are baked into the exported postprocess as static args — a
    # drifted value must refuse too, not silently serve different boxes
    drifted = cfg.replace_in("serve", score_thresh=cfg.serve.score_thresh
                             + 0.2)
    with pytest.raises(ExportMismatch, match="serve_score_thresh"):
        store.check(drifted)


def test_export_store_refuses_corrupt_program(predictor, tmp_path):
    cfg = _fleet_cfg()
    root = str(tmp_path / "store")
    export_serve_programs(predictor, cfg, root, verify=False)
    store = ExportStore(root)
    name = store.names()[0]
    path = os.path.join(root, store.manifest()["entries"][name]["file"])
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(ExportMismatch):
        store.load(name)


def test_install_program_refuses_resident_slot(predictor):
    cfg = _fleet_cfg()
    from mx_rcnn_tpu.core.tester import Predictor
    pred = Predictor(predictor.model, predictor.variables, cfg)
    key = pred.program_key("rpn", (np.zeros((2, 128, 160, 3), np.float32),
                                   np.zeros((2, 3), np.float32)))
    pred.install_program(key, lambda *a: None)
    with pytest.raises(ValueError):
        pred.install_program(key, lambda *a: None)


# ---------------------------------------------------------------------------
# routing: batch-aware JSQ, deadline, shed composition
# ---------------------------------------------------------------------------

def test_jsq_avoids_backlogged_bucket_lane(predictor):
    """The convoy-stall pin: a replica whose lane for THIS bucket is
    cycles deep loses to one with an idle lane, even when total depths
    would say otherwise (total-depth JSQ measured a ~5-cycle lane stall
    in the fleet bench — serve/fleet.py ``_dispatch``)."""
    cfg = _fleet_cfg(replicas=2)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        gate.close()
        r0, r1 = router.manager.replicas
        # jam replica 0's landscape lane 2 batch-cycles deep
        for seed in range(5):
            req = r0.engine.submit(_img(True, seed), timeout_ms=0)
            assert req.state not in (SHED,)
        assert r0.engine.bucket_depth((128, 160)) >= 3
        assert r0.depth() > r1.depth()
        freq = router.submit(_img(True, 99), timeout_ms=30_000)
        assert freq.replica_id == r1.id
        # the portrait bucket is idle on BOTH replicas: depth tiebreak
        # must send it to the emptier replica 1
        freq2 = router.submit(_img(False, 7), timeout_ms=30_000)
        assert freq2.replica_id == r1.id
    finally:
        gate.open()
        _drain(router)
        router.close()


def test_request_expired_during_routing_terminates_expired(predictor):
    """Deadline composition: a request already past its deadline when
    routing runs terminates EXPIRED and never consumes a replica slot."""
    cfg = _fleet_cfg(replicas=2)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        from mx_rcnn_tpu.serve.fleet import FleetRequest
        now = time.monotonic()
        freq = FleetRequest(_img(), now - 1.0, now)  # born expired
        before = [r.engine.metrics.counters["submitted"]
                  for r in router.manager.replicas]
        router._dispatch(freq)
        assert freq.state == EXPIRED
        after = [r.engine.metrics.counters["submitted"]
                 for r in router.manager.replicas]
        assert after == before
        assert router.metrics.counters["expired"] == 1
    finally:
        router.close()


def test_fleet_shed_requires_every_replica_saturated(predictor):
    """Watermark composition: JSQ routes to the least-loaded replica, so
    a fleet-level SHED means every replica was at its watermark; while
    ANY replica has room the fleet must keep admitting."""
    cfg = _fleet_cfg(replicas=2, serve__shed_watermark=2)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        gate.close()
        handles = []
        shed_at = None
        for seed in range(12):  # 2 replicas x (1 lane watermark 2 + batch)
            freq = router.submit(_img(True, seed), timeout_ms=0)
            handles.append(freq)
            if freq.state == SHED:
                shed_at = seed
                break
        assert shed_at is not None, "fleet never shed at tiny watermark"
        # the shed decision was made with BOTH replicas' landscape lanes
        # at the watermark
        for r in router.manager.replicas:
            assert r.engine.bucket_depth((128, 160)) >= 2
        gate.open()
        _drain(router)
        snap = router.metrics.snapshot()
        assert snap["counters"]["submitted"] == snap["terminated"]
    finally:
        gate.open()
        router.close()


def test_reroute_does_not_extend_deadline(predictor):
    """A replica death mid-request must not grant the rider more time:
    the reroute path re-checks expiry first and terminates EXPIRED (the
    dispatcher would have cancelled the queued request at take had the
    replica lived — deadline authority outranks the death)."""
    cfg = _fleet_cfg(replicas=2, fleet__reroute_retries=1)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        gate.close()
        # occupy both replicas' landscape dispatchers so the victim
        # request stays QUEUED (kill only strands queued work; a batch
        # already mid-model completes, like a real preemption)
        for r in router.manager.replicas:
            for s in range(2):
                r.engine.submit(_img(True, s), timeout_ms=0)
        time.sleep(0.15)  # dispatchers take their batches and block
        freq = router.submit(_img(True, 9), timeout_ms=150.0)
        target = router.manager.replicas[freq.replica_id]
        time.sleep(0.25)  # deadline passes while queued
        target.engine.kill()  # queued → FAILED → reroute → expiry check
        deadline = time.monotonic() + 5.0
        while freq.state == PENDING and time.monotonic() < deadline:
            time.sleep(0.01)
        assert freq.state == EXPIRED
    finally:
        gate.open()
        router.close()


# ---------------------------------------------------------------------------
# lifecycle: crash → eject → reroute → relaunch → rejoin
# ---------------------------------------------------------------------------

def test_crash_eject_reroute_relaunch_rejoin(predictor):
    cfg = _fleet_cfg(replicas=2)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        gate.close()
        victim = router.manager.replicas[0]
        survivor = router.manager.replicas[1]
        # strand work on the victim: jam its landscape lane
        riders = []
        while victim.engine.bucket_depth((128, 160)) < 3:
            freq = router.submit(_img(True, len(riders)),
                                 timeout_ms=30_000)
            riders.append(freq)
        victim.engine.kill()
        assert not victim.engine.alive()
        router.manager.tick(now=time.monotonic())
        assert victim.state in (R_RELAUNCHING, R_READY)
        assert router.manager.ejects == 1
        gate.open()
        _drain(router)
        # every stranded rider reached exactly one terminal state, and
        # the reroutes landed somewhere that served them
        assert all(f.state == SERVED for f in riders)
        assert router.rerouted() > 0
        # drive the health loop until the relaunch rejoins
        deadline = time.monotonic() + 15.0
        while victim.generation < 2 and time.monotonic() < deadline:
            router.manager.tick(now=time.monotonic() + 10.0)
            time.sleep(0.02)
        assert victim.generation == 2 and victim.ready()
        # the rejoined replica serves again
        freq = router.submit(_img(True, 123), timeout_ms=30_000)
        freq.wait(timeout=10.0)
        assert freq.state == SERVED
    finally:
        gate.open()
        router.close()


def test_manager_counters_are_thread_safe():
    """Regression for the ISSUE-10 threadlint TL201 fix: ejects (health
    monitor thread) and relaunches (per-replica rebuild threads) are
    bumped concurrently; unguarded += on a plain int loses updates under
    interleaving.  48 concurrent ejects must count exactly 48."""
    cfg = _fleet_cfg(replicas=48, fleet__relaunch=False)
    manager = ReplicaManager(lambda rid: (None, {}), cfg)
    for r in manager.replicas:
        r.state = R_READY          # stub: never launched, engine None
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)    # force frequent interleaving
    try:
        threads = [threading.Thread(target=manager.eject, args=(r, "test"))
                   for r in manager.replicas]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert manager.ejects == len(manager.replicas)
    assert all(r.state == R_DEAD for r in manager.replicas)


def test_crash_loop_becomes_verdict_not_infinite_relaunch(predictor):
    """A replica whose build ALWAYS fails must end R_DEAD via the
    RestartPolicy give-up, not relaunch forever."""
    cfg = _fleet_cfg(replicas=1)

    def bad_build(rid):
        raise RuntimeError("no devices for you")

    manager = ReplicaManager(bad_build, cfg)
    for r in manager.replicas:
        r.policy.give_up_after = 3
    # boot failure + identical relaunch failures until the verdict
    if not manager.replicas[0].launch():
        manager._schedule_relaunch(manager.replicas[0], ("boot-failed",),
                                   made_progress=False)
    r = manager.replicas[0]
    for _ in range(10):
        # wait for the (a)sync failure handling to settle: either the
        # verdict landed (R_DEAD) or the next relaunch is scheduled
        deadline = time.monotonic() + 5.0
        while r.state != R_DEAD and not (
                r.state == R_RELAUNCHING and r.relaunch_at is not None) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        if r.state == R_DEAD:
            break
        manager.tick(now=time.monotonic() + 3600.0)
    assert r.state == R_DEAD
    manager.close()


def test_relaunch_disabled_goes_dead(predictor):
    cfg = _fleet_cfg(replicas=2, fleet__relaunch=False)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        victim = router.manager.replicas[0]
        victim.engine.kill()
        router.manager.tick()
        assert victim.state == R_DEAD
        # the fleet keeps serving on the survivor
        freq = router.submit(_img(True, 5), timeout_ms=30_000)
        freq.wait(timeout=10.0)
        assert freq.state == SERVED
        assert freq.replica_id == router.manager.replicas[1].id
    finally:
        router.close()


# ---------------------------------------------------------------------------
# fleet-wide terminate-exactly-once
# ---------------------------------------------------------------------------

def test_fleet_terminate_exactly_once_under_kill(predictor):
    """The accounting invariant under the worst case: a replica dies
    mid-burst, work reroutes, and still every fleet request reaches
    EXACTLY one terminal state — counted both per-handle (double
    transitions raise in _finish's guard) and in the roll-up."""
    cfg = _fleet_cfg(replicas=2, fleet__health_interval_s=0.1)
    router, gate = _stub_fleet(predictor, cfg)
    terminal_counts = {}
    lock = threading.Lock()

    def on_done(req):
        with lock:
            terminal_counts[id(req)] = terminal_counts.get(id(req), 0) + 1

    try:
        handles = []
        stop = time.monotonic() + 2.0
        killed = False
        seed = 0
        while time.monotonic() < stop:
            freq = router.submit(_img(seed % 2 == 0, seed),
                                 timeout_ms=10_000)
            freq.add_done_callback(on_done)
            handles.append(freq)
            seed += 1
            if not killed and time.monotonic() > stop - 1.5:
                router.manager.replicas[0].engine.kill()
                killed = True
            time.sleep(0.005)
        _drain(router)
        snap = router.metrics.snapshot()
        c = snap["counters"]
        assert c["submitted"] == len(handles)
        assert snap["terminated"] == c["submitted"], "lost requests"
        assert all(n == 1 for n in terminal_counts.values())
        assert len(terminal_counts) == len(handles)
        assert all(f.state in (SERVED, SHED, EXPIRED, FAILED)
                   for f in handles)
        assert c["served"] > 0
    finally:
        router.close()


def test_done_callback_fires_for_already_terminal_request():
    """The router attaches its callback AFTER submit returns; a request
    shed inside submit must still fire the hook exactly once."""
    req = ServeRequest(None, None, (128, 160), None, time.monotonic())
    req._finish(SHED)
    fired = []
    req.add_done_callback(lambda r: fired.append(r.state))
    assert fired == [SHED]


def test_fleet_healthz_surface(predictor):
    cfg = _fleet_cfg(replicas=2)
    router, gate = _stub_fleet(predictor, cfg)
    try:
        h = router.healthz()
        assert h["ok"] and h["fleet"] and h["ready"] == 2
        states = [r["state"] for r in h["replicas"]]
        assert states == [R_READY, R_READY]
        assert h["batch_size"] == cfg.serve.batch_size
    finally:
        router.close()
