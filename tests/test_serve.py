"""Serving subsystem tests (ISSUE 2): admission control, micro-batching,
deadline/shed semantics, eval-parity of results, metrics, and the
zero-recompile steady-state invariant.

The engine under test is the tiny network on the quick-tier 128x160
buckets; one module-scoped Predictor shares its per-shape jit cache
across every engine instance, so the whole file compiles a handful of
tiny programs once.
"""

import base64
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.tester import _postprocess_batch, detections_from_keep
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.metrics import Histogram, LoweringCounter
from mx_rcnn_tpu.serve.queue import (EXPIRED, SERVED, SHED, BoundedQueue,
                                     DeadlineExceeded, ServeRequest,
                                     ShedError)
from mx_rcnn_tpu.tools.loadgen import init_predictor, synthetic_images


def _serve_cfg(**serve_kw):
    cfg = generate_config(
        "tiny", "synthetic",
        bucket__scale=128, bucket__max_size=160,
        bucket__shapes=((128, 160), (160, 128)),
        test__rpn_pre_nms_top_n=512, test__rpn_post_nms_top_n=64)
    if serve_kw:
        cfg = cfg.replace_in("serve", **serve_kw)
    return cfg


@pytest.fixture(scope="module")
def predictor():
    return init_predictor(_serve_cfg())


@pytest.fixture(scope="module")
def engine(predictor):
    """Warmed steady-state engine shared by the read-mostly tests."""
    eng = ServingEngine(predictor,
                        _serve_cfg(batch_size=2, max_delay_ms=30.0))
    eng.warmup()
    yield eng
    eng.close()


def _img(landscape=True, seed=0):
    rng = np.random.RandomState(seed)
    h, w = (128, 160) if landscape else (160, 128)
    return rng.randint(0, 256, size=(h, w, 3), dtype=np.uint8)


# ---------------------------------------------------------------------------
# config + primitives
# ---------------------------------------------------------------------------

def test_serve_config_section_and_overrides():
    cfg = generate_config("tiny", "synthetic", serve__batch_size=8,
                          serve__max_delay_ms=3.5)
    assert cfg.serve.batch_size == 8
    assert cfg.serve.max_delay_ms == 3.5
    # string CLI values coerce like every other section
    cfg = generate_config("tiny", "synthetic", serve__queue_depth="16")
    assert cfg.serve.queue_depth == 16


def test_engine_rejects_inconsistent_policy(predictor):
    bad = _serve_cfg(shed_watermark=100, queue_depth=10)
    with pytest.raises(ValueError, match="shed_watermark"):
        ServingEngine(predictor, bad, start=False)
    with pytest.raises(ValueError, match="batch_size"):
        ServingEngine(predictor, _serve_cfg(batch_size=0), start=False)


def test_histogram_percentiles_conservative():
    """Bucket-upper-bound percentiles never understate and overstate by
    at most one log-bucket (x1.39 at the default resolution)."""
    h = Histogram()
    vals = np.random.RandomState(0).uniform(1.0, 500.0, size=1000)
    for v in vals:
        h.record(v)
    for p in (50, 90, 99):
        true = float(np.percentile(vals, p))
        est = h.percentile(p)
        assert est >= true * 0.999, (p, est, true)
        assert est <= true * 1.40, (p, est, true)
    # overflow bucket reports the observed max, not +inf
    h.record(1e9)
    assert h.percentile(100) == 1e9
    assert Histogram().percentile(50) is None


def test_bounded_queue_sheds_at_watermark():
    q = BoundedQueue(depth=8, shed_watermark=2)
    reqs = [ServeRequest(None, None, (1, 1), None, 0.0) for _ in range(3)]
    assert q.offer(reqs[0]) and q.offer(reqs[1])
    assert not q.offer(reqs[2])  # at watermark: shed
    assert len(q) == 2


def test_bounded_queue_cancels_expired_before_dispatch():
    q = BoundedQueue(depth=8, shed_watermark=8)
    now = time.monotonic()
    dead = ServeRequest(None, None, (1, 1), now - 1.0, now - 2.0)
    live = ServeRequest(None, None, (1, 1), now + 60.0, now)
    q.offer(dead)
    q.offer(live)
    expired = []
    batch = q.take_batch(4, 0.0, on_expire=expired.append)
    assert batch == [live]
    assert dead.state == EXPIRED and expired == [dead]
    with pytest.raises(DeadlineExceeded):
        dead.wait(timeout=0)


def test_request_terminates_exactly_once():
    req = ServeRequest(None, None, (1, 1), None, 0.0)
    assert req._finish(SERVED, result={}) is True
    assert req._finish(SHED) is False  # already terminal
    assert req.state == SERVED and req.wait(timeout=0) == {}


# ---------------------------------------------------------------------------
# engine behavior
# ---------------------------------------------------------------------------

def test_bucket_routing(engine):
    """Landscape/portrait images route to their static buckets and both
    serve successfully."""
    _, _, b_land = engine.preprocess(_img(landscape=True))
    _, _, b_port = engine.preprocess(_img(landscape=False))
    assert b_land == (128, 160) and b_port == (160, 128)
    # oversized input shrinks-to-fit but stays in the orientation bucket
    big = np.zeros((640, 800, 3), np.uint8)
    _, info, b = engine.preprocess(big)
    assert b == (128, 160) and info[0] <= 128 and info[1] <= 160
    for landscape in (True, False):
        dets = engine.detect(_img(landscape))
        assert isinstance(dets, dict)
        for arr in dets.values():
            assert arr.shape[1] == 5


def test_batch_coalescing_under_max_delay(predictor):
    """Requests arriving inside the coalescing window ride ONE
    micro-batch; a full batch dispatches without waiting the window
    out."""
    eng = ServingEngine(predictor,
                        _serve_cfg(batch_size=4, max_delay_ms=1000.0))
    try:
        # timeout_ms=0 (no deadline): the first batch on this unwarmed
        # engine pays the batch-4 jit compile, which would otherwise trip
        # the completion-time deadline re-check — not this test's subject
        reqs = [eng.submit(_img(seed=i), timeout_ms=0) for i in range(3)]
        for r in reqs:
            r.wait(timeout=30.0)
        snap = eng.metrics.snapshot()
        assert snap["counters"]["batches"] == 1, snap
        assert snap["counters"]["served"] == 3
        assert all(r.batch_rows == 3 for r in reqs)
        assert snap["batch_occupancy"]["mean_rows"] == 3.0

        # full batch: 4 requests must NOT stall for the 1 s window
        t0 = time.monotonic()
        reqs = [eng.submit(_img(seed=i), timeout_ms=0) for i in range(4)]
        for r in reqs:
            r.wait(timeout=30.0)
        assert time.monotonic() - t0 < 0.9, "full batch waited the window"
        assert eng.metrics.snapshot()["counters"]["batches"] == 2
    finally:
        eng.close()


def test_deadline_expiry_and_watermark_shedding(predictor):
    """Admission control end to end: over-watermark requests shed with
    429 semantics, expired requests cancel BEFORE dispatch, live ones
    serve — and every request reaches exactly one terminal state."""
    eng = ServingEngine(
        predictor,
        _serve_cfg(batch_size=4, max_delay_ms=50.0, queue_depth=4,
                   shed_watermark=2),
        start=False)  # hold dispatch so the queue fills deterministically
    img = _img()
    r_expire = eng.submit(img, timeout_ms=30.0)
    r_live = eng.submit(img, timeout_ms=0)      # 0 = no deadline
    r_shed = eng.submit(img)                     # queue at watermark
    assert r_shed.state == SHED
    with pytest.raises(ShedError):
        r_shed.wait(timeout=0)
    time.sleep(0.06)                             # r_expire's deadline passes
    eng.start()
    assert r_live.wait(timeout=30.0) is not None
    with pytest.raises(DeadlineExceeded):
        r_expire.wait(timeout=30.0)
    snap = eng.metrics.snapshot()
    c = snap["counters"]
    assert (c["submitted"], c["served"], c["shed"], c["expired"]) \
        == (3, 1, 1, 1)
    assert snap["in_flight"] == 0 and snap["terminated"] == 3
    eng.close()
    # closed engine sheds new work instead of hanging it
    r_after = eng.submit(img)
    assert r_after.state == SHED


def test_deadline_expiring_during_coalescing_window(predictor):
    """A request ALIVE when collected but expiring while the dispatcher
    holds the partial batch for stragglers must terminate EXPIRED (504),
    never as a late success — the completion-time re-check."""
    eng = ServingEngine(predictor,
                        _serve_cfg(batch_size=4, max_delay_ms=400.0),
                        start=False)
    r = eng.submit(_img(), timeout_ms=100.0)
    eng.start()  # pops r immediately, then waits ~400 ms for company
    with pytest.raises(DeadlineExceeded):
        r.wait(timeout=30.0)
    c = eng.metrics.snapshot()["counters"]
    assert c["expired"] == 1 and c["served"] == 0
    eng.close()


def test_engine_detections_bit_equal_predictor(predictor, engine):
    """The acceptance parity check: an engine response must be BIT-EQUAL
    to composing the same padded micro-batch by hand and running the
    offline Predictor + eval postprocess + shared demux."""
    import jax.numpy as jnp

    cfg = engine.cfg
    img = _img(seed=7)
    dets = engine.detect(img)

    canvas, info, bucket = engine.preprocess(img)
    bh, bw = bucket
    n = cfg.serve.batch_size
    images = np.zeros((n, bh, bw, 3), np.float32)
    im_info = np.tile(np.array([bh, bw, 1.0], np.float32), (n, 1))
    images[0], im_info[0] = canvas, info
    rois, roi_valid, cls_prob, deltas = predictor.raw(images, im_info)
    boxes_b, scores_b, keep_b = map(np.asarray, _postprocess_batch(
        rois, roi_valid, cls_prob, deltas, jnp.asarray(im_info),
        jnp.asarray(im_info[:, 2]), engine._stds, engine._means,
        nms_thresh=cfg.test.nms, score_thresh=cfg.serve.score_thresh))
    expected = detections_from_keep(boxes_b, scores_b, keep_b, 0)

    assert sorted(dets) == sorted(expected)
    for c in expected:
        np.testing.assert_array_equal(dets[c], expected[c])
    assert expected, "degenerate check: random-init net emitted nothing"


def test_warmed_engine_mixed_buckets_zero_recompiles(engine):
    """THE serving recompile guard: after warmup, mixed landscape and
    portrait traffic (full and partial batches) must lower ZERO new
    programs — the serving analog of the train-step compile budget."""
    engine.detect(_img(True))   # both buckets already warm; settle once
    engine.detect(_img(False))
    programs_before = engine.program_count()
    with LoweringCounter() as lc:
        for i in range(6):
            dets = engine.detect(_img(landscape=i % 2 == 0, seed=i))
            assert isinstance(dets, dict)
    assert lc.n == 0, f"{lc.n} recompiles while serving warmed buckets"
    # the shared-predictor jit cache must not have grown either (the
    # module-scoped predictor may carry other engines' batch shapes, so
    # the budget is zero GROWTH, not an absolute count)
    assert engine.program_count() == programs_before


def test_metrics_snapshot_sanity(engine):
    snap = engine.metrics.snapshot()
    c = snap["counters"]
    assert c["served"] > 0 and c["failed"] == 0
    assert snap["terminated"] + snap["in_flight"] == c["submitted"]
    for hist in ("queue_wait_ms", "model_ms", "total_ms"):
        h = snap[hist]
        assert h["count"] > 0
        assert h["p50"] <= h["p90"] <= h["p99"], h
    occ = snap["batch_occupancy"]["mean_rows"]
    assert 0 < occ <= engine.cfg.serve.batch_size


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

@pytest.fixture()
def http_server(engine):
    from mx_rcnn_tpu.serve.server import make_server

    srv = make_server(engine, port=0, class_names=None)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    host, port = srv.server_address[:2]
    yield f"http://{host}:{port}"
    srv.shutdown()
    srv.server_close()


def _http(url, payload=None):
    if payload is None:
        req = urllib.request.Request(url)
    else:
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_detect_healthz_metrics(http_server):
    img = _img(seed=3)
    status, body = _http(http_server + "/detect", {
        "pixels_b64": base64.b64encode(img.tobytes()).decode(),
        "shape": list(img.shape)})
    assert status == 200
    assert "latency_ms" in body
    assert 1 <= body["batch_rows"] <= 2  # the documented wire field
    for det in body["detections"]:
        assert set(det) == {"class_id", "class", "score", "box"}
        assert len(det["box"]) == 4
    scores = [d["score"] for d in body["detections"]]
    assert scores == sorted(scores, reverse=True)

    status, health = _http(http_server + "/healthz")
    assert status == 200 and health["ok"] is True
    assert health["programs"] >= len(health["buckets"])

    status, snap = _http(http_server + "/metrics")
    assert status == 200 and snap["counters"]["served"] > 0

    status, err = _http(http_server + "/detect", {"shape": [2, 2, 3]})
    assert status == 400 and "error" in err
    # valid JSON that is not an object must 400, not drop the connection
    status, err = _http(http_server + "/detect", "image_b64")
    assert status == 400 and "JSON object" in err["error"]
    status, err = _http(http_server + "/nope")
    assert status == 404


def test_http_oversized_image_shrinks_to_fit(http_server):
    """An image whose resize target exceeds every bucket must be shrunk
    to fit (choose_bucket's contract, same step as the loader path) and
    served — historically it escaped as a raw ValueError that killed the
    handler thread (dropped connection, replica thread dead)."""
    rng = np.random.default_rng(7)
    img = rng.integers(0, 255, (900, 1400, 3), dtype=np.uint8)
    status, body = _http(http_server + "/detect", {
        "pixels_b64": base64.b64encode(img.tobytes()).decode(),
        "shape": list(img.shape)})
    assert status == 200 and "detections" in body


def test_http_image_b64_roundtrip(http_server):
    """The encoded-file payload path decodes through the same BGR→RGB
    convention as ``imread_rgb``."""
    import cv2

    img = _img(seed=11)
    ok, buf = cv2.imencode(".png", img[:, :, ::-1])  # encode as BGR file
    assert ok
    status, body = _http(http_server + "/detect", {
        "image_b64": base64.b64encode(buf.tobytes()).decode()})
    assert status == 200 and "detections" in body


def test_http_body_admission_bounds(http_server):
    """ISSUE 16 satellite: the 411/413 refusal contract (netio).  A
    peer CLAIMING a multi-GB Content-Length costs a 413 off the claim
    alone — before a single body byte is read — and a body with no
    Content-Length at all (chunked transfer included) is a 411."""
    from mx_rcnn_tpu.analysis.wirefuzz import http_post_raw

    host, _, port = http_server.removeprefix("http://").partition(":")
    t0 = time.monotonic()
    res = http_post_raw(host, int(port), "/detect", b"{}",
                        ctype="application/json",
                        content_length=3 << 30)
    assert res["status"] == 413
    assert time.monotonic() - t0 < 5.0  # refused, not buffered
    res = http_post_raw(host, int(port), "/detect", b"",
                        ctype="application/json",
                        content_length="absent")
    assert res["status"] == 411


def test_http_trickled_body_is_408_at_the_deadline(engine):
    """The slow-loris bound: per-recv socket timeouts never trip on a
    one-byte-per-tick sender, so the WHOLE body read carries a
    wall-clock deadline (server.body_deadline_s → 408)."""
    from mx_rcnn_tpu.analysis.wirefuzz import http_post_raw
    from mx_rcnn_tpu.serve.server import make_server

    srv = make_server(engine, port=0, class_names=None)
    srv.body_deadline_s = 1.0
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    host, port = srv.server_address[:2]
    try:
        res = http_post_raw(host, port, "/detect", b"x" * 400,
                            ctype="application/json", mode="trickle",
                            trickle_bytes=10 ** 9,
                            trickle_delay_s=0.05, timeout_s=20.0)
        assert res["status"] == 408
        assert res["elapsed_s"] < 10.0
    finally:
        srv.shutdown()
        srv.server_close()


def test_http_hostile_timeout_ms_is_400(http_server):
    """A peer-supplied inf/NaN/negative timeout_ms dies at admission as
    a 400 — wirefuzz found inf reaching ``Condition.wait`` as an
    OverflowError (a 500 for client bytes)."""
    img = _img(seed=5)
    payload = {"pixels_b64": base64.b64encode(img.tobytes()).decode(),
               "shape": list(img.shape)}
    for hostile in (float("inf"), float("nan"), -3.0, 1e38, "soon"):
        status, err = _http(http_server + "/detect",
                            dict(payload, timeout_ms=hostile))
        assert status == 400, (hostile, status, err)
        assert "timeout_ms" in err["error"]
    # a sane value still serves
    status, body = _http(http_server + "/detect",
                         dict(payload, timeout_ms=30000.0))
    assert status == 200 and "detections" in body


# ---------------------------------------------------------------------------
# loadgen
# ---------------------------------------------------------------------------

def test_loadgen_smoke_checks_pass(capsys):
    """The `make serve-smoke` path in miniature: closed loop on the tiny
    canvas, asserting the acceptance invariants (zero lost, zero
    recompiles) via --check."""
    from mx_rcnn_tpu.tools.loadgen import main

    rc = main(["--smoke", "--duration", "2", "--check",
               "--concurrency", "3"])
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rc == 0
    assert rec["lost"] == 0
    assert rec["recompiles_after_warmup"] == 0
    assert rec["served"] > 0 and rec["measured"] is True
    assert rec["submitted"] == (rec["served"] + rec["shed"]
                                + rec["expired"] + rec["failed"])
    assert rec["p50_ms"] <= rec["p99_ms"]
    assert rec["shed_rate"] == 0.0  # closed loop cannot overrun the queue


def test_loadgen_open_loop_sheds_gracefully_when_overdriven(capsys,
                                                            monkeypatch):
    """Open-loop arrivals far past capacity must terminate EVERY request
    (served, shed, or expired — none lost, none failed) with a tight
    admission queue — overload degrades by rejection, not collapse.

    The service time is a stub (50 ms per dispatched batch of 2, so at
    most 80 imgs/s over the two bucket lanes): "400 qps overdrives the
    engine" must hold by construction, not because the host happens to
    be slow — on a fast host the real tiny model served all 574 of 574
    and nothing shed."""
    from mx_rcnn_tpu.tools import loadgen
    from mx_rcnn_tpu.tools.loadgen import main, make_stub_run_fn

    monkeypatch.setattr(
        loadgen, "ServingEngine",
        lambda predictor, cfg: ServingEngine(
            predictor, cfg, run_fn=make_stub_run_fn(cfg, model_ms=50.0)))
    rc = main(["--smoke", "--mode", "open", "--duration", "2",
               "--qps", "400", "--timeout_ms", "250",
               "--set", "serve__queue_depth=8",
               "--set", "serve__shed_watermark=4"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert rec["lost"] == 0 and rec["failed"] == 0
    assert rec["submitted"] == rec["served"] + rec["shed"] + rec["expired"]
    # at 400 qps against a <=80 imgs/s engine with a depth-4 watermark,
    # admission control MUST have engaged
    assert rec["shed"] + rec["expired"] > 0, rec
    assert rec["recompiles_after_warmup"] == 0
