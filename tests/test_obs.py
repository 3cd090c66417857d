"""Unified observability layer tests (ISSUE 4).

Pinned properties:

* the ``serve.metrics`` back-compat shim re-exports the PROMOTED classes
  and the ``ServeMetrics`` snapshot stays bit-identical to the
  pre-promotion implementation (vendored here verbatim as the oracle),
  so ``docs/serve_bench_*.json`` comparisons remain valid;
* the registry is exact under concurrent recorders;
* spans nest/order correctly in the exported chrome trace and carry
  bound trace ids;
* ONE trace id survives a serve request's queue → engine → respond hops
  across threads;
* SIGUSR2 opens/closes a profiler window that rolls up to a parseable,
  non-empty device-time table;
* the Speedometer registry wiring leaves its stdout line byte-identical;
* the DISABLED hot path costs near zero (the seed fit loop had no obs
  code at all, so the delta vs seed is exactly the cost of the disabled
  branches measured here), and the measured enabled-mode overhead
  recorded in docs/obs_overhead.json is inside the <2% acceptance bar.
"""

import json
import os
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

from mx_rcnn_tpu.obs import trace as obs_trace
from mx_rcnn_tpu.obs.metrics import (Histogram, Registry, ServeMetrics,
                                     registry, start_metrics_server)
from mx_rcnn_tpu.obs.runrec import RunRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# back-compat shim + bit-identical snapshot format
# ---------------------------------------------------------------------------

def test_serve_metrics_shim_reexports_promoted_classes():
    import mx_rcnn_tpu.obs.metrics as obs_metrics
    import mx_rcnn_tpu.serve.metrics as serve_metrics

    assert serve_metrics.Histogram is obs_metrics.Histogram
    assert serve_metrics.ServeMetrics is obs_metrics.ServeMetrics
    assert serve_metrics.LoweringCounter is obs_metrics.LoweringCounter


class _OldHistogram:
    """The pre-promotion serve/metrics.py Histogram, verbatim — the
    oracle for bucket edges and percentile readout."""

    def __init__(self, lo=0.1, hi=30_000.0, buckets=40):
        self.bounds = np.geomspace(lo, hi, buckets)
        self.counts = np.zeros(buckets + 1, np.int64)
        self.total = 0
        self.sum = 0.0
        self.max = 0.0

    def record(self, value):
        i = int(np.searchsorted(self.bounds, value))
        self.counts[i] += 1
        self.total += 1
        self.sum += value
        self.max = max(self.max, value)

    def percentile(self, p):
        if self.total == 0:
            return None
        rank = int(np.ceil(p / 100.0 * self.total))
        rank = min(max(rank, 1), self.total)
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, rank))
        if i >= len(self.bounds):
            return float(self.max)
        return float(self.bounds[i])

    @property
    def mean(self):
        return self.sum / self.total if self.total else None


def _old_snapshot(counters, hists, rows):
    """The pre-promotion ServeMetrics.snapshot(), verbatim."""
    out = {"counters": dict(counters)}
    for name, h in hists.items():
        pct = {p: h.percentile(p) for p in (50, 90, 99)}
        out[name] = {
            "count": h.total,
            "mean": None if h.mean is None else round(h.mean, 3),
            **{f"p{p}": None if v is None else round(v, 3)
               for p, v in pct.items()},
            "max": round(h.max, 3) if h.total else None,
        }
    b = counters["batches"]
    out["batch_occupancy"] = {
        "batches": b,
        "mean_rows": round(rows / b, 3) if b else None,
        "padded_rows": counters["padded_rows"],
    }
    c = counters
    out["terminated"] = c["served"] + c["shed"] + c["expired"] + c["failed"]
    out["in_flight"] = c["submitted"] - out["terminated"]
    return out


def test_serve_snapshot_bit_identical_to_old_format():
    """Feed an identical traffic pattern into the promoted ServeMetrics
    and the vendored old implementation: the JSON must match byte for
    byte (docs/serve_bench_*.json comparability)."""
    rng = np.random.RandomState(0)
    new = ServeMetrics()
    old_counters = {k: 0 for k in ("submitted", "served", "shed",
                                   "expired", "failed", "batches",
                                   "padded_rows")}
    old_hists = {"queue_wait_ms": _OldHistogram(),
                 "model_ms": _OldHistogram(), "total_ms": _OldHistogram()}
    old_rows = 0
    for i in range(500):
        new.count("submitted")
        old_counters["submitted"] += 1
        q, t = rng.uniform(0.05, 900.0, 2)
        new.observe("queue_wait_ms", q)
        old_hists["queue_wait_ms"].record(q)
        terminal = ("served", "shed", "expired", "failed")[i % 4]
        new.count(terminal)
        old_counters[terminal] += 1
        new.observe("total_ms", t)
        old_hists["total_ms"].record(t)
        if i % 3 == 0:
            rows = 1 + i % 4
            m = float(rng.uniform(1.0, 50.0))
            new.observe_batch(rows, 4, m)
            old_counters["batches"] += 1
            old_counters["padded_rows"] += 4 - rows
            old_rows += rows
            old_hists["model_ms"].record(m)
    expect = _old_snapshot(old_counters, old_hists, old_rows)
    assert json.dumps(new.snapshot(), sort_keys=True) \
        == json.dumps(expect, sort_keys=True)
    # bucket edges pinned exactly
    np.testing.assert_array_equal(Histogram().bounds,
                                  np.geomspace(0.1, 30_000.0, 40))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_exact_under_concurrent_recorders():
    reg = Registry()
    threads, per = 8, 2000

    def worker(wid):
        for i in range(per):
            reg.inc("c.total")
            reg.observe("h.lat_ms", float(i % 7) + 0.5)
            reg.set_gauge(f"g.w{wid}", i)

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("c.total") == threads * per
    assert reg.hist("h.lat_ms").total == threads * per
    snap = reg.snapshot()
    assert snap["counters"]["c.total"] == threads * per
    assert snap["hists"]["h.lat_ms"]["count"] == threads * per
    assert all(snap["gauges"][f"g.w{w}"] == per - 1 for w in range(threads))


def test_registry_reset_is_prefix_scoped():
    reg = Registry()
    reg.inc("serve.submitted")
    reg.inc("train.steps")
    reg.observe("serve.total_ms", 1.0)
    reg.reset("serve.")
    assert reg.counter("serve.submitted") == 0  # recreated lazily at 0
    assert reg.counter("train.steps") == 1
    assert reg.hist("serve.total_ms") is None


def test_serve_metrics_survive_registry_reset_mid_traffic():
    """Registry.reset REMOVES entries; a ServeMetrics sharing that
    registry must keep recording (keys recreate at zero) instead of
    KeyError-ing the dispatcher thread mid-traffic."""
    reg = Registry()
    m = ServeMetrics(registry=reg)
    m.count("submitted")
    m.observe_batch(2, 4, 5.0)
    reg.reset()  # e.g. a phase boundary clearing the process registry
    m.count("served")
    m.observe_batch(1, 4, 3.0)
    snap = m.snapshot()
    assert snap["counters"]["served"] == 1
    assert snap["counters"]["submitted"] == 0  # cleared, recreated at 0
    assert snap["batch_occupancy"]["batches"] == 1
    assert snap["model_ms"]["count"] == 1
    assert m.counters["shed"] == 0 and "total_ms" in m.hists


def test_serve_metrics_on_shared_registry_namespaces_cleanly():
    """A ServeMetrics on the process-style shared registry publishes
    under serve.* without clobbering other subsystems, and its reset
    leaves them alone."""
    reg = Registry()
    reg.inc("train.steps", 5)
    m = ServeMetrics(registry=reg)
    m.count("submitted")
    assert reg.counter("serve.submitted") == 1
    assert reg.counter("train.steps") == 5
    m.reset()
    assert reg.counter("serve.submitted") == 0
    assert reg.counter("train.steps") == 5


# ---------------------------------------------------------------------------
# spans + chrome trace
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering_in_chrome_trace(tmp_path):
    obs_trace.enable()
    obs_trace.reset()
    try:
        obs_trace.set_trace_id("tid-1")
        with obs_trace.span("outer"):
            time.sleep(0.002)
            with obs_trace.span("inner"):
                time.sleep(0.002)
        obs_trace.set_trace_id(None)
        with obs_trace.span("after"):
            pass
        path = obs_trace.export_chrome_trace(str(tmp_path / "t.json"))
    finally:
        obs_trace.disable()
    evs = json.load(open(path))["traceEvents"]
    by = {e["name"]: e for e in evs}
    outer, inner, after = by["outer"], by["inner"], by["after"]
    # containment: inner lies inside outer on the time axis, one deeper
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert inner["args"]["depth"] == outer["args"]["depth"] + 1
    # ordering: "after" starts after outer ends, back at depth 0
    assert after["ts"] >= outer["ts"] + outer["dur"] - 1
    assert after["args"]["depth"] == outer["args"]["depth"]
    # bound trace id attached while bound, absent after clearing
    assert outer["args"]["trace_id"] == "tid-1"
    assert inner["args"]["trace_id"] == "tid-1"
    assert "trace_id" not in after["args"]
    assert all(e["tid"] == threading.get_ident() for e in evs)


def test_trace_disabled_emits_nothing():
    obs_trace.disable()
    obs_trace.reset()
    with obs_trace.span("x"):
        pass
    obs_trace.complete("y", 1.0)
    obs_trace.async_begin("z", "t1")
    assert obs_trace.events() == []


def test_trace_buffer_is_bounded():
    obs_trace.enable(cap=10)
    obs_trace.reset()
    try:
        for i in range(50):
            with obs_trace.span(f"s{i}"):
                pass
        assert len(obs_trace.events()) == 10
        assert obs_trace.dropped() == 40
        # a ring: the ten kept are the newest, oldest first
        assert [e["name"] for e in obs_trace.events()] == [
            f"s{i}" for i in range(40, 50)]
        # a smaller cap keeps the newest of what is held
        obs_trace.enable(cap=3)
        assert [e["name"] for e in obs_trace.events()] == [
            "s47", "s48", "s49"]
    finally:
        obs_trace.disable()


# ---------------------------------------------------------------------------
# trace-id continuity across a serve request's hops
# ---------------------------------------------------------------------------

class _FakePredictor:
    _fns = {}


def _fake_run_outputs(cfg):
    n = cfg.serve.batch_size
    r, C = 4, cfg.num_classes
    return (np.zeros((n, r, C * 4), np.float32),
            np.zeros((n, r, C), np.float32),
            np.zeros((n, C, r), bool))


def test_trace_id_continuity_queue_engine_respond():
    """ONE trace id stamped at admission must appear on the queue-wait
    span (dispatcher thread), the engine batch span, and the respond-hop
    async close — the cross-thread lifecycle the chrome trace shows."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.serve.engine import ServingEngine

    cfg = generate_config(
        "tiny", "synthetic",
        bucket__scale=128, bucket__max_size=160,
        bucket__shapes=((128, 160), (160, 128)),
        serve__batch_size=2, serve__max_delay_ms=5.0)
    obs_trace.enable()
    obs_trace.reset()
    eng = None
    try:
        eng = ServingEngine(_FakePredictor(), cfg, start=False)
        outs = _fake_run_outputs(cfg)
        eng._run = lambda images, im_info: outs  # no model: hops only
        eng.start()
        img = np.zeros((128, 160, 3), np.uint8)
        req = eng.submit(img, timeout_ms=0)
        req.wait(timeout=30.0)
        tid = req.trace_id
        assert tid is not None
        evs = obs_trace.events()
        begin = [e for e in evs if e["ph"] == "b"
                 and e["name"] == "serve.request" and e["id"] == tid]
        qwait = [e for e in evs if e["name"] == "serve.queue_wait"
                 and e["args"].get("trace_id") == tid]
        batch = [e for e in evs if e["name"] == "serve.batch"
                 and tid in (e["args"].get("trace_ids") or [])]
        end = [e for e in evs if e["ph"] == "e"
               and e["name"] == "serve.request" and e["id"] == tid]
        assert begin and qwait and batch and end, (
            f"missing hops for {tid}: b={len(begin)} q={len(qwait)} "
            f"batch={len(batch)} e={len(end)}")
        assert end[0]["args"]["state"] == "served"
        # the hops genuinely crossed threads: admission on this thread,
        # dispatch on the bucket's dispatcher thread
        assert begin[0]["tid"] == threading.get_ident()
        assert batch[0]["tid"] != begin[0]["tid"]
    finally:
        if eng is not None:
            eng.close()
        obs_trace.disable()


def test_shed_request_closes_its_trace_interval():
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.serve.engine import ServingEngine

    cfg = generate_config(
        "tiny", "synthetic",
        bucket__scale=128, bucket__max_size=160,
        bucket__shapes=((128, 160), (160, 128)),
        serve__queue_depth=2, serve__shed_watermark=1)
    obs_trace.enable()
    obs_trace.reset()
    try:
        eng = ServingEngine(_FakePredictor(), cfg, start=False)
        img = np.zeros((128, 160, 3), np.uint8)
        eng.submit(img, timeout_ms=0)          # fills the watermark
        shed = eng.submit(img, timeout_ms=0)   # shed at admission
        assert shed.state == "shed"
        ends = [e for e in obs_trace.events() if e["ph"] == "e"
                and e["id"] == shed.trace_id]
        assert ends and ends[0]["args"]["state"] == "shed"
        eng.close()
    finally:
        obs_trace.disable()


# ---------------------------------------------------------------------------
# SIGUSR2 profiler window
# ---------------------------------------------------------------------------

def test_sigusr2_window_produces_parseable_rollup(tmp_path):
    """The toggle runs on a worker thread (NEVER jax.profiler inline in
    the handler — that deadlocks a busy process), so effects are polled
    with a deadline."""
    import jax
    import jax.numpy as jnp

    import mx_rcnn_tpu.obs.profiler as prof

    def wait_for(pred, what, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.05)
        pytest.fail(f"timed out waiting for {what}")

    old = signal.getsignal(signal.SIGUSR2)
    try:
        prof.install_sigusr2(str(tmp_path))
        signal.raise_signal(signal.SIGUSR2)  # opens the window (async)
        wait_for(lambda: prof._active_dir is not None, "window open")

        @jax.jit
        def f(x):
            return (jnp.sin(x) @ x).sum()

        x = jnp.ones((96, 96))
        f(x).block_until_ready()
        signal.raise_signal(signal.SIGUSR2)  # closes + rolls up (async)
        rollup_path = tmp_path / "sigusr2-0" / "rollup.json"
        wait_for(rollup_path.exists, "rollup.json")
    finally:
        signal.signal(signal.SIGUSR2, old)
    roll = json.load(open(rollup_path))
    assert any(groups for groups in roll["by_op_class"].values()), roll
    total = sum(ms for groups in roll["by_op_class"].values()
                for ms in groups.values())
    assert total > 0


# ---------------------------------------------------------------------------
# run records + /metrics exporter
# ---------------------------------------------------------------------------

def test_runrec_events_schema_and_bench_summary(tmp_path):
    reg = Registry()
    reg.inc("train.steps", 7)
    rr = RunRecord("train", base_dir=str(tmp_path))
    rr.event("epoch_start", epoch=0)
    rr.event("log", epoch=0, nbatch=2, loss=np.float32(1.5))  # np degrades
    summary = rr.finish(metric="train_samples_per_sec", value=12.5,
                        unit="imgs/s", registry=reg)
    rr.close()
    lines = [json.loads(line) for line in open(rr.events_path)]
    assert len(lines) == 4  # run_start + 2 events + run_finish
    for rec in lines:
        assert isinstance(rec["ts"], float) and isinstance(rec["event"], str)
    assert [r["event"] for r in lines] == ["run_start", "epoch_start",
                                           "log", "run_finish"]
    assert lines[2]["loss"] == 1.5
    disk = json.load(open(rr.summary_path))
    for d in (summary, disk):
        assert d["metric"] == "train_samples_per_sec"
        assert d["value"] == 12.5 and d["measured"] is True
        assert d["metrics"]["counters"]["train.steps"] == 7


def test_metrics_http_scrape(tmp_path):
    reg = Registry()
    reg.inc("train.steps", 3)
    reg.observe("train.step_ms", 20.0)
    reg.set_gauge("loader.queue_depth", 4)
    srv = start_metrics_server(reg, port=0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            snap = json.loads(resp.read())
        assert snap["counters"]["train.steps"] == 3
        assert snap["gauges"]["loader.queue_depth"] == 4
        assert snap["hists"]["train.step_ms"]["count"] == 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            assert json.loads(resp.read())["ok"] is True
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# Speedometer: registry wiring, stdout byte-identical
# ---------------------------------------------------------------------------

def test_speedometer_stdout_byte_identical_and_registry(monkeypatch):
    import mx_rcnn_tpu.core.fit as fit_mod

    lines = []
    reg = Registry()
    s = fit_mod.Speedometer(batch_size=2, frequent=2, log=lines.append,
                            registry=reg)
    s._tic = 100.0
    monkeypatch.setattr(fit_mod.time, "perf_counter", lambda: 101.0)
    s(3, 40, {"loss": 1.23456, "rpn_acc": 0.875})
    # the exact reference-port format the seed printed (regression pin:
    # the registry wiring must not perturb a byte of it)
    assert lines == ["Epoch[3] Batch [40] Speed: 2.00 samples/sec, "
                     "loss=1.2346, rpn_acc=0.8750"]
    assert reg.gauge("train.samples_per_sec") == pytest.approx(2.0)
    assert reg.gauge("train.metric.loss") == pytest.approx(1.23456)
    # non-log batches print nothing and record nothing new
    s(3, 41, {})
    assert len(lines) == 1


# ---------------------------------------------------------------------------
# disabled-mode overhead
# ---------------------------------------------------------------------------

def test_disabled_mode_overhead_near_zero():
    """The seed fit loop had NO obs code; the delta vs seed is exactly
    the disabled branches left in the hot path: two disabled span()
    calls, two `rec is None` checks and one sentinel-`next` per step.
    Budget: <=1% of the measured tiny step (12.9 ms on this box,
    docs/obs_overhead.json) = 129 µs; asserted with >2x slack at 50 µs
    for a contended box."""
    obs_trace.disable()
    rec = None
    it = iter(range(10_000))
    _END = object()
    n = 0
    t0 = time.perf_counter()
    while True:
        with obs_trace.span("train.data_wait"):
            item = next(it, _END)
        if item is _END:
            break
        if rec is not None:  # pragma: no cover - disabled path
            pass
        with obs_trace.span("train.dispatch"):
            pass
        if rec is not None:  # pragma: no cover
            pass
        n += 1
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 50e-6, f"disabled obs path costs {per_step * 1e6:.1f}µs/step"


def test_recorded_overhead_inside_acceptance_bar():
    """docs/obs_overhead.json is the measured enabled-vs-disabled record
    the acceptance criterion asks for: present, well-formed, <2%."""
    path = os.path.join(REPO, "docs", "obs_overhead.json")
    rec = json.load(open(path))
    assert rec["metric"] == "obs_enabled_step_overhead_pct"
    assert rec["measured"] is True
    assert rec["disabled_step_ms_p50"] > 0
    assert abs(rec["value"]) < 2.0
