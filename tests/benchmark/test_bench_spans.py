"""Host spans on the device trace (``benchmark/hostspans.py``) and the
readers this adds: the recorded chip trace with gaps planted in it and
hand-made spans of the fit and stager threads around them."""

import json
import os

import pytest

from bench_tiny import ROOT
from benchmark import hostspans, trace, window
from benchmark import run as bench_run
from benchmark.families import _detector

BENCH = bench_run.manifest()
MS = 1e6                   # ns
US = 1e-6                  # s: a span's stamp is a float of epoch us, good
#                            to a quarter of one
HOST = 1_790_000_000e9     # host clock (unix ns) at the device clock's zero
WARMUP = 4                 # log edges at 2, 4, 6, 8: traced from 4 to 8
SPAN_READERS = ("idle.log_step_ms", "idle.data_wait_pct", "idle.unnamed_pct",
                "stage.assemble_ms", "stage.place_ms")


def _reads_hostspans(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".py")) as f:
        return "hostspans" in f.read()


# the readers this file is about: those built on benchmark/hostspans.py
NEW = [m["name"] for m in BENCH["per_layer"] if _reads_hostspans(m["name"])]


def _recorded(sync_gap_ms=9.0, wait_gap_ms=100.0):
    """The recorded three executions A, B, C with the device left idle for
    exactly ``sync_gap_ms`` between A and B (a log step's sync) and
    ``wait_gap_ms`` between B and C (the loop waiting for a batch); None
    leaves a gap as recorded (15 us).  Returns the reduced two-step window
    and its executions."""
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "small_trace.json")) as f:
        neutral = json.load(f)
    dev = neutral["devices"][0]
    red = trace.Reduced(neutral, steps=neutral["steps"], chips=1)
    (_, a1), (b0, b1) = hostspans.executions(red.devices[0], red.steps)
    c0 = red.devices[0]["hi"]
    by_sync = 0.0 if sync_gap_ms is None else sync_gap_ms * MS - (b0 - a1)
    by_wait = 0.0 if wait_gap_ms is None else wait_gap_ms * MS - (c0 - b1)

    def shift(t):
        return t + (by_sync if t >= b0 else 0.0) + (
            by_wait if t >= c0 else 0.0)

    dev["ops"] = [[n, p, shift(s), d] for n, p, s, d in dev["ops"]]
    dev["programs"] = [[n, shift(s), d] for n, s, d in dev["programs"]]
    red = trace.Reduced(neutral, steps=neutral["steps"], chips=1)
    return red, hostspans.executions(red.devices[0], red.steps)


def _span(name, start_ns, end_ns, tid=1, **args):
    """A span as obs/trace.py records it (ts, dur in us on the host clock),
    from device-clock ns."""
    return {"name": name, "ph": "X", "ts": (HOST + start_ns) * 1e-3,
            "dur": (end_ns - start_ns) * 1e-3, "pid": 7, "tid": tid,
            "args": dict(args, depth=0)}


def _events(runs, wait_gap_ms=100.0, late_dispatch=False):
    """What the fit loop (tid 1) and the stager (tid 2) leave around the
    recorded executions A, B and the start of C: steps 6, 7, 8."""
    (a0, a1), (b0, b1) = runs
    c0 = b1 + wait_gap_ms * MS
    ev = [_span("train.log", a0 - (9 - s) * 100 * MS,
                a0 - (9 - s) * 100 * MS + MS, step=s,
                backend_compile_s=4.5) for s in (2, 4)]
    # step 6, a log step: dispatched a step ahead of the device, then the sync
    # waits for A, fetches for 0.2 ms, the log line takes 3 ms
    ev += [_span("train.data_wait", a0 - 131 * MS, a0 - 130 * MS, step=6),
           _span("train.dispatch", a0 - 130 * MS, a0 - 129 * MS, step=6),
           _span("train.hooks", a0 - 129 * MS, a0 - 128.9 * MS, step=6),
           _span("train.sync", a0 - 128.9 * MS, a1 + 0.2 * MS, step=6, n=2,
                 fetch_us=200.0),
           _span("train.log", a1 + 0.2 * MS, a1 + 3.2 * MS, step=6,
                 backend_compile_s=4.5)]
    # step 7: the batch is there (0.5 ms), the dispatch takes 6 ms and the
    # device starts B 5.3 ms into it (9 ms after A ended)
    d7 = b0 + MS if late_dispatch else a1 + 3.7 * MS
    ev += [_span("train.data_wait", a1 + 3.2 * MS, a1 + 3.7 * MS, step=7),
           _span("train.dispatch", d7, d7 + 6 * MS, step=7),
           _span("train.hooks", d7 + 6 * MS, d7 + 6.1 * MS, step=7)]
    # step 8: the loop waits for its batch from 20 ms before B ends until
    # 0.5 ms before C starts; the stager assembled it all that time but
    # for the last 9.5 ms, in which it placed it
    ev += [_span("train.data_wait", b1 - 20 * MS, c0 - 0.5 * MS, step=8),
           _span("train.dispatch", c0 - 0.5 * MS, c0 + MS, step=8),
           _span("train.hooks", c0 + MS, c0 + 1.1 * MS, step=8),
           _span("train.sync", c0 + 1.1 * MS, c0 + 140 * MS, step=8, n=2,
                 fetch_us=200.0),
           _span("train.log", c0 + 140 * MS, c0 + 143 * MS, step=8,
                 backend_compile_s=4.5),
           _span("stage.assemble", b1 - 30 * MS, c0 - 10 * MS, tid=2, seq=8),
           _span("stage.place", c0 - 10 * MS, c0 - 0.5 * MS, tid=2, seq=8),
           _span("stage.put_wait", c0 - 0.5 * MS, c0 - 0.4 * MS, tid=2,
                 seq=8),
           _span("stage.assemble", a0 + 10 * MS, a0 + 14 * MS, tid=2, seq=7),
           _span("stage.place", a0 + 14 * MS, a0 + 16 * MS, tid=2, seq=7)]
    ev += [_span("setup.init", a0 - 3000 * MS, a0 - 1000 * MS),
           _span("setup.load", a0 - 990 * MS, a0 - 900 * MS),
           _span("setup.load", a0 - 900 * MS, a0 - 890 * MS),
           _span("train.dispatch", a0 - 880 * MS, a0 - 800 * MS, step=1)]
    for t, step in ((a0 - 2000 * MS, None), (a0 - 850 * MS, 1),
                    (c0 + 500 * MS, 9)):
        ev.append({"name": "compile.lowering", "ph": "i", "s": "t",
                   "ts": (HOST + t) * 1e-3, "pid": 7, "tid": 1,
                   "args": {"step": step}})
    return ev


def _ctx(red, events):
    return {"trace": red, "cell": {"traffic": {"warmup_steps": WARMUP}},
            "hostspans.events": events}


def _read(ctx, names):
    return {n: bench_run.read_metric(n, ctx) for n in names}


def test_a_gap_lands_on_the_span_that_covers_it():
    red, runs = _recorded()
    events = _events(runs)
    al = hostspans.align(red, events, WARMUP)
    assert al["first_step"] == 6
    assert al["offset_ns"] == pytest.approx(HOST, abs=1.0)
    # the least slack is step 7's: B starts 9 ms after A ends, its dispatch
    # began 3.7 ms after
    assert al["slack_us"] == pytest.approx(5300.0, abs=1.0)
    got = hostspans.attribute(red, events, WARMUP)
    # the log step's gap lies under step 6's sync and log line and step 7's
    # wait and dispatch; the planted one under step 8's wait but for the
    # 0.5 ms of its dispatch
    assert got["idle_s"] == pytest.approx(
        {"train.sync": 0.2e-3, "train.log": 3e-3, "train.data_wait": 100e-3,
         "train.dispatch": 5.8e-3, "unnamed": 0.0}, abs=US)
    # the sync, the log line and the dispatch after them; step 8's dispatch
    # follows no log step
    assert got["log_step_s"] == pytest.approx(8.5e-3, abs=US)
    assert got["log_steps"] == 1
    # all of the idle is accounted for: named, unnamed or inside a step
    idle = red.window_s - red.busy_s()
    assert sum(got["idle_s"].values()) + got["short_s"] == pytest.approx(
        idle, rel=1e-5)
    assert idle == pytest.approx(sum(s for _, s in red.idle_gaps()), rel=1e-6)


def test_a_planted_data_wait_shows_in_its_metric_and_in_nothing_else():
    names = SPAN_READERS + ("host.clock_slack_us", "host.clock_tied")
    base_red, base_runs = _recorded(wait_gap_ms=0.0)
    base = _read(_ctx(base_red, _events(base_runs, wait_gap_ms=0.0)), names)
    red, runs = _recorded(wait_gap_ms=100.0)
    got = _read(_ctx(red, _events(runs, wait_gap_ms=100.0)), names)
    assert base["idle.data_wait_pct"] == pytest.approx(
        100 * 0.5e-3 / base_red.window_s, abs=1e-3)
    assert got["idle.data_wait_pct"] == pytest.approx(
        100 * 100e-3 / red.window_s, abs=1e-3)
    # (the stager's long assemble is the planted cause itself)
    for name in ("idle.log_step_ms", "host.clock_slack_us",
                 "stage.place_ms", "idle.unnamed_pct"):
        assert got[name] == pytest.approx(base[name], abs=1e-3), name
    assert got["idle.log_step_ms"] == pytest.approx(8.5, abs=1e-3)
    assert got["idle.unnamed_pct"] == pytest.approx(0.0, abs=1e-3)
    assert got["host.clock_tied"] == 1.0
    # the stager's spans that ran in the window, mean per batch
    assert got["stage.assemble_ms"] == pytest.approx((120 + 4) / 2)
    assert got["stage.place_ms"] == pytest.approx((9.5 + 2) / 2)


def test_an_idle_stretch_no_span_covers_is_unnamed():
    red, runs = _recorded()
    events = [e for e in _events(runs)
              if not (e["name"] == "train.log" and e["args"]["step"] == 6)]
    # without step 6's log span the edges read 2, 4, 8: the one that
    # stopped the trace is not among them and the tie cannot be made
    assert hostspans.align(red, events, WARMUP) is None
    events = [e for e in _events(runs)
              if not (e["name"] == "train.data_wait"
                      and e["args"]["step"] == 8)]
    got = hostspans.attribute(red, events, WARMUP)
    assert got["idle_s"]["unnamed"] == pytest.approx(99.5e-3, abs=US)
    ctx = _ctx(red, events)
    assert bench_run.read_metric("idle.unnamed_pct", ctx) == pytest.approx(
        100 * 99.5e-3 / red.window_s, abs=1e-3)
    assert bench_run.read_metric("idle.data_wait_pct", ctx) == pytest.approx(
        100 * 0.5e-3 / red.window_s, abs=1e-3)


def test_a_negative_slack_silences_every_span_reader():
    red, runs = _recorded()
    ctx = _ctx(red, _events(runs, late_dispatch=True))
    # step 7's dispatch span begins 1 ms after its execution started
    assert bench_run.read_metric("host.clock_slack_us", ctx) == \
        pytest.approx(-1000.0, abs=1.0)
    assert bench_run.read_metric("host.clock_tied", ctx) == 0.0
    assert _read(ctx, SPAN_READERS) == dict.fromkeys(SPAN_READERS)
    assert hostspans.attribute(red, ctx["hostspans.events"], WARMUP) is None


@pytest.mark.parametrize("warmup,frequent,last", [(20, 20, 200), (4, 2, 30),
                                                  (50, 20, 300), (7, 5, 60)])
def test_step_arithmetic_is_window_edges(warmup, frequent, last):
    """The steps of the trace's first and last log edge as the driver's
    ``window.Edges`` decided them, from the ``train.log`` spans alone."""
    logs = [(10.0 + 0.7 * k, frequent * k)
            for k in range(1, last // frequent + 1)]
    edges = window.Edges(warmup, 5.0, traced=True)
    actions = [edges.add(t, step) for t, step in logs]
    want = (logs[actions.index("start_trace")][1],
            logs[actions.index("stop_trace")][1])
    events = [{"name": "train.log", "ph": "X", "ts": t * 1e6, "dur": 900.0,
               "pid": 1, "tid": 1, "args": {"step": step}}
              for t, step in logs]
    assert hostspans.traced_steps(events, warmup) == want
    first_log = -(-warmup // frequent) * frequent
    assert want == (first_log, first_log + window.TRACED_INTERVALS * frequent)
    # a run cut before the trace stopped has no traced window
    assert hostspans.traced_steps(events[:actions.index("stop_trace")],
                                  warmup) is None


def test_setup_and_compile_readers():
    red, runs = _recorded()
    ctx = _ctx(red, _events(runs))
    got = _read(ctx, ("setup.init_s", "setup.load_s", "setup.first_step_s",
                      "setup.backend_compile_s", "compile.lowerings"))
    assert got == pytest.approx(
        {"setup.init_s": 2.0, "setup.load_s": 0.1, "setup.first_step_s": 0.08,
         "setup.backend_compile_s": 4.5, "compile.lowerings": 2})
    # spans there but none of a name: the number 0, not nothing
    bare = [e for e in ctx["hostspans.events"]
            if not e["name"].startswith(("setup.", "stage."))]
    got = _read(_ctx(red, bare), ("setup.load_s", "stage.place_ms"))
    assert got == {"setup.load_s": 0.0, "stage.place_ms": 0.0}


def test_spans_come_from_the_programs_buffer():
    from mx_rcnn_tpu.obs import trace as obs_trace

    red, _ = _recorded()
    ctx = {"trace": red, "cell": {"traffic": {"warmup_steps": WARMUP}}}
    obs_trace.enable()
    obs_trace.reset()
    try:
        # a program that collected nothing of the fit loop: no reader speaks
        with obs_trace.span("serve.request"):
            pass
        assert hostspans.spans(dict(ctx)) is None
        assert bench_run.read_metric("setup.init_s", dict(ctx)) is None
        with obs_trace.span("setup.init"):
            pass
        with obs_trace.span("train.dispatch", step=1):
            pass
        assert len(hostspans.spans(dict(ctx))) == 3
        assert bench_run.read_metric("setup.init_s", dict(ctx)) >= 0.0
        # no log edges: setup readers speak, the tie cannot be made
        assert bench_run.read_metric("host.clock_slack_us", dict(ctx)) is None
        assert bench_run.read_metric("host.clock_tied", dict(ctx)) is None
        assert bench_run.read_metric("compile.lowerings", dict(ctx)) is None
    finally:
        obs_trace.disable()
        obs_trace.reset()


@pytest.mark.parametrize("name", NEW)
def test_new_reader_returns_nothing_without_a_trace(name):
    ctx = {"trace": None, "counters": {}, "peak_bytes": 0, "bytes_limit": 0,
           "window": {"slowest_ms_per_step": 200.0}, "layers": [],
           "stages": _detector.STAGES, "chips": 1, "cell": {},
           "hostspans.events": _events(_recorded()[1])}
    assert bench_run.read_metric(name, ctx) is None
    # and without spans, on the recorded trace of the program before the
    # new scopes: only what that program names by its jitted functions
    # (``jit(roi_align)`` is a path component too) is read
    red, _ = _recorded()
    ctx = {"trace": red, "cell": {"traffic": {"warmup_steps": WARMUP}},
           "stages": _detector.STAGES, "hostspans.events": None}
    value = bench_run.read_metric(name, ctx)
    assert (value is not None) == (name in (
        "step.unscoped_ms", "roi_align.device_ms",
        "proposal_target.device_ms", "anchor_target.device_ms")), value


def test_stages_and_the_unscoped_rest_add_up_to_the_step():
    red, _ = _recorded(sync_gap_ms=None, wait_gap_ms=None)
    ctx = {"trace": red, "stages": _detector.STAGES}
    step_ms = bench_run.read_metric("step.device_ms", ctx)
    unscoped = bench_run.read_metric("step.unscoped_ms", ctx)
    stages = [1e3 * (red.scope_s(s) or 0.0) / red.steps
              for s in _detector.STAGES]
    assert 0 < unscoped < 0.05 * step_ms
    # ops on one device do not overlap by more than rounding
    assert unscoped + sum(stages) == pytest.approx(step_ms, rel=1e-3)
    # the recorded program has no roi_head scope: its reader finds nothing
    assert bench_run.read_metric("roi_head.device_ms", ctx) is None
    # a nested scope and a jitted function of its name count once
    ops = [["a", "jit(step)/jvp(rcnn_losses)/roi_align/jit(roi_align)/dot",
            0.0, 40.0],
           ["b", "jit(step)/transpose(jvp(rcnn_losses))/roi_align/mul",
            50.0, 30.0],
           ["c", "jit(step)/jvp(rcnn_losses)/roi_head/conv", 90.0, 10.0],
           ["d", "jit(step)/optimizer/add", 100.0, 5.0],
           ["e", "", 110.0, 2.0]]
    small = trace.Reduced(
        {"devices": [{"name": "d", "ops": ops + [
            [n, p, s + 200.0, d] for n, p, s, d in ops],
            "programs": [["jit_step(1)", 0.0, 120.0],
                         ["jit_step(1)", 200.0, 120.0]]}]},
        steps=1, chips=1)
    ctx = {"trace": small, "stages": _detector.STAGES}
    assert bench_run.read_metric("roi_align.device_ms", ctx) == \
        pytest.approx(70e-6)
    assert bench_run.read_metric("roi_head.device_ms", ctx) == \
        pytest.approx(10e-6)
    assert bench_run.read_metric("step.unscoped_ms", ctx) == \
        pytest.approx(2e-6)
    assert bench_run.read_metric("nms.device_ms", ctx) is None


def test_a_loops_body_counts_with_its_loop():
    """A ``while`` op lies on the device line around its body's ops.  The
    copies the compiler makes in a body carry no name path, and a body
    under ``lax.map`` around a checkpoint is named from ``closed_call``,
    with no stage in its path: both are the stage's where the ``while``
    is, and unscoped where the ``while`` is."""
    from benchmark import hostspans

    ops = [["while.1", "jit(step)/jvp(Net)/l0/kda_mixer/while", 0.0, 100.0],
           ["fusion.1", "closed_call/checkpoint/kda_scan/kda_solve/dot",
            10.0, 30.0],
           ["fusion.2", "closed_call/checkpoint/mul", 50.0, 20.0],
           ["copy.2", "", 70.0, 20.0],
           ["copy.1", "", 100.0, 7.0],
           ["while.2", "jit(step)/while", 110.0, 20.0],
           ["fusion.3", "closed_call/add", 112.0, 10.0],
           ["fusion.4", "jit(step)/optimizer/add", 130.0, 5.0]]
    red = trace.Reduced(
        {"devices": [{"name": "d", "ops": ops + [
            [n, p, s + 200.0, d] for n, p, s, d in ops],
            "programs": [["jit_step(1)", 0.0, 140.0],
                         ["jit_step(1)", 200.0, 140.0]]}]},
        steps=1, chips=1)
    stages = ("kda_mixer", "optimizer")
    # the copy, and the unstaged loop with its body: not the staged one's
    assert hostspans.unscoped_s(red, stages) == pytest.approx(27e-9)
    ctx = {"trace": red, "stages": stages}
    assert bench_run.read_metric("step.unscoped_ms", ctx) == \
        pytest.approx(27e-6)
    staged = sum(red.scope_s(s) for s in stages)
    assert staged + hostspans.unscoped_s(red, stages) == pytest.approx(
        red.busy_s()) == pytest.approx(132e-9)
    # an inner scope is read through the body's own path
    assert bench_run.read_metric("kda_solve.device_ms", ctx) == \
        pytest.approx(30e-6)


def new_metrics_name_source_and_layer(bench):
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for m in bench["per_layer"]:
        # (of the detectors' cells: a later family's readers name layers
        # and cells of their own)
        if m["name"] in NEW and "r101-coco.train" in m.get(
                "workloads", ["r101-coco.train"]):
            assert m["layer"] in layers, m
            # the detector's own scopes are asked of the detector cells
            # alone; what any cell through the fit loop leaves, of all
            assert ("workloads" in m) == m["name"].endswith(".device_ms"), m
            assert m["moves"] == ("setup_s" if m["name"].startswith(
                ("setup.", "compile.")) else "train_imgs_per_s")


# what ``test_bench_family_seam.py`` runs against a manifest with a further
# family's entries appended: each takes the manifest
MANIFEST_CHECKS = [new_metrics_name_source_and_layer]


def test_every_new_metric_names_its_source_and_layer():
    new_metrics_name_source_and_layer(BENCH)
    assert len(NEW) >= 18
