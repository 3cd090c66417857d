"""``setup_s`` laid out in the program's spans (``benchmark/setupspans.py``)
and the eight readers it serves: hand-made span buffers of a run from
``setup.entry`` to the window's opening edge, the program before these
spans, and a tiny ``train_net`` measured by the driver's own
``Measurement``."""

import math
import time

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on the path)
from benchmark import run as bench_run
from benchmark import setupspans, window

S = 1e6                    # us: a span's stamp is a float of epoch us
T0 = 1_790_000_000 * S     # setup.entry on the buffer's clock
WARMUP, EVERY = 4, 2       # log edges at 2, 4, ..: traced 4 to 8, opened at 10
PROCESS_S = 21.5           # the process's age at the entry
INTERPRETER_S = 0.3        # the process's age at run.py's first line
READERS = ("setup.before_entry_s", "setup.harness_s", "setup.fit_s",
           "setup.trace_s", "setup.lower_s", "compile.cache_misses",
           "setup.warmup_s", "setup.unattributed_s")
# the readers that need what the program of PR 43 leaves
PROGRAM_SIDE = ("setup.before_entry_s", "setup.fit_s", "setup.trace_s",
                "setup.lower_s", "compile.cache_misses",
                "setup.unattributed_s")


def _x(name, t0, t1, tid=1, **args):
    """A span as obs/trace.py records it, from seconds after the entry."""
    return {"name": name, "ph": "X", "ts": T0 + t0 * S, "dur": (t1 - t0) * S,
            "pid": 7, "tid": tid, "args": args}


def _loop(first, steps, t, step_s=0.1):
    """The fit loop's spans from step ``first`` on, ``t`` seconds after the
    entry; a log edge every ``EVERY`` steps.  Returns (spans, end)."""
    ev = []
    for step in range(first, first + steps):
        ev += [_x("train.data_wait", t, t + 0.001, step=step),
               _x("train.dispatch", t + 0.001, t + 0.002, step=step),
               _x("train.hooks", t + 0.002, t + 0.003, step=step)]
        t += 0.003
        if step % EVERY == 0:
            ev += [_x("train.sync", t, t + EVERY * step_s, step=step, n=EVERY,
                      fetch_us=100.0),
                   _x("train.log", t + EVERY * step_s,
                      t + EVERY * step_s + 0.002, step=step)]
            t += EVERY * step_s + 0.002
    return ev, t


def _events(gap_s=0.05):
    """A run: the entry, the set-up spans with the init program's compile in
    them, the prologue, ``gap_s`` under no span, then the first dispatch
    (the step's nested traces, lowering and backend compile, a miss) and the
    loop to step 12.  The window opens at step 10's log edge."""
    ev = [{"name": "setup.entry", "ph": "i", "s": "t", "ts": T0, "pid": 7,
           "tid": 1, "args": {"process_s": PROCESS_S}},
          _x("setup.loader", 0.0, 1.0),
          _x("setup.init", 1.0, 5.0),
          _x("compile.trace", 1.2, 2.0, fun="init", step=None),
          _x("compile.trace", 1.5, 1.8, fun="inner", step=None),   # nested
          _x("compile.lower", 2.0, 2.5, fun="jit(init)", step=None),
          _x("compile.backend", 2.5, 4.0, fun="jit(init)", step=None, hit=1),
          _x("setup.load", 5.0, 5.2, source="init_from"),
          _x("setup.fit", 5.2, 6.0)]
    t = 6.0 + gap_s
    first = [_x("train.data_wait", t, t + 0.01, step=1),
             _x("train.dispatch", t + 0.01, t + 12.0, step=1),
             _x("compile.trace", t + 0.02, t + 3.0, fun="step", step=1),
             _x("compile.trace", t + 0.5, t + 1.0, fun="inner", step=1),
             _x("compile.trace", t + 2.5, t + 3.5, fun="later", step=1),
             _x("compile.lower", t + 3.5, t + 5.0, fun="jit(step)", step=1),
             _x("compile.backend", t + 5.0, t + 11.9, fun="jit(step)",
                step=1, hit=0),
             _x("train.hooks", t + 12.0, t + 12.001, step=1)]
    loop, end = _loop(2, 11, t + 12.001)
    # a compile after the edge is no set-up's
    late = [_x("compile.backend", end - 0.05, end - 0.04, fun="jit(f)",
               step=12, hit=0),
            # the stager's thread, under no span of the caller's
            _x("stage.place", 6.0, 9.0, tid=2, seq=1)]
    return ev + first + loop + late


def _edge_s(events):
    """Seconds from the entry to the end of step 10's log span."""
    (log,) = [e for e in events if e["name"] == "train.log"
              and e["args"]["step"] == 10]
    return (log["ts"] + log["dur"] - T0) / S


def _ctx(events, **extra):
    ctx = {"trace": object(), "cell": {"traffic": {"warmup_steps": WARMUP}},
           "hostspans.events": events,
           "phases": {"imports_s": 4.25, "data_s": 6.5, "weights_s": 12.75}}
    ctx.update(extra)
    return ctx


def _read(ctx, names=READERS):
    return {n: bench_run.read_metric(n, ctx) for n in names}


def test_the_readers_on_a_run_from_entry_to_the_opening_edge():
    events = _events()
    edge = _edge_s(events)
    got = _read(_ctx(events))
    t = 6.05
    # first dispatch ends at t + 12.0; the edge after the traced intervals
    assert got["setup.warmup_s"] == pytest.approx(edge - (t + 12.0))
    assert got == pytest.approx({
        "setup.before_entry_s": PROCESS_S,
        "setup.harness_s": 8.5,
        "setup.fit_s": 0.8,
        # nested traces once: the init's 0.8, the step's 0.02..3.5 whole
        "setup.trace_s": 0.8 + 3.48,
        "setup.lower_s": 0.5 + 1.5,
        # the step's compile; the init's was a hit, the late one is after
        "compile.cache_misses": 1,
        "setup.warmup_s": got["setup.warmup_s"],
        # the planted gap before the first wait; the stager's span and the
        # compile spans cover nothing of it
        "setup.unattributed_s": 0.05}, abs=1e-6)


@pytest.mark.parametrize("gap_s", [0.0, 0.3])
def test_unattributed_is_what_no_span_of_the_callers_thread_covers(gap_s):
    got = setupspans.unattributed_s(_ctx(_events(gap_s)))
    assert got == pytest.approx(gap_s, abs=1e-6)
    # a compile span of the caller's thread alone covers its time too
    events = [e for e in _events(gap_s) if e["name"] != "setup.init"]
    assert setupspans.unattributed_s(_ctx(events)) == pytest.approx(
        gap_s + 4.0 - (0.8 + 0.5 + 1.5), abs=1e-6)


def test_the_setup_rows_and_the_process_age_add_up_to_setup_s():
    """``setup_s`` counts from ``run.py``'s first line, ``process_s`` from
    the process's start: entry age plus entry-to-edge less ``setup_s`` is
    the interpreter's start, inside [0, 1) s."""
    events = _events()
    setup_s = PROCESS_S + _edge_s(events) - INTERPRETER_S
    ctx = _ctx(events, setup_s=setup_s)
    run, entry, edge = setupspans.run_of(events, WARMUP)
    left = (setupspans.before_entry_s(ctx) + (edge - entry["ts"]) / S
            - ctx["setup_s"])
    assert left == pytest.approx(INTERPRETER_S)
    assert 0.0 <= left < 1.0


@pytest.mark.parametrize("warmup,every,last", [(20, 20, 200), (4, 2, 30),
                                               (50, 20, 300), (7, 5, 60)])
def test_the_opening_edge_is_where_window_edges_opens(warmup, every, last):
    """The log span whose step the driver's ``window.Edges`` opened the
    traced run's window at: after the two traced intervals and the one
    that absorbs the profiler's write-out, not at warm-up's end."""
    logs = [(10.0 + 0.7 * k, every * k) for k in range(1, last // every + 1)]
    edges = window.Edges(warmup, math.inf, traced=True)
    for t, step in logs:
        edges.add(t, step)
    spans = [{"name": "train.log", "ph": "X", "ts": t * S, "dur": 900.0,
              "pid": 1, "tid": 1, "args": {"step": step}}
             for t, step in reversed(logs)]
    got = setupspans.opening_log(spans, warmup)
    assert got["args"]["step"] == logs[edges.opened][1]
    first_log = -(-warmup // every) * every
    assert got["args"]["step"] == (
        first_log + (window.TRACED_INTERVALS + 1) * every)
    # a run cut before the window opened has no edge
    assert setupspans.opening_log(spans[-edges.opened:], warmup) is None


def test_the_last_entry_starts_the_run():
    """A process that called ``train_net`` before keeps that run's spans:
    the readers take the events from the last ``setup.entry`` on."""
    old = [dict(e, ts=e["ts"] - 1000 * S) for e in _events(gap_s=0.7)]
    got = _read(_ctx(old + _events()))
    assert got == pytest.approx(_read(_ctx(_events())), abs=1e-6)


def test_a_parent_style_buffer_gives_none_where_it_has_no_spans():
    """The program before PR 43 leaves the fit loop's spans and ``setup.init``
    / ``setup.load`` but no entry, prologue or compile phases: the six
    program-side readers give nothing (not 0), the harness's phases and the
    warm-up still read."""
    events = [e for e in _events() if e["name"] not in (
        "setup.entry", "setup.fit", "compile.trace", "compile.lower",
        "compile.backend")]
    got = _read(_ctx(events))
    assert {n: got[n] for n in PROGRAM_SIDE} == dict.fromkeys(PROGRAM_SIDE)
    assert got["setup.harness_s"] == pytest.approx(8.5)
    assert got["setup.warmup_s"] == pytest.approx(
        _edge_s(events) - (6.05 + 12.0))
    # without a trace, spans or phases nothing reads
    bare = {"trace": None, "cell": {}, "counters": {}}
    assert _read(bare) == dict.fromkeys(READERS)
    assert _read(_ctx(None)) == dict(dict.fromkeys(READERS),
                                     **{"setup.harness_s": 8.5})


def test_a_tiny_train_net_measured_by_the_driver(tmp_path):
    """A tiny ``train_net`` run as a traced benchmark run drives it (the
    driver's ``Measurement``, its clock started at the process's start as
    ``run.py``'s is near it): every reader speaks, the caller's thread is
    tiled, and the identity holds to the process clock's tick."""
    from benchmark.drivers import measure
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net
    from tests.conftest import shrink_tiny_cfg

    cfg = shrink_tiny_cfg(generate_config(
        "tiny", "synthetic", dataset__root_path=str(tmp_path),
        dataset__dataset_path=str(tmp_path / "synthetic"),
        dataset__num_classes=4, train__batch_images=2, obs__enabled=True,
        default__frequent=EVERY))
    m = measure.Measurement(chips=1, warmup_steps=WARMUP, log_every=EVERY,
                            seconds=0.0, trace=True, work=str(tmp_path),
                            t_start=time.perf_counter()
                            - obs_trace.process_age_s())
    m.mark("imports_s")
    m.mark("weights_s")
    obs_trace.disable()
    obs_trace.reset()
    try:
        train_net(cfg, prefix=None, end_epoch=1, lr=1e-3, seed=0,
                  dataset_kw=dict(num_images=32, image_size=(128, 160),
                                  max_objects=3),
                  run_record=m.events, stop_flag=m.closed)
        m.end(2)
        events = obs_trace.events()
    finally:
        obs_trace.reset()
    ctx = _ctx(events, setup_s=m.setup_s, phases=m.phases)
    got = _read(ctx)
    assert all(v is not None for v in got.values()), got
    assert got["setup.before_entry_s"] > 0 and got["setup.fit_s"] > 0
    assert got["setup.trace_s"] > 0 and got["setup.lower_s"] > 0
    assert got["compile.cache_misses"] >= 0
    assert 0 < got["setup.warmup_s"] < m.setup_s
    assert got["setup.unattributed_s"] < 0.5
    run, entry, edge = setupspans.run_of(events, WARMUP)
    # the window's edge as the driver stamped it, by the spans
    opened = m.edges.logs[m.edges.opened][1]
    assert setupspans.opening_log(events, WARMUP)["args"]["step"] == opened
    left = got["setup.before_entry_s"] + (edge - entry["ts"]) / S - m.setup_s
    # what lies between: the clock tick of /proc (10 ms) and the log span's
    # last microseconds after the driver's stamp
    assert abs(left) < 0.05, left


def eight_setup_readers_fit_every_cell(bench):
    """The eight readers of PR 43 are there, asked of every cell (no
    ``workloads`` list), in the fit loop's layer, moving ``setup_s``."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert "workloads" not in m, name
        assert m["layer"] == "fit loop and input plane"
        assert m["moves"] == "setup_s" and m["better"] == "lower"
    assert by_name["setup.harness_s"]["source"] == "host_clock"
    assert by_name["compile.cache_misses"]["source"] == "program_counter"


# what ``test_bench_family_seam.py`` runs against a manifest with a further
# family's entries appended: each takes the manifest
MANIFEST_CHECKS = [eight_setup_readers_fit_every_cell]


def test_the_eight_readers_are_in_the_manifest():
    eight_setup_readers_fit_every_cell(bench_run.manifest())
