"""What a traced train run is read by: the named scopes of the compiled
step (device side) and the spans ``train_net`` leaves under
``obs.enabled`` alone (host side, two threads tied by step number)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.train import RCNNBatch
from mx_rcnn_tpu.obs import trace as obs_trace
from mx_rcnn_tpu.parallel.dp import device_mesh, make_dp_train_step
from tests.conftest import shrink_tiny_cfg
from tests.test_train_step import KEY, make_batch, tiny_setup

# how each scope shows in the lowered text's name paths: the outer stages
# wrapped by the transformation (``jvp(rcnn_losses)``), the inner ones as a
# bare component right under their stage — which a jitted function of the
# same name (``jit(roi_align)``) is not; the sweep lowers inside the jitted
# ``nms_batch`` and the update inside the ``shard_map`` body, whose paths
# start anew
PATTERN = {
    "backbone": r"\(backbone\)+/", "rpn_head": r"\(rpn_head\)+/",
    "rpn_losses": r"\(rpn_losses\)+/", "proposal": r"\(proposal\)+/",
    "rcnn_losses": r"\(rcnn_losses\)+/",
    "anchor_target": r"\(rpn_losses\)+/anchor_target/",
    "proposal_target": r"\(rcnn_losses\)+/proposal_target/",
    "roi_align": r"\(rcnn_losses\)+/roi_align/",
    "roi_head": r"\(rcnn_losses\)+/roi_head/",
    "nms_sweep": r"\"nms_sweep/", "optimizer": r"[/\"]optimizer/",
    "grad_sync": r"[/\"]grad_sync/",
}
# the scopes each objective's step carries; grad_sync because the step is
# lowered for a two-device mesh
SCOPES = {
    "e2e": set(PATTERN),
    "rpn": {"backbone", "rpn_head", "rpn_losses", "anchor_target",
            "optimizer", "grad_sync"},
    "rcnn": {"backbone", "rcnn_losses", "proposal_target", "roi_align",
             "roi_head", "optimizer", "grad_sync"},
}


@pytest.mark.parametrize("mode", sorted(SCOPES))
def test_named_scopes_are_in_the_lowered_step(mode):
    cfg, model, tx, state = tiny_setup(batch_images=1)
    batch = make_batch(n=2)
    if mode == "rcnn":
        rois = jnp.tile(jnp.array([[10.0, 10.0, 60.0, 70.0]]), (2, 16, 1))
        batch = RCNNBatch(*batch, rois=rois,
                          rois_valid=jnp.ones((2, 16), bool))
    step = make_dp_train_step(model, cfg, tx, device_mesh(2), mode=mode)
    text = step.lower(state, batch, KEY).as_text(debug_info=True)
    found = {s for s, pat in PATTERN.items() if re.search(pat, text)}
    assert found == SCOPES[mode]


def _by_name(events, prefix):
    out = {}
    for e in events:
        if e["name"].startswith(prefix):
            out.setdefault(e["name"], []).append(e)
    return out


def test_train_net_leaves_spans_under_obs_enabled_alone(tmp_path):
    from mx_rcnn_tpu.obs.metrics import registry
    from mx_rcnn_tpu.tools.train import train_net

    cfg = shrink_tiny_cfg(generate_config(
        "tiny", "synthetic", dataset__root_path=str(tmp_path),
        dataset__dataset_path=str(tmp_path / "synthetic"),
        dataset__num_classes=4, train__batch_images=2, obs__enabled=True))
    assert not cfg.obs.trace
    obs_trace.disable()
    obs_trace.reset()
    steps = 4
    train_net(cfg, prefix=None, end_epoch=1, lr=1e-3, frequent=2, seed=0,
              dataset_kw=dict(num_images=2 * steps, image_size=(128, 160),
                              max_objects=3))
    # collection is left as it was found; the spans stay
    assert not obs_trace.enabled()
    events = obs_trace.events()
    obs_trace.reset()

    train = _by_name(events, "train.")
    fit_tid = {e["tid"] for e in train["train.dispatch"]}
    assert len(fit_tid) == 1
    for name in ("train.data_wait", "train.dispatch", "train.hooks"):
        got = [e["args"]["step"] for e in train[name]]
        # data_wait runs once more: the pull that finds the epoch drained
        assert got[:steps] == list(range(1, steps + 1)), (name, got)
    for name in ("train.sync", "train.log"):
        assert [e["args"]["step"] for e in train[name]] == [2, 4], name
    for e in train["train.sync"]:
        assert e["args"]["n"] == 2
        assert 0 <= e["args"]["fetch_us"] <= e["dur"]
    assert all("backend_compile_s" in e["args"] for e in train["train.log"])
    # the loop body is covered: within a step the spans follow one another
    # and none starts before the one before it ended
    body = sorted((e for es in train.values() for e in es),
                  key=lambda e: e["ts"])
    for a, b in zip(body, body[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 1.0, (a["name"], b["name"])

    stage = _by_name(events, "stage.")
    stage_tid = {e["tid"] for es in stage.values() for e in es}
    assert len(stage_tid) == 1 and stage_tid != fit_tid
    for name in ("stage.place", "stage.put_wait"):
        assert [e["args"]["seq"] for e in stage[name]] == list(
            range(1, steps + 1)), name
    assert [e["args"]["seq"] for e in stage["stage.assemble"]][:steps] == \
        list(range(1, steps + 1))
    # batch k is placed before step k is dispatched
    placed = {e["args"]["seq"]: e["ts"] + e["dur"] for e in stage["stage.place"]}
    for e in train["train.dispatch"]:
        assert placed[e["args"]["step"]] <= e["ts"] + 1.0

    setup = _by_name(events, "setup.")
    assert set(setup) == {"setup.entry", "setup.loader", "setup.model",
                          "setup.init", "setup.fit"}
    d1 = train["train.dispatch"][0]
    order = [setup[name][0]["ts"] for name in (
        "setup.loader", "setup.model", "setup.init", "setup.fit")]
    order.append(d1["ts"])
    assert order == sorted(order)
    # the entry comes first, with the process's age; the prologue ends
    # before the loop's first wait for a batch
    entry = setup["setup.entry"][0]
    assert min(events, key=lambda e: e["ts"]) is entry
    assert entry["ph"] == "i" and entry["tid"] in fit_tid
    assert entry["args"]["process_s"] > 0
    (prologue,) = setup["setup.fit"]
    assert prologue["tid"] in fit_tid
    assert (prologue["ts"] + prologue["dur"]
            <= train["train.data_wait"][0]["ts"] + 1.0)

    # the step's trace, lowering and compile or cache read lie inside the
    # first dispatch, each phase with the step and the function's name
    phases = {name: [e for e in events if e["name"] == name
                     and e["tid"] in fit_tid and e["args"]["step"] == 1]
              for name in ("compile.trace", "compile.lower",
                           "compile.backend")}
    for name, spans in phases.items():
        assert spans, name
        for e in spans:
            assert d1["ts"] - 1.0 <= e["ts"], (name, e)
            assert e["ts"] + e["dur"] <= d1["ts"] + d1["dur"] + 1.0, (name, e)
    assert {e["args"]["fun"] for e in phases["compile.backend"]} == {
        "jit(step)"}
    assert all(e["args"]["hit"] in (0, 1) for e in phases["compile.backend"])
    assert "step" in {e["args"]["fun"] for e in phases["compile.trace"]}

    # the caller's thread is tiled from the entry to the second log edge:
    # no stretch over 5 ms lies under no setup.*, train.* or compile.* span
    edge = train["train.log"][1]
    mine = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                  if e["ph"] == "X" and e["tid"] in fit_tid
                  and e["name"].startswith(("setup.", "train.", "compile."))
                  and e["ts"] < edge["ts"] + edge["dur"])
    covered, holes = entry["ts"], []
    for s, t in mine:
        if s > covered:
            holes.append((s - covered, s))
        covered = max(covered, t)
    assert covered >= edge["ts"] + edge["dur"] - 1.0
    assert max(holes, default=(0.0,))[0] < 5e3, sorted(holes)[-3:]

    # the step program lowers inside the first dispatch and says so
    low = [e for e in events if e["name"] == "compile.lowering"]
    assert low and all(e["ph"] == "i" for e in low)
    in_first = [e for e in low if d1["ts"] <= e["ts"] <= d1["ts"] + d1["dur"]]
    assert in_first and all(e["args"]["step"] == 1 for e in in_first)
    assert not [e for e in low if (e["args"]["step"] or 0) > 1]
    assert all(e["args"]["lower_s"] > 0 for e in low)
    assert registry().counter("compile.backend_s") >= train["train.log"][0][
        "args"]["backend_compile_s"] > 0


def test_a_second_start_reads_the_step_from_the_persistent_cache(tmp_path):
    """Two ``train_net`` starts in one process against an empty cache: the
    first compiles the step (``hit`` 0), the second reads it (``hit`` 1),
    and the registry counts each backend compile as a miss or a hit."""
    from jax.experimental.compilation_cache import compilation_cache

    from mx_rcnn_tpu.obs.metrics import registry
    from mx_rcnn_tpu.tools.train import train_net

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    cfg = shrink_tiny_cfg(generate_config(
        "tiny", "synthetic", dataset__root_path=str(tmp_path),
        dataset__dataset_path=str(tmp_path / "synthetic"),
        dataset__num_classes=4, train__batch_images=2, obs__enabled=True))
    reg = registry()
    hits, counts = [], []
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        for _ in range(2):
            obs_trace.reset()
            before = (reg.counter("compile.cache_hits"),
                      reg.counter("compile.cache_misses"))
            train_net(cfg, prefix=None, end_epoch=1, lr=1e-3, frequent=2,
                      seed=0, dataset_kw=dict(num_images=4,
                                              image_size=(128, 160),
                                              max_objects=3))
            hits.append([e["args"]["hit"] for e in obs_trace.events()
                         if e["name"] == "compile.backend"
                         and e["args"]["fun"] == "jit(step)"])
            counts.append((reg.counter("compile.cache_hits") - before[0],
                           reg.counter("compile.cache_misses") - before[1]))
    finally:
        obs_trace.reset()
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert hits == [[0], [1]]
    (_, misses_1), (hits_2, misses_2) = counts
    # what the first start compiled, the second read
    assert misses_1 >= 1 and hits_2 >= 1 and misses_2 == 0


def test_the_listener_places_compile_phases_on_the_buffers_clock():
    """JAX's phase events, fired by hand: each becomes a span that ends
    when its callback runs and lasts what JAX measured, on JAX's wall
    clock or not; ``hit`` is 1 only for a cache hit inside the span."""
    import time

    from mx_rcnn_tpu.obs.metrics import LoweringCounter, registry

    LoweringCounter._ensure_listener()
    backend = "/jax/core/compile/backend_compile_duration"
    reg = registry()
    before = (reg.counter("compile.cache_hits"),
              reg.counter("compile.cache_misses"))
    obs_trace.enable()
    obs_trace.reset()
    try:
        LoweringCounter.mark_step(7)
        # a wall clock stepped a day back: the span still lies at now
        t = time.time() - 86400.0
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/jaxpr_trace_duration", t, t + 0.25,
            fun_name="f")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        now = time.time()
        jax.monitoring.record_event_time_span(backend, now - 0.5, now + 0.1,
                                              fun_name="jit(f)")
        # the hit lies before this span: a compile
        jax.monitoring.record_event_time_span(backend, now + 0.2, now + 0.3,
                                              fun_name="jit(g)")
        t_read = obs_trace._now_us()
        events = obs_trace.events()
    finally:
        LoweringCounter.mark_step(None)
        obs_trace.disable()
        obs_trace.reset()
    trace, hit, miss = events
    assert trace["name"] == "compile.trace"
    assert trace["args"] == {"fun": "f", "step": 7}
    assert trace["dur"] == pytest.approx(0.25e6)
    assert 0 <= t_read - (trace["ts"] + trace["dur"]) < 1e6
    assert (hit["name"], hit["args"]) == (
        "compile.backend", {"fun": "jit(f)", "step": 7, "hit": 1})
    assert miss["args"]["hit"] == 0
    assert (reg.counter("compile.cache_hits") - before[0],
            reg.counter("compile.cache_misses") - before[1]) == (1, 1)


def test_stager_spans_count_from_the_first_batch_it_is_given():
    from mx_rcnn_tpu.data.staging import DeviceStager

    obs_trace.enable()
    obs_trace.reset()
    try:
        stager = DeviceStager(iter([np.zeros(2)] * 3), lambda b: b,
                              first_seq=5)
        assert len(list(stager)) == 3
        stager.close()
        seqs = [e["args"]["seq"] for e in obs_trace.events()
                if e["name"] == "stage.place"]
    finally:
        obs_trace.disable()
        obs_trace.reset()
    assert seqs == [5, 6, 7]


def test_device_lines_are_shifted_onto_the_hosts_clock(tmp_path):
    """An .xplane.pb counts its lines from the profiler session's start and
    states that start; ``device_trace_events`` lays them on the unix clock
    the spans use."""
    import time

    from mx_rcnn_tpu.obs.profiler import newest_xplane
    from mx_rcnn_tpu.utils.xplane import parse_xspace

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    t0 = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.2)
        t_run = time.time_ns()
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t1 = time.time_ns()
    planes = parse_xspace(newest_xplane(str(tmp_path)))
    start = obs_trace.session_start_ns(planes)
    assert t0 <= start <= t_run
    raw = [line.get("timestamp_ns", 0) + ev.get("offset_ps", 0) / 1e3
           for p in planes for line in p["lines"] for ev in line["events"]]
    # in the file: nanoseconds since the session began, not since 1970
    assert raw and 0 <= min(raw) and max(raw) <= t1 - start
    events = obs_trace.device_trace_events(planes)
    assert events
    assert all(t0 / 1e3 <= e["ts"] <= t1 / 1e3 for e in events)
    # the run itself began 0.2 s in: its events say so on the host's clock
    assert any(e["ts"] >= t_run / 1e3 - 1e3 for e in events)
    # a file that states no start leaves the lines as they are
    bare = [p for p in planes if p.get("name") != "Task Environment"]
    assert obs_trace.session_start_ns(bare) is None
    assert min(e["ts"] for e in obs_trace.device_trace_events(bare)) < 60e6
