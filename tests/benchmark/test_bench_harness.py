"""The benchmark's own arithmetic and its files: window edges and rate,
trace reduction, operation counts, manifest <-> files, and the refusal to
run without the chip."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from bench_tiny import ROOT
from benchmark import flops, trace, window
from benchmark import run as bench_run

BENCH = bench_run.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ---- window arithmetic ---------------------------------------------------

LOGS = [(10.0, 20), (14.0, 40), (18.0, 60), (22.5, 80), (26.5, 100),
        (30.5, 120), (34.5, 140), (38.5, 160), (42.5, 180)]


def _feed(edges):
    return [edges.add(t, step) for t, step in LOGS]


def test_window_opens_after_warmup_and_closes_after_seconds():
    edges = window.Edges(warmup_steps=40, seconds=12.0, traced=False)
    assert _feed(edges) == [None, None, None, None, "close",
                            None, None, None, None]
    assert (edges.opened, edges.closed) == (1, 4)
    never = window.Edges(warmup_steps=40, seconds=100.0, traced=False)
    assert set(_feed(never)) == {None} and never.closed is None
    cold = window.Edges(warmup_steps=1000, seconds=1.0, traced=False)
    assert set(_feed(cold)) == {None} and cold.opened is None


def test_traced_run_traces_two_intervals_then_opens_its_window():
    edges = window.Edges(warmup_steps=40, seconds=7.0, traced=True)
    assert _feed(edges) == [None, "start_trace", None, "stop_trace", None,
                            None, "close", None, None]
    assert (edges.trace_from, edges.trace_to) == (1, 3)
    # the interval after the trace absorbs its write-out: the window opens
    # at that interval's end
    assert (edges.opened, edges.closed) == (4, 6)


def test_window_rate_is_all_images_over_all_time():
    edges = window.Edges(warmup_steps=40, seconds=12.0, traced=False)
    _feed(edges)
    stats = edges.stats(images_per_step=16)
    assert stats["steps"] == 60
    assert stats["seconds"] == pytest.approx(12.5)
    assert stats["imgs_per_s"] == pytest.approx(60 * 16 / 12.5)
    # the slow stretch (4.5 s for 20 steps) is in the rate and is the slowest
    assert stats["slowest_ms_per_step"] == pytest.approx(225.0)
    assert stats["mean_ms_per_step"] == pytest.approx(12.5 / 60 * 1e3)


# ---- the measurement's half of a driver -----------------------------------

def _measurement(tmp_path, losses, seconds=0.0):
    """A fit loop's log events through ``drivers/measure.py`` with no
    program behind them: a log edge every 2 steps, warm-up 4."""
    from benchmark.drivers import measure

    m = measure.Measurement(chips=1, warmup_steps=4, log_every=2,
                            seconds=seconds, trace=False, work=str(tmp_path),
                            t_start=time.perf_counter())
    m.mark("imports_s")
    for i, loss in enumerate(losses):
        m.events.event("step", nbatch=2 * i + 1)
        m.events.event("log", nbatch=2 * (i + 1), loss=loss)
    return m


def test_measurement_gives_the_result_its_fixed_keys(tmp_path):
    m = _measurement(tmp_path, [3.0, 2.5, float("nan"), 2.0, 1.5])
    # opens at the first edge at or after warm-up, closes at the next
    assert m.closed() and (m.edges.opened, m.edges.closed) == (1, 2)
    m.end(rows_per_step=16)
    m.reduce()
    r = m.result(correct=True, numbers={}, notes={}, reference_s=1.0,
                 end_to_end={"train_imgs_per_s": m.stats["imgs_per_s"]})
    assert set(r) == {
        "correct", "attempted", "failed", "end_to_end", "window", "setup_s",
        "peak_bytes", "bytes_limit", "counters", "trace", "reference_s",
        "numbers", "notes", "phases", "images_per_step", "device"}
    assert r["attempted"] == 2 and r["images_per_step"] == 16
    # the one interval of the window logged a loss that is not finite
    assert r["failed"] == 2
    assert r["end_to_end"] == {"train_imgs_per_s": r["window"]["imgs_per_s"],
                               "setup_s": r["setup_s"]}
    assert r["window"]["imgs_per_s"] == pytest.approx(
        2 * 16 / r["window"]["seconds"])
    assert 0 < r["phases"]["imports_s"] <= r["phases"]["first_log_s"] \
        <= r["setup_s"]
    assert r["trace"] is None and r["counters"] == {}
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["device"]["memory_peak_bytes"] == r["peak_bytes"]


def test_measurement_fails_the_cell_where_the_window_never_closed(tmp_path):
    from benchmark.drivers import measure, train

    m = _measurement(tmp_path, [3.0, 2.5, 2.0], seconds=3600.0)
    assert not m.closed() and m.edges.opened == 1
    with pytest.raises(measure.CellFailure, match="before the window closed"):
        m.end(rows_per_step=16)
    # one failure class for every driver kind: run.py catches the driver's
    assert train.CellFailure is measure.CellFailure


def test_no_chip_is_a_cell_failure():
    from benchmark.drivers import measure

    with pytest.raises(measure.CellFailure, match="needs 1 tpu chip"):
        measure.need_chips(1)


# ---- trace reduction -----------------------------------------------------

def test_union_and_gaps():
    spans = [(0, 10), (5, 12), (20, 30), (22, 25)]
    assert trace.union_ns(spans) == 22
    assert trace.gaps(spans) == [(12, 20)]


def _recorded():
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "small_trace.json")) as f:
        return json.load(f)


def test_reduction_of_a_recorded_trace():
    neutral = _recorded()
    dev = neutral["devices"][0]
    red = trace.Reduced(neutral, steps=neutral["steps"], chips=1)
    # the window runs from the start of one execution of the step program
    # to the start of the one ``steps`` later, the last such pair
    starts = sorted(s for name, s, _ in dev["programs"]
                    if name.startswith("jit_step"))
    assert red.steps == neutral["steps"] == len(starts) - 1
    assert red.window_s == pytest.approx((starts[-1] - starts[0]) * 1e-9)
    inside = [o for o in dev["ops"] if starts[0] <= o[2] < starts[-1]]
    busy = red.busy_s()
    # ops on one device never overlap by more than rounding: the union is
    # within a part in a thousand of the plain sum, and inside the window
    assert busy == pytest.approx(sum(o[3] for o in inside) * 1e-9, rel=1e-3)
    assert 0 < busy <= red.window_s
    assert red.scope_s("backbone") > red.scope_s("proposal") > 0
    assert red.scope_s("no_such_scope") is None
    top = red.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    idle = sum(sec for _, sec in red.idle_gaps(10))
    assert idle == pytest.approx(red.window_s - busy, rel=1e-6)


def test_window_is_cut_from_the_step_programs_last_executions():
    programs = [["jit_step(7)", 100.0 * i, 90.0] for i in range(1, 8)]
    programs += [["jit_fetch(9)", 250.0, 5.0], ["jit_fetch(9)", 650.0, 5.0]]
    assert trace.cut_window(programs, steps=3) == (400.0, 700.0, 3)
    # fewer executions than asked for: what the trace holds
    assert trace.cut_window(programs[:3], steps=20) == (100.0, 300.0, 2)
    assert trace.cut_window(programs[:1], steps=20) is None
    assert trace.cut_window([], steps=20) is None
    ops = [["op", "jit(step)/jvp(backbone)/x", 100.0 * i + 10.0, 50.0]
           for i in range(1, 8)]
    red = trace.Reduced({"devices": [{"name": "d", "ops": ops,
                                      "programs": programs}]},
                        steps=3, chips=1)
    assert (red.steps, red.window_s) == (3, pytest.approx(300e-9))
    assert red.busy_s() == pytest.approx(150e-9)
    assert red.scope_s("backbone") == pytest.approx(150e-9)


def test_named_scopes_are_read_from_the_programs_in_a_trace(tmp_path):
    """The device's op events name HLO instructions; their named scopes are
    in the compiled programs the profiler stores beside them."""
    import glob

    import jax
    import jax.numpy as jnp

    from benchmark import xplane

    @jax.jit
    def f(x):
        with jax.named_scope("backbone"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("bench_target_zz"):
            return jnp.sort(y, axis=-1)

    x = jnp.ones((64, 64))
    # compiled here and now: a CPU executable read back from the persistent
    # cache has lost the name stacks of its instructions
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        f(x).block_until_ready()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pb, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    programs = xplane.read_hlo_programs(pb)
    mine, = [p for p in programs
             if any("bench_target_zz" in v for v in p.values())]
    # instruction names repeat between programs: the one picked is the one
    # the events seen come from, however large the others are
    bigger = {f"{name}.other": "" for name in mine} | {
        name: "jit(g)/elsewhere" for name in list(mine)[:3]}
    paths = trace.pick_program(programs + [bigger], set(mine))
    assert paths is mine
    ops = [[name, path, 10.0 * i, 5.0] for i, (name, path)
           in enumerate(sorted(paths.items()))]
    programs = [["jit_f(1)", 0.0, 1.0], ["jit_f(1)", 10.0 * len(ops), 1.0]]
    red = trace.Reduced({"devices": [{"name": "d", "ops": ops,
                                      "programs": programs}]},
                        steps=1, chips=1)
    assert red.scope_s("backbone") > 0
    assert red.scope_s("bench_target_zz") > 0
    # a scope is a whole component of the name path, not a piece of one
    assert red.scope_s("bench_target") is None
    assert red.scope_s("back") is None


def test_reader_returns_nothing_without_a_trace():
    ctx = {"trace": None, "counters": {}, "peak_bytes": 0, "bytes_limit": 0,
           "window": {"slowest_ms_per_step": 200.0}, "layers": [],
           "stages": (), "chips": 1, "cell": {}}
    for m in BENCH["per_layer"]:
        value = bench_run.read_metric(m["name"], ctx)
        if m["name"] == "fit.slowest_window_ms":
            assert value == 200.0
        else:
            assert value is None, m["name"]


# ---- operation counts ----------------------------------------------------

def test_conv_and_dense_hand_counts():
    conv = {"kind": "conv", "cin": 64, "cout": 128, "k": 3, "stride": 2,
            "out_hw": [10, 12], "times": 1, "grad": "both",
            "scope": "backbone"}
    assert flops.forward_flops(conv) == 2 * 9 * 64 * 128 * 120
    dense = {"kind": "dense", "cin": 4096, "cout": 21, "times": 128,
             "grad": "both", "scope": "rcnn_losses"}
    assert flops.forward_flops(dense) == 2 * 4096 * 21
    # forward + input gradient + weight gradient; the dense layer per ROI
    assert flops.step_flops_per_image([conv, dense]) == (
        3 * 2 * 9 * 64 * 128 * 120 + 3 * 128 * 2 * 4096 * 21)
    assert flops.step_flops_per_image([conv, dense], "backbone") == (
        3 * 2 * 9 * 64 * 128 * 120)
    frozen = dict(conv, grad="none")
    assert flops.step_flops_per_image([frozen]) == flops.forward_flops(conv)


def _config(name, bdir=os.path.join(ROOT, "benchmark")):
    with open(os.path.join(bdir, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,want_tflop", [("r101-coco", 1.16),
                                             ("vgg16-voc07", 1.02)])
def test_layer_tables_match_the_compilers_count(name, want_tflop):
    """XLA's ``cost_analysis`` of the program's jitted train step, compiled
    for the chip at batch 2 and 16, counted 1.15-1.16 TFLOP an image for
    ResNet-101 and 1.01-1.03 for VGG16 (ISSUE 30's sizing table) on the
    whole 608x1024 bucket; the tables at that extent must land within 3 %
    of that."""
    config = _config(name)
    table = flops.layer_table(config, {"image_hw": config["bucket"]})
    got = flops.step_flops_per_image(table) / 1e12
    assert got == pytest.approx(want_tflop, rel=0.03)


@pytest.mark.parametrize("name,image_hw,share", [
    ("r101-coco", (600, 1000), 0.98), ("vgg16-voc07", (600, 800), 0.77)])
def test_padding_is_not_counted(name, image_hw, share):
    """The backbone's operations follow the images' own extent, not the
    bucket's; what runs per ROI does not change."""
    config = _config(name)
    bucket = flops.layer_table(config, {"image_hw": config["bucket"]})
    real = flops.layer_table(config, {"image_hw": image_hw})
    assert [r["name"] for r in real] == [r["name"] for r in bucket]
    ratio = (flops.step_flops_per_image(real, "backbone")
             / flops.step_flops_per_image(bucket, "backbone"))
    assert ratio == pytest.approx(share, abs=0.01)
    assert (flops.step_flops_per_image(real, "rcnn_losses")
            == flops.step_flops_per_image(bucket, "rcnn_losses"))


def test_counts_against_cost_analysis_of_a_small_forward():
    import jax
    import jax.numpy as jnp

    layers = [
        {"kind": "conv", "cin": 3, "cout": 16, "k": 3, "stride": 1,
         "out_hw": [32, 48], "times": 1, "grad": "none", "scope": "s"},
        {"kind": "conv", "cin": 16, "cout": 32, "k": 3, "stride": 2,
         "out_hw": [16, 24], "times": 1, "grad": "none", "scope": "s"},
        {"kind": "dense", "cin": 16 * 24 * 32, "cout": 10, "times": 1,
         "grad": "none", "scope": "s"}]

    def forward(x, k1, k2, w):
        dn = ("NHWC", "HWIO", "NHWC")
        y = jax.lax.conv_general_dilated(x, k1, (1, 1), "SAME",
                                         dimension_numbers=dn)
        y = jax.lax.conv_general_dilated(y, k2, (2, 2), "SAME",
                                         dimension_numbers=dn)
        return y.reshape(1, -1) @ w

    args = [jnp.zeros(s, jnp.float32) for s in
            ((1, 32, 48, 3), (3, 3, 3, 16), (3, 3, 16, 32),
             (16 * 24 * 32, 10))]
    cost = jax.jit(forward).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    want = flops.step_flops_per_image(layers)
    # XLA leaves out the taps that fall on SAME padding: within 10 %
    assert cost["flops"] == pytest.approx(want, rel=0.10)


def test_a_row_may_state_its_own_flops_and_bytes():
    """The door beside the kinds: a row with no ``kind`` carries the
    forward ``flops`` and ``bytes`` its family computed, and is counted
    like any other with its passes and its times a sample."""
    own = {"name": "scan", "scope": "mixer", "flops": 1.5e9, "bytes": 4e6,
           "times": 8192, "grad": "both"}
    assert flops.forward_flops(own) == 1.5e9
    assert flops.forward_bytes(own) == 4e6
    assert flops.step_flops_per_image([own]) == 1.5e9 * 3 * 8192
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    least, bound = flops.least_seconds_per_image([own], peak, "mixer")
    assert least == pytest.approx(max(1.5e9 / 1e12, 4e6 / 1e9) * 3 * 8192)
    assert bound == "memory"
    # a row's own count wins over its kind's
    conv = flops.conv("c", "mixer", 4, 4, 1, 1, (2, 2), 1, "none")
    assert flops.forward_flops(dict(conv, flops=7.0)) == 7.0
    assert flops.forward_bytes(dict(conv, bytes=9.0)) == 9.0


@pytest.mark.parametrize("row", [
    {"name": "x", "kind": "attention", "times": 1, "grad": "both"},
    {"name": "x", "times": 1, "grad": "both"}])
def test_a_row_of_no_known_kind_and_no_count_of_its_own_is_refused(row):
    with pytest.raises(ValueError, match="unknown kind"):
        flops.forward_flops(row)
    with pytest.raises(ValueError, match="unknown kind"):
        flops.forward_bytes(dict(row, flops=1.0))


def _data(name):
    with open(os.path.join(ROOT, "tests", "benchmark", "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(
    _data("parent_layer_tables.json")["configs"]))
def test_layer_table_is_the_parents_row_for_row(name):
    """The family now builds the whole table; it has to be the table
    ``flops.layer_table`` of the commit before built (recorded from it:
    ``data/parent_layer_tables.json``), row for row and key for key, and
    the sums the readers take of it the same numbers."""
    want = _data("parent_layer_tables.json")["configs"][name]
    config = _config(name)
    traffic = bench_run._json("traffic", f"{want['traffic']}.json")
    table = flops.layer_table(config, traffic)
    assert len(table) == len(want["rows"])
    for got, row in zip(table, want["rows"]):
        assert got == row, row["name"]
    assert flops.step_flops_per_image(table) == want["step_flops_per_image"]
    assert (flops.step_flops_per_image(table, "backbone")
            == want["backbone_flops_per_image"])
    least, bound = flops.least_seconds_per_image(
        table, flops.peaks("TPU v5 lite"), "backbone")
    assert least == want["backbone_least_seconds_per_image"]
    assert bound == want["backbone_bound"]


_PARENT = _data("parent_readers.json")


@pytest.fixture(scope="module")
def parent_result():
    """The driver result the parent's readers were run on: the recorded
    trace, reduced, under the recorded counters and memory."""
    neutral = _recorded()
    return dict(_PARENT["result"], trace=trace.Reduced(
        neutral, steps=neutral["steps"], chips=1))


@pytest.mark.parametrize("cell", sorted(_PARENT["values"]))
@pytest.mark.parametrize("metric", _PARENT["asked"])
def test_reader_reads_what_the_parents_read(parent_result, cell, metric):
    """Every reader the parent had, on the recorded trace with the parent's
    ``ctx``: the same number as the commit before to the last bit, and
    nothing where it gave nothing (the span readers, which need a live
    program)."""
    want = _PARENT["values"][cell]
    one = dict(BENCH, per_layer=[m for m in BENCH["per_layer"]
                                 if m["name"] == metric])
    got = bench_run.metrics_of(dict(parent_result),
                               bench_run.load_cell(cell), one, True)
    assert set(got) == {metric} & set(want)
    if metric in want:
        assert got[metric]["value"] == want[metric]


def test_unknown_device_kind_has_no_peaks():
    assert flops.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("_source")


# ---- manifest <-> files --------------------------------------------------

def names_units_and_sources(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200 and "\n" not in c["source"]
    for w in bench["workloads"]:
        assert 1 <= len(w["why"]) <= 200
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1


def test_manifest_names_units_and_sources():
    names_units_and_sources(BENCH)


DETECTOR_SCOPED = {
    "backbone.device_ms", "backbone_roofline", "proposal.device_ms",
    "nms.device_ms", "roi_align.device_ms", "roi_head.device_ms",
    "proposal_target.device_ms", "anchor_target.device_ms"}


def listed_cells_exist_and_the_detectors_come_first(bench):
    """Every listed cell exists, once; a metric of a scope only the
    detectors' step names lists their two cells first."""
    cells = {w["name"] for w in bench["workloads"]}
    listed = {m["name"]: m["workloads"] for m in
              bench["end_to_end"] + bench["per_layer"] if "workloads" in m}
    assert DETECTOR_SCOPED <= set(listed)
    for name, where in listed.items():
        assert set(where) <= cells and len(set(where)) == len(where), name
        if name in DETECTOR_SCOPED:
            assert where[:2] == ["r101-coco.train", "vgg16-voc07.train"], name


def test_metrics_of_the_detectors_scopes_list_the_detector_cells(
        parent_result):
    """A metric read from a scope only the detectors' step names is asked
    of their cells alone; what any cell through the fit loop can report
    lists none and is asked of all.  Every listed cell exists."""
    listed_cells_exist_and_the_detectors_come_first(BENCH)
    # a cell that is not listed is not asked: no reader runs for it
    other = dict(bench_run.load_cell("vgg16-voc07.train"), name="other.train")
    got = bench_run.metrics_of(dict(parent_result), other, BENCH, True)
    assert got and not set(got) & DETECTOR_SCOPED


def every_name_has_its_file_and_every_file_its_name(bench, bdir):
    """``bdir``: the ``benchmark/`` directory the manifest ``bench``
    belongs to."""
    def listed(sub, ext):
        return {f[:-len(ext)] for f in os.listdir(os.path.join(bdir, sub))
                if f.endswith(ext)}

    cells = {w["name"] for w in bench["workloads"]}
    assert cells == listed("workloads", ".json")
    configs = {c["name"] for c in bench["configs"]}
    assert configs == listed("configs", ".json")
    # a file whose name starts with ``_`` is a helper families share
    families = {f for f in listed("families", ".py") if not f.startswith("_")}
    assert families == {_config(c, bdir)["network"]["family"]
                        for c in configs}
    assert listed("metrics", ".py") == {m["name"] for m in bench["per_layer"]}
    assert listed("traffic", ".json") == {w["traffic"]
                                          for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


# what ``test_bench_family_seam.py`` runs against a manifest with a further
# family's entries appended: each takes the manifest (the third the
# directory of its files too, and is called there by name)
MANIFEST_CHECKS = [names_units_and_sources,
                   listed_cells_exist_and_the_detectors_come_first]


def test_every_name_has_its_file_and_every_file_its_name():
    every_name_has_its_file_and_every_file_its_name(
        BENCH, os.path.join(ROOT, "benchmark"))
    for c in BENCH["configs"]:
        mod = flops.family(_config(c["name"])["network"])
        assert callable(mod.layers) and mod.STAGES and all(
            isinstance(s, str) for s in mod.STAGES), c["name"]
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cell = bench_run.load_cell(w["name"])
        assert cell["name"] == w["name"] and cell["chips"] == w["chips"]
        assert cell["config"]["name"] == w["config"] in configs
        assert cell["why"] == w["why"]
        c = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        assert c["file"] == f"benchmark/configs/{w['config']}.json"
        assert c["reduced"] == cell["config"]["reduced"]
        if cell["driver"] != "train":
            continue   # another driver kind compares numbers of its own
        known = {f"{k}_s{i}" for k in ("loss", "rpn_loss")
                 for i in range(1, cell["check"]["steps"] + 1)} | {
            "grad_worst", "grad_median", "first_delta_worst",
            "first_delta_median", "delta_worst", "delta_median"}
        limits = cell["check"]["limits"]
        assert limits and set(limits) <= known
        assert all(0 < v < 1 for v in limits.values()), limits


@pytest.mark.parametrize("name", [
    c["name"] for c in BENCH["configs"]
    if _config(c["name"])["network"]["family"] in ("resnet", "vgg")])
def test_config_file_states_what_the_program_runs(name):
    """The reference reads its sizes from the configuration's file, never
    from the program: the two must say the same."""
    from mx_rcnn_tpu.config import generate_config

    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    prog = config["program"]
    cfg = generate_config(prog["network"], prog["dataset"], **prog["overrides"])
    net, tr, opt = config["network"], config["train"], config["optimizer"]
    for key, value in tr.items():
        got = getattr(cfg.train, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    assert cfg.num_classes == net["num_classes"]
    assert list(cfg.network.anchor_scales) == net["anchor_scales"]
    assert list(cfg.network.anchor_ratios) == net["anchor_ratios"]
    assert cfg.network.rpn_feat_stride == net["feat_stride"]
    assert list(cfg.network.rcnn_pooled_size) == net["pooled_size"]
    assert list(cfg.network.pixel_means) == net["pixel_means"]
    assert cfg.network.compute_dtype == net["compute_dtype"]
    assert list(cfg.network.fixed_params) == opt["fixed_params"]
    assert (cfg.default.momentum, cfg.default.wd, cfg.default.clip_gradient,
            cfg.default.momentum_dtype) == (
                opt["momentum"], opt["wd"], opt["clip_gradient"],
                opt["momentum_dtype"])
    assert list(cfg.bucket.shapes[0]) == config["bucket"]


# ---- no chip, no result --------------------------------------------------

def test_run_without_the_chip_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "chip" in p.stderr


def test_run_without_the_program_fails_and_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own directories there is no system under test."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "7", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=dict(env, JAX_PLATFORMS="cpu"),
        cwd=tmp_path, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_readings_plant_only_what_the_reference_takes():
    """``benchmark/readings.py`` re-reads the limits' upper readings: what
    it plants has to be an option of ``reference_steps`` and a policy of
    ``reference/nets.py``."""
    import inspect

    from benchmark import readings
    from benchmark.reference import nets, step

    taken = inspect.signature(step.reference_steps).parameters
    for tag, kw in readings.PLANTED.items():
        assert set(kw) <= set(taken), tag
        if "precision" in kw:
            assert len(nets.policy(kw["precision"])) == 4
