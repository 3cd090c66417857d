"""A configuration of a family the benchmark has never seen is new files.

The test copies ``benchmark/`` and ``BENCHMARK.json`` into a temporary tree,
adds a family that is no detector (two matrix products a token with stated
``flops`` and ``bytes``, ``times`` the tokens of a sequence, scopes of its
own; no image size, no ROIs, no anchors), its configuration, traffic and
workload files, a driver kind that returns a canned result over the recorded
chip trace, three readers, and entries appended to the manifest; then runs
the copy's own ``benchmark/run.py``.  Every file it writes has to be new and
every file it copied has to be as the repo has it: an edit the stub would
need to ``run.py``, ``flops.py``, ``hostspans.py`` or a reader fails here.

The accepted cells' tests have to take it too.  Each ``test_bench_*.py``
beside this file keeps what it asserts of the manifest in functions that
take the manifest and lists them as ``MANIFEST_CHECKS``; the last test here
runs them all against the stub's manifest.  So a cell's test may assert that
the accepted entries come first, in order, and never how many follow: one
that pins a total or a tail fails here, in the PR that writes it.
"""

import glob
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT
from benchmark import flops, trace
from benchmark import run as bench_run

BENCH = bench_run.manifest()
RECORDED = os.path.join(ROOT, "tests", "benchmark", "data", "small_trace.json")
CELL = "stub-lm.train"
HIDDEN, WIDE, TOKENS, BATCH = 64, 256, 128, 4
# the recorded trace is a detector's: the canned driver gives two of its
# scopes the stub's names, so the stub's scopes have something under them
RENAME = {"backbone": "mixer", "rcnn_losses": "mlp"}

FAMILY = '''"""A family that is no detector: two matrix products a token."""

STAGES = ("mixer", "mlp")


def _matmul(name, scope, d_in, d_out, tokens):
    return {"name": name, "scope": scope, "flops": 2.0 * d_in * d_out,
            "bytes": 2.0 * (d_in + d_out + d_in * d_out), "times": tokens,
            "grad": "both"}


def layers(config, traffic):
    d, wide = config["hidden_size"], config["intermediate_size"]
    tokens = traffic["sequence_length"]
    return [_matmul("up", "mixer", d, wide, tokens),
            _matmul("down", "mlp", wide, d, tokens)]
'''

DRIVER = '''"""A driver kind that measures nothing: the workload file's canned result
over the recorded trace, two of its scopes renamed to the family's."""

import json

from benchmark import trace as trace_mod


class CellFailure(RuntimeError):
    pass


def run(cell, *, seed, seconds, trace, t_start):
    canned = cell["canned"]
    reduced = None
    if trace:
        with open(canned["trace"]) as f:
            neutral = json.load(f)
        for dev in neutral["devices"]:
            for op in dev["ops"]:
                for old, new in canned["rename"].items():
                    op[1] = op[1].replace(old, new)
        reduced = trace_mod.Reduced(neutral, steps=neutral["steps"], chips=1)
    return dict(canned["result"], trace=reduced)
'''

READER = '''"""Device milliseconds per step under the stub's ``%s`` scope."""


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("%s") if t else None
    return None if not sec else 1e3 * sec / t.steps
'''

COUNTER = '''"""Sequences an optimizer step, as the driver's result states them."""


def read(ctx):
    return ctx.get("images_per_step")
'''

# the stub's three per_layer entries, as a further family would append them
ENTRIES = [
    {"name": "mixer.device_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "models",
     "moves": "train_imgs_per_s", "workloads": [CELL]},
    {"name": "mlp.device_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "models",
     "moves": "train_imgs_per_s", "workloads": [CELL]},
    {"name": "stub.rows_per_step", "unit": "rows", "better": "higher",
     "source": "program_counter", "layer": "fit loop and input plane",
     "moves": "train_imgs_per_s", "workloads": [CELL]}]

RESULT = {
    "correct": True, "attempted": 80, "failed": 0,
    "end_to_end": {"train_imgs_per_s": 80 * BATCH / 11.8125, "setup_s": 41.5},
    "window": {"steps": 80, "seconds": 11.8125,
               "imgs_per_s": 80 * BATCH / 11.8125,
               "slowest_ms_per_step": 148.25, "mean_ms_per_step": 147.65625},
    "setup_s": 41.5, "peak_bytes": 6170345472, "bytes_limit": 16909336576,
    "counters": {"train.data_wait_ms": 3.25, "train.step_ms": 150.5},
    "reference_s": 0.0, "numbers": {"loss_s1": {"value": 0.001,
                                                "limit": 0.01}},
    "notes": {}, "phases": {}, "images_per_step": BATCH,
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 6170345472}}


def _files(top):
    out = {}
    for base, dirs, names in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for n in names:
            path = os.path.join(base, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = f.read()
    return out


def _write_new(path, text):
    assert not os.path.exists(path), f"{path}: the stub may only add files"
    with open(path, "w") as f:
        f.write(text)


@pytest.fixture(scope="module")
def stub_tree(tmp_path_factory):
    """The temporary tree, and the files copied into it as they were."""
    top = str(tmp_path_factory.mktemp("seam"))
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    copied = _files(bdir)
    _write_new(os.path.join(bdir, "families", "stub.py"), FAMILY)
    _write_new(os.path.join(bdir, "drivers", "canned.py"), DRIVER)
    for scope in ("mixer", "mlp"):
        _write_new(os.path.join(bdir, "metrics", f"{scope}.device_ms.py"),
                   READER % (scope, scope))
    _write_new(os.path.join(bdir, "metrics", "stub.rows_per_step.py"), COUNTER)
    _write_new(os.path.join(bdir, "configs", "stub-lm.json"), json.dumps({
        "name": "stub-lm", "reduced": [], "network": {"family": "stub"},
        "hidden_size": HIDDEN, "intermediate_size": WIDE}))
    _write_new(os.path.join(bdir, "traffic", "stub-tokens.json"), json.dumps({
        "kind": "tokens", "sequence_length": TOKENS, "per_chip_batch": BATCH,
        "warmup_steps": 4}))
    why = "4 sequences of 128 tokens a step: the seam, not a model"
    _write_new(os.path.join(bdir, "workloads", f"{CELL}.json"), json.dumps({
        "name": CELL, "config": "stub-lm", "traffic": "stub-tokens",
        "chips": 1, "driver": "canned", "why": why,
        "canned": {"trace": RECORDED, "rename": RENAME, "result": RESULT}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "stub-lm", "source": "tests/benchmark", "reduced": [],
        "file": "benchmark/configs/stub-lm.json", "why": "the seam"})
    bench["workloads"].append({"name": CELL, "config": "stub-lm",
                               "traffic": "stub-tokens", "chips": 1,
                               "why": why})
    bench["per_layer"] += ENTRIES
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top, copied, bench


def _run(top, traced):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", str(int(traced))],
        capture_output=True, text=True, env=env, cwd=top, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def _renamed():
    with open(RECORDED) as f:
        neutral = json.load(f)
    for dev in neutral["devices"]:
        for op in dev["ops"]:
            for old, new in RENAME.items():
                op[1] = op[1].replace(old, new)
    return trace.Reduced(neutral, steps=neutral["steps"], chips=1)


def test_a_new_family_is_new_files(stub_tree):
    top, copied, bench = stub_tree
    line, err = _run(top, traced=True)
    assert line["correct"] is True and line["attempted"] == 80
    assert "compared loss_s1 value 0.001 limit 0.01" in err
    got = {k: v["value"] for k, v in line["metrics"].items()}
    red = _renamed()
    peak = flops.peaks("TPU v5 lite")["flops_per_s"]
    # two matrix products a token, forward and both gradients, 128 tokens a
    # sequence, 4 sequences a step, over the traced window on one chip
    per_sequence = 3 * TOKENS * (2 * HIDDEN * WIDE + 2 * WIDE * HIDDEN)
    assert got["step_mfu"] == pytest.approx(
        100.0 * per_sequence * red.steps * BATCH / (red.window_s * peak),
        rel=1e-12)
    # under neither of the stub's scopes, by hand from the ops
    inside = re.compile(r"(^|[/(])(mixer|mlp)([/)]|$)")
    rest = trace.union_ns([(s, s + d) for _, path, s, d
                           in red.devices[0]["ops"]
                           if not inside.search(path)])
    assert got["step.unscoped_ms"] == pytest.approx(
        rest * 1e-6 / red.steps, rel=1e-12)
    both = sum(red.scope_s(s) for s in ("mixer", "mlp"))
    assert got["step.unscoped_ms"] + 1e3 * both / red.steps == pytest.approx(
        got["step.device_ms"], rel=1e-3)
    # far more than the detector's stages leave unscoped on the same trace
    with open(os.path.join(os.path.dirname(RECORDED),
                           "parent_readers.json")) as f:
        detectors = json.load(f)["values"]["vgg16-voc07.train"]
    assert got["step.unscoped_ms"] > 5 * detectors["step.unscoped_ms"]
    for scope in ("mixer", "mlp"):
        assert got[f"{scope}.device_ms"] == pytest.approx(
            1e3 * red.scope_s(scope) / red.steps, rel=1e-12)
    assert got["stub.rows_per_step"] == BATCH
    # the device's readers and the driver's counters speak for any family
    assert got["device.idle_pct"] == pytest.approx(
        100.0 * (1 - red.busy_s() / red.window_s), rel=1e-9)
    assert got["device.peak_hbm_pct"] == pytest.approx(
        100.0 * RESULT["peak_bytes"] / RESULT["bytes_limit"])
    assert got["fit.slowest_window_ms"] == 148.25
    assert got["fit.data_wait_pct"] == pytest.approx(100 * 3.25 / 150.5)
    # the metrics that list the detectors' cells are not asked of this one,
    # and the span readers find no live program
    asked = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    listed_elsewhere = {m["name"] for m in bench["per_layer"]} - asked
    assert len(listed_elsewhere) >= 8 and not set(got) & listed_elsewhere
    assert {"backbone.device_ms", "backbone_roofline", "nms.device_ms",
            "roi_head.device_ms"} <= listed_elsewhere
    assert set(got) >= {
        "step_mfu", "step.unscoped_ms", "step.device_ms", "mixer.device_ms",
        "device.idle_pct", "device.peak_hbm_pct", "fit.slowest_window_ms",
        "fit.data_wait_pct"}
    assert not {m for m in got if m.startswith(("idle.", "stage.", "host.",
                                                "setup.", "compile."))}
    assert line["device"]["busy_s"] == pytest.approx(red.busy_s())
    assert line["device"]["window_s"] == pytest.approx(red.window_s)
    assert len(line["breakdown"]["device_ops"]) == 10


def test_a_new_familys_cell_reports_the_end_to_end_metrics(stub_tree):
    top, _, bench = stub_tree
    line, _ = _run(top, traced=False)
    assert line["metrics"] == {
        "train_imgs_per_s": {"value": RESULT["end_to_end"]["train_imgs_per_s"],
                             "unit": "imgs/s"},
        "setup_s": {"value": 41.5, "unit": "s"}}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_the_stub_changed_no_file_the_benchmark_has(stub_tree):
    """After both runs every copied file is still
    what the repo holds, the stub's eight files are all that is new, and the
    manifest's own entries stand as they were, the stub's appended."""
    top, copied, bench = stub_tree
    now = _files(os.path.join(top, "benchmark"))
    repo = _files(os.path.join(ROOT, "benchmark"))
    assert {k: now[k] for k in copied} == copied == repo
    assert sorted(set(now) - set(copied)) == [
        "configs/stub-lm.json", "drivers/canned.py", "families/stub.py",
        "metrics/mixer.device_ms.py", "metrics/mlp.device_ms.py",
        "metrics/stub.rows_per_step.py", "traffic/stub-tokens.json",
        f"workloads/{CELL}.json"]
    for group in ("configs", "workloads", "per_layer", "end_to_end"):
        assert bench[group][:len(BENCH[group])] == BENCH[group]
    for key in ("command", "paths", "run_seconds"):
        assert bench[key] == BENCH[key]


def _manifest_checks():
    """(module, function) of every ``MANIFEST_CHECKS`` entry of the test
    files beside this one.  A file that reads the manifest has to have the
    list: what it asserts of the manifest is then run against the stub's."""
    here = os.path.dirname(os.path.abspath(__file__))
    out = []
    for path in sorted(glob.glob(os.path.join(here, "test_bench_*.py"))):
        name = os.path.basename(path)[:-3]
        with open(path) as f:
            source = f.read()
        if path == os.path.abspath(__file__) or not (
                "manifest()" in source or "BENCHMARK.json" in source):
            continue
        mod = importlib.import_module(name)
        assert hasattr(mod, "MANIFEST_CHECKS"), (
            f"{name}.py reads the manifest: keep what it asserts of it in "
            "functions that take the manifest, listed as MANIFEST_CHECKS")
        out += [(name, fn) for fn in mod.MANIFEST_CHECKS]
    return out


def test_a_further_familys_entries_pass_every_accepted_cells_checks(
        stub_tree):
    """The manifest with a fifth family's configuration, cell and three
    ``per_layer`` entries appended, its cell joining the list of a metric
    whose scope it shares, passes everything the accepted cells' tests
    assert of the manifest; a check that counts what follows does not."""
    top, _, bench = stub_tree
    bench = json.loads(json.dumps(bench))
    shared = next(m for m in bench["per_layer"]
                  if m["name"] == "optimizer.device_ms")
    shared["workloads"].append(CELL)
    checks = _manifest_checks()
    assert {name for name, _ in checks} >= {
        "test_bench_harness", "test_bench_spans", "test_bench_lm",
        "test_bench_ling"}
    for _, check in checks:
        check(bench)
    import test_bench_harness

    test_bench_harness.every_name_has_its_file_and_every_file_its_name(
        bench, os.path.join(top, "benchmark"))

    # the form PR 38's test had: the last entries are mine, and so many
    def pinned(bench):
        names = [m["name"] for m in bench["per_layer"]]
        mine = [m["name"] for m in BENCH["per_layer"]][-3:]
        assert names[-3:] == mine and len(names) == len(BENCH["per_layer"])

    pinned(BENCH)
    with pytest.raises(AssertionError):
        pinned(bench)
