"""The ``evabyte_train`` cell on the CPU at a size a test run can hold
(``bench_tiny_evabyte.py``: the chip's own driver, reference and comparison
on the cell's own files, cut to the program's tiny preset, float32): a
sound run is ``correct`` under the cell's own limits and each fault that
can be planted under the timed path is not (``test_bench_evabyte_control.py``
plants each of the reference's faults and the float8 control in the
reference's place); the family's layer table against a hand count and the
model's parameters; the configuration's file against the catalog's row and
the program's preset; the cell's files are new files beside the accepted
ones; every reader that lists the cell reads its program's scopes and
counters, the kernels' readers their kernels by name.
"""

import json
import os
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny_evabyte import CELL, tiny_evabyte_cell
from benchmark import flops, hostspans
from benchmark import run as bench_run
from benchmark import trace as trace_mod
from benchmark.drivers import evabyte_train
from benchmark.reference import evabyte as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM_CELL = "nemotron3-nano-9l-ep16.train-8k"
LING_CELL = "ling3-flash-6l-ep64.train-8k"
JOYAI_CELL = "joyai-flash-5l-mtp-ep16.train-8k"
KEYE_CELL = "keye-vl2-a3b-6l-ep8.train-32k"
# A JSON-lines catalog of published architectures (one row a model, with its
# ``source_url`` and ``config``); the row check runs where it is given.
CATALOG = os.environ.get("MODEL_CATALOG", "")
SEED = 2147483659


def _run(monkeypatch=None, plant=None):
    cell = tiny_evabyte_cell()
    if plant is not None:
        plant(monkeypatch)
    return evabyte_train.run_cell(cell, seed=SEED, seconds=0.3, trace=False,
                                  t_start=time.perf_counter())


@pytest.fixture(autouse=True)
def _leave_no_spans():
    """The traced run leaves its spans in the process-wide buffer, which
    later test files of the same worker read."""
    yield
    from mx_rcnn_tpu.obs import trace as obs_trace

    obs_trace.reset()


@pytest.fixture(scope="module")
def reference_once():
    """The plain reference of one (cell, seed) is the same for every run of
    this module: compute it once."""
    real, memo = ref.reference_steps, {}

    def cached(net, opt, params, batches, **kw):
        key = tuple(sorted(kw.items()))
        if key not in memo:
            memo[key] = real(net, opt, params, batches, **kw)
        return memo[key]

    ref.reference_steps = cached
    yield memo
    ref.reference_steps = real


@pytest.fixture(scope="module")
def sound(reference_once):
    return _run()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["numbers"]
    assert sound["attempted"] >= 2 and sound["failed"] == 0
    assert sound["end_to_end"]["train_imgs_per_s"] > 0
    assert list(sound["numbers"]) == list(
        bench_run.load_cell(CELL)["check"]["limits"])
    for name, row in sound["numbers"].items():
        assert row["value"] <= 0.1 * row["limit"], sound["numbers"]
    # every leaf of the program is compared by its norm, none left out
    assert sound["notes"]["left_out"] == []
    # mu and phi of two layers; the two norms of two layers and the final
    assert len(sound["notes"]["eva_grad_worst_leaves"]) == 4
    assert len(sound["notes"]["norm_grad_worst_leaves"]) == 5
    assert not {"scan_grad_worst", "routing_diff", "moe_overflow"} & set(
        sound["notes"]["all"])
    json.dumps(sound["notes"], allow_nan=False)
    assert set(evabyte_train.COUNTERS) <= set(sound["counters"])
    assert sound["counters"]["eva_count_misses"] == 0.0
    assert sound["counters"]["eva_summary_keys_pct"] == pytest.approx(
        100 * (16 * 1.5 * 64) / (16 * 1.5 * 64 + 4 * 64 * 65 / 2))


# ---- faults under the timed path ------------------------------------------------

def _no_summaries(mp):
    """Every query sees its window alone: the op in windows, the summaries
    given to none."""
    from mx_rcnn_tpu.models import evabyte
    from mx_rcnn_tpu.ops import eva

    def local(q, k, v, kbar, vbar, window, chunk, block_q):
        s = q.shape[1]
        outs = [eva.eva_attention(q[:, a:a + window], k[:, a:a + window],
                                  v[:, a:a + window], kbar[:, :0],
                                  vbar[:, :0], window, chunk, block_q)[0]
                for a in range(0, s, window)]
        return jnp.concatenate(outs, 1), jnp.float32(0), jnp.int32(0)

    mp.setattr(evabyte, "eva_attention", local)


def _mean_pool(mp):
    from mx_rcnn_tpu.models import evabyte
    from mx_rcnn_tpu.ops import eva

    mp.setattr(evabyte, "pool_chunks", lambda k, v, mu, phi, chunk: (
        eva.pool_chunks(k, v, 0 * mu, 0 * phi, chunk)))


def _targets_off(mp):
    """Each head scored against the token one nearer than its own."""
    from mx_rcnn_tpu.models import evabyte
    from mx_rcnn_tpu.models.nemotron_h import chunked_cross_entropy

    def shifted(h, w, targets, weights, chunk):
        b = 2
        t = targets.reshape(b, -1)
        return chunked_cross_entropy(
            h, w, jnp.roll(t, 1, axis=1).reshape(-1), weights, chunk)

    mp.setattr(evabyte, "chunked_cross_entropy", shifted)


@pytest.mark.parametrize("plant,caught_by", [
    (_no_summaries, "eva_grad_worst"),
    (_mean_pool, "eva_grad_worst"),
    (_targets_off, "norm_grad_worst"),
], ids=["summaries_dropped", "mean_pooling", "targets_off_by_one"])
def test_fault_under_the_timed_path_is_not_correct(
        monkeypatch, reference_once, plant, caught_by):
    result = _run(monkeypatch, plant)
    assert not result["correct"], result["numbers"]
    row = result["numbers"][caught_by]
    assert row["value"] > row["limit"], result["numbers"]


def test_a_program_without_the_family_fails_the_cell_cleanly():
    cell = tiny_evabyte_cell()
    cell["config"]["program"]["network"] = "no_such_family"
    with pytest.raises(evabyte_train.CellFailure):
        evabyte_train.run(cell, seed=1, seconds=1, trace=False,
                          t_start=time.perf_counter())


# ---- the layer table ------------------------------------------------------------

def test_layer_table_hand_count():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    rows = flops.layer_table(config, cell["traffic"])
    # the parameters: the table's rows own what the model holds, 821.37 M
    n_model = sum(int(np.prod(shape)) for _, shape, _ in
                  ref.param_rows(config))
    assert sum(r["params"] for r in rows) == n_model == 821_366_784
    by_name = {r["name"]: r for r in rows}
    t = 16384
    # a query's exact keys: its window's up to itself, (2048 + 1) / 2 on
    # average; its summaries: 128 for each whole window before it
    assert by_name["l1.eva_local"]["flops"] == pytest.approx(
        2.0 * 2 * 32 * 128 * 2049 / 2)
    assert by_name["l1.eva_summaries"]["flops"] == pytest.approx(
        2.0 * 2 * 32 * 128 * 128 * 3.5)
    share = (by_name["l1.eva_summaries"]["flops"]
             / (by_name["l1.eva_summaries"]["flops"]
                + by_name["l1.eva_local"]["flops"]))
    assert 100 * share == pytest.approx(30.42, abs=0.01)
    assert by_name["l1.q_proj"]["flops"] == 2.0 * 4096 * 4096
    assert by_name["l1.gate"]["flops"] == 2.0 * 4096 * 11008
    assert by_name["head7"]["times"] == t - 8
    # the dense products hold most of the step's work; the attention core
    # some percent of it
    step = flops.step_flops_per_image(rows)
    core = sum(flops.forward_flops(r) * 3 * r["times"] for r in rows
               if r["name"].endswith((".eva_local", ".eva_summaries")))
    assert 0.03 < core / step < 0.1
    mod = flops.family(config["network"])
    assert set(r["scope"] for r in rows) <= set(mod.STAGES)


# ---- the configuration's file ------------------------------------------------------

def test_config_file_keeps_the_catalogs_row():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-4l-pp8.json")) as f:
        config = json.load(f)
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
        assert config["source"] == row["source_url"]
        assert set(row["config"]) <= set(config)
        differs = [k for k, v in row["config"].items() if config[k] != v]
        assert sorted(differs) == sorted(config["reduced"])
    published = {
        "hidden_size": 4096, "intermediate_size": 11008,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "vocab_size": 320, "window_size": 2048, "chunk_size": 16,
        "num_pred_heads": 8, "rope_theta": 100000, "rms_norm_eps": 1e-05,
        "init_std": 0.01275, "attention_class": "eva", "fp32_skip_add": True,
        "fp32_logits": True, "fp32_ln": False, "mixedp_attn": True,
        "norm_add_unit_offset": True, "attention_bias": False,
        "max_seq_length": 32768}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 4
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["assumed"] and "8 pipeline stages" in config["deployment"]


def test_config_file_states_what_the_program_runs():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    cfg = evabyte_train.program_config(config, cell["traffic"], False)
    n = cfg.network
    assert n.layer_pattern == "V" * config["num_hidden_layers"]
    got = {"hidden_size": n.hidden_size, "vocab_size": n.vocab_size,
           "num_attention_heads": n.num_attention_heads,
           "num_key_value_heads": n.num_key_value_heads,
           "intermediate_size": n.intermediate_size,
           "window_size": n.eva_window, "chunk_size": n.eva_chunk,
           "num_pred_heads": n.num_pred_heads, "rope_theta": n.rope_theta,
           "rms_norm_eps": n.norm_eps, "init_std": n.init_std}
    for key, value in got.items():
        assert config[key] == value, key
    assert n.head_dim == config["hidden_size"] // config["num_attention_heads"]
    from mx_rcnn_tpu.models import evabyte

    assert evabyte.POOL_INIT_STD == config["network"]["pool_init_std"]
    assert n.compute_dtype == config["network"]["compute_dtype"]
    opt = config["optimizer"]
    from mx_rcnn_tpu.core import optim

    assert (cfg.default.e2e_lr, cfg.default.momentum, optim.ADAM_B2,
            optim.ADAM_EPS, cfg.default.wd, cfg.default.clip_gradient) == (
        opt["lr"], opt["beta1"], opt["beta2"], opt["eps"], opt["wd"],
        opt["clip_global_norm"])
    assert (cfg.train.batch_images, cfg.train.seq_len, cfg.train.shuffle) == (
        1, 16384, False)
    # the program's leaves are the reference's, shape for shape
    from mx_rcnn_tpu.models import build_model

    shapes = jax.eval_shape(build_model(cfg).init_variables,
                            jax.random.PRNGKey(0))[0]
    assert {k: v.shape for k, v in ref.tree_paths(shapes).items()} == {
        path: shape for path, shape, _ in ref.param_rows(config)}


# ---- the cell's files and readers ---------------------------------------------------

def test_the_cells_limits_each_have_a_reason():
    check = bench_run.load_cell(CELL)["check"]
    assert set(check["limits"]) == set(check["reasons"]) == {
        "loss_s1", "loss_s2", "loss_main_s1", "grad_worst",
        "eva_grad_worst", "norm_grad_worst", "count_misses",
        "first_delta_worst"}
    assert check["limits"]["count_misses"] == 0
    assert all(len(r) > 40 for r in check["reasons"].values())


# this PR's eight readers, as the manifest has them at 77..84
NEW = ["eva.device_ms", "eva_pool.device_ms", "eva_attend.device_ms",
       "eva_roofline", "eva_attend_fwd_roofline", "eva_attend_bwd_roofline",
       "eva.summary_keys_pct", "eva_attend.fwd_per_bwd"]
KERNELS = ["eva_attend_fwd_roofline", "eva_attend_bwd_roofline"]
# the device-time readers of the blocked path's scopes and the counter
TINY = ["eva.device_ms", "eva_pool.device_ms", "eva_attend.device_ms",
        "eva_roofline", "eva.summary_keys_pct"]
# the sequence readers this cell shares with the accepted sequence cells
SHARED = ["lm_head.device_ms", "optimizer.device_ms"]


def _entry(bench, name):
    return next(m for m in bench["per_layer"] if m["name"] == name)


def readers_list_the_cell(bench):
    """This cell's own readers list it first; those it shares list the
    accepted cells first, in their order, then this one.  A later cell that
    names the same scope comes after."""
    for name in NEW:
        entry = _entry(bench, name)
        assert entry["workloads"][:1] == [CELL], name
        assert entry["moves"] == "train_imgs_per_s", name
    for name in SHARED:
        assert _entry(bench, name)["workloads"][:5] == [
            LM_CELL, LING_CELL, JOYAI_CELL, KEYE_CELL, CELL], name
    assert _entry(bench, "dense_mlp.device_ms")["workloads"][:3] == [
        LING_CELL, JOYAI_CELL, CELL]
    for name in ("moe.device_ms", "flash.fwd_per_bwd", "dsa.device_ms"):
        assert CELL not in _entry(bench, name)["workloads"], name


@pytest.mark.parametrize("name", NEW)
def test_new_reader_lists_the_cell_and_returns_nothing_without_its_source(
        name):
    """A program without the scope, the counter or the kernel (the
    parent's) gives the reader nothing to read: it returns nothing and does
    not raise."""
    entry = _entry(bench_run.manifest(), name)
    assert entry["workloads"][:1] == [CELL]
    ctx = {"trace": None, "counters": {}, "layers": [], "peak": {},
           "images_per_step": 1, "chips": 1}
    assert bench_run.read_metric(name, ctx) is None


def _ops_of_the_stored_step(path):
    """``trace.load`` for a CPU trace, which has no device line: the step
    program the profiler stored (the one with most instructions under
    ``eva``), every instruction a microsecond, one after another, three
    executions.  The names and name paths are the program's own; the times
    are nobody's."""
    from benchmark import xplane

    step = max(xplane.read_hlo_programs(path), key=lambda names: sum(
        "eva" in v for v in names.values()))
    rows = sorted(step.items())
    span = 1e3 * len(rows)
    return {"devices": [{
        "name": "/device:TPU:0",
        "ops": [[name, path, k * span + 1e3 * i, 1e3] for k in range(3)
                for i, (name, path) in enumerate(rows)],
        "programs": [["jit_step", k * span, span] for k in range(3)]}]}


@pytest.fixture(scope="module")
def traced(reference_once):
    """(the per-layer metrics of the tiny cell's traced run under the
    chip's peaks (a CPU has none in ``peaks.json``), its reduced trace, the
    family's stages)."""
    cached = jax.config.jax_enable_compilation_cache
    # compiled here and now: a CPU executable read back from the persistent
    # cache has lost the name stacks of its instructions
    jax.config.update("jax_enable_compilation_cache", False)
    real, trace_mod.load = trace_mod.load, _ops_of_the_stored_step
    try:
        cell = tiny_evabyte_cell()
        result = evabyte_train.run_cell(cell, seed=SEED, seconds=0.3,
                                        trace=True,
                                        t_start=time.perf_counter())
    finally:
        trace_mod.load = real
        jax.config.update("jax_enable_compilation_cache", cached)
    assert result["correct"], result["numbers"]
    result["device"] = dict(result["device"], kind="TPU v5 lite")
    got = bench_run.metrics_of(result, cell, bench_run.manifest(), True)
    return ({k: v["value"] for k, v in got.items()}, result["trace"],
            flops.family(cell["config"]["network"]).STAGES)


@pytest.mark.parametrize("name", SHARED + ["dense_mlp.device_ms"] + TINY)
def test_reader_reads_the_tiny_programs_scope_or_counter(traced, name):
    """Every scope and counter the cell's listed readers read is one the
    program names: on the traced tiny run each gives a number."""
    assert traced[0][name] > 0.0, name


def test_off_the_tpu_the_kernels_readers_find_nothing(traced):
    """The tiny run takes the blocked path: no op bears a kernel's name."""
    for name in KERNELS + ["eva_attend.fwd_per_bwd"]:
        assert name not in traced[0]


def test_the_scopes_nest_and_the_stages_account_for_the_step(traced):
    metrics, reduced, stages = traced
    assert metrics["eva.device_ms"] > (metrics["eva_pool.device_ms"]
                                       + metrics["eva_attend.device_ms"])
    # no op lies under two stages, and the stages' union and what lies
    # under none add up to the step
    inside = {s: re.compile(r"(^|[/(])" + re.escape(s) + r"([/)]|$)")
              for s in stages}
    for _, path, _, _ in reduced.devices[0]["ops"]:
        assert sum(bool(r.search(path)) for r in inside.values()) <= 1, path
    staged = sum(reduced.scope_s(s) or 0.0 for s in stages)
    unscoped = hostspans.unscoped_s(reduced, stages)
    assert 1e3 * (staged + unscoped) / reduced.steps == pytest.approx(
        metrics["step.device_ms"], rel=1e-9)
    assert metrics["eva_roofline"] < 100 and metrics["step_mfu"] < 100
    assert metrics["eva.summary_keys_pct"] == pytest.approx(
        100 * (16 * 1.5 * 64) / (16 * 1.5 * 64 + 4 * 64 * 65 / 2))


@pytest.mark.parametrize("name", KERNELS)
def test_a_kernels_reader_reads_its_kernel_by_name(name):
    """On a trace whose ops bear the kernels' names (and the compiler's
    ``.N``), each reader counts its own kernel's time against the table's
    work for it: a share under 100 % at a millisecond a step, and nothing
    for another kernel's name."""
    cell = bench_run.load_cell(CELL)
    kernel = name[:-len("_roofline")]
    # 50 ms a step, in two executions, one with the compiler's suffix
    ops = [[kernel, "jit(step)/eva", 0.0, 2e7],
           [kernel + ".3", "jit(step)/eva", 5e7, 3e7],
           ["eva_other", "jit(step)/eva", 2e9, 1e9]]
    ctx = {"trace": SimpleNamespace(devices=[{"ops": ops}], steps=1),
           "layers": flops.layer_table(cell["config"], cell["traffic"]),
           "peak": flops.peaks("TPU v5 lite"), "images_per_step": 1,
           "chips": 1}
    value = bench_run.read_metric(name, ctx)
    from benchmark.families.evabyte import kernel_least_seconds

    least = kernel_least_seconds(ctx["layers"], ctx["peak"], kernel)
    assert value == pytest.approx(100.0 * least / 0.05)
    assert 0.0 < value < 100.0
    ctx["trace"] = SimpleNamespace(devices=[{"ops": ops[2:]}], steps=1)
    assert bench_run.read_metric(name, ctx) is None


@pytest.mark.parametrize("forward,ratio", [(4, 1.0), (8, 2.0)])
def test_the_forward_per_backward_reader_counts_the_kernels_by_name(
        forward, ratio):
    ops = ([["eva_attend_fwd" + (".%d" % i if i else ""), "p", 0.0, 1.0]
            for i in range(forward)]
           + [["eva_attend_bwd", "p", 0.0, 1.0]] * 4
           + [["eva_attend_fwd_other", "p", 0.0, 1.0]])
    ctx = {"trace": SimpleNamespace(devices=[{"ops": ops}])}
    assert bench_run.read_metric("eva_attend.fwd_per_bwd", ctx) == ratio
    ctx = {"trace": SimpleNamespace(devices=[{"ops": ops[:forward]}])}
    assert bench_run.read_metric("eva_attend.fwd_per_bwd", ctx) is None


def test_readers_list_the_cell_after_the_accepted_cells():
    readers_list_the_cell(bench_run.manifest())


def test_counter_reader_reads_the_log_events_counter():
    ctx = {"counters": {"eva_summary_keys_pct": 30.42}}
    assert bench_run.read_metric("eva.summary_keys_pct", ctx) == 30.42


def accepted_entries_come_first(bench):
    """The manifest's accepted entries come first, in order: this PR's
    configuration and cell seventh, its eight readers at 77..84.  How many
    entries follow is the next PR's to say (PERF.md section 3)."""
    assert [c["name"] for c in bench["configs"]][:6] == [
        "r101-coco", "vgg16-voc07", "nemotron3-nano-9l-ep16",
        "ling3-flash-6l-ep64", "joyai-flash-5l-mtp-ep16",
        "keye-vl2-a3b-6l-ep8"]
    assert bench["configs"][6]["name"] == "evabyte-4l-pp8"
    assert bench["configs"][6]["reduced"] == ["num_hidden_layers"]
    assert [w["name"] for w in bench["workloads"]][6] == CELL
    assert bench["workloads"][6]["chips"] == 1
    names = [m["name"] for m in bench["per_layer"]]
    assert names[76] == "dsa_kl.per_bwd"
    assert names[77:85] == NEW
    assert bench["run_seconds"] == 10
    assert [m["name"] for m in bench["end_to_end"]][:2] == [
        "train_imgs_per_s", "setup_s"]
    # every metric asked of the cell has a reader's file
    for m in bench["per_layer"]:
        if "workloads" not in m or CELL in m["workloads"]:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py")), m["name"]


# what ``test_bench_family_seam.py`` runs against a manifest with a further
# family's entries appended: each takes the manifest
MANIFEST_CHECKS = [readers_list_the_cell, accepted_entries_come_first]


def test_the_new_cell_is_new_files_and_appended_entries():
    """The seam of ``test_bench_family_seam.py`` for this cell: its files
    are there under the names ``run.py`` finds them by, and the manifest's
    accepted entries come first and in order."""
    bench = bench_run.manifest()
    accepted_entries_come_first(bench)
    for path in ["configs/evabyte-4l-pp8.json", f"workloads/{CELL}.json",
                 "traffic/train-1x16384.json", "families/evabyte.py",
                 "drivers/evabyte_train.py", "reference/evabyte.py",
                 "reference/evabyte_compare.py", "evabyte_readings.py"] + [
                     f"metrics/{n}.py" for n in NEW]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path
    cell = bench_run.load_cell(CELL)
    assert cell["driver"] == "evabyte_train"
    assert cell["config"]["network"]["family"] == "evabyte"
    assert cell["traffic"] == {
        "kind": "lm_train", "seq_len": 16384, "per_chip_batch": 1,
        "sequences_per_chip": 32, "ids": "uniform", "warmup_steps": 8,
        "epoch_steps": 200}
