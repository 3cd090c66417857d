"""The ``keye_train`` cell on the CPU at a size a test run can hold
(``bench_tiny_keye.py``: the chip's own driver, reference and comparison on
the cell's own files, cut to the program's tiny preset, float32): a sound
run is ``correct`` under the cell's own limits and each fault that can be
planted under the timed path is not (``test_bench_keye_control.py`` plants
every fault and the float8 control in the reference's place); the family's
layer table against a hand count and the model's parameters; the
configuration's file against the catalog's row and the program's preset;
the cell's files are new files beside the accepted ones; every reader that
lists the cell reads its program's scopes and counters, the kernels'
readers their kernels by name.
"""

import json
import os
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench_tiny_keye import CELL, tiny_keye_cell
from benchmark import flops, hostspans
from benchmark import run as bench_run
from benchmark import trace as trace_mod
from benchmark.drivers import keye_train
from benchmark.reference import keye_vl2 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM_CELL = "nemotron3-nano-9l-ep16.train-8k"
LING_CELL = "ling3-flash-6l-ep64.train-8k"
JOYAI_CELL = "joyai-flash-5l-mtp-ep16.train-8k"
# A JSON-lines catalog of published architectures (one row a model, with its
# ``source_url`` and ``config``); the row check runs where it is given.
CATALOG = os.environ.get("MODEL_CATALOG", "")
SEED = 2147483659


def _run(monkeypatch=None, plant=None, overrides=None):
    cell = tiny_keye_cell()
    cell["config"]["program"]["overrides"].update(overrides or {})
    if plant is not None:
        plant(monkeypatch)
    return keye_train.run_cell(cell, seed=SEED, seconds=0.3, trace=False,
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
    # every matrix the program trains is held; the norm scales that a
    # softmax makes nearly scale-free are the vectors' numbers'
    left = sound["notes"]["left_out"]
    assert all(k.split("/")[-1] in ref.SCAN_LEAVES + ("norm",)
               for k in left), left
    # q_norm, k_norm of two layers; the weights and the LayerNorm's two
    # vectors of two indexers
    assert len(sound["notes"]["head_norm_grad_worst_leaves"]) == 4
    assert len(sound["notes"]["indexer_grad_worst_leaves"]) == 6
    assert "scan_grad_worst" not in sound["notes"]["all"]
    json.dumps(sound["notes"], allow_nan=False)
    assert set(keye_train.COUNTERS) <= set(sound["counters"])
    assert sound["counters"]["moe_overflow"] == 0.0
    assert sound["counters"]["tiles_touched_pct"] == 100.0
    assert 0.0 < sound["counters"]["index_loss"] < 2.0


# ---- faults under the timed path ------------------------------------------------

def _choose_every_key(mp):
    """Dense causal attention: the choice keeps every key before a query."""
    from mx_rcnn_tpu.ops import sparse_attention
    mp.setattr(sparse_attention, "choose", lambda scores, top_k: (
        jnp.full(scores.shape[:1], -jnp.inf),
        jnp.full(scores.shape[:1], scores.shape[1], jnp.int32)))


def _scores_without(weights=True, relu=True):
    def plant(mp):
        from mx_rcnn_tpu.ops import sparse_attention

        def scores(qi, w, ki):
            dots = jnp.einsum("qjd,kd->qjk", qi, ki) * qi.shape[-1] ** -0.5
            if relu:
                dots = jax.nn.relu(dots)
            w = w if weights else jnp.full(w.shape, w.shape[1] ** -0.5)
            return jnp.sum(dots * w[:, :, None], axis=1)
        mp.setattr(sparse_attention, "indexer_scores", scores)
    return plant


def _no_head_norms(mp):
    """The head norms are the stack's only RMSNorms over a head's 16."""
    from mx_rcnn_tpu.models import keye_vl2
    real = keye_vl2.rms_norm
    mp.setattr(keye_vl2, "rms_norm", lambda x, scale, eps: (
        x if x.shape[-1] == 16 else real(x, scale, eps)))


def _no_index_rope(mp):
    from mx_rcnn_tpu.models import keye_vl2
    mp.setattr(keye_vl2, "rotary_part", lambda x, theta, width: x)


@pytest.mark.parametrize("plant,overrides,caught_by", [
    (_choose_every_key, None, "selection_diff"),
    (None, {"network__indexer_topk": 8}, "index_loss_s1"),
    (_scores_without(weights=False), None, "selection_diff"),
    (_scores_without(relu=False), None, "selection_diff"),
    (_no_head_norms, None, "head_norm_grad_worst"),
    (_no_index_rope, None, "selection_diff"),
], ids=["dense_attention", "top_k_halved", "weights_dropped",
        "relu_dropped", "head_norms_dropped", "index_rotary_dropped"])
def test_fault_under_the_timed_path_is_not_correct(
        monkeypatch, reference_once, plant, overrides, caught_by):
    result = _run(monkeypatch, plant, overrides)
    assert not result["correct"], result["numbers"]
    row = result["numbers"][caught_by]
    assert row["value"] > row["limit"], result["numbers"]


def test_a_program_without_the_family_fails_the_cell_cleanly():
    cell = tiny_keye_cell()
    cell["config"]["program"]["network"] = "no_such_family"
    with pytest.raises(keye_train.CellFailure):
        keye_train.run(cell, seed=1, seconds=1, trace=False,
                       t_start=time.perf_counter())


# ---- the layer table ------------------------------------------------------------

def test_layer_table_hand_count():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    rows = flops.layer_table(config, cell["traffic"])
    # the parameters: the table's rows own what the model holds, 659.19 M
    n_model = sum(int(jnp.prod(jnp.array(shape))) for _, shape, _ in
                  ref.param_rows(config))
    assert sum(r["params"] for r in rows) == n_model == 659_190_016
    by_name = {r["name"]: r for r in rows}
    t, k = 32768, 2048
    # the sparse count: every causal pair scored, the chosen ones attended
    chosen = (k * (k + 1) / 2 + (t - k) * k) / t
    assert 1983 < chosen < 1985
    assert by_name["l1.index_scores"]["flops"] == pytest.approx(
        2.0 * 16 * 64 * (t + 1) / 2 + 3.0 * 16 * (t + 1) / 2)
    assert by_name["l1.index_scores"]["grad"] == "none"
    assert by_name["l1.attention"]["flops"] == pytest.approx(
        2.0 * 2 * 32 * 128 * chosen)
    assert by_name["l1.index_q_proj"]["grad"] == "weight"
    assert by_name["l1.q_proj"]["flops"] == 2.0 * 2048 * 4096
    assert by_name["l1.k_proj"]["flops"] == 2.0 * 2048 * 512
    # one held expert's assignment a token: 8 x 16 / 128
    assert by_name["l1.experts_up"]["times"] == t
    assert not any("shared" in r["name"] for r in rows)
    # the attention block, its indexer over every pair among it, holds most
    # of the step's work
    per_token = {s: flops.step_flops_per_image(rows, s) / t
                 for s in ("dsa", "moe", "lm_head")}
    assert per_token["dsa"] > 5 * per_token["moe"]
    mod = flops.family(config["network"])
    assert set(r["scope"] for r in rows) <= set(mod.STAGES)


# ---- the configuration's file ------------------------------------------------------

def test_config_file_keeps_the_catalogs_row():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl2-a3b-6l-ep8.json")) as f:
        config = json.load(f)
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert config["source"] == row["source_url"]
        assert set(row["config"]) <= set(config)
        differs = [k for k, v in row["config"].items() if config[k] != v]
        assert sorted(differs) == sorted(config["reduced"])
    published = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "rope_theta": 10000000, "rms_norm_eps": 1e-06,
        "num_local_experts": 128, "attention_bias": False,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048}}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert [config[k] for k in config["reduced"]] == [6, 16, 18992]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert ref.held(config) == (0, 16)
    assert config["assumed"] and "8 chips" in config["deployment"]


def test_config_file_states_what_the_program_runs():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    cfg = keye_train.program_config(config, cell["traffic"], False)
    n = cfg.network
    assert n.layer_pattern == "S" * config["num_hidden_layers"]
    assert tuple(n.experts_held) == ref.held(config)
    sa = config["sa_config"]
    got = {"hidden_size": n.hidden_size, "vocab_size": n.vocab_size,
           "num_attention_heads": n.num_attention_heads,
           "num_key_value_heads": n.num_key_value_heads,
           "head_dim": n.head_dim, "rope_theta": n.rope_theta,
           "num_experts_per_tok": n.num_experts_per_tok,
           "moe_intermediate_size": n.moe_intermediate_size,
           "norm_topk_prob": n.norm_topk_prob, "rms_norm_eps": n.norm_eps}
    for key, value in got.items():
        assert config[key] == value, key
    assert (n.indexer_num_heads, n.indexer_head_dim, n.indexer_topk) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert n.indexer_rope_dim == config["network"]["indexer_rope_dim"]
    assert n.router_scoring == "softmax"
    assert n.moe_shared_expert_intermediate_size == 0
    assert n.n_routed_experts == config["published"]["num_experts"]
    assert n.init_layers == config["published"]["num_hidden_layers"]
    assert n.compute_dtype == config["network"]["compute_dtype"]
    assert n.moe_capacity_factor == config["network"]["moe_capacity_factor"]
    opt = config["optimizer"]
    from mx_rcnn_tpu.core import optim

    assert (cfg.default.e2e_lr, cfg.default.momentum, optim.ADAM_B2,
            optim.ADAM_EPS, cfg.default.wd, cfg.default.clip_gradient) == (
        opt["lr"], opt["beta1"], opt["beta2"], opt["eps"], opt["wd"],
        opt["clip_global_norm"])
    assert (cfg.train.batch_images, cfg.train.seq_len, cfg.train.shuffle) == (
        1, 32768, False)
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
        "loss_s1", "loss_s2", "index_loss_s1", "grad_worst",
        "head_norm_grad_worst", "indexer_grad_worst", "selection_diff",
        "count_misses", "first_delta_worst", "routing_diff", "moe_overflow"}
    assert check["limits"]["moe_overflow"] == 0
    assert check["limits"]["count_misses"] == 0
    assert all(len(r) > 40 for r in check["reasons"].values())


# this PR's eleven readers, as the manifest has them at 65..75
NEW = ["dsa.device_ms", "dsa_index.device_ms", "dsa_attend.device_ms",
       "dsa_index_loss.device_ms", "dsa_roofline", "dsa.index_loss",
       "dsa.tiles_touched_pct", "dsa_select_roofline",
       "dsa_attend_fwd_roofline", "dsa_attend_bwd_roofline",
       "dsa_kl_roofline"]
KERNELS = NEW[7:]
# the sequence readers the accepted sequence cells list
SHARED = ["moe.device_ms", "moe_route.device_ms", "moe_experts.device_ms",
          "moe_grouped.device_ms", "moe_roofline", "lm_head.device_ms",
          "optimizer.device_ms", "moe.assignments_per_token",
          "moe.load_max_over_mean", "moe.overflow"]


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
        assert _entry(bench, name)["workloads"][:4] == [
            LM_CELL, LING_CELL, JOYAI_CELL, CELL], name
    assert CELL not in _entry(bench, "flash.fwd_per_bwd")["workloads"]


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
    ``dsa``), every instruction a microsecond, one after another, three
    executions.  The names and name paths are the program's own; the times
    are nobody's."""
    from benchmark import xplane

    step = max(xplane.read_hlo_programs(path), key=lambda names: sum(
        "dsa" in v for v in names.values()))
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
        cell = tiny_keye_cell()
        result = keye_train.run_cell(cell, seed=SEED, seconds=0.3,
                                     trace=True, t_start=time.perf_counter())
    finally:
        trace_mod.load = real
        jax.config.update("jax_enable_compilation_cache", cached)
    assert result["correct"], result["numbers"]
    result["device"] = dict(result["device"], kind="TPU v5 lite")
    got = bench_run.metrics_of(result, cell, bench_run.manifest(), True)
    return ({k: v["value"] for k, v in got.items()}, result["trace"],
            flops.family(cell["config"]["network"]).STAGES)


@pytest.mark.parametrize("name", SHARED + NEW[:7])
def test_reader_reads_the_tiny_programs_scope_or_counter(traced, name):
    """Every scope and counter the cell's listed readers read is one the
    program names: on the traced tiny run each gives a number."""
    value = traced[0][name]
    if name == "moe.overflow":
        assert value == 0.0
    else:
        assert value > 0.0, name


def test_off_the_tpu_the_kernels_readers_find_nothing(traced):
    """The tiny run takes the blocked path: no op bears a kernel's name."""
    for name in KERNELS:
        assert name not in traced[0]


def test_the_scopes_nest_and_the_stages_account_for_the_step(traced):
    metrics, reduced, stages = traced
    assert metrics["dsa.device_ms"] > (metrics["dsa_index.device_ms"]
                                       + metrics["dsa_attend.device_ms"])
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
    assert metrics["dsa_roofline"] < 100 and metrics["moe_roofline"] < 100
    assert metrics["step_mfu"] < 100


@pytest.mark.parametrize("name", KERNELS)
def test_a_kernels_reader_reads_its_kernel_by_name(name):
    """On a trace whose ops bear the kernels' names (and the compiler's
    ``.N``), each reader counts its own kernel's time against the table's
    sparse work for it: a share under 100 % at the time a dense first form
    could take, and nothing for another kernel's name."""
    cell = bench_run.load_cell(CELL)
    kernel = name[:-len("_roofline")]
    # a second a step, in two executions, one with the compiler's suffix
    ops = [[kernel, "jit(step)/dsa", 0.0, 4e8],
           [kernel + ".3", "jit(step)/dsa", 5e8, 6e8],
           ["dsa_other", "jit(step)/dsa", 2e9, 1e9]]
    ctx = {"trace": SimpleNamespace(devices=[{"ops": ops}], steps=1), "layers": flops.layer_table(cell["config"],
                                                   cell["traffic"]),
           "peak": flops.peaks("TPU v5 lite"), "images_per_step": 1,
           "chips": 1}
    value = bench_run.read_metric(name, ctx)
    assert 0.0 < value < 100.0
    ctx["trace"] = SimpleNamespace(devices=[{"ops": ops[2:]}], steps=1)
    assert bench_run.read_metric(name, ctx) is None


def test_readers_list_the_cell_after_the_accepted_cells():
    readers_list_the_cell(bench_run.manifest())


def test_counter_readers_read_the_log_events_counters():
    ctx = {"counters": {"index_loss": 0.5, "tiles_touched_pct": 99.0}}
    assert bench_run.read_metric("dsa.index_loss", ctx) == 0.5
    assert bench_run.read_metric("dsa.tiles_touched_pct", ctx) == 99.0


def accepted_entries_come_first(bench):
    """The manifest's accepted entries come first, in order: this PR's
    configuration and cell sixth, its eleven readers at 65..75.  How many
    entries follow is the next PR's to say (PERF.md section 3)."""
    assert [c["name"] for c in bench["configs"]][:5] == [
        "r101-coco", "vgg16-voc07", "nemotron3-nano-9l-ep16",
        "ling3-flash-6l-ep64", "joyai-flash-5l-mtp-ep16"]
    assert bench["configs"][5]["name"] == "keye-vl2-a3b-6l-ep8"
    assert bench["configs"][5]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert [w["name"] for w in bench["workloads"]][5] == CELL
    assert bench["workloads"][5]["chips"] == 1
    names = [m["name"] for m in bench["per_layer"]]
    assert names[64] == "kda_scores.kernel_ms"
    assert names[65:76] == NEW
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
    for path in ["configs/keye-vl2-a3b-6l-ep8.json",
                 f"workloads/{CELL}.json", "traffic/train-1x32768.json",
                 "families/keye_vl2.py", "drivers/keye_train.py",
                 "reference/keye_vl2.py", "reference/keye_compare.py",
                 "keye_readings.py"] + [f"metrics/{n}.py" for n in NEW]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path
    cell = bench_run.load_cell(CELL)
    assert cell["driver"] == "keye_train"
    assert cell["config"]["network"]["family"] == "keye_vl2"
    assert cell["traffic"] == {
        "kind": "lm_train", "seq_len": 32768, "per_chip_batch": 1,
        "sequences_per_chip": 32, "ids": "uniform", "warmup_steps": 8,
        "epoch_steps": 200}
    entry = bench["workloads"][5]
    assert (entry["why"], entry["traffic"]) == (cell["why"], "train-1x32768")
    assert len(entry["why"]) <= 200
