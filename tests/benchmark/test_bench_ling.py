"""The ``ling_train`` cell on the CPU at a size a test run can hold
(``bench_tiny_ling.py``: the chip's own driver, reference and comparison on
the cell's own files, cut to the program's tiny preset, float32): a sound
run is ``correct`` under the cell's own limits, each fault planted under
the timed path or in the reference's place is not, nor is the float8
control; the family's layer table against a hand count and the compiler's
count; the configuration's file against the catalog's row and the
program's preset; the cell's files are new files beside the accepted ones;
the sequence readers it shares with the third cell list both and read its
program's scopes.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny_ling import CELL, tiny_ling_cell
from benchmark import flops, ling_readings
from benchmark import run as bench_run
from benchmark import trace as trace_mod
from benchmark.drivers import ling_train
from benchmark.reference import ling_flash as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM_CELL = "nemotron3-nano-9l-ep16.train-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2147483659


def _run(monkeypatch=None, plant=None):
    if plant is not None:
        plant(monkeypatch)
    return ling_train.run_cell(tiny_ling_cell(), seed=SEED, seconds=0.3,
                               trace=False, t_start=time.perf_counter())


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
    for name, row in sound["numbers"].items():
        assert row["value"] <= 0.1 * row["limit"], sound["numbers"]
    # every leaf the program trains is held, bar the KDA vectors (far under
    # the median leaf: ``scan_grad_worst`` holds them instead) and the
    # scales of norms that a gate or a softmax makes nearly scale-free
    left = sound["notes"]["left_out"]
    assert all(k.split("/")[-1] in ref.SCAN_LEAVES + (
        "kv_a_norm", "q_norm", "k_norm", "o_norm", "norm") for k in left), left
    # A_log, dt_bias of three KDA blocks; three norm scales of the latent one
    assert len(sound["notes"]["scan_grad_leaves"]) == 6
    assert len(sound["notes"]["latent_grad_leaves"]) == 3
    assert set(ling_train.COUNTERS) <= set(sound["counters"])
    assert sound["counters"]["moe_overflow"] == 0.0
    assert -80.0 < sound["counters"]["kda_chunk_log_decay_min"] < 0.0


# ---- faults under the timed path ------------------------------------------------

def _rule_with(change):
    def plant(mp):
        from mx_rcnn_tpu.models import ling_flash
        real = ling_flash.kda_chunked
        mp.setattr(ling_flash, "kda_chunked",
                   lambda q, k, v, g, beta, chunk: change(
                       real, q, k, v, g, beta, chunk))
    return plant


def _no_carry(real, q, k, v, g, beta, chunk):
    """Every chunk run as a sequence of its own."""
    split = lambda t: t.reshape((-1, chunk) + t.shape[2:])  # noqa: E731
    o, g_min = real(split(q), split(k), split(v), split(g), split(beta),
                    chunk)
    return o.reshape(v.shape), g_min


def _route_with(**change):
    def plant(mp):
        from mx_rcnn_tpu.ops import moe
        real = moe.route

        def route(x, w, bias, top_k, scale, norm_topk, groups=None):
            kw = dict(scale=scale, groups=groups)
            kw.update(change)
            return real(x, w, bias, top_k, kw["scale"], norm_topk,
                        kw["groups"])

        mp.setattr(moe, "route", route)
    return plant


def _no_routed(mp):
    from mx_rcnn_tpu.ops import moe
    mp.setattr(moe, "held_experts",
               lambda x, *a, **kw: jnp.zeros(x.shape, jnp.float32))


def _no_shared(mp):
    """The shared expert is the one SwiGLU of the experts' width."""
    from mx_rcnn_tpu.ops import moe
    real = moe.swiglu_ffn
    mp.setattr(moe, "swiglu_ffn", lambda x, gate, up, down: (
        jnp.zeros(x.shape, jnp.float32) if gate.shape[1] == 32
        else real(x, gate, up, down)))


def _no_rope(mp):
    from mx_rcnn_tpu.models import ling_flash
    mp.setattr(ling_flash, "rotary", lambda x, theta: x)


def _no_latent_norm(mp):
    """The latent is the one three-axis array of its width that is
    normalised."""
    from mx_rcnn_tpu.models import ling_flash
    real = ling_flash.rms_norm
    mp.setattr(ling_flash, "rms_norm", lambda x, scale, eps: (
        x if x.ndim == 3 and x.shape[-1] == 24 else real(x, scale, eps)))


@pytest.mark.parametrize("plant,caught_by", [
    (_rule_with(_no_carry), "scan_grad_worst"),
    (_rule_with(lambda real, q, k, v, g, beta, chunk: real(
        q, k, v, 0.0 * g, beta, chunk)), "scan_grad_worst"),
    (_rule_with(lambda real, q, k, v, g, beta, chunk: real(
        q, k, v, g, jnp.ones_like(beta), chunk)), "grad_worst"),
    (_route_with(groups=None), "routing_diff"),
    (_no_rope, "latent_grad_worst"), (_no_latent_norm, "latent_grad_worst"),
    (_route_with(scale=1.0), "grad_worst"),
    (_no_routed, "grad_worst"), (_no_shared, "grad_worst"),
], ids=["state_not_carried_across_chunks", "decay_replaced_by_1",
        "beta_replaced_by_1", "group_limit_dropped", "rotary_term_dropped",
        "latent_norm_dropped", "scaling_dropped", "held_experts_left_out",
        "shared_expert_left_out"])
def test_fault_under_the_timed_path_is_not_correct(
        monkeypatch, reference_once, plant, caught_by):
    result = _run(monkeypatch, plant)
    assert not result["correct"], result["numbers"]
    row = result["numbers"][caught_by]
    assert row["value"] > row["limit"], result["numbers"]


def test_planted_in_the_reference_each_fault_and_the_control_fail():
    """What ``ling_readings.py`` reads on the chip: the reference with a
    fault or in float8, in the program's place, against itself plain."""
    tags = ["float8"] + list(ref.FAULTS)
    row = ling_readings.planted_rows(tiny_ling_cell(), SEED, tags, ref)
    for tag in tags:
        assert not row[tag]["correct"], (tag, row[tag]["all"])


def test_a_program_without_the_family_fails_the_cell_cleanly():
    cell = tiny_ling_cell()
    cell["config"]["program"]["network"] = "no_such_family"
    with pytest.raises(ling_train.CellFailure):
        ling_train.run(cell, seed=1, seconds=1, trace=False,
                       t_start=time.perf_counter())


# ---- the layer table ------------------------------------------------------------

def test_layer_table_hand_count():
    cell = bench_run.load_cell(CELL)
    rows = flops.layer_table(cell["config"], cell["traffic"])
    per_token = flops.step_flops_per_image(rows) / 8192
    # forward multiply-adds a token, by hand from the published widths:
    # KDA 5 x (5 x 2560 x 4096 + 2 x 2560 x 32), MLA 2560 x (6144 + 576) +
    # 512 x 8192 + 4096 x 2560 + 32 x 320 x 8193 / 2 of scores, dense 3 x
    # 2560 x 6144, E 5 x (router 1.31 M + shared 5.90 M + 0.125 x 5.90 M),
    # head 50.3 M
    kda = 5 * (5 * 2560 * 4096 + 2 * 2560 * 32)
    mla = (2560 * (6144 + 576) + 512 * 8192 + 4096 * 2560 + 2560 * 32
           + 32 * 320 * 8193 / 2)
    dense = 3 * 2560 * 6144
    moe = 5 * (2560 * 512 + 1.125 * 3 * 2560 * 768)
    head = 2560 * 19648
    macs = kda + mla + dense + moe + head
    # the delta rule (4.5 MFLOP a token a layer) and the pointwise rows add
    # 3 %; the head and the loss run on 8191 of 8192 positions
    assert 1.0 < per_token / (3 * 2 * macs) < 1.05, per_token / (6 * macs)
    assert 2.7e9 < per_token < 3.0e9
    by_scope = {s: flops.step_flops_per_image(rows, s) for s in
                ("kda_mixer", "mla", "dense_mlp", "moe", "lm_head", "embed")}
    assert by_scope["embed"] == 0
    share = {k: v / sum(by_scope.values()) for k, v in by_scope.items()}
    assert 0.53 < share["kda_mixer"] < 0.60 and 0.13 < share["mla"] < 0.17
    rule = [r for r in rows if r["name"].endswith(".delta_rule")]
    assert len(rule) == 5 and rule[0]["flops"] == 2.0 * 32 * (
        2 * 32.5 * 128 + 31.5 * 256 + 3 * 128 * 128 + 32.5 * 128)
    mod = flops.family(cell["config"]["network"])
    assert set(r["scope"] for r in rows) <= set(mod.STAGES)


def test_counts_against_cost_analysis_of_a_small_forward():
    """The table's forward operations for the tiny configuration against
    what the compiler counts for the reference's forward of one sequence
    (its held experts run on every token: the table is asked for the same;
    the recurrence is counted apart)."""
    cell = tiny_ling_cell()
    config, traffic = cell["config"], dict(cell["traffic"], seq_len=64)
    rows = flops.layer_table(config, traffic)
    held_rows = 64 * 2 * 2 / 16
    want = 0.0
    for r in rows:
        times = r["times"]
        if "experts_" in r["name"]:
            times = 64 * 2         # the dense-mask loop: every token, 2 held
            assert abs(r["times"] - held_rows) < 1e-9
        if r["name"].endswith(".delta_rule"):
            continue               # the reference runs the recurrence instead
        want += flops.forward_flops(r) * times
    params = ref.make_weights(config, 1)
    ids = jnp.zeros((64,), jnp.int32)
    got = jax.jit(lambda p: ref.sequence_loss(config, p, ids)[0]).lower(
        params).compile().cost_analysis()["flops"]
    # the recurrence: 9 operations a state element a step, 3 KDA layers
    got -= 3 * 64 * 9 * 4 * 16 * 16
    assert 0.9 < got / want < 1.2, (got, want)


# ---- the configuration's file ------------------------------------------------------

def test_config_file_keeps_the_catalogs_row():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling3-flash-6l-ep64.json")) as f:
        config = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash-VL")
        assert config["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if config.get(k) != v]
        assert sorted(differs) == sorted(config["reduced"])
    # the widths, as the model's config.json has them
    published = {
        "hidden_size": 2560, "intermediate_size": 6144, "head_dim": 128,
        "num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "moe_shared_expert_intermediate_size": 768, "n_group": 8,
        "topk_group": 4, "routed_scaling_factor": 2.5,
        "short_conv_kernel_size": 4, "layer_group_size": 6,
        "kda_lower_bound": -5, "rope_theta": 6000000, "rms_norm_eps": 1e-06}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "num_experts", "vocab_size"]
    assert [config[k] for k in config["reduced"]] == [6, 1, 8, 19648]
    assert config["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    assert ref.pattern(config) == "KKKKLK" and ref.held(config) == (0, 8)
    assert config["assumed"] and "64 chips" in config["deployment"]
    # 715 M parameters, 11.44 GB at 16 bytes
    n = sum(int(jnp.prod(jnp.array(shape))) for _, shape, _ in
            ref.param_rows(config))
    assert 714.5e6 < n < 715.5e6


def test_config_file_states_what_the_program_runs():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    cfg = ling_train.program_config(config, cell["traffic"], False)
    n = cfg.network
    assert n.layer_pattern == ref.pattern(config)
    assert tuple(n.experts_held) == ref.held(config)
    got = {"hidden_size": n.hidden_size, "vocab_size": n.vocab_size,
           "first_k_dense_replace": n.first_k_dense_replace,
           "intermediate_size": n.intermediate_size,
           "num_attention_heads": n.num_attention_heads,
           "head_dim": n.head_dim, "short_conv_kernel_size": n.conv_kernel,
           "kda_lower_bound": n.kda_lower_bound,
           "kv_lora_rank": n.kv_lora_rank,
           "qk_nope_head_dim": n.qk_nope_head_dim,
           "qk_rope_head_dim": n.qk_rope_head_dim,
           "v_head_dim": n.v_head_dim, "rope_theta": n.rope_theta,
           "num_experts_per_tok": n.num_experts_per_tok,
           "n_group": n.n_group, "topk_group": n.topk_group,
           "moe_intermediate_size": n.moe_intermediate_size,
           "moe_shared_expert_intermediate_size":
               n.moe_shared_expert_intermediate_size,
           "routed_scaling_factor": n.routed_scaling_factor,
           "norm_topk_prob": n.norm_topk_prob, "rms_norm_eps": n.norm_eps}
    for key, value in got.items():
        assert config[key] == value, key
    assert n.n_routed_experts == config["published"]["num_experts"]
    assert n.init_layers == config["published"]["num_hidden_layers"]
    assert n.compute_dtype == config["network"]["compute_dtype"]
    assert n.moe_capacity_factor == config["network"]["moe_capacity_factor"]
    assert n.chunk_size == config["network"]["chunk_size"]
    opt = config["optimizer"]
    from mx_rcnn_tpu.core import optim

    assert (cfg.default.e2e_lr, cfg.default.momentum, optim.ADAM_B2,
            optim.ADAM_EPS, cfg.default.wd, cfg.default.clip_gradient) == (
        opt["lr"], opt["beta1"], opt["beta2"], opt["eps"], opt["wd"],
        opt["clip_global_norm"])
    assert cfg.default.warmup_step == 0 and cfg.default.e2e_lr_step == ""
    assert (cfg.train.batch_images, cfg.train.seq_len, cfg.train.shuffle) == (
        2, 8192, False)
    assert cfg.default.frequent == 4


# ---- the cell's files and readers ---------------------------------------------------

def test_the_cells_limits_each_have_a_reason():
    check = bench_run.load_cell(CELL)["check"]
    assert set(check["limits"]) == set(check["reasons"]) >= {
        "loss_s1", "loss_s2", "grad_worst", "scan_grad_worst",
        "latent_grad_worst", "first_delta_worst", "routing_diff",
        "moe_overflow"}
    assert check["limits"]["moe_overflow"] == 0
    assert all(len(r) > 40 for r in check["reasons"].values())


# PR 38's six readers, as the manifest has them at 42..47, and PR 40's three
PR38 = ["kda.device_ms", "kda_scan.device_ms", "mla.device_ms",
        "kda_roofline", "mla_roofline", "kda.chunk_log_decay_min"]
NEW = PR38 + ["kda_solve.device_ms", "kda_scores.device_ms",
              "dense_mlp.device_ms"]
# the sequence readers of the third cell whose scopes and counters this
# stack names letter for letter: they list both cells since PR 40
SHARED = ["moe.device_ms", "moe_route.device_ms", "moe_experts.device_ms",
          "moe_grouped.device_ms", "moe_roofline", "lm_head.device_ms",
          "optimizer.device_ms", "moe.assignments_per_token",
          "moe.load_max_over_mean", "moe.overflow"]


def _entry(bench, name):
    return next(m for m in bench["per_layer"] if m["name"] == name)


def readers_list_the_cell(bench):
    """This cell's own readers list it first; the ten it shares with the
    third cell list that cell, then this one.  A later cell that names the
    same scope comes after them."""
    for name in NEW:
        entry = _entry(bench, name)
        assert entry["workloads"][:1] == [CELL], name
        assert entry["moves"] == "train_imgs_per_s", name
    for name in SHARED:
        assert _entry(bench, name)["workloads"][:2] == [LM_CELL, CELL], name


@pytest.mark.parametrize("name", NEW)
def test_new_reader_lists_the_cell_and_returns_nothing_without_its_source(
        name):
    """A program without the scope or the counter gives the reader nothing
    to read: it returns nothing and does not raise."""
    entry = _entry(bench_run.manifest(), name)
    assert entry["workloads"][:1] == [CELL]
    ctx = {"trace": None, "counters": {}, "layers": [], "peak": {},
           "images_per_step": 2, "chips": 1}
    assert bench_run.read_metric(name, ctx) is None


def _ops_of_the_stored_step(path):
    """``trace.load`` for a CPU trace, which has no device line: the step
    program the profiler stored (the one with most instructions under
    ``kda_mixer``), every instruction a microsecond, one after another,
    three executions.  The names and name paths are the program's own; the
    times are nobody's."""
    from benchmark import xplane

    step = max(xplane.read_hlo_programs(path), key=lambda names: sum(
        "kda_mixer" in v for v in names.values()))
    rows = sorted(step.items())
    span = 1e3 * len(rows)
    return {"devices": [{
        "name": "/device:TPU:0",
        "ops": [[name, path, k * span + 1e3 * i, 1e3] for k in range(3)
                for i, (name, path) in enumerate(rows)],
        "programs": [["jit_step", k * span, span] for k in range(3)]}]}


@pytest.fixture(scope="module")
def traced_metrics(reference_once):
    """The per-layer metrics of the tiny cell's traced run, under the
    chip's peaks (a CPU has none in ``peaks.json``)."""
    cached = jax.config.jax_enable_compilation_cache
    # compiled here and now: a CPU executable read back from the persistent
    # cache has lost the name stacks of its instructions
    jax.config.update("jax_enable_compilation_cache", False)
    real, trace_mod.load = trace_mod.load, _ops_of_the_stored_step
    try:
        cell = tiny_ling_cell()
        result = ling_train.run_cell(cell, seed=SEED, seconds=0.3, trace=True,
                                     t_start=time.perf_counter())
    finally:
        trace_mod.load = real
        jax.config.update("jax_enable_compilation_cache", cached)
    assert result["correct"], result["numbers"]
    result["device"] = dict(result["device"], kind="TPU v5 lite")
    got = bench_run.metrics_of(result, cell, bench_run.manifest(), True)
    return {k: v["value"] for k, v in got.items()}


@pytest.mark.parametrize("name", SHARED + NEW)
def test_reader_reads_the_tiny_programs_scope_or_counter(traced_metrics,
                                                         name):
    """Every scope and counter the cell's listed readers read is one the
    program names: on the traced tiny run each gives a number."""
    value = traced_metrics[name]
    if name == "moe.overflow":
        assert value == 0.0
    elif name == "kda.chunk_log_decay_min":
        assert -80.0 < value < 0.0
    else:
        assert value > 0.0, name


def test_shared_readers_list_both_sequence_cells():
    readers_list_the_cell(bench_run.manifest())


def test_counter_reader_reads_the_log_events_counter():
    ctx = {"counters": {"kda_chunk_log_decay_min": -3.25}}
    assert bench_run.read_metric("kda.chunk_log_decay_min", ctx) == -3.25


def accepted_entries_come_first(bench):
    """The manifest's accepted entries come first, in order: PR 38's
    configuration and cell fourth, its six readers at 42..47.  How many
    entries follow is the next PR's to say (PERF.md section 3)."""
    assert [c["name"] for c in bench["configs"]][:3] == [
        "r101-coco", "vgg16-voc07", "nemotron3-nano-9l-ep16"]
    assert bench["configs"][3]["name"] == "ling3-flash-6l-ep64"
    assert [w["name"] for w in bench["workloads"]][3] == CELL
    assert bench["workloads"][3]["chips"] == 1
    assert bench["workloads"][3]["traffic"] == bench["workloads"][2]["traffic"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[42:48] == PR38
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
    are there under the names ``run.py`` finds them by, the manifest's
    accepted entries come first and in order, and the cell takes the
    traffic file the accepted sequence cell has."""
    accepted_entries_come_first(bench_run.manifest())
    for path in ["configs/ling3-flash-6l-ep64.json", f"workloads/{CELL}.json",
                 "families/ling_flash.py", "drivers/ling_train.py",
                 "reference/ling_flash.py", "reference/ling_compare.py",
                 "ling_readings.py"] + [
                     f"metrics/{n}.py" for n in NEW]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path
    cell = bench_run.load_cell(CELL)
    assert cell["driver"] == "ling_train"
    assert cell["config"]["network"]["family"] == "ling_flash"
