"""The ``joyai_train`` cell on the CPU at a size a test run can hold
(``bench_tiny_joyai.py``: the chip's own driver, reference and comparison on
the cell's own files, cut to the program's tiny preset, float32): a sound
run is ``correct`` under the cell's own limits and each fault that can be
planted under the timed path is not (``test_bench_joyai_control.py`` plants
every fault and the float8 control in the reference's place); the family's
layer table against a hand count, the model's parameters and the compiler's
count; the configuration's file against the catalog's row and the program's
preset; the cell's files are new files beside the accepted ones; every
reader that lists the cell reads its program's scopes and counters.
"""

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny_joyai import CELL, tiny_joyai_cell
from benchmark import flops, hostspans
from benchmark import run as bench_run
from benchmark import trace as trace_mod
from benchmark.drivers import joyai_train
from benchmark.reference import joyai_flash as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM_CELL = "nemotron3-nano-9l-ep16.train-8k"
LING_CELL = "ling3-flash-6l-ep64.train-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2147483659


def _run(monkeypatch=None, plant=None, overrides=None):
    cell = tiny_joyai_cell()
    cell["config"]["program"]["overrides"].update(overrides or {})
    if plant is not None:
        plant(monkeypatch)
    return joyai_train.run_cell(cell, seed=SEED, seconds=0.3, trace=False,
                                t_start=time.perf_counter())


@pytest.fixture(autouse=True)
def _leave_no_spans():
    """The traced run leaves its spans in the process-wide buffer, which
    later test files of the same worker read (``test_bench_harness.py``'s
    span readers have to find nothing)."""
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
        # the module's two masked positions a sequence are routed: 8 of the
        # tiny run's ~700 assignments
        part = 1.0 if name == "routing_diff" else 0.1
        assert row["value"] <= part * row["limit"], sound["numbers"]
    # every matrix the program trains is held; the norm scales that a
    # softmax makes nearly scale-free are the vectors' numbers'
    left = sound["notes"]["left_out"]
    assert all(k.split("/")[-1] in ref.SCAN_LEAVES + ("norm",)
               for k in left), left
    # q_a_norm, kv_a_norm of three layers and the module; its three norms
    # and the final norm; a slice of the embedding and of the head
    assert len(sound["notes"]["latent_grad_worst_leaves"]) == 8
    assert len(sound["notes"]["mtp_grad_worst_leaves"]) == 3
    assert list(sound["notes"]["final_norm_grad_leaves"]) == ["final_norm"]
    assert set(sound["notes"]["shared_grad_worst_leaves"]) == {"embed",
                                                               "head"}
    assert "scan_grad_worst" not in sound["notes"]["all"]
    json.dumps(sound["notes"], allow_nan=False)
    assert set(joyai_train.COUNTERS) <= set(sound["counters"])
    assert sound["counters"]["moe_overflow"] == 0.0
    assert 4.5 < sound["counters"]["mtp_loss"] < 6.0


# ---- faults under the timed path ------------------------------------------------

def _shift_one(mp):
    from mx_rcnn_tpu.models import joyai_flash
    real = joyai_flash.shifted_loss
    mp.setattr(joyai_flash, "shifted_loss",
               lambda h, w, ids, shift, chunk: real(h, w, ids, 1, chunk))


def _route_without_scale(mp):
    from mx_rcnn_tpu.ops import moe
    real = moe.route
    mp.setattr(moe, "route",
               lambda x, w, bias, top_k, scale, norm_topk, groups=None: real(
                   x, w, bias, top_k, 1.0, norm_topk, groups))


def _no_shared(mp):
    """The shared expert is the one SwiGLU of the experts' width."""
    from mx_rcnn_tpu.ops import moe
    real = moe.swiglu_ffn
    mp.setattr(moe, "swiglu_ffn", lambda x, gate, up, down: (
        jnp.zeros(x.shape, jnp.float32) if gate.shape[1] == 32
        else real(x, gate, up, down)))


def _rotary_with(change):
    def plant(mp):
        from mx_rcnn_tpu.models import ling_flash
        mp.setattr(ling_flash, "rotary_interleaved", change)
    return plant


def ling_flash_rotary_by_halves(x, theta):
    from mx_rcnn_tpu.models import ling_flash
    return ling_flash.rotary(x, theta)


def _no_q_norm(mp):
    """The low-rank query is the one array of its width that is
    normalised."""
    from mx_rcnn_tpu.models import ling_flash
    real = ling_flash.rms_norm
    mp.setattr(ling_flash, "rms_norm", lambda x, scale, eps: (
        x if x.shape[-1] == 40 else real(x, scale, eps)))


@pytest.mark.parametrize("plant,overrides,caught_by", [
    (_shift_one, None, "mtp_grad_worst"),
    (None, {"network__mtp_loss_weight": 0.0}, "loss_s1"),
    (None, {"network__mtp_loss_weight": 1.0}, "loss_s1"),
    (_no_q_norm, None, "latent_grad_worst"),
    (_rotary_with(ling_flash_rotary_by_halves), None, "latent_grad_worst"),
    (_rotary_with(lambda x, theta: x), None, "latent_grad_worst"),
    (_route_without_scale, None, "grad_worst"),
    (_no_shared, None, "grad_worst"),
], ids=["targets_shifted_by_one", "module_weight_0", "module_weight_1",
        "query_norm_dropped", "rotary_paired_by_halves",
        "rotary_term_dropped", "scaling_dropped", "shared_expert_left_out"])
def test_fault_under_the_timed_path_is_not_correct(
        monkeypatch, reference_once, plant, overrides, caught_by):
    result = _run(monkeypatch, plant, overrides)
    assert not result["correct"], result["numbers"]
    row = result["numbers"][caught_by]
    assert row["value"] > row["limit"], result["numbers"]


def test_a_program_without_the_family_fails_the_cell_cleanly():
    cell = tiny_joyai_cell()
    cell["config"]["program"]["network"] = "no_such_family"
    with pytest.raises(joyai_train.CellFailure):
        joyai_train.run(cell, seed=1, seconds=1, trace=False,
                        t_start=time.perf_counter())


# ---- the layer table ------------------------------------------------------------

def test_layer_table_hand_count():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    rows = flops.layer_table(config, cell["traffic"])
    # the parameters: the table's rows own what the model holds, 680.4 M
    n_model = sum(int(jnp.prod(jnp.array(shape))) for _, shape, _ in
                  ref.param_rows(config))
    assert sum(r["params"] for r in rows) == n_model
    assert 680.3e6 < n_model < 680.5e6
    # a latent block's rows, by the equations: the query in two products
    # with a norm between them, no head norm, no gate
    block = [r["name"].split(".", 1)[1] for r in rows
             if r["name"].startswith("l1.") and r["scope"] == "mla"]
    assert block == ["mix_norm", "q_a_proj", "q_a_norm", "q_b_proj",
                     "kv_a_proj", "kv_a_norm", "kv_b_proj", "rotary",
                     "scores", "o_proj"]
    by_name = {r["name"]: r for r in rows}
    assert by_name["l1.q_a_proj"]["flops"] == 2.0 * 2048 * 1536
    assert by_name["l1.q_b_proj"]["flops"] == 2.0 * 1536 * 32 * 192
    assert by_name["l1.kv_a_proj"]["flops"] == 2.0 * 2048 * 576
    assert by_name["l1.kv_b_proj"]["flops"] == 2.0 * 512 * 32 * 256
    assert by_name["l1.scores"]["flops"] == 2.0 * 32 * 320 * 8193 / 2
    assert by_name["l1.o_proj"]["flops"] == 2.0 * 4096 * 2048
    # six blocks: five layers on S positions, the module's on S - 2
    mixers = [r for r in rows if r["name"].endswith(".scores")]
    assert [r["times"] for r in mixers] == [8192] * 5 + [8190]
    assert by_name["mtp.scores"]["flops"] == 2.0 * 32 * 320 * 8191 / 2
    # the head twice: S - 1 positions, then S - 2 under the module's scope
    assert (by_name["head"]["times"], by_name["head"]["scope"]) == (
        8191, "lm_head")
    assert (by_name["mtp.head"]["times"], by_name["mtp.head"]["scope"],
            by_name["mtp.head"]["params"]) == (8190, "mtp_head", 0)
    assert by_name["mtp.eh_proj"]["flops"] == 2.0 * 4096 * 2048
    # forward multiply-adds a token, by hand from the published widths
    mla = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
           + 4096 * 2048 + 32 * 320 * 8193 / 2)
    expert_layer = 2048 * 256 + 1.5 * 3 * 2048 * 768
    macs = (6 * mla + 3 * 2048 * 7168 + 4 * expert_layer + 2048 * 16160
            + (4096 * 2048 + expert_layer + 2048 * 16160))
    per_token = flops.step_flops_per_image(rows) / 8192
    # the pointwise rows add 1 %; the heads, the module run short of S
    assert 0.995 < per_token / (3 * 2 * macs) < 1.02, per_token / (6 * macs)
    by_scope = {s: flops.step_flops_per_image(rows, s) for s in
                ("embed", "mla", "dense_mlp", "moe", "lm_head",
                 "mtp_combine", "mtp_head")}
    assert by_scope["embed"] == 0
    share = {k: v / sum(by_scope.values()) for k, v in by_scope.items()}
    assert 0.70 < share["mla"] < 0.75
    assert share["lm_head"] == pytest.approx(share["mtp_head"], rel=1e-3)
    mod = flops.family(config["network"])
    assert set(r["scope"] for r in rows) <= set(mod.STAGES)
    assert "mtp" not in mod.STAGES     # its parts are: no op under two


def test_counts_against_cost_analysis_of_a_small_forward():
    """The table's forward operations for the tiny configuration against
    what the compiler counts for the reference's forward of one sequence.
    The reference runs its held experts one after the other in a scan, on
    every token, and the compiler counts a loop's body once: the table is
    asked for one expert on every token."""
    cell = tiny_joyai_cell()
    config, traffic = cell["config"], dict(cell["traffic"], seq_len=64)
    rows = flops.layer_table(config, traffic)
    want = 0.0
    for r in rows:
        times = r["times"]
        if "experts_" in r["name"]:
            tokens = 62 if r["name"].startswith("mtp.") else 64
            assert abs(r["times"] - tokens * 2 * 4 / 16) < 1e-9
            times = tokens
        want += flops.forward_flops(r) * times
    params = ref.make_weights(config, 1)
    ids = jnp.zeros((64,), jnp.int32)
    got = jax.jit(lambda p: sum(ref.sequence_losses(config, p, ids)[:2])
                  ).lower(params).compile().cost_analysis()["flops"]
    assert 0.9 < got / want < 1.2, (got, want)


# ---- the configuration's file ------------------------------------------------------

def test_config_file_keeps_the_catalogs_row():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-flash-5l-mtp-ep16.json")) as f:
        config = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert config["source"] == row["source_url"]
        assert set(row["config"]) <= set(config)
        differs = [k for k, v in row["config"].items() if config[k] != v]
        assert sorted(differs) == sorted(config["reduced"])
    # the widths, as the model's config.json has them
    published = {
        "hidden_size": 2048, "intermediate_size": 7168, "head_dim": 64,
        "num_attention_heads": 32, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
        "v_head_dim": 128, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": 2.5,
        "rope_theta": 32000000, "rope_interleave": True,
        "rms_norm_eps": 1e-06, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert [config[k] for k in config["reduced"]] == [5, 16, 16160]
    assert config["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 256,
        "vocab_size": 129280}
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert ref.held(config) == (0, 16)
    assert config["assumed"] and "16 chips" in config["deployment"]
    assert config["network"]["mtp_loss_weight"] == 0.3


def test_config_file_states_what_the_program_runs():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    cfg = joyai_train.program_config(config, cell["traffic"], False)
    n = cfg.network
    assert n.layer_pattern == "L" * config["num_hidden_layers"]
    assert tuple(n.experts_held) == ref.held(config)
    got = {"hidden_size": n.hidden_size, "vocab_size": n.vocab_size,
           "first_k_dense_replace": n.first_k_dense_replace,
           "intermediate_size": n.intermediate_size,
           "num_attention_heads": n.num_attention_heads,
           "q_lora_rank": n.q_lora_rank, "kv_lora_rank": n.kv_lora_rank,
           "qk_nope_head_dim": n.qk_nope_head_dim,
           "qk_rope_head_dim": n.qk_rope_head_dim,
           "v_head_dim": n.v_head_dim, "rope_theta": n.rope_theta,
           "rope_interleave": n.rope_interleave,
           "num_experts_per_tok": n.num_experts_per_tok,
           "n_group": n.n_group, "topk_group": n.topk_group,
           "moe_intermediate_size": n.moe_intermediate_size,
           "routed_scaling_factor": n.routed_scaling_factor,
           "norm_topk_prob": n.norm_topk_prob, "rms_norm_eps": n.norm_eps,
           "num_nextn_predict_layers": n.num_nextn_predict_layers}
    for key, value in got.items():
        assert config[key] == value, key
    assert n.moe_shared_expert_intermediate_size == (
        config["n_shared_experts"] * config["moe_intermediate_size"])
    assert n.n_routed_experts == config["published"]["n_routed_experts"]
    assert n.init_layers == config["published"]["num_hidden_layers"]
    assert n.compute_dtype == config["network"]["compute_dtype"]
    assert n.moe_capacity_factor == config["network"]["moe_capacity_factor"]
    assert n.mtp_loss_weight == config["network"]["mtp_loss_weight"]
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
    # the program's leaves are the reference's, shape for shape
    from mx_rcnn_tpu.models import build_model

    shapes = jax.eval_shape(build_model(cfg).init_variables,
                            jax.random.PRNGKey(0))[0]
    assert {k: v.shape for k, v in ref.tree_paths(shapes).items()} == {
        path: shape for path, shape, _ in ref.param_rows(config)}


# ---- the cell's files and readers ---------------------------------------------------

def test_the_cells_limits_each_have_a_reason():
    check = bench_run.load_cell(CELL)["check"]
    assert set(check["limits"]) == set(check["reasons"]) >= {
        "loss_s1", "loss_s2", "mtp_loss_s1", "grad_worst",
        "latent_grad_worst", "mtp_grad_worst", "final_norm_grad",
        "shared_grad_worst", "first_delta_worst",
        "routing_diff", "moe_overflow"}
    assert check["limits"]["moe_overflow"] == 0
    assert all(len(r) > 40 for r in check["reasons"].values())


# this PR's four readers, as the manifest has them at 51..54
NEW = ["mtp.device_ms", "mtp_combine.device_ms", "mtp_head.device_ms",
       "mtp.loss"]
# readers of the fourth cell's own scopes that this stack names too
FROM_LING = ["mla.device_ms", "mla_roofline", "dense_mlp.device_ms"]
# the sequence readers both accepted sequence cells list
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
    for name in FROM_LING:
        assert _entry(bench, name)["workloads"][:2] == [LING_CELL, CELL], name
    for name in SHARED:
        assert _entry(bench, name)["workloads"][:3] == [
            LM_CELL, LING_CELL, CELL], name


@pytest.mark.parametrize("name", NEW)
def test_new_reader_lists_the_cell_and_returns_nothing_without_its_source(
        name):
    """A program without the scope or the counter (the parent's) gives the
    reader nothing to read: it returns nothing and does not raise."""
    entry = _entry(bench_run.manifest(), name)
    assert entry["workloads"][:1] == [CELL]
    ctx = {"trace": None, "counters": {}, "layers": [], "peak": {},
           "images_per_step": 2, "chips": 1}
    assert bench_run.read_metric(name, ctx) is None


def _ops_of_the_stored_step(path):
    """``trace.load`` for a CPU trace, which has no device line: the step
    program the profiler stored (the one with most instructions under
    ``mla``), every instruction a microsecond, one after another, three
    executions.  The names and name paths are the program's own; the times
    are nobody's."""
    from benchmark import xplane

    step = max(xplane.read_hlo_programs(path), key=lambda names: sum(
        "mla" in v for v in names.values()))
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
        cell = tiny_joyai_cell()
        result = joyai_train.run_cell(cell, seed=SEED, seconds=0.3,
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


@pytest.mark.parametrize("name", SHARED + FROM_LING + NEW)
def test_reader_reads_the_tiny_programs_scope_or_counter(traced, name):
    """Every scope and counter the cell's listed readers read is one the
    program names: on the traced tiny run each gives a number."""
    value = traced[0][name]
    if name == "moe.overflow":
        assert value == 0.0
    elif name == "mtp.loss":
        assert 4.5 < value < 6.0
    else:
        assert value > 0.0, name


def test_the_modules_scopes_nest_and_the_stages_account_for_the_step(traced):
    metrics, reduced, stages = traced
    # the module's three parts and its block lie inside it
    assert metrics["mtp.device_ms"] > (metrics["mtp_combine.device_ms"]
                                       + metrics["mtp_head.device_ms"])
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
    assert metrics["step.unscoped_ms"] == pytest.approx(
        1e3 * unscoped / reduced.steps, rel=1e-9)
    # the module's block is read with the stack's blocks, under their scopes
    paths = [p for _, p, _, _ in reduced.devices[0]["ops"]]
    assert any("mtp/" in p and inside["mla"].search(p) for p in paths)
    assert any("mtp/" in p and inside["moe"].search(p) for p in paths)
    assert metrics["mla_roofline"] < 100 and metrics["moe_roofline"] < 100


def test_readers_list_the_cell_after_the_accepted_cells():
    readers_list_the_cell(bench_run.manifest())


def test_counter_reader_reads_the_log_events_counter():
    assert bench_run.read_metric("mtp.loss",
                                 {"counters": {"mtp_loss": 9.5}}) == 9.5


def accepted_entries_come_first(bench):
    """The manifest's accepted entries come first, in order: this PR's
    configuration and cell fifth, its four readers at 51..54.  How many
    entries follow is the next PR's to say (PERF.md section 3)."""
    assert [c["name"] for c in bench["configs"]][:4] == [
        "r101-coco", "vgg16-voc07", "nemotron3-nano-9l-ep16",
        "ling3-flash-6l-ep64"]
    assert bench["configs"][4]["name"] == "joyai-flash-5l-mtp-ep16"
    assert bench["configs"][4]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert [w["name"] for w in bench["workloads"]][4] == CELL
    assert bench["workloads"][4]["chips"] == 1
    assert bench["workloads"][4]["traffic"] == bench["workloads"][3]["traffic"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[48:51] == ["kda_solve.device_ms", "kda_scores.device_ms",
                            "dense_mlp.device_ms"]
    assert names[51:55] == NEW
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
    traffic file the accepted sequence cells have."""
    bench = bench_run.manifest()
    accepted_entries_come_first(bench)
    for path in ["configs/joyai-flash-5l-mtp-ep16.json",
                 f"workloads/{CELL}.json", "families/joyai_flash.py",
                 "drivers/joyai_train.py", "reference/joyai_flash.py",
                 "reference/joyai_compare.py", "joyai_readings.py"] + [
                     f"metrics/{n}.py" for n in NEW]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path
    cell = bench_run.load_cell(CELL)
    assert cell["driver"] == "joyai_train"
    assert cell["config"]["network"]["family"] == "joyai_flash"
    entry = bench["workloads"][4]
    assert (entry["why"], entry["traffic"]) == (cell["why"], "train-2x8192")
    assert len(entry["why"]) <= 200
