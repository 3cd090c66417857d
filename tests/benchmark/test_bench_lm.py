"""The ``lm_train`` cell on the CPU at a size a test run can hold
(``bench_tiny_lm.py``: the chip's own driver, reference and comparison on
the cell's own files, cut to the program's tiny preset, float32): a sound
run is ``correct`` under the cell's own limits, each fault planted under
the timed path or in the reference's place is not, nor is the float8
control; the family's layer table against a hand count and the compiler's
count; the configuration's file against the catalog's keys and the
program's preset.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny_lm import tiny_lm_cell
from benchmark import flops, lm_readings
from benchmark import run as bench_run
from benchmark.drivers import lm_train
from benchmark.reference import lm, lm_compare

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "nemotron3-nano-9l-ep16.train-8k"
SEED = 2147483659


def _run(monkeypatch=None, plant=None):
    if plant is not None:
        plant(monkeypatch)
    return lm_train.run_cell(tiny_lm_cell(), seed=SEED, seconds=0.3,
                             trace=False, t_start=time.perf_counter())


@pytest.fixture(scope="module")
def reference_once():
    """The plain reference of one (cell, seed) is the same for every run of
    this module: compute it once."""
    real, memo = lm.reference_steps, {}

    def cached(net, opt, params, batches, **kw):
        key = tuple(sorted(kw.items()))
        if key not in memo:
            memo[key] = real(net, opt, params, batches, **kw)
        return memo[key]

    lm.reference_steps = cached
    yield memo
    lm.reference_steps = real


@pytest.fixture(scope="module")
def sound(reference_once):
    return _run()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["numbers"]
    assert sound["attempted"] >= 2 and sound["failed"] == 0
    assert sound["end_to_end"]["train_imgs_per_s"] > 0
    for name, row in sound["numbers"].items():
        assert row["value"] <= 0.1 * row["limit"], sound["numbers"]
    # every leaf the program trains is held, bar the state-space vectors
    # (far under the median leaf: ``scan_grad_worst`` holds them instead)
    left = sound["notes"]["left_out"]
    assert all(k.split("/")[-1] in lm.SCAN_LEAVES for k in left), left
    assert set(lm_train.COUNTERS) <= set(sound["counters"])
    assert sound["counters"]["moe_overflow"] == 0.0


# ---- faults under the timed path ------------------------------------------------

def _no_routed(mp):
    from mx_rcnn_tpu.ops import moe
    mp.setattr(moe, "held_experts",
               lambda x, routed, up, down: jnp.zeros(x.shape, jnp.float32))


def _no_shared(mp):
    from mx_rcnn_tpu.ops import moe
    mp.setattr(moe, "relu2_ffn",
               lambda x, up, down: jnp.zeros(x.shape, jnp.float32))


def _route_with(**change):
    def plant(mp):
        from mx_rcnn_tpu.ops import moe
        real = moe.route

        def route(x, w, bias, top_k, scale, norm_topk):
            kw = dict(scale=scale, norm_topk=norm_topk)
            kw.update(change)
            return real(x, w, bias, top_k, kw["scale"], kw["norm_topk"])

        mp.setattr(moe, "route", route)
    return plant


def _no_decay(mp):
    from mx_rcnn_tpu.models import nemotron_h
    real = nemotron_h.ssd_scan
    mp.setattr(nemotron_h, "ssd_scan",
               lambda x, dt, a, b, c, chunk: real(x, dt, 0.0 * a, b, c, chunk))


def _no_carry(mp):
    """Every chunk scanned as a sequence of its own."""
    from mx_rcnn_tpu.models import nemotron_h
    real = nemotron_h.ssd_scan

    def cut(x, dt, a, b, c, chunk):
        split = lambda t: t.reshape((-1, chunk) + t.shape[2:])  # noqa: E731
        return real(split(x), split(dt), a, split(b), split(c),
                    chunk).reshape(x.shape)

    mp.setattr(nemotron_h, "ssd_scan", cut)


@pytest.mark.parametrize("plant,caught_by", [
    (_no_routed, "grad_worst"), (_no_shared, "grad_worst"),
    (_route_with(scale=1.0), "grad_worst"),
    (_route_with(norm_topk=False), "first_delta_worst"),
    (_no_decay, "scan_grad_worst"), (_no_carry, "scan_grad_worst"),
], ids=["held_experts_left_out", "shared_expert_left_out", "scaling_dropped",
        "renormalisation_dropped", "decay_replaced_by_1",
        "state_not_carried_across_chunks"])
def test_fault_under_the_timed_path_is_not_correct(
        monkeypatch, reference_once, plant, caught_by):
    result = _run(monkeypatch, plant)
    assert not result["correct"], result["numbers"]
    row = result["numbers"][caught_by]
    assert row["value"] > row["limit"], result["numbers"]


def test_planted_in_the_reference_each_fault_and_the_control_fail():
    """What ``lm_readings.py`` reads on the chip: the reference with a
    fault or in float8, in the program's place, against itself plain."""
    row = lm_readings.planted_rows(tiny_lm_cell(), SEED,
                                   ["float8"] + list(lm.FAULTS))
    for tag in ["float8"] + list(lm.FAULTS):
        assert not row[tag]["correct"], (tag, row[tag]["all"])


def test_an_overflow_is_not_correct():
    leaves = (("w",), ("b0", "mixer", "A_log"))
    ref = {"losses": [1.0, 1.0], "grad_norm": {k: 1.0 for k in leaves},
           "first_delta_norm": {k: 1.0 for k in leaves},
           "scan_grad": {leaves[1]: [1.0, 2.0]},
           "counts": [[3, 4]]}
    program = dict(ref, overflow=1.0)
    limits = bench_run.load_cell(CELL)["check"]["limits"]
    ok, numbers, _ = lm_compare.compare_lm(program, ref, limits)
    assert not ok and numbers["moe_overflow"]["value"] == 1.0
    ok, _, _ = lm_compare.compare_lm(dict(ref, overflow=0.0), ref, limits)
    assert ok


def test_a_program_without_the_family_fails_the_cell_cleanly():
    cell = tiny_lm_cell()
    cell["config"]["program"]["network"] = "no_such_family"
    with pytest.raises(lm_train.CellFailure):
        lm_train.run(cell, seed=1, seconds=1, trace=False,
                     t_start=time.perf_counter())


# ---- the layer table ------------------------------------------------------------

def test_layer_table_hand_count():
    cell = bench_run.load_cell(CELL)
    rows = flops.layer_table(cell["config"], cell["traffic"])
    per_token = flops.step_flops_per_image(rows) / 8192
    # forward multiply-adds a token, by hand from the published widths:
    # M 4 x (2688 x 10304 + 4096 x 2688), * 23.4 M + 34.4 M of scores,
    # E 4 x (router 0.34 M + shared 19.96 M + 0.375 x 9.98 M), head 44.0 M
    mamba = 4 * (2688 * 10304 + 4096 * 2688)
    attn = 2688 * (4096 + 2 * 256) + 4096 * 2688 + 32 * 128 * 8193
    moe = 4 * (2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856)
    head = 2688 * 16384
    macs = mamba + attn + moe + head
    # the scan (3.4 MFLOP a token a block) and the pointwise rows add 3 %;
    # the head and the loss run on 8191 of 8192 positions
    assert 1.0 < per_token / (3 * 2 * macs) < 1.05, per_token
    assert 2.1e9 < per_token < 2.3e9
    by_scope = {s: flops.step_flops_per_image(rows, s) for s in
                ("ssm_mixer", "attention", "moe", "lm_head", "embed")}
    assert by_scope["embed"] == 0
    share = {k: v / sum(by_scope.values()) for k, v in by_scope.items()}
    assert 0.42 < share["ssm_mixer"] < 0.48 and 0.24 < share["moe"] < 0.30
    scan = [r for r in rows if r["name"].endswith(".scan")]
    assert len(scan) == 4 and scan[0]["flops"] == 2.0 * (
        128 * 128 * 8 + 128 * 4096 + 2 * 4096 * 128)
    mod = flops.family(cell["config"]["network"])
    assert set(r["scope"] for r in rows) <= set(mod.STAGES)


def test_counts_against_cost_analysis_of_a_small_forward():
    """The table's forward operations for the tiny configuration against
    what the compiler counts for the reference's forward of one sequence
    (its held experts run on every token: the table is asked for the same).
    """
    cell = tiny_lm_cell()
    config, traffic = cell["config"], dict(cell["traffic"], seq_len=64)
    rows = flops.layer_table(config, traffic)
    held_rows = 64 * 2 * 2 / 8
    want = 0.0
    for r in rows:
        times = r["times"]
        if "experts_" in r["name"]:
            times = 64 * 2         # the dense-mask loop: every token, 2 held
            assert abs(r["times"] - held_rows) < 1e-9
        if r["name"].endswith(".scan"):
            continue               # the reference runs the recurrence instead
        want += flops.forward_flops(r) * times
    params = lm.make_weights(config, 1)
    ids = jnp.zeros((64,), jnp.int32)
    got = jax.jit(lambda p: lm.sequence_loss(config, p, ids)[0]).lower(
        params).compile().cost_analysis()["flops"]
    # the recurrence: 5 operations a state element a step
    got -= 64 * 5 * 8 * 16 * 16
    assert 0.9 < got / want < 1.15, (got, want)


# ---- the configuration's file ------------------------------------------------------

def test_config_file_keeps_the_catalogs_keys():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3-nano-9l-ep16.json")) as f:
        config = json.load(f)
    published = {  # the widths, as the model's config.json has them
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "intermediate_size": 1856,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "num_experts_per_tok": 6,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "max_position_embeddings": 262144, "rope_theta": 10000}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    assert config["published"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert lm.pattern(config) == "MEMEM*EME"
    assert config["source"].startswith("https://huggingface.co/nvidia/")
    assert config["assumed"] and config["deployment"]
    # 667 M parameters, 10.7 GB at 16 bytes
    n = sum(int(jnp.prod(jnp.array(shape))) for _, shape, _ in
            lm.param_rows(config))
    assert 666e6 < n < 668e6


def test_config_file_states_what_the_program_runs():
    cell = bench_run.load_cell(CELL)
    config = cell["config"]
    cfg = lm_train.program_config(config, cell["traffic"], False)
    n = cfg.network
    assert n.layer_pattern == lm.pattern(config)
    assert tuple(n.experts_held) == lm.held(config)
    got = {"hidden_size": n.hidden_size, "vocab_size": n.vocab_size,
           "mamba_num_heads": n.mamba_num_heads,
           "mamba_head_dim": n.mamba_head_dim,
           "ssm_state_size": n.ssm_state_size, "n_groups": n.ssm_groups,
           "conv_kernel": n.conv_kernel, "chunk_size": n.chunk_size,
           "num_attention_heads": n.num_attention_heads,
           "num_key_value_heads": n.num_key_value_heads,
           "head_dim": n.head_dim,
           "num_experts_per_tok": n.num_experts_per_tok,
           "moe_intermediate_size": n.moe_intermediate_size,
           "moe_shared_expert_intermediate_size":
               n.moe_shared_expert_intermediate_size,
           "routed_scaling_factor": n.routed_scaling_factor,
           "norm_topk_prob": n.norm_topk_prob,
           "layer_norm_epsilon": n.norm_eps}
    for key, value in got.items():
        assert config[key] == value, key
    assert n.n_routed_experts == config["published"]["n_routed_experts"]
    assert n.init_layers == config["published"]["num_hidden_layers"]
    assert n.compute_dtype == config["network"]["compute_dtype"]
    assert n.moe_capacity_factor == config["network"]["moe_capacity_factor"]
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
        "first_delta_worst", "routing_diff", "moe_overflow"}
    assert check["limits"]["moe_overflow"] == 0


NEW = ["ssm.device_ms", "ssd_scan.device_ms", "attention.device_ms",
       "moe.device_ms", "moe_route.device_ms", "moe_experts.device_ms",
       "lm_head.device_ms", "optimizer.device_ms", "ssm_roofline",
       "moe_roofline", "moe.assignments_per_token", "moe.load_max_over_mean",
       "moe.overflow"]


def reader_lists_the_cell(bench, name):
    """The cell comes first in the reader's list; a later cell whose
    program names the same scope or counter follows it (PERF.md section
    3)."""
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"][:1] == [CELL], name
    assert entry["moves"] == "train_imgs_per_s", name


def readers_list_the_cell(bench):
    for name in NEW:
        reader_lists_the_cell(bench, name)


# what ``test_bench_family_seam.py`` runs against a manifest with a further
# family's entries appended: each takes the manifest
MANIFEST_CHECKS = [readers_list_the_cell]


@pytest.mark.parametrize("name", NEW)
def test_new_reader_lists_the_cell_and_returns_nothing_without_its_source(
        name):
    """A program without the scope or the counter (the parent) gives the
    reader nothing to read: it returns nothing and does not raise."""
    reader_lists_the_cell(bench_run.manifest(), name)
    ctx = {"trace": None, "counters": {}, "layers": [], "peak": {},
           "images_per_step": 2, "chips": 1}
    assert bench_run.read_metric(name, ctx) is None


def test_counter_readers_read_the_log_events_counters():
    ctx = {"counters": {"moe_assignments_per_token": 0.37,
                        "moe_load_max_over_mean": 1.2, "moe_overflow": 0.0}}
    assert bench_run.read_metric("moe.assignments_per_token", ctx) == 0.37
    assert bench_run.read_metric("moe.load_max_over_mean", ctx) == 1.2
    assert bench_run.read_metric("moe.overflow", ctx) == 0.0


def test_traffic_is_the_seeds_and_nothing_elses():
    from benchmark import lm_traffic

    traffic = bench_run.load_cell(CELL)["traffic"]
    a = lm_traffic.make_sequences(traffic, SEED % (2 ** 31 - 1), 16384, 4)
    b = lm_traffic.make_sequences(traffic, SEED % (2 ** 31 - 1), 16384, 4)
    assert a.shape == (4, 8192) and (a == b).all() and a.max() < 16384
    source = lm_traffic.token_source(a, 10)
    assert (source[5] == a[1]).all()
    batches = lm_traffic.reference_batches(a, 2, 3)
    assert (batches[2] == a[[0, 1]]).all() and (batches[1] == a[[2, 3]]).all()
