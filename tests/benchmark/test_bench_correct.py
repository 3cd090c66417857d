"""``correct`` on the CPU at a size a test run can hold: a sound run of the
whole harness reads true, each fault planted under the timed path reads
false, and so does the lower-precision control (``test_bench_control.py``:
a file of its own, so that a run with several workers gives it to another
worker than this file's eight minutes).

The harness under test is the chip's own (``benchmark/drivers/train.py``
with its look for a chip skipped) on the cell's own files cut to a 96x128
bucket (``bench_tiny.py``), held to the cell's own limits.  The program
runs in float32 here so that a sound run sits far under those limits and
what fails them is the fault, not CPU-sized noise.
"""

import time

import pytest

from bench_tiny import tiny_cell
from benchmark.drivers import train as driver
from benchmark.reference import step as ref_step

SEED = 11


def _cell(name):
    cell = tiny_cell(name)
    cell["config"]["program"]["overrides"].update({
        "network__compute_dtype": "float32",
        "default__momentum_dtype": "float32"})
    return cell


@pytest.fixture(scope="module")
def reference_once():
    """The reference of one (cell, seed) is the same for every run of this
    module: compute it once."""
    real = ref_step.reference_steps
    memo = {}

    def cached(net, tr, opt, params, batches, seed, **kw):
        key = (net["family"], seed, tuple(sorted(kw.items())))
        if key not in memo:
            memo[key] = real(net, tr, opt, params, batches, seed, **kw)
        return memo[key]

    ref_step.reference_steps = cached
    yield memo
    ref_step.reference_steps = real


def _run(cell, change=None):
    """The harness from below its look for a chip; ``change(step_fn) ->
    step_fn`` is planted under the fit loop for the run: the program builds
    its jitted step from ``core.fit.make_train_step``."""
    from mx_rcnn_tpu.core import fit as fit_mod

    real = fit_mod.make_train_step
    if change is not None:
        fit_mod.make_train_step = lambda *a, **kw: change(real(*a, **kw))
    try:
        return driver.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                               t_start=time.perf_counter())
    finally:
        fit_mod.make_train_step = real


def _unchanged(step):
    def broken(state, batch, key):
        _, metrics = step(state, batch, key)
        return state, metrics
    return broken


def _half_batch(step):
    def broken(state, batch, key):
        import jax

        half = batch.images.shape[0] // 2
        return step(state, jax.tree.map(lambda x: x[:half], batch), key)
    return broken


def _frozen_branches(step):
    """conv1 and conv2 of every residual unit left unmoved: their weights
    and their momentum come back as they went in."""
    def broken(state, batch, key):
        import jax

        new, metrics = step(state, batch, key)

        def keep_old(path, fresh, old):
            names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            return old if {"conv1", "conv2"} & set(names) else fresh

        return new._replace(
            params=jax.tree_util.tree_map_with_path(
                keep_old, new.params, state.params),
            opt_state=jax.tree_util.tree_map_with_path(
                keep_old, new.opt_state, state.opt_state)), metrics
    return broken


def _altered_loss(step):
    def broken(state, batch, key):
        state, metrics = step(state, batch, key)
        return state, {k: v * 1.5 for k, v in metrics.items()}
    return broken


def test_sound_run_is_correct(reference_once):
    result = _run(_cell("r101-coco.train"))
    assert result["correct"], result["numbers"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["window"]["imgs_per_s"] > 0
    for row in result["numbers"].values():
        assert row["value"] < 0.1 * row["limit"], result["numbers"]
    # every kernel is held: the thousandth rule leaves out biases alone
    notes = result["notes"]
    assert notes["leaves_left_out"] == len(notes["left_out"]) <= 6, notes
    assert all(name.endswith("/bias") for name in notes["left_out"]), notes
    assert notes["delta_leaves_left_out"] <= notes["leaves_left_out"], notes


@pytest.mark.parametrize("change,caught_by", [
    (_unchanged, "first_delta_worst"),
    (_half_batch, "rpn_loss_s1"),
    (_altered_loss, "rpn_loss_s1"),
    (_frozen_branches, "grad_worst"),
    (_frozen_branches, "delta_worst"),
], ids=["state_unchanged", "half_batch_left_out", "loss_altered",
        "residual_branches_frozen", "residual_branches_frozen_two_steps"])
def test_fault_under_the_timed_path_is_not_correct(reference_once, change,
                                                   caught_by):
    result = _run(_cell("r101-coco.train"), change)
    assert not result["correct"], result["numbers"]
    row = result["numbers"][caught_by]
    assert row["value"] > row["limit"], result["numbers"]
