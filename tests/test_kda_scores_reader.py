"""``kda_scores.kernel_ms`` (``benchmark/metrics/kda_scores.kernel_ms.py``):
the device milliseconds a step of the delta rule's scores kernels, read from
a hand-made trace by op name, and the manifest's entry for it.  These live
outside ``tests/benchmark`` because a PR that changes the program may not
edit the benchmark's accepted test files: the entry is checked only for
what PERF.md section 3's rule allows, that the accepted entries come first.
"""

import pytest

from benchmark import run as bench_run
from benchmark import trace

CELL = "ling3-flash-6l-ep64.train-8k"


def _reduced(ops, steps=2, chips=1):
    """One device a chip, each with the ops (name, start, duration) in a
    window of ``steps`` executions of the step program."""
    programs = [["jit_step(1)", 1e7 * i, 1.0] for i in range(steps + 1)]
    devices = [{"name": f"d{c}", "programs": programs,
                "ops": [[name, "jit(step)/x", start, dur]
                        for name, start, dur in ops]}
               for c in range(chips)]
    return trace.Reduced({"devices": devices}, steps=steps, chips=chips)


@pytest.mark.parametrize("ops,want", [
    pytest.param([("kda_scores_fwd", 0.0, 4e5), ("kda_scores_bwd", 1e6, 6e5),
                  ("fusion.3", 5e5, 9e5)], 0.5, id="both_kernels"),
    pytest.param([("kda_scores_fwd.2", 0.0, 4e5), ("kda_scores_fwd.3", 1e6,
                                                   4e5),
                  ("kda_scores_bwd.1", 2e6, 1.2e6)], 1.0,
                 id="numbered_by_the_compiler"),
    pytest.param([("kda_scores_fwd", 0.0, 4e5), ("kda_scores_bwd", 1e5, 4e5)],
                 0.25, id="overlap_counted_once"),
    pytest.param([("kda_inv_unit_lower", 0.0, 4e5),
                  ("fusion.kda_scores_fwd.1", 0.0, 4e5),
                  ("copy.kda_scores_bwd", 0.0, 4e5)], None,
                 id="other_names_are_no_kernel"),
])
def test_kernel_reader_sums_the_scores_kernels_per_step(ops, want):
    """Starts and durations in ns, in a window of two steps of 10 ms: the
    union of the kernels' intervals in ms a step."""
    got = bench_run.read_metric("kda_scores.kernel_ms",
                                {"trace": _reduced(ops)})
    assert got == (None if want is None else pytest.approx(want))


def test_kernel_reader_is_the_mean_over_chips():
    ops = [("kda_scores_fwd", 0.0, 2e6)]
    assert bench_run.read_metric(
        "kda_scores.kernel_ms",
        {"trace": _reduced(ops, steps=4, chips=2)}) == pytest.approx(0.5)


def test_kernel_reader_reads_nothing_without_a_trace():
    assert bench_run.read_metric("kda_scores.kernel_ms", {"trace": None}) \
        is None


def test_kernel_reader_follows_the_accepted_entries_and_lists_the_cell():
    """The 65th ``per_layer`` entry, in the kernels' layer, moving the rate
    and listing the fourth cell; how many entries follow is a later PR's."""
    bench = bench_run.manifest()
    entry = bench["per_layer"][64]
    assert entry["name"] == "kda_scores.kernel_ms"
    assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]) \
        == ("kernels", "train_imgs_per_s", "device_trace", "ms")
    assert entry["workloads"][:1] == [CELL]
    assert CELL in [w["name"] for w in bench["workloads"]]
