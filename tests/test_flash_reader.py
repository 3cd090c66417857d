"""``flash.fwd_per_bwd`` (``benchmark/metrics/flash.fwd_per_bwd.py``): the
flash forward kernel's executions over its backward kernel's, read from a
hand-made trace, and the manifest's entry for it.  These live outside
``tests/benchmark`` because a PR that changes the program may not edit the
benchmark's accepted test files: the entry is checked only for what
PERF.md section 3's rule allows, that the accepted entries come first.
"""

import pytest

from benchmark import run as bench_run
from benchmark import trace

SEQUENCE_CELLS = ["nemotron3-nano-9l-ep16.train-8k",
                  "ling3-flash-6l-ep64.train-8k",
                  "joyai-flash-5l-mtp-ep16.train-8k"]


@pytest.mark.parametrize("names,want", [
    pytest.param(["flash_causal_gqa.2", "flash_causal_gqa.3",
                  "flash_causal_gqa_bwd.1"], 2.0, id="forward_twice"),
    pytest.param(["flash_causal_gqa", "flash_causal_gqa_bwd"], 1.0,
                 id="forward_once"),
    pytest.param(["flash_causal_gqa.2", "fusion.7"], None,
                 id="no_backward"),
    pytest.param(["flash_causal_gqa.3", "flash_causal_gqa_bwd.61",
                  "flash_causal_gqa_bwd.62", "flash_causal_gqa.4",
                  "fusion.flash_causal_gqa.5"], 1.0,
                 id="backward_names_are_no_forwards"),
])
def test_flash_reader_counts_forward_kernels_over_backward(names, want):
    ops = [[name, "jit(step)/x", 10.0 * i, 5.0]
           for i, name in enumerate(names)]
    programs = [["jit_step(1)", 0.0, 1.0], ["jit_step(1)", 100.0, 1.0]]
    red = trace.Reduced({"devices": [{"name": "d", "ops": ops,
                                      "programs": programs}]},
                        steps=1, chips=1)
    assert bench_run.read_metric("flash.fwd_per_bwd", {"trace": red}) == want


@pytest.mark.parametrize("cell", SEQUENCE_CELLS)
def test_flash_reader_follows_the_accepted_entries_and_lists_the_cell(cell):
    """The 56th ``per_layer`` entry, listing the three sequence cells in the
    manifest's order; how many entries or cells follow is a later PR's."""
    bench = bench_run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    entry = bench["per_layer"][55]
    assert entry["name"] == "flash.fwd_per_bwd"
    assert entry["workloads"][:3] == cells[2:5] == SEQUENCE_CELLS
    assert entry["workloads"].index(cell) == SEQUENCE_CELLS.index(cell)
