"""``dsa_kl.per_bwd`` (``benchmark/metrics/dsa_kl.per_bwd.py``): the indexer
loss kernel's executions over sparse attention's backward kernel's, read
from a hand-made trace, and the manifest's entry for it.  These live
outside ``tests/benchmark`` because a PR that changes the program may not
edit the benchmark's accepted test files: the entry is checked only for
what PERF.md section 3's rule allows, that the accepted entries come first.
"""

import pytest

from benchmark import run as bench_run
from benchmark import trace

CELL = "keye-vl2-a3b-6l-ep8.train-32k"


@pytest.mark.parametrize("names,want", [
    pytest.param(["dsa_kl.1", "dsa_kl.2", "dsa_attend_bwd"] * 6, 2.0,
                 id="loss_kernel_twice"),
    pytest.param(["dsa_kl", "dsa_attend_bwd.3"] * 6, 1.0,
                 id="loss_kernel_once"),
    pytest.param(["dsa_kl", "dsa_attend_fwd", "fusion.7"] * 6, None,
                 id="no_backward"),
    pytest.param(["dsa_kl.4", "dsa_attend_bwd", "dsa_kl_bwd.1",
                  "fusion.dsa_kl.5", "dsa_attend_fwd.2"], 1.0,
                 id="other_names_are_not_counted"),
])
def test_dsa_kl_reader_counts_loss_kernels_over_backward(names, want):
    ops = [[name, "jit(step)/x", 10.0 * i, 5.0]
           for i, name in enumerate(names)]
    programs = [["jit_step(1)", 0.0, 1.0], ["jit_step(1)", 1000.0, 1.0]]
    red = trace.Reduced({"devices": [{"name": "d", "ops": ops,
                                      "programs": programs}]},
                        steps=1, chips=1)
    assert bench_run.read_metric("dsa_kl.per_bwd", {"trace": red}) == want


def test_dsa_kl_reader_follows_the_accepted_entries_and_lists_the_cell():
    """The 77th ``per_layer`` entry, listing the sixth cell first; how many
    entries or cells follow is a later PR's."""
    bench = bench_run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[75] == "dsa_kl_roofline"
    entry = bench["per_layer"][76]
    assert entry["name"] == "dsa_kl.per_bwd"
    assert entry["workloads"][:1] == cells[5:6] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("calls/call", "lower", "device_trace",
                                "kernels", "train_imgs_per_s")
