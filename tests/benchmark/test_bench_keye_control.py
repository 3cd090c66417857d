"""The controls of the ``keye_train`` cell on the CPU at a size a test run
can hold (``test_bench_keye.py`` has the sound run and the faults under the
timed path, ``bench_tiny_keye.py`` says how the cell is cut): what
``keye_readings.py`` reads on the chip — the reference with each of its
faults planted, or in float8, put in the program's place against itself
plain — reads not ``correct`` under the cell's own limits, each by the
number that is there for it; the bfloat16 witness reads ``correct``.  The
faults no number sees, the indexer reading the block's input with its
gradient, and the sigmoid router in the softmax's place are PERF.md
section 7's.  A file of its own: workers are given
whole files, and the eleven references it computes take as long as the
rest of the cell's cases.
"""

import pytest

from bench_tiny_keye import tiny_keye_cell
from benchmark import keye_readings
from benchmark.reference import keye_vl2 as ref

SEED = 2147483659
# each fault with the number that is there for it
CAUGHT_BY = {
    "float8": "grad_worst",
    "dense": "selection_diff",
    "topk_half": "index_loss_s1",
    "no_weights": "selection_diff",
    "no_relu": "selection_diff",
    "target_one_head": "index_loss_s1",
    "no_index_rope": "selection_diff",
    "no_head_norms": "head_norm_grad_worst",
    "no_renorm": "grad_worst",
}
UNSEEN = ("not_detached", "sigmoid_router")


@pytest.fixture(scope="module")
def planted():
    assert set(CAUGHT_BY) | set(UNSEEN) == set(ref.FAULTS) | {"float8"}
    return keye_readings.planted_rows(
        tiny_keye_cell(), SEED, list(CAUGHT_BY) + ["bfloat16"])


@pytest.mark.parametrize("tag", list(CAUGHT_BY))
def test_planted_in_the_reference_the_fault_or_control_is_not_correct(
        planted, tag):
    row = planted[tag]
    assert not row["correct"], (tag, row["all"])
    limits = tiny_keye_cell()["check"]["limits"]
    name = CAUGHT_BY[tag]
    assert row["all"][name] > limits[name], (tag, row["all"])


def test_the_bfloat16_witness_is_correct(planted):
    """The configuration's own precision, in the reference's arithmetic,
    passes every limit."""
    assert planted["bfloat16"]["correct"], planted["bfloat16"]["all"]
