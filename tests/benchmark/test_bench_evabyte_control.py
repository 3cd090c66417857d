"""The controls of the ``evabyte_train`` cell on the CPU at a size a test
run can hold (``test_bench_evabyte.py`` has the sound run and the faults
under the timed path, ``bench_tiny_evabyte.py`` says how the cell is cut):
what ``evabyte_readings.py`` reads on the chip — the reference with each of
its faults planted, or in float8, put in the program's place against
itself plain — reads not ``correct`` under the cell's own limits, each by
the number that is there for it; the bfloat16 witness reads ``correct``.
The fault no number sees, the residual stream summed in bfloat16, is
PERF.md section 7's (``tests/test_evabyte.py`` checks the program's stream
instead).  A file of its own: workers are given whole files, and the seven references
it computes take as long as the rest of the cell's cases.
"""

import pytest

from bench_tiny_evabyte import tiny_evabyte_cell
from benchmark import evabyte_readings
from benchmark.reference import evabyte as ref

SEED = 2147483659
# each fault with the number that is there for it
CAUGHT_BY = {
    "float8": "grad_worst",
    "no_summaries": "eva_grad_worst",
    "own_block": "eva_grad_worst",
    "mean_pool": "eva_grad_worst",
    "targets_off": "norm_grad_worst",
}
UNSEEN = ("bf16_residual",)


@pytest.fixture(scope="module")
def planted():
    assert set(CAUGHT_BY) | set(UNSEEN) == set(ref.FAULTS) | {"float8"}
    return evabyte_readings.planted_rows(
        tiny_evabyte_cell(), SEED, list(CAUGHT_BY) + ["bfloat16"])


@pytest.mark.parametrize("tag", list(CAUGHT_BY))
def test_planted_in_the_reference_the_fault_or_control_is_not_correct(
        planted, tag):
    row = planted[tag]
    assert not row["correct"], (tag, row["all"])
    limits = tiny_evabyte_cell()["check"]["limits"]
    name = CAUGHT_BY[tag]
    assert row["all"][name] > limits[name], (tag, row["all"])


def test_the_bfloat16_witness_is_correct(planted):
    """The configuration's own precision, in the reference's arithmetic,
    passes every limit."""
    assert planted["bfloat16"]["correct"], planted["bfloat16"]["all"]
