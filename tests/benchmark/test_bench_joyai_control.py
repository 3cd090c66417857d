"""The controls of the ``joyai_train`` cell on the CPU at a size a test run
can hold (``test_bench_joyai.py`` has the sound run and the faults under the
timed path, ``bench_tiny_joyai.py`` says how the cell is cut): what
``joyai_readings.py`` reads on the chip — the reference with each of its
faults planted, or in float8, put in the program's place against itself
plain — reads not ``correct`` under the cell's own limits, each by the
number that is there for it; the bfloat16 witness reads ``correct``.  A file
of its own: workers are given whole files, and the fourteen references it
computes take as long as the rest of the cell's cases.
"""

import pytest

from bench_tiny_joyai import tiny_joyai_cell
from benchmark import joyai_readings
from benchmark.reference import joyai_flash as ref

SEED = 2147483659
# each fault with the number that is there for it
CAUGHT_BY = {
    "float8": "grad_worst",
    "mtp_shift_one": "mtp_grad_worst",
    "mtp_embed_unshifted": "mtp_grad_worst",
    "mtp_halves_swapped": "mtp_grad_worst",
    "mtp_weight_0": "loss_s1",
    "mtp_weight_1": "loss_s1",
    "mtp_h_before_norm": "final_norm_grad",
    "mtp_shared_cut": "shared_grad_worst",
    "no_q_norm": "latent_grad_worst",
    "rope_halves": "latent_grad_worst",
    "no_rope": "latent_grad_worst",
    "no_scale": "grad_worst",
    "no_shared": "grad_worst",
}


@pytest.fixture(scope="module")
def planted():
    assert set(CAUGHT_BY) == set(ref.FAULTS) | {"float8"}
    return joyai_readings.planted_rows(
        tiny_joyai_cell(), SEED, list(CAUGHT_BY) + ["bfloat16"])


@pytest.mark.parametrize("tag", list(CAUGHT_BY))
def test_planted_in_the_reference_the_fault_or_control_is_not_correct(
        planted, tag):
    row = planted[tag]
    assert not row["correct"], (tag, row["all"])
    limits = tiny_joyai_cell()["check"]["limits"]
    name = CAUGHT_BY[tag]
    assert row["all"][name] > limits[name], (tag, row["all"])


def test_the_bfloat16_witness_is_correct(planted):
    """The configuration's own precision, in the reference's arithmetic,
    passes every limit."""
    assert planted["bfloat16"]["correct"], planted["bfloat16"]["all"]
