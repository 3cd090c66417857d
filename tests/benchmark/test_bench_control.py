"""The lower-precision control of the detector cells on the CPU at a size a
test run can hold (``test_bench_correct.py`` has the sound run and the
planted faults, and says how the cells are cut): the reference in float8, in
the program's place, reads not ``correct`` under the cell's own limits.  A
file of its own because the two references it computes take as long as half
of that file's cases: workers that are given whole files run them side by
side.
"""

import pytest

from benchmark.reference import compare, nets
from benchmark.reference import step as ref_step
from test_bench_correct import SEED, _cell


@pytest.mark.parametrize("name", ["r101-coco.train", "vgg16-voc07.train"])
def test_lower_precision_control_is_not_correct(name):
    """The control: the reference in float8 by a plain cast, where the
    configuration has bfloat16, put in the program's place."""
    from benchmark import traffic_gen as traffic

    cell = _cell(name)
    config, net = cell["config"], cell["config"]["network"]
    items = traffic.make_images(cell["traffic"], SEED, net["num_classes"], 8)
    batches = traffic.reference_batches(
        items, config["bucket"], 2, 2, config["train"]["max_gt_boxes"])
    runs = {p: ref_step.reference_steps(
        net, config["train"], config["optimizer"],
        nets.make_weights(net, SEED), batches, SEED, steps=2, block=2,
        precision=p, scan=False) for p in ("float32", "float8")}
    limits = cell["check"]["limits"]
    ok, numbers, _ = compare.compare_training(
        runs["float8"], runs["float32"], limits)
    assert not ok, numbers
