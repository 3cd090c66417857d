"""Read the numbers that a training cell's limits are set from, on the chip
at the cell's own size, through the same ``compare_training`` a run uses.

    python3 benchmark/readings.py <cell> --seeds 1,2,3 --control-seeds 4,5,6

* lower readings: the program through the cell's own driver (a window of
  two seconds) against the reference, on each of ``--seeds``; every number
  is printed, compared or not;
* upper readings: on each of ``--control-seeds`` the plain reference in
  float32 beside what is put in the program's place: the reference in
  ``float8`` (the control, a plain cast where the configuration has
  bfloat16), in ``float8_scaled`` (the careful 8-bit recipe: since PR 40
  no limit of ``r101-coco.train`` stands under its readings, PERF.md
  section 2 names it under "Cannot see") and with half of every batch left
  out (``half``).  A state left unchanged, or leaves left unmoved, read 1
  by the measure and need no run.

One process reads everything, so each program compiles once.  Rows go to
standard output and to ``chiprun_out/readings_<cell>_<time>.json``; PERF.md
section 2 says which limits were set from them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLANTED = {"float8": {"precision": "float8"},
           "float8_scaled": {"precision": "float8_scaled"},
           "half": {"skip_half": True}}


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--planted", default=",".join(PLANTED))
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run as bench_run, traffic_gen
    from benchmark.drivers import train as driver
    from benchmark.reference import compare, nets, step

    rows = {"cell": args.cell, "program": [], "planted": []}
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = driver.run(bench_run.load_cell(args.cell), seed=seed, seconds=2.0,
                       trace=False, t_start=t0)
        row = {"seed": seed, "correct": r["correct"], **r["notes"],
               "imgs_per_s": r["window"]["imgs_per_s"],
               "reference_s": r["reference_s"],
               "wall_s": time.perf_counter() - t0}
        rows["program"].append(row)
        print("PROGRAM " + json.dumps(row), flush=True)

    cell = bench_run.load_cell(args.cell)
    config, traffic, check = cell["config"], cell["traffic"], cell["check"]
    net, n = config["network"], traffic["per_chip_batch"] * cell["chips"]
    for seed in args.control_seeds:
        s32 = seed % (2 ** 31 - 1)
        items = traffic_gen.make_images(traffic, s32, net["num_classes"],
                                        traffic["images_per_chip"])
        batches = traffic_gen.reference_batches(
            items, config["bucket"], n, check["steps"],
            config["train"]["max_gt_boxes"])

        def follow(**kw):
            return step.reference_steps(
                net, config["train"], config["optimizer"],
                nets.make_weights(net, s32), batches, s32,
                steps=check["steps"], block=check["block"], **kw)

        plain = follow()
        row = {"seed": seed}
        for tag in args.planted.split(","):
            t0 = time.perf_counter()
            ok, _, notes = compare.compare_training(
                follow(**PLANTED[tag]), plain, check["limits"])
            row[tag] = dict(notes, correct=ok, s=time.perf_counter() - t0)
        rows["planted"].append(row)
        print("PLANTED " + json.dumps(row), flush=True)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"readings_{args.cell}_{int(time.time())}.json"), "w") as f:
        json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
