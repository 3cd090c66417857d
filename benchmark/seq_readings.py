"""Read the numbers that a sequence cell's limits are set from, on the chip
at the cell's own size, for a family whose driver runs through
``drivers/seq_train.py``: the family's readings script names its driver,
reference and comparison, and this file does the rest
(``benchmark/evabyte_readings.py``).

* lower readings: the program through the cell's own driver (a window of
  two seconds) against the reference, on each of ``--seeds``; every number
  is printed, compared or not;
* upper readings: on each of ``--control-seeds`` the plain reference in
  float32 beside what is put in the program's place: the reference in
  ``float8`` (the control), in ``bfloat16`` (a witness: what any sound
  bfloat16 step may read) and with each of the reference's ``FAULTS``
  planted (``--planted`` names a subset).  A state left unchanged reads 1
  by the leaf measure and needs no run.

One process reads everything, so each program compiles once.  Rows go to
standard output and to ``chiprun_out/<family>_readings_<cell>_<time>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lm_readings import _seeds  # noqa: E402


def planted_rows(cell, seed, tags, reference, compare: Callable):
    """{tag: ``compare``'s notes} of the reference module ``reference``
    with ``tag`` planted, against itself plain, on one seed."""
    from mx_rcnn_tpu import runtime

    from benchmark import lm_traffic

    # a second control seed finds the first one's programs compiled
    runtime.enable_compile_cache()
    config, traffic, check = cell["config"], cell["traffic"], cell["check"]
    s32 = seed % (2 ** 31 - 1)
    n = traffic["per_chip_batch"] * cell["chips"]
    batches = lm_traffic.reference_batches(
        lm_traffic.make_sequences(traffic, s32, config["vocab_size"],
                                  traffic["sequences_per_chip"]),
        n, check["steps"])

    def follow(**kw):
        out = reference.reference_steps(
            config, config["optimizer"],
            reference.make_weights(config, s32), batches, **kw)
        # the reference takes its own key sets: no count to miss
        return dict(out, overflow=0.0, count_misses=[0])

    plain, row = follow(), {"seed": seed}
    for tag in tags:
        t0 = time.perf_counter()
        kw = ({"fault": tag} if tag in reference.FAULTS
              else {"precision": tag})
        ok, _, notes = compare(follow(**kw), plain, check["limits"])
        row[tag] = dict(notes, correct=ok, s=time.perf_counter() - t0)
    return row


def main(argv, family: str, driver, planted: Callable) -> int:
    """``driver``: the family's driver module; ``planted(cell, seed,
    tags)``: its ``planted_rows``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--planted", default=None)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from benchmark import run as bench_run

    tags = (args.planted.split(",") if args.planted
            else ["float8", "bfloat16"] + list(driver.reference.FAULTS))
    rows = {"cell": args.cell, "program": [], "planted": []}
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = driver.run(bench_run.load_cell(args.cell), seed=seed,
                       seconds=args.seconds, trace=False, t_start=t0)
        row = {"seed": seed, "correct": r["correct"], **r["notes"],
               "imgs_per_s": r["window"]["imgs_per_s"],
               "peak_bytes": r["peak_bytes"], "setup_s": r["setup_s"],
               "counters": r["counters"],
               "reference_s": r["reference_s"], "phases": r["phases"],
               "wall_s": time.perf_counter() - t0}
        rows["program"].append(row)
        print("PROGRAM " + json.dumps(row), flush=True)
    for seed in args.control_seeds:
        row = planted(bench_run.load_cell(args.cell), seed, tags)
        rows["planted"].append(row)
        print("PLANTED " + json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"{family}_readings_{args.cell}_{int(time.time())}.json"),
            "w") as f:
        json.dump(rows, f)
    return 0
