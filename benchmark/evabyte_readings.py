"""Read the numbers that an ``evabyte_train`` cell's limits are set from, on
the chip at the cell's own size, through the same ``compare_evabyte`` a run
uses (``benchmark/seq_readings.py`` reads them).

    python3 benchmark/evabyte_readings.py <cell> --seeds 1,2,3 --control-seeds 4

The planted faults (``reference/evabyte.py::FAULTS``): the summaries
dropped; summaries of the query's own window too; a plain mean in place of
the learned pooling; every head's targets one token nearer; the residual
stream summed in bfloat16.  Rows go to standard output and to
``chiprun_out/evabyte_readings_<cell>_<time>.json``.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import seq_readings  # noqa: E402


def planted_rows(cell, seed, tags):
    """{tag: compare_evabyte's notes} of the reference with ``tag``
    planted, against itself plain, on one seed."""
    from benchmark.reference import evabyte as reference
    from benchmark.reference import evabyte_compare

    return seq_readings.planted_rows(cell, seed, tags, reference,
                                     evabyte_compare.compare_evabyte)


def main(argv=None) -> int:
    from benchmark.drivers import evabyte_train

    return seq_readings.main(argv, "evabyte", evabyte_train, planted_rows)


if __name__ == "__main__":
    sys.exit(main())
