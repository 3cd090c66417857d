"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name: ``benchmark/workloads/<name>.json``
names a configuration (``configs/``), a traffic mix (``traffic/``) and a
driver kind (``drivers/<kind>.py``); ``BENCHMARK.json`` names the metrics,
and each per-layer metric has a reader of its own (``metrics/<name>.py``)
that returns a number or nothing.  The last stdout line is the result
object; the numbers compared for ``correct`` go to stderr beside their
limits and into the result under ``compared``.  Without the chips the cell
asks for the run exits non-zero and prints no result.

Nothing here knows a model.  What depends on the model's family comes from
files the cell's data names, so a cell of a family the benchmark has never
seen is new files and ``BENCHMARK.json`` entries, and no edit to this one:

* ``configs/<c>.json`` states ``network.family``, and
  ``families/<family>.py`` exports ``layers(config, traffic)``, the whole
  layer table of one sample (rows as ``benchmark/flops.py`` describes them),
  and ``STAGES``, the step's named scopes;
* ``drivers/<kind>.py`` exports ``run(cell, *, seed, seconds, trace,
  t_start)`` and ``CellFailure``; ``run`` returns what
  ``drivers/measure.py::Measurement.result`` assembles, and a driver through
  the fit loop calls that class for the measurement's half (window edges,
  profiler, memory, ``failed``, ``setup_s``);
* ``metrics/<name>.py`` exports ``read(ctx)``; a ``per_layer`` entry with a
  ``workloads`` list is read in those cells alone.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse        # noqa: E402
import importlib       # noqa: E402
import importlib.util  # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import sys             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The workload file with its configuration and traffic loaded in."""
    cell = _json("workloads", f"{name}.json")
    cell["config"] = _json("configs", f"{cell['config']}.json")
    cell["traffic"] = _json("traffic", f"{cell['traffic']}.json")
    return cell


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _listed(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_metric(name: str, ctx: dict):
    """Run the reader ``metrics/<name>.py``; None where it finds nothing."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def metrics_of(result: dict, cell: dict, bench: dict, trace: bool) -> dict:
    """The result line's ``metrics``: the cell's end-to-end metrics without
    a trace (the driver's ``end_to_end``, by name), its per-layer metrics
    with one.  A reader gets the driver's result and the whole loaded cell:
    ``cell`` (with its ``config`` and ``traffic``), ``chips``, the chip's
    ``peak``, and from the configuration's family its ``layers`` for one
    sample of the traffic (``benchmark/flops.py``) and its ``stages``, so a
    new metric is a new file."""
    name = cell["name"]
    if not trace:
        return {m["name"]: {"value": result["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in bench["end_to_end"] if _listed(m, name)}
    from benchmark import flops

    config = cell["config"]
    ctx = dict(result, cell=cell, chips=cell["chips"],
               peak=flops.peaks(result["device"]["kind"]),
               layers=flops.layer_table(config, cell["traffic"]),
               stages=flops.family(config["network"]).STAGES)
    out = {}
    for m in bench["per_layer"]:
        if _listed(m, name):
            value = read_metric(m["name"], ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = manifest()
    cell = load_cell(args.workload)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    try:
        result = driver.run(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t_start=T_START)
    except driver.CellFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"],
            "metrics": metrics_of(result, cell, bench, bool(args.trace)),
            "device": dict(result["device"])}
    t = result["trace"]
    if t is not None:
        line["device"]["busy_s"] = t.busy_s()
        line["device"]["window_s"] = t.window_s
        line["breakdown"] = {"device_ops": t.top_ops(10),
                             "idle_gaps": t.idle_gaps(10)}
    # not read by the driver: where a run's time went, so that a run that
    # reads far off can be looked into from the ledger's last line
    line["window"] = result["window"]
    line["reference_s"] = result["reference_s"]
    line["phases"] = result["phases"]
    line["notes"] = result["notes"]
    line["compared"] = result["numbers"]
    for name, row in result["numbers"].items():
        print(f"compared {name} value {row['value']:.6g} limit "
              f"{row['limit']:.6g}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
