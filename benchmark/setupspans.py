"""``setup_s`` laid out in the program's spans, from the process's start to
the window's opening edge: what the ``setup.*`` readers of PR 43 read.

The opening edge is the ``train.log`` span at which ``window.Edges``, fed
the log spans as the driver fed it the log events, opens the window; the
span's end stands for the edge (the driver stamps its log event inside
it, as the span's last work).  ``train_net`` leaves the ``setup.entry``
instant first, with ``process_s``, the process's age at that moment, so

    process_s + (edge - entry) - setup_s

is the interpreter's start before ``run.py``'s first line, a fraction of
a second.  Between entry and edge the caller's thread (the one that left
``setup.entry``) lies under ``setup.*``, ``train.*`` and ``compile.*``
spans; what lies under none is ``setup.unattributed_s``, the tiling check.
The compile phases' spans (``compile.trace``, ``compile.lower``,
``compile.backend`` with ``hit=``) nest where one jitted function is
traced inside another: they are read as unions, not sums.

Only the events from the buffer's last ``setup.entry`` on are the run's
(a process that called ``train_net`` before keeps that run's spans too).
A program that leaves no ``setup.entry`` (the parent of PR 43) gives None
from every reader that needs it, and ``setup.warmup_s`` from the fit
loop's spans alone; nothing here raises for want of a span.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import hostspans, window
from benchmark import trace as trace_mod

# the caller thread's spans that account for its time between entry and edge
COVER = ("setup.", "train.", "compile.")


def _durations(events: Sequence[dict], name: str) -> List[dict]:
    return [e for e in events if e.get("name") == name and e.get("ph") == "X"]


def _end(e: dict) -> float:
    return e["ts"] + e["dur"]


def opening_log(events: Sequence[dict], warmup_steps: int
                ) -> Optional[dict]:
    """The ``train.log`` span at which the window opened, by the driver's
    own ``window.Edges`` (a traced run's: after the traced intervals and
    the one that absorbs the profiler's write-out); None where the spans
    do not reach that far."""
    logs = sorted((e for e in _durations(events, "train.log")
                   if (e.get("args") or {}).get("step") is not None),
                  key=lambda e: e["ts"])
    edges = window.Edges(warmup_steps, math.inf, traced=True)
    for e in logs:
        edges.add(e["ts"] * 1e-6, int(e["args"]["step"]))
    return None if edges.opened is None else logs[edges.opened]


def run_of(events: Sequence[dict], warmup_steps: int
           ) -> Optional[Tuple[List[dict], Optional[dict], float]]:
    """(the run's events, its ``setup.entry`` or None, the opening edge's
    time in the buffer's us); None without an opening edge."""
    entries = [e for e in events if e.get("name") == "setup.entry"]
    entry = max(entries, key=lambda e: e["ts"]) if entries else None
    run = [e for e in events if entry is None or e["ts"] >= entry["ts"]]
    edge = opening_log(run, warmup_steps)
    return None if edge is None else (run, entry, _end(edge))


def _run(ctx: Dict):
    events = hostspans.spans(ctx)
    if events is None:
        return None
    if "setupspans.run" not in ctx:
        ctx["setupspans.run"] = run_of(
            events, int(ctx["cell"]["traffic"]["warmup_steps"]))
    return ctx["setupspans.run"]


def _entered(ctx: Dict):
    """The run with its ``setup.entry``; None where the program leaves none."""
    r = _run(ctx)
    return None if r is None or r[1] is None else r


def _clipped(spans: Sequence[dict], lo: float, hi: float
             ) -> List[Tuple[float, float]]:
    return [(max(e["ts"], lo), min(_end(e), hi)) for e in spans
            if _end(e) > lo and e["ts"] < hi]


# ---- what the readers (benchmark/metrics/setup.*.py) call -------------------

def before_entry_s(ctx: Dict) -> Optional[float]:
    """The process's age at ``setup.entry``: interpreter, imports, the
    backend's start and the harness's own work before ``train_net``."""
    r = _entered(ctx)
    return None if r is None else r[1]["args"].get("process_s")


def harness_s(ctx: Dict) -> Optional[float]:
    """The driver's seconds between its ``imports_s`` and ``weights_s``
    marks: the data and seed-made weights that no user pays for."""
    phases = ctx.get("phases") or {}
    if "imports_s" not in phases or "weights_s" not in phases:
        return None
    return phases["weights_s"] - phases["imports_s"]


def span_s(ctx: Dict, name: str) -> Optional[float]:
    """Seconds under the run's ``name`` spans up to the opening edge; None
    where there is none."""
    r = _entered(ctx)
    if r is None:
        return None
    run, _, edge = r
    spans = [e for e in _durations(run, name) if e["ts"] < edge]
    return sum(e["dur"] for e in spans) * 1e-6 if spans else None


def union_s(ctx: Dict, name: str) -> Optional[float]:
    """Seconds covered by the ``name`` spans (nested ones once) between
    ``setup.entry`` and the opening edge."""
    r = _entered(ctx)
    if r is None:
        return None
    run, entry, edge = r
    return trace_mod.union_ns(
        _clipped(_durations(run, name), entry["ts"], edge)) * 1e-6


def cache_misses(ctx: Dict) -> Optional[int]:
    """``compile.backend`` spans that compiled (``hit`` 0) between
    ``setup.entry`` and the opening edge."""
    r = _entered(ctx)
    if r is None:
        return None
    run, entry, edge = r
    return sum(1 for e in _durations(run, "compile.backend")
               if entry["ts"] <= e["ts"] < edge
               and not (e.get("args") or {}).get("hit"))


def warmup_s(ctx: Dict) -> Optional[float]:
    """From the end of the run's first ``train.dispatch`` (the step's
    trace, lowering and compile or cache read returned) to the opening
    edge: the warm-up steps, and in a traced run the traced intervals."""
    r = _run(ctx)
    if r is None:
        return None
    run, _, edge = r
    dispatches = _durations(run, "train.dispatch")
    if not dispatches:
        return None
    return (edge - _end(min(dispatches, key=lambda e: e["ts"]))) * 1e-6


def unattributed_s(ctx: Dict) -> Optional[float]:
    """Seconds of the caller's thread between ``setup.entry`` and the
    opening edge under no ``setup.*``, ``train.*`` or ``compile.*`` span."""
    r = _entered(ctx)
    if r is None:
        return None
    run, entry, edge = r
    mine = [e for e in run if e.get("ph") == "X" and e["tid"] == entry["tid"]
            and e["name"].startswith(COVER)]
    covered = trace_mod.union_ns(_clipped(mine, entry["ts"], edge))
    return max(edge - entry["ts"] - covered, 0.0) * 1e-6
