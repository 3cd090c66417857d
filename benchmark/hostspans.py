"""The program's host spans laid on the reduced device trace: which span of
the fit loop covers each idle gap of the device, the stager thread's spans
in the window, and the setup spans of ``train_net``.

The readers get the ``Reduced`` trace, whose times count from the profiler
session's start, and the program's span buffer (``mx_rcnn_tpu/obs/trace.py``),
stamped with the unix clock; the trace file, which states the session's
start, is gone by then.  The two clocks are tied by step number instead.  The
traced window starts at the execution of a log step ``S``: the fit loop
dispatches it and goes straight into ``train.sync(step=S)``, which returns
``fetch_us`` after the host learned that the execution had ended.  So

    offset = (end of train.sync(step=S) - fetch_us) - (end of the window's
             first execution on the device)

and ``S`` is the step at the log edge that stopped the trace less the steps
the window holds, by the arithmetic of ``window.Edges`` that the driver ran.
The offset is then checked on every step of the window: no execution may
start before its own ``train.dispatch`` span began.  The smallest such slack
is ``host.clock_slack_us``; where it is negative nothing below is given, a
missing cause being better than a wrong one.

A parent commit that leaves no spans (or none of these names) gives None
everywhere; nothing here raises for want of a span.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import trace as trace_mod
from benchmark import window

GAP_NS = 50e3   # the same 50 us that Reduced.idle_gaps splits at
UNNAMED = "unnamed"
# spans whose idle is the log step's: the sync, the log line and the hooks,
# and the dispatch that follows them (the first after the loop has lost its
# lead on the device)
LOG_STEP = ("train.sync", "train.log", "train.hooks")


def collected() -> List[dict]:
    """The program's span buffer as it stands (empty where the program
    collected nothing, as the parent commit's does in a traced run)."""
    from mx_rcnn_tpu.obs import trace as obs_trace

    return obs_trace.events()


def _named(events: Sequence[dict], name: str) -> List[dict]:
    return [e for e in events if e.get("name") == name and e.get("ph") == "X"]


def _step(e: dict):
    return (e.get("args") or {}).get("step")


def traced_steps(events: Sequence[dict], warmup_steps: int
                 ) -> Optional[Tuple[int, int]]:
    """(step at the log edge that started the trace, step at the edge that
    stopped it), from the ``train.log`` spans by the driver's own
    ``window.Edges``; None where the spans do not reach that far."""
    edges = window.Edges(warmup_steps, math.inf, traced=True)
    for e in sorted(_named(events, "train.log"), key=lambda e: e["ts"]):
        if _step(e) is not None:
            edges.add(e["ts"] * 1e-6, int(_step(e)))
    if edges.trace_to is None:
        return None
    return edges.logs[edges.trace_from][1], edges.logs[edges.trace_to][1]


def executions(dev: Dict, steps: int) -> Optional[List[Tuple[float, float]]]:
    """(start_ns, end_ns) of each of the window's executions on one device
    of a ``Reduced`` trace: an execution starts where the window's first
    instruction recurs and ends with the last op before the next start."""
    ops = sorted(dev["ops"], key=lambda o: o[2])
    if not ops:
        return None
    starts = [o[2] for o in ops if o[0] == ops[0][0]]
    if len(starts) != steps:
        return None
    ends = [0.0] * steps
    k = 0
    for _, _, s, d in ops:
        while k + 1 < steps and s >= starts[k + 1]:
            k += 1
        ends[k] = max(ends[k], s + d)
    return list(zip(starts, ends))


def align(reduced, events: Sequence[dict], warmup_steps: int
          ) -> Optional[Dict]:
    """{'first_step', 'offset_ns' (host clock less device clock),
    'slack_us', 'executions'} or None where the spans or the trace do not
    allow it."""
    if reduced is None or not reduced.devices or not reduced.steps:
        return None
    traced = traced_steps(events, warmup_steps)
    runs = executions(reduced.devices[0], reduced.steps)
    if traced is None or runs is None:
        return None
    first = traced[1] - reduced.steps
    sync = [e for e in _named(events, "train.sync") if _step(e) == first
            and "fetch_us" in e["args"]]
    dispatch = {_step(e): e for e in _named(events, "train.dispatch")}
    if not sync or any(first + k not in dispatch
                       for k in range(reduced.steps)):
        return None
    ready_us = sync[-1]["ts"] + sync[-1]["dur"] - sync[-1]["args"]["fetch_us"]
    offset_ns = ready_us * 1e3 - runs[0][1]
    slack_ns = min(runs[k][0] - (dispatch[first + k]["ts"] * 1e3 - offset_ns)
                   for k in range(reduced.steps))
    return {"first_step": first, "offset_ns": offset_ns,
            "slack_us": slack_ns * 1e-3, "executions": runs}


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _on_device(spans: Sequence[dict], offset_ns: float, lo: float, hi: float
               ) -> List[Tuple[float, float, dict]]:
    """(start_ns, end_ns, span) on the device's clock, those that touch
    [lo, hi)."""
    out = []
    for e in spans:
        s = e["ts"] * 1e3 - offset_ns
        t = s + e["dur"] * 1e3
        if t > lo and s < hi:
            out.append((s, t, e))
    return out


def attribute(reduced, events: Sequence[dict], warmup_steps: int
              ) -> Optional[Dict]:
    """Every idle gap of 50 us or more on the window's first device, laid
    on the fit thread's spans.  Returns None without an alignment or with a
    negative slack; else

    - ``idle_s``: seconds of those gaps while the fit thread was in each
      span name, 'unnamed' for the seconds no span covers;
    - ``log_step_s`` and ``log_steps``: the part of it under the log
      step's spans and the log steps the window holds;
    - ``short_s``: the gaps under 50 us, between ops inside a step;
    - ``stage_ms``: per stager span name, the durations of those that ran
      in the window.
    """
    al = align(reduced, events, warmup_steps)
    if al is None or al["slack_us"] < 0:
        return None
    dev = reduced.devices[0]
    lo, hi, off = dev["lo"], dev["hi"], al["offset_ns"]
    fit_tid = {e["tid"] for e in _named(events, "train.dispatch")}
    fit = _on_device([e for e in events if e.get("ph") == "X"
                      and e["tid"] in fit_tid
                      and e["name"].startswith("train.")], off, lo, hi)
    log_steps = {_step(e) for _, _, e in fit if e["name"] == "train.sync"}

    def of_log_step(e: dict) -> bool:
        return e["name"] in LOG_STEP or (
            e["name"] == "train.dispatch" and _step(e) is not None
            and _step(e) - 1 in log_steps)

    found = trace_mod.gaps([(lo, lo)]
                           + [(s, s + d) for _, _, s, d in dev["ops"]]
                           + [(hi, hi)])
    idle: Dict[str, float] = {}
    short, log_step = 0.0, 0.0
    for a, b in found:
        if b - a < GAP_NS:
            short += (b - a) * 1e-9
            continue
        for s, t, e in fit:
            sec = _overlap(a, b, s, t) * 1e-9
            if sec <= 0:
                continue
            idle[e["name"]] = idle.get(e["name"], 0.0) + sec
            if of_log_step(e):
                log_step += sec
        # the fit thread's spans do not overlap one another but where one
        # nests (a snapshot inside the hooks): what no span covers is the
        # gap less the union
        covered = trace_mod.union_ns(
            [(max(a, s), min(b, t)) for s, t, _ in fit
             if _overlap(a, b, s, t) > 0])
        idle[UNNAMED] = (idle.get(UNNAMED, 0.0)
                         + max(b - a - covered, 0.0) * 1e-9)
    stage_ms: Dict[str, List[float]] = {}
    for s, t, e in _on_device([e for e in events if e.get("ph") == "X"
                               and e["name"].startswith("stage.")],
                              off, lo, hi):
        if lo <= 0.5 * (s + t) < hi:
            stage_ms.setdefault(e["name"], []).append(e["dur"] * 1e-3)
    return {"slack_us": al["slack_us"], "first_step": al["first_step"],
            "idle_s": idle, "short_s": short, "log_step_s": log_step,
            "log_steps": sum(1 for k in range(reduced.steps)
                             if al["first_step"] + k in log_steps),
            "stage_ms": stage_ms}


def unscoped_s(reduced, stages: Sequence[str]) -> Optional[float]:
    """Device seconds (mean over chips) in which an op ran and none under
    ``stages``, the scopes the cell's family names (its ``STAGES``): the
    union of all the ops' intervals less the union of the staged ops'.

    Taken from the intervals and not op by op, because a loop's body is not
    named as its loop is: the ``while`` op of a ``lax.map`` / ``lax.scan``
    lies under its stage and, on the same device line, around its body's
    ops in time, but the copies and fusions the compiler makes inside the
    body carry no name path, and under a ``jax.checkpoint`` a body's path
    begins at ``closed_call`` with no ``jit(step)/.../<stage>`` before it.
    The body so counts with its loop: it is unscoped only where that
    ``while`` is, and the stages' union plus this is the step's busy time
    exactly."""
    if reduced is None or not reduced.devices:
        return None
    inside = re.compile(r"(^|[/(])(" + "|".join(map(re.escape, stages))
                        + r")([/)]|$)")
    per = []
    for dev in reduced.devices:
        every = [(s, s + d) for _, _, s, d in dev["ops"]]
        staged = [(s, s + d) for _, path, s, d in dev["ops"]
                  if inside.search(path)]
        # in ns before the scale: the recorded trace's reading to the bit
        per.append((trace_mod.union_ns(every) - trace_mod.union_ns(staged))
                   * 1e-9)
    return sum(per) / len(per)


# ---- what the readers (benchmark/metrics/<name>.py) call --------------------

def _warmup(ctx: Dict) -> int:
    return int(ctx["cell"]["traffic"]["warmup_steps"])


def spans(ctx: Dict) -> Optional[List[dict]]:
    """The collected events of a traced run of a program that leaves the fit
    loop's spans; None without the trace or without them."""
    if ctx.get("trace") is None:
        return None
    if "hostspans.events" not in ctx:
        events = collected()
        ctx["hostspans.events"] = (
            events if _named(events, "train.dispatch") else None)
    return ctx["hostspans.events"]


def aligned(ctx: Dict) -> Optional[Dict]:
    events = spans(ctx)
    if events is None:
        return None
    if "hostspans.align" not in ctx:
        ctx["hostspans.align"] = align(ctx["trace"], events, _warmup(ctx))
    return ctx["hostspans.align"]


def attributed(ctx: Dict) -> Optional[Dict]:
    events = spans(ctx)
    if events is None:
        return None
    if "hostspans.attribute" not in ctx:
        ctx["hostspans.attribute"] = attribute(ctx["trace"], events,
                                               _warmup(ctx))
    return ctx["hostspans.attribute"]


def scope_ms(ctx: Dict, scope: str) -> Optional[float]:
    """Device milliseconds per step under a named scope; None where the
    trace holds no op under it (a program that does not name it)."""
    t = ctx.get("trace")
    sec = t.scope_s(scope) if t else None
    return None if sec is None else 1e3 * sec / t.steps


def idle_pct(ctx: Dict, name: str) -> Optional[float]:
    """Share of the window the first device idles, in gaps of 50 us or
    more, while the fit thread is in span ``name``."""
    a = attributed(ctx)
    if a is None:
        return None
    return 100.0 * a["idle_s"].get(name, 0.0) / ctx["trace"].window_s


def stage_mean_ms(ctx: Dict, name: str) -> Optional[float]:
    a = attributed(ctx)
    if a is None:
        return None
    durs = a["stage_ms"].get(name, [])
    return sum(durs) / len(durs) if durs else 0.0


def setup_s(ctx: Dict, name: str) -> Optional[float]:
    """Seconds under the setup span ``name`` (all of them where there are
    several: ``setup.load`` of a graft and of ``init_from``)."""
    events = spans(ctx)
    if events is None:
        return None
    return sum(e["dur"] for e in _named(events, name)) * 1e-6


def first_dispatch_s(ctx: Dict) -> Optional[float]:
    """Seconds of the run's first ``train.dispatch``: the call that traces,
    lowers and compiles the step (or reads it from the cache) before it
    returns; the execution it enqueues is the first of the warm-up steps."""
    events = spans(ctx)
    if events is None:
        return None
    first = min(_named(events, "train.dispatch"), key=lambda e: e["ts"])
    return first["dur"] * 1e-6


def first_log(ctx: Dict) -> Optional[dict]:
    events = spans(ctx)
    logs = sorted(_named(events or [], "train.log"), key=lambda e: e["ts"])
    return logs[0] if logs else None


def lowerings_through_window(ctx: Dict) -> Optional[int]:
    """``compile.lowering`` instants up to the sync that closed the traced
    window (their ``step`` says where each fell)."""
    events = spans(ctx)
    if events is None:
        return None
    traced = traced_steps(events, _warmup(ctx))
    if traced is None:
        return None
    end = max((e["ts"] + e["dur"] for e in _named(events, "train.sync")
               if _step(e) == traced[1]), default=None)
    if end is None:
        return None
    return sum(1 for e in events if e.get("name") == "compile.lowering"
               and e["ts"] <= end)
