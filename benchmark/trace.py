"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, time per named scope, the operations that
took most time, and the idle gaps by where they lie.

The traced window is cut on the device's own clock: the trace holds the
executions of the step program one after another, and the window runs from
the start of one execution to the start of the execution ``steps`` later,
the last such stretch in the trace.  It so holds ``steps`` whole steps with
every gap between them (the fit loop's sync among them) and none of the
profiler's start-up, which stalls the device for the first steps traced.

``load`` turns the file (read by ``benchmark/xplane.py``) into a neutral
form (plain lists, so a small recorded trace can be kept as JSON and the
reduction tested on it); ``Reduced`` answers the readers' questions about
it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str) -> Dict:
    """{"devices": [{"name", "ops": [[name, path, start_ns, dur_ns]],
    "programs": [[name, start_ns, dur_ns]]}]} from an ``.xplane.pb``.  An
    op's ``name`` is the HLO instruction's name, ``path`` its ``op_name`` in
    the compiled program the trace stores (the JAX name stack, named scopes
    in it; empty where the compiler gave none).  ``programs`` are the
    executions of whole compiled programs."""
    from benchmark import xplane

    programs = xplane.read_hlo_programs(path)

    rows = xplane.read_events(
        path, lambda plane: plane.startswith("/device:TPU:"),
        lambda plane, line: line in (OPS_LINE, MODULES_LINE))
    devices: Dict[str, Dict] = {}
    for row in rows:
        dev = devices.setdefault(row["plane"], {"name": row["plane"],
                                                "ops": [], "programs": []})
        if row["line"] == MODULES_LINE:
            dev["programs"] = row["events"]
            continue
        events = [[text.split(" = ", 1)[0].lstrip("%"), start, dur]
                  for text, start, dur in row["events"]]
        paths = pick_program(programs, {e[0] for e in events})
        dev["ops"] = [[name, paths.get(name, ""), start, dur]
                      for name, start, dur in events]
    return {"devices": [devices[k] for k in sorted(devices)]}


def pick_program(programs: Sequence[Dict[str, str]], seen) -> Dict[str, str]:
    """Of the compiled programs a trace stores, the one that holds most of
    the instruction names ``seen`` in a device's op events: the step
    program, whose ops are nearly all of them."""
    return max(programs, key=lambda names: len(seen & names.keys()),
               default={})


def union_ns(spans: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) spans."""
    total, end = 0.0, -1.0
    for s, e in sorted(spans):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(spans: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Uncovered (start, end) stretches between the first and last span."""
    out, end = [], None
    for s, e in sorted(spans):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def cut_window(programs: Sequence, steps: int
               ) -> Optional[Tuple[float, float, int]]:
    """(start_ns, end_ns, steps held) of the traced window among one
    device's program executions: the step program is the one that took
    most device time; the window runs from the start of one of its
    executions to the start of the one ``steps`` later, the last such pair
    (fewer steps where the trace holds fewer)."""
    total: Dict[str, float] = {}
    for name, _, dur in programs:
        total[name] = total.get(name, 0.0) + dur
    if not total:
        return None
    step_program = max(total, key=total.get)
    starts = sorted(s for name, s, _ in programs if name == step_program)
    held = min(steps, len(starts) - 1)
    if held < 1:
        return None
    return starts[-1 - held], starts[-1], held


class Reduced:
    def __init__(self, neutral: Dict, steps: int, chips: int):
        """``steps``: the steps the window should hold (one log interval
        of the fit loop)."""
        self.devices = []
        self.steps, self.window_s = 0, 0.0
        for dev in [d for d in neutral["devices"] if d["ops"]][:chips]:
            cut = cut_window(dev.get("programs", []), steps)
            if cut is None:
                continue
            lo, hi, held = cut
            self.devices.append({"name": dev["name"], "lo": lo, "hi": hi,
                                 "ops": [o for o in dev["ops"]
                                         if lo <= o[2] < hi]})
            self.steps = held if not self.steps else min(self.steps, held)
            self.window_s = max(self.window_s, (hi - lo) * 1e-9)

    # ---- whole device -------------------------------------------------
    def busy_s_per_device(self) -> List[float]:
        return [union_ns([(s, s + d) for _, _, s, d in dev["ops"]]) * 1e-9
                for dev in self.devices]

    def busy_s(self) -> Optional[float]:
        per = self.busy_s_per_device()
        return sum(per) / len(per) if per else None

    # ---- scopes and ops -----------------------------------------------
    def scope_s(self, scope: str) -> Optional[float]:
        """Device seconds (mean over chips) of ops whose name path holds
        the named scope ``scope`` as a whole component, bare or wrapped by a
        transformation (``jvp(scope)``, ``transpose(jvp(scope))``); None
        where no op does."""
        inside = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
        per = []
        for dev in self.devices:
            spans = [(s, s + d) for _, path, s, d in dev["ops"]
                     if inside.search(path)]
            if spans:
                per.append(union_ns(spans) * 1e-9)
        return sum(per) / len(self.devices) if per else None

    def top_ops(self, n: int = 10) -> List[List]:
        """[[instruction name and the end of its name path, seconds]], the
        ``n`` that took most device time (mean over chips)."""
        total: Dict[str, float] = {}
        for dev in self.devices:
            for name, path, _, d in dev["ops"]:
                label = f"{name} {path[-120:]}".strip()
                total[label] = total.get(label, 0.0) + d * 1e-9
        k = max(len(self.devices), 1)
        rows = sorted(total.items(), key=lambda r: -r[1])[:n]
        return [[name, sec / k] for name, sec in rows]

    # ---- idle gaps ----------------------------------------------------
    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle seconds of the first device by where the gap lies: the
        longest single gap of the window (in a steady run the fit loop's
        sync at its log step: the host waits for the device, fetches the
        metrics, then dispatches again), the other gaps of 50 us or more
        (between two executions of the step program: dispatch, or a wait
        for input), and the short ones between ops inside a step.  Host
        threads are not traced: their events slow the input path."""
        if not self.devices:
            return []
        dev = self.devices[0]
        found = gaps([(dev["lo"], dev["lo"])]
                     + [(s, s + d) for _, _, s, d in dev["ops"]]
                     + [(dev["hi"], dev["hi"])])
        lengths = sorted(((b - a) * 1e-9 for a, b in found), reverse=True)
        if not lengths:
            return []
        rows = [["longest single gap", lengths[0]],
                ["other gaps of 50 us or more, between step programs",
                 sum(x for x in lengths[1:] if x >= 50e-6)],
                ["gaps under 50 us, between ops inside a step",
                 sum(x for x in lengths[1:] if x < 50e-6)]]
        return [r for r in rows if r[1] > 0][:n]


def reduce_dir(trace_dir: str, *, steps: int, chips: int) -> Reduced:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Reduced(load(max(files, key=os.path.getmtime)), steps, chips)
