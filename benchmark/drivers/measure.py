"""The measurement's half of a run through the fit loop, for every driver
kind that drives one (``drivers/train.py`` today): what no program decides.

A driver builds the program's half (its configuration, data, seed-made
weights, the call of the program's entry, the reference and the comparison)
and calls, in this order:

    devices = measure.need_chips(cell["chips"])        # in ``run``
    m = measure.Measurement(...)                        # before set-up work
    m.mark("data_s") ...                                # set-up phases
    entry(..., run_record=m.events, stop_flag=m.closed) # the program
    m.end(rows_per_step)       # CellFailure if the window never closed
    ... free the program's state ...
    m.reduce()                 # the traced window, where there is a trace
    ... the reference, the comparison ...
    return m.result(correct=..., numbers=..., notes=..., reference_s=...,
                    end_to_end={"<the cell's rate>": m.stats["imgs_per_s"]})

``m.events`` is the fit loop's ``run_record`` hook: each ``log`` event (the
loop has just fetched the metrics of the steps before it, so its host time
is a synced edge) goes to ``benchmark/window.py::Edges``, which says when to
start and stop the profiler and when the window has closed.  A driver kind
also exports ``CellFailure`` (this one) and ``run``.
"""

from __future__ import annotations

import math
import os
import time
from typing import Callable, Dict, List

import jax

from benchmark import trace as trace_mod
from benchmark import window


class CellFailure(RuntimeError):
    """The run cannot produce a result line."""


class Events:
    """``run_record`` hook: the fit loop's events with their host time."""

    def __init__(self, on_log: Callable[[float, Dict], None]):
        self.rows: List = []
        self._on_log = on_log

    def event(self, kind: str, **fields) -> None:
        now = time.perf_counter()
        self.rows.append((now, kind, fields))
        if kind == "log":
            self._on_log(now, fields)


def need_chips(chips: int) -> List:
    """JAX's devices where they are the TPU chips the cell asks for; without
    them there is no result."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise CellFailure(
            f"cell needs {chips} tpu chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}")
    return devices


class Measurement:
    """Window edges, profiler, memory, ``failed``, ``setup_s`` and the
    result object of one run.

    ``warmup_steps``: the window may open at the first log edge at or after
    it; ``log_every``: the steps of one log interval of the fit loop;
    ``work``: the run's scratch directory (the trace is written under it).
    """

    def __init__(self, *, chips: int, warmup_steps: int, log_every: int,
                 seconds: float, trace: bool, work: str, t_start: float):
        self.chips, self.log_every, self.trace = chips, log_every, trace
        self.t_start = t_start
        self.trace_dir = os.path.join(work, "trace")
        self.edges = window.Edges(warmup_steps, seconds, trace)
        self.events = Events(self._on_log)
        self.phases: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.reduced = None
        self._log_loss: List[float] = []
        self._held = 0

    def mark(self, phase: str) -> None:
        """Seconds from the process's start to now, under ``phase``."""
        self.phases[phase] = time.perf_counter() - self.t_start

    def closed(self) -> bool:
        """The fit loop's ``stop_flag``."""
        return self.edges.closed is not None

    def _on_log(self, now: float, fields: Dict) -> None:
        self._log_loss.append(float(fields.get("loss", math.nan)))
        action = self.edges.add(now, int(fields["nbatch"]))
        if action == "start_trace":
            # the device alone: Python call tracing slows the thread
            # that dispatches, and the host's own events (15 million
            # in 20 steps, one per chunk of every input transfer's
            # relayout) slow the input path until the chip starves
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        elif action == "stop_trace":
            jax.profiler.stop_trace()
        elif action == "close":
            # what the chip holds while the step runs: live arrays plus
            # the memory reserved for the loaded programs' scratch
            for d in jax.local_devices()[:self.chips]:
                m = d.memory_stats() or {}
                self._held = max(self._held, m.get("bytes_in_use", 0)
                                 + m.get("bytes_reserved", 0))

    def end(self, rows_per_step: int) -> None:
        """The program's entry has returned: the window's rate
        (``rows_per_step`` rows of the batch an optimizer step, over all
        chips), ``setup_s``, the memory, and in a traced run the fit loop's
        own counters."""
        edges = self.edges
        if edges.closed is None:
            if edges.trace_from is not None and edges.trace_to is None:
                jax.profiler.stop_trace()
            raise CellFailure("the epoch ended before the window closed: "
                              "raise the traffic file's epoch_steps")
        self.rows_per_step = rows_per_step
        self.stats = edges.stats(rows_per_step)
        self.setup_s = edges.logs[edges.opened][0] - self.t_start
        mem = [d.memory_stats() or {}
               for d in jax.local_devices()[:self.chips]]
        # the allocator's peak leaves out the programs' scratch, which it
        # books as reserved (PERF.md section 6): the peak is at least what
        # was held at the closing edge
        self.peak = max(max((m.get("peak_bytes_in_use", 0) for m in mem),
                            default=0), self._held)
        self.limit = max((m.get("bytes_limit", 0) for m in mem), default=0)
        if self.trace:
            from mx_rcnn_tpu.obs.metrics import registry

            reg = registry()
            for name in ("train.data_wait_ms", "train.step_ms"):
                h = reg.hist(name)
                if h is not None and h.mean is not None:
                    self.counters[name] = h.mean

    def reduce(self) -> None:
        """Reduce the traced window (one log interval's steps), where the
        run was traced."""
        if self.trace:
            self.reduced = trace_mod.reduce_dir(
                self.trace_dir, steps=self.log_every, chips=self.chips)

    def result(self, *, correct: bool, numbers: Dict, notes: Dict,
               reference_s: float, end_to_end: Dict) -> Dict:
        """The result object ``benchmark/run.py`` prints and the readers
        get.  ``end_to_end``: the cell's end-to-end metrics by name, but
        ``setup_s``; ``failed``: the steps of the window's log intervals
        whose logged loss is not finite."""
        edges, devices = self.edges, jax.devices()
        failed = sum(self.log_every for v in
                     self._log_loss[edges.opened + 1:edges.closed + 1]
                     if not math.isfinite(v))
        return {
            "correct": correct, "attempted": self.stats["steps"],
            "failed": failed,
            "end_to_end": dict(end_to_end, setup_s=self.setup_s),
            "window": self.stats, "setup_s": self.setup_s,
            "peak_bytes": self.peak, "bytes_limit": self.limit,
            "counters": self.counters, "trace": self.reduced,
            "reference_s": reference_s, "numbers": numbers, "notes": notes,
            "phases": dict(self.phases,
                           first_log_s=edges.logs[0][0] - self.t_start),
            "images_per_step": self.rows_per_step,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices),
                       "memory_peak_bytes": self.peak},
        }
