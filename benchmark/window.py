"""Window arithmetic: from the fit loop's synced ``log`` events to the
edges of the measured window, the rate over it and its slowest stretch.

A ``log`` event arrives right after the fit loop fetched the metrics of
the steps before it, so its host time is a synced edge.  Warm-up ends at
the first ``log`` event at or after ``warmup_steps``.  An untraced run
opens its window there; the window closes at the first ``log`` event at
least ``seconds`` later.  A traced run first traces the two intervals that
start there (the first absorbs the profiler's start-up, the reduction
takes the last interval's worth of steps), lets one more interval absorb
the profiler's write-out, and opens its window at the edge after that.
What the rate counts is said once, here: the rows of the batch of all
optimizer steps between the two edges over all the time between them
(``steps x images_per_step / seconds``, ``images_per_step`` being the rows of
one step's batch over all chips).  For the detectors' cells a row is an
image; for a cell whose batch is ``(B, S)`` token ids it is a sequence.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

TRACED_INTERVALS = 2


class Edges:
    """Decides, log event by log event, what each edge is."""

    def __init__(self, warmup_steps: int, seconds: float, traced: bool):
        self.warmup_steps, self.seconds, self.traced = (
            warmup_steps, seconds, traced)
        self.logs: List[Tuple[float, int]] = []   # (host time, step)
        self.opened: Optional[int] = None
        self.closed: Optional[int] = None
        self.trace_from: Optional[int] = None
        self.trace_to: Optional[int] = None

    def add(self, now: float, step: int) -> Optional[str]:
        """Record a log event; returns what the caller has to do at it:
        'start_trace', 'stop_trace', 'close' (the window has closed: read
        the memory, stop the loop) or nothing."""
        self.logs.append((now, step))
        i = len(self.logs) - 1
        if step < self.warmup_steps or self.closed is not None:
            return None
        if self.traced and self.trace_from is None:
            self.trace_from = i
            return "start_trace"
        if self.traced and self.trace_to is None:
            if i < self.trace_from + TRACED_INTERVALS:
                return None
            self.trace_to = i
            return "stop_trace"
        if self.opened is None:
            if not self.traced or i > self.trace_to:
                self.opened = i
            return None
        if now - self.logs[self.opened][0] >= self.seconds:
            self.closed = i
            return "close"
        return None

    def stats(self, images_per_step: int) -> dict:
        """Steps, seconds, rows of the batch per second (``imgs_per_s``)
        and the slowest stretch (ms per step of the longest interval
        between consecutive edges)."""
        edges = self.logs[self.opened:self.closed + 1]
        steps = edges[-1][1] - edges[0][1]
        seconds = edges[-1][0] - edges[0][0]
        slowest = max((b[0] - a[0]) / (b[1] - a[1])
                      for a, b in zip(edges, edges[1:]))
        return {"steps": steps, "seconds": seconds,
                "imgs_per_s": steps * images_per_step / seconds,
                "slowest_ms_per_step": slowest * 1e3,
                "mean_ms_per_step": seconds / steps * 1e3}
