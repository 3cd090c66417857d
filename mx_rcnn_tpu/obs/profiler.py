"""On-demand ``jax.profiler`` windows with automatic xplane rollup.

No reference equivalent (the reference has no profiler wiring at all —
SURVEY.md §5.1).  Two triggers, one mechanism:

* **config** — ``obs.profile_at_step=N`` makes the fit loop capture a
  ``obs.profile_steps``-step window starting at global step N
  (:class:`StepProfiler`, wired in ``core/fit.py``);
* **signal** — :func:`install_sigusr2` arms a live process: the first
  ``SIGUSR2`` starts a window, the second stops it — profile a
  production run mid-flight without restarting it
  (``kill -USR2 <pid>`` twice; runbook in docs/OBSERVABILITY.md).

Either way the captured trace is auto-rolled-up by
``utils/xplane.py — summarize_device_time`` into per-scope and
per-op-class device-time tables written next to the trace
(``rollup.json``) — the answer a human wants, without opening
TensorBoard.

Only ONE window can be open at a time (module-level guard;
``start_window`` fails soft with a log line if the profiler is busy —
e.g. a caller that drives ``jax.profiler`` itself, as the benchmark's
traced run does).  These two triggers are the program's only ways to
open a window.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import signal
import threading
from typing import Callable, Dict, Optional

logger = logging.getLogger("mx_rcnn_tpu")

_lock = threading.Lock()
_active_dir: Optional[str] = None


def newest_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output dir."""
    pbs = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                 "*", "*.xplane.pb"))
    return max(pbs, key=os.path.getmtime) if pbs else None


# inline-rollup trace-size cap: the pure-Python protobuf walk is
# ~seconds per 10 MB; a long-open window over a fast loop can write
# hundreds of MB (observed live: a ~60 s window -> 432 MB xplane whose
# parse starved a 1-core box for minutes).  Bigger traces skip the
# inline parse with a pointer to the offline path.
MAX_ROLLUP_BYTES = 64 << 20


def rollup(trace_dir: str, depth: int = 1) -> Dict:
    """Roll the newest xplane under ``trace_dir`` up into device-time
    tables: ``{"by_scope": {plane: {scope: ms}}, "by_op_class": ...}``.
    Empty dict when no trace was captured; for traces over
    :data:`MAX_ROLLUP_BYTES` the parse is SKIPPED (a ``"skipped"`` note
    replaces the tables) — summarize offline with
    ``utils/xplane.py``'s ``summarize_device_time(parse_xspace(pb))``."""
    pb = newest_xplane(trace_dir)
    if pb is None:
        return {}
    size = os.path.getsize(pb)
    if size > MAX_ROLLUP_BYTES:
        note = (f"trace is {size >> 20} MB (> {MAX_ROLLUP_BYTES >> 20} MB "
                "inline cap) — keep profile windows short; summarize "
                "offline: python -c \"from mx_rcnn_tpu.utils.xplane import "
                "parse_xspace, summarize_device_time; "
                f"print(summarize_device_time(parse_xspace('{pb}')))\"")
        logger.warning("obs profiler: %s", note)
        return {"xplane": pb, "skipped": note,
                "by_scope": {}, "by_op_class": {}}
    from mx_rcnn_tpu.utils.xplane import (category_of, parse_xspace,
                                          summarize_device_time)

    planes = parse_xspace(pb)  # parse once, summarize twice
    return {
        "xplane": pb,
        "by_scope": summarize_device_time(planes, depth=depth),
        "by_op_class": summarize_device_time(planes, key=category_of),
    }


def start_window(out_dir: str) -> bool:
    """Open a profiler window into ``out_dir``.  Fails SOFT (False + log)
    when a window is already open or the profiler is busy — a profiling
    hiccup must never kill a training/serving process."""
    global _active_dir
    with _lock:
        if _active_dir is not None:
            logger.warning("obs profiler: window already open (%s)",
                           _active_dir)
            return False
        try:
            import jax.profiler

            os.makedirs(out_dir, exist_ok=True)
            jax.profiler.start_trace(out_dir)
        except Exception as e:  # profiler busy / backend quirk
            logger.warning("obs profiler: could not start window: %s", e)
            return False
        _active_dir = out_dir
    logger.info("obs profiler: window started -> %s", out_dir)
    return True


def stop_window(sync: Callable[[], None] = None) -> Dict:
    """Close the open window, write ``rollup.json`` next to the trace and
    return the rollup dict.  ``sync`` (e.g. ``jax.block_until_ready`` on
    the last step's outputs) runs first so in-flight device work lands
    inside the window."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            return {}
        out_dir, _active_dir = _active_dir, None
        try:
            import jax.profiler

            if sync is not None:
                sync()
            jax.profiler.stop_trace()
        except Exception as e:
            logger.warning("obs profiler: could not stop window: %s", e)
            return {}
    roll = rollup(out_dir)
    path = os.path.join(out_dir, "rollup.json")
    try:
        with open(path, "w") as f:
            json.dump(roll, f, indent=1)
    except OSError as e:
        logger.warning("obs profiler: rollup write failed: %s", e)
    _log_rollup(roll)
    logger.info("obs profiler: window closed -> %s (rollup.json)", out_dir)
    return roll


def _log_rollup(roll: Dict, top: int = 8) -> None:
    for plane, groups in roll.get("by_op_class", {}).items():
        total = sum(groups.values())
        if not total:
            continue
        logger.info("obs profiler: %s device time by op class "
                    "(total %.2f ms):", plane, total)
        for g, ms in list(groups.items())[:top]:
            logger.info("  %-36s %9.3f ms  %5.1f%%", g, ms,
                        100 * ms / total)


class StepProfiler:
    """Config-triggered window for the fit loop: starts at global step
    ``at_step``, captures ``steps`` steps, rolls up, stays inert
    otherwise.  ``on_step`` is called once per executed step with the
    global step index and a ``sync`` thunk."""

    def __init__(self, out_dir: str, at_step: int, steps: int = 3):
        self.out_dir = out_dir
        self.at_step = at_step
        self.steps = max(int(steps), 1)
        self._started = False
        self._done = False
        self._stop_at = None
        self.result: Dict = {}

    def on_step(self, step: int, sync: Callable[[], None] = None) -> None:
        if self._done or self.at_step <= 0:
            return
        if not self._started and step >= self.at_step:
            self._started = start_window(self.out_dir)
            self._done = not self._started
            self._stop_at = step + self.steps
        elif self._started and step >= self._stop_at:
            self.result = stop_window(sync)
            self._done = True

    def close(self, sync: Callable[[], None] = None) -> None:
        """Epoch/loop ended with the window still open (e.g. the run was
        shorter than ``at_step + steps``): close it now."""
        if self._started and not self._done:
            self.result = stop_window(sync)
            self._done = True


def install_sigusr2(out_dir: str) -> Callable:
    """Arm SIGUSR2 as a profiler toggle: first signal starts a window
    under ``out_dir/sigusr2-<n>``, the next stops it and writes the
    rollup.  Returns the installed handler (the tests drive it via
    ``signal.raise_signal``).

    The handler itself only flips state and spawns a daemon worker
    thread — it must NOT call ``jax.profiler`` inline: a signal handler
    runs on the main thread at an arbitrary bytecode boundary, possibly
    with the interrupted frame holding jax runtime locks, and
    ``stop_trace`` from inside it deadlocks (observed live: a training
    process wedged in 'Sl' until SIGKILL).  The worker serializes
    toggles through a queue-less chain so start/stop cannot race each
    other, and everything fails soft — a profiling problem must never
    take down the process it is observing."""
    state = {"open": False, "n": 0, "worker": None}

    def toggle():
        try:
            if not state["open"]:
                d = os.path.join(out_dir, f"sigusr2-{state['n']}")
                state["open"] = start_window(d)
            else:
                stop_window()
                state["open"] = False
                state["n"] += 1
        except Exception:  # pragma: no cover - belt and braces
            logger.exception("obs profiler: SIGUSR2 toggle failed")

    def handler(signum, frame):
        prev = state["worker"]
        if prev is not None and prev.is_alive():
            # a toggle is still in flight (e.g. a large trace being
            # rolled up) — drop this signal instead of racing it
            logger.warning("obs profiler: SIGUSR2 ignored, previous "
                           "toggle still running")
            return
        t = threading.Thread(target=toggle, name="obs-sigusr2-toggle",
                             daemon=True)
        state["worker"] = t
        t.start()

    signal.signal(signal.SIGUSR2, handler)
    logger.info("obs profiler: SIGUSR2 armed (kill -USR2 %d to toggle a "
                "profile window under %s)", os.getpid(), out_dir)
    return handler
