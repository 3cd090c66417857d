"""Structured run records: ``runs/<id>/events.jsonl`` + BENCH summary.

No reference equivalent — the reference's only run artifact is the stdout
log.  Every ``tools/train.py`` / ``tools/serve.py`` invocation with
``obs.enabled`` writes:

* ``runs/<id>/events.jsonl`` — one JSON object per line, appended live,
  schema::

      {"ts": <unix seconds>, "event": "<kind>", ...payload}

  Crash contract (LINE-GRANULAR, verified by the crashsim regression in
  ``tests/test_persistlint.py``): each line is flushed to the kernel as
  it is written (line buffering), so a PROCESS crash loses nothing; a
  HOST crash may lose an un-fsynced tail and may tear the last line at
  a byte boundary — readers must treat ``events.jsonl`` as "every fully
  parseable line is real, a torn tail line is the crash point", never
  as an atomic document.  ``close()`` fsyncs the stream so a finished
  run's final flush is durable.  Per-event fsync (or an atomic rewrite
  per event) would serialize the training/serving hot path behind disk
  latency, which the line-granular contract exists to avoid.
* ``runs/<id>/summary.json`` — ONE final BENCH-compatible record
  (``{"metric": ..., "value": ..., "measured": ...}`` like
  ``tools/loadgen.py`` emits) plus the closing snapshot of the
  process metrics registry, so a finished run is analyzable without
  re-parsing the event stream.  Unlike the event stream this IS an
  atomic document (one shot, read as a whole), so it goes through
  ``utils/checkpoint._atomic_write`` — a crash during the final write
  leaves the previous state, never a torn half-summary that parses as
  a finished run.
* ``runs/<id>/trace.json`` / ``runs/<id>/profile/`` — chrome trace and
  profiler windows, when those subsystems are enabled (written by the
  CLIs, not by this class).

The record never throws into the training path: write failures log and
disable the record (observability must not kill the run it observes).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from typing import Dict, Optional

logger = logging.getLogger("mx_rcnn_tpu")


def _jsonable(obj):
    """Fallback serializer: numpy scalars/arrays and anything else that
    sneaks into an event payload degrade to plain types, never a crash."""
    for attr in ("item",):
        if hasattr(obj, attr):
            try:
                return obj.item()
            except Exception:
                pass
    if hasattr(obj, "tolist"):
        try:
            return obj.tolist()
        except Exception:
            pass
    return repr(obj)


class RunRecord:
    """One run directory under ``base_dir`` with a live event stream.

    ``run_id`` defaults to ``<kind>-<utc timestamp>-<pid>`` — unique per
    process without coordination.  Thread-safe: the fit loop, snapshot
    writer and HTTP threads may all emit events.
    """

    def __init__(self, kind: str, base_dir: str = "runs",
                 run_id: Optional[str] = None):
        self.kind = kind
        self.run_id = run_id or "{}-{}-{}".format(
            kind, time.strftime("%Y%m%d-%H%M%S", time.gmtime()),
            os.getpid())
        self.dir = os.path.join(base_dir, self.run_id)
        self.events_path = os.path.join(self.dir, "events.jsonl")
        self.summary_path = os.path.join(self.dir, "summary.json")
        self._lock = threading.Lock()
        self._n = 0
        self._dead = False
        self._listeners = []
        try:
            os.makedirs(self.dir, exist_ok=True)
            # persistlint: disable=PL101 append-only event stream with a LINE-GRANULAR crash contract (module docstring): each line is kernel-flushed, readers tolerate a torn tail line, close() fsyncs; an atomic rewrite per event would put disk latency on the hot path
            self._f = open(self.events_path, "a", buffering=1)
        except OSError as e:
            logger.warning("obs runrec: cannot open %s (%s) — run record "
                           "disabled", self.events_path, e)
            self._f, self._dead = None, True
        self.event("run_start", kind=kind, pid=os.getpid(),
                   argv=list(sys.argv))

    def add_listener(self, fn) -> None:
        """Register ``fn(event_dict)`` to observe every event as it is
        emitted — the flight recorder (``obs/flightrec.py``) rings the
        run's events into its black box this way, with zero extra
        instrumentation at the emit sites.  Listeners run on the
        emitting thread and must be cheap; exceptions log and never
        propagate into the emitter."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    def event(self, event: str, **payload) -> None:
        """Append one event line (flushed immediately); never raises."""
        if self._dead:
            return
        rec = {"ts": round(time.time(), 6), "event": event, **payload}
        try:
            line = json.dumps(rec, default=_jsonable)
        except (TypeError, ValueError) as e:
            logger.warning("obs runrec: unserializable event %r: %s",
                           event, e)
            return
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(rec)
            except Exception:
                logger.exception("obs runrec: event listener failed")
        with self._lock:
            if self._dead:
                return
            try:
                self._f.write(line + "\n")
                self._n += 1
            except OSError as e:
                logger.warning("obs runrec: write failed (%s) — run "
                               "record disabled", e)
                self._dead = True

    @property
    def num_events(self) -> int:
        return self._n

    def finish(self, metric: Optional[str] = None, value=None,
               unit: Optional[str] = None, registry=None,
               **extra) -> Dict:
        """Write the final BENCH-compatible ``summary.json`` (and a
        closing ``run_finish`` event).  ``registry`` (default: the
        process registry) snapshots into the summary under
        ``"metrics"``."""
        if registry is None:
            from mx_rcnn_tpu.obs.metrics import registry as _registry

            registry = _registry()
        self.event("run_finish", metric=metric, value=value)
        summary = {
            "metric": metric or f"{self.kind}_run",
            "value": value,
            "unit": unit,
            "measured": value is not None,
            "run_id": self.run_id,
            "kind": self.kind,
            "events": self._n,
            **extra,
            "metrics": registry.snapshot(),
        }
        try:
            from mx_rcnn_tpu.utils.checkpoint import _atomic_write

            _atomic_write(self.summary_path,
                          json.dumps(summary, indent=1,
                                     default=_jsonable).encode())
        except OSError as e:
            logger.warning("obs runrec: summary write failed: %s", e)
        return summary

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                try:
                    # the final flush is durable: a run that reported
                    # success must not lose its closing events to a host
                    # crash (the line-granular contract's one fsync)
                    self._f.flush()
                    os.fsync(self._f.fileno())
                except (OSError, ValueError) as e:
                    logger.warning("obs runrec: final event-stream "
                                   "fsync failed (%s) — a host crash "
                                   "may lose the closing events", e)
                try:
                    self._f.close()
                except OSError:
                    pass
                self._f = None
            self._dead = True


class CliObs:
    """The shared ``tools/train.py`` / ``tools/serve.py`` obs wiring:
    run record + optional span trace + optional ``/metrics`` exporter +
    optional SIGUSR2 profiler toggle, with a FAIL-SOFT teardown — one
    place to keep the two CLIs in sync, and nothing in setup-after-record
    or teardown may mask the run's own exception or fail a successful
    run (the runrec invariant: observability never throws into the path
    it observes).

    Build with :func:`cli_obs` (returns None when ``cfg.obs`` is off);
    read ``.record`` for the RunRecord to thread into the run; call
    :meth:`close` exactly once from a ``finally``.
    """

    def __init__(self, cfg, kind: str):
        self.cfg = cfg
        self.record = RunRecord(kind, base_dir=cfg.obs.run_dir)
        logger.info("obs: run record -> %s", self.record.dir)
        self._metrics_srv = None
        self.store = None
        self.sampler = None
        self.health = None
        self.flight = None
        try:
            from mx_rcnn_tpu.obs import trace as obs_trace

            if cfg.obs.trace:
                obs_trace.enable(cfg.obs.trace_cap)
            if cfg.obs.metrics_port:
                from mx_rcnn_tpu.obs.metrics import start_metrics_server

                self._metrics_srv = start_metrics_server(
                    port=cfg.obs.metrics_port)
            if cfg.obs.sigusr2:
                from mx_rcnn_tpu.obs.profiler import install_sigusr2

                install_sigusr2(self.record.dir)
        except Exception:
            logger.exception("obs: CLI wiring failed — continuing "
                             "without the failed piece")
        # time-series plane (obs/timeseries.py + health.py + flightrec
        # .py — docs/OBSERVABILITY.md "Time-series plane"): the sampler
        # drives the ring store AND the health engine on one daemon
        # thread; the flight recorder rings runrec events and arms the
        # crash/SIGTERM/watchdog triggers.  Same fail-soft posture as
        # the block above.
        try:
            if (cfg.obs.timeseries or cfg.obs.health
                    or cfg.obs.flight):
                from mx_rcnn_tpu.obs import timeseries as obs_ts
                from mx_rcnn_tpu.obs.metrics import registry

                self.store = obs_ts.TimeSeriesStore(cfg.obs.ts_capacity)
                obs_ts.set_active(self.store)
                if cfg.obs.flight:
                    from mx_rcnn_tpu.obs import flightrec

                    self.flight = flightrec.FlightRecorder(
                        self.store, self.record.dir,
                        window_s=cfg.obs.flight_window_s,
                        max_events=cfg.obs.flight_events)
                    self.record.add_listener(self.flight.note_event)
                    flightrec.set_active(self.flight)
                    self.flight.arm(
                        signals=threading.current_thread()
                        is threading.main_thread())
                if cfg.obs.health:
                    from mx_rcnn_tpu.obs import health as obs_health

                    self.health = obs_health.HealthEngine(
                        obs_health.default_rules(cfg), self.store,
                        registry=registry(), record=self.record,
                        on_transition=(self.flight.on_health_transition
                                       if self.flight else None))
                    obs_health.set_active_engine(self.health)
                self.sampler = obs_ts.Sampler(
                    self.store, interval_s=cfg.obs.sample_interval_s,
                    after_sample=(self.health.evaluate_sample
                                  if self.health else None))
                self.sampler.start()
        except Exception:
            logger.exception("obs: time-series wiring failed — "
                             "continuing without the failed piece")

    def close(self, metric: Optional[str] = None, value=None,
              unit: Optional[str] = None, **extra) -> None:
        """Export the chrome trace (if spans were collected), write the
        BENCH summary, stop the exporter.  Never raises."""
        try:
            if self.sampler is not None:
                # one last sample (and health pass) so the ring's tail
                # reflects shutdown state before the summary snapshots
                self.sampler.stop(final_sample=True)
        except Exception:
            logger.exception("obs: sampler stop failed")
        try:
            from mx_rcnn_tpu.obs import trace as obs_trace

            # obs.trace's one meaning for a train run (train_net collects
            # spans under obs.enabled alone): write them out at exit
            if self.cfg.obs.trace:
                obs_trace.export_chrome_trace(
                    os.path.join(self.record.dir, "trace.json"))
        except Exception:
            logger.exception("obs: chrome-trace export failed")
        try:
            self.record.finish(metric=metric, value=value, unit=unit,
                               **extra)
        except Exception:
            logger.exception("obs: run summary write failed")
        self.record.close()
        try:
            if self.flight is not None:
                from mx_rcnn_tpu.obs import flightrec

                self.flight.disarm()
                flightrec.set_active(None)
            if self.health is not None:
                from mx_rcnn_tpu.obs import health as obs_health

                obs_health.set_active_engine(None)
            if self.store is not None:
                from mx_rcnn_tpu.obs import timeseries as obs_ts

                obs_ts.set_active(None)
        except Exception:
            logger.exception("obs: time-series teardown failed")
        if self._metrics_srv is not None:
            try:
                self._metrics_srv.shutdown()
                self._metrics_srv.server_close()
            except Exception:
                logger.exception("obs: metrics exporter shutdown failed")


def cli_obs(cfg, kind: str) -> Optional[CliObs]:
    """:class:`CliObs` when ``cfg.obs.enabled``, else None — so callers
    write ``obs_sess = cli_obs(cfg, "train")`` and guard on None."""
    if getattr(cfg, "obs", None) is not None and cfg.obs.enabled:
        return CliObs(cfg, kind)
    return None
