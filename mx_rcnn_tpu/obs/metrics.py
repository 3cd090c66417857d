"""Process-wide metrics registry: counters, gauges, log-bucket histograms.

Promoted from ``serve/metrics.py`` (which is now a thin back-compat shim
over this module) so ONE registry can carry training, data-loading,
checkpointing AND serving metrics, and a single ``/metrics`` scrape shows
the whole process.  Design constraints carried over unchanged: recording
must be cheap and lock-bounded (it runs on every request/step), and a
snapshot must be computable without storing per-sample history — so
latencies land in log-spaced fixed-bound histograms (40 buckets spanning
0.1 ms .. ~28 s at ×1.37 steps, ~±16% percentile resolution) and
percentiles are read off the cumulative counts.

Metric naming scheme (the full catalog: docs/OBSERVABILITY.md):

    <subsystem>.<metric>[_<unit>]

    train.step_ms / train.data_wait_ms / train.samples_per_sec / ...
    loader.decode_ms / loader.assemble_ms / loader.queue_depth / ...
    snapshot.stall_ms / snapshot.commit_ms / snapshot.bytes / ...
    serve.queue_wait_ms / serve.submitted / serve.batches / ...

Also here: :class:`LoweringCounter` — counts ``jax.monitoring`` lowering
events so tests/loadgen/obs-smoke can assert that a warmed program serves
steady-state traffic with ZERO new compiles — and
:func:`start_metrics_server`, the stdlib JSON ``/metrics`` exporter used
by ``tools/train.py`` (``obs.metrics_port``) and ``make obs-smoke``.
"""

from __future__ import annotations

import json
import logging
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger("mx_rcnn_tpu")


class Histogram:
    """Fixed log-spaced-bucket histogram with percentile readout.

    ``percentile`` returns the UPPER bound of the bucket holding the
    rank — a conservative (never-understated) latency estimate.
    """

    def __init__(self, lo: float = 0.1, hi: float = 30_000.0,
                 buckets: int = 40):
        # bounds[i] is the inclusive upper edge of bucket i; the last
        # bucket is open-ended (+inf) so no sample is ever dropped
        self.bounds = np.geomspace(lo, hi, buckets)
        self.counts = np.zeros(buckets + 1, np.int64)
        self.total = 0
        self.sum = 0.0
        self.max = 0.0

    def record(self, value: float) -> None:
        # Histogram is Registry-internal: every record AND every read
        # (snapshot/percentile) runs under Registry.lock — the lock just
        # lives one object up (the per-line waivers document that)
        i = int(np.searchsorted(self.bounds, value))
        self.counts[i] += 1  # threadlint: disable=TL201 guarded by Registry.lock at every call site (observe/observe_batch)
        self.total += 1      # threadlint: disable=TL201 guarded by Registry.lock at every call site (observe/observe_batch)
        self.sum += value    # threadlint: disable=TL201 guarded by Registry.lock at every call site (observe/observe_batch)
        self.max = max(self.max, value)  # threadlint: disable=TL201 guarded by Registry.lock at every call site (observe/observe_batch)

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 100]; None when empty.  Bucket-upper-bound estimate;
        the overflow bucket reports the observed max."""
        if self.total == 0:
            return None
        rank = int(np.ceil(p / 100.0 * self.total))
        rank = min(max(rank, 1), self.total)
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, rank))
        if i >= len(self.bounds):
            return float(self.max)
        return float(self.bounds[i])

    @property
    def mean(self) -> Optional[float]:
        return self.sum / self.total if self.total else None

    def summary(self) -> Dict:
        """The standard readout dict (same shape/rounding the serving
        snapshot has always used)."""
        pct = {p: self.percentile(p) for p in (50, 90, 99)}
        return {
            "count": self.total,
            "mean": None if self.mean is None else round(self.mean, 3),
            **{f"p{p}": None if v is None else round(v, 3)
               for p, v in pct.items()},
            "max": round(self.max, 3) if self.total else None,
        }


class Registry:
    """Thread-safe named counters, gauges and histograms.

    One shared lock bounds every record (a dict lookup + a few float ops
    under it — the same cost profile the serving metrics always had);
    :meth:`snapshot` reads everything consistently under the same lock.
    Metrics are created lazily on first record, so wiring a subsystem
    costs nothing until it actually records.
    """

    def __init__(self):
        self.lock = threading.RLock()
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, Histogram] = {}

    # -- record -------------------------------------------------------------
    def inc(self, name: str, n: float = 1) -> None:
        """Add ``n`` to a counter: a count, or seconds that accumulate
        (``compile.backend_s``)."""
        with self.lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_gauge(self, name: str, value: float) -> None:
        with self.lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, value: float, lo: float = 0.1,
                hi: float = 30_000.0, buckets: int = 40) -> None:
        """Record ``value`` into the named histogram (created on first
        use with the given bucket geometry)."""
        with self.lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(lo, hi, buckets)
            h.record(value)

    # -- read ---------------------------------------------------------------
    def counter(self, name: str) -> int:
        with self.lock:
            return self._counters.get(name, 0)

    def gauge(self, name: str) -> Optional[float]:
        with self.lock:
            return self._gauges.get(name)

    def hist(self, name: str) -> Optional[Histogram]:
        with self.lock:
            return self._hists.get(name)

    def names(self) -> Tuple[str, ...]:
        with self.lock:
            return tuple(sorted(set(self._counters) | set(self._gauges)
                                | set(self._hists)))

    def snapshot(self) -> Dict:
        """One consistent dict over every metric — the unified
        ``/metrics`` response body."""
        with self.lock:
            return {
                "counters": dict(sorted(self._counters.items())),
                "gauges": {k: round(v, 6) for k, v in
                           sorted(self._gauges.items())},
                "hists": {name: h.summary()
                          for name, h in sorted(self._hists.items())},
            }

    def reset(self, prefix: str = "") -> None:
        """REMOVE counters/gauges/histograms whose name starts with
        ``prefix`` (default: everything); they recreate lazily at zero on
        the next record.  Not atomic w.r.t. concurrent recorders — call
        it only between phases."""
        with self.lock:
            for d in (self._counters, self._gauges, self._hists):
                for k in [k for k in d if k.startswith(prefix)]:
                    del d[k]


# The process-wide default registry: train, loader, snapshot and (when
# wired by the CLIs) serve all record here, so one scrape sees them all.
_GLOBAL = Registry()


def registry() -> Registry:
    """The process-wide registry (see module docstring for naming)."""
    return _GLOBAL


_COUNTERS = ("submitted", "served", "shed", "expired", "failed",
             "batches", "padded_rows")


class ServeMetrics:
    """Thread-safe counters + histograms for the serving engine — now a
    facade over a :class:`Registry` (names prefixed ``serve.``) with the
    ORIGINAL snapshot format preserved bit for bit (pinned by
    ``tests/test_obs.py`` so ``tools/loadgen.py`` and the
    ``docs/serve_bench_*.json`` comparisons stay valid).

    Counters: every request increments ``submitted`` and exactly one of
    ``served`` / ``shed`` / ``expired`` / ``failed`` — the zero-lost
    accounting invariant (``submitted == sum of terminals`` once traffic
    drains).  ``batches`` counts dispatches; ``padded_rows`` counts dead
    rows shipped to keep the batch shape static.

    Histograms (milliseconds): ``queue_wait_ms`` (admission → dispatch),
    ``model_ms`` (per-batch forward+postprocess wall), ``total_ms``
    (admission → response).

    ``registry=None`` (the default) gives the engine a PRIVATE registry —
    engines stay isolated, exactly the old behavior.  Pass the process
    registry (``obs.metrics.registry()``) to publish serving metrics into
    the unified ``/metrics`` scrape (``tools/serve.py`` does when
    ``cfg.obs.enabled``).
    """

    PREFIX = "serve."

    def __init__(self, registry: Registry = None):
        self.registry = registry if registry is not None else Registry()
        self.reset()

    def reset(self) -> None:
        """Zero everything (loadgen excludes warmup from the measured
        window this way).  Not atomic w.r.t. concurrent recorders — call
        it only between traffic phases."""
        p = self.PREFIX
        with self.registry.lock:
            for k in _COUNTERS + ("rows",):
                self.registry._counters[p + k] = 0
            for h in ("queue_wait_ms", "model_ms", "total_ms"):
                self.registry._hists[p + h] = Histogram()

    # NOTE every accessor below tolerates missing keys (setdefault/get):
    # Registry.reset REMOVES entries, and a ServeMetrics sharing the
    # process registry must survive someone resetting it mid-traffic
    # instead of KeyError-ing the dispatcher thread.

    # live views kept for back-compat with pre-registry callers
    @property
    def counters(self) -> Dict[str, int]:
        with self.registry.lock:
            return {k: self.registry._counters.get(self.PREFIX + k, 0)
                    for k in _COUNTERS}

    @property
    def hists(self) -> Dict[str, Histogram]:
        with self.registry.lock:
            return {h: self.registry._hists.setdefault(self.PREFIX + h,
                                                       Histogram())
                    for h in ("queue_wait_ms", "model_ms", "total_ms")}

    def count(self, name: str, n: int = 1) -> None:
        self.registry.inc(self.PREFIX + name, n)

    def observe(self, name: str, value_ms: float) -> None:
        self.registry.observe(self.PREFIX + name, value_ms)

    def observe_batch(self, rows: int, batch_size: int,
                      model_ms: float) -> None:
        p = self.PREFIX
        with self.registry.lock:
            c = self.registry._counters
            c[p + "batches"] = c.get(p + "batches", 0) + 1
            c[p + "padded_rows"] = (c.get(p + "padded_rows", 0)
                                    + batch_size - rows)
            c[p + "rows"] = c.get(p + "rows", 0) + rows
            self.registry._hists.setdefault(p + "model_ms",
                                            Histogram()).record(model_ms)

    def in_flight(self) -> int:
        """Admitted-but-not-terminal request count — O(1) under one lock
        (five counter reads), cheap enough for the fleet router's
        per-request join-shortest-queue decision (``serve/fleet.py``),
        where a full :meth:`snapshot` per routing choice would not be."""
        p = self.PREFIX
        with self.registry.lock:
            c = self.registry._counters
            return c.get(p + "submitted", 0) - (
                c.get(p + "served", 0) + c.get(p + "shed", 0)
                + c.get(p + "expired", 0) + c.get(p + "failed", 0))

    def snapshot(self) -> Dict:
        """One consistent dict: counters, percentiles, occupancy — the
        serving ``/metrics`` response body and the loadgen record source.
        Format identical to the pre-registry ``serve/metrics.py``."""
        p = self.PREFIX
        with self.registry.lock:
            cnt = {k: self.registry._counters.get(p + k, 0)
                   for k in _COUNTERS}
            out: Dict = {"counters": cnt}
            for name in ("queue_wait_ms", "model_ms", "total_ms"):
                out[name] = self.registry._hists.setdefault(
                    p + name, Histogram()).summary()
            b = cnt["batches"]
            rows = self.registry._counters.get(p + "rows", 0)
            out["batch_occupancy"] = {
                "batches": b,
                "mean_rows": round(rows / b, 3) if b else None,
                "padded_rows": cnt["padded_rows"],
            }
            out["terminated"] = (cnt["served"] + cnt["shed"]
                                 + cnt["expired"] + cnt["failed"])
            out["in_flight"] = cnt["submitted"] - out["terminated"]
            return out


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# its seconds accumulate in the registry's ``compile.backend_s``; a
# persistent-cache read is inside it
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
# fired inside the backend compile that read its program from the cache
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# JAX's three compile phases as spans of the buffer
_PHASE_SPAN = {_TRACE_EVENT: "compile.trace", _LOWER_EVENT: "compile.lower",
               _BACKEND_EVENT: "compile.backend"}


class LoweringCounter:
    """Counts pjit lowering events (jit cache misses) inside a ``with``
    block via ``jax.monitoring`` — fired on every trace+lower regardless
    of the persistent XLA compile cache, so "zero new compiles on a
    warmed program" is assertable across cold and warm processes.

    The one listener also adds every backend compile's seconds to the
    process registry (``compile.backend_s``) and, for each lowering, emits
    a ``compile.lowering`` instant into the span buffer (``obs/trace.py``;
    a no-op unless spans are collected) carrying its seconds and the train
    step current at the time (:meth:`mark_step`; None outside the fit
    loop), so "which step recompiled" has an answer with a time on it.
    Each of JAX's three compile phases becomes a span on the compiling
    thread, ``compile.trace``, ``compile.lower`` and ``compile.backend``,
    with ``fun=`` (JAX's ``fun_name``) and ``step=``; ``compile.backend``
    also ``hit=``, 1 where the persistent cache answered inside it on that
    thread, and each backend compile counts in the registry as
    ``compile.cache_hits`` or ``compile.cache_misses``.

    Import-light: registering the listener touches jax only on first use.
    """

    _events = {"lowerings": 0}
    _registered = False
    _step: Optional[int] = None
    _hit = threading.local()   # .at: JAX's clock at the thread's last hit

    @classmethod
    def _ensure_listener(cls) -> None:
        if cls._registered:
            return
        import time

        import jax

        from mx_rcnn_tpu.obs import trace as obs_trace

        def on_event(event, duration, **kw):
            if event == _LOWER_EVENT:
                cls._events["lowerings"] += 1
                obs_trace.instant("compile.lowering", step=cls._step,
                                  lower_s=float(duration))
            elif event == _BACKEND_EVENT:
                _GLOBAL.inc("compile.backend_s", float(duration))

        def on_cache_hit(event, **kw):
            if event == _CACHE_HIT_EVENT:
                cls._hit.at = time.time()   # the clock JAX stamps spans by

        def on_span(event, start, end, **kw):
            name = _PHASE_SPAN.get(event)
            if name is None:
                return
            args = {"fun": kw.get("fun_name"), "step": cls._step}
            if event == _BACKEND_EVENT:
                hit = int(start <= getattr(cls._hit, "at", -1.0) <= end)
                _GLOBAL.inc("compile.cache_hits" if hit
                            else "compile.cache_misses")
                args["hit"] = hit
            # on the buffer's clock: it ends now and lasted what JAX says
            obs_trace.complete(name, (end - start) * 1e3, **args)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        jax.monitoring.register_event_listener(on_cache_hit)
        jax.monitoring.register_event_time_span_listener(on_span)
        cls._registered = True

    @classmethod
    def mark_step(cls, step: Optional[int]) -> None:
        """The fit loop's current global step (one attribute store): the
        ``compile.lowering`` instants carry it."""
        cls._step = step

    def __enter__(self) -> "LoweringCounter":
        self._ensure_listener()
        self._start = self._events["lowerings"]
        return self

    def __exit__(self, *exc) -> bool:
        return False

    @property
    def n(self) -> int:
        return self._events["lowerings"] - self._start


# ---------------------------------------------------------------------------
# stdlib /metrics exporter
# ---------------------------------------------------------------------------

class _MetricsHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def _reply(self, status: int, payload: Dict) -> None:
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):  # route to the repo logger
        logger.debug("obs metrics http: " + fmt, *args)

    def do_GET(self):
        if self.path == "/metrics":
            snap = self.server.registry.snapshot()
            # when the time-series plane is armed (cfg.obs.timeseries →
            # CliObs sets the active store) one scrape also answers
            # "what moved lately" — pure host-side work, no lowerings
            # (asserted by make obs-smoke)
            from mx_rcnn_tpu.obs.timeseries import active

            store = active()
            if store is not None:
                snap["timeseries"] = store.scrape_section()
            self._reply(200, snap)
        elif self.path == "/healthz":
            from mx_rcnn_tpu.obs.health import active_verdict

            payload = {"ok": True}
            verdict = active_verdict()
            if verdict is not None:
                payload["ok"] = verdict["verdict"] != "CRITICAL"
                payload["health"] = verdict
            # a CRITICAL verdict fails the probe (matches the serving
            # plane's /healthz: load balancers key on the status code)
            self._reply(200 if payload["ok"] else 503, payload)
        else:
            self._reply(404, {"error": f"no such path {self.path!r}"})


def start_metrics_server(reg: Registry = None, host: str = "127.0.0.1",
                         port: int = 0) -> ThreadingHTTPServer:
    """Start a daemon-threaded JSON ``GET /metrics`` server over ``reg``
    (default: the process registry).  ``port=0`` picks a free port (read
    it back from ``server.server_address``).  Call ``shutdown()`` +
    ``server_close()`` to stop."""
    srv = ThreadingHTTPServer((host, port), _MetricsHandler)
    srv.registry = reg if reg is not None else registry()
    t = threading.Thread(target=srv.serve_forever,
                         name="obs-metrics-http", daemon=True)
    t.start()
    logger.info("obs: /metrics on http://%s:%d", *srv.server_address[:2])
    return srv
