"""Unified observability (docs/OBSERVABILITY.md) — one answer to "where
did this step/request spend its time?" across training, data loading,
checkpointing and serving (ISSUE 4).

Before this package the repo had three disjoint fragments: the serving
histograms (``serve/metrics.py``), the XSpace decoder
(``utils/xplane.py``), and the ``Speedometer``
stdout line in ``core/fit.py`` — none of which could see each other or
the ``ft/`` snapshot path.  Layers, bottom-up:

* ``metrics.py``  — process-wide :class:`Registry` (counters, gauges,
  log-bucket histograms) + the promoted ``Histogram`` /
  ``LoweringCounter`` / ``ServeMetrics`` (``serve.metrics`` is now a
  back-compat shim over this module) + the stdlib ``/metrics`` HTTP
  exporter;
* ``trace.py``    — cheap host-side spans (``with span("h2d")``) with a
  trace-context id propagated through the serve request lifecycle and
  the train loop, exported as chrome-trace JSON that merges with the
  XLA device timeline via ``utils/xplane.py`` timestamps;
* ``profiler.py`` — on-demand ``jax.profiler`` windows (config
  ``obs.profile_at_step`` or SIGUSR2 on a live process), auto-rolled-up
  by ``utils/xplane.py — summarize_device_time``;
* ``runrec.py``   — structured run records: every ``tools/train.py`` /
  ``tools/serve.py`` run writes ``runs/<id>/events.jsonl`` plus a final
  BENCH-compatible ``summary.json``.

The fleet-wide time-series plane (ISSUE 14) stacks on top:

* ``timeseries.py`` — bounded ring-buffer store sampling the registry
  (counters→windowed rates, gauges, exact windowed histogram
  percentiles) on a daemon-thread cadence;
* ``collect.py``    — cross-process aggregation: N replica/worker
  registries (in-process or scraped over ``/metrics``) merged into one
  source-labeled, generation-tagged fleet view;
* ``health.py``     — declarative SLO rules over the time-series
  windows → OK/WARN/CRITICAL verdict as gauges + runrec events +
  enriched ``/healthz`` + exit codes (``tools/obs.py check``);
* ``flightrec.py``  — black-box flight recorder: last-N-seconds of
  samples + spans + events dumped to ``runs/<id>/flight/`` on crash,
  SIGTERM, lock-watchdog trip, or a health-critical transition.

Everything is DISABLED by default (``cfg.obs.enabled``); the disabled
hot-path cost is pinned near zero by ``tests/test_obs.py`` and the
sampling-enabled overhead by ``tools/obs_smoke.py --overhead_out``
(<2% acceptance bar, docs/obs_overhead.json).
"""

from mx_rcnn_tpu.obs.metrics import (Histogram, LoweringCounter,  # noqa: F401
                                     Registry, ServeMetrics, registry,
                                     start_metrics_server)
from mx_rcnn_tpu.obs.runrec import RunRecord  # noqa: F401
from mx_rcnn_tpu.obs.timeseries import (Sampler,  # noqa: F401
                                        TimeSeriesStore)
