"""Serving fleet: replica manager + join-shortest-queue front-end router.

No reference equivalent — this is the tier above ``serve/engine.py``
(ROADMAP item 2): N replica engines, each a full
:class:`~mx_rcnn_tpu.serve.engine.ServingEngine` over its OWN
``Predictor`` on its own device subset (a subset of size > 1 becomes the
replica's 1-D data mesh — the mesh-sharded inference math from
``core/tester.py``, per replica), behind a router that:

* **spreads load** by batch-aware join-shortest-queue: primary key is
  the batch-cycle backlog of the request's own bucket lane
  (``ServingEngine.bucket_depth``), so same-bucket traffic packs full
  micro-batches; per-replica in-flight depth
  (``ServeMetrics.in_flight`` — one lock, five counter reads) breaks
  ties, a rotating index breaks those;
* **composes with the existing overload semantics** rather than
  replacing them: deadlines are fleet-scoped (a reroute never extends
  one; a request that expires DURING routing terminates EXPIRED before
  touching a replica), and shed stays watermark-driven — JSQ routes to
  the least-loaded replica, so an admission shed there means every
  replica is at/over its watermark and the fleet answer is 429;
* **keeps the terminate-exactly-once invariant fleet-wide**: the
  client-facing :class:`FleetRequest` reaches exactly one terminal state
  no matter how many replica-level requests served it (a replica that
  dies with queued work FAILs it; the router re-dispatches within the
  deadline up to ``fleet.reroute_retries`` times, then fails honestly);
* **ejects and relaunches**: a health monitor removes dead replicas from
  the routing set, terminates their stranded work (which reroutes), and
  rebuilds them through the ``ft/supervisor.py — RestartPolicy`` backoff
  schedule — repeated identical launch failures become a crash-loop
  verdict instead of an infinite rebuild loop.

Cold replicas join warm-from-export (``serve/export.py``) in seconds:
deserialized AOT programs install straight into the Predictor's program
cache, so a join pays neither tracing nor (with the bundled persistent
cache) XLA compilation.  Architecture + measured numbers:
docs/SERVING.md "Fleet tier".
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.obs import trace as obs_trace
from mx_rcnn_tpu.obs.metrics import Registry, ServeMetrics
from mx_rcnn_tpu.obs.metrics import registry as process_registry
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.queue import (EXPIRED, FAILED, PENDING, SERVED, SHED,
                                     RequestFailed, ServeRequest)

logger = logging.getLogger("mx_rcnn_tpu")

# drain_replica "any version" sentinel (None is a real version — the
# boot model — so a default arg can't be None)
_ANY_VERSION = object()

# replica lifecycle states (healthz-visible)
R_STARTING = "starting"
R_READY = "ready"
R_EJECTED = "ejected"
R_RELAUNCHING = "relaunching"
R_DEAD = "dead"          # crash-loop verdict or relaunch disabled


def jsq_key(lane_depth: int, total_depth: int, rid: int, rot: int,
            n_cands: int, batch: int) -> Tuple[int, int, int]:
    """Batch-aware JSQ sort key — pick the candidate with the SMALLEST.

    Primary is ``ceil((lane_depth + 1) / batch)``: how many dispatch
    cycles until a request appended to this candidate's bucket lane
    would serve, so same-bucket traffic packs full batches and spreads
    lanes evenly.  Total in-flight depth breaks cycle ties, a rotating
    index breaks those.  Pure (no replica objects) so the fleet-scale
    simulator routes with the SHIPPED decision logic, not a copy."""
    cycles = -(-(int(lane_depth) + 1) // int(batch))
    return (cycles, int(total_depth), (int(rid) + int(rot)) % int(n_cands))


class FleetMetrics(ServeMetrics):
    """Fleet-level request accounting: same counters / histograms /
    snapshot format as :class:`ServeMetrics` (so ``serve/server.py`` and
    the loadgen read a router exactly like an engine) under the
    ``fleet.`` prefix — per-replica engines keep their own ``serve.``
    metrics in PRIVATE registries, so fleet and replica counts never
    double-report into one scrape."""

    PREFIX = "fleet."


class FleetRequest(ServeRequest):
    """The client-facing handle: one terminal state, fleet-wide.

    ``image`` holds the RAW client image (replica engines preprocess per
    dispatch — a reroute re-resizes, trading a few host ms for not
    caching canvases twice); it is dropped at the terminal transition so
    a drained burst holds no pixel memory.
    """

    __slots__ = ("attempts", "tried", "replica_id", "prepared", "source",
                 "version", "tparent")

    def __init__(self, image: np.ndarray, deadline: Optional[float],
                 now: float, im_info: np.ndarray = None,
                 bucket: Tuple[int, int] = None, prepared: bool = False,
                 source: bool = False):
        super().__init__(image, im_info, bucket, deadline, now)
        self.attempts = 0          # dispatches so far (1 = no reroute)
        self.tried: set = set()    # replica ids already dispatched to
        self.replica_id: Optional[int] = None  # last dispatch target
        # model version of the last dispatch target (rollout plane):
        # stamps the per-version exactly-once accounting at terminal
        self.version: Optional[str] = None
        # bulk plane (serve/bulk.py): image is the ALREADY-preprocessed
        # fp32 bucket canvas and im_info its record — dispatch goes
        # through ``ServingEngine.submit_prepared`` (a reroute re-offers
        # the same canvas; there is no raw image to re-resize)
        self.prepared = prepared
        # v2 wire plane (serve/remote.py): image is the resized-but-
        # unnormalized u8 source with bucket/im_info already resolved —
        # dispatch goes through ``submit_source`` (local engines
        # pad+normalize at admission, remote engines ship the small u8
        # frame; a reroute re-offers the SAME source bytes elsewhere)
        self.source = source
        # distributed tracing: the span id this request's root span
        # nests under (0 = head-originated; inbound contexts carry the
        # upstream parent).  ``tctx``'s own parent is the ROOT span id
        # every attempt/terminal span nests under.
        self.tparent = 0


class Replica:
    """One managed serving replica: engine + lifecycle + restart pacing.

    ``build_fn(replica_id) -> (engine, join_stats)`` builds a WARMED
    engine (export-warm or trace-warm — the manager records which and
    how long).  All state transitions happen under ``_lock``; the
    routing set reads ``ready()`` lock-free-ish (one lock hop).

    ``version`` (class default None = the boot model) tags which model
    version this replica serves — each replica owns its build_fn, so a
    rollout builds v2 replicas from the v2 store while v1 replicas keep
    their original closure, side by side in one routing set.
    """

    version: Optional[str] = None

    def __init__(self, rid: int,
                 build_fn: Callable[[int], Tuple[ServingEngine, Dict]],
                 policy=None):
        from mx_rcnn_tpu.ft.supervisor import RestartPolicy

        self.id = rid
        self.build_fn = build_fn
        self.engine: Optional[ServingEngine] = None
        self.state = R_STARTING
        self.closed = False        # manager shut down: launches refuse
        self.generation = 0        # successful launches
        self.joins: List[Dict] = []
        self.relaunch_at: Optional[float] = None
        # private registry: N policies would otherwise fight over the
        # shared ft.supervisor.* gauge names
        self.policy = policy or RestartPolicy(seed=rid,
                                              registry=Registry())
        self._lock = threading.RLock()

    def launch(self) -> bool:
        """Build + warm the engine (blocking; seconds export-warm).
        Returns success; the caller owns failure pacing."""
        with self._lock:
            if self.closed:
                return False
            self.state = R_STARTING
        try:
            t0 = time.perf_counter()
            engine, join = self.build_fn(self.id)
        except Exception:
            logger.exception("replica %d launch failed", self.id)
            with self._lock:
                self.engine = None
            return False
        join = dict(join or {})
        join["join_s"] = round(time.perf_counter() - t0, 3)
        join["ready_t"] = time.monotonic()  # rejoin-latency accounting
        with self._lock:
            if self.closed:
                # manager closed while this build was in flight: a late
                # READY would resurrect the replica with an engine
                # nobody will ever close
                self.state = R_DEAD
                stale = engine
            else:
                stale = None
        if stale is not None:
            stale.close()
            return False
        with self._lock:
            self.engine = engine
            self.generation += 1
            self.joins.append(join)
            self.state = R_READY
        logger.info("replica %d ready (generation %d, join %.2fs, %s)",
                    self.id, self.generation, join["join_s"],
                    "export-warm" if join.get("export_root")
                    else "trace-warm")
        return True

    def ready(self) -> bool:
        with self._lock:
            return self.state == R_READY and self.engine is not None

    def depth(self) -> float:
        """JSQ signal; an unready replica reads infinitely deep."""
        with self._lock:
            if self.state != R_READY or self.engine is None:
                return float("inf")
            return self.engine.depth()

    def describe(self) -> Dict:
        with self._lock:
            eng = self.engine
            d = {"id": self.id, "state": self.state,
                 "generation": self.generation,
                 "version": self.version,
                 "last_join_s": (self.joins[-1]["join_s"]
                                 if self.joins else None)}
            if eng is not None and self.state == R_READY:
                d["depth"] = eng.depth()
                d["programs"] = eng.program_count()
                d["export_root"] = eng._export_root
            return d


class ReplicaManager:
    """Owns the replica set: boot, health monitoring, eject, relaunch.

    The health loop (every ``fleet.health_interval_s``) ejects replicas
    whose engine died (closed, or a bucket dispatcher thread gone —
    its bucket would be permanently unserved), kills their stranded
    queue (FAILED → the router reroutes), and relaunches on the
    RestartPolicy schedule in a dedicated thread so one slow rebuild
    never blinds monitoring of the others.  ``made_progress`` for the
    policy = the dead generation served at least one request, so a
    replica that keeps dying before its first serve escalates to the
    crash-loop verdict while preemption-style churn restarts freely.
    """

    def __init__(self, build_fn: Callable[[int], Tuple[ServingEngine, Dict]],
                 cfg: Config, registry: Registry = None, record=None,
                 replica_cls: type = None):
        if cfg.fleet.replicas < 1:
            raise ValueError(
                f"fleet.replicas must be >= 1, got {cfg.fleet.replicas}")
        self.cfg = cfg
        # replica_cls: the cross-host plane manages RemoteReplica
        # (serve/remote.py) through this same lifecycle
        self._replica_cls = replica_cls or Replica
        self._build_fn = build_fn
        # the version plain resize-adds are tagged with (the rollout
        # plane repoints this together with _build_fn when a host
        # completes a swap, so scheduler adds keep building v2)
        self.default_version: Optional[str] = None
        self.replicas = [self._replica_cls(i, build_fn)
                         for i in range(cfg.fleet.replicas)]
        # resize surface (serve/scheduler.py → agent /replicas): list
        # mutations only under this lock; readers iterate snapshots
        self._resize_lock = threading.Lock()
        self._next_rid = cfg.fleet.replicas
        self.registry = registry or process_registry()
        # optional RunRecord (obs/runrec.py): eject/rejoin land in
        # runs/<id>/events.jsonl — and through the record's listener
        # hook in the flight recorder's black box, so a kill-mid-burst
        # dump names the ejected replica (tools/fleet.py wires it)
        self.record = record
        self.ejects = 0
        self.relaunches = 0
        # eject (health-monitor thread) and relaunch (per-replica rebuild
        # threads) bump these concurrently; += on a plain int loses
        # updates under interleaving (threadlint TL201; regression:
        # test_fleet.py — test_manager_counters_are_thread_safe)
        self._counts_lock = threading.Lock()
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> "ReplicaManager":
        """Launch every replica (sequentially — replica warmups contend
        for the same host cores; concurrent builds measured slower on
        the 1-core tier) then start the health monitor."""
        for r in self.replicas:
            if not r.launch():
                self._schedule_relaunch(r, ("boot-failed",),
                                        made_progress=False)
        self._monitor = threading.Thread(target=self._health_loop,
                                         name="fleet-health", daemon=True)
        self._monitor.start()
        return self

    def close(self, timeout: float = 10.0) -> None:
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout)
        for r in list(self.replicas):
            with r._lock:
                r.closed = True
                eng, r.engine, r.state = r.engine, None, R_DEAD
            if eng is not None:
                eng.close(timeout)

    # ------------------------------------------------------------------
    # routing set
    # ------------------------------------------------------------------

    def ready_replicas(self) -> List[Replica]:
        return [r for r in list(self.replicas) if r.ready()]

    def versions(self) -> Dict[str, int]:
        """Ready capacity per model-version label (rollout status
        surface; 'base' is the boot version)."""
        from mx_rcnn_tpu.serve.rollout import version_label

        out: Dict[str, int] = {}
        for r in self.ready_replicas():
            lbl = version_label(r.version)
            out[lbl] = out.get(lbl, 0) + 1
        return out

    # ------------------------------------------------------------------
    # resize (the scheduler's add/drain surface — serve/scheduler.py
    # drives it through the agent's POST /replicas)
    # ------------------------------------------------------------------

    def add_replica(self, build_fn: Callable = None,
                    version: str = None) -> Replica:
        """Grow the set by one replica (fresh id — ids are never
        reused, so per-replica gauges and flight records stay
        unambiguous).  The launch runs on its own thread: the caller
        (an HTTP control handler) must not block for a multi-second
        warmup; a boot failure lands in the standard RestartPolicy
        relaunch schedule.

        ``build_fn``/``version`` (rollout plane): build this replica
        from a DIFFERENT store than the boot set — a v2 replica joins
        the same routing set tagged with its version; default keeps the
        manager's boot build_fn and the boot (None) version."""
        with self._resize_lock:
            rid = self._next_rid
            self._next_rid += 1
            r = self._replica_cls(rid, build_fn or self._build_fn)
            r.version = (version if (version is not None
                                     or build_fn is not None)
                         else self.default_version)
            self.replicas.append(r)
        if self.record is not None:
            self.record.event("fleet_scale", action="add", replica=rid,
                              version=version)

        def boot():
            if not r.launch():
                self._schedule_relaunch(r, ("boot-failed",),
                                        made_progress=False)

        threading.Thread(target=boot, name=f"fleet-add-{rid}",
                         daemon=True).start()
        return r

    def drain_replica(self, rid: int = None,
                      version=_ANY_VERSION) -> Optional[int]:
        """Shrink the set by one replica: remove it from routing, then
        drain-close its engine (queued work finishes serving — a drain
        is graceful by definition; abrupt death is ``eject``'s job).
        Default victim: the highest-id ready replica.  Refuses to drain
        the last replica (a fleet of zero serves nothing and can never
        recover without an external add).  Returns the drained id, or
        None if nothing was eligible.

        ``version`` narrows the default-victim pool to replicas of one
        model version (None = the boot version) — the rollout swaps
        "drain one v1" without naming ids."""
        with self._resize_lock:
            if len(self.replicas) <= 1:
                return None
            if rid is None:
                cands = [r for r in self.replicas if r.ready()]
                if version is not _ANY_VERSION:
                    cands = [r for r in cands if r.version == version]
                if not cands:
                    return None
                r = max(cands, key=lambda x: x.id)
            else:
                matches = [x for x in self.replicas if x.id == rid]
                if not matches:
                    return None
                r = matches[0]
            self.replicas.remove(r)
        with r._lock:
            r.closed = True
            eng, r.engine, r.state = r.engine, None, R_DEAD
        if eng is not None:
            eng.close()
        # the per-replica gauges would otherwise freeze at their last
        # value and read as a live replica forever
        self.registry.reset(f"fleet.replica{r.id}.")
        if self.record is not None:
            self.record.event("fleet_scale", action="drain", replica=r.id)
        return r.id

    # ------------------------------------------------------------------
    # health
    # ------------------------------------------------------------------

    def _health_loop(self) -> None:
        interval = max(self.cfg.fleet.health_interval_s, 0.05)
        while not self._stop.wait(interval):
            try:
                self.tick()
            except Exception:  # monitor must never die silently
                logger.exception("fleet health tick failed")

    def tick(self, now: float = None) -> None:
        """One health pass (public so tests drive it deterministically
        without the wall-clock loop)."""
        now = time.monotonic() if now is None else now
        for r in list(self.replicas):
            with r._lock:
                state, eng, due = r.state, r.engine, r.relaunch_at
            if state == R_READY and (eng is None or not eng.alive()):
                self.eject(r, "engine-dead")
            elif state == R_RELAUNCHING and due is not None and now >= due:
                with r._lock:
                    if r.state != R_RELAUNCHING or r.relaunch_at != due:
                        continue  # someone else picked it up
                    r.relaunch_at = None
                threading.Thread(target=self._relaunch, args=(r,),
                                 name=f"fleet-relaunch-{r.id}",
                                 daemon=True).start()
        self.export_gauges()

    def eject(self, r: Replica, reason: str) -> None:
        """Remove a replica from the routing set and terminate its
        stranded queue (FAILED — the router's reroute path picks the
        work up); then schedule the relaunch."""
        with r._lock:
            if r.state not in (R_READY, R_STARTING):
                return
            r.state = R_EJECTED
            eng = r.engine
        with self._counts_lock:
            self.ejects += 1
        served = 0
        if eng is not None:
            eng.kill()
            served = eng.metrics.counters["served"]
        logger.warning("replica %d ejected (%s) after serving %d "
                       "requests this generation", r.id, reason, served)
        if self.record is not None:
            self.record.event("fleet_eject", replica=r.id, reason=reason,
                              generation=r.generation, served=served)
        self._schedule_relaunch(r, (reason,), made_progress=served > 0)

    def _schedule_relaunch(self, r: Replica, signature: tuple,
                           made_progress: bool) -> None:
        if not self.cfg.fleet.relaunch:
            with r._lock:
                r.state = R_DEAD
            return
        delay, give_up = r.policy.record(signature, made_progress)
        with r._lock:
            if give_up or r.closed:
                r.state = R_DEAD
                return
            r.state = R_RELAUNCHING
            r.relaunch_at = time.monotonic() + delay

    def _relaunch(self, r: Replica) -> None:
        with self._counts_lock:
            self.relaunches += 1
        if r.launch():
            r.policy.record(("rejoined",), made_progress=True)
            logger.info("replica %d rejoined the fleet", r.id)
            if self.record is not None:
                self.record.event("fleet_rejoin", replica=r.id,
                                  generation=r.generation)
        else:
            self._schedule_relaunch(r, ("launch-failed",),
                                    made_progress=False)

    def export_gauges(self) -> None:
        """Fleet state → obs registry gauges (scheduler-visible, like
        the elastic gauges): readiness, per-replica depth/generation,
        eject/relaunch counts."""
        g = self.registry.set_gauge
        replicas = list(self.replicas)
        g("fleet.replicas", len(replicas))
        g("fleet.replicas_ready", len(self.ready_replicas()))
        g("fleet.ejects", self.ejects)
        g("fleet.relaunches", self.relaunches)
        for r in replicas:
            d = r.depth()
            g(f"fleet.replica{r.id}.depth",
              -1.0 if d == float("inf") else d)
            g(f"fleet.replica{r.id}.generation", r.generation)


class FleetRouter:
    """The fleet front end: same submit/detect/healthz/metrics surface
    as a single :class:`ServingEngine`, so ``serve/server.py`` serves a
    fleet through the identical HTTP handler (duck typing is the whole
    interface contract — pinned by tests).
    """

    def __init__(self, manager: ReplicaManager, cfg: Config,
                 metrics: FleetMetrics = None):
        self.manager = manager
        self.cfg = cfg
        self.metrics = metrics or FleetMetrics()
        self._rr = itertools.count()  # JSQ tie-break rotation
        # distributed tracing plane: the router owns the head's sampling
        # decision (obs.trace_sample; 0 keeps the hot path at exactly
        # one None-check per seam and wire frames bit-identical)
        obs_trace.configure_distributed(
            sample=cfg.obs.trace_sample, ring=cfg.obs.trace_ring,
            slow_pct=cfg.obs.trace_slow_pct)
        # canary version lane (rollout plane): (version, fraction) or
        # None; the fraction accumulator makes lane choice DETERMINISTIC
        # (request k goes canary iff floor(k·f) > floor((k−1)·f)), so
        # the sim's decision log is byte-reproducible and a 25% canary
        # is exactly 1-in-4, not a coin flip
        self._canary_lock = threading.Lock()
        self._canary: Optional[Tuple[str, float]] = None
        self._canary_acc = 0.0

    # ------------------------------------------------------------------
    # canary version lane (serve/rollout.py drives this)
    # ------------------------------------------------------------------

    def set_canary(self, version: Optional[str], fraction: float) -> None:
        """Route ``fraction`` of admitted traffic to replicas of
        ``version`` (the rest to everything else).  ``version=None``
        clears the lane (version-blind JSQ); fraction 0.0 with a version
        set starves that version of NEW work — the rollback posture
        while v2 replicas drain."""
        with self._canary_lock:
            if version is None:
                self._canary = None
            else:
                self._canary = (version,
                                max(0.0, min(1.0, float(fraction))))
            self._canary_acc = 0.0

    def canary(self) -> Optional[Tuple[str, float]]:
        with self._canary_lock:
            return self._canary

    def _canary_lane(self, cands: List[Replica]) -> List[Replica]:
        """Partition the JSQ candidate set by the canary lane choice.
        Availability outranks canary purity: an empty chosen lane falls
        back to the full candidate set (counted — a fallback-heavy
        canary means the fraction outruns v2 capacity), so the lane can
        never fail a request that ANY replica could serve."""
        with self._canary_lock:
            if self._canary is None:
                return cands
            version, fraction = self._canary
            self._canary_acc += fraction
            take = self._canary_acc >= 1.0
            if take:
                self._canary_acc -= 1.0
        lane = [r for r in cands if (r.version == version) == take]
        if lane:
            return lane
        self.metrics.count("canary_fallback")
        return cands

    def _count_version(self, freq: FleetRequest, state: str,
                       ms: float = None) -> None:
        """Per-version terminal accounting (``fleet.ver.<label>.*`` —
        the series :func:`~mx_rcnn_tpu.serve.rollout.rollout_rules`
        compares): counted for requests that reached a replica, under
        the version of the LAST dispatch target, so per-version sums
        reconcile exactly with the fleet terminals that dispatched."""
        if freq.replica_id is None:
            return
        from mx_rcnn_tpu.serve.rollout import version_label

        lbl = version_label(freq.version)
        # publish into the manager's (scrape-visible) registry when one
        # exists — an agent's canary series must reach the /metrics
        # plane the rollout health rules judge; the in-process tier
        # falls back to the router's private fleet registry
        reg = (self.manager.registry
               if self.manager.registry is not None
               else self.metrics.registry)
        reg.inc(f"fleet.ver.{lbl}.{state}")
        if ms is not None:
            reg.observe(f"fleet.ver.{lbl}.total_ms", ms)

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------

    def submit(self, img: np.ndarray,
               timeout_ms: float = None,
               tctx: "obs_trace.TraceContext" = None) -> FleetRequest:
        """Admit one image fleet-wide; returns the fleet handle (same
        wait()/state contract as ``ServingEngine.submit``).  ``tctx``
        is an INBOUND distributed trace context (the /detect header);
        None lets the head's own sampler decide."""
        now = time.monotonic()
        t = (self.cfg.serve.default_timeout_ms if timeout_ms is None
             else timeout_ms)
        deadline = now + t / 1000.0 if t and t > 0 else None
        freq = FleetRequest(img, deadline, now)
        self._trace_admit(freq, tctx)
        self.metrics.count("submitted")
        self._dispatch(freq)
        return freq

    def submit_prepared(self, data: np.ndarray, im_info: np.ndarray,
                        bucket: Tuple[int, int],
                        timeout_ms: float = None,
                        tctx: "obs_trace.TraceContext" = None
                        ) -> FleetRequest:
        """Bulk-plane admission (``serve/bulk.py``): route one
        ALREADY-preprocessed canvas into its bucket lane fleet-wide —
        same JSQ spread, deadline authority, reroute and exactly-once
        accounting as :meth:`submit`, with the per-dispatch preprocess
        skipped (the canvas was built once, by the streaming loader)."""
        now = time.monotonic()
        t = (self.cfg.serve.default_timeout_ms if timeout_ms is None
             else timeout_ms)
        deadline = now + t / 1000.0 if t and t > 0 else None
        freq = FleetRequest(np.asarray(data), deadline, now,
                            im_info=np.asarray(im_info, np.float32),
                            bucket=tuple(bucket), prepared=True)
        self._trace_admit(freq, tctx)
        self.metrics.count("submitted")
        self._dispatch(freq)
        return freq

    def submit_source(self, img: np.ndarray, im_info: np.ndarray,
                      bucket: Tuple[int, int],
                      timeout_ms: float = None,
                      tctx: "obs_trace.TraceContext" = None
                      ) -> FleetRequest:
        """v2 wire admission (``serve/agent.py`` u8 source frames):
        route one resized-but-unnormalized u8 image into its bucket
        lane fleet-wide.  Same JSQ spread, deadline authority, reroute
        and exactly-once accounting as :meth:`submit_prepared`; the
        SOURCE pixels ride the request, so every (re)dispatch offers
        the same bytes — a local engine runs the shared pad_normalize
        at admission, a remote engine re-ships the 1 B/px frame."""
        now = time.monotonic()
        t = (self.cfg.serve.default_timeout_ms if timeout_ms is None
             else timeout_ms)
        deadline = now + t / 1000.0 if t and t > 0 else None
        freq = FleetRequest(np.asarray(img), deadline, now,
                            im_info=np.asarray(im_info, np.float32),
                            bucket=tuple(bucket), source=True)
        self._trace_admit(freq, tctx)
        self.metrics.count("submitted")
        self._dispatch(freq)
        return freq

    @staticmethod
    def _trace_admit(freq: FleetRequest,
                     tctx: "obs_trace.TraceContext") -> None:
        """Attach the request's distributed trace root: an inbound
        context is adopted (its parent becomes the root span's parent),
        otherwise the head's deterministic sampler decides.  Untraced
        requests leave ``freq.tctx`` None — the whole hot-path cost."""
        if tctx is None:
            tctx = obs_trace.sample_trace()
        if tctx is None:
            return
        root_sid = obs_trace.new_span_id()
        freq.tparent = tctx.parent
        # every attempt/terminal span nests under the root span id
        freq.tctx = obs_trace.TraceContext(tctx.trace_id, root_sid,
                                           tctx.hop, tctx.sampled)

    def _finish_trace(self, freq: FleetRequest, state: str) -> None:
        """Close the request's trace at its (exactly-once) fleet
        terminal: record the root "request" span, then apply the tail
        retention policy — forced keep for every non-SERVED or rerouted
        request, slowest-percentile keep for the rest."""
        ctx = freq.tctx
        if ctx is None:
            return
        total_ms = (freq.done_t - freq.enqueue_t) * 1e3
        obs_trace.record_span(ctx, "request", total_ms,
                              span_id=ctx.parent, parent=freq.tparent,
                              state=state, attempts=freq.attempts)
        keep = obs_trace.retain_trace(state.upper(), total_ms=total_ms,
                                      attempts=freq.attempts)
        obs_trace.close_trace(ctx, keep=keep, state=state,
                              attempts=freq.attempts,
                              total_ms=round(total_ms, 3))

    def detect(self, img: np.ndarray, timeout_ms: float = None):
        req = self.submit(img, timeout_ms=timeout_ms)
        wait_s = None
        if req.deadline is not None:
            wait_s = max(req.deadline - time.monotonic(), 0.0) + 30.0
        return req.wait(timeout=wait_s)

    def _route_bucket(self, freq: FleetRequest) -> Tuple[int, int]:
        """The bucket this image will serve in (dims-only shape math —
        the same resolution ``ServingEngine.submit`` uses for its
        pre-admission check), computed once and cached on the request so
        reroutes don't repeat it."""
        if freq.bucket is None:
            from mx_rcnn_tpu.data.image import estimate_bucket

            h, w = freq.image.shape[:2]
            freq.bucket = estimate_bucket(
                h, w, self.cfg.bucket.scale, self.cfg.bucket.max_size,
                [tuple(b) for b in self.cfg.bucket.shapes])
        return freq.bucket

    def _dispatch(self, freq: FleetRequest) -> None:
        """Route (or re-route) one request: deadline check FIRST (a
        request that expired during routing/reroute terminates EXPIRED —
        it must never consume a replica slot), then batch-aware JSQ over
        the ready set minus replicas this request already tried.

        The JSQ key is (batch cycles ahead in this request's BUCKET
        lane, total in-flight depth, rotating tie-break): primary is
        ``ceil((lane_queue + 1) / batch)`` — how many dispatch cycles
        until this request would serve — so same-bucket traffic packs
        full batches and spreads lanes evenly; replica-blind total depth
        alone let one replica's lane run cycles deep while its twin on
        the other replica idled (a measured ~5-cycle convoy stall, and
        partial-batch padding, both visible in the fleet bench)."""
        now = time.monotonic()
        if freq.expired(now):
            if freq._finish(EXPIRED):
                self.metrics.count("expired")
                self._count_version(freq, "expired")
                self._finish_trace(freq, EXPIRED)
                freq.image = None
            return
        cands = [r for r in self.manager.ready_replicas()
                 if r.id not in freq.tried]
        if not cands:
            err = RequestFailed(
                "no ready replica to serve this request "
                f"(tried {sorted(freq.tried) or 'none'})")
            if freq._finish(FAILED, error=err):
                self.metrics.count("failed")
                self._count_version(freq, "failed")
                self._finish_trace(freq, FAILED)
                freq.image = None
            return
        cands = self._canary_lane(cands)
        bucket = self._route_bucket(freq)
        batch = self.cfg.serve.batch_size
        rot = next(self._rr)

        def _score(r: Replica):
            with r._lock:
                eng = r.engine if r.state == R_READY else None
            if eng is None:
                return (float("inf"), float("inf"), 0)
            return jsq_key(eng.bucket_depth(bucket), r.depth(), r.id,
                           rot, len(cands), batch)

        target = min(cands, key=_score)
        freq.tried.add(target.id)
        freq.attempts += 1
        freq.replica_id = target.id
        freq.version = target.version
        self._count_version(freq, "dispatched")
        with target._lock:
            eng = target.engine if target.state == R_READY else None
        if eng is None:  # lost the race with an eject — try the rest
            self._dispatch(freq)
            return
        remaining_ms = (0.0 if freq.deadline is None
                        else max((freq.deadline - now) * 1000.0, 0.001))
        # per-attempt trace context: each dispatch gets its own
        # "fleet.attempt" span under the root, so a reroute-after-kill
        # reconstructs as ONE trace with both attempt subtrees
        inner_ctx = (freq.tctx.child(obs_trace.new_span_id())
                     if freq.tctx is not None else None)
        if freq.source:
            if inner_ctx is not None:
                inner = eng.submit_source(freq.image, freq.im_info,
                                          freq.bucket,
                                          timeout_ms=remaining_ms,
                                          tctx=inner_ctx)
            else:
                inner = eng.submit_source(freq.image, freq.im_info,
                                          freq.bucket,
                                          timeout_ms=remaining_ms)
        elif freq.prepared:
            if inner_ctx is not None:
                inner = eng.submit_prepared(freq.image, freq.im_info,
                                            freq.bucket,
                                            timeout_ms=remaining_ms,
                                            tctx=inner_ctx)
            else:
                inner = eng.submit_prepared(freq.image, freq.im_info,
                                            freq.bucket,
                                            timeout_ms=remaining_ms)
        elif inner_ctx is not None:
            inner = eng.submit(freq.image, timeout_ms=remaining_ms,
                               tctx=inner_ctx)
        else:
            inner = eng.submit(freq.image, timeout_ms=remaining_ms)
        inner.add_done_callback(
            lambda done, _freq=freq, _eng=eng:
            self._on_inner_done(_freq, done, _eng))

    def _on_inner_done(self, freq: FleetRequest, inner: ServeRequest,
                       eng: ServingEngine = None) -> None:
        """Inner terminal → fleet terminal (or reroute).  Runs on
        whichever thread terminated the inner request — dispatcher,
        health monitor (via ``engine.kill``) or the submitting caller
        (immediate shed) — and is the ONLY place a fleet request
        terminates after dispatch, so fleet accounting mirrors the
        per-request exactly-once guarantee."""
        state = inner.state
        if inner.tctx is not None:
            # the attempt span: one per dispatch, nesting under the
            # root — its id is the parent every replica-side span of
            # this attempt carries
            obs_trace.record_span(
                freq.tctx, "fleet.attempt",
                (inner.done_t - inner.enqueue_t) * 1e3,
                span_id=inner.tctx.parent, replica=freq.replica_id,
                attempt=freq.attempts, state=state)
        if state == SERVED:
            freq.batch_rows = inner.batch_rows
            if freq._finish(SERVED, result=inner.result):
                ms = (freq.done_t - freq.enqueue_t) * 1e3
                self.metrics.count("served")
                self.metrics.observe("total_ms", ms)
                self._count_version(freq, "served", ms=ms)
                self._finish_trace(freq, SERVED)
                freq.image = None
        elif state == SHED:
            if eng is not None and eng._closed:
                # not a watermark shed: the engine was killed/closed in
                # the submit race window — treat as replica death, not
                # client-visible backpressure
                self._retry_or_fail(freq, inner)
                return
            # JSQ sent this to the least-loaded replica; its watermark
            # shed means the whole fleet is saturated — 429, immediately
            if freq._finish(SHED):
                self.metrics.count("shed")
                self._count_version(freq, "shed")
                self._finish_trace(freq, SHED)
                freq.image = None
        elif state == EXPIRED:
            if freq._finish(EXPIRED):
                self.metrics.count("expired")
                self._count_version(freq, "expired")
                self._finish_trace(freq, EXPIRED)
                freq.image = None
        else:  # FAILED — replica died under it, or the batch errored
            self._retry_or_fail(freq, inner)

    def _retry_or_fail(self, freq: FleetRequest,
                       inner: ServeRequest) -> None:
        """Re-dispatch a replica-failure within the deadline and retry
        budget; reroutes never extend the deadline.  A request already
        past its deadline terminates EXPIRED, not FAILED — had the
        replica lived, its dispatcher would have cancelled the request
        at take (cancel-expired-before-dispatch); the deadline authority
        outranks the replica's death."""
        if freq.expired(time.monotonic()):
            if freq._finish(EXPIRED):
                self.metrics.count("expired")
                self._count_version(freq, "expired")
                self._finish_trace(freq, EXPIRED)
                freq.image = None
            return
        if freq.attempts < 1 + max(self.cfg.fleet.reroute_retries, 0):
            self.metrics.count("rerouted")
            self._dispatch(freq)
        elif freq._finish(FAILED, error=inner.error):
            self.metrics.count("failed")
            self._count_version(freq, "failed")
            self._finish_trace(freq, FAILED)
            freq.image = None

    # ------------------------------------------------------------------
    # status surface (server.py-compatible)
    # ------------------------------------------------------------------

    def healthz(self) -> Dict:
        reps = [r.describe() for r in list(self.manager.replicas)]
        ready = sum(1 for r in reps if r["state"] == R_READY)
        return {
            "ok": ready > 0,
            "fleet": True,
            "replicas": reps,
            "ready": ready,
            "ejects": self.manager.ejects,
            "relaunches": self.manager.relaunches,
            "buckets": [list(b) for b in self.cfg.bucket.shapes],
            "batch_size": self.cfg.serve.batch_size,
            "versions": self.manager.versions(),
            "canary": (list(self.canary()) if self.canary() is not None
                       else None),
        }

    def rerouted(self) -> int:
        return self.metrics.registry.counter(
            self.metrics.PREFIX + "rerouted")

    def close(self, timeout: float = 10.0) -> None:
        self.manager.close(timeout)


# ---------------------------------------------------------------------------
# fleet assembly helpers (tools/fleet.py, tools/loadgen.py, tests)
# ---------------------------------------------------------------------------

def partition_devices(n_replicas: int, devices: Sequence = None,
                      per_replica: int = 0) -> List[List]:
    """Split the device inventory into per-replica subsets.  Disjoint
    slices while the supply lasts; replicas beyond it wrap around and
    SHARE devices — logged, because throughput then validates the router,
    not the silicon (the 1-core CPU tier runs every replica on the same
    device; docs/SERVING.md "Fleet tier" is explicit about which is
    which)."""
    import jax

    devices = list(devices if devices is not None else jax.devices())
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    d = len(devices)
    if per_replica <= 0:
        per_replica = max(d // n_replicas, 1)
    per_replica = min(per_replica, d)
    if n_replicas * per_replica > d:
        logger.warning(
            "fleet: %d replicas x %d device(s) over %d device(s) — replica "
            "subsets wrap around and SHARE devices; rates from this fleet "
            "are not per-device rates", n_replicas, per_replica, d)
    return [[devices[(i * per_replica + j) % d]
             for j in range(per_replica)] for i in range(n_replicas)]


def make_engine_build_fn(cfg: Config, model, variables, *,
                         export_root: str = None,
                         run_fn_factory: Callable[[int], Callable] = None,
                         devices: Sequence = None
                         ) -> Callable[[int], Tuple[ServingEngine, Dict]]:
    """The standard replica ``build_fn``: per-replica device subset →
    (optional) per-replica data mesh → private Predictor → warmed engine.
    ``export_root`` selects AOT warm-from-export; ``run_fn_factory``
    (bench/test rigs) replaces the model path entirely."""
    subsets = partition_devices(cfg.fleet.replicas, devices,
                                cfg.fleet.devices_per_replica)

    def build(rid: int) -> Tuple[ServingEngine, Dict]:
        from mx_rcnn_tpu.core.tester import Predictor
        from mx_rcnn_tpu.parallel.dp import device_mesh

        sub = subsets[rid % len(subsets)]
        # always a mesh, even over ONE device: the mesh is what places the
        # replica's variables and batches on ITS device — without one the
        # Predictor commits to the default device and four one-chip
        # replicas all land on device 0.  Exported programs are
        # nr_devices=1 modules, so an export-warm replica runs on its
        # subset's first device; mesh-sharded replicas are a trace-warm
        # feature.
        mesh = device_mesh(devices=sub[:1] if export_root else sub)
        run_fn = run_fn_factory(rid) if run_fn_factory else None
        predictor = Predictor(model, variables, cfg, mesh=mesh)
        engine = ServingEngine(predictor, cfg, run_fn=run_fn)
        t0 = time.perf_counter()
        if run_fn is not None:
            engine.warmup()
            join = {"stub": True}
        elif export_root:
            from mx_rcnn_tpu.serve.export import ExportStore

            join = engine.warm_from_export(ExportStore(export_root))
        else:
            engine.warmup()
            join = {}
        join["warm_s"] = round(time.perf_counter() - t0, 3)
        join["devices"] = len(sub)
        return engine, join

    return build


def build_fleet(cfg: Config, model, variables, *, export_root: str = None,
                run_fn_factory=None, devices=None,
                registry: Registry = None, record=None) -> FleetRouter:
    """One-call fleet: manager + router, replicas launched and warmed."""
    build = make_engine_build_fn(cfg, model, variables,
                                 export_root=export_root,
                                 run_fn_factory=run_fn_factory,
                                 devices=devices)
    manager = ReplicaManager(build, cfg, registry=registry,
                             record=record).start()
    return FleetRouter(manager, cfg)
