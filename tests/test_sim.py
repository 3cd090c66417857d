"""Sim tier tests (ISSUE 17): virtual-time kernel determinism, clock
seams through the production control plane, trace-generator
invariants, scorer exactness on a hand-computed mini-trace, the
never-sampled == downed regression, and the policy gauntlet's
discrimination contract (shipped clean, mistuned breaches) on a
seconds-scale mini storm.

Everything here runs in virtual time — no sleeps, no wall-clock
dependence — so the whole file is quick-tier.
"""

import hashlib
import json
import logging
import time

import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.ft.supervisor import RestartPolicy
from mx_rcnn_tpu.obs.collect import Collector, RegistrySource
from mx_rcnn_tpu.obs.health import CRITICAL, HealthEngine, Rule
from mx_rcnn_tpu.obs.metrics import Registry
from mx_rcnn_tpu.obs.timeseries import TimeSeriesStore
from mx_rcnn_tpu.serve.fleet import jsq_key
from mx_rcnn_tpu.serve.scheduler import SchedulerPolicy
from mx_rcnn_tpu.sim.control import MISTUNED_OVERRIDES, SimRun
from mx_rcnn_tpu.sim.kernel import SimKernel, VirtualClock
from mx_rcnn_tpu.sim.score import decision_log_bytes, score_run
from mx_rcnn_tpu.sim.traffic import (SCENARIOS, bucket_weights,
                                     fleet_capacity_rps, generate,
                                     rate_at)
from mx_rcnn_tpu.tools.sim import check_gauntlet

logging.getLogger("mx_rcnn_tpu").setLevel(logging.ERROR)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

class TestKernel:
    def test_same_instant_fires_in_scheduling_order(self):
        k = SimKernel(seed=0)
        order = []
        k.at(5.0, lambda: order.append("a"))
        k.at(5.0, lambda: order.append("b"))
        k.at(2.0, lambda: order.append("c"))
        k.run_until(10.0)
        assert order == ["c", "a", "b"]
        assert k.clock.now == 10.0
        assert k.fired == 3

    def test_past_scheduling_clamps_to_now(self):
        k = SimKernel(seed=0)
        k.run_until(7.0)
        fired = []
        k.at(3.0, lambda: fired.append(k.clock.now))
        k.run_until(7.0)
        assert fired == [7.0]  # not time travel

    def test_callback_may_schedule_at_current_instant(self):
        k = SimKernel(seed=0)
        order = []
        def outer():
            order.append("outer")
            k.at(k.clock.now, lambda: order.append("inner"))
        k.at(1.0, outer)
        k.run_until(1.0)
        assert order == ["outer", "inner"]

    def test_rng_substreams_stable_and_independent(self):
        a1 = SimKernel(seed=3).rng("arrivals").random_sample(4)
        a2 = SimKernel(seed=3).rng("arrivals").random_sample(4)
        assert list(a1) == list(a2)
        # a DIFFERENT consumer draws a different stream and never
        # perturbs the first one
        k = SimKernel(seed=3)
        other = k.rng("buckets").random_sample(4)
        assert list(k.rng("arrivals").random_sample(4)) == list(a1)
        assert list(other) != list(a1)
        assert list(SimKernel(seed=4).rng("arrivals").random_sample(4)) \
            != list(a1)


# ---------------------------------------------------------------------------
# clock seams: the production classes on an injected clock
# ---------------------------------------------------------------------------

class TestClockSeams:
    def test_store_and_collector_stamp_virtual_time(self):
        clk = VirtualClock(100.0)
        store = TimeSeriesStore(capacity=8, clock=clk)
        reg = Registry()
        reg.set_gauge("g", 1.0)
        smp = store.sample(reg)
        assert smp["ts"] == 100.0
        coll = Collector([RegistrySource("a", lambda: (reg, {}))],
                         clock=clk)
        clk._now = 107.0
        assert coll.collect()["ts"] == 107.0
        assert store.append_snapshot({"gauges": {}})["ts"] == 107.0

    def test_store_default_clock_is_wall_time(self):
        store = TimeSeriesStore(capacity=4)
        reg = Registry()
        t0 = time.time()
        smp = store.sample(reg)
        assert abs(smp["ts"] - t0) < 5.0

    def test_health_engine_verdict_ts_from_clock(self):
        clk = VirtualClock(42.0)
        store = TimeSeriesStore(capacity=8, clock=clk)
        store.append_snapshot({"gauges": {"x": 1.0}})
        eng = HealthEngine(
            [Rule("r", "x", "gauge", ">", 0.0, severity=CRITICAL)],
            store, clock=clk)
        assert eng.evaluate()["ts"] == 42.0

    def test_scheduler_cooldown_runs_on_injected_clock(self):
        cfg = generate_config(
            "tiny", "synthetic", crosshost__for_samples=1,
            crosshost__cooldown_s=30.0, crosshost__target_replicas=2,
            crosshost__min_replicas=1)
        clk = VirtualClock(0.0)
        pol = SchedulerPolicy(cfg, clock=clk)
        store = TimeSeriesStore(capacity=8, clock=clk)
        store.append_snapshot(
            {"gauges": {"agent.replicas_ready@agent-0": 1.0}})
        act = pol.decide(store)
        assert act is not None and act["action"] == "add"
        # inside the virtual cooldown: silent; after it: acts again
        clk._now = 29.0
        store.append_snapshot(
            {"gauges": {"agent.replicas_ready@agent-0": 1.0}})
        assert pol.decide(store) is None
        clk._now = 31.0
        store.append_snapshot(
            {"gauges": {"agent.replicas_ready@agent-0": 1.0}})
        assert pol.decide(store) is not None

    def test_restart_policy_ready_at_from_clock(self):
        clk = VirtualClock(50.0)
        pol = RestartPolicy(base_s=4.0, factor=2.0, cap_s=60.0,
                            give_up_after=3, seed=1, clock=clk)
        delay, give_up = pol.record(("boom", 1), made_progress=False)
        assert not give_up
        assert pol.ready_at == pytest.approx(50.0 + delay)


# ---------------------------------------------------------------------------
# never-sampled == downed (the missing-gauge deficit path)
# ---------------------------------------------------------------------------

class TestAbsentEqualsDown:
    def test_gauge_window_ages_out_stale_sources(self):
        clk = VirtualClock(0.0)
        store = TimeSeriesStore(capacity=16, clock=clk)
        store.append_snapshot({"gauges": {"g@agent-1": 3.0}})
        for t in (10.0, 20.0, 30.0):
            clk._now = t
            store.append_snapshot({"gauges": {}})  # agent-1 went dark
        # unbounded read keeps the stale value; a windowed read ages it
        # out — indistinguishable from a gauge that never existed
        assert store.gauge("g@agent-1") == 3.0
        assert store.gauge("g@agent-1", window_s=15.0) is None
        assert store.gauge("never-produced", window_s=15.0) is None

    def test_scheduler_deficit_same_for_never_sampled_and_downed(self):
        cfg = generate_config(
            "tiny", "synthetic", crosshost__for_samples=1,
            crosshost__cooldown_s=0.0, crosshost__target_replicas=4,
            crosshost__min_replicas=1)

        def decide_with(gauges):
            clk = VirtualClock(0.0)
            store = TimeSeriesStore(capacity=8, clock=clk)
            store.append_snapshot({"gauges": dict(gauges)})
            return SchedulerPolicy(cfg, clock=clk).decide(store)

        # agent-1 NEVER produced the ready gauge vs. agent-1 produced
        # it in an older sample but is absent from the latest: the
        # policy reads the latest sample only, so both are a deficit
        # of identical size with identical placement
        never = decide_with({"agent.replicas_ready@agent-0": 2.0})
        clk = VirtualClock(0.0)
        store = TimeSeriesStore(capacity=8, clock=clk)
        store.append_snapshot(
            {"gauges": {"agent.replicas_ready@agent-0": 2.0,
                        "agent.replicas_ready@agent-1": 2.0}})
        clk._now = 10.0
        store.append_snapshot(
            {"gauges": {"agent.replicas_ready@agent-0": 2.0}})
        downed = SchedulerPolicy(cfg, clock=clk).decide(store)
        assert never is not None and downed is not None
        for k in ("action", "source", "ready"):
            assert never[k] == downed[k]
        assert never["action"] == "add"

    def test_run_check_lists_never_up_sources(self):
        from mx_rcnn_tpu.tools.obs import run_check
        cfg = generate_config("tiny", "synthetic")
        reg = Registry()
        reg.set_gauge("serve.replicas_ready", 1.0)
        coll = Collector([RegistrySource("live", lambda: (reg, {})),
                          RegistrySource("dead", lambda: None)])
        verdict = run_check(coll, cfg, samples=2, interval_s=0.0)
        assert verdict["never_up"] == ["dead"]
        assert verdict["sources_up"] == 1
        assert verdict["view"]["dead"] == {"up": False}


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

class TestTraffic:
    @pytest.fixture(scope="class")
    def cfg(self):
        return generate_config("tiny", "synthetic")

    @pytest.mark.parametrize("name", SCENARIOS)
    def test_generator_invariants(self, cfg, name):
        hosts = 20
        tr = generate(name, cfg, hosts, seed=5)
        assert tr["name"] == name
        assert tr["hosts"] == hosts and tr["seed"] == 5
        T = tr["duration_s"]
        assert T > 0
        # rate curve: time-sorted, non-negative, starts inside [0, T)
        times = [t for t, _ in tr["rate"]]
        assert times == sorted(times)
        assert all(0.0 <= t < T for t in times)
        assert all(r >= 0.0 for _, r in tr["rate"])
        # events: known kinds, in-range hosts, time-sorted
        for ev in tr["events"]:
            assert ev["kind"] in ("host_down", "host_flap",
                                  "drain_host")
            assert 0 <= ev["host"] < hosts
            assert 0.0 <= ev["t"] < T
        # the fleet-shape knobs every scenario must pin for both arms
        for key in ("crosshost__target_replicas",
                    "crosshost__max_replicas",
                    "crosshost__min_replicas"):
            assert key in tr["overrides"]
        # deterministic: byte-equal JSON and a stable fingerprint
        again = generate(name, cfg, hosts, seed=5)
        assert json.dumps(tr, sort_keys=True) == \
            json.dumps(again, sort_keys=True)
        assert tr["fingerprint"] == again["fingerprint"]
        assert generate(name, cfg, hosts, seed=6)["fingerprint"] \
            != tr["fingerprint"]

    def test_storm_kills_fifteen_percent_with_capped_flappers(self, cfg):
        tr = generate("failure_storm", cfg, 40, seed=0)
        kills = [e for e in tr["events"]
                 if e["kind"] in ("host_down", "host_flap")]
        assert len(kills) == 6  # 15% of 40
        assert sum(e["kind"] == "host_flap" for e in kills) == 3

    def test_rolling_update_drains_every_host_once(self, cfg):
        hosts = 16
        tr = generate("rolling_update", cfg, hosts, seed=0)
        drained = [e["host"] for e in tr["events"]
                   if e["kind"] == "drain_host"]
        assert sorted(drained) == list(range(hosts))

    def test_rate_at_piecewise_constant_and_zero_past_end(self, cfg):
        tr = {"duration_s": 100.0,
              "rate": [[0.0, 5.0], [40.0, 9.0], [70.0, 2.0]]}
        assert rate_at(tr, 0.0) == 5.0
        assert rate_at(tr, 39.9) == 5.0
        assert rate_at(tr, 40.0) == 9.0
        assert rate_at(tr, 99.9) == 2.0
        assert rate_at(tr, 100.0) == 0.0

    def test_bucket_weights_normalized(self, cfg):
        w = bucket_weights(cfg)
        assert sum(frac for _, frac in w) == pytest.approx(1.0)
        assert fleet_capacity_rps(cfg, 10) > 0.0


# ---------------------------------------------------------------------------
# scorer: exact on a hand-computed mini-trace
# ---------------------------------------------------------------------------

class TestScore:
    def test_score_run_exact(self):
        stats = {"submitted": 10, "served": 6, "shed": 2,
                 "expired": 1, "failed": 1, "rerouted": 3}
        log = [{"t": 1, "kind": "action"}, {"kind": "health", "t": 2}]
        s = score_run(stats, critical_s=90.0, warn_s=30.0,
                      wasted_replica_s=12.34, wait_ms_max=55.57,
                      p99_ms=432.1, log=log)
        assert s["lost"] == 2                      # expired + failed
        assert s["slo_critical_minutes"] == 1.5    # 90 s
        assert s["slo_warn_minutes"] == 0.5
        assert s["capacity_wasted_replica_s"] == 12.3
        assert s["wait_ms_max"] == 55.6
        assert s["served_p99_ms"] == 432.1
        assert s["actions"] == 1
        assert s["decision_log_entries"] == 2
        # the canonical byte form is pinned by hand — one sorted-key
        # JSON object per line, trailing newline
        blob = (b'{"kind": "action", "t": 1}\n'
                b'{"kind": "health", "t": 2}\n')
        assert decision_log_bytes(log) == blob
        assert s["decision_log_sha256"] == \
            hashlib.sha256(blob).hexdigest()

    def test_empty_log_scores(self):
        stats = {"submitted": 0, "served": 0, "shed": 0,
                 "expired": 0, "failed": 0, "rerouted": 0}
        s = score_run(stats, 0.0, 0.0, 0.0, 0.0, None, [])
        assert s["lost"] == 0 and s["served_p99_ms"] is None
        assert decision_log_bytes([]) == b""


# ---------------------------------------------------------------------------
# the routing key the cluster shares with the production router
# ---------------------------------------------------------------------------

class TestJsqKey:
    def test_cycles_quantize_by_batch(self):
        # lane depths 0..3 all cost one dispatch cycle at batch 4;
        # depth 4 starts the second cycle
        assert jsq_key(0, 9, 0, 0, 4, 4)[0] == 1
        assert jsq_key(3, 9, 0, 0, 4, 4)[0] == 1
        assert jsq_key(4, 9, 0, 0, 4, 4)[0] == 2

    def test_rotation_breaks_ties_fairly(self):
        a = jsq_key(2, 5, 0, 1, 3, 4)
        b = jsq_key(2, 5, 1, 1, 3, 4)
        assert a[:2] == b[:2] and a[2] != b[2]
        assert jsq_key(2, 5, 2, 1, 3, 4)[2] == 0  # (2+1) % 3


# ---------------------------------------------------------------------------
# the gauntlet contract on a seconds-scale mini storm
# ---------------------------------------------------------------------------

def _mini_storm(cfg, hosts=10, duration_s=90.0, seed=7):
    """A hand-built failure_storm at test scale: 40% of the fleet
    preempted under ~70% base load — shipped re-places the capacity;
    a policy blind to the deficit overloads the survivors past the
    deadline."""
    cap = fleet_capacity_rps(cfg, hosts)
    return {
        "name": "mini_storm", "seed": seed, "hosts": hosts,
        "duration_s": duration_s,
        "rate": [[0.0, round(0.7 * cap, 3)]],
        "bucket_weights": [[list(s), w] for s, w in bucket_weights(cfg)],
        "events": [{"t": 15.0 + 2.5 * j, "kind": "host_down",
                    "host": hosts - 1 - j} for j in range(4)],
        "overrides": {
            "crosshost__target_replicas": hosts,
            "crosshost__max_replicas": hosts * 4,
            "crosshost__min_replicas": hosts,
            "crosshost__up_backlog": 50.0,
            "serve__default_timeout_ms": 6000.0,
            "serve__shed_watermark": 96,
            "fleet__reroute_retries": 2,
        },
        "fingerprint": "test-mini-storm",
    }


class TestGauntlet:
    @pytest.fixture(scope="class")
    def cfg(self):
        return generate_config("tiny", "synthetic")

    @pytest.fixture(scope="class")
    def shipped_runs(self, cfg):
        """The same trace + seed, twice — the determinism substrate."""
        out = []
        for _ in range(2):
            run = SimRun(_mini_storm(cfg), cfg, label="shipped")
            score = run.run()
            out.append((score, decision_log_bytes(run.log)))
        return out

    def test_decision_log_byte_identical(self, shipped_runs):
        (s1, b1), (s2, b2) = shipped_runs
        assert b1 == b2
        assert s1 == s2
        assert s1["decision_log_sha256"] == s2["decision_log_sha256"]

    def test_shipped_clean_and_acts(self, shipped_runs):
        s, _ = shipped_runs[0]
        assert s["lost"] == 0 and s["expired"] == 0 \
            and s["failed"] == 0
        assert s["slo_critical_minutes"] == 0.0
        assert s["actions"] > 0  # it re-placed the killed capacity
        # conservation: every accepted request reached ONE terminal
        assert s["submitted"] == (s["served"] + s["shed"]
                                  + s["expired"] + s["failed"])

    def test_mistuned_measurably_breaches(self, cfg, shipped_runs):
        run = SimRun(_mini_storm(cfg), cfg, label="mistuned",
                     arm_overrides=MISTUNED_OVERRIDES)
        s = run.run()
        assert s["actions"] == 0            # blind, as sabotaged
        assert s["lost"] > 0                # and it pays for it
        assert s["slo_critical_minutes"] > 0.0
        assert s["submitted"] == (s["served"] + s["shed"]
                                  + s["expired"] + s["failed"])
        # same trace, same seed: the divergence is the policy alone
        assert s["decision_log_sha256"] != \
            shipped_runs[0][0]["decision_log_sha256"]


# ---------------------------------------------------------------------------
# the driver's acceptance predicate
# ---------------------------------------------------------------------------

class TestCheckGauntlet:
    @staticmethod
    def _record(shipped_lost=0, shipped_crit=0.0, mistuned_lost=5,
                mistuned_crit=0.3, hosts=100, det=True):
        arm = lambda lost, crit: {"lost": lost, "expired": lost,
                                  "failed": 0,
                                  "slo_critical_minutes": crit}
        return {
            "scenarios": {"s": {
                "hosts": hosts,
                "arms": {"shipped": arm(shipped_lost, shipped_crit),
                         "mistuned": arm(mistuned_lost,
                                         mistuned_crit)}}},
            "determinism": {"log_identical": det,
                            "score_identical": det},
        }

    def test_clean_record_passes(self):
        assert check_gauntlet(self._record()) == []

    def test_shipped_loss_fails(self):
        assert any("LOST" in p
                   for p in check_gauntlet(self._record(shipped_lost=3)))

    def test_no_discrimination_fails(self):
        probs = check_gauntlet(self._record(mistuned_lost=0,
                                            mistuned_crit=0.0))
        assert any("discrimination" in p for p in probs)

    def test_small_fleet_fails(self):
        assert any(">= 100" in p
                   for p in check_gauntlet(self._record(hosts=20)))

    def test_broken_determinism_fails(self):
        probs = check_gauntlet(self._record(det=False))
        assert any("determinism" in p for p in probs)
