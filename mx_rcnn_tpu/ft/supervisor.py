"""Crash-loop supervisor: kill training M times, auto-resume, prove the
survivor bit-identical to an uninterrupted run.

This is the machine-checked version of the claim in ``core/fit.py`` —
"the step-folded RNG + deterministic per-epoch shuffle make the continued
run bit-identical to an uninterrupted one" — which until this subsystem
was pinned only by in-process pytest (no real process ever died).  The
supervisor runs ``tools/train.py`` as a SUBPROCESS, injects kills
(SIGTERM through the production preemption path, SIGKILL with no chance
to react) and disk faults (truncate / flip-byte / stale-interrupt) via
``--fault_plan``, restarts with ``--resume auto`` until the run
completes, then compares the survivor's final checkpoint against a
control run byte for byte.

Progress is guaranteed, not assumed: SIGTERM advances the resume point to
the kill step (interrupt checkpoint), while SIGKILL loses exactly the
steps since the last committed snapshot — so SIGKILL triggers are placed
just past an epoch boundary (the supervisor schedules against the next
boundary; a SIGKILL storm inside one epoch would otherwise loop forever,
which is a real deployment lesson, not a harness artifact).

``measure_snapshot_overhead`` times the same jitted step with and without
per-epoch snapshots (async and sync) for the <5%-overhead acceptance
number.  ``python -m mx_rcnn_tpu.tools.crashloop`` drives everything and
emits the BENCH-style record (``docs/ft_crashloop.json``).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger("mx_rcnn_tpu")


class RestartPolicy:
    """Restart pacing + crash-loop verdict for supervised training.

    Replaces the fixed (zero) restart delay: consecutive NO-PROGRESS
    failures back off exponentially (``base_s * factor^(n-1)``, capped)
    with DETERMINISTIC jitter (hash of (seed, attempt) — reproducible
    schedules, yet a fleet of supervisors won't thundering-herd a shared
    filesystem), and ``give_up_after`` consecutive IDENTICAL failures
    (same exit signature, same resume step) return a crash-loop verdict —
    the transient-vs-deterministic distinction a scheduler needs: a
    preemption storm makes progress between kills and never trips this; a
    run that dies the same way at the same step every time is a bug, and
    restarting it forever just burns fleet capacity.

    Exported as registry gauges (``ft.supervisor.backoff_s``,
    ``ft.supervisor.consecutive_failures``, ``ft.supervisor.crash_loop``)
    so the verdict is scheduler-visible.  Schedule pinned by
    ``tests/test_ft.py — test_restart_policy_backoff_schedule``.
    """

    def __init__(self, base_s: float = 0.25, factor: float = 2.0,
                 cap_s: float = 30.0, jitter_frac: float = 0.25,
                 give_up_after: int = 4, seed: int = 0, registry=None,
                 clock=time.monotonic):
        self.base_s = base_s
        self.factor = factor
        self.cap_s = cap_s
        self.jitter_frac = jitter_frac
        self.give_up_after = give_up_after
        self.seed = seed
        # restart-instant clock: monotonic by default, virtual under
        # sim/; record() stamps ready_at = clock() + backoff so callers
        # that schedule (rather than sleep) share one time base
        self._clock = clock
        self.ready_at: float = float("-inf")
        self.failures = 0          # consecutive no-progress failures
        self.identical = 0         # consecutive IDENTICAL failures
        self._last_sig: Optional[tuple] = None
        # one policy is shared between the fleet health monitor and the
        # per-replica relaunch threads (serve/fleet.py): an unguarded
        # failures/identical update could lose a count and push a
        # crash-looping replica past its give-up verdict (threadlint
        # TL201; regression: test_restart_policy_record_is_thread_safe).
        # RLock so delay_s stays callable from inside record.
        self._lock = threading.RLock()
        if registry is None:
            from mx_rcnn_tpu.obs.metrics import registry as _registry

            registry = _registry()
        self._rec = registry

    def delay_s(self, n_failures: Optional[int] = None) -> float:
        """The backoff before restart attempt ``n_failures`` (1-based);
        0.0 while the run is making progress."""
        with self._lock:
            n = self.failures if n_failures is None else n_failures
        if n <= 0:
            return 0.0
        d = min(self.base_s * self.factor ** (n - 1), self.cap_s)
        # deterministic jitter in [-jitter_frac, +jitter_frac]: same
        # (seed, n) -> same delay, different supervisors -> spread
        h = int(hashlib.sha256(f"{self.seed}:{n}".encode()).hexdigest(),
                16) % 10_000
        return d * (1.0 + self.jitter_frac * (h / 5_000.0 - 1.0))

    def record(self, signature: tuple, made_progress: bool
               ) -> Tuple[float, bool]:
        """Record one attempt outcome; returns ``(delay_s, give_up)``.

        ``signature`` identifies the failure mode (exit code + resume
        step works well); ``made_progress`` resets the whole schedule —
        a storm that advances between kills never backs off.
        """
        with self._lock:
            if made_progress:
                self.failures = 0
                self.identical = 0
                self._last_sig = None
            else:
                self.failures += 1
                self.identical = (self.identical + 1
                                  if signature == self._last_sig else 1)
                self._last_sig = signature
            give_up = self.identical >= self.give_up_after
            delay = self.delay_s()
            self.ready_at = self._clock() + delay
            failures, identical = self.failures, self.identical
        self._rec.set_gauge("ft.supervisor.backoff_s", delay)
        self._rec.set_gauge("ft.supervisor.consecutive_failures", failures)
        self._rec.set_gauge("ft.supervisor.crash_loop", int(give_up))
        if give_up:
            logger.error(
                "crash-loop verdict: %d consecutive identical failures "
                "(%r) — this is a deterministic bug, not a transient; "
                "refusing to restart", identical, signature)
        return delay, give_up

# one kill event the scheduler will realize as a concrete fault plan once
# it knows the resume point: (file_fault or None, signal name, placement)
# placement 'mid' = resume point + small delta (step-exact TERM resume);
# 'boundary' = next epoch boundary + small delta (a committed epoch
# checkpoint exists to fall back to — required for SIGKILL progress and
# for file faults, which need a checkpoint on disk to corrupt)
KillEvent = Tuple[Optional[str], str, str]

DEFAULT_EVENTS: Tuple[KillEvent, ...] = (
    (None, "TERM", "mid"),          # planned preemption, mid-epoch
    (None, "KILL", "boundary"),     # planned hard kill
    (None, "TERM", "mid"),          # random-step preemption
    ("truncate-last-ckpt", "KILL", "boundary"),  # torn write + hard kill
    ("flip-byte", "KILL", "boundary"),           # bit rot + hard kill
    ("stale-interrupt", "KILL", "boundary"),     # crash between commit+clear
)

SMOKE_EVENTS: Tuple[KillEvent, ...] = (
    (None, "TERM", "mid"),
    ("truncate-last-ckpt", "KILL", "boundary"),
)


def _child_env() -> Dict[str, str]:
    """The crash loop is a CPU correctness protocol: children are pinned
    to the CPU platform.  Each child (``tools.train``) arms the persistent
    compile cache itself (``runtime.enable_compile_cache``), so restart
    attempts pay disk reads instead of recompiles."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _train_cmd(prefix: str, *, network: str, dataset: str, end_epoch: int,
               seed: int, num_images: int, image_size: Tuple[int, int],
               resume: bool, fault_plan: Optional[str]) -> List[str]:
    h, w = image_size
    cmd = [sys.executable, "-m", "mx_rcnn_tpu.tools.train",
           "--network", network, "--dataset", dataset,
           "--prefix", prefix, "--end_epoch", str(end_epoch),
           "--seed", str(seed), "--frequent", "1000", "--no_flip",
           "--dataset_kw",
           repr({"num_images": num_images, "image_size": (h, w),
                 "max_objects": 3}),
           # the miniature recipe of tests/conftest.py — shrink_tiny_cfg —
           # expressed as CLI overrides so the child is a REAL production
           # entry point, not a test harness
           "--set", "train__rpn_pre_nms_top_n=1024",
           "--set", "train__rpn_post_nms_top_n=300",
           "--set", "train__max_gt_boxes=8",
           "--set", f"bucket__scale={min(h, w)}",
           "--set", f"bucket__max_size={max(h, w)}",
           "--set", f"bucket__shapes=(({h},{w}),({w},{h}))"]
    if resume:
        cmd += ["--resume", "auto"]
    if fault_plan:
        cmd += ["--fault_plan", fault_plan]
    return cmd


def _progress(prefix: str):
    """(step, ref) of the newest VALID checkpoint under prefix (0, None if
    nothing restorable) — the supervisor's only view of child progress,
    deliberately the same scanner the child resumes through."""
    from mx_rcnn_tpu.ft.integrity import latest_valid_checkpoint

    ref = latest_valid_checkpoint(prefix)
    return (0, None) if ref is None else (ref.step, ref)


def run_crashloop(workdir: str, *, events: Tuple[KillEvent, ...] = None,
                  network: str = "tiny", dataset: str = "synthetic",
                  end_epoch: int = 5, num_images: int = 32,
                  image_size: Tuple[int, int] = (128, 160), seed: int = 0,
                  rng_seed: int = 0, attempt_timeout_s: float = 900.0,
                  max_attempts: int = 30) -> Dict:
    """Control run + kill/resume gauntlet + bit-exact comparison.

    Returns the record dict (see ``tools/crashloop.py`` for the CLI and
    the JSON contract).  Raises on a child that dies for a reason other
    than an injected kill, on no-progress loops, and on timeout.
    """
    from mx_rcnn_tpu.utils.checkpoint import checkpoint_path, load_checkpoint

    events = DEFAULT_EVENTS if events is None else tuple(events)
    steps_per_epoch = num_images  # batch 1, --no_flip
    total_steps = end_epoch * steps_per_epoch
    rng = np.random.RandomState(rng_seed)
    os.makedirs(workdir, exist_ok=True)
    kw = dict(network=network, dataset=dataset, end_epoch=end_epoch,
              seed=seed, num_images=num_images, image_size=image_size)
    env = _child_env()

    def run_child(prefix, resume, fault_plan, label):
        cmd = _train_cmd(prefix, resume=resume, fault_plan=fault_plan, **kw)
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=attempt_timeout_s)
        wall = time.perf_counter() - t0
        fallbacks = proc.stderr.count("checkpoint integrity: SKIPPING")
        logger.info("[%s] exit=%s wall=%.1fs fallbacks=%d", label,
                    proc.returncode, wall, fallbacks)
        return proc, wall, fallbacks

    # ---- control: uninterrupted run, same seed/recipe --------------------
    control_prefix = os.path.join(workdir, "control", "e2e")
    proc, control_wall, _ = run_child(control_prefix, False, None, "control")
    if proc.returncode != 0:
        raise RuntimeError(
            f"control run failed (exit {proc.returncode}):\n{proc.stderr[-4000:]}")
    cstep, _ = _progress(control_prefix)
    if cstep < total_steps:
        raise RuntimeError(f"control run finished at step {cstep} < "
                           f"{total_steps} — recipe/schedule mismatch")

    # ---- survivor: the kill/resume gauntlet ------------------------------
    prefix = os.path.join(workdir, "survivor", "e2e")
    attempts: List[Dict] = []
    kills_survived = 0
    fallback_events = 0
    pending = list(events)
    policy = RestartPolicy(seed=rng_seed)
    for attempt in range(max_attempts):
        cur, _ref = _progress(prefix)
        if cur >= total_steps:
            break
        plan = None
        event = None
        if pending:
            file_fault, sig, placement = pending[0]
            if placement == "boundary":
                # +1 epoch: a committed checkpoint exists to resume from.
                # Corrupting faults go +2: they destroy the NEWEST committed
                # checkpoint, so an OLDER one must exist for the scanner's
                # fallback to be a real fallback and not a fresh start.
                # (stale-interrupt corrupts nothing — +1 is enough.)
                ahead = 2 if file_fault in ("truncate-last-ckpt",
                                            "flip-byte") else 1
                boundary = (cur // steps_per_epoch + ahead) * steps_per_epoch
                kill_step = boundary + int(rng.randint(2, 6))
            else:
                boundary = None
                kill_step = cur + int(rng.randint(3, 12))
            if kill_step <= total_steps - 2:
                event = pending.pop(0)
                parts = []
                if file_fault:
                    # @after pins the fault to the snapshot committed at
                    # this boundary (the async writer lands a beat later)
                    parts.append(f"{file_fault}@step={kill_step - 1}"
                                 f"@after={boundary}")
                parts.append(f"kill@step={kill_step}@sig={sig}")
                plan = ",".join(parts)
            else:
                # too close to the end to kill meaningfully: drop the
                # remaining events LOUDLY (the caller checks kills_survived)
                logger.warning("dropping %d unplaced kill event(s) — run "
                               "too close to completion", len(pending))
                pending.clear()
        proc, wall, fallbacks = run_child(
            prefix, resume=attempt > 0 or cur > 0, fault_plan=plan,
            label=f"attempt {attempt} plan={plan}")
        fallback_events += fallbacks
        after, _ = _progress(prefix)
        rec = {"attempt": attempt, "plan": plan, "exit": proc.returncode,
               "resume_step": cur, "progress_step": after,
               "wall_s": round(wall, 1), "fallbacks": fallbacks}
        attempts.append(rec)
        killed = proc.returncode < 0 or (
            plan is not None and "sig=TERM" in plan and proc.returncode == 0
            and after < total_steps)
        if killed:
            kills_survived += 1
        elif proc.returncode != 0:
            raise RuntimeError(
                f"survivor attempt {attempt} died WITHOUT an injected kill "
                f"(exit {proc.returncode}):\n{proc.stderr[-4000:]}")
        # restart pacing + crash-loop verdict: progress resets the
        # backoff, identical no-progress failures eventually give up
        delay, give_up = policy.record((proc.returncode, cur), after > cur)
        rec["backoff_s"] = round(delay, 3)
        if give_up:
            raise RuntimeError(
                f"crash-loop verdict after {policy.identical} identical "
                f"no-progress failures (exit {proc.returncode} at step "
                f"{cur}); attempts={attempts}")
        if delay:
            logger.info("restart backoff: sleeping %.2fs", delay)
            time.sleep(delay)
    else:
        raise RuntimeError(f"crashloop did not converge in {max_attempts} "
                           f"attempts; attempts={attempts}")

    # ---- verdict: bit-identical final TrainState -------------------------
    pa = checkpoint_path(control_prefix, end_epoch)
    pb = checkpoint_path(prefix, end_epoch)
    import hashlib

    sha = [hashlib.sha256(open(p, "rb").read()).hexdigest() for p in (pa, pb)]
    ra, rb = load_checkpoint(control_prefix, end_epoch), \
        load_checkpoint(prefix, end_epoch)
    import jax

    la, ta = jax.tree_util.tree_flatten(ra)
    lb, tb = jax.tree_util.tree_flatten(rb)
    bit_identical = (ta == tb and len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)))

    return {
        "total_steps": total_steps,
        "steps_per_epoch": steps_per_epoch,
        "end_epoch": end_epoch,
        "kills_survived": kills_survived,
        "kills_planned": len(events),
        "fallback_events": fallback_events,
        "attempts": attempts,
        "control_wall_s": round(control_wall, 1),
        "final_ckpt_sha256": {"control": sha[0], "survivor": sha[1]},
        "files_identical": sha[0] == sha[1],
        "bit_identical": bool(bit_identical),
    }


def measure_snapshot_overhead(steps: int = 96, snapshot_every: int = 32,
                              warmup: int = 5) -> Dict:
    """Snapshot cost at the crashloop's per-epoch cadence, two views:

    * ``*_overhead_pct`` — end-to-end mean-step-time inflation vs no
      checkpointing.  On THIS 1-core box the async writer contends with
      training for the only core, so async ≈ sync here — an upper bound,
      not the design point (a TPU host runs the writer on one of 180+
      idle cores).
    * ``*_stall_ms_per_snapshot`` / ``async_stall_overhead_pct`` — time
      the TRAINING THREAD is blocked per snapshot (async: device_get +
      owned copy + enqueue; sync: the full serialize+write+fsync).  This
      is what the step pipeline pays on a host with spare cores, i.e. the
      number the <5% acceptance criterion is checked against — and the
      async/sync stall ratio is the measured value of moving
      serialization off the training thread.

    Uses the tiny network on a 128x160 canvas (CPU-sized); the stall gap
    GROWS with model size (the stall is a memcpy vs a full serialize).
    """
    import tempfile

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.train import make_train_step, setup_training
    from mx_rcnn_tpu.ft.snapshot import AsyncSnapshotter, SyncSnapshotter
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.data.synthetic import make_batch

    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=256,
                         rpn_post_nms_top_n=64, batch_rois=32,
                         max_gt_boxes=8, rpn_min_size=2)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    state, tx = setup_training(model, cfg, key, (1, 128, 160, 3),
                               steps_per_epoch=1000)
    batch = make_batch(cfg, 1, 128, 160)
    step = jax.jit(make_train_step(model, cfg, tx), donate_argnums=(0,))

    def run(n, snap=None, s0=None):
        s = jax.tree_util.tree_map(np.asarray, s0)  # fresh, undonated copy
        s = jax.device_put(s)
        for _ in range(warmup):
            s, m = step(s, batch, key)
        jax.block_until_ready(m)
        stalls = []
        t0 = time.perf_counter()
        for i in range(n):
            s, m = step(s, batch, key)
            if snap is not None and (i + 1) % snapshot_every == 0:
                t1 = time.perf_counter()
                snap.save_epoch((i + 1) // snapshot_every, s)
                stalls.append(time.perf_counter() - t1)
        jax.block_until_ready(m)
        if snap is not None:
            snap.flush()
        wall = time.perf_counter() - t0
        return wall / n, (float(np.mean(stalls)) if stalls else 0.0)

    base, _ = run(steps, None, state)
    with tempfile.TemporaryDirectory() as d:
        a = AsyncSnapshotter(os.path.join(d, "async", "m"), cfg,
                             steps_per_epoch=snapshot_every)
        t_async, stall_a = run(steps, a, state)
        a.close()
        t_sync, stall_s = run(
            steps, SyncSnapshotter(os.path.join(d, "sync", "m"), cfg,
                                   snapshot_every), state)
    epoch_s = snapshot_every * base
    return {
        "steps": steps,
        "snapshot_every": snapshot_every,
        "base_step_ms": round(base * 1e3, 2),
        "async_step_ms": round(t_async * 1e3, 2),
        "sync_step_ms": round(t_sync * 1e3, 2),
        # end-to-end on this box (1-core writer-contention upper bound)
        "async_overhead_pct_1core": round((t_async - base) / base * 100, 2),
        "sync_overhead_pct_1core": round((t_sync - base) / base * 100, 2),
        # train-thread stall: the pipeline cost on a host with spare cores
        "async_stall_ms_per_snapshot": round(stall_a * 1e3, 2),
        "sync_stall_ms_per_snapshot": round(stall_s * 1e3, 2),
        "async_stall_overhead_pct": round(stall_a / epoch_s * 100, 2),
        "sync_stall_overhead_pct": round(stall_s / epoch_s * 100, 2),
    }


# ---------------------------------------------------------------------------
# Elastic storm orchestration (docs/FT.md "Elasticity"; ISSUE 6)
# ---------------------------------------------------------------------------
# The multi-process generalization of the crash loop above: instead of one
# training process killed M times, a WORLD of N ``jax.distributed``
# processes is driven through a preemption storm — staggered SIGTERM with
# grace windows, SIGKILL without — and every casualty becomes a mesh
# RESIZE instead of a dead run: the supervisor publishes a topology
# directive (ft/elastic.py — write_topology) naming the surviving device
# set, relaunches (or SIGUSR1-nudges) the world, and the elastic
# controller restores the latest valid checkpoint onto the new mesh and
# keeps stepping.  Recovery time is measured detect -> first step on the
# new mesh, per transition; every restore must prove itself bit-identical
# to the checkpoint it came from (the controller re-serializes and
# SHA-256s against the manifest — a failed audit aborts the worker).


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


class _Worker:
    """One supervised training process with live stdout capture: lines
    accumulate as they arrive (the world's ELASTIC_EVENT timeline must be
    visible WHILE workers run — the supervisor synchronizes on it)."""

    def __init__(self, proc: subprocess.Popen, idx: int, gen: int):
        self.proc = proc
        self.idx = idx
        self.gen = gen
        # the pump thread appends while the supervisor polls (wait_event
        # spins on the event list mid-run) — both sides go through _lock
        # so a poll can never observe a list mid-resize (threadlint TL201)
        self._lock = threading.Lock()
        self._lines: List[str] = []
        self._events: List[Dict] = []
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    @property
    def events(self) -> List[Dict]:
        """Snapshot of the ELASTIC_EVENT records seen so far (the dicts
        are shared — the supervisor's harvest tags them in place)."""
        with self._lock:
            return list(self._events)

    def _pump(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            with self._lock:
                self._lines.append(line)
            if line.startswith("ELASTIC_EVENT "):
                try:
                    ev = json.loads(line[len("ELASTIC_EVENT "):])
                    ev["proc"] = self.idx
                    with self._lock:
                        self._events.append(ev)
                except ValueError:
                    pass  # torn line (process killed mid-write)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def signal(self, sig: int) -> None:
        if self.alive():
            self.proc.send_signal(sig)

    def join(self, timeout: float) -> Optional[int]:
        """Wait for exit; returns the exit code or None on timeout."""
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        self._thread.join(timeout=5.0)
        return self.proc.returncode

    def tail(self, n: int = 30) -> str:
        with self._lock:
            return "\n".join(self._lines[-n:])

    def locksan_dirty(self) -> bool:
        """True when a sanitizer-armed child reported inversions or
        watchdog trips at exit (analysis/sanitizer.py prints the
        LOCKSAN_DIRTY marker; make threadlint-smoke fails on it)."""
        with self._lock:
            return any(l.startswith("LOCKSAN_DIRTY") for l in self._lines)


def run_elastic_storm(workdir: str, *, smoke: bool = False,
                      network: str = "tiny", dataset: str = "synthetic",
                      end_epoch: Optional[int] = None, num_images: int = 24,
                      image_size: Tuple[int, int] = (128, 160),
                      seed: int = 0, base_devices: int = 2,
                      grace_s: float = 60.0,
                      world_timeout_s: float = 600.0) -> Dict:
    """Drive a multi-process elastic run through a preemption storm;
    returns the BENCH-style record (``tools/crashloop.py --elastic``
    wraps it as ``ELASTIC_r06.json`` / ``make elastic-smoke``).

    Full drill: 4 planned kills (2 SIGTERM, 2 SIGKILL) + the collateral
    peer-failure casualty, one world shrink (2 procs x 1 dev -> 1 proc x
    1 dev, grad_accum 2), one LIVE in-process device grow (1 -> 2
    devices, no relaunch), one SIGKILL on the grown mesh, and one world
    grow-back (1 proc -> 2 procs) that runs to completion.  ``smoke``:
    one TERM preemption -> shrink -> grow-back -> completion (the
    ``make elastic-smoke`` shape).
    """
    from mx_rcnn_tpu.ft.elastic import (EXIT_RESIZE, topology_path,
                                        write_topology)

    # epoch budget: every storm phase advances >= 1 epoch between
    # preemptions (the full drill has six such phases), and the final
    # grown world must still have epochs left to run to completion
    end_epoch = end_epoch or (4 if smoke else 12)
    spe = num_images // base_devices  # optimizer steps/epoch (no flip,
    # batch_images=1, global batch preserved across every topology)
    total_steps = end_epoch * spe
    prefix = os.path.join(workdir, "storm", "e2e")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    tpath = topology_path(prefix)
    env = _child_env()
    kw = dict(network=network, dataset=dataset, end_epoch=end_epoch,
              seed=seed, num_images=num_images, image_size=image_size,
              resume=False, fault_plan=None)

    timeline: List[Dict] = []
    recoveries: List[Dict] = []
    kills = {"TERM": 0, "KILL": 0}
    casualties = 0
    worlds = 0
    locksan_dirty_workers = 0
    all_events: List[Dict] = []
    policy = RestartPolicy(seed=seed)

    def sup_event(event: str, **payload) -> Dict:
        rec = {"ts": round(time.time(), 6), "event": event,
               "by": "supervisor", **payload}
        timeline.append(rec)
        logger.info("storm: %s %s", event, payload)
        return rec

    def harvest(workers: List[_Worker]) -> None:
        nonlocal locksan_dirty_workers
        for w in workers:
            evs = w.events
            for ev in evs:
                ev.setdefault("by", f"worker{w.idx}.g{w.gen}")
            all_events.extend(evs)
            if w.locksan_dirty():
                locksan_dirty_workers += 1

    def launch_world(gen: int, devices: int, procs: int,
                     local_devices: int) -> List[_Worker]:
        nonlocal worlds
        worlds += 1
        cmd_base = _train_cmd(prefix, **kw)
        cmd_base += ["--elastic",
                     "--set", f"elastic__base_devices={base_devices}"]
        workers = []
        port = _free_port() if procs > 1 else None
        for i in range(procs):
            cmd = list(cmd_base)
            wenv = dict(env)
            # pin the virtual device count EXPLICITLY (an inherited
            # XLA_FLAGS — e.g. the test conftest's 8-device rig — would
            # otherwise override --local_devices and change the mesh)
            wenv["XLA_FLAGS"] = ("--xla_force_host_platform_device_"
                                 f"count={local_devices}")
            if procs > 1:
                cmd += ["--coordinator", f"localhost:{port}",
                        "--num_processes", str(procs),
                        "--process_id", str(i),
                        "--local_devices", str(local_devices)]
            workers.append(_Worker(subprocess.Popen(
                cmd, env=wenv, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True), i, gen))
        sup_event("world_launch", generation=gen, num_processes=procs,
                  num_devices=devices, local_devices=local_devices)
        return workers

    def wait_event(workers: List[_Worker], name: str, gen: int,
                   timeout: float) -> Dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for w in workers:
                for ev in list(w.events):
                    if ev["event"] == name and ev.get("generation") == gen:
                        return ev
            if all(not w.alive() for w in workers):
                break
            time.sleep(0.05)
        tails = "\n---\n".join(w.tail() for w in workers)
        raise RuntimeError(
            f"storm: timed out ({timeout:.0f}s) waiting for worker event "
            f"{name!r} gen {gen} (workers alive="
            f"{[w.alive() for w in workers]}):\n{tails}")

    def wait_progress(step: int, timeout: float = None) -> int:
        deadline = time.monotonic() + (timeout or world_timeout_s)
        while time.monotonic() < deadline:
            cur, _ = _progress(prefix)
            if cur >= step:
                return cur
            time.sleep(0.1)
        raise RuntimeError(f"storm: no progress to step {step} "
                           f"(at {_progress(prefix)[0]})")

    def record_recovery(kind: str, detect_ts: float, ev: Dict) -> None:
        recoveries.append({
            "kind": kind, "detect_ts": round(detect_ts, 6),
            "first_step_ts": ev["ts"], "generation": ev.get("generation"),
            "recovery_ms": round((ev["ts"] - detect_ts) * 1e3, 1)})
        sup_event("recovered", kind=kind, generation=ev.get("generation"),
                  recovery_ms=recoveries[-1]["recovery_ms"])

    def preempt(workers: List[_Worker], victim: int, sig_name: str
                ) -> float:
        """Inject one preemption and wind down the world; returns the
        detect timestamp (the send — a real scheduler's watchdog would
        observe the exit an instant later).

        TERM gets its grace window: the victim finishes its in-flight
        step (peers still participate in that collective) and drains.
        Then the rest of the sync world — which CANNOT step on without
        the victim — is asked to stop and, when wedged inside the dead
        collective (a TERM handler only flips a flag the step loop never
        reaches again), hard-killed: the scheduler-reality escalation.
        Multi-process exit codes after a member dies are deliberately
        not policed — the distributed shutdown barrier and coordination
        service make peers abort in messy ways, and all of them are the
        preemption's collateral."""
        nonlocal casualties
        kills[sig_name] += 1
        detect = time.time()
        sup_event("preempt", victim=victim, sig=sig_name)
        workers[victim].signal(getattr(signal, "SIG" + sig_name))
        if sig_name == "TERM":
            deadline = time.monotonic() + grace_s
            while time.monotonic() < deadline:
                drained = not workers[victim].alive() or any(
                    e["event"] in ("drain", "generation_end")
                    for e in list(workers[victim].events))
                if drained:
                    break
                time.sleep(0.05)
        for w in workers:            # graceful ask for the stragglers
            w.signal(signal.SIGTERM)
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and any(w.alive() for w in workers)):
            time.sleep(0.05)
        for w in workers:
            if w.alive():
                w.proc.kill()
                casualties += 1
                sup_event("hard_casualty", proc=w.idx,
                          reason="wedged in dead collective")
        for w in workers:
            w.join(30.0)
        harvest(workers)
        return detect

    # ---- phase 1: the full world, then lose a process --------------------
    gen = 0
    write_topology(tpath, gen, base_devices, 2)
    workers = launch_world(gen, base_devices, 2, 1)
    wait_event(workers, "first_step", gen, world_timeout_s)
    wait_progress(spe)          # >= 1 committed epoch before the storm
    time.sleep(0.5)             # drift into the next epoch (mid-epoch)
    # staggered: the victim gets its grace window and drains; the rest
    # of the world follows through TERM->KILL escalation inside preempt()
    detect = preempt(workers, victim=1, sig_name="TERM")
    cur, _ = _progress(prefix)
    policy.record(("TERM", cur), made_progress=cur > 0)

    # ---- phase 2: shrink onto the survivor's devices ---------------------
    gen = 1
    sup_event("shrink", from_devices=base_devices, from_processes=2,
              num_devices=base_devices // 2, num_processes=1)
    write_topology(tpath, gen, base_devices // 2, 1, ts=detect)
    workers = launch_world(gen, base_devices // 2, 1,
                           local_devices=base_devices)
    ev = wait_event(workers, "first_step", gen, world_timeout_s)
    record_recovery("shrink_world", detect, ev)
    start = _progress(prefix)[0]
    wait_progress(start + spe)

    if not smoke:
        # ---- phase 3: SIGKILL, no grace — restart on the same mesh -------
        time.sleep(0.3)
        detect = preempt(workers, victim=0, sig_name="KILL")
        cur2, _ = _progress(prefix)
        delay, give_up = policy.record(("KILL", cur2),
                                       made_progress=cur2 > cur)
        assert not give_up, "storm made progress — give-up must not fire"
        if delay:
            time.sleep(delay)
        write_topology(tpath, gen, base_devices // 2, 1, ts=detect)
        workers = launch_world(gen, base_devices // 2, 1,
                               local_devices=base_devices)
        ev = wait_event(workers, "first_step", gen, world_timeout_s)
        record_recovery("kill_restart", detect, ev)
        wait_progress(_progress(prefix)[0] + spe)

        # ---- phase 4: graceful TERM — step-exact interrupt resume --------
        time.sleep(0.3)
        detect = preempt(workers, victim=0, sig_name="TERM")
        cur3, _ = _progress(prefix)
        policy.record(("TERM", cur3), made_progress=True)
        write_topology(tpath, gen, base_devices // 2, 1, ts=detect)
        workers = launch_world(gen, base_devices // 2, 1,
                               local_devices=base_devices)
        ev = wait_event(workers, "first_step", gen, world_timeout_s)
        record_recovery("term_restart", detect, ev)
        wait_progress(_progress(prefix)[0] + spe)

        # ---- phase 5: LIVE device grow (no relaunch) ---------------------
        gen = 2
        detect = time.time()
        sup_event("grow", kind="live", num_devices=base_devices,
                  num_processes=1)
        write_topology(tpath, gen, base_devices, 1, ts=detect)
        workers[0].signal(signal.SIGUSR1)
        ev = wait_event(workers, "first_step", gen, world_timeout_s)
        record_recovery("grow_live", detect, ev)
        wait_progress(_progress(prefix)[0] + spe)

        # ---- phase 6: SIGKILL the grown mesh, restart it -----------------
        time.sleep(0.3)
        detect = preempt(workers, victim=0, sig_name="KILL")
        write_topology(tpath, gen, base_devices, 1, ts=detect)
        workers = launch_world(gen, base_devices, 1,
                               local_devices=base_devices)
        ev = wait_event(workers, "first_step", gen, world_timeout_s)
        record_recovery("kill_restart_grown", detect, ev)
        wait_progress(_progress(prefix)[0] + spe)

    # ---- final phase: grow the WORLD back and run to completion ----------
    final_gen = 3 if not smoke else 2
    detect = time.time()
    sup_event("grow", kind="world", num_devices=base_devices,
              num_processes=2)
    write_topology(tpath, final_gen, base_devices, 2, ts=detect)
    workers[0].signal(signal.SIGUSR1)
    code = workers[0].join(grace_s)
    if code is None:
        raise RuntimeError("storm: worker did not drain for the world "
                           "grow within the grace window:\n"
                           + workers[0].tail(60))
    if code != EXIT_RESIZE:
        raise RuntimeError(f"storm: expected EXIT_RESIZE={EXIT_RESIZE} "
                           f"drain, got exit {code}:\n{workers[0].tail(60)}")
    harvest(workers)
    sup_event("drain_observed", exit=code)
    workers = launch_world(final_gen, base_devices, 2, 1)
    ev = wait_event(workers, "first_step", final_gen, world_timeout_s)
    record_recovery("grow_world", detect, ev)
    exit_codes = [w.join(world_timeout_s) for w in workers]
    harvest(workers)
    if any(c != 0 for c in exit_codes):
        tails = "\n---\n".join(w.tail(60) for w in workers)
        raise RuntimeError(
            f"storm: final world did not complete cleanly "
            f"(exits {exit_codes}):\n{tails}")
    final_step, final_ref = _progress(prefix)
    sup_event("complete", step=final_step)

    # ---- verdicts --------------------------------------------------------
    restores = [e for e in all_events if e["event"] == "restore"]
    first_steps = [e for e in all_events if e["event"] == "first_step"]
    gen_ends = [e for e in all_events if e["event"] == "generation_end"]
    # zero unexpected recompiles: every lowering of a generation happened
    # at or before its first step (mesh-rebuild compiles are the budget;
    # anything after step 1 is a leak)
    unexpected = []
    for ge in gen_ends:
        match = [fs for fs in first_steps
                 if fs.get("by") == ge.get("by")
                 and fs.get("generation") == ge.get("generation")]
        if match and ge.get("lowerings", 0) > match[-1].get("lowerings", 0):
            unexpected.append({"by": ge.get("by"),
                               "generation": ge.get("generation"),
                               "extra": ge["lowerings"]
                               - match[-1]["lowerings"]})
    samples = sorted(r["recovery_ms"] for r in recoveries)

    def pct(p):
        if not samples:
            return None
        return samples[min(int(round(p / 100 * (len(samples) - 1))),
                           len(samples) - 1)]

    merged = sorted(timeline + all_events, key=lambda e: e["ts"])
    return {
        "metric": "elastic_storm",
        "measured": True,
        "smoke": smoke,
        "network": network, "dataset": dataset,
        "base_devices": base_devices,
        "end_epoch": end_epoch, "steps_per_epoch": spe,
        "total_steps": total_steps, "final_step": final_step,
        "completed": final_step >= total_steps,
        "worlds_launched": worlds,
        "kills": kills,
        "kills_total": kills["TERM"] + kills["KILL"],
        "peer_casualties": casualties,
        "shrinks": sum(1 for e in merged if e["event"] == "shrink"),
        "grows": sum(1 for e in merged if e["event"] == "grow"),
        "restores": len(restores),
        "restores_bit_identical": all(e.get("bit_identical")
                                      for e in restores),
        "unexpected_recompiles": unexpected,
        # nonzero only when MXRCNN_THREAD_SANITIZER armed the children
        "locksan_dirty_workers": locksan_dirty_workers,
        "recovery_ms": {
            "samples": [r["recovery_ms"] for r in recoveries],
            "by_kind": {r["kind"]: r["recovery_ms"] for r in recoveries},
            "p50": pct(50), "p90": pct(90),
            "max": samples[-1] if samples else None,
        },
        "timeline": merged,
    }
