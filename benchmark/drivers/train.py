"""The ``train`` driver: one run of one training cell through the
program's own entry, ``mx_rcnn_tpu.tools.train.train_net``.

The program is steered only through arguments ``train_net`` already has:
an injected ``roidb`` (generated images, repeated so that one epoch
outlasts the window), ``init_from`` (weights the benchmark made from the
seed), ``run_record`` (a hook that stamps the fit loop's events with the
host clock), ``step_callback`` and ``stop_flag``.  The window's edges are
the fit loop's synced ``log`` events (``benchmark/window.py``).

This file is the detector program's half of the run: its configuration,
``roidb``, seed-made weights and their hand-over as a checkpoint, the probe
of the first steps, the call of ``train_net``, the reference and the
comparison.  What no program decides (the window, the profiler, the memory
read, ``failed``, ``setup_s``, the result object) is
``drivers/measure.py``'s, shared with every driver kind.

What the timed path produced in its first steps is read where the fit
loop holds it: the callbacks run inside ``fit``'s frame, whose ``state``
and ``metrics`` locals are the one compiled step's own output.  After the
window has closed, the peak memory has been read and the program's state
is freed, the plain reference (``benchmark/reference``) follows the same
steps from the same seed-made weights and ``compare_training`` decides
``correct``.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmark.drivers import measure
from benchmark.drivers.measure import CellFailure
from benchmark.reference.step import tree_paths


def _fit_locals() -> Dict:
    """Locals of the nearest caller frame that holds a train state: the fit
    loop, from which the hooks are called."""
    frame = sys._getframe(2)
    while frame is not None:
        state = frame.f_locals.get("state")
        if hasattr(state, "opt_state") and hasattr(state, "params"):
            return frame.f_locals
        frame = frame.f_back
    raise CellFailure("no caller frame holds the train state: the fit "
                      "loop no longer keeps it in a local named 'state'")


def _momentum(opt_state) -> Dict:
    """{param path: momentum leaf} found in an optax state by the ``trace``
    field of its momentum transformation."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [getattr(k, "name", getattr(k, "key", None)) for k in path]
        if "trace" in names:
            tail = names[names.index("trace") + 1:]
            out[tuple(str(n) for n in tail)] = leaf
    if not out:
        raise CellFailure("no momentum trace in the optimizer state")
    return out


def _norm(x) -> float:
    x = np.asarray(x, np.float32).ravel()
    return float(np.sqrt(np.dot(x, x)))


class Probe:
    """Reads what the first steps of the timed path produced."""

    def __init__(self, p0: Dict, opt: Dict, steps: int):
        self.p0 = tree_paths(p0)     # host copies of the seed-made weights
        self.opt = opt
        self.steps = steps
        self.losses: List[Dict] = []
        self.grad_norm: Dict = {}
        self.first_delta_norm: Dict = {}
        self.delta_norm: Dict = {}

    def on_step(self, step: int) -> None:
        if step > self.steps:
            return
        import jax

        loc = _fit_locals()
        m = jax.device_get(loc["metrics"])
        self.losses.append({k: float(v) for k, v in m.items()})
        state = loc["state"]
        if step == 1:
            trace = jax.device_get(_momentum(state.opt_state))
            wd = self.opt["wd"]
            self.grad_norm = {
                k: _norm(np.asarray(t, np.float32) - wd * self.p0[k])
                for k, t in trace.items()}
        if step in (1, self.steps):
            params = tree_paths(jax.device_get(state.params))
            moved = {k: _norm(np.asarray(v) - self.p0[k])
                     for k, v in params.items()}
            if step == 1:
                self.first_delta_norm = moved
            if step == self.steps:
                self.delta_norm = moved
                self.p0 = {}

    def result(self) -> Dict:
        return {"losses": self.losses, "grad_norm": self.grad_norm,
                "first_delta_norm": self.first_delta_norm,
                "delta_norm": self.delta_norm}


def _write_init(params: Dict, stats: Dict, prefix: str) -> None:
    """The seed-made weights in the checkpoint form ``train_net(init_from=)``
    reads: a msgpack of {'params', 'batch_stats'} at ``<prefix>-0000.ckpt``."""
    from flax import serialization

    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    with open(f"{prefix}-0000.ckpt", "wb") as f:
        f.write(serialization.msgpack_serialize(
            {"params": params, "batch_stats": stats}))


def run(cell: Dict, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict:
    """One run of a training cell on the chips it asks for; returns the
    result object.  Without them there is no result."""
    from mx_rcnn_tpu import native

    native.backend()   # the one child process (g++) ends before JAX starts
    measure.need_chips(cell["chips"])
    return run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                    t_start=t_start)


def run_cell(cell: Dict, *, seed: int, seconds: float, trace: bool,
             t_start: float) -> Dict:
    """The run itself, on whatever devices JAX has (``run`` has looked for
    the chip; the CPU tests of ``correct`` start here).

    ``cell``: the workload file's content with its ``config`` and
    ``traffic`` files loaded into it.
    """
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    check = cell["check"]
    import jax

    from mx_rcnn_tpu import runtime

    devices = jax.devices()
    runtime.enable_compile_cache()

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.tools.train import train_net

    from benchmark import traffic_gen as traffic_mod
    from benchmark.reference import compare, nets, step as ref_step

    seed32 = seed % (2 ** 31 - 1)
    batch = traffic["per_chip_batch"]
    n_total = batch * chips
    prog = config["program"]
    overrides = dict(prog["overrides"])
    overrides["train__batch_images"] = batch
    if trace:
        overrides["obs__enabled"] = True
    cfg = generate_config(prog["network"], prog["dataset"], **{
        k: tuple(tuple(x) if isinstance(x, list) else x for x in v)
        if isinstance(v, list) else v for k, v in overrides.items()})

    work = tempfile.mkdtemp(prefix="bench_")
    m = measure.Measurement(
        chips=chips, warmup_steps=traffic["warmup_steps"],
        log_every=cfg.default.frequent, seconds=seconds, trace=trace,
        work=work, t_start=t_start)
    m.mark("imports_s")
    try:
        net = config["network"]
        items = traffic_mod.make_images(
            traffic, seed32, net["num_classes"],
            traffic["images_per_chip"] * chips)
        roidb = traffic_mod.write_roidb(
            items, os.path.join(work, "images"),
            traffic["epoch_steps"] * n_total)
        m.mark("data_s")
        weights = nets.make_weights(net, seed32)
        p0 = jax.device_get(weights)
        del weights
        _write_init(p0, jax.device_get(nets.fixed_stats(p0)),
                    os.path.join(work, "init", "w"))
        probe = Probe(p0, config["optimizer"], check["steps"])
        del p0

        m.mark("weights_s")
        state = train_net(
            cfg, prefix=None, end_epoch=1, lr=config["optimizer"]["lr"],
            num_devices=chips, seed=seed32, roidb=roidb,
            init_from=(os.path.join(work, "init", "w"), 0),
            run_record=m.events, step_callback=probe.on_step,
            stop_flag=m.closed)
        m.end(n_total)
        del state
        program = probe.result()

        m.reduce()

        # ---- the plain reference, on the freed chip ---------------------
        t_ref = time.perf_counter()
        batches = traffic_mod.reference_batches(
            items, config["bucket"], n_total, check["steps"],
            config["train"]["max_gt_boxes"])
        reference = ref_step.reference_steps(
            net, config["train"], config["optimizer"],
            nets.make_weights(net, seed32), batches, seed32,
            steps=check["steps"], block=check["block"],
            scan=devices[0].platform == "tpu")
        ok, numbers, notes = compare.compare_training(
            program, reference, check["limits"])
        ref_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(work, ignore_errors=True)

    return m.result(
        correct=ok, numbers=numbers, notes=notes, reference_s=ref_s,
        end_to_end={"train_imgs_per_s": m.stats["imgs_per_s"]})
