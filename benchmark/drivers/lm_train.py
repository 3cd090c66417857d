"""The ``lm_train`` driver: one run of a training cell of a sequence family
(``nemotron_h``) through the program's own entry,
``mx_rcnn_tpu.tools.train.train_net``.

This file is the program's half of the run: its configuration, the token
source (``benchmark/lm_traffic.py``), seed-made weights handed over in
memory (``init_from`` as a mapping: no file), the probe of the first steps,
the call of ``train_net``, the plain reference
(``benchmark/reference/lm.py``) and the comparison
(``reference/lm_compare.py``).  The measurement's half (the window, the
profiler, the memory read, ``failed``, ``setup_s``, the result object) is
``drivers/measure.py``'s, called in the order its docstring gives.

What the timed path produced in its first two steps is read where the fit
loop holds it (``drivers/train.py::_fit_locals``): the step's ``metrics``
(loss, the routed-expert counters, the rows of every held expert) and, after
step 1, the train state.  Its leaves are never fetched: one program on the
device takes, leaf by leaf, the norm of Adam's first moment (the clipped
gradient times ``1 - beta1``) and of the parameters' change from the
seed-made weights, which it makes again from the seed; only the state-space
vectors' moments (``lm.SCAN_LEAVES``, 64 numbers each) come back whole.  After the window has
closed and the state is freed, the reference follows the same two steps one
sequence at a time.

A program without this family (``generate_config`` knows no ``nemotron_h``)
is a ``CellFailure`` before any work.
"""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmark.drivers import measure
from benchmark.drivers.measure import CellFailure
from benchmark.drivers.train import _fit_locals

COUNTERS = ("moe_assignments_per_token", "moe_load_max_over_mean",
            "moe_overflow")


def _adam_mu(opt_state):
    """The first-moment tree of the optax state (the ``mu`` field of its
    Adam transformation)."""
    import jax

    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise CellFailure("no Adam first moment in the optimizer state")
    return found[0]


class Probe:
    """Reads what the first steps of the timed path produced."""

    def __init__(self, config: Dict, seed: int, steps: int):
        self.config, self.seed, self.steps = config, seed, steps
        self.losses: List[float] = []
        self.counts: List = []
        self.overflow = 0.0
        self.grad_norm: Dict = {}
        self.first_delta_norm: Dict = {}
        self.scan_grad: Dict = {}

    def _norms(self, params, mu):
        """({path: (norm of mu / (1 - beta1), norm of params - seed-made)},
        {path: mu / (1 - beta1) of a state-space vector}), computed on the
        device; the seed-made weights are made again there, leaf by leaf,
        and never held whole by the host."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import lm

        b1 = self.config["optimizer"]["beta1"]

        def fn(params, mu, seed):
            p0 = lm.tree_paths(lm.make_weights(self.config, seed))
            p1, m = lm.tree_paths(params), lm.tree_paths(mu)
            norms = {k: (jnp.sqrt(jnp.sum(jnp.square(m[k]))) / (1 - b1),
                         jnp.sqrt(jnp.sum(jnp.square(p1[k] - p0[k]))))
                     for k in p0}
            return norms, {k: v / (1 - b1)
                           for k, v in lm.scan_grads(mu).items()}

        return jax.device_get(jax.jit(fn)(params, mu, self.seed))

    def on_step(self, step: int) -> None:
        if step > self.steps:
            return
        import jax

        loc = _fit_locals()
        m = jax.device_get(loc["metrics"])
        self.losses.append(float(m["loss"]))
        self.overflow += float(m["moe_overflow"])
        if step == 1:
            self.counts = np.asarray(m["moe_expert_rows"]).round().astype(
                int).tolist()
            state = loc["state"]
            norms, self.scan_grad = self._norms(
                state.params, _adam_mu(state.opt_state))
            for k, (g, d) in norms.items():
                self.grad_norm[k] = float(g)
                self.first_delta_norm[k] = float(d)

    def result(self) -> Dict:
        return {"losses": self.losses, "counts": self.counts,
                "overflow": self.overflow, "grad_norm": self.grad_norm,
                "first_delta_norm": self.first_delta_norm,
                "scan_grad": self.scan_grad}


def program_config(config: Dict, traffic: Dict, trace: bool):
    """The program's Config for the cell; a program that does not know the
    family cannot run it."""
    from mx_rcnn_tpu.config import generate_config

    prog = config["program"]
    overrides = dict(prog["overrides"])
    overrides["train__batch_images"] = traffic["per_chip_batch"]
    overrides["train__seq_len"] = traffic["seq_len"]
    if trace:
        overrides["obs__enabled"] = True
    try:
        return generate_config(prog["network"], prog["dataset"], **{
            k: tuple(v) if isinstance(v, list) else v
            for k, v in overrides.items()})
    except (KeyError, TypeError, ValueError) as e:
        raise CellFailure(f"the program cannot build configuration "
                          f"{config['name']!r}: {e!r}")


def run(cell: Dict, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict:
    """One run on the chips the cell asks for; without them, or without the
    family in the program, there is no result."""
    program_config(cell["config"], cell["traffic"], trace)
    measure.need_chips(cell["chips"])
    return run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                    t_start=t_start)


def run_cell(cell: Dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, reference_kw: Dict = None) -> Dict:
    """The run itself, on whatever devices JAX has (the CPU tests of
    ``correct`` start here).  ``reference_kw``: options of
    ``lm.reference_steps`` (``precision``, ``fault``) for the readings and
    the tests that plant something in the reference's place."""
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    check = cell["check"]
    import jax

    from mx_rcnn_tpu import runtime

    runtime.enable_compile_cache()
    cfg = program_config(config, traffic, trace)

    from mx_rcnn_tpu.tools.train import train_net

    from benchmark import lm_traffic
    from benchmark.reference import lm, lm_compare

    seed32 = seed % (2 ** 31 - 1)
    n_total = traffic["per_chip_batch"] * chips
    work = tempfile.mkdtemp(prefix="bench_")
    m = measure.Measurement(
        chips=chips, warmup_steps=traffic["warmup_steps"],
        log_every=cfg.default.frequent, seconds=seconds, trace=trace,
        work=work, t_start=t_start)
    m.mark("imports_s")
    try:
        sequences = lm_traffic.make_sequences(
            traffic, seed32, config["vocab_size"],
            traffic["sequences_per_chip"] * chips)
        source = lm_traffic.token_source(
            sequences, traffic["epoch_steps"] * n_total)
        m.mark("data_s")
        weights = jax.jit(lambda s: lm.make_weights(config, s))(seed32)
        jax.block_until_ready(weights)
        probe = Probe(config, seed32, check["steps"])
        m.mark("weights_s")
        state = train_net(
            cfg, prefix=None, end_epoch=1, lr=config["optimizer"]["lr"],
            num_devices=chips, seed=seed32, roidb=source,
            init_from={"params": weights}, run_record=m.events,
            step_callback=probe.on_step, stop_flag=m.closed)
        m.end(n_total)
        del state, weights
        program = probe.result()
        # the step's counters over the window's log intervals, as the fit
        # loop's log events carry them
        logs = [f for _, kind, f in m.events.rows if kind == "log"][
            m.edges.opened + 1:m.edges.closed + 1]
        for name in COUNTERS:
            values = [f[name] for f in logs if name in f]
            if values:
                m.counters[name] = (max(values) if name != COUNTERS[0]
                                    else sum(values) / len(values))
        m.reduce()

        # ---- the plain reference, on the freed chip ---------------------
        t_ref = time.perf_counter()
        reference = lm.reference_steps(
            config, config["optimizer"], lm.make_weights(config, seed32),
            lm_traffic.reference_batches(sequences, n_total, check["steps"]),
            **(reference_kw or {}))
        ok, numbers, notes = lm_compare.compare_lm(
            program, reference, check["limits"])
        ref_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(work, ignore_errors=True)

    return m.result(
        correct=ok, numbers=numbers, notes=notes, reference_s=ref_s,
        end_to_end={"train_imgs_per_s": m.stats["imgs_per_s"]})
