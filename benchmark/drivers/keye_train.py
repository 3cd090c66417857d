"""The ``keye_train`` driver: one run of a training cell of the
``keye_vl2`` family (Keye-VL-2.0's language stack: grouped-query attention
over the keys a learned indexer chooses, a softmax-routed expert layer)
through the program's own entry, ``mx_rcnn_tpu.tools.train.train_net``.

The run is ``drivers/joyai_train.py``'s, with this family's reference and
comparison in the others' place: the program's configuration
(``lm_train.program_config``), the token source (``benchmark/lm_traffic.py``),
seed-made weights handed over in memory, the probe of the first two steps
(``lm_train.Probe``, which here keeps the next-token loss ``loss_main`` in
the losses' place, step 1's indexer loss, the chosen keys of the sample
rows the step hands out and each step's ``dsa_count_misses``, and brings
back whole the gradients of the vectors
``reference.SCAN_LEAVES`` names), the call of ``train_net``, the plain
reference (``benchmark/reference/keye_vl2.py``) one sequence at a time
after the state is freed, and the comparison (``reference/keye_compare.py``).
The measurement's half is ``drivers/measure.py``'s.  Beside ``lm_train``'s
three routed-expert counters the window keeps the means of the logged
``index_loss``, ``tiles_touched_pct`` and ``loss_main``.

A program without this family (``generate_config`` knows no ``keye_vl2``)
is a ``CellFailure`` before any work.
"""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict

import numpy as np

from benchmark.drivers import lm_train, measure
from benchmark.drivers.lm_train import program_config
from benchmark.drivers.measure import CellFailure  # noqa: F401 (exported)
from benchmark.drivers.train import _fit_locals
from benchmark.reference import keye_vl2 as reference

# the window's value of each counter: its mean, or its worst
COUNTERS = {"moe_assignments_per_token": None, "moe_load_max_over_mean": max,
            "moe_overflow": max, "index_loss": None,
            "tiles_touched_pct": None, "loss_main": None}


class Probe(lm_train.Probe):
    """``lm_train.Probe`` with this family's seed-made weights and small
    vectors, which keeps the next-token loss in the losses' place, step
    1's indexer loss, the chosen keys of the sample rows and each step's
    count of queries whose attention took another number of keys than the
    choice holds."""

    def __init__(self, config: Dict, seed: int, steps: int):
        super().__init__(config, seed, steps)
        self.main_losses, self.index_losses, self.chosen = [], [], None
        self.count_misses = []

    def _norms(self, params, mu):
        import jax
        import jax.numpy as jnp

        b1 = self.config["optimizer"]["beta1"]

        def fn(params, mu, seed):
            p0 = reference.tree_paths(reference.make_weights(self.config,
                                                             seed))
            p1, m = reference.tree_paths(params), reference.tree_paths(mu)
            norms = {k: (jnp.sqrt(jnp.sum(jnp.square(m[k]))) / (1 - b1),
                         jnp.sqrt(jnp.sum(jnp.square(p1[k] - p0[k]))))
                     for k in p0}
            return norms, {k: v / (1 - b1)
                           for k, v in reference.scan_grads(mu).items()}

        return jax.device_get(jax.jit(fn)(params, mu, self.seed))

    def on_step(self, step: int) -> None:
        if step <= self.steps:
            import jax

            m = _fit_locals()["metrics"]
            self.main_losses.append(float(jax.device_get(m["loss_main"])))
            self.count_misses.append(int(jax.device_get(
                m["dsa_count_misses"])))
            if step == 1:
                self.index_losses.append(float(jax.device_get(
                    m["index_loss"])))
                self.chosen = np.asarray(jax.device_get(m["dsa_chosen"]),
                                         np.int8)
        super().on_step(step)

    def result(self) -> Dict:
        return dict(super().result(), losses=self.main_losses,
                    index_losses=self.index_losses, chosen=self.chosen,
                    count_misses=self.count_misses)


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
    ``reference.reference_steps`` (``precision``, ``fault``)."""
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    check = cell["check"]
    import jax

    from mx_rcnn_tpu import runtime

    runtime.enable_compile_cache()
    cfg = program_config(config, traffic, trace)

    from mx_rcnn_tpu.tools.train import train_net

    from benchmark import lm_traffic
    from benchmark.reference import keye_compare

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
        weights = jax.jit(lambda s: reference.make_weights(config, s))(seed32)
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
        for name, worst in COUNTERS.items():
            values = [f[name] for f in logs if name in f]
            if values:
                m.counters[name] = (worst(values) if worst
                                    else sum(values) / len(values))
        m.reduce()

        # ---- the plain reference, on the freed chip ---------------------
        t_ref = time.perf_counter()
        follow = reference.reference_steps(
            config, config["optimizer"],
            reference.make_weights(config, seed32),
            lm_traffic.reference_batches(sequences, n_total, check["steps"]),
            **(reference_kw or {}))
        ok, numbers, notes = keye_compare.compare_keye(
            program, follow, check["limits"])
        ref_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(work, ignore_errors=True)

    return m.result(
        correct=ok, numbers=numbers, notes=notes, reference_s=ref_s,
        end_to_end={"train_imgs_per_s": m.stats["imgs_per_s"]})
