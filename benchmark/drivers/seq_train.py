"""The run of a sequence family's training cell through the program's own
entry, ``mx_rcnn_tpu.tools.train.train_net``, for a family whose plain
reference and comparison are modules of its own: a driver names them and
its probe, and this file does the rest (``drivers/evabyte_train.py``).

The run is ``drivers/lm_train.py``'s: the program's configuration
(``lm_train.program_config``), the token source (``benchmark/lm_traffic.py``),
seed-made weights (the reference module's ``make_weights``) handed over in
memory, the probe of the first steps, the call of ``train_net``, the
window's counters, then the plain reference (the module's
``reference_steps``) one sequence at a time after the state is freed, and
the family's comparison.  The measurement's half is
``drivers/measure.py``'s.
"""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Callable, Dict

from benchmark.drivers import lm_train, measure
from benchmark.drivers.lm_train import program_config


class Probe(lm_train.Probe):
    """``lm_train.Probe`` with the seed-made weights and the small vectors
    of the reference module ``reference`` (a subclass sets it)."""
    reference = None

    def _norms(self, params, mu):
        import jax
        import jax.numpy as jnp

        ref = self.reference
        b1 = self.config["optimizer"]["beta1"]

        def fn(params, mu, seed):
            p0 = ref.tree_paths(ref.make_weights(self.config, seed))
            p1, m = ref.tree_paths(params), ref.tree_paths(mu)
            norms = {k: (jnp.sqrt(jnp.sum(jnp.square(m[k]))) / (1 - b1),
                         jnp.sqrt(jnp.sum(jnp.square(p1[k] - p0[k]))))
                     for k in p0}
            return norms, {k: v / (1 - b1)
                           for k, v in ref.scan_grads(mu).items()}

        return jax.device_get(jax.jit(fn)(params, mu, self.seed))


def run(cell: Dict, run_cell: Callable, *, seed: int, seconds: float,
        trace: bool, t_start: float) -> Dict:
    """One run on the chips the cell asks for; without them, or without the
    family in the program, there is no result."""
    program_config(cell["config"], cell["traffic"], trace)
    measure.need_chips(cell["chips"])
    return run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                    t_start=t_start)


def run_cell(cell: Dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, reference, compare: Callable, probe: type,
             counters: Dict, reference_kw: Dict = None) -> Dict:
    """The run itself, on whatever devices JAX has (the CPU tests of
    ``correct`` start here).  ``reference``: the family's reference module;
    ``compare(program, reference, limits)``: its comparison; ``probe``: a
    ``Probe`` class; ``counters``: {logged counter: None for the window's
    mean, or the reduction of its worst}; ``reference_kw``: options of
    ``reference.reference_steps`` (``precision``, ``fault``)."""
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    check = cell["check"]
    import jax

    from mx_rcnn_tpu import runtime

    runtime.enable_compile_cache()
    cfg = program_config(config, traffic, trace)

    from mx_rcnn_tpu.tools.train import train_net

    from benchmark import lm_traffic

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
        watch = probe(config, seed32, check["steps"])
        m.mark("weights_s")
        state = train_net(
            cfg, prefix=None, end_epoch=1, lr=config["optimizer"]["lr"],
            num_devices=chips, seed=seed32, roidb=source,
            init_from={"params": weights}, run_record=m.events,
            step_callback=watch.on_step, stop_flag=m.closed)
        m.end(n_total)
        del state, weights
        program = watch.result()
        # the step's counters over the window's log intervals, as the fit
        # loop's log events carry them
        logs = [f for _, kind, f in m.events.rows if kind == "log"][
            m.edges.opened + 1:m.edges.closed + 1]
        for name, worst in counters.items():
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
        ok, numbers, notes = compare(program, follow, check["limits"])
        ref_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(work, ignore_errors=True)

    return m.result(
        correct=ok, numbers=numbers, notes=notes, reference_s=ref_s,
        end_to_end={"train_imgs_per_s": m.stats["imgs_per_s"]})
