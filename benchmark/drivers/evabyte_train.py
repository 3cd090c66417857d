"""The ``evabyte_train`` driver: one run of a training cell of the
``evabyte`` family (EvaByte: EVA attention, exact keys inside a window and
one pooled key and value for each earlier chunk, a dense MLP on a float32
residual stream, several byte-prediction heads) through the program's own
entry, ``mx_rcnn_tpu.tools.train.train_net``.

The run is ``drivers/seq_train.py``'s, with this family's reference
(``benchmark/reference/evabyte.py``), comparison
(``reference/evabyte_compare.py``) and probe: ``lm_train.Probe``'s reading
of the state after step 1, the losses being the heads' mean ``L``, beside
them step 1's head-0 loss ``loss_main`` and each step's
``eva_count_misses``; the stack has no expert layer to count.  The window
keeps the means of the logged ``eva_summary_keys_pct`` and ``loss_main``
and the worst ``eva_count_misses``.

A program without this family (``generate_config`` knows no ``evabyte``)
is a ``CellFailure`` before any work.
"""

from __future__ import annotations

from typing import Dict

from benchmark.drivers import lm_train, seq_train
from benchmark.drivers.lm_train import program_config  # noqa: F401 (exported)
from benchmark.drivers.measure import CellFailure  # noqa: F401 (exported)
from benchmark.drivers.train import _fit_locals
from benchmark.reference import evabyte as reference

# the window's value of each counter: its mean, or its worst
COUNTERS = {"eva_summary_keys_pct": None, "eva_count_misses": max,
            "loss_main": None}


class Probe(seq_train.Probe):
    """Also keeps step 1's head-0 loss and each step's count of rows whose
    softmax took another number of keys; no expert layer."""
    reference = reference

    def __init__(self, config: Dict, seed: int, steps: int):
        super().__init__(config, seed, steps)
        self.main_losses, self.count_misses = [], []

    def on_step(self, step: int) -> None:
        if step > self.steps:
            return
        import jax

        loc = _fit_locals()
        m = jax.device_get(loc["metrics"])
        self.losses.append(float(m["loss"]))
        self.count_misses.append(int(m["eva_count_misses"]))
        if step == 1:
            self.main_losses.append(float(m["loss_main"]))
            state = loc["state"]
            norms, self.scan_grad = self._norms(
                state.params, lm_train._adam_mu(state.opt_state))
            for k, (g, d) in norms.items():
                self.grad_norm[k] = float(g)
                self.first_delta_norm[k] = float(d)

    def result(self) -> Dict:
        return dict(super().result(), main_losses=self.main_losses,
                    count_misses=self.count_misses)


def run(cell: Dict, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict:
    return seq_train.run(cell, run_cell, seed=seed, seconds=seconds,
                         trace=trace, t_start=t_start)


def run_cell(cell: Dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, reference_kw: Dict = None) -> Dict:
    """``seq_train.run_cell`` with this family's parts."""
    from benchmark.reference import evabyte_compare

    return seq_train.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace, t_start=t_start,
        reference=reference, compare=evabyte_compare.compare_evabyte,
        probe=Probe, counters=COUNTERS, reference_kw=reference_kw)
