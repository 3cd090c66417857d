"""The comparison that decides ``correct`` for an ``evabyte_train`` cell:
``reference/lm_compare.py``'s numbers (``loss_s1`` and ``loss_s2`` of the
heads' mean loss ``L``, ``grad_worst``, ``first_delta_worst``), every one
held against a limit of its own (the cell's ``limits``; PERF.md section 2
has the readings), and four of this family:

* ``loss_main_s1``: relative gap of step 1's head-0 loss ``L_main``;
* ``eva_grad_worst``: the gradient vectors of every layer's pooling vectors
  ``adaptive_mu_k`` and ``adaptive_phi`` (``evabyte.EVA_LEAVES``): per leaf
  |program - reference| over |reference|, no floor.  Only the chunk
  summaries reach them, so a pooling that ignores them, or summaries that
  are not there, read 1 here while the leaf norms above stay nearly still;
* ``norm_grad_worst``: the same measure on every norm's offset
  (``evabyte.NORM_LEAVES``); the final norm's reads every head's target;
* ``count_misses``: the program's own, over steps 1 and 2 (the worse): the
  (query, head) rows whose softmax took another number of keys than ``t -
  s(t) + 1 + s(t) / chunk``, summed over layers, counted from the masks the
  attention applied.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import lm_compare
from benchmark.reference.compare import rel_gap, worst_and_median
from benchmark.reference.evabyte import EVA_LEAVES, NORM_LEAVES

VECTORS = {"eva_grad_worst": EVA_LEAVES, "norm_grad_worst": NORM_LEAVES}


def compare_evabyte(program, reference, limits):
    """As ``lm_compare.compare_lm``, with ``main_losses`` beside ``losses``
    and the program's ``count_misses``; returns (correct, numbers, notes)."""
    own = set(VECTORS) | {"loss_main_s1", "count_misses"}
    no_vectors = lambda r: dict(r, scan_grad={})  # noqa: E731
    ok, out, notes = lm_compare.compare_lm(
        no_vectors(program), no_vectors(reference),
        {k: v for k, v in limits.items() if k not in own})
    # ``compare_lm``'s own vector number and expert counts have nothing to
    # read here
    for key in ("scan_grad_worst_leaf", "scan_grad_leaves", "assignments"):
        notes.pop(key)
    for key in ("scan_grad_worst", "routing_diff", "moe_overflow"):
        notes["all"].pop(key)
    values = {"loss_main_s1": rel_gap(program["main_losses"][0],
                                      reference["main_losses"][0]),
              "count_misses": float(max(program["count_misses"]))}
    for name, leaves in VECTORS.items():
        gaps = {}
        for k, w in reference["scan_grad"].items():
            if k[-1] in leaves:
                w = np.asarray(w, np.float64)
                g = np.asarray(program["scan_grad"].get(k, np.zeros_like(w)),
                               np.float64)
                gaps[k] = float(np.linalg.norm(g - w)) / max(
                    float(np.linalg.norm(w)), 1e-30)
        values[name], _, at = worst_and_median(gaps)
        notes[f"{name}_leaf"] = "/".join(at) if at else None
        notes[f"{name}_leaves"] = {"/".join(k): v for k, v in gaps.items()}
    notes["reference_main_loss"] = reference["main_losses"]
    for name, value in values.items():
        notes["all"][name] = value
        if name in limits:
            out[name] = {"value": value, "limit": limits[name]}
            ok = ok and value <= limits[name]
    # the limits' order, every compared number beside its limit
    return bool(ok), {k: out[k] for k in limits}, notes
