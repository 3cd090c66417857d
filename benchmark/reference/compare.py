"""The comparison that decides ``correct`` for a training cell.

Every number compared is a relative gap between what the timed path
produced and what the plain reference gives, held against a limit of its
own (the cell's ``limits``, set in PERF.md from chip readings):

* ``loss_sK`` and ``rpn_loss_sK``: step K's total loss and its RPN part
  (which no proposal or ROI sample feeds, so it is smooth in the weights);
* ``grad_worst``: the first gradient as the optimizer gets it, by the
  worst leaf: |program's norm - reference's norm| over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``first_delta_worst``: the same measure on the parameters' change after
  the first step, ``delta_worst`` after the last step followed;
  ``grad_median`` / ``first_delta_median`` / ``delta_median``: the median
  leaf's.

Only the numbers a cell's ``limits`` name are compared (and printed).

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of the leaf measures: they move by weight decay and rounding
alone.  The gradient that gates a measure is the reference's own at the
steps the measure spans: the first step's for ``grad_*`` and
``first_delta_*``, each leaf's largest over the steps followed for
``delta_*``, so a leaf that the reference moves at any of them is held.
"""

from __future__ import annotations

import math
import statistics


def rel_gap(got, want):
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


def leaf_gaps(got, want, gate):
    """{leaf: |got - want| / max(want, median want)} over the leaves whose
    ``gate`` norm clears a thousandth of the median gate norm.  A leaf the
    program lacks reads 1 (it has not moved)."""
    live = [k for k, g in gate.items() if g > 0]
    if not live:
        return {}
    floor = 1e-3 * statistics.median(gate[k] for k in live)
    keep = [k for k in live if gate[k] >= floor]
    med = statistics.median(want[k] for k in keep)
    gaps = {}
    for k in keep:
        gap = abs(got.get(k, 0.0) - want[k]) / max(want[k], med, 1e-30)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    return gaps


def worst_and_median(gaps):
    if not gaps:
        return math.inf, math.inf, None
    where = max(gaps, key=gaps.get)
    return gaps[where], statistics.median(gaps.values()), where


def compare_training(program, reference, limits):
    """``program`` / ``reference``: dicts with ``losses`` (list of dicts with
    'loss', 'rpn_logloss', 'rpn_l1loss'), ``grad_norm``,
    ``first_delta_norm`` and ``delta_norm`` ({path: norm}); the reference
    also has ``grad_norm_any``.  Returns (correct, numbers, notes) where
    numbers is {name: {"value", "limit"}} in the limits' order, every
    compared number beside its limit."""
    numbers = {}
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        numbers[f"loss_s{i + 1}"] = rel_gap(p["loss"], r["loss"])
        numbers[f"rpn_loss_s{i + 1}"] = rel_gap(
            p["rpn_logloss"] + p["rpn_l1loss"],
            r["rpn_logloss"] + r["rpn_l1loss"])
    gate = reference["grad_norm"]
    grad_gaps = leaf_gaps(program["grad_norm"], reference["grad_norm"], gate)
    numbers["grad_worst"], numbers["grad_median"], grad_at = worst_and_median(
        grad_gaps)
    numbers["first_delta_worst"], numbers["first_delta_median"], _ = (
        worst_and_median(leaf_gaps(program["first_delta_norm"],
                                   reference["first_delta_norm"], gate)))
    delta_gaps = leaf_gaps(program["delta_norm"], reference["delta_norm"],
                           reference["grad_norm_any"])
    numbers["delta_worst"], numbers["delta_median"], delta_at = (
        worst_and_median(delta_gaps))
    unknown = set(limits) - set(numbers)
    if unknown:
        raise KeyError(f"limits name numbers that are not computed: {unknown}")
    out, ok = {}, True
    for name, limit in limits.items():
        out[name] = {"value": numbers[name], "limit": limit}
        ok = ok and numbers[name] <= limit
    notes = {"all": numbers, "leaves_compared": len(grad_gaps),
             "leaves_left_out": len(gate) - len(grad_gaps),
             "delta_leaves_left_out": len(gate) - len(delta_gaps),
             "left_out": sorted("/".join(k) for k in set(gate) - set(grad_gaps)
                                )[:12],
             "grad_worst_leaf": "/".join(grad_at) if grad_at else None,
             "delta_worst_leaf": "/".join(delta_at) if delta_at else None}
    return bool(ok), out, notes
