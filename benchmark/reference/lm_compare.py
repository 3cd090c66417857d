"""The comparison that decides ``correct`` for an ``lm_train`` cell: what
the timed path produced in its first two steps against the plain reference
(``benchmark/reference/lm.py``), every number a gap held against a limit of
its own (the cell's ``limits``; PERF.md section 2 has the readings).

* ``loss_s1``, ``loss_s2``: relative gap of step 1's and step 2's loss;
* ``grad_worst``: the first gradient as Adam's first moment holds it after
  step 1 (``mu / (1 - beta1)``: the clipped gradient), by the worst leaf:
  |program's norm - reference's norm| over the larger of the reference's
  norm of that leaf and of the median leaf (``compare.leaf_gaps``);
  ``grad_median`` the median leaf's;
* ``scan_grad_worst``: the gradients of the state-space vectors ``A_log``
  and ``dt_bias`` of every ``M`` block (``lm.SCAN_LEAVES``), which read the scan's decay and its
  carried state and nothing else: per leaf |program - reference| over
  |reference| of the gradient vectors themselves, with no floor.  The leaf
  measures above do not see these leaves (their norms lie far under the
  median leaf's); a decay replaced by 1 reads 1 or more here;
* ``first_delta_worst``: the same measure on the parameters' change after
  step 1; a state left unchanged reads 1;
* ``routing_diff``: the share of step 1's assignments to held experts that
  differ, sum over expert layers and held experts of |program's count -
  reference's| over the reference's total: bfloat16 flips a choice among
  near-equal scores, as it flips a detector's choice among ROIs;
* ``moe_overflow``: rows the program did not compute in steps 1 and 2; its
  limit is 0.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.compare import leaf_gaps, rel_gap, worst_and_median


def compare_lm(program, reference, limits):
    """``program`` / ``reference``: ``losses`` (two floats), ``grad_norm``,
    ``first_delta_norm`` ({path: norm}), ``scan_grad`` ({path: vector}),
    ``counts`` (expert layers x held);
    the program also ``overflow``.  Returns (correct, numbers, notes) as
    ``compare.compare_training`` does."""
    numbers = {f"loss_s{i + 1}": rel_gap(p, r) for i, (p, r) in enumerate(
        zip(program["losses"], reference["losses"]))}
    gate = reference["grad_norm"]
    grad_gaps = leaf_gaps(program["grad_norm"], gate, gate)
    numbers["grad_worst"], numbers["grad_median"], grad_at = worst_and_median(
        grad_gaps)
    # a state-space vector the program lacks counts as zeros
    pairs = {k: (np.asarray(program["scan_grad"].get(k, np.zeros_like(w)),
                            np.float64), np.asarray(w, np.float64))
             for k, w in reference["scan_grad"].items()}
    scan = {k: float(np.linalg.norm(g - w)) / max(float(np.linalg.norm(w)),
                                                  1e-30)
            for k, (g, w) in pairs.items()}
    numbers["scan_grad_worst"], _, scan_at = worst_and_median(scan)
    delta_gaps = leaf_gaps(program["first_delta_norm"],
                           reference["first_delta_norm"], gate)
    (numbers["first_delta_worst"], numbers["first_delta_median"],
     delta_at) = worst_and_median(delta_gaps)
    want = [c for row in reference["counts"] for c in row]
    got = [c for row in program["counts"] for c in row]
    numbers["routing_diff"] = (
        sum(abs(a - b) for a, b in zip(got, want)) / max(sum(want), 1)
        if len(got) == len(want) else float("inf"))
    numbers["moe_overflow"] = float(program["overflow"])
    unknown = set(limits) - set(numbers)
    if unknown:
        raise KeyError(f"limits name numbers that are not computed: {unknown}")
    out, ok = {}, True
    for name, limit in limits.items():
        out[name] = {"value": numbers[name], "limit": limit}
        ok = ok and numbers[name] <= limit
    worst = sorted(grad_gaps, key=grad_gaps.get, reverse=True)[:5]
    notes = {"all": numbers, "leaves_compared": len(grad_gaps),
             "left_out": sorted("/".join(k) for k in set(gate) - set(grad_gaps)),
             "grad_worst_leaf": "/".join(grad_at) if grad_at else None,
             "grad_worst_leaves": {"/".join(k): grad_gaps[k] for k in worst},
             "scan_grad_worst_leaf": "/".join(scan_at) if scan_at else None,
             "scan_grad_leaves": {"/".join(k): v for k, v in scan.items()},
             "delta_worst_leaf": "/".join(delta_at) if delta_at else None,
             "reference_loss": reference["losses"],
             "assignments": [sum(got), sum(want)]}
    return bool(ok), out, notes
