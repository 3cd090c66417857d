"""The comparison that decides ``correct`` for a ``keye_train`` cell:
``reference/lm_compare.py``'s numbers (``loss_s1`` and ``loss_s2`` of the
next-token loss ``L_main``, ``grad_worst``, ``first_delta_worst``,
``routing_diff``, ``moe_overflow``), every one held against a limit of its
own (the cell's ``limits``; PERF.md section 2 has the readings), and five of
this family:

* ``index_loss_s1``: relative gap of step 1's indexer loss, summed over the
  layers;
* ``head_norm_grad_worst``: the gradient vectors of every layer's
  ``q_norm`` and ``k_norm`` (``keye_vl2.HEAD_LEAVES``): per leaf |program -
  reference| over |reference|, no floor.  A rotation keeps every norm the
  leaf measures see, so the head norms and the rotary term are read here
  channel by channel;
* ``indexer_grad_worst``: the same measure on the indexer's head weights
  ``index_weights_proj`` (as one vector) and its key's LayerNorm
  (``keye_vl2.INDEX_LEAVES``), which only the indexer's loss reaches;
* ``selection_diff``: of step 1's chosen (query, key) pairs in the rows the
  step hands out (four a layer, the first sequence) and the reference's,
  the pairs only one of them chose over the pairs either chose;
* ``count_misses``: the program's own, over steps 1 and 2 (the worse): the
  queries whose attention took another number of keys than the choice
  holds, ``min(t + 1, topk)``, summed over layers.  The attention's mask is
  formed from scores computed again; a count that is not the choice's says
  that attention masked in another set than the one chosen.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import lm_compare
from benchmark.reference.compare import rel_gap, worst_and_median
from benchmark.reference.keye_vl2 import HEAD_LEAVES, INDEX_LEAVES

VECTORS = {"head_norm_grad_worst": HEAD_LEAVES,
           "indexer_grad_worst": INDEX_LEAVES}


def selection_diff(program, reference) -> float:
    """Chosen by one of the two alone, over chosen by either, in the sample
    rows."""
    got = np.asarray(program, bool)
    want = np.asarray(reference, bool)
    if got.shape != want.shape:
        return float("inf")
    return float((got ^ want).sum()) / max(int((got | want).sum()), 1)


def compare_keye(program, reference, limits):
    """As ``lm_compare.compare_lm``, with ``index_losses`` and ``chosen``
    beside ``losses``; returns (correct, numbers, notes)."""
    own = set(VECTORS) | {"index_loss_s1", "selection_diff", "count_misses"}
    no_vectors = lambda r: dict(r, scan_grad={})  # noqa: E731
    ok, out, notes = lm_compare.compare_lm(
        no_vectors(program), no_vectors(reference),
        {k: v for k, v in limits.items() if k not in own})
    # ``compare_lm``'s own vector number is given nothing to read here
    for key in ("scan_grad_worst_leaf", "scan_grad_leaves"):
        notes.pop(key)
    notes["all"].pop("scan_grad_worst")
    values = {"index_loss_s1": rel_gap(program["index_losses"][0],
                                       reference["index_losses"][0]),
              "selection_diff": selection_diff(program["chosen"],
                                               reference["chosen"]),
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
    notes["reference_index_loss"] = reference["index_losses"]
    for name, value in values.items():
        notes["all"][name] = value
        if name in limits:
            out[name] = {"value": value, "limit": limits[name]}
            ok = ok and value <= limits[name]
    # the limits' order, every compared number beside its limit
    return bool(ok), {k: out[k] for k in limits}, notes
