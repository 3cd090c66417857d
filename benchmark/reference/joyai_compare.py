"""The comparison that decides ``correct`` for a ``joyai_train`` cell:
``reference/lm_compare.py``'s numbers (``loss_s1`` and ``loss_s2`` of the
weighted loss ``L``, ``grad_worst``, ``first_delta_worst``, ``routing_diff``,
``moe_overflow``), every one held against a limit of its own (the cell's
``limits``; PERF.md section 2 has the readings), and three of this family:

* ``mtp_loss_s1``: relative gap of step 1's module loss alone, which the
  weighted loss holds at three tenths;
* ``latent_grad_worst``: the gradient vectors of ``q_a_norm`` and
  ``kv_a_norm`` of every latent block, the module's among them
  (``joyai_flash.LATENT_LEAVES``): per leaf |program - reference| over
  |reference|, no floor.  A rotation keeps every norm the leaf measures see,
  so the rotary term dropped or paired by halves, and the low-rank query's
  norm dropped, are read here channel by channel;
* ``mtp_grad_worst``: the same measure on the module's three norm scales
  (``joyai_flash.MTP_LEAVES``: ``enorm``, ``hnorm``, ``shared_head_norm``),
  which read the module's shift, the order of its halves and the weight of
  its loss;
* ``final_norm_grad``: the same measure on the stack's final norm scale,
  whose gradient is the next-token loss's plus what the module sends back
  through ``h``: the one number that moves where the module reads ``h``
  before that norm (the module's own norm of ``h`` follows it, so at unit
  scales no activation moves);
* ``shared_grad_worst``: the same measure on a slice of the two arrays both
  paths read (``joyai_flash.SHARED_LEAVES``: the embedding's first 256 rows,
  the head's first 256 columns): the two paths' gradients add there, and a
  path cut off moves the whole leaf's norm by a few hundredths only.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import lm_compare
from benchmark.reference.compare import rel_gap, worst_and_median
from benchmark.reference.joyai_flash import (FINAL_LEAVES, LATENT_LEAVES,
                                             MTP_LEAVES, SHARED_LEAVES)

VECTORS = {"latent_grad_worst": LATENT_LEAVES, "mtp_grad_worst": MTP_LEAVES,
           "final_norm_grad": FINAL_LEAVES,
           "shared_grad_worst": SHARED_LEAVES}


def compare_joyai(program, reference, limits):
    """As ``lm_compare.compare_lm``, with ``mtp_losses`` beside ``losses``;
    returns (correct, numbers, notes)."""
    own = set(VECTORS) | {"mtp_loss_s1"}
    no_vectors = lambda r: dict(r, scan_grad={})  # noqa: E731
    ok, out, notes = lm_compare.compare_lm(
        no_vectors(program), no_vectors(reference),
        {k: v for k, v in limits.items() if k not in own})
    # ``compare_lm``'s own vector number is given nothing to read here
    for key in ("scan_grad_worst_leaf", "scan_grad_leaves"):
        notes.pop(key)
    notes["all"].pop("scan_grad_worst")
    values = {"mtp_loss_s1": rel_gap(program["mtp_losses"][0],
                                     reference["mtp_losses"][0])}
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
    notes["reference_mtp_loss"] = reference["mtp_losses"]
    for name, value in values.items():
        notes["all"][name] = value
        if name in limits:
            out[name] = {"value": value, "limit": limits[name]}
            ok = ok and value <= limits[name]
    # the limits' order, every compared number beside its limit
    return bool(ok), {k: out[k] for k in limits}, notes
