"""The comparison that decides ``correct`` for a ``ling_train`` cell:
``reference/lm_compare.py``'s numbers, every one held against a limit of its
own (the cell's ``limits``; PERF.md section 2 has the readings), with the
small vectors' gradients in two numbers instead of one:

* ``scan_grad_worst``: the gradient vectors of ``A_log`` and ``dt_bias`` of
  every KDA block (``ling_flash.KDA_LEAVES``), which read the delta rule's
  decay and its carried state and nothing else: per leaf |program -
  reference| over |reference|, no floor (``compare_lm``'s own number, given
  these leaves alone); a state not carried across chunks or a decay
  replaced by 1 reads 1 or more;
* ``latent_grad_worst``: the same measure on the latent-attention block's
  three norm scales (``ling_flash.LATENT_LEAVES``: ``q_norm``, ``k_norm``,
  ``kv_a_norm``).  Dropping the rotary term turns a third of every head's
  channels without changing one norm, so the leaf measures (norms) read
  0.0015 where this reads 0.73; dropping the latent's norm hardly moves an
  activation at these weights (the latent's RMS is 1.02) and reads 1 here.
  bfloat16 reads 0.05-0.10 here against 0.18-0.25 on the KDA vectors, which
  is why the two are held apart.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import lm_compare
from benchmark.reference.compare import worst_and_median
from benchmark.reference.ling_flash import KDA_LEAVES, LATENT_LEAVES


def _only(result, names):
    return dict(result, scan_grad={k: v for k, v in result["scan_grad"].items()
                                   if k[-1] in names})


def compare_ling(program, reference, limits):
    """As ``lm_compare.compare_lm``; returns (correct, numbers, notes)."""
    own = "latent_grad_worst"
    ok, out, notes = lm_compare.compare_lm(
        _only(program, KDA_LEAVES), _only(reference, KDA_LEAVES),
        {k: v for k, v in limits.items() if k != own})
    got = _only(program, LATENT_LEAVES)["scan_grad"]
    gaps = {}
    for k, w in _only(reference, LATENT_LEAVES)["scan_grad"].items():
        w = np.asarray(w, np.float64)
        g = np.asarray(got.get(k, np.zeros_like(w)), np.float64)
        gaps[k] = float(np.linalg.norm(g - w)) / max(float(np.linalg.norm(w)),
                                                     1e-30)
    value, _, at = worst_and_median(gaps)
    notes["all"][own] = value
    notes["latent_grad_worst_leaf"] = "/".join(at) if at else None
    notes["latent_grad_leaves"] = {"/".join(k): v for k, v in gaps.items()}
    if own in limits:
        out[own] = {"value": value, "limit": limits[own]}
        ok = ok and value <= limits[own]
    return bool(ok), out, notes
