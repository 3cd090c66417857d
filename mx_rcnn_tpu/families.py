"""The one table of model families (``cfg.network.family``).

A family is what ``tools/train.py``, ``core/optim.py``, ``core/train.py``
and ``models/faster_rcnn.py::build_model`` have to know to train a model
they have never seen: who builds it, which loss of ``core/train.py::
LOSS_FNS`` its step takes, which optimizer, where its rows come from and
what a row of the batch is.  Every entry names its callables as
``"module:attribute"`` and they are imported when first asked for, so this
module imports nothing of the package and any module may import it.

A new family is one entry here, its model under ``models/``, and (for a
sequence family) a preset in ``config.py``; no other file tests the
family's name.  The sequence families are four: ``nemotron_h`` (Mamba-2,
attention and routed-expert blocks), ``ling_flash`` (a gated delta rule or
latent attention, then a dense or routed-expert MLP), ``joyai_flash``
(latent attention with a low-rank query in every layer, the same MLPs, and
a multi-token-prediction module on the stack's own embedding and head) and
``keye_vl2`` (grouped-query attention over the keys a learned indexer
chooses, then a softmax-routed expert MLP); all four take the ``lm`` step,
AdamW and the token loader.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class Family:
    """``build(cfg, quant_phase)`` -> the Flax model; ``mode``: the key of
    ``LOSS_FNS`` the family's step always takes, or None where the caller
    chooses (the detectors' 'e2e' | 'rpn' | 'rcnn'); ``optimizer(cfg,
    sched, params, frozen_prefixes)`` -> the optax transformation;
    ``source(cfg, seed)`` -> the rows ``train_net`` loads when handed none,
    and ``loader(rows, cfg, batch_images=, shuffle=, seed=)`` the iterator
    over them — both None for the detectors, whose roidb and image loaders
    ``tools/train.py`` picks by mode and input plane; ``row``: what one row
    of the batch is ('image' rows come with a decode pool and a
    decoded-image cache, 'sequence' rows with neither)."""

    build: str
    mode: Optional[str]
    optimizer: str
    source: Optional[str]
    loader: Optional[str]
    row: str

    def get(self, field: str) -> Any:
        """The callable a field names."""
        module, attr = getattr(self, field).split(":")
        return getattr(importlib.import_module(module), attr)


def _sequence(build: str) -> Family:
    return Family(build=build, mode="lm",
                  optimizer="mx_rcnn_tpu.core.optim:adamw",
                  source="mx_rcnn_tpu.data.tokens:load_token_source",
                  loader="mx_rcnn_tpu.data.tokens:TokenLoader",
                  row="sequence")


FAMILIES: Dict[str, Family] = {
    "detector": Family(
        build="mx_rcnn_tpu.models.faster_rcnn:build_detector", mode=None,
        optimizer="mx_rcnn_tpu.core.optim:sgd_frozen", source=None,
        loader=None, row="image"),
    "nemotron_h": _sequence("mx_rcnn_tpu.models.nemotron_h:build_lm"),
    "ling_flash": _sequence("mx_rcnn_tpu.models.ling_flash:build_lm"),
    "joyai_flash": _sequence("mx_rcnn_tpu.models.joyai_flash:build_lm"),
    "keye_vl2": _sequence("mx_rcnn_tpu.models.keye_vl2:build_lm"),
}


def of(cfg) -> Family:
    """The family of ``cfg.network.family``."""
    name = cfg.network.family
    if name not in FAMILIES:
        raise ValueError(f"no model family {name!r}; have {sorted(FAMILIES)}")
    return FAMILIES[name]
