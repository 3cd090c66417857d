"""Optimizer construction: SGD + momentum + weight decay + step-decay LR,
with reference ``FIXED_PARAMS`` freezing.

Reference: ``train_end2end.py — train_net`` configures
``optimizer='sgd'`` with ``momentum=0.9``, ``wd=0.0005``,
``lr`` warm-from-config with an ``MultiFactorScheduler`` stepping ×0.1 at
``lr_step`` epoch boundaries, and ``rcnn/core/module.py — MutableModule``
excludes parameters whose name starts with any ``fixed_param_prefix`` from
the update.

TPU-native: one ``optax`` chain; freezing is an explicit gradient mask over
the param tree (prefix match on the top-level module scope names, e.g.
``backbone/conv1_*`` for VGG, ``backbone/stage1_*``/``backbone/bn*`` for
ResNet).  MXNet applies weight decay to every parameter, so we do too.
"""

from __future__ import annotations

from typing import Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from mx_rcnn_tpu.config import Config


def lr_schedule(base_lr: float, lr_step_epochs: Sequence[int],
                steps_per_epoch: int, factor: float = 0.1,
                warmup_step: int = 0, warmup_lr: float = 0.0
                ) -> optax.Schedule:
    """Step-decay schedule with optional linear warmup.

    Step decay follows ref MultiFactorScheduler semantics (multiply lr by
    ``factor`` when crossing each epoch boundary in ``lr_step``); warmup
    follows the upstream lineage's WarmupMultiFactorScheduler
    (``warmup='linear'``: ramp from ``warmup_lr`` to ``base_lr`` over
    ``warmup_step`` steps) — off by default, matters at large DP batch.
    """
    boundaries = {
        int(e) * steps_per_epoch: factor for e in lr_step_epochs if int(e) > 0
    }
    decay = optax.piecewise_constant_schedule(base_lr, boundaries)
    if warmup_step <= 0:
        return decay

    def schedule(count):
        # decay boundaries count from global step 0 (the ref scheduler also
        # counts warmup steps against the decay boundaries)
        frac = jnp.minimum(count / warmup_step, 1.0)
        warm = warmup_lr + (base_lr - warmup_lr) * frac
        return jnp.where(count < warmup_step, warm, decay(count))

    return schedule


def parse_lr_step(lr_step: str) -> Tuple[int, ...]:
    """'7' or '5,7' → (7,) / (5, 7) (ref: comma-separated epoch list)."""
    return tuple(int(s) for s in str(lr_step).split(",") if s.strip())


def frozen_mask(params, fixed_prefixes: Iterable[str]):
    """True = trainable, False = frozen.

    A parameter is frozen when any path component starts with one of the
    reference's FIXED_PARAMS prefixes (ref MutableModule fixed_param_prefix
    matching by substring of the MXNet param name).

    The reference ResNet FIXED_PARAMS also lists ``'gamma'``/``'beta'`` —
    MXNet names every BN affine ``*_gamma``/``*_beta``, so those two tokens
    freeze the affine of EVERY BatchNorm network-wide (statistics are frozen
    anyway; training an affine against frozen stats with weight decay is the
    divergence ADVICE r1 flagged).  Here the equivalent leaves are
    ``scale``/``bias`` directly under a ``bn*`` scope.
    """
    prefixes = tuple(fixed_prefixes)
    freeze_gamma = "gamma" in prefixes
    freeze_beta = "beta" in prefixes

    def trainable(path: Tuple, _leaf) -> bool:
        names = [getattr(k, "key", str(k)) for k in path]
        for name in names:
            if any(name.startswith(p) for p in prefixes):
                return False
        leaf = names[-1] if names else ""
        parent = names[-2] if len(names) > 1 else ""
        if parent.startswith("bn"):
            if freeze_gamma and leaf == "scale":
                return False
            if freeze_beta and leaf == "bias":
                return False
        return True

    return jax.tree_util.tree_map_with_path(trainable, params)


ADAM_B2, ADAM_EPS = 0.95, 1e-8


def adamw(cfg: Config, sched: optax.Schedule, params=None,
          frozen_prefixes=None) -> optax.GradientTransformation:
    """The sequence families' optimizer: clip the whole gradient to a global
    norm of ``default.clip_gradient``, Adam (beta1 ``default.momentum``,
    beta2 0.95, eps 1e-8, float32 moments), decoupled weight decay
    ``default.wd`` on matrices alone (leaves of two or more axes: norm
    scales, biases and the state-space vectors are not decayed), the
    schedule's lr.  The clip needs every gradient before any update, so
    the update cannot fuse into the gradients' producers.  Nothing of a
    sequence family is frozen: ``params`` and ``frozen_prefixes`` are the
    family table's signature (``families.py``) and are not read."""
    return optax.chain(
        optax.clip_by_global_norm(cfg.default.clip_gradient),
        optax.adamw(sched, b1=cfg.default.momentum, b2=ADAM_B2, eps=ADAM_EPS,
                    weight_decay=cfg.default.wd,
                    mask=lambda params: jax.tree.map(
                        lambda p: p.ndim >= 2, params)))


def sgd_frozen(cfg: Config, sched: optax.Schedule, params,
               frozen_prefixes: Sequence[str]
               ) -> optax.GradientTransformation:
    """The detectors' optimizer: SGD(momentum, wd) on the schedule with the
    reference's elementwise clip and FIXED_PARAMS freezing; ``params`` is
    only used to build the freeze mask pytree."""
    # momentum accumulator dtype: bfloat16 halves optimizer-state HBM and
    # bandwidth (config.default.momentum_dtype — TPU addition; float32 =
    # exact reference semantics); unknown spellings raise
    from mx_rcnn_tpu.config import validate_dtype_string

    md = validate_dtype_string(cfg.default.momentum_dtype,
                               "default__momentum_dtype")
    acc_dtype = jnp.bfloat16 if md == "bfloat16" else None
    sgd = optax.chain(
        # ref optimizer_params: elementwise clip_gradient=5 before update
        optax.clip(cfg.default.clip_gradient),
        optax.add_decayed_weights(cfg.default.wd),
        optax.sgd(learning_rate=sched, momentum=cfg.default.momentum,
                  accumulator_dtype=acc_dtype),
    )
    mask = frozen_mask(params, frozen_prefixes)
    return optax.chain(
        optax.masked(sgd, mask),
        optax.masked(optax.set_to_zero(), jax.tree.map(lambda t: not t, mask)),
    )


def make_optimizer(
    cfg: Config,
    params,
    steps_per_epoch: int,
    base_lr: float | None = None,
    lr_step: str | None = None,
    frozen_prefixes: Sequence[str] | None = None,
) -> optax.GradientTransformation:
    """The family's optimizer (``families.py``) on the step-decay schedule:
    :func:`sgd_frozen` for the detectors, :func:`adamw` for a sequence
    family.

    ``params`` is only used to build the freeze mask pytree.
    """
    from mx_rcnn_tpu import families

    base_lr = cfg.default.e2e_lr if base_lr is None else base_lr
    lr_step = cfg.default.e2e_lr_step if lr_step is None else lr_step
    if frozen_prefixes is None:
        frozen_prefixes = cfg.network.fixed_params
    sched = lr_schedule(base_lr, parse_lr_step(lr_step), steps_per_epoch,
                        cfg.default.lr_factor,
                        warmup_step=cfg.default.warmup_step,
                        warmup_lr=cfg.default.warmup_lr)
    return families.of(cfg).get("optimizer")(cfg, sched, params,
                                             frozen_prefixes)
