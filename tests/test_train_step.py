"""End-to-end train step tests: single jitted step, freezing, overfit.

The overfit test is the framework's "is it learning" proxy (SURVEY.md §4:
the reference's signal was RPNAcc≈0.9+/RCNNAcc≈0.8+ early in training).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.optim import frozen_mask, make_optimizer
from mx_rcnn_tpu.core.train import (
    Batch,
    init_state,
    loss_and_metrics,
    make_train_step,
    setup_training,
)
from mx_rcnn_tpu.models import build_model

KEY = jax.random.PRNGKey(42)


def tiny_setup(batch_images=1, size=128):
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=256, rpn_post_nms_top_n=64,
                         batch_rois=32, max_gt_boxes=8, rpn_min_size=2)
    model = build_model(cfg)
    state, tx = setup_training(model, cfg, KEY, (batch_images, size, size, 3),
                               steps_per_epoch=100)
    return cfg, model, tx, state




def make_batch(n=1, size=128, seed=0):
    rng = np.random.RandomState(seed)
    images = jnp.array(rng.randn(n, size, size, 3).astype(np.float32))
    im_info = jnp.tile(jnp.array([[float(size), float(size), 1.0]]), (n, 1))
    g = 8
    gt_boxes = jnp.zeros((n, g, 4))
    gt_classes = jnp.zeros((n, g), jnp.int32)
    gt_valid = jnp.zeros((n, g), bool)
    for i in range(n):
        gt_boxes = gt_boxes.at[i, 0].set(jnp.array([20.0, 24.0, 70.0, 90.0]))
        gt_classes = gt_classes.at[i, 0].set(7)
        gt_valid = gt_valid.at[i, 0].set(True)
        gt_boxes = gt_boxes.at[i, 1].set(jnp.array([80.0, 30.0, 120.0, 70.0]))
        gt_classes = gt_classes.at[i, 1].set(12)
        gt_valid = gt_valid.at[i, 1].set(True)
    return Batch(images, im_info, gt_boxes, gt_classes, gt_valid)


def test_loss_and_metrics_finite():
    cfg, model, tx, state = tiny_setup()
    batch = make_batch()
    loss, metrics = loss_and_metrics(model, state.params, state.batch_stats,
                                     batch, KEY, cfg)
    assert np.isfinite(float(loss))
    for k, v in metrics.items():
        assert np.isfinite(float(v)), k
    assert 0.0 <= float(metrics["rpn_acc"]) <= 1.0
    assert float(metrics["num_fg"]) >= 1  # gt-append guarantees fg


def test_train_step_updates_params_and_step():
    cfg, model, tx, state = tiny_setup()
    step = jax.jit(make_train_step(model, cfg, tx))
    batch = make_batch()
    new_state, metrics = step(state, batch, KEY)
    assert int(new_state.step) == 1
    # some parameter must have moved
    diff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                        state.params, new_state.params)
    assert max(jax.tree.leaves(diff)) > 0
    # batch_stats are frozen — must be bit-identical
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        state.batch_stats, new_state.batch_stats)
    assert all(jax.tree.leaves(same))


def test_frozen_params_do_not_move():
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("network", fixed_params=("conv1",))
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=256, rpn_post_nms_top_n=64,
                         batch_rois=32, max_gt_boxes=8, rpn_min_size=2)
    model = build_model(cfg)
    state, tx = setup_training(model, cfg, KEY, (1, 128, 128, 3),
                               steps_per_epoch=100)
    step = jax.jit(make_train_step(model, cfg, tx))
    new_state, _ = step(state, make_batch(), KEY)
    frozen_before = state.params["backbone"]["conv1"]["kernel"]
    frozen_after = new_state.params["backbone"]["conv1"]["kernel"]
    assert bool((frozen_before == frozen_after).all())
    live_before = state.params["backbone"]["conv2"]["kernel"]
    live_after = new_state.params["backbone"]["conv2"]["kernel"]
    assert float(jnp.abs(live_before - live_after).max()) > 0


def test_frozen_mask_prefixes():
    cfg = generate_config("resnet101", "PascalVOC")
    fake_params = {
        "backbone": {
            "conv0": {"kernel": jnp.zeros(1)},
            "stage1_unit1": {"conv1": {"kernel": jnp.zeros(1)}},
            "stage2_unit1": {"conv1": {"kernel": jnp.zeros(1)}},
            "bn_data": {"scale": jnp.zeros(1)},
        },
        "rpn": {"rpn_conv_3x3": {"kernel": jnp.zeros(1)}},
    }
    mask = frozen_mask(fake_params, cfg.network.fixed_params)
    assert mask["backbone"]["conv0"]["kernel"] is False
    assert mask["backbone"]["stage1_unit1"]["conv1"]["kernel"] is False
    assert mask["backbone"]["bn_data"]["scale"] is False
    assert mask["backbone"]["stage2_unit1"]["conv1"]["kernel"] is True
    assert mask["rpn"]["rpn_conv_3x3"]["kernel"] is True


def test_frozen_mask_bn_affine_network_wide():
    """Ref ResNet FIXED_PARAMS lists 'gamma'/'beta': EVERY BatchNorm affine
    is frozen (ADVICE r1 medium), including unfrozen stages and the head —
    but not conv kernels there, and not non-BN biases."""
    cfg = generate_config("resnet101", "PascalVOC")
    fake_params = {
        "backbone": {
            "stage3_unit5": {
                "bn1": {"scale": jnp.zeros(1), "bias": jnp.zeros(1)},
                "conv1": {"kernel": jnp.zeros(1)},
            },
        },
        "head": {
            "stage4_unit1": {"bn2": {"scale": jnp.zeros(1)}},
            "bn1": {"scale": jnp.zeros(1), "bias": jnp.zeros(1)},
        },
        "cls_score": {"kernel": jnp.zeros(1), "bias": jnp.zeros(1)},
    }
    mask = frozen_mask(fake_params, cfg.network.fixed_params)
    assert mask["backbone"]["stage3_unit5"]["bn1"]["scale"] is False
    assert mask["backbone"]["stage3_unit5"]["bn1"]["bias"] is False
    assert mask["backbone"]["stage3_unit5"]["conv1"]["kernel"] is True
    assert mask["head"]["stage4_unit1"]["bn2"]["scale"] is False
    assert mask["head"]["bn1"]["scale"] is False
    # dense bias is NOT a BN beta
    assert mask["cls_score"]["bias"] is True
    assert mask["cls_score"]["kernel"] is True
    # shared-stage freezing must leave stage4 trainable (ADVICE r1 low)
    shared = frozen_mask(fake_params, cfg.network.fixed_params_shared)
    assert shared["head"]["stage4_unit1"]["bn2"]["scale"] is False  # BN affine
    assert shared["backbone"]["stage3_unit5"]["conv1"]["kernel"] is False


@pytest.mark.slow
def test_overfit_single_batch():
    """~40 SGD steps on one synthetic image must drive the losses down and
    the accuracies up — the smoke signal that gradients flow end-to-end."""
    cfg, model, tx, state = tiny_setup()
    cfg2 = cfg.replace_in("default", e2e_lr=0.02)
    tx2 = make_optimizer(cfg2, state.params, steps_per_epoch=10_000)
    state = init_state(model, KEY, tx2, (1, 128, 128, 3))
    step = jax.jit(make_train_step(model, cfg2, tx2))
    batch = make_batch()
    first = None
    for i in range(40):
        state, metrics = step(state, batch, KEY)
        if first is None:
            first = {k: float(v) for k, v in metrics.items()}
    last = {k: float(v) for k, v in metrics.items()}
    assert last["loss"] < first["loss"] * 0.7, (first, last)
    assert last["rpn_acc"] >= 0.9, (first, last)
    assert last["rcnn_acc"] >= 0.8, (first, last)


def test_lr_schedule_warmup_and_decay():
    """Linear warmup ramps warmup_lr -> base_lr, then step decay applies at
    epoch boundaries counted from global step 0 (ref
    WarmupMultiFactorScheduler semantics)."""
    import numpy as np

    from mx_rcnn_tpu.core.optim import lr_schedule

    sched = lr_schedule(0.01, (2,), steps_per_epoch=100, factor=0.1,
                        warmup_step=50, warmup_lr=0.001)
    np.testing.assert_allclose(float(sched(0)), 0.001)
    np.testing.assert_allclose(float(sched(25)), 0.0055, rtol=1e-6)
    np.testing.assert_allclose(float(sched(50)), 0.01, rtol=1e-6)
    np.testing.assert_allclose(float(sched(199)), 0.01, rtol=1e-6)
    np.testing.assert_allclose(float(sched(200)), 0.001, rtol=1e-6)
    # warmup off: plain step decay
    plain = lr_schedule(0.01, (2,), steps_per_epoch=100, factor=0.1)
    np.testing.assert_allclose(float(plain(0)), 0.01, rtol=1e-6)
    np.testing.assert_allclose(float(plain(200)), 0.001, rtol=1e-6)


def test_bf16_momentum_state_and_training():
    """momentum_dtype='bfloat16' halves the accumulator dtype (checked in
    opt_state) and trains to a loss trajectory close to fp32 momentum —
    same data/RNG, only the accumulator rounds."""
    from mx_rcnn_tpu.core.train import make_train_step, setup_training

    # build cfg/model directly — tiny_setup's state/tx would be discarded
    # and rebuilt per-config inside run()
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=256,
                         rpn_post_nms_top_n=64, batch_rois=32,
                         max_gt_boxes=8, rpn_min_size=2)
    model = build_model(cfg)
    # both arms explicit: the shipped DEFAULT is bfloat16 (adopted from the
    # r5 A/B — docs/PERF.md), so the fp32 arm must be requested
    cfg = cfg.replace_in("default", momentum_dtype="float32")
    cfg16 = cfg.replace_in("default", momentum_dtype="bfloat16")
    batch = make_batch(1, 128, seed=5)

    def run(c):
        state, tx = setup_training(model, c, KEY, (1, 128, 128, 3),
                                   steps_per_epoch=100)
        step = jax.jit(make_train_step(model, c, tx))
        losses = []
        for _ in range(4):
            state, m = step(state, batch, KEY)
            losses.append(float(m["loss"]))
        return state, losses

    s32, l32 = run(cfg)
    s16, l16 = run(cfg16)
    # accumulator dtype is actually bfloat16 (trace momentum leaves)
    momenta16 = [leaf for leaf in jax.tree.leaves(s16.opt_state)
                 if hasattr(leaf, "dtype") and leaf.dtype == jnp.bfloat16]
    assert momenta16, "no bfloat16 accumulator found in opt_state"
    momenta32 = [leaf for leaf in jax.tree.leaves(s32.opt_state)
                 if hasattr(leaf, "dtype") and leaf.dtype == jnp.bfloat16]
    assert not momenta32, "fp32 config grew bfloat16 state"
    # trajectories agree closely (bf16 has ~3 decimal digits)
    for a, b in zip(l32, l16):
        assert abs(a - b) < 0.05 * abs(a) + 0.02, (l32, l16)


def test_dtype_strings_validated():
    """Typos like 'bf16' must raise, not silently fall back to float32."""
    import pytest as _pytest

    from mx_rcnn_tpu.core.optim import make_optimizer
    from mx_rcnn_tpu.core.train import init_variables

    cfg = generate_config("tiny", "PascalVOC")
    params, _ = init_variables(build_model(cfg), KEY, (1, 64, 64, 3))
    bad = cfg.replace_in("default", momentum_dtype="bf16")
    with _pytest.raises(ValueError, match="momentum_dtype"):
        make_optimizer(bad, params, steps_per_epoch=10)
    with _pytest.raises(ValueError, match="compute_dtype"):
        build_model(cfg.replace_in("network", compute_dtype="bfloat"))
