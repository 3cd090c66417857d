"""ROIAlign / ROIPool tests vs small hand-checkable feature maps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.roi_pool import (roi_align, roi_align_batched,
                                      roi_pool)


def ramp_feature(h, w, c=1):
    """feature[y, x, 0] = y * w + x — linear in both axes."""
    return jnp.arange(h * w, dtype=jnp.float32).reshape(h, w, 1).repeat(c, axis=2)


def test_roi_align_constant_map():
    feat = jnp.ones((16, 16, 3))
    rois = jnp.array([[0.0, 0.0, 63.0, 63.0]])  # image coords, stride 4
    out = roi_align(feat, rois, (7, 7), spatial_scale=0.25)
    assert out.shape == (1, 7, 7, 3)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)


def test_roi_align_linear_map_is_exact():
    # bilinear sampling of a linear function reproduces it exactly at bin centers
    h = w = 32
    feat = ramp_feature(h, w)
    # roi covering feature region [4, 20] x [8, 24] at stride 1
    rois = jnp.array([[8.0, 4.0, 24.0, 20.0]])
    ph = pw = 4
    out = np.asarray(roi_align(feat, rois, (ph, pw), spatial_scale=1.0))[0, :, :, 0]
    bin_h = 16.0 / ph
    bin_w = 16.0 / pw
    for p in range(ph):
        for q in range(pw):
            cy = 4.0 + (p + 0.5) * bin_h - 0.5
            cx = 8.0 + (q + 0.5) * bin_w - 0.5
            want = cy * w + cx
            np.testing.assert_allclose(out[p, q], want, rtol=1e-5)


def test_roi_align_batched_rois_shapes():
    feat = jnp.ones((38, 64, 8))
    rois = jnp.tile(jnp.array([[0.0, 0.0, 100.0, 100.0]]), (5, 1))
    out = roi_align(feat, rois, (14, 14), 1.0 / 16)
    assert out.shape == (5, 14, 14, 8)


def test_roi_pool_max_semantics():
    feat = jnp.zeros((8, 8, 1)).at[2, 3, 0].set(7.0).at[6, 6, 0].set(5.0)
    rois = jnp.array([[0.0, 0.0, 7.0, 7.0]])  # whole map, stride 1
    out = np.asarray(roi_pool(feat, rois, (2, 2), 1.0))[0, :, :, 0]
    # quadrant maxes: TL contains (2,3)->7; BR contains (6,6)->5
    assert out[0, 0] == 7.0
    assert out[1, 1] == 5.0
    assert out[0, 1] == 0.0 and out[1, 0] == 0.0


def test_roi_pool_single_cell_roi():
    feat = ramp_feature(8, 8)
    rois = jnp.array([[3.0, 2.0, 3.0, 2.0]])  # one pixel at (y=2, x=3)
    out = np.asarray(roi_pool(feat, rois, (2, 2), 1.0))[0]
    # all bins cover the same single pixel (value 2*8+3=19)
    np.testing.assert_allclose(out[..., 0], 19.0)


def test_roi_align_bf16_passthrough():
    feat = jnp.ones((16, 16, 4), dtype=jnp.bfloat16)
    rois = jnp.array([[0.0, 0.0, 32.0, 32.0]])
    out = roi_align(feat, rois, (7, 7), 0.25)
    assert out.dtype == jnp.bfloat16


def test_roi_align_bf16_close_to_fp32():
    """The bf16 fast path (default precision, folded-mean matrices) must
    track the fp32 'highest' path within bf16 quantization error."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    feat = rng.randn(24, 32, 16).astype(np.float32)
    rois = np.array([[10.0, 8.0, 200.0, 150.0],
                     [0.0, 0.0, 511.0, 383.0],
                     [33.3, 21.7, 95.2, 64.9]], np.float32)
    out32 = np.asarray(roi_align(jnp.asarray(feat), rois, (7, 7), 1 / 16.0))
    out16 = np.asarray(roi_align(jnp.asarray(feat, jnp.bfloat16), rois,
                                 (7, 7), 1 / 16.0)).astype(np.float32)
    # bf16 has ~2-3 significant decimal digits; interpolated activations are
    # O(1), so 3% absolute tolerance is ~4x the expected rounding noise
    np.testing.assert_allclose(out16, out32, atol=3e-2)


def _rand_rois(rng, n, r, h_img, w_img):
    x1 = rng.uniform(0, w_img * 0.7, (n, r))
    y1 = rng.uniform(0, h_img * 0.7, (n, r))
    bw = rng.uniform(8, w_img * 0.4, (n, r))
    bh = rng.uniform(8, h_img * 0.4, (n, r))
    return np.stack([x1, y1, x1 + bw, y1 + bh], axis=-1).astype(np.float32)


def _numpy_roi_align(feat, rois, ph, pw, scale, sr, cot):
    """Plain float64 ROIAlign, one bilinear sample at a time: the pooled
    features and, for the cotangent ``cot``, the features' gradient."""
    h, w, _ = feat.shape
    out = np.zeros((len(rois), ph, pw, feat.shape[2]))
    d_feat = np.zeros(feat.shape)

    def taps(start, bin_size, k, size):
        pos = np.clip(start + (k + 0.5) * bin_size / sr - 0.5, 0, size - 1)
        lo = int(np.floor(pos))
        return ((lo, 1.0 - (pos - lo)), (min(lo + 1, size - 1), pos - lo))

    for r, (x1, y1, x2, y2) in enumerate(rois.astype(np.float64) * scale):
        bin_h = max(y2 - y1, 1.0) / ph
        bin_w = max(x2 - x1, 1.0) / pw
        for p in range(ph):
            for q in range(pw):
                for ky in range(sr):
                    for kx in range(sr):
                        for y, wy in taps(y1, bin_h, p * sr + ky, h):
                            for x, wx in taps(x1, bin_w, q * sr + kx, w):
                                wt = wy * wx / (sr * sr)
                                out[r, p, q] += wt * feat[y, x]
                                d_feat[y, x] += wt * cot[r, p, q]
    return out, d_feat


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("r,pooled", [(13, 7), (8, 14), (1, 7), (5, 14)])
def test_roi_align_feature_gradient_matches_numpy_reference(r, pooled, dtype):
    """The pooled features and their transpose (the features' gradient for
    a random cotangent) against a float64 reference that samples point by
    point.  float32 features take exact arithmetic; bfloat16 ones round
    the weights, the intermediate and the result to 8 bits of mantissa."""
    rng = np.random.RandomState(r * 100 + pooled)
    h, w, c = 9, 12, 4
    feat = rng.randn(h, w, c).astype(np.float32)
    rois = _rand_rois(rng, 1, r, h * 16, w * 16)[0]
    cot = rng.randn(r, pooled, pooled, c).astype(np.float32)
    # what the op sees after the cast is what the reference is given
    feat_d = jnp.asarray(feat, dtype)
    cot_d = jnp.asarray(cot, dtype)
    want, d_want = _numpy_roi_align(
        np.asarray(feat_d.astype(jnp.float32), np.float64), rois, pooled,
        pooled, 1 / 16.0, 2,
        np.asarray(cot_d.astype(jnp.float32), np.float64))

    got, vjp = jax.vjp(
        lambda f: roi_align(f, jnp.asarray(rois), (pooled, pooled),
                            1 / 16.0, 2), feat_d)
    (d_got,) = vjp(cot_d)
    assert got.dtype == d_got.dtype == feat_d.dtype
    tol = 1e-5 if dtype == "float32" else 2e-2
    for g, wnt in ((got, want), (d_got, d_want)):
        np.testing.assert_allclose(
            np.asarray(g.astype(jnp.float32)), wnt, rtol=0,
            atol=tol * np.abs(wnt).max())


@pytest.mark.parametrize("n", [1, 2, 4])
def test_roi_align_batched_bit_equal_per_image(n):
    """``roi_align_batched`` is ``roi_align`` image by image, to the bit."""
    rng = np.random.RandomState(2 + n)
    feat = jnp.asarray(rng.randn(n, 8, 8, 16).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, n, 4, 128, 128))
    out = roi_align_batched(feat, rois, (7, 7), 1 / 16.0)
    assert out.shape == (n, 4, 7, 7, 16)
    for i in range(n):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            np.asarray(roi_align(feat[i], rois[i], (7, 7), 1 / 16.0)))


def test_test_forward_pools_through_roi_align_batched(monkeypatch):
    """The test forward's pooled features are ``roi_align_batched``'s on
    the same ROIs, to the bit: train and test pool through one function."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.train import init_variables
    from mx_rcnn_tpu.models import build_model, faster_rcnn

    model = build_model(generate_config("tiny", "synthetic"))
    params, stats = init_variables(model, jax.random.PRNGKey(0),
                                   (2, 64, 96, 3))
    variables = {"params": params, "batch_stats": stats}
    rng = np.random.RandomState(9)
    feat = model.apply(
        variables, jnp.asarray(rng.randn(2, 64, 96, 3).astype(np.float32)),
        method=model.features)
    rois = jnp.asarray(_rand_rois(rng, 2, 6, 64, 96))
    seen = []

    def recording(*args, **kw):
        seen.append(roi_align_batched(*args, **kw))
        return seen[-1]

    monkeypatch.setattr(faster_rcnn, "roi_align_batched", recording)
    logits, deltas, r = model.apply(variables, feat, rois,
                                    method=model._pool_and_classify)
    assert r == 6 and len(seen) == 1
    want = roi_align_batched(feat, rois, model.pooled_size,
                             1.0 / model.feat_stride)
    np.testing.assert_array_equal(np.asarray(seen[0]), np.asarray(want))
    head = model.apply(variables, want.reshape((-1,) + want.shape[2:]),
                       False, method=model.roi_head)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(head[0]))
    np.testing.assert_array_equal(np.asarray(deltas), np.asarray(head[1]))


@pytest.mark.parametrize("pooled,channels", [(14, 1024), (7, 512)])
def test_roi_align_batched_lowers_for_tpu_at_cell_shapes(pooled, channels):
    """The pooled-feature path and its transpose lower for the TPU
    platform at both detector cells' shapes — 16 images x 128 ROIs on a
    38x64 bfloat16 map, 14x14x1024 (r101-coco.train) and 7x7x512
    (vgg16-voc07.train) — as plain XLA: no custom call, which is what a
    future kernel has to stay inside.  Lowering runs on CPU."""
    feat = jax.ShapeDtypeStruct((16, 38, 64, channels), jnp.bfloat16)
    rois = jax.ShapeDtypeStruct((16, 128, 4), jnp.float32)
    cot = jax.ShapeDtypeStruct((16, 128, pooled, pooled, channels),
                               jnp.bfloat16)

    def fwd(f, r):
        return roi_align_batched(f, r, (pooled, pooled), 1 / 16.0)

    def bwd(f, r, g):
        return jax.vjp(lambda x: fwd(x, r), f)[1](g)[0]

    for fn, args, shape in ((fwd, (feat, rois), cot.shape),
                            (bwd, (feat, rois, cot), feat.shape)):
        lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
        assert "custom_call" not in lowered.as_text()
        assert lowered.out_info.shape == shape
        assert lowered.out_info.dtype == jnp.bfloat16


@pytest.mark.parametrize("fn,name", [
    ("roi_align_batched", "backend"), ("roi_align_batched", "chunk"),
    ("propose_batch", "batched_nms")])
def test_removed_parameters_are_refused(fn, name):
    """The backends' selectors are gone, not ignored: passing one is an
    error that names it."""
    from mx_rcnn_tpu import ops

    with pytest.raises(TypeError, match=name):
        getattr(ops, fn)(None, None, None, None, **{name: None})
