"""ROIAlign / ROIPool tests vs small hand-checkable feature maps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.roi_pool import roi_align, roi_pool


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


# ---------------------------------------------------------------------------
# Pallas fused ROIAlign (ops/roi_align_pallas.py): parity vs the einsum
# oracle in interpreter mode (r5 — removes the HBM inter-matmul
# intermediate measured at 5.84 ms of the 26.44 ms train step).
# ---------------------------------------------------------------------------

def _rand_rois(rng, n, r, h_img, w_img):
    x1 = rng.uniform(0, w_img * 0.7, (n, r))
    y1 = rng.uniform(0, h_img * 0.7, (n, r))
    bw = rng.uniform(8, w_img * 0.4, (n, r))
    bh = rng.uniform(8, h_img * 0.4, (n, r))
    return np.stack([x1, y1, x1 + bw, y1 + bh], axis=-1).astype(np.float32)


def test_roi_align_pallas_forward_matches_einsum():
    from mx_rcnn_tpu.ops.roi_align_pallas import roi_align_pallas
    from mx_rcnn_tpu.ops.roi_pool import roi_align

    rng = np.random.RandomState(0)
    n, h, w, c, r = 2, 19, 32, 64, 12  # r NOT a multiple of RB=8: pad path
    feat = rng.randn(n, h, w, c).astype(np.float32)
    rois = _rand_rois(rng, n, r, h * 16, w * 16)
    want = jax.vmap(lambda f, b: roi_align(f, b, (7, 7), 1 / 16.0))(
        jnp.asarray(feat), jnp.asarray(rois))
    got = roi_align_pallas(jnp.asarray(feat), jnp.asarray(rois), (7, 7),
                           1 / 16.0, 2, True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_roi_align_pallas_grad_matches_einsum():
    """d(pooled)/d(features) must match the einsum path's autodiff — the
    custom VJP re-derives the transposed contractions by hand."""
    from mx_rcnn_tpu.ops.roi_align_pallas import roi_align_pallas
    from mx_rcnn_tpu.ops.roi_pool import roi_align

    rng = np.random.RandomState(1)
    n, h, w, c, r = 2, 10, 16, 32, 8
    feat = jnp.asarray(rng.randn(n, h, w, c).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, n, r, h * 16, w * 16))
    cot = jnp.asarray(rng.randn(n, r, 7, 7, c).astype(np.float32))

    def loss_ein(f):
        p = jax.vmap(lambda fi, b: roi_align(fi, b, (7, 7), 1 / 16.0))(
            f, rois)
        return jnp.sum(p * cot)

    def loss_pal(f):
        p = roi_align_pallas(f, rois, (7, 7), 1 / 16.0, 2, True)
        return jnp.sum(p * cot)

    g_ein = jax.grad(loss_ein)(feat)
    g_pal = jax.grad(loss_pal)(feat)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ein),
                               atol=1e-4, rtol=1e-4)


def test_roi_align_batched_dispatch():
    """backend='jnp' and 'pallas' (interpret via CPU default resolve →
    jnp; explicit pallas exercised above) agree; unknown backend raises."""
    from mx_rcnn_tpu.ops.roi_pool import roi_align_batched

    rng = np.random.RandomState(2)
    feat = jnp.asarray(rng.randn(1, 8, 8, 16).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, 1, 4, 128, 128))
    out = roi_align_batched(feat, rois, (7, 7), 1 / 16.0)
    assert out.shape == (1, 4, 7, 7, 16)
    with pytest.raises(ValueError, match="unknown roi_align backend"):
        roi_align_batched(feat, rois, backend="cuda")


def test_roi_align_pallas_rois_grad_is_explicit_zeros():
    """ADVICE r5: the custom-VJP bwd must return a zeros cotangent for
    rois, not bare None — grads w.r.t. rois then trace cleanly while rois
    stay non-differentiable data (like the reference ROIPooling)."""
    from mx_rcnn_tpu.ops.roi_align_pallas import roi_align_pallas

    rng = np.random.RandomState(3)
    n, h, w, c, r = 1, 8, 8, 16, 4
    feat = jnp.asarray(rng.randn(n, h, w, c).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, n, r, h * 16, w * 16))

    g_feat, g_rois = jax.grad(
        lambda f, b: jnp.sum(roi_align_pallas(f, b, (7, 7), 1 / 16.0, 2,
                                              True)),
        argnums=(0, 1))(feat, rois)
    assert g_rois.shape == rois.shape
    assert g_rois.dtype == rois.dtype
    assert not np.any(np.asarray(g_rois))
    assert np.any(np.asarray(g_feat))


# ---------------------------------------------------------------------------
# Blocked ROIAlign (r6 tentpole, ops/roi_pool.py — roi_align_blocked): the
# einsum pair run lax.map-chunked over ROIs, bit-equal forward (the ROI
# axis is a batch axis of both contractions — chunking it cannot change any
# per-element reduction), custom-VJP backward blocked the same way.
# ---------------------------------------------------------------------------

from mx_rcnn_tpu.ops.roi_pool import roi_align_batched, roi_align_blocked


def test_roi_align_pallas_lowers_for_tpu_at_production_shape():
    """Forward and backward kernels lower for the TPU platform at the
    ResNet-101 training shape — (2, 38, 64, 1024) bf16 features, 128
    ROIs/image, 14x14 pool — to exactly one Mosaic custom call each.
    Lowering runs on CPU; the on-chip compile outcome is recorded in
    ROADMAP.md D3."""
    from mx_rcnn_tpu.ops.roi_align_pallas import roi_align_pallas

    feat = jax.ShapeDtypeStruct((2, 38, 64, 1024), jnp.bfloat16)
    rois = jax.ShapeDtypeStruct((2, 128, 4), jnp.float32)

    def fwd(f, r):
        return roi_align_pallas(f, r, (14, 14), 1 / 16.0, 2, False)

    def bwd(f, r, g):
        return jax.vjp(lambda x: fwd(x, r), f)[1](g)[0]

    cot = jax.ShapeDtypeStruct((2, 128, 14, 14, 1024), jnp.bfloat16)
    for fn, args in ((fwd, (feat, rois)), (bwd, (feat, rois, cot))):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1


def _assert_within_ulps(got, want, ulps=2):
    """fp32 agreement to ``ulps`` units in the last place of the LARGEST
    output magnitude.  The blocked path runs the same two einsums on
    ROI-chunked operands; jaxlib 0.9.0's CPU backend picks its dot
    reduction order per operand shape, so the same products are summed in
    another association (measured: exactly 1 ulp at the output scale,
    2.4e-7 on O(1) values) — bit-equality was a property of jaxlib
    0.4.37's CPU dot, not of the math.  Two ulps still fails any change
    of weights, padding or chunk bookkeeping, which moves values by far
    more."""
    got, want = np.asarray(got), np.asarray(want)
    atol = ulps * np.spacing(np.float32(np.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


@pytest.mark.parametrize("r,chunk", [(13, 4), (8, 8), (5, 64), (1, 4)])
def test_roi_align_blocked_forward_bit_equal_fp32(r, chunk):
    """Odd ROI counts vs chunk size: forward must match the einsum pair
    to the last place (see ``_assert_within_ulps``), including when
    padding rounds R up and when one chunk covers everything."""
    rng = np.random.RandomState(0)
    feat = jnp.asarray(rng.randn(19, 32, 16).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, 1, r, 19 * 16, 32 * 16)[0])
    want = roi_align(feat, rois, (7, 7), 1 / 16.0)
    got = roi_align_blocked(feat, rois, (7, 7), 1 / 16.0, 2, chunk)
    assert got.dtype == want.dtype
    _assert_within_ulps(got, want)


def test_roi_align_blocked_forward_bit_equal_bf16():
    """The bf16 fast path (default precision) is chunked identically."""
    rng = np.random.RandomState(1)
    feat = jnp.asarray(rng.randn(24, 16, 8).astype(np.float32),
                       jnp.bfloat16)
    rois = jnp.asarray(_rand_rois(rng, 1, 11, 24 * 16, 16 * 16)[0])
    want = roi_align(feat, rois, (7, 7), 1 / 16.0)
    got = roi_align_blocked(feat, rois, (7, 7), 1 / 16.0, 2, 4)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(want.astype(jnp.float32)),
        np.asarray(got.astype(jnp.float32)))


def _dyadic_case():
    """Inputs on which every product and partial sum is exactly
    representable (small integers, power-of-two ROI geometry at pooled
    size 4 → dyadic bilinear weights): fp addition is then associative,
    so chunked and monolithic backward reductions must agree BIT-for-bit
    — this pins the contract (same math) independently of XLA's
    reduction-order freedom on general inputs."""
    rng = np.random.RandomState(2)
    feat = rng.randint(-4, 5, (16, 16, 8)).astype(np.float32)
    rois = np.array([[0, 0, 64, 64], [16, 32, 80, 96], [8, 8, 40, 72],
                     [32, 0, 96, 32], [0, 16, 32, 48]], np.float32)
    cot = rng.randint(-2, 3, (5, 4, 4, 8)).astype(np.float32)
    return feat, rois, cot


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_roi_align_blocked_grads_bit_equal_exact_vectors(dtype):
    """Custom-VJP grads vs einsum autodiff, both dtype paths, BIT-equal
    on reduction-order-insensitive vectors (odd chunking: 5 ROIs, chunk
    2 → 3 chunks with padding)."""
    feat_np, rois_np, cot_np = _dyadic_case()
    feat = jnp.asarray(feat_np).astype(
        jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
    rois, cot = jnp.asarray(rois_np), jnp.asarray(cot_np)

    g_ein = jax.grad(lambda f: jnp.sum(
        roi_align(f, rois, (4, 4), 1 / 16.0).astype(jnp.float32)
        * cot))(feat)
    g_blk = jax.grad(lambda f: jnp.sum(
        roi_align_blocked(f, rois, (4, 4), 1 / 16.0, 2,
                          2).astype(jnp.float32) * cot))(feat)
    assert g_blk.dtype == g_ein.dtype
    np.testing.assert_array_equal(
        np.asarray(g_ein.astype(jnp.float32)),
        np.asarray(g_blk.astype(jnp.float32)))


def test_roi_align_blocked_grads_close_random():
    """On general random vectors the chunked backward accumulates the
    same sum in a different association — grads agree to float tolerance
    (measured ~1 ulp of O(1) values), while the FORWARD stays bit-equal
    even here."""
    rng = np.random.RandomState(3)
    feat = jnp.asarray(rng.randn(19, 32, 16).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, 1, 13, 19 * 16, 32 * 16)[0])
    cot = jnp.asarray(rng.randn(13, 7, 7, 16).astype(np.float32))

    g_ein = jax.grad(lambda f: jnp.sum(
        roi_align(f, rois, (7, 7), 1 / 16.0) * cot))(feat)
    g_blk = jax.grad(lambda f: jnp.sum(
        roi_align_blocked(f, rois, (7, 7), 1 / 16.0, 2, 4) * cot))(feat)
    np.testing.assert_allclose(np.asarray(g_blk), np.asarray(g_ein),
                               atol=1e-5, rtol=1e-5)


def test_roi_align_blocked_single_chunk_grads_bit_equal_random():
    """chunk >= R is ONE chunk of the identical einsums — grads agree to
    the last place even on random vectors (no cross-chunk accumulation
    exists; see ``_assert_within_ulps``)."""
    rng = np.random.RandomState(4)
    feat = jnp.asarray(rng.randn(12, 20, 8).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, 1, 7, 12 * 16, 20 * 16)[0])
    cot = jnp.asarray(rng.randn(7, 7, 7, 8).astype(np.float32))
    g_ein = jax.grad(lambda f: jnp.sum(
        roi_align(f, rois, (7, 7), 1 / 16.0) * cot))(feat)
    g_blk = jax.grad(lambda f: jnp.sum(
        roi_align_blocked(f, rois, (7, 7), 1 / 16.0, 2, 64) * cot))(feat)
    _assert_within_ulps(g_blk, g_ein)


def test_roi_align_blocked_rois_grad_is_explicit_zeros():
    """Same contract as the Pallas backend (and the reference ROIPooling):
    rois are non-differentiable data — zeros cotangent, clean trace."""
    rng = np.random.RandomState(5)
    feat = jnp.asarray(rng.randn(8, 8, 16).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, 1, 4, 128, 128)[0])
    g_feat, g_rois = jax.grad(
        lambda f, b: jnp.sum(roi_align_blocked(f, b, (7, 7), 1 / 16.0, 2,
                                               2)),
        argnums=(0, 1))(feat, rois)
    assert g_rois.shape == rois.shape
    assert not np.any(np.asarray(g_rois))
    assert np.any(np.asarray(g_feat))


def test_roi_align_batched_blocked_dispatch():
    """backend='blocked' routes through roi_align_blocked and matches the
    default batched einsum path bit-for-bit."""
    rng = np.random.RandomState(6)
    feat = jnp.asarray(rng.randn(2, 9, 12, 8).astype(np.float32))
    rois = jnp.asarray(_rand_rois(rng, 2, 5, 9 * 16, 12 * 16))
    want = roi_align_batched(feat, rois, (7, 7), 1 / 16.0)
    got = roi_align_batched(feat, rois, (7, 7), 1 / 16.0,
                            backend="blocked", chunk=2)
    assert got.shape == (2, 5, 7, 7, 8)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
