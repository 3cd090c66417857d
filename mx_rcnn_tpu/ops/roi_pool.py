"""ROI feature extraction: ROIAlign (primary) and ROIPooling (parity op).

Reference: ``mx.symbol.ROIPooling`` — a C++/CUDA MXNet op used by the
symbols (``rcnn/symbol/symbol_vgg.py`` ROIPooling 7x7 /16,
``symbol_resnet.py`` 14x14 /16).

TPU-native design: instead of translating the CUDA gather kernel, ROIAlign
is reformulated as **two small dense matmuls per ROI** — a (S_h, H)
row-interpolation matrix and a (S_w, W) column-interpolation matrix applied
around the (H, W, C) feature map:

    sampled[s, t, c] = W_y[s, h] · feat[h, w, c] · W_x[t, w]

Expressing the op as dense matmuls routes it onto the MXU systolic array
and lets XLA batch it over ROIs — far better than 4-point gathers, which
scatter into HBM-latency-bound loads.  The ROIAlign mean over the sr×sr
sample points per bin (standard semantics, aligned=True convention) is
folded into the interpolation matrices, so each matrix row carries the
averaged bilinear weights of a whole output bin: the contractions and the
(R, ·, ·, C) intermediate shrink by sr× each, and the op is
HBM-bandwidth-, not FLOP-, bound on TPU.

``roi_pool`` reproduces the reference's quantized max-pool semantics
(rounded ROI corners, ceil/floor bin edges, empty bins → 0) for numerical
parity runs; models default to ROIAlign which is uniformly better on mAP.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def _interp_matrix(starts: jnp.ndarray, bin_sizes: jnp.ndarray, num_bins: int,
                   sampling_ratio: int, size: int) -> jnp.ndarray:
    """Pooled bilinear sampling matrix (num_bins, size) for one axis.

    starts/bin_sizes: scalars (per-ROI, one axis).  Sample positions use the
    aligned=True convention: integer coordinate i is the center of pixel i.

    The ROIAlign mean over the ``sampling_ratio`` sample points per bin is
    folded INTO the matrix (mean of bilinear samples = matmul with averaged
    weights; the mean over an (sr, sr) sample grid factorizes exactly into
    per-axis means) — this shrinks both matmul contractions and the
    intermediate tensor by sr× each versus materializing every sample.
    """
    s = num_bins * sampling_ratio
    k = jnp.arange(s, dtype=jnp.float32)
    # position of each sample point in continuous pixel-center coordinates
    pos = starts + (k + 0.5) * (bin_sizes / sampling_ratio) - 0.5
    pos = jnp.clip(pos, 0.0, size - 1.0)
    lo = jnp.floor(pos)
    frac = pos - lo
    lo_i = lo.astype(jnp.int32)
    hi_i = jnp.minimum(lo_i + 1, size - 1)
    m = jax.nn.one_hot(lo_i, size, dtype=jnp.float32) * (1.0 - frac)[:, None]
    m = m + jax.nn.one_hot(hi_i, size, dtype=jnp.float32) * frac[:, None]
    return m.reshape(num_bins, sampling_ratio, size).mean(axis=1)


def interp_matrices(rois: jnp.ndarray, ph: int, pw: int, h: int, w: int,
                    spatial_scale: float, sampling_ratio: int):
    """Per-ROI (wy (R, ph, H), wx (R, pw, W)) fp32 interpolation matrices
    — the ONE place the ROI corner scaling / min-size clamp / bilinear
    weights are defined."""
    x1 = rois[:, 0].astype(jnp.float32) * spatial_scale
    y1 = rois[:, 1].astype(jnp.float32) * spatial_scale
    x2 = rois[:, 2].astype(jnp.float32) * spatial_scale
    y2 = rois[:, 3].astype(jnp.float32) * spatial_scale
    roi_w = jnp.maximum(x2 - x1, 1.0)
    roi_h = jnp.maximum(y2 - y1, 1.0)
    wy = jax.vmap(lambda s, b: _interp_matrix(s, b, ph, sampling_ratio, h))(
        y1, roi_h / ph)
    wx = jax.vmap(lambda s, b: _interp_matrix(s, b, pw, sampling_ratio, w))(
        x1, roi_w / pw)
    return wy, wx


@functools.partial(
    jax.jit, static_argnames=("output_size", "spatial_scale", "sampling_ratio")
)
def roi_align(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: Tuple[int, int] = (14, 14),
    spatial_scale: float = 1.0 / 16.0,
    sampling_ratio: int = 2,
) -> jnp.ndarray:
    """ROIAlign over a single image's feature map.

    Args:
      features: (H, W, C) NHWC feature map.  fp32 features use exact fp32
        ('highest') arithmetic; bf16 features use native MXU bf16 passes
        with the inter-matmul intermediate also in bf16 (see test
        ``test_roi_align_bf16_close_to_fp32`` for the accuracy envelope).
      rois: (R, 4) boxes in input-image coordinates (x1, y1, x2, y2).
      output_size: (pooled_h, pooled_w).
      spatial_scale: 1 / feature stride (ref ROIPooling spatial_scale=1/16).
      sampling_ratio: bilinear sample points per bin edge.

    Returns:
      (R, pooled_h, pooled_w, C) pooled features, in ``features.dtype``.
    """
    ph, pw = output_size
    h, w, _ = features.shape
    dtype = features.dtype

    wy, wx = interp_matrices(rois, ph, pw, h, w, spatial_scale,
                             sampling_ratio)

    # Two batched matmuls on the MXU.  Compute stays in the feature dtype:
    # in bf16 the weight rounding costs <0.3% of a pixel's bilinear frac —
    # far below the feature quantization already present — while fp32
    # features get fp32 ('highest') arithmetic so the op is exact for
    # parity/eval runs.  The cheaper contraction runs first to minimize the
    # (R, ·, ·, C) intermediate that HBM bandwidth pays for.
    prec = "highest" if dtype == jnp.float32 else "default"
    wy = wy.astype(dtype)
    wx = wx.astype(dtype)
    if ph * w <= h * pw:
        rows = jnp.einsum("rsh,hwc->rswc", wy, features, precision=prec)
        pooled = jnp.einsum("rswc,rtw->rstc", rows, wx, precision=prec)
    else:
        cols = jnp.einsum("hwc,rtw->rhtc", features, wx, precision=prec)
        pooled = jnp.einsum("rhtc,rsh->rstc", cols, wy, precision=prec)
    return pooled.astype(dtype)


def roi_align_batched(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: Tuple[int, int] = (14, 14),
    spatial_scale: float = 1.0 / 16.0,
    sampling_ratio: int = 2,
) -> jnp.ndarray:
    """:func:`roi_align` over a batch of images: features (N, H, W, C),
    rois (N, R, 4) → (N, R, ph, pw, C).  The one pooled-feature path of
    the train step and the test forward.

    What a future kernel must beat: it has to stay inside the XLA program.
    A custom call that fused the einsum pair in VMEM won the forward alone
    and lost the step, because the opaque boundary forced layout copies of
    the pooled tensor and its cotangent and blocked fusion across the op
    (docs/PERF.md "ROIAlign backends").
    """
    return jax.vmap(
        lambda f, r: roi_align(f, r, output_size, spatial_scale,
                               sampling_ratio)
    )(features, rois)


@functools.partial(jax.jit, static_argnames=("output_size", "spatial_scale"))
def roi_pool(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: Tuple[int, int] = (7, 7),
    spatial_scale: float = 1.0 / 16.0,
) -> jnp.ndarray:
    """Reference-parity quantized max ROI pooling.

    Matches ``mx.symbol.ROIPooling`` semantics: ROI corners rounded at
    feature scale, width/height floored at 1, bin edges
    ``floor(p·rh/ph)``/``ceil((p+1)·rh/ph)``, max over each (possibly
    overlapping) bin, empty bins → 0.
    """
    ph, pw = output_size
    h, w, _ = features.shape
    dtype = features.dtype
    feat32 = features.astype(jnp.float32)
    neg = jnp.float32(-3.4e38)

    def one_roi(roi):
        # floor(x + 0.5), not jnp.round: C round() rounds half away from
        # zero while jnp.round rounds half to even, and ROI corners landing
        # on half-integer feature coords are common (multiples of 8 px).
        def rnd(v):
            return jnp.floor(v * spatial_scale + 0.5).astype(jnp.int32)

        x1, y1, x2, y2 = rnd(roi[0]), rnd(roi[1]), rnd(roi[2]), rnd(roi[3])
        rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
        rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)

        p = jnp.arange(ph, dtype=jnp.float32)
        hstart = jnp.clip(jnp.floor(p * rh / ph).astype(jnp.int32) + y1, 0, h)
        hend = jnp.clip(jnp.ceil((p + 1) * rh / ph).astype(jnp.int32) + y1, 0, h)
        q = jnp.arange(pw, dtype=jnp.float32)
        wstart = jnp.clip(jnp.floor(q * rw / pw).astype(jnp.int32) + x1, 0, w)
        wend = jnp.clip(jnp.ceil((q + 1) * rw / pw).astype(jnp.int32) + x1, 0, w)

        hidx = jnp.arange(h)
        widx = jnp.arange(w)

        def col_bin(q_i):
            mask = (widx >= wstart[q_i]) & (widx < wend[q_i])  # (W,)
            return jnp.max(jnp.where(mask[None, :, None], feat32, neg), axis=1)

        tmp = jax.vmap(col_bin)(jnp.arange(pw))  # (pw, H, C)

        def row_bin(p_i):
            mask = (hidx >= hstart[p_i]) & (hidx < hend[p_i])  # (H,)
            return jnp.max(jnp.where(mask[None, :, None], tmp, neg), axis=1)

        out = jax.vmap(row_bin)(jnp.arange(ph))  # (ph, pw, C)
        return jnp.where(out <= neg / 2, 0.0, out)

    return jax.vmap(one_roi)(rois.astype(jnp.float32)).astype(dtype)
