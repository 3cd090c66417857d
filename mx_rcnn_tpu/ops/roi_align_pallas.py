"""Pallas TPU kernel for fused two-stage ROIAlign (batched, custom VJP).

Reference: ``mx.symbol.ROIPooling`` (CUDA gather kernel) — already
redesigned as two separable interpolation matmuls in ``ops/roi_pool.py``.
This kernel is the VMEM-fused version of those matmuls.

Why: the XLA einsum pair is FLOP-efficient (it batches all ROIs into one
big matmul) but materializes the inter-matmul intermediate in HBM —
(R, ·, ·, C) ≈ 280 MB in bf16 at the production shape (256 rois,
38x64x1024 feature map) — written and read back every step, in forward
AND backward.  Measured on chip (r5 stage table, N=16 chains): 5.84 ms of
a 26.44 ms train step for ~18 GFLOP of useful work (~2% MFU; a pure HBM
wall).  Fusing the two contractions in VMEM removes the intermediate
entirely: HBM traffic drops to the feature map, the tiny interpolation
matrices, and the pooled output.

**Measured outcome (r5, v5e), and why this is NOT the default**: isolated
the kernel wins the forward (3.8 vs 4.1 ms) and loses fwd+bwd by ~2 ms
(12.1 vs 10.1) against the einsum pair (after the design iterations
recorded in the kernel docstrings: per-(roi, s) tiny dots, per-roi
transposes, and a VMEM spill each cost 2x before the final shape).  Inside the FULL train step the
einsum pair still wins by ~13 ms (25.0 vs 38.6 ms): the opaque
custom-call boundary forces layout copies of the ~100 MB pooled and
cotangent tensors and blocks XLA fusion across the op — costs invisible
at op scope that dwarf the intermediate being saved.  Retained behind
``cfg.train.roi_align_backend='pallas'`` with parity + grad tests as
measured groundwork; revisit if the boundary tax shrinks (custom-call
layout negotiation) or R*C grows past the copy cost.

Design (forward):
* inputs are the PRE-BUILT per-ROI interpolation matrices ``wy`` / ``wx``
  (built in jnp — tiny one_hot machinery XLA handles fine; the SAME
  ``_interp_matrix`` as the einsum path, so the two backends share
  bilinear weights bit-for-bit) plus the feature maps ``(N, H, W, C)``,
* grid = (N, C/Cb, R/RB): ROI blocks innermost, so the feature block
  stays VMEM-resident across each image's whole ROI sweep,
* stage 1 is ONE MXU matmul per grid step — (RB*ph, H) @ (H, W*Cb) — the
  ROI-batched shape XLA itself uses, keeping MXU row occupancy high,
* stage 2 contracts W per ROI (ph unrolled (pw, W) @ (W, Cb) dots) out of
  the fp32 VMEM scratch; small matmuls, but only ~6.6 GFLOP total and
  entirely VMEM-resident.

The batch dimension is part of the GRID, not vmap: the backward kernel's
accumulator logic depends on ``program_id`` of the ROI axis, and a vmap
batching rule would silently renumber the axes.  The TRAIN path calls
this under ``shard_map`` (local dense arrays), where an opaque kernel
shards trivially; the GSPMD eval path keeps the einsum backend, which
XLA can partition (an opaque pallas_call would force a gather).

Backward (custom VJP; ROIs are non-differentiable data, exactly like the
reference ROIPooling which propagates no gradient to rois):
  dFeat = sum_r wy[r]^T @ (g[r] contracted with wx[r] over t)
* same grid with an (H, W*Cb) fp32 VMEM accumulator: zeroed at ROI block
  0, accumulated across ROI blocks (the wy^T contraction is again one
  ROI-batched MXU matmul per step), flushed on the last — dFeat hits HBM
  exactly once per (image, channel block).

VMEM at the production shape (RB=8, Cb=256): feature block 1.2 MB (bf16)
+ stage scratch (fp32) 7.3 MB + accumulator/out blocks ~2 MB + interp
blocks <0.2 MB ≈ 11 MB < 16 MB/core.  ``_pick_blocks`` shrinks Cb (or
keeps small C whole) for the tiny/VGG heads, which then trivially fit.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.roi_pool import interp_matrices


# raise the default 16 MiB scoped-VMEM cap: v5e has far more physical
# VMEM, and the backward's value chain (g block, its transpose, RB fat-dot
# results, da2, the fp32 accumulator) measured a 2x slowdown when Mosaic
# spilled it under the default cap.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _pick_blocks(r: int, c: int) -> Tuple[int, int]:
    """(RB, Cb) block sizes: R is padded to a multiple of RB by the
    wrapper; Cb must divide C, falling back to full C for small heads."""
    rb = 8 if r >= 8 else max(r, 1)
    cb = 256 if c % 256 == 0 else c
    return rb, cb


def _fwd_kernel(wy_ref, feat_ref, wx_ref, out_ref, *,
                rb: int, ph: int, pw: int, h: int, w: int, cb: int):
    """One grid step = one image x one channel block x RB rois.

    wy_ref: (1, RB*ph, H); feat_ref: (1, H, W, Cb); wx_ref: (1, RB, pw, W);
    out_ref: (1, RB, ph, pw, Cb).

    Shape discipline learned by measurement (all on the r5 chip):
    per-(roi, s) tiny dots ≈ 28k sequential MXU ops per step (35.4 ms
    full step vs einsum's 25.5); per-roi transposes pay Mosaic's high
    fixed transpose cost RB times (39.1 ms).  This version does exactly
    TWO whole-block transposes per grid step and RB fat dots, everything
    as VMEM values (no scratch round-trips).
    """
    feat2d = feat_ref[0].reshape(h, w * cb)
    # stage 1: every ROI's row interpolation in ONE MXU matmul
    a = jnp.dot(wy_ref[0], feat2d,
                preferred_element_type=jnp.float32)  # (RB*ph, W*Cb)
    cdt = wx_ref.dtype
    # s-w axis swap between the contractions, once for the whole block
    at = jnp.swapaxes(a.reshape(rb * ph, w, cb), 0,
                      1).reshape(w, rb * ph * cb).astype(cdt)
    outs = [
        jnp.dot(wx_ref[0, r], at[:, r * ph * cb:(r + 1) * ph * cb],
                preferred_element_type=jnp.float32)  # (pw, ph*Cb)
        for r in range(rb)
    ]
    o = jnp.concatenate(outs, axis=0).reshape(rb, pw, ph, cb)
    # back-swap the whole block's output in the second transpose
    out_ref[0] = jnp.swapaxes(o, 1, 2).astype(out_ref.dtype)


def _bwd_kernel(wy_ref, wx_ref, g_ref, dfeat_ref, acc_ref, *,
                rb: int, ph: int, pw: int, h: int, w: int, cb: int):
    """dFeat for one (image, channel block), accumulated over ROI blocks.

    wy_ref: (1, RB*ph, H); wx_ref: (1, RB, pw, W); g_ref: (1, RB, ph, pw,
    Cb); dfeat_ref: (1, H, W, Cb); acc_ref: fp32 (H, W*Cb).

    Mirrors _fwd_kernel's shape discipline: two whole-block transposes,
    RB fat dots, one ROI-batched accumulate matmul — all values, the only
    stateful buffer is the fp32 accumulator (zeroed at ROI block 0,
    flushed to HBM once per (image, channel block)).
    """
    ri = pl.program_id(2)

    @pl.when(ri == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cdt = wx_ref.dtype
    # block transpose 1: g (RB, ph, pw, Cb) -> (pw, RB*ph*Cb)
    gt = jnp.transpose(g_ref[0], (2, 0, 1, 3)).reshape(pw, rb * ph * cb)
    # stage 2 transposed, one fat dot per ROI:
    # da[(w), (s c)] = sum_t wx[r, t, w] g[r, s, t, c]
    das = [
        jnp.dot(wx_ref[0, r].T, gt[:, r * ph * cb:(r + 1) * ph * cb],
                preferred_element_type=jnp.float32).astype(cdt)  # (W, ph*Cb)
        for r in range(rb)
    ]
    # block transpose 2: collect to ((r s), (w c)) for the batched matmul
    da2 = jnp.transpose(
        jnp.concatenate(das, axis=1).reshape(w, rb, ph, cb),
        (1, 2, 0, 3)).reshape(rb * ph, w * cb)
    # stage 1 transposed, ROI-batched: acc += wy^T (H, RB*ph) @ da2
    acc_ref[:] += jnp.dot(wy_ref[0].T, da2,
                          preferred_element_type=jnp.float32)

    @pl.when(ri == pl.num_programs(2) - 1)
    def _flush():
        dfeat_ref[0] = acc_ref[:].reshape(h, w, cb).astype(dfeat_ref.dtype)


def _build_interp(rois: jnp.ndarray, ph: int, pw: int, h: int, w: int,
                  spatial_scale: float, sampling_ratio: int, dtype):
    """Per-ROI (wy, wx) for ONE image — the einsum path's own
    ``interp_matrices``, so backends agree bit-for-bit on weights."""
    wy, wx = interp_matrices(rois, ph, pw, h, w, spatial_scale,
                             sampling_ratio)
    return wy.astype(dtype), wx.astype(dtype)


def _specs(n, r_pad, ph, pw, h, w, c, rb, cb):
    grid = (n, c // cb, r_pad // rb)
    wy_spec = pl.BlockSpec((1, rb * ph, h),
                           lambda bi, ci, ri: (bi, ri, 0),
                           memory_space=pltpu.VMEM)
    wx_spec = pl.BlockSpec((1, rb, pw, w), lambda bi, ci, ri: (bi, ri, 0, 0),
                           memory_space=pltpu.VMEM)
    feat_spec = pl.BlockSpec((1, h, w, cb), lambda bi, ci, ri: (bi, 0, 0, ci),
                             memory_space=pltpu.VMEM)
    pooled_spec = pl.BlockSpec((1, rb, ph, pw, cb),
                               lambda bi, ci, ri: (bi, ri, 0, 0, ci),
                               memory_space=pltpu.VMEM)
    return grid, wy_spec, wx_spec, feat_spec, pooled_spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def roi_align_pallas(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: Tuple[int, int] = (14, 14),
    spatial_scale: float = 1.0 / 16.0,
    sampling_ratio: int = 2,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused-VMEM ROIAlign over a batch.

    Args match ``ops.roi_pool.roi_align`` but BATCHED:
      features: (N, H, W, C); rois: (N, R, 4) in input coordinates.
    Returns (N, R, ph, pw, C) pooled features in ``features.dtype``.
    ``interpret=True`` runs the kernels in the Pallas interpreter so CPU
    tests can pin parity against the einsum oracle.
    """
    out, _ = _roi_align_fwd(features, rois, output_size, spatial_scale,
                            sampling_ratio, interpret)
    return out


def _roi_align_fwd(features, rois, output_size, spatial_scale,
                   sampling_ratio, interpret):
    ph, pw = output_size
    n, h, w, c = features.shape
    r = rois.shape[1]
    rb, cb = _pick_blocks(r, c)
    pad = (-r) % rb
    wy, wx = jax.vmap(
        lambda rs: _build_interp(rs, ph, pw, h, w, spatial_scale,
                                 sampling_ratio, features.dtype))(rois)
    if pad:
        wy = jnp.concatenate(
            [wy, jnp.zeros((n, pad) + wy.shape[2:], wy.dtype)], axis=1)
        wx = jnp.concatenate(
            [wx, jnp.zeros((n, pad) + wx.shape[2:], wx.dtype)], axis=1)
    r_pad = r + pad
    grid, wy_spec, wx_spec, feat_spec, pooled_spec = _specs(
        n, r_pad, ph, pw, h, w, c, rb, cb)
    kern = functools.partial(_fwd_kernel, rb=rb, ph=ph, pw=pw, h=h, w=w,
                             cb=cb)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[wy_spec, feat_spec, wx_spec],
        out_specs=pooled_spec,
        out_shape=jax.ShapeDtypeStruct((n, r_pad, ph, pw, c),
                                       features.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(wy.reshape(n, r_pad * ph, h), features, wx)
    # rois ride the residuals only to shape the zero cotangent in bwd
    return out[:, :r], (wy, wx, rois, h, w, c)


def _roi_align_bwd(output_size, spatial_scale, sampling_ratio, interpret,
                   res, g):
    wy, wx, rois, h, w, c = res
    ph, pw = output_size
    n, r_pad = wy.shape[0], wy.shape[1]
    rb, cb = _pick_blocks(r_pad, c)
    pad = r_pad - g.shape[1]
    if pad:
        g = jnp.concatenate(
            [g, jnp.zeros((n, pad) + g.shape[2:], g.dtype)], axis=1)
    grid, wy_spec, wx_spec, feat_spec, pooled_spec = _specs(
        n, r_pad, ph, pw, h, w, c, rb, cb)
    kern = functools.partial(_bwd_kernel, rb=rb, ph=ph, pw=pw, h=h,
                             w=w, cb=cb)
    dfeat = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[wy_spec, wx_spec, pooled_spec],
        out_specs=feat_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, w, c), g.dtype),
        scratch_shapes=[pltpu.VMEM((h, w * cb), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(wy.reshape(n, r_pad * ph, h), wx, g)
    # no gradient to rois: proposal boxes are data (ref ROIPooling
    # likewise propagates only to the feature map) — but the cotangent is
    # an explicit zeros array, not bare None: None-as-zero worked by
    # accident of the pytree check and fails opaquely at trace time the
    # moment anything differentiates w.r.t. rois (ADVICE r5)
    return dfeat, jnp.zeros_like(rois)


roi_align_pallas.defvjp(_roi_align_fwd, _roi_align_bwd)
