"""Anchor generation golden tests.

The canonical anchor values for base_size=16, ratios (0.5,1,2), scales
(8,16,32) are fixed in the py-faster-rcnn lineage the reference inherits
(ref ``rcnn/processing/generate_anchor.py — generate_anchors``).
"""

import numpy as np

from mx_rcnn_tpu.ops.anchors import generate_anchors, generate_shifted_anchors

# The canonical 9-anchor table (documented in py-faster-rcnn's
# generate_anchors.py docstring; reproduced by the reference).
GOLDEN = np.array(
    [
        [-84., -40., 99., 55.],
        [-176., -88., 191., 103.],
        [-360., -184., 375., 199.],
        [-56., -56., 71., 71.],
        [-120., -120., 135., 135.],
        [-248., -248., 263., 263.],
        [-36., -80., 51., 95.],
        [-80., -168., 95., 183.],
        [-168., -344., 183., 359.],
    ],
    dtype=np.float32,
)


def test_generate_anchors_golden():
    got = generate_anchors(16, (0.5, 1.0, 2.0), (8, 16, 32))
    np.testing.assert_allclose(got, GOLDEN)


def test_shifted_anchor_layout():
    a = generate_shifted_anchors(2, 3, feat_stride=16)
    assert a.shape == (2 * 3 * 9, 4)
    # index (y, x, k) = (y*W + x)*A + k; shifting one cell right adds 16 to x
    np.testing.assert_allclose(a[9] - a[0], [16, 0, 16, 0])
    # one cell down adds 16 to y
    np.testing.assert_allclose(a[3 * 9] - a[0], [0, 16, 0, 16])
    # anchor 0 at cell (0,0) is the golden base anchor
    np.testing.assert_allclose(a[0], GOLDEN[0])


def test_shifted_anchor_count_stride8():
    a = generate_shifted_anchors(4, 4, feat_stride=8, scales=(4,))
    assert a.shape == (4 * 4 * 3, 4)


def test_sublane_bucket_640x1024_regenerates_valid_anchors():
    """r6 bucket experiment: switching the bucket to 640x1024 (40x64
    stride-16 grid — 40 is a whole number of 8-row sublanes, unlike the
    default 38) must regenerate anchors automatically and validly
    through the config override path."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.image import choose_bucket

    cfg = generate_config(
        "resnet101", "coco",
        bucket__shapes=[[640, 1024], [1024, 640]])
    assert cfg.bucket.shapes == ((640, 1024), (1024, 640))
    for h, w in cfg.bucket.shapes:
        assert h % 32 == 0 and w % 32 == 0  # feature grid stays aligned
        fh, fw = h // 16, w // 16
        a = generate_shifted_anchors(fh, fw, 16)
        assert a.shape == (fh * fw * 9, 4)
        assert np.isfinite(a).all()
        # grid covers the full bucket: last cell's base anchor sits at
        # ((fw-1)*16, (fh-1)*16) offset from the golden base anchor
        np.testing.assert_allclose(
            a[-9] - GOLDEN[0],
            [(fw - 1) * 16, (fh - 1) * 16, (fw - 1) * 16, (fh - 1) * 16])
    assert 640 // 16 == 40 and 40 % 8 == 0  # the sublane-friendly point
    # a landscape VOC-scale image routes into the landscape bucket
    assert choose_bucket(600, 1000, cfg.bucket.shapes) == (640, 1024)
