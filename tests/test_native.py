"""Tests for the native C++ host kernels (mx_rcnn_tpu/native).

Covers both backends: every op is checked native-vs-NumPy-fallback (they
must agree exactly) and against independent dense/oracle computations.
Reference parity targets: ``rcnn/cython/cpu_nms.pyx``,
``rcnn/cython/bbox.pyx``, ``rcnn/pycocotools/maskApi.c``.
"""

import numpy as np
import pytest

from mx_rcnn_tpu import native


@pytest.fixture(scope="module")
def has_native():
    return native.ensure_built()


def _numpy_backend(monkeypatch):
    """Force the NumPy fallback paths."""
    monkeypatch.setattr(native, "_load", lambda: None)


def _greedy_nms_oracle(dets, thresh):
    # tie-break matches cpu_nms / ref scores.argsort()[::-1]: higher index first
    order = dets[:, 4].argsort(kind="stable")[::-1]
    keep, live = [], np.ones(len(dets), bool)
    for i in order:
        if not live[i]:
            continue
        keep.append(i)
        for j in order:
            if not live[j] or j == i:
                continue
            xx1 = max(dets[i, 0], dets[j, 0])
            yy1 = max(dets[i, 1], dets[j, 1])
            xx2 = min(dets[i, 2], dets[j, 2])
            yy2 = min(dets[i, 3], dets[j, 3])
            w, h = max(0.0, xx2 - xx1 + 1), max(0.0, yy2 - yy1 + 1)
            inter = w * h
            a = lambda d: (d[2] - d[0] + 1) * (d[3] - d[1] + 1)
            if inter / (a(dets[i]) + a(dets[j]) - inter) > thresh:
                live[j] = False
    return np.asarray(keep, np.int64)


def _rand_dets(rng, n):
    xy = rng.uniform(0, 80, (n, 2)).astype(np.float32)
    wh = rng.uniform(5, 40, (n, 2)).astype(np.float32)
    scores = rng.uniform(size=(n, 1)).astype(np.float32)
    return np.hstack([xy, xy + wh, scores])


def test_cpu_nms_matches_oracle(has_native):
    rng = np.random.RandomState(0)
    for n in (1, 7, 50, 300):
        dets = _rand_dets(rng, n)
        keep = native.cpu_nms(dets, 0.3)
        np.testing.assert_array_equal(keep, _greedy_nms_oracle(dets, 0.3))


def test_cpu_nms_backends_agree(has_native, monkeypatch):
    if not has_native:
        pytest.skip("native lib unavailable")
    rng = np.random.RandomState(1)
    dets = _rand_dets(rng, 200)
    got_native = native.cpu_nms(dets, 0.5)
    _numpy_backend(monkeypatch)
    got_numpy = native.cpu_nms(dets, 0.5)
    np.testing.assert_array_equal(got_native, got_numpy)


def test_cpu_nms_empty():
    assert native.cpu_nms(np.zeros((0, 5), np.float32), 0.3).size == 0


def test_cpu_nms_tie_break_matches_reference(has_native, monkeypatch):
    """Among equal scores the reference's ``scores.argsort()[::-1]`` visits
    the HIGHER original index first (ADVICE r2).  Two disjoint boxes with
    identical scores: both kept, higher index reported first."""
    dets = np.array([[0, 0, 10, 10, 0.5],
                     [100, 100, 110, 110, 0.5]], np.float32)
    np.testing.assert_array_equal(native.cpu_nms(dets, 0.3), [1, 0])
    if has_native:
        _numpy_backend(monkeypatch)
        np.testing.assert_array_equal(native.cpu_nms(dets, 0.3), [1, 0])


def test_bbox_overlaps_against_jnp(has_native):
    from mx_rcnn_tpu.ops.boxes import bbox_overlaps as jnp_overlaps

    rng = np.random.RandomState(2)
    a = _rand_dets(rng, 40)[:, :4]
    b = _rand_dets(rng, 17)[:, :4]
    got = native.bbox_overlaps(a, b)
    want = np.asarray(jnp_overlaps(a, b))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_bbox_overlaps_backends_agree(has_native, monkeypatch):
    if not has_native:
        pytest.skip("native lib unavailable")
    rng = np.random.RandomState(3)
    a = _rand_dets(rng, 25)[:, :4]
    b = _rand_dets(rng, 31)[:, :4]
    got_native = native.bbox_overlaps(a, b)
    _numpy_backend(monkeypatch)
    np.testing.assert_allclose(got_native, native.bbox_overlaps(a, b),
                               atol=1e-6)


# ---- RLE --------------------------------------------------------------------


def _rand_mask(rng, h, w):
    # blobby mask: a few rectangles
    m = np.zeros((h, w), np.uint8)
    for _ in range(rng.randint(1, 4)):
        y, x = rng.randint(0, h), rng.randint(0, w)
        m[y:y + rng.randint(1, h + 1), x:x + rng.randint(1, w + 1)] = 1
    return m


def test_rle_roundtrip_and_area(has_native):
    rng = np.random.RandomState(4)
    for h, w in ((1, 1), (5, 7), (33, 21), (64, 64)):
        m = _rand_mask(rng, h, w)
        rle = native.encode(m)
        assert rle["size"] == [h, w]
        np.testing.assert_array_equal(native.decode(rle), m)
        assert native.area(rle) == int(m.sum())


def test_rle_golden_string():
    """Hand-verified COCO-format compressed counts (5-bit chunks + 48
    offset, delta-coded from index 3): a 3x3 block in a 5x7 canvas."""
    m = np.zeros((5, 7), np.uint8)
    m[1:4, 2:5] = 1
    rle = native.encode(m)
    # col-major counts: [11, 3, 2, 3, 2, 3, 11]
    assert rle["counts"] == b";320009"
    np.testing.assert_array_equal(native.decode(rle), m)


def test_rle_backends_agree(has_native, monkeypatch):
    if not has_native:
        pytest.skip("native lib unavailable")
    rng = np.random.RandomState(5)
    m1, m2 = _rand_mask(rng, 40, 30), _rand_mask(rng, 40, 30)
    r1n, r2n = native.encode(m1), native.encode(m2)
    iou_n = native.iou(r1n, r2n)
    merge_n = native.decode(native.merge([r1n, r2n]))
    bb_n = native.to_bbox(r1n)
    _numpy_backend(monkeypatch)
    r1p, r2p = native.encode(m1), native.encode(m2)
    assert r1n["counts"] == r1p["counts"]
    assert abs(iou_n - native.iou(r1p, r2p)) < 1e-12
    np.testing.assert_array_equal(merge_n,
                                  native.decode(native.merge([r1p, r2p])))
    np.testing.assert_array_equal(bb_n, native.to_bbox(r1p))


def test_rle_iou_dense_check(has_native):
    rng = np.random.RandomState(6)
    m1, m2 = _rand_mask(rng, 25, 25), _rand_mask(rng, 25, 25)
    r1, r2 = native.encode(m1), native.encode(m2)
    inter = np.logical_and(m1, m2).sum()
    union = np.logical_or(m1, m2).sum()
    assert abs(native.iou(r1, r2) - inter / union) < 1e-12
    # crowd semantics: denominator is the dt area
    assert abs(native.iou(r1, r2, iscrowd=True) - inter / m1.sum()) < 1e-12


def test_rle_merge_union_and_intersection(has_native):
    rng = np.random.RandomState(7)
    m1, m2 = _rand_mask(rng, 18, 22), _rand_mask(rng, 18, 22)
    r1, r2 = native.encode(m1), native.encode(m2)
    np.testing.assert_array_equal(
        native.decode(native.merge([r1, r2])), np.logical_or(m1, m2))
    np.testing.assert_array_equal(
        native.decode(native.merge([r1, r2], intersect=True)),
        np.logical_and(m1, m2))


def test_rle_to_bbox(has_native):
    m = np.zeros((10, 12), np.uint8)
    m[3:8, 4:9] = 1
    np.testing.assert_array_equal(native.to_bbox(native.encode(m)),
                                  [4, 3, 5, 5])
    # empty mask
    np.testing.assert_array_equal(
        native.to_bbox(native.encode(np.zeros((4, 4), np.uint8))),
        [0, 0, 0, 0])


def test_rle_from_bbox_and_poly(has_native):
    # integer-aligned box: exact pixel coverage
    rle = native.from_bbox([2, 1, 3, 4], 8, 10)
    m = native.decode(rle)
    want = np.zeros((8, 10), np.uint8)
    want[1:5, 2:5] = 1
    np.testing.assert_array_equal(m, want)
    # triangle: area approximately half the bounding square
    tri = native.from_poly([0, 0, 0, 20, 20, 20], 20, 20)
    a = native.area(tri)
    assert abs(a - 200) < 25


def test_rle_string_codec_large_counts(has_native):
    """Counts that need multiple 5-bit chunks (and negative deltas)."""
    m = np.zeros((100, 90), np.uint8)
    m[50:, :] = 1
    m[0, 0] = 1
    rle = native.encode(m)
    np.testing.assert_array_equal(native.decode(rle), m)
    assert native.area(rle) == int(m.sum())


@pytest.mark.parametrize("backend", ["native", "numpy"])
def test_iou_matrix_matches_pairwise(backend, monkeypatch):
    """Batched rle_iou_matrix == pairwise iou, incl. crowd columns, on
    random masks; empty-side cases return empty matrices.  Runs on both
    the native and the NumPy-fallback backend."""
    from mx_rcnn_tpu import native

    rng = np.random.RandomState(3)
    h = w = 40

    def rand_rle():
        m = np.zeros((h, w), np.uint8)
        x1, y1 = rng.randint(0, 25, 2)
        m[y1:y1 + rng.randint(5, 15), x1:x1 + rng.randint(5, 15)] = 1
        return native.encode(m)

    dts = [rand_rle() for _ in range(5)]
    gts = [rand_rle() for _ in range(4)]
    crowd = np.array([False, True, False, True])
    want = np.array([[native.iou(d, g, bool(c))
                      for g, c in zip(gts, crowd)] for d in dts])
    if backend == "numpy":
        _numpy_backend(monkeypatch)
    got = native.iou_matrix(dts, gts, crowd)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)
    assert native.iou_matrix([], gts).shape == (0, 4)
    assert native.iou_matrix(dts, []).shape == (5, 0)
    with pytest.raises(ValueError, match="crowd flags"):
        native.iou_matrix(dts, gts, [True])


def test_build_trusts_content_digest_not_file_times(tmp_path, monkeypatch,
                                                    has_native):
    """A library whose stamp does not match these sources (copied in from
    another tree, sources edited since) is rebuilt even when its mtime is
    newest; one built from exactly these sources is kept."""
    if not has_native:
        pytest.skip("no C++ toolchain")
    import os

    lib = tmp_path / "libmxrcnn_native.so"
    stamp = tmp_path / "libmxrcnn_native.so.srchash"
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(native, "_STAMP_PATH", str(stamp))

    # a foreign library, newer than every source, no stamp
    lib.write_bytes(b"not an ELF file")
    assert native.build()
    assert lib.read_bytes()[:4] == b"\x7fELF"
    # same sources: kept, even when a copy left the library OLDER than
    # its sources (the mtime rule would rebuild; a rebuild resets mtime)
    os.utime(lib, ns=(1, 1))
    assert native.build()
    assert os.stat(lib).st_mtime_ns == 1
    # stamp of other sources: rebuilt
    stamp.write_text("0" * 64 + "\n")
    lib.write_bytes(b"stale")
    assert native.build()
    assert lib.read_bytes()[:4] == b"\x7fELF"
