"""Native host-side kernels: C++ NMS/IoU and COCO RLE mask ops.

Reference: ``rcnn/cython/`` (bbox.pyx, cpu_nms.pyx, gpu_nms.pyx) and the C
core of the vendored ``rcnn/pycocotools`` (maskApi.c), built by the
reference's top-level ``Makefile``.  Here the same split exists:

* the DEVICE hot path (proposal NMS inside the train step) is XLA/jnp —
  ``mx_rcnn_tpu/ops/nms.py`` — there is no CUDA to port;
* the HOST path (per-class NMS in eval postprocessing, RLE mask algebra for
  COCO annotations) is this C++ library, loaded via ctypes.

The library builds on demand with ``g++ -O3`` (``ensure_built()``, also
``make native`` at the repo root); every entry point has a NumPy fallback
so a machine without a toolchain still runs — just slower.  Use
``backend()`` to see which is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Dict, Optional, Sequence

import numpy as np

logger = logging.getLogger("mx_rcnn_tpu")

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_PATH = os.path.join(os.path.dirname(__file__), "libmxrcnn_native.so")
_SOURCES = ("nms.cc", "maskapi.cc")
_CXX = ("g++", "-O3", "-shared", "-fPIC", "-std=c++17")
# digest of what the library beside it was built from; a library without a
# matching stamp (copied in from another tree or machine, sources edited
# since) is rebuilt, never loaded on trust
_STAMP_PATH = _LIB_PATH + ".srchash"

_lib: Optional[ctypes.CDLL] = None
_tried = False
_lock = threading.Lock()


def _build_digest(srcs: Sequence[str]) -> str:
    """sha256 over the compile command, the machine and the source bytes."""
    h = hashlib.sha256(" ".join(_CXX + (platform.machine(),)).encode())
    for path in srcs:
        with open(path, "rb") as f:
            h.update(os.path.basename(path).encode() + b"\0" + f.read())
    return h.hexdigest()


def _stamped_digest() -> Optional[str]:
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def build(force: bool = False) -> bool:
    """Compile the shared library unless the one on disk was built from
    exactly these sources (content digest in ``_STAMP_PATH`` — file times
    say nothing once a tree has been copied).  Returns True on success."""
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    digest = _build_digest(srcs)
    if (not force and os.path.exists(_LIB_PATH)
            and _stamped_digest() == digest):
        return True
    try:
        subprocess.run(list(_CXX) + ["-o", _LIB_PATH] + srcs, check=True,
                       capture_output=True, text=True)
        # stamp last: an interrupted build leaves the old (mismatching)
        # stamp and is redone
        with open(_STAMP_PATH, "w") as f:
            f.write(digest + "\n")
        return True
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        logger.warning("native build failed (%s); using NumPy fallbacks",
                       detail.strip().splitlines()[-1] if detail else e)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not build():
            return None
        lib = ctypes.CDLL(_LIB_PATH)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        f32p = ctypes.POINTER(ctypes.c_float)
        f64p = ctypes.POINTER(ctypes.c_double)
        i64 = ctypes.c_int64
        lib.bbox_overlaps.argtypes = [f32p, i64, f32p, i64, f32p]
        lib.bbox_overlaps.restype = None
        lib.cpu_nms.argtypes = [f32p, i64, ctypes.c_float,
                                ctypes.POINTER(i64)]
        lib.cpu_nms.restype = i64
        lib.rle_encode.argtypes = [ctypes.POINTER(ctypes.c_uint8), i64, i64,
                                   u32p]
        lib.rle_encode.restype = i64
        lib.rle_decode.argtypes = [u32p, i64, i64, i64,
                                   ctypes.POINTER(ctypes.c_uint8)]
        lib.rle_decode.restype = ctypes.c_int
        lib.rle_area.argtypes = [u32p, i64]
        lib.rle_area.restype = i64
        lib.rle_to_bbox.argtypes = [u32p, i64, i64, i64, f64p]
        lib.rle_to_bbox.restype = None
        lib.rle_iou.argtypes = [u32p, i64, u32p, i64, ctypes.c_int]
        lib.rle_iou.restype = ctypes.c_double
        i64p = ctypes.POINTER(i64)
        lib.rle_iou_matrix.argtypes = [
            u32p, i64p, i64p, i64, u32p, i64p, i64p, i64,
            ctypes.POINTER(ctypes.c_uint8), f64p]
        lib.rle_iou_matrix.restype = None
        lib.rle_merge.argtypes = [u32p, i64, u32p, i64, ctypes.c_int, u32p]
        lib.rle_merge.restype = i64
        lib.rle_to_string.argtypes = [u32p, i64, ctypes.c_char_p]
        lib.rle_to_string.restype = i64
        lib.rle_from_string.argtypes = [ctypes.c_char_p, i64, u32p]
        lib.rle_from_string.restype = i64
        lib.rle_from_poly.argtypes = [f64p, i64, i64, i64, u32p]
        lib.rle_from_poly.restype = i64
        lib.rle_from_bbox.argtypes = [f64p, i64, i64, u32p]
        lib.rle_from_bbox.restype = i64
        _lib = lib
        return _lib


def ensure_built() -> bool:
    """Build+load eagerly; True if the native backend is active."""
    return _load() is not None


def backend() -> str:
    return "native" if _load() is not None else "numpy"


def _f32(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float32)


def _cptr(a: np.ndarray, typ):
    return a.ctypes.data_as(ctypes.POINTER(typ))


# ---- box kernels (ref rcnn/cython) -----------------------------------------


def bbox_overlaps(boxes: np.ndarray, query: np.ndarray) -> np.ndarray:
    """(n,4) x (k,4) → (n,k) IoU matrix, +1-pixel areas
    (ref ``bbox_overlaps_cython``)."""
    boxes, query = _f32(boxes).reshape(-1, 4), _f32(query).reshape(-1, 4)
    n, k = len(boxes), len(query)
    lib = _load()
    if lib is not None:
        out = np.empty((n, k), np.float32)
        lib.bbox_overlaps(_cptr(boxes, ctypes.c_float), n,
                          _cptr(query, ctypes.c_float), k,
                          _cptr(out, ctypes.c_float))
        return out
    # NumPy fallback
    bw = boxes[:, 2] - boxes[:, 0] + 1
    bh = boxes[:, 3] - boxes[:, 1] + 1
    qw = query[:, 2] - query[:, 0] + 1
    qh = query[:, 3] - query[:, 1] + 1
    iw = np.clip(
        np.minimum(boxes[:, None, 2], query[None, :, 2])
        - np.maximum(boxes[:, None, 0], query[None, :, 0]) + 1, 0, None)
    ih = np.clip(
        np.minimum(boxes[:, None, 3], query[None, :, 3])
        - np.maximum(boxes[:, None, 1], query[None, :, 1]) + 1, 0, None)
    inter = iw * ih
    union = (bw * bh)[:, None] + (qw * qh)[None, :] - inter
    return np.where(inter > 0, inter / np.maximum(union, 1e-12), 0.0
                    ).astype(np.float32)


def cpu_nms(dets: np.ndarray, thresh: float) -> np.ndarray:
    """Greedy NMS over (n,5) [x1 y1 x2 y2 score]; returns kept indices in
    descending-score order (ref ``cpu_nms.pyx``).

    Tie-break matches the reference's ``scores.argsort()[::-1]``: among
    equal scores the HIGHER original index is visited first (deterministic
    here via a stable sort; the reference's introsort leaves ties
    platform-defined).  Note the in-graph NMS (``ops/nms.py``) breaks ties
    lower-index-first, so tied detections may differ across backends.
    """
    dets = _f32(dets).reshape(-1, 5)
    order = dets[:, 4].argsort(kind="stable")[::-1]
    sorted_dets = np.ascontiguousarray(dets[order])
    n = len(sorted_dets)
    if n == 0:
        return np.zeros((0,), np.int64)
    lib = _load()
    if lib is not None:
        keep = np.empty((n,), np.int64)
        cnt = lib.cpu_nms(_cptr(sorted_dets, ctypes.c_float), n,
                          ctypes.c_float(thresh),
                          _cptr(keep, ctypes.c_int64))
        return order[keep[:cnt]]
    # NumPy fallback: suppress against kept boxes
    keep = []
    suppressed = np.zeros(n, bool)
    boxes = sorted_dets[:, :4]
    areas = (boxes[:, 2] - boxes[:, 0] + 1) * (boxes[:, 3] - boxes[:, 1] + 1)
    for i in range(n):
        if suppressed[i]:
            continue
        keep.append(i)
        rest = np.arange(i + 1, n)
        rest = rest[~suppressed[i + 1:]]
        if len(rest) == 0:
            continue
        xx1 = np.maximum(boxes[i, 0], boxes[rest, 0])
        yy1 = np.maximum(boxes[i, 1], boxes[rest, 1])
        xx2 = np.minimum(boxes[i, 2], boxes[rest, 2])
        yy2 = np.minimum(boxes[i, 3], boxes[rest, 3])
        inter = (np.clip(xx2 - xx1 + 1, 0, None)
                 * np.clip(yy2 - yy1 + 1, 0, None))
        iou = inter / (areas[i] + areas[rest] - inter)
        suppressed[rest[iou > thresh]] = True
    return order[np.asarray(keep, np.int64)]


# ---- RLE mask ops (ref rcnn/pycocotools/maskApi.c) -------------------------
# RLE dicts use the pycocotools wire format: {"size": [h, w],
# "counts": bytes} (compressed) — interchangeable with COCO result files.


def _counts_of(rle: Dict) -> np.ndarray:
    c = rle["counts"]
    if isinstance(c, (bytes, str)):
        return _string_to_counts(c if isinstance(c, bytes) else c.encode())
    return np.ascontiguousarray(c, dtype=np.uint32)


def _string_to_counts(s: bytes) -> np.ndarray:
    lib = _load()
    if lib is not None:
        out = np.empty((max(len(s), 1),), np.uint32)
        m = lib.rle_from_string(s, len(s), _cptr(out, ctypes.c_uint32))
        if m < 0:
            raise ValueError("malformed RLE string")
        return out[:m].copy()
    counts, x, k, i = [], 0, 0, 0
    for ch in s:
        c = ch - 48
        x |= (c & 0x1F) << (5 * k)
        k += 1
        if not (c & 0x20):
            if c & 0x10:
                x -= 1 << (5 * k)
            if len(counts) > 2:
                x += counts[-2]
            counts.append(x)
            x, k = 0, 0
    return np.asarray(counts, np.uint32)


def _counts_to_string(counts: np.ndarray) -> bytes:
    counts = np.ascontiguousarray(counts, np.uint32)
    lib = _load()
    if lib is not None:
        buf = ctypes.create_string_buffer(len(counts) * 8 + 1)
        n = lib.rle_to_string(_cptr(counts, ctypes.c_uint32), len(counts),
                              buf)
        return buf.raw[:n]
    out = bytearray()
    lst = [int(v) for v in counts]
    for i, v in enumerate(lst):
        x = v - (lst[i - 2] if i > 2 else 0)
        more = True
        while more:
            c = x & 0x1F
            x >>= 5
            more = (x != -1) if (c & 0x10) else (x != 0)
            if more:
                c |= 0x20
            out.append(c + 48)
    return bytes(out)


def encode(mask: np.ndarray) -> Dict:
    """Binary (h, w) mask → RLE dict (compressed counts)."""
    mask = np.asarray(mask)
    if mask.ndim != 2:
        raise ValueError(f"mask must be (h, w), got {mask.shape}")
    h, w = mask.shape
    flat = np.ascontiguousarray(mask.astype(np.uint8).T.reshape(-1))
    return _encode_colmajor(flat, h, w)


def _encode_colmajor(flat: np.ndarray, h: int, w: int) -> Dict:
    lib = _load()
    if lib is not None:
        out = np.empty((h * w + 1,), np.uint32)
        m = lib.rle_encode(_cptr(flat, ctypes.c_uint8), h, w,
                           _cptr(out, ctypes.c_uint32))
        counts = out[:m].copy()
    else:
        v = flat.astype(bool)
        change = np.flatnonzero(np.diff(v.astype(np.int8))) + 1
        edges = np.concatenate([[0], change, [len(v)]])
        counts = np.diff(edges).astype(np.uint32)
        if v.size and v[0]:
            counts = np.concatenate([[np.uint32(0)], counts])
    return {"size": [h, w], "counts": _counts_to_string(counts)}


def decode(rle: Dict) -> np.ndarray:
    """RLE dict → binary (h, w) uint8 mask."""
    h, w = rle["size"]
    counts = _counts_of(rle)
    lib = _load()
    if lib is not None:
        out = np.empty((h * w,), np.uint8)
        rc = lib.rle_decode(_cptr(counts, ctypes.c_uint32), len(counts),
                            h, w, _cptr(out, ctypes.c_uint8))
        if rc != 0:
            raise ValueError("RLE counts do not cover the canvas")
    else:
        if counts.sum() != h * w:
            raise ValueError("RLE counts do not cover the canvas")
        vals = np.arange(len(counts)) % 2
        out = np.repeat(vals.astype(np.uint8), counts)
    return out.reshape(w, h).T


def area(rle: Dict) -> int:
    counts = _counts_of(rle)
    lib = _load()
    if lib is not None:
        return int(lib.rle_area(_cptr(counts, ctypes.c_uint32), len(counts)))
    return int(counts[1::2].sum())


def to_bbox(rle: Dict) -> np.ndarray:
    """RLE → (x, y, w, h) COCO bbox."""
    h, w = rle["size"]
    counts = _counts_of(rle)
    lib = _load()
    if lib is not None:
        bb = np.empty((4,), np.float64)
        lib.rle_to_bbox(_cptr(counts, ctypes.c_uint32), len(counts), h, w,
                        _cptr(bb, ctypes.c_double))
        return bb
    m = decode(rle)
    ys, xs = np.nonzero(m)
    if len(xs) == 0:
        return np.zeros((4,), np.float64)
    return np.array([xs.min(), ys.min(), xs.max() - xs.min() + 1,
                     ys.max() - ys.min() + 1], np.float64)


def iou(dt: Dict, gt: Dict, iscrowd: bool = False) -> float:
    """Mask IoU; crowd gt uses dt area as denominator (COCO semantics)."""
    cd, cg = _counts_of(dt), _counts_of(gt)
    lib = _load()
    if lib is not None:
        return float(lib.rle_iou(_cptr(cd, ctypes.c_uint32), len(cd),
                                 _cptr(cg, ctypes.c_uint32), len(cg),
                                 int(iscrowd)))
    md, mg = decode(dt).astype(bool), decode(gt).astype(bool)
    inter = np.logical_and(md, mg).sum()
    denom = md.sum() if iscrowd else np.logical_or(md, mg).sum()
    return float(inter / denom) if denom else 0.0


def iou_matrix(dts: Sequence[Dict], gts: Sequence[Dict],
               iscrowd: Sequence[bool] = None) -> np.ndarray:
    """Full (len(dts), len(gts)) mask-IoU matrix in ONE native call (the
    batched form of pycocotools ``rleIou``); per-mask areas are computed
    once instead of once per pair.  Falls back to pairwise :func:`iou`."""
    nd, ng = len(dts), len(gts)
    # ascontiguousarray: a non-contiguous uint8 view would hand its BASE
    # buffer pointer to C and silently read the wrong crowd flags
    crowd = np.zeros(ng, np.uint8) if iscrowd is None else \
        np.ascontiguousarray(iscrowd, np.uint8)
    if len(crowd) != ng:
        raise ValueError(f"{len(crowd)} crowd flags for {ng} gts")
    out = np.zeros((nd, ng), np.float64)
    if nd == 0 or ng == 0:
        return out
    lib = _load()
    if lib is None:
        for d in range(nd):
            for g in range(ng):
                out[d, g] = iou(dts[d], gts[g], bool(crowd[g]))
        return out

    def pack(rles):
        counts = [_counts_of(r) for r in rles]
        lens = np.array([len(c) for c in counts], np.int64)
        offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
        return np.concatenate(counts).astype(np.uint32), offs, lens

    cd, do, dl = pack(dts)
    cg, go, gl = pack(gts)
    lib.rle_iou_matrix(
        _cptr(cd, ctypes.c_uint32), _cptr(do, ctypes.c_int64),
        _cptr(dl, ctypes.c_int64), nd,
        _cptr(cg, ctypes.c_uint32), _cptr(go, ctypes.c_int64),
        _cptr(gl, ctypes.c_int64), ng,
        _cptr(crowd, ctypes.c_uint8), _cptr(out, ctypes.c_double))
    return out


def merge(rles: Sequence[Dict], intersect: bool = False) -> Dict:
    """Union (default) or intersection of RLEs on one canvas."""
    if not rles:
        raise ValueError("merge of zero masks")
    h, w = rles[0]["size"]
    acc = _counts_of(rles[0])
    lib = _load()
    for r in rles[1:]:
        c = _counts_of(r)
        if lib is not None:
            out = np.empty((h * w + 1,), np.uint32)
            m = lib.rle_merge(_cptr(acc, ctypes.c_uint32), len(acc),
                              _cptr(c, ctypes.c_uint32), len(c),
                              int(intersect), _cptr(out, ctypes.c_uint32))
            acc = out[:m].copy()
        else:
            a = np.repeat(np.arange(len(acc)) % 2, acc).astype(bool)
            b = np.repeat(np.arange(len(c)) % 2, c).astype(bool)
            v = (a & b) if intersect else (a | b)
            change = np.flatnonzero(np.diff(v.astype(np.int8))) + 1
            edges = np.concatenate([[0], change, [len(v)]])
            acc = np.diff(edges).astype(np.uint32)
            if v.size and v[0]:
                acc = np.concatenate([[np.uint32(0)], acc])
    return {"size": [h, w], "counts": _counts_to_string(acc)}


def from_poly(xy: Sequence[float], h: int, w: int) -> Dict:
    """Flat polygon [x0,y0,x1,y1,...] → RLE via even-odd pixel-center fill.

    NOTE: the reference maskApi rasterizes a 5x-upsampled boundary, which
    includes boundary pixels slightly more aggressively (measured: a <=1-px
    boundary band, worst-case IoU 0.93 vs an independent rasterizer on
    25-55 px star polygons — tests/test_coco_eval.py); differences are
    confined to the 1-px boundary ring.
    """
    xy = np.ascontiguousarray(xy, np.float64).reshape(-1)
    k = len(xy) // 2
    lib = _load()
    if lib is not None:
        out = np.empty((h * w + 1,), np.uint32)
        m = lib.rle_from_poly(_cptr(xy, ctypes.c_double), k, h, w,
                              _cptr(out, ctypes.c_uint32))
        return {"size": [h, w], "counts": _counts_to_string(out[:m].copy())}
    pts = xy.reshape(-1, 2)
    mask = np.zeros((h, w), np.uint8)
    cx = np.arange(w) + 0.5
    for col in range(w):
        ys = []
        for i in range(k):
            x1, y1 = pts[i]
            x2, y2 = pts[(i + 1) % k]
            if (x1 <= cx[col] < x2) or (x2 <= cx[col] < x1):
                t = (cx[col] - x1) / (x2 - x1)
                ys.append(y1 + t * (y2 - y1))
        ys.sort()
        for j in range(0, len(ys) - 1, 2):
            r0 = int(np.ceil(ys[j] - 0.5))
            r1 = int(np.floor(ys[j + 1] - 0.5))
            mask[max(r0, 0):min(r1, h - 1) + 1, col] = 1
    return _encode_colmajor(
        np.ascontiguousarray(mask.T.reshape(-1)), h, w)


def from_uncompressed(size: Sequence[int], counts: Sequence[int]) -> Dict:
    """COCO *uncompressed* RLE (counts as an int list, the crowd-annotation
    json form) → compressed RLE dict (ref ``pycocotools — frUncompressedRLE``)."""
    return {"size": list(size),
            "counts": _counts_to_string(np.asarray(counts, np.uint32))}


def from_bbox(bb: Sequence[float], h: int, w: int) -> Dict:
    """COCO (x, y, w, h) box → RLE."""
    x, y, bw, bh = (float(v) for v in bb)
    return from_poly([x, y, x, y + bh, x + bw, y + bh, x + bw, y], h, w)
