"""Synthetic detection dataset: colored rectangles on textured backgrounds.

No reference equivalent — the reference assumes VOC/COCO downloads.  This
dataset generates deterministic images on first use (cached as PNGs under
``root_path``) so the whole pipeline — loader, training, eval, demo — runs
end-to-end on a machine with no datasets.  Class k draws rectangles with a
class-specific color, so the task is learnable to high mAP and serves as an
integration-level correctness check of the entire framework.
"""

from __future__ import annotations

import os
import zlib
from typing import Dict, List

import numpy as np

from mx_rcnn_tpu.data.roidb import IMDB, Roidb
from mx_rcnn_tpu.data.voc_eval import voc_eval


def _class_color(c: int) -> np.ndarray:
    rng = np.random.RandomState(1234 + c)
    return rng.randint(40, 255, size=3).astype(np.uint8)


class SyntheticDataset(IMDB):
    def __init__(self, image_set: str, root_path: str, dataset_path: str,
                 num_images: int = None, num_classes: int = 4,
                 image_size=(320, 400), max_objects: int = 3):
        if num_images is None:
            num_images = 64 if "train" in image_set else 16
        super().__init__("synthetic", image_set, root_path,
                         dataset_path or os.path.join(root_path, "synthetic"))
        self.classes = ["__background__"] + [
            f"class{i}" for i in range(1, num_classes)]
        self.num_images = num_images
        self.image_size = image_size
        self.max_objects = max_objects
        # stable across processes (str hash() is PYTHONHASHSEED-randomized,
        # which would regenerate different images each run and desync any
        # cached PNGs from the in-memory ground truth)
        seed = zlib.crc32(image_set.encode()) % (2 ** 31)
        self._rng = np.random.RandomState(seed)
        self.image_dir = os.path.join(self.data_path, self.image_set)
        self._specs = self._make_specs()
        self.image_index = list(range(num_images))

    @staticmethod
    def _iou(a, b) -> float:
        ix = max(0.0, min(a[2], b[2]) - max(a[0], b[0]) + 1)
        iy = max(0.0, min(a[3], b[3]) - max(a[1], b[1]) + 1)
        inter = ix * iy
        area = lambda r: (r[2] - r[0] + 1) * (r[3] - r[1] + 1)
        return inter / (area(a) + area(b) - inter)

    def _make_specs(self) -> List[Dict]:
        h, w = self.image_size
        specs = []
        for i in range(self.num_images):
            n = self._rng.randint(1, self.max_objects + 1)
            boxes, classes = [], []
            for _ in range(n):
                # rejection-sample low-overlap placements: heavily overlapping
                # solid rectangles occlude each other (later draws overwrite
                # earlier pixels), which would make gt boxes unlearnable and
                # the eval mAP ceiling ill-defined
                for _attempt in range(20):
                    # object sizes scale with the canvas so tiny images work
                    bw = self._rng.randint(max(16, w // 5), max(17, w // 2))
                    bh = self._rng.randint(max(16, h // 5), max(17, h // 2))
                    x1 = self._rng.randint(0, w - bw)
                    y1 = self._rng.randint(0, h - bh)
                    cand = [x1, y1, x1 + bw - 1, y1 + bh - 1]
                    if all(self._iou(cand, b) < 0.2 for b in boxes):
                        boxes.append(cand)
                        classes.append(self._rng.randint(1, self.num_classes))
                        break
            specs.append(dict(
                boxes=np.asarray(boxes, np.float32),
                gt_classes=np.asarray(classes, np.int32),
                noise_seed=int(self._rng.randint(0, 2 ** 31)),
            ))
        return specs

    def _render(self, spec: Dict) -> np.ndarray:
        h, w = self.image_size
        rng = np.random.RandomState(spec["noise_seed"])
        img = rng.randint(0, 60, size=(h, w, 3)).astype(np.uint8)
        for box, cls in zip(spec["boxes"], spec["gt_classes"]):
            x1, y1, x2, y2 = box.astype(int)
            img[y1:y2 + 1, x1:x2 + 1] = _class_color(int(cls))
        return img

    def image_path(self, i: int) -> str:
        return os.path.join(self.image_dir, f"{self.image_set}_{i:05d}.png")

    def _spec_signature(self) -> str:
        """Content hash of the generation parameters + all gt.  The PNG cache
        is only valid for exactly this dataset; reusing stale pixels against
        fresh in-memory gt would silently break the learnable-color
        invariant the dataset exists for."""
        h = zlib.crc32(repr((self.num_images, self.num_classes,
                             self.image_size, self.max_objects)).encode())
        for spec in self._specs:
            h = zlib.crc32(spec["boxes"].tobytes(), h)
            h = zlib.crc32(spec["gt_classes"].tobytes(), h)
            h = zlib.crc32(str(spec["noise_seed"]).encode(), h)
        return f"{h:08x}"

    def _materialize(self) -> None:
        os.makedirs(self.image_dir, exist_ok=True)
        stamp = os.path.join(self.image_dir, f".spec-{self._spec_signature()}")
        fresh = os.path.exists(stamp)
        for i, spec in enumerate(self._specs):
            path = self.image_path(i)
            if not fresh or not os.path.exists(path):
                img = self._render(spec)
                try:
                    import cv2

                    cv2.imwrite(path, img[:, :, ::-1])
                except Exception:  # pragma: no cover
                    from PIL import Image

                    Image.fromarray(img).save(path)
        if not fresh:
            # drop stamps of other configurations: their pixels were just
            # overwritten, so leaving them would validate a stale cache if
            # that configuration is ever requested again (A→B→A pattern)
            for name in os.listdir(self.image_dir):
                if name.startswith(".spec-"):
                    os.unlink(os.path.join(self.image_dir, name))
            with open(stamp, "w"):
                pass

    def _load_annotations(self) -> Roidb:
        self._materialize()
        h, w = self.image_size
        return [
            dict(
                image=self.image_path(i),
                index=i,
                height=h,
                width=w,
                boxes=spec["boxes"].copy(),
                gt_classes=spec["gt_classes"].copy(),
                flipped=False,
            )
            for i, spec in enumerate(self._specs)
        ]

    def gt_roidb(self) -> Roidb:
        # no pkl cache: generation is deterministic and instant
        return self._load_annotations()

    def evaluate_detections(self, all_boxes, out_dir: str = None
                            ) -> Dict[str, float]:
        gt = {
            i: dict(
                boxes=spec["boxes"],
                gt_classes=spec["gt_classes"],
                difficult=np.zeros(len(spec["boxes"]), bool),
            )
            for i, spec in enumerate(self._specs)
        }
        aps = []
        results = {}
        for c in range(1, self.num_classes):
            dets = {
                i: np.asarray(all_boxes[c][i]).reshape(-1, 5)
                for i in range(self.num_images)
            }
            has_gt = any((g["gt_classes"] == c).any() for g in gt.values())
            if not has_gt:
                continue
            ap = voc_eval(dets, gt, c, ovthresh=0.5, use_07_metric=True)
            results[self.classes[c]] = ap
            aps.append(ap)
        results["mAP"] = float(np.mean(aps)) if aps else 0.0
        return results


# fixed well-separated saturated palette: hue is the class signature (the
# task must be LEARNABLE); everything else — scale, pattern, brightness,
# occlusion, distractors — is intra-class variation that makes it HARD
_HARD_PALETTE = np.array([
    [220, 40, 40],    # red
    [40, 200, 40],    # green
    [50, 80, 230],    # blue
    [230, 220, 40],   # yellow
    [220, 50, 220],   # magenta
    [40, 220, 220],   # cyan
    [240, 140, 30],   # orange
    [150, 60, 220],   # purple
], np.uint8)


class HardSyntheticDataset(SyntheticDataset):
    """Harder generated benchmark (VERDICT r03 item 3): the 16-image easy
    set (±0.1 mAP seed spread) cannot catch point-level accuracy
    regressions, so this set adds, deterministically per seed:

    * **scale**: object sizes log-uniform over canvas/12 .. canvas/2,
    * **crowding**: 2..max_objects (default 8) objects per image,
    * **occlusion**: placements may overlap up to IoU 0.4 (later draws
      overwrite earlier pixels, but each box keeps >=~50% visible),
    * **appearance noise**: per-instance brightness jitter and an optional
      darker stripe pattern — class identity stays the hue,
    * **distractors**: gray/desaturated rectangles that are not any class
      (hard negatives for the RPN and the classifier).

    Defaults: 9 classes (8 fg + background), 400 train / 100 test images on
    a 240x320 canvas — 400, not fewer, because measured seed spread scales
    with training-set size (200 imgs: 0.043 mAP spread; 400 imgs: <0.02 —
    docs/GAUNTLET.md), and the gauntlet's regression budget needs the
    tight end.  Deterministic per (image_set, generation params);
    evaluation inherits the VOC-style AP of :class:`SyntheticDataset`.
    """

    def __init__(self, image_set: str, root_path: str, dataset_path: str,
                 num_images: int = None, num_classes: int = 9,
                 image_size=(240, 320), max_objects: int = 8):
        if num_images is None:
            num_images = 400 if "train" in image_set else 100
        if num_classes > len(_HARD_PALETTE) + 1:
            raise ValueError(
                f"num_classes <= {len(_HARD_PALETTE) + 1} supported")
        super().__init__(image_set, root_path,
                         dataset_path
                         or os.path.join(root_path, "synthetic_hard"),
                         num_images=num_images, num_classes=num_classes,
                         image_size=image_size, max_objects=max_objects)

    # every gt box must keep at least this fraction of its own pixels
    # visible after all later draws — an almost-fully-overdrawn gt box is
    # unfindable even by a perfect detector and would reintroduce the
    # seed-dependent mAP noise floor this set exists to eliminate
    MIN_VISIBLE = 0.5

    def _make_specs(self) -> List[Dict]:
        h, w = self.image_size
        lo, hi = np.log(max(12.0, w / 12)), np.log(w / 2)
        specs = []
        for i in range(self.num_images):
            n = self._rng.randint(2, self.max_objects + 1)
            boxes, classes = [], []
            # painter's-algorithm owner grid: visibility is checked against
            # the TOTAL coverage of each earlier box, not pairwise IoU (a
            # box can be buried by several small overlaps)
            owner = np.full((h, w), -1, np.int32)
            visible = []  # visible pixel count per placed box
            areas = []
            for _ in range(n):
                for _attempt in range(25):
                    bw = int(round(np.exp(self._rng.uniform(lo, hi))))
                    bh = int(round(np.exp(self._rng.uniform(lo, hi))))
                    bw, bh = min(bw, w - 2), min(bh, h - 2)
                    x1 = self._rng.randint(0, w - bw)
                    y1 = self._rng.randint(0, h - bh)
                    cand = [x1, y1, x1 + bw - 1, y1 + bh - 1]
                    # quick pairwise cap (moderate occlusion allowed) ...
                    if not all(self._iou(cand, b) < 0.4 for b in boxes):
                        continue
                    # ... then the true visibility check: how much of each
                    # earlier box would remain after this draw?
                    region = owner[y1:y1 + bh, x1:x1 + bw]
                    covered = np.bincount(region[region >= 0],
                                          minlength=len(boxes))
                    if any((visible[e] - covered[e]) / areas[e]
                           < self.MIN_VISIBLE for e in range(len(boxes))):
                        continue
                    for e in range(len(boxes)):
                        visible[e] -= int(covered[e])
                    owner[y1:y1 + bh, x1:x1 + bw] = len(boxes)
                    boxes.append(cand)
                    classes.append(self._rng.randint(1, self.num_classes))
                    visible.append(bh * bw)
                    areas.append(bh * bw)
                    break
            # 2..4 distractor rectangles (class of none)
            n_distract = self._rng.randint(2, 5)
            distract = []
            for _ in range(n_distract):
                dw = self._rng.randint(12, max(13, w // 4))
                dh = self._rng.randint(12, max(13, h // 4))
                dx = self._rng.randint(0, w - dw)
                dy = self._rng.randint(0, h - dh)
                cand = [dx, dy, dx + dw - 1, dy + dh - 1]
                # distractors must not occlude real objects into ambiguity
                if all(self._iou(cand, b) < 0.2 for b in boxes):
                    distract.append(cand)
            specs.append(dict(
                boxes=np.asarray(boxes, np.float32),
                gt_classes=np.asarray(classes, np.int32),
                distractors=np.asarray(distract, np.float32).reshape(-1, 4),
                noise_seed=int(self._rng.randint(0, 2 ** 31)),
            ))
        return specs

    def _render(self, spec: Dict) -> np.ndarray:
        h, w = self.image_size
        rng = np.random.RandomState(spec["noise_seed"])
        img = rng.randint(0, 90, size=(h, w, 3)).astype(np.uint8)
        # distractors first: never on top of an object
        for box in spec["distractors"]:
            x1, y1, x2, y2 = box.astype(int)
            g = rng.randint(60, 140)
            jit = rng.randint(-15, 16, 3)
            img[y1:y2 + 1, x1:x2 + 1] = np.clip(g + jit, 0, 255
                                                ).astype(np.uint8)
        for box, cls in zip(spec["boxes"], spec["gt_classes"]):
            x1, y1, x2, y2 = box.astype(int)
            color = _HARD_PALETTE[int(cls) - 1].astype(np.float32)
            # per-instance brightness jitter (±25%): intra-class variation
            color = np.clip(color * rng.uniform(0.75, 1.25), 0, 255)
            patch = np.broadcast_to(
                color, (y2 - y1 + 1, x2 - x1 + 1, 3)).copy()
            if rng.rand() < 0.5:  # darker stripe pattern, axis random
                period = rng.randint(4, 9)
                axis = rng.randint(2)
                idx = np.arange(patch.shape[axis])
                stripe = (idx // max(1, period // 2)) % 2 == 1
                if axis == 0:
                    patch[stripe, :, :] *= 0.6
                else:
                    patch[:, stripe, :] *= 0.6
            img[y1:y2 + 1, x1:x2 + 1] = patch.astype(np.uint8)
            # thin dark outline helps delineate occluded stacks — real
            # detectors get edges for free; solid same-color overlaps would
            # be genuinely ambiguous even for a perfect model
            img[y1:y2 + 1, [x1, x2]] = 20
            img[[y1, y2], x1:x2 + 1] = 20
        return img

    def _spec_signature(self) -> str:
        base = super()._spec_signature()
        hshd = zlib.crc32(b"hard", int(base, 16))
        for spec in self._specs:
            hshd = zlib.crc32(spec["distractors"].tobytes(), hshd)
        return f"{hshd:08x}"


class StreamSyntheticDataset(SyntheticDataset):
    """COCO-cardinality rehearsal set (ROADMAP item 3 / docs/DATA.md).

    Every pre-r7 loader/cache/decode-pool claim was measured on <=400
    generated images — sets that fit in HBM, let alone RAM.  The
    reference trained 118k-image COCO epochs; this set rehearses that
    CARDINALITY (default 10k train / 1k test, 80 fg classes to match
    COCO's class count) without rehearsing COCO's resolution: the
    240x320 canvas keeps one-time materialization and per-image decode
    cheap enough that a 1-core host can drive a full streaming epoch,
    while the image COUNT exercises exactly the paths small sets cannot
    — bounded cache windows, shard unions, mid-epoch cursors.

    Generation-cost deltas vs :class:`SyntheticDataset` (which is
    O(canvas) of np.random per image and writes poorly-compressible
    full-canvas noise):

    * the background is a 16x16 noise TILE repeated across the canvas —
      PNGs compress ~10x smaller (10k images ~ 150 MB, not ~2 GB) and
      encode/decode markedly faster, at zero cost to the class-color
      learnability invariant,
    * class identity stays the ``_class_color`` hue (deterministic for
      ANY class count — 80 works as well as 4).

    Deterministic per (image_set, generation params) like every
    synthetic set; evaluation inherits the VOC-style AP machinery.
    """

    def __init__(self, image_set: str, root_path: str, dataset_path: str,
                 num_images: int = None, num_classes: int = 81,
                 image_size=(240, 320), max_objects: int = 6):
        if num_images is None:
            num_images = 10_000 if "train" in image_set else 1_000
        super().__init__(image_set, root_path,
                         dataset_path
                         or os.path.join(root_path, "synthetic_stream"),
                         num_images=num_images, num_classes=num_classes,
                         image_size=image_size, max_objects=max_objects)

    def _render(self, spec: Dict) -> np.ndarray:
        h, w = self.image_size
        rng = np.random.RandomState(spec["noise_seed"])
        tile = rng.randint(0, 60, size=(16, 16, 3)).astype(np.uint8)
        img = np.tile(tile, ((h + 15) // 16, (w + 15) // 16, 1))[:h, :w]
        img = np.ascontiguousarray(img)
        for box, cls in zip(spec["boxes"], spec["gt_classes"]):
            x1, y1, x2, y2 = box.astype(int)
            img[y1:y2 + 1, x1:x2 + 1] = _class_color(int(cls))
        return img

    def _spec_signature(self) -> str:
        # distinct from the base class: the pixels differ (tiled
        # background), so a PNG cache written by one class must never
        # validate for the other
        base = super()._spec_signature()
        return f"{zlib.crc32(b'stream', int(base, 16)):08x}"


def make_batch(cfg, batch_images, h, w, seed=0, raw=False):
    """Synthetic training batch; ``raw=True`` emits the uint8 image layout
    the production loader ships (device-side normalization path)."""
    import jax.numpy as jnp

    from mx_rcnn_tpu.core.train import Batch

    rng = np.random.RandomState(seed)
    g = cfg.train.max_gt_boxes
    n_gt = 8
    gt_boxes = np.zeros((batch_images, g, 4), np.float32)
    gt_classes = np.zeros((batch_images, g), np.int32)
    gt_valid = np.zeros((batch_images, g), bool)
    for i in range(batch_images):
        xy = rng.uniform(0, [w * 0.8, h * 0.8], (n_gt, 2))
        wh = rng.uniform(0.05, 0.4, (n_gt, 2)) * [w, h]
        gt_boxes[i, :n_gt, :2] = xy
        gt_boxes[i, :n_gt, 2:] = np.minimum(xy + wh, [w - 1, h - 1])
        gt_classes[i, :n_gt] = rng.randint(1, cfg.dataset.num_classes, n_gt)
        gt_valid[i, :n_gt] = True
    if raw:
        images = jnp.asarray(
            rng.randint(0, 256, (batch_images, h, w, 3)), jnp.uint8)
    else:
        images = jnp.asarray(rng.randn(batch_images, h, w, 3), jnp.float32)
    return Batch(
        images=images,
        im_info=jnp.tile(jnp.array([[float(h), float(w), 1.0]]),
                         (batch_images, 1)),
        gt_boxes=jnp.asarray(gt_boxes),
        gt_classes=jnp.asarray(gt_classes),
        gt_valid=jnp.asarray(gt_valid),
    )
