"""The one general generator of training traffic: a set of images with
boxes, made from a seed and a traffic file's parameters.

Every seed gives the same number of images of the same size, so the work
of a run does not depend on the seed; only pixel content, box positions,
box counts and classes do.  Images are a coarse random texture with one
flat-coloured, lightly textured rectangle per object, written as PNG.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np


def make_images(traffic: Dict, seed: int, num_classes: int, count: int):
    """``count`` (image uint8 (H, W, 3) RGB, boxes float32 (k, 4) inclusive
    corners, classes int32 (k,)) triples."""
    rng = np.random.RandomState(seed % (2 ** 32))
    h, w = traffic["image_hw"]
    cell = traffic["texture_cell"]
    lo_n, hi_n = traffic["boxes_per_image"]
    lo_s, hi_s = traffic["box_side"]
    out = []
    for _ in range(count):
        coarse = rng.randint(0, 256, (-(-h // cell), -(-w // cell), 3))
        img = np.repeat(np.repeat(coarse, cell, 0), cell, 1)[:h, :w]
        img = img.astype(np.uint8)
        k = rng.randint(lo_n, hi_n + 1)
        boxes = np.zeros((k, 4), np.float32)
        classes = rng.randint(1, num_classes, k).astype(np.int32)
        for j in range(k):
            bw = rng.randint(lo_s, min(hi_s, w - 1) + 1)
            bh = rng.randint(lo_s, min(hi_s, h - 1) + 1)
            x1 = rng.randint(0, w - bw)
            y1 = rng.randint(0, h - bh)
            boxes[j] = (x1, y1, x1 + bw - 1, y1 + bh - 1)
            colour = rng.randint(0, 256, 3)
            patch = img[y1:y1 + bh, x1:x1 + bw].astype(np.int32)
            img[y1:y1 + bh, x1:x1 + bw] = ((patch + 3 * colour) // 4
                                           ).astype(np.uint8)
        out.append((img, boxes, classes))
    return out


def write_roidb(items, out_dir: str, repeat_to: int) -> List[Dict]:
    """Write the images under ``out_dir`` and return roidb records, the
    distinct ones repeated in order up to ``repeat_to`` records."""
    import cv2

    os.makedirs(out_dir, exist_ok=True)
    recs = []
    for i, (img, boxes, classes) in enumerate(items):
        path = os.path.join(out_dir, f"{i:05d}.png")
        if not cv2.imwrite(path, np.ascontiguousarray(img[:, :, ::-1]),
                           [cv2.IMWRITE_PNG_COMPRESSION, 1]):
            raise OSError(f"cannot write {path}")
        recs.append({"image": path, "height": int(img.shape[0]),
                     "width": int(img.shape[1]), "boxes": boxes,
                     "gt_classes": classes, "flipped": False, "index": i})
    return [recs[i % len(recs)] for i in range(repeat_to)]


def reference_batches(items, bucket_hw, batch: int, steps: int,
                      max_gt: int) -> List[Dict]:
    """The first ``steps`` batches as the recipe assembles them from records
    in order: images padded with zeros into the bucket, (h, w, scale 1)
    as im_info, boxes padded to ``max_gt`` rows."""
    bh, bw = bucket_hw
    out = []
    for s in range(steps):
        rows = [items[(s * batch + j) % len(items)] for j in range(batch)]
        images = np.zeros((batch, bh, bw, 3), np.uint8)
        im_info = np.zeros((batch, 3), np.float32)
        gt = np.zeros((batch, max_gt, 4), np.float32)
        cls = np.zeros((batch, max_gt), np.int32)
        valid = np.zeros((batch, max_gt), bool)
        for j, (img, boxes, classes) in enumerate(rows):
            h, w = img.shape[:2]
            images[j, :h, :w] = img
            im_info[j] = (h, w, 1.0)
            k = len(boxes)
            gt[j, :k], cls[j, :k], valid[j, :k] = boxes, classes, True
        out.append({"images": images, "im_info": im_info, "gt_boxes": gt,
                    "gt_classes": cls, "gt_valid": valid})
    return out
