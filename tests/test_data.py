"""Data layer tests: image transforms, roidb, VOC parsing/eval, loaders."""

import os
import textwrap

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data.image import choose_bucket, resize_keep_ratio
from mx_rcnn_tpu.data.loader import AnchorLoader, TestLoader
from mx_rcnn_tpu.data.pascal_voc import PascalVOC
from mx_rcnn_tpu.data.roidb import IMDB, filter_roidb, merge_roidbs
from mx_rcnn_tpu.data.synthetic import SyntheticDataset
from mx_rcnn_tpu.data.voc_eval import voc_ap, voc_eval


def test_resize_keep_ratio_short_side():
    img = np.zeros((480, 640, 3), np.uint8)
    out, scale = resize_keep_ratio(img, 600, 1000)
    assert min(out.shape[:2]) == 600
    assert abs(scale - 600 / 480) < 1e-6


def test_resize_keep_ratio_long_side_cap():
    img = np.zeros((300, 900, 3), np.uint8)
    out, scale = resize_keep_ratio(img, 600, 1000)
    assert max(out.shape[:2]) <= 1000
    assert abs(scale - 1000 / 900) < 1e-6


def test_choose_bucket_orientation():
    buckets = ((608, 1024), (1024, 608))
    assert choose_bucket(600, 1000, buckets) == (608, 1024)
    assert choose_bucket(1000, 600, buckets) == (1024, 608)


def test_append_flipped_images():
    roidb = [dict(image="x.jpg", height=100, width=200,
                  boxes=np.array([[10.0, 20.0, 50.0, 60.0]], np.float32),
                  gt_classes=np.array([3], np.int32), flipped=False)]
    out = IMDB.append_flipped_images(roidb)
    assert len(out) == 2
    assert out[1]["flipped"] is True
    np.testing.assert_allclose(out[1]["boxes"], [[149.0, 20.0, 189.0, 60.0]])


def test_merge_and_filter_roidb():
    a = [dict(boxes=np.zeros((1, 4)))]
    b = [dict(boxes=np.zeros((0, 4))), dict(boxes=np.zeros((2, 4)))]
    merged = merge_roidbs([a, b])
    assert len(merged) == 3
    assert len(filter_roidb(merged)) == 2


def test_voc_ap_known_curve():
    rec = np.array([0.0, 0.5, 1.0])
    prec = np.array([1.0, 1.0, 1.0])
    assert abs(voc_ap(rec, prec, use_07_metric=True) - 1.0) < 1e-6
    assert abs(voc_ap(rec, prec, use_07_metric=False) - 1.0) < 1e-6


def test_voc_eval_perfect_and_miss():
    gt = {"img1": dict(boxes=np.array([[0.0, 0.0, 10.0, 10.0]]),
                       gt_classes=np.array([1]),
                       difficult=np.zeros(1, bool))}
    perfect = {"img1": np.array([[0.0, 0.0, 10.0, 10.0, 0.9]])}
    assert voc_eval(perfect, gt, 1) > 0.99
    miss = {"img1": np.array([[50.0, 50.0, 60.0, 60.0, 0.9]])}
    assert voc_eval(miss, gt, 1) == 0.0


def _write_fake_voc(root):
    voc = os.path.join(root, "VOCdevkit", "VOC2007")
    os.makedirs(os.path.join(voc, "ImageSets", "Main"), exist_ok=True)
    os.makedirs(os.path.join(voc, "Annotations"), exist_ok=True)
    os.makedirs(os.path.join(voc, "JPEGImages"), exist_ok=True)
    with open(os.path.join(voc, "ImageSets", "Main", "train.txt"), "w") as f:
        f.write("000001\n")
    xml = textwrap.dedent("""\
        <annotation>
          <size><width>353</width><height>500</height><depth>3</depth></size>
          <object><name>dog</name><difficult>0</difficult>
            <bndbox><xmin>48</xmin><ymin>240</ymin><xmax>195</xmax><ymax>371</ymax></bndbox>
          </object>
          <object><name>person</name><difficult>1</difficult>
            <bndbox><xmin>8</xmin><ymin>12</ymin><xmax>352</xmax><ymax>498</ymax></bndbox>
          </object>
        </annotation>""")
    with open(os.path.join(voc, "Annotations", "000001.xml"), "w") as f:
        f.write(xml)
    return os.path.join(root, "VOCdevkit")


def test_pascal_voc_parsing(tmp_path):
    devkit = _write_fake_voc(str(tmp_path))
    ds = PascalVOC("2007_train", str(tmp_path), devkit)
    roidb = ds._load_annotations()
    assert len(roidb) == 1
    rec = roidb[0]
    assert rec["width"] == 353 and rec["height"] == 500
    # difficult object excluded by default; dog = class 12 in VOC order
    assert len(rec["boxes"]) == 1
    assert rec["gt_classes"][0] == ds.classes.index("dog")
    np.testing.assert_allclose(rec["boxes"][0], [47.0, 239.0, 194.0, 370.0])


def test_synthetic_dataset_and_loaders(tmp_path):
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("bucket", shapes=((128, 160), (160, 128)),
                         scale=120, max_size=160)
    cfg = cfg.replace_in("train", max_gt_boxes=8)
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=6,
                          image_size=(96, 128))
    roidb = ds.gt_roidb()
    assert len(roidb) == 6
    assert all(os.path.exists(r["image"]) for r in roidb)

    loader = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True)
    batches = list(loader)
    assert len(batches) == 3
    b = batches[0]
    assert b.images.shape[0] == 2
    assert b.images.shape[1:] in ((128, 160, 3), (160, 128, 3))
    assert b.gt_valid.any()
    # gt boxes scaled into resized image extent
    for j in range(2):
        h, w = b.im_info[j, 0], b.im_info[j, 1]
        valid_boxes = b.gt_boxes[j][b.gt_valid[j]]
        assert (valid_boxes[:, 2] <= w - 1 + 1e-3).all()
        assert (valid_boxes[:, 3] <= h - 1 + 1e-3).all()

    tl = TestLoader(roidb, cfg, batch_images=2)
    seen = []
    for batch, indices, scales in tl:
        seen.extend(indices)
        assert batch.images.shape[0] == len(indices) == len(scales)
    assert sorted(seen) == list(range(6))


def test_synthetic_eval_selfconsistent(tmp_path):
    """Feeding the ground truth as detections must give mAP ≈ 1."""
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=4,
                          image_size=(96, 128), num_classes=5)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(4)]
                 for _ in range(ds.num_classes)]
    for i, spec in enumerate(ds._specs):
        for box, cls in zip(spec["boxes"], spec["gt_classes"]):
            det = np.concatenate([box, [0.99]]).astype(np.float32)[None]
            all_boxes[int(cls)][i] = np.vstack([all_boxes[int(cls)][i], det])
    res = ds.evaluate_detections(all_boxes)
    assert res["mAP"] > 0.95, res


def test_prefetch_loader_identical_batches(tmp_path):
    """Prefetched iteration must yield batches identical (content and
    order) to the synchronous path — thread-pool assembly is an overlap
    optimization, never a semantics change."""
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("bucket", shapes=((128, 160), (160, 128)),
                         scale=120, max_size=160)
    cfg = cfg.replace_in("train", max_gt_boxes=8)
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=10,
                          image_size=(96, 128))
    roidb = ds.gt_roidb()

    sync = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=3,
                        num_workers=0)
    pre = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=3,
                       num_workers=3, prefetch=4)
    sync.set_epoch(1)
    pre.set_epoch(1)
    got_s, got_p = list(sync), list(pre)
    assert len(got_s) == len(got_p) > 0
    for bs, bp in zip(got_s, got_p):
        for fs, fp in zip(bs, bp):
            np.testing.assert_array_equal(np.asarray(fs), np.asarray(fp))

    tls = TestLoader(roidb, cfg, batch_images=3, num_workers=0)
    tlp = TestLoader(roidb, cfg, batch_images=3, num_workers=3, prefetch=2)
    for (b1, i1, s1), (b2, i2, s2) in zip(tls, tlp):
        assert i1 == i2
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(b1.images, b2.images)


def test_skip_next_batches(tmp_path):
    """skip_next_batches trims the next iteration's batch order (preemption
    resume) without touching later epochs."""
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("bucket", shapes=((128, 160), (160, 128)),
                         scale=120, max_size=160)
    cfg = cfg.replace_in("train", max_gt_boxes=8)
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=12,
                          image_size=(96, 128))
    roidb = ds.gt_roidb()
    ref = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=5,
                       num_workers=0)
    cut = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=5,
                       num_workers=0)
    ref.set_epoch(2)
    cut.set_epoch(2)
    full = list(ref)
    cut.skip_next_batches(2)
    tail = list(cut)
    assert len(tail) == len(full) - 2
    for bs, bp in zip(full[2:], tail):
        np.testing.assert_array_equal(bs.images, bp.images)
    # skip applies ONCE: the following epoch is complete again
    ref.set_epoch(3)
    cut.set_epoch(3)
    assert len(list(cut)) == len(list(ref))


def _mini_roidb(tmp_path, n=4):
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=n,
                          image_size=(120, 160))
    return ds.gt_roidb()


def test_raw_loader_bitexact_vs_host_normalized(tmp_path):
    """The uint8 raw path + device normalization must reproduce the host
    fp32 mean-subtract path BITWISE (ops/normalize.py contract)."""
    import jax.numpy as jnp

    from mx_rcnn_tpu.ops.normalize import normalize_images

    cfg = generate_config("tiny", "synthetic")
    roidb = _mini_roidb(tmp_path)
    host = AnchorLoader(roidb, cfg, batch_images=2, shuffle=False,
                        num_workers=0, raw_images=False)
    raw = AnchorLoader(roidb, cfg, batch_images=2, shuffle=False,
                       num_workers=0, raw_images=True)
    for bh, br in zip(host, raw):
        assert br.images.dtype == np.uint8
        assert bh.images.dtype == np.float32
        np.testing.assert_array_equal(bh.im_info, br.im_info)
        normed = np.asarray(normalize_images(
            jnp.asarray(br.images), jnp.asarray(br.im_info),
            cfg.network.pixel_means))
        np.testing.assert_array_equal(normed, bh.images)


def test_normalize_passthrough_and_uint8_guard():
    import jax.numpy as jnp
    import pytest as _pytest

    from mx_rcnn_tpu.ops.normalize import normalize_images

    x = jnp.ones((1, 4, 4, 3), jnp.float32)
    assert normalize_images(x, None, (1.0, 2.0, 3.0)) is x
    with _pytest.raises(ValueError):
        normalize_images(x.astype(jnp.uint8), None, (1.0, 2.0, 3.0))


def test_decoded_image_cache_ram_and_disk(tmp_path):
    from mx_rcnn_tpu.data.cache import DecodedImageCache, plan_scale
    from mx_rcnn_tpu.data.image import load_resized_uint8

    cfg = generate_config("tiny", "synthetic")
    roidb = _mini_roidb(tmp_path)
    bucket = cfg.bucket.shapes[0]
    sc, ms = cfg.bucket.scale, cfg.bucket.max_size

    cache = DecodedImageCache(ram_bytes=1 << 30,
                              cache_dir=str(tmp_path / "imgcache"))
    rec = roidb[0]
    direct, direct_scale = load_resized_uint8(rec["image"], False, sc, ms,
                                              bucket)
    got = cache.load(rec["image"], False, sc, ms, bucket)
    np.testing.assert_array_equal(got, direct)
    assert cache.misses == 1 and cache.hits == 0
    # RAM hit
    got2 = cache.load(rec["image"], False, sc, ms, bucket)
    np.testing.assert_array_equal(got2, direct)
    assert cache.hits == 1
    # disk tier: a fresh cache instance over the same dir must hit disk
    cache2 = DecodedImageCache(ram_bytes=0,
                               cache_dir=str(tmp_path / "imgcache"))
    got3 = cache2.load(rec["image"], False, sc, ms, bucket)
    np.testing.assert_array_equal(got3, direct)
    assert cache2.hits == 1 and cache2.misses == 0
    # plan_scale matches the decode path's scale exactly
    assert plan_scale(rec["height"], rec["width"], sc, ms, bucket) \
        == direct_scale
    # flipped variant gets its own key
    flipped = cache.load(rec["image"], True, sc, ms, bucket)
    assert (flipped != got).any()


def test_plan_scale_matches_decode_over_random_geometries(tmp_path):
    """plan_scale must equal the im_scale the REAL decode path returns for
    randomized (h, w, scale, max_size, bucket) combos — actually decoding
    an image each time, so any future edit to load_resized_uint8's resize
    arithmetic that desyncs the cached scale fails here (advisor r3)."""
    from PIL import Image

    from mx_rcnn_tpu.data.cache import plan_scale
    from mx_rcnn_tpu.data.image import load_resized_uint8

    rng = np.random.RandomState(0)
    for i in range(25):
        h = int(rng.randint(40, 500))
        w = int(rng.randint(40, 500))
        scale = int(rng.choice([120, 240, 400]))
        max_size = int(rng.choice([200, 320, 640]))
        bucket = (int(rng.choice([128, 256, 416])),
                  int(rng.choice([128, 256, 416])))
        flipped = bool(rng.randint(2))
        p = tmp_path / f"g{i}.png"
        Image.fromarray(rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
                        ).save(p)
        img, s_decode = load_resized_uint8(str(p), flipped, scale, max_size,
                                           bucket)
        s_plan = plan_scale(h, w, scale, max_size, bucket)
        assert s_plan == s_decode, (h, w, scale, max_size, bucket, flipped)
        # and the decoded image always fits the bucket
        assert img.shape[0] <= bucket[0] and img.shape[1] <= bucket[1]


def test_torn_disk_cache_falls_through_to_decode(tmp_path):
    """The ISSUE-12 triage decision behind cache.py's PL102/PL103
    waiver: the decoded-image cache commits via os.replace WITHOUT an
    fsync because it is rebuildable, not durable state — a crash-torn
    (truncated) or zero-length .npy must fail np.load's own validation,
    fall through to a fresh decode, and be overwritten with a good
    entry.  If this stops holding, the waiver (and the fsync-free
    commit) must go."""
    from PIL import Image

    from mx_rcnn_tpu.data.cache import DecodedImageCache

    p = tmp_path / "img.png"
    Image.fromarray(np.full((40, 60, 3), 77, np.uint8)).save(p)
    cache = DecodedImageCache(ram_bytes=0, cache_dir=str(tmp_path / "c"))
    good = cache.load(str(p), False, 32, 64, (32, 64))
    assert cache.misses == 1
    import glob as _glob
    (entry,) = _glob.glob(str(tmp_path / "c" / "*.npy"))
    full = open(entry, "rb").read()
    for torn in (full[: len(full) // 2], b""):
        with open(entry, "wb") as f:   # simulate the crash state
            f.write(torn)
        fresh = DecodedImageCache(ram_bytes=0,
                                  cache_dir=str(tmp_path / "c"))
        got = fresh.load(str(p), False, 32, 64, (32, 64))
        np.testing.assert_array_equal(got, good)
        assert fresh.misses == 1, "torn entry must MISS, not serve"
        # and the re-decode repaired the on-disk entry
        assert open(entry, "rb").read() == full


def test_cache_invalidates_on_source_file_change(tmp_path):
    """Replacing a source image must invalidate its disk-cache entry
    (advisor r3: the key previously hashed only path + geometry)."""
    from PIL import Image

    from mx_rcnn_tpu.data.cache import DecodedImageCache

    p = tmp_path / "img.png"
    a = np.full((40, 60, 3), 10, np.uint8)
    Image.fromarray(a).save(p)
    cache = DecodedImageCache(ram_bytes=0, cache_dir=str(tmp_path / "c"))
    got = cache.load(str(p), False, 32, 64, (32, 64))
    assert got.mean() > 5
    # replace the file with different pixels (force a distinct mtime_ns)
    b = np.full((40, 60, 3), 200, np.uint8)
    Image.fromarray(b).save(p)
    os.utime(p, ns=(1, 1))
    got2 = cache.load(str(p), False, 32, 64, (32, 64))
    assert got2.mean() > 100, "stale cache entry served after file change"
    assert cache.misses == 2
    # the superseded on-disk version was evicted, not orphaned
    import glob as _glob
    assert len(_glob.glob(str(tmp_path / "c" / "*.npy"))) == 1
    # a pre-versioning legacy file (digest-stem.npy, no version segment)
    # is also swept when its entry is rewritten
    cur = _glob.glob(str(tmp_path / "c" / "*.npy"))[0]
    stable = os.path.basename(cur).rsplit(".", 2)[0]
    legacy = tmp_path / "c" / (stable + ".npy")
    legacy.write_bytes(b"old-format")
    os.utime(p, ns=(2, 2))  # force yet another version
    cache.load(str(p), False, 32, 64, (32, 64))
    assert not legacy.exists(), "legacy versionless entry not evicted"
    assert len(_glob.glob(str(tmp_path / "c" / "*.npy"))) == 1


def test_decode_pool_from_config():
    from mx_rcnn_tpu.data import decode_pool_from_config

    cfg = generate_config("tiny", "synthetic")
    assert decode_pool_from_config(cfg) is None  # default: in-thread
    pool = decode_pool_from_config(
        generate_config("tiny", "synthetic", default__decode_procs=1))
    try:
        assert pool is not None and pool.num_procs == 1
    finally:
        pool.close()


@pytest.mark.slow
def test_decode_pool_identical_batches(tmp_path):
    """A DecodePool-backed loader must yield batches identical to the
    in-thread loader (pixels AND im_info), and the pool must be spawn-safe
    (workers never import JAX).  Slow tier: spawning interpreters costs
    seconds."""
    from mx_rcnn_tpu.data.decode_pool import DecodePool
    from mx_rcnn_tpu.data.roidb import IMDB

    cfg = generate_config("tiny", "synthetic")
    roidb = IMDB.append_flipped_images(_mini_roidb(tmp_path))
    plain = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=7,
                         num_workers=0)
    with DecodePool(2, cache_dir=str(tmp_path / "pc")) as pool:
        pooled = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True,
                              seed=7, num_workers=2, decode_pool=pool)
        for bp, bc in zip(plain, pooled):
            np.testing.assert_array_equal(bp.images, bc.images)
            np.testing.assert_array_equal(bp.im_info, bc.im_info)
            np.testing.assert_array_equal(bp.gt_boxes, bc.gt_boxes)
        # second epoch rides the shared disk cache written by the workers
        for bp, bc in zip(plain, pooled):
            np.testing.assert_array_equal(bp.images, bc.images)
    with pytest.raises(ValueError):
        DecodePool(0)


def test_cached_loader_identical_batches(tmp_path):
    """A cache-backed loader must yield batches identical to the direct
    loader, epoch after epoch (including flip keys)."""
    from mx_rcnn_tpu.data.cache import DecodedImageCache
    from mx_rcnn_tpu.data.roidb import IMDB

    cfg = generate_config("tiny", "synthetic")
    roidb = IMDB.append_flipped_images(_mini_roidb(tmp_path))
    cache = DecodedImageCache(ram_bytes=1 << 30)
    plain = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=7,
                         num_workers=0)
    cached = AnchorLoader(roidb, cfg, batch_images=2, shuffle=True, seed=7,
                          num_workers=0, cache=cache)
    for _ in range(2):  # second epoch runs fully from cache
        for bp, bc in zip(plain, cached):
            np.testing.assert_array_equal(bp.images, bc.images)
            np.testing.assert_array_equal(bp.im_info, bc.im_info)
            np.testing.assert_array_equal(bp.gt_boxes, bc.gt_boxes)
    assert cache.hits > 0


def test_ram_cache_eviction_budget():
    from mx_rcnn_tpu.data.cache import DecodedImageCache

    c = DecodedImageCache(ram_bytes=100)
    a = np.zeros((5, 8, 3), np.uint8)  # 120 bytes > budget: never stored
    c._ram_put("a", a)
    assert c._ram_used == 0
    b = np.zeros((4, 4, 3), np.uint8)  # 48 bytes
    c._ram_put("b", b)
    c._ram_put("c", b.copy())
    assert c._ram_used == 96
    c._ram_put("d", b.copy())  # evicts the LRU entry ("b")
    assert c._ram_used == 96 and "b" not in c._ram and "d" in c._ram


def test_config_from_args_set_overrides():
    """--set section__field=value parses literals and rejects bad keys."""
    import argparse

    from mx_rcnn_tpu.tools.train import config_from_args

    ns = argparse.Namespace(network="tiny", dataset="synthetic",
                            set=["train__rpn_pre_nms_top_n=6000",
                                 "bucket__scale=600",
                                 "default__prefix=model/x"])
    cfg = config_from_args(ns)
    assert cfg.train.rpn_pre_nms_top_n == 6000
    assert cfg.bucket.scale == 600
    assert cfg.default.prefix == "model/x"  # literal_eval fallback → str
    with pytest.raises(ValueError, match="section__field"):
        config_from_args(argparse.Namespace(
            network="tiny", dataset="synthetic", set=["badkey"]))


@pytest.mark.parametrize("key,val", [
    ("train__roi_align_backend", "blocked"), ("train__roi_align_chunk", 32),
    ("train__remat_backbone", True), ("train__nms_batched", False)])
def test_removed_levers_are_refused_by_name(key, val):
    """The decided levers are gone from the config, not ignored: an
    override or a ``--set`` of one fails and names the field."""
    import argparse

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.tools.train import config_from_args

    field = key.split("__", 1)[1]
    with pytest.raises(TypeError, match=field):
        generate_config("tiny", "synthetic", **{key: val})
    with pytest.raises(TypeError, match=field):
        config_from_args(argparse.Namespace(
            network="tiny", dataset="synthetic", set=[f"{key}={val}"]))


def test_set_override_type_coercion():
    """--set values coerce to the field's declared type; bad types are
    rejected loudly (the string 'false' must never become a truthy flag)."""
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("tiny", "synthetic", train__shuffle="false")
    assert cfg.train.shuffle is False
    cfg = generate_config("tiny", "synthetic", train__shuffle="True")
    assert cfg.train.shuffle is True
    cfg = generate_config("tiny", "synthetic", default__e2e_lr="0.01")
    assert cfg.default.e2e_lr == 0.01
    cfg = generate_config("tiny", "synthetic",
                          bucket__shapes=[[320, 416]])
    assert cfg.bucket.shapes == ((320, 416),)  # deep tuple conversion
    with pytest.raises(TypeError, match="expects a float"):
        generate_config("tiny", "synthetic", default__e2e_lr=True)
    with pytest.raises(TypeError, match="expects a bool"):
        generate_config("tiny", "synthetic", train__shuffle="maybe")
    with pytest.raises(TypeError, match="expects an int"):
        generate_config("tiny", "synthetic", train__batch_images="two")
    with pytest.raises(TypeError, match="expects an int"):
        generate_config("tiny", "synthetic", train__batch_images=1.5)


def test_coerce_override_none_current_uses_annotation():
    """A known field whose CURRENT value is None must still coerce/reject
    by its declared (resolved) type (advisor r3: None used to skip all
    type checks); unknown fields (no annotation) still pass through."""
    from typing import Optional, Tuple, Union

    from mx_rcnn_tpu.config import _coerce_override

    assert _coerce_override(None, "false", "s__f", bool) is False
    assert _coerce_override(None, "7", "s__f", int) == 7
    assert _coerce_override(None, "0.5", "s__f", float) == 0.5
    assert _coerce_override(None, [[1, 2]], "s__f",
                            Tuple[Tuple[int, int], ...]) == ((1, 2),)
    assert _coerce_override(None, "x", "s__f", Optional[str]) == "x"
    # every Optional/Union spelling resolves to the same union form
    assert _coerce_override(None, "3", "s__f", Optional[int]) == 3
    assert _coerce_override(None, "3", "s__f", Union[int, None]) == 3
    assert _coerce_override(None, "3", "s__f", eval("int | None")) == 3
    with pytest.raises(TypeError, match="expects an int"):
        _coerce_override(None, "two", "s__f", Optional[int])
    with pytest.raises(TypeError, match="expects a bool"):
        _coerce_override(None, "maybe", "s__f", bool)
    # genuinely multi-typed union: stored as-is (no exemplar)
    assert _coerce_override(None, "raw", "s__f", Union[int, str]) == "raw"
    # unknown field: passes through so replace_in raises its own error
    assert _coerce_override(None, "raw", "s__f", None) == "raw"
    # None value always passes through (meaning "unset")
    assert _coerce_override(None, None, "s__f", int) is None


def test_test_cli_consumes_set_overrides(tmp_path, monkeypatch):
    """tools/test.py must actually APPLY --set overrides (regression: the
    flag was once registered but ignored)."""
    from mx_rcnn_tpu.tools import test as test_tool

    seen = {}

    def fake_test_rcnn(cfg, **kw):
        seen["thresh"] = cfg.test.score_thresh
        return {}

    monkeypatch.setattr(test_tool, "test_rcnn", fake_test_rcnn)
    test_tool.main(["--network", "tiny", "--dataset", "synthetic",
                    "--epoch", "1", "--set", "test__score_thresh=0.25"])
    assert seen["thresh"] == 0.25


def test_decode_pool_small_cache_budget_clamped(monkeypatch, caplog):
    """image_cache_mb < decode_procs used to floor the per-worker RAM
    share to 0, silently disabling the cache the config asked for
    (ADVICE r5): now it clamps to 1 MB and says so."""
    import logging

    from mx_rcnn_tpu.data import loader as loader_mod

    built = {}

    class FakePool:
        def __init__(self, procs, cache_dir=None, ram_bytes=None):
            built.update(procs=procs, cache_dir=cache_dir,
                         ram_bytes=ram_bytes)

    monkeypatch.setattr("mx_rcnn_tpu.data.decode_pool.DecodePool", FakePool)
    cfg = generate_config("tiny", "synthetic", default__decode_procs=8,
                          default__image_cache_mb=4)
    with caplog.at_level(logging.WARNING, logger="mx_rcnn_tpu"):
        loader_mod.decode_pool_from_config(cfg)
    assert built["ram_bytes"] == 1 << 20
    assert "cache budget 4 MB" in caplog.text
    assert "decode_procs=8" in caplog.text
    # a healthy budget still splits undisturbed, without the warning
    built.clear()
    caplog.clear()
    cfg = generate_config("tiny", "synthetic", default__decode_procs=4,
                          default__image_cache_mb=12)
    with caplog.at_level(logging.WARNING, logger="mx_rcnn_tpu"):
        loader_mod.decode_pool_from_config(cfg)
    assert built["ram_bytes"] == 3 << 20
    assert "clamping" not in caplog.text
