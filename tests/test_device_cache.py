"""HBM-resident epoch cache (data/device_cache.py): equivalence with the
streaming path and on-device shuffle coverage."""

import numpy as np
import jax
import pytest
import jax.numpy as jnp

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.train import make_train_step, setup_training
from mx_rcnn_tpu.data.device_cache import (DeviceEpochCache, build_caches,
                                           make_cached_step)
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.data.synthetic import make_batch


def _tiny_setup(n_batches=3):
    cfg = generate_config("tiny", "synthetic")
    cfg = cfg.replace_in("train", batch_images=1, rpn_pre_nms_top_n=64,
                         rpn_post_nms_top_n=16, batch_rois=8, max_gt_boxes=8,
                         rpn_batch_size=16, rpn_min_size=2)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    batches = [make_batch(cfg, 1, 64, 96, seed=s, raw=True)
               for s in range(n_batches)]
    state, tx = setup_training(model, cfg, key, (1, 64, 96, 3),
                               steps_per_epoch=100)
    return cfg, model, tx, state, key, batches


def test_cached_step_matches_streaming_bitwise():
    """shuffle=False cached steps must reproduce the streaming step
    sequence exactly (same weights after an epoch)."""
    cfg, model, tx, state, key, batches = _tiny_setup()
    base = make_train_step(model, cfg, tx)
    step = jax.jit(base)
    s_stream = state
    for b in batches:
        s_stream, m_stream = step(s_stream, b, key)

    cache = DeviceEpochCache(batches)
    cstep = jax.jit(make_cached_step(base, cache.num_batches, shuffle=False))
    s_cache, idx = state, cache.index_handle()
    for _ in range(len(batches)):
        s_cache, idx, m_cache = cstep(s_cache, cache.data, idx, key)
    assert int(idx) == len(batches)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s_stream.params, s_cache.params)
    np.testing.assert_array_equal(np.asarray(m_stream["loss"]),
                                  np.asarray(m_cache["loss"]))


def test_cached_step_shuffle_regroups_images_per_epoch():
    """shuffle=True must (a) visit every IMAGE exactly once per epoch and
    (b) re-GROUP images into different batches across epochs — the
    streaming loader's in-bucket semantics (r5; rounds 2-4 froze batch
    composition at staging and only permuted batch order).  Probed by
    running the REAL cached step with a spy base_step that reports the
    gathered images' tags."""
    cfg, _model, _tx, state, key, _ = _tiny_setup(n_batches=0)
    # bi=2: composition only exists with >1 image per batch
    batches = [make_batch(cfg, 2, 64, 96, seed=s, raw=True)
               for s in range(5)]
    # tag every IMAGE with a unique global id via gt_classes
    for i, b in enumerate(batches):
        tags = np.asarray(b.gt_classes).copy()
        tags[0, :] = 2 * i
        tags[1, :] = 2 * i + 1
        batches[i] = b._replace(gt_classes=jnp.asarray(tags))
    cache = DeviceEpochCache(batches)

    def spy(state, batch, key):
        return state, {"tags": batch.gt_classes[:, 0]}

    cstep = jax.jit(make_cached_step(spy, cache.num_batches, shuffle=True))
    epochs = []
    s, idx = state, cache.index_handle()
    for _e in range(3):
        groups = []
        for _p in range(5):
            s, idx, m = cstep(s, cache.data, idx, key)
            groups.append(tuple(sorted(np.asarray(m["tags"]).tolist())))
        # (a) every image exactly once per epoch
        flat = sorted(t for g in groups for t in g)
        assert flat == list(range(10)), flat
        epochs.append(sorted(groups))
    # (b) composition differs across epochs: the sorted multiset of
    # batch groupings cannot be identical for all three epochs
    assert not (epochs[0] == epochs[1] == epochs[2]), epochs
    # and differs from the staged composition itself
    staged = sorted((2 * i, 2 * i + 1) for i in range(5))
    assert any(e != staged for e in epochs), epochs


def test_build_caches_groups_by_bucket_and_budget(tmp_path):
    from mx_rcnn_tpu.data.loader import AnchorLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset

    cfg = generate_config("tiny", "synthetic")
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=6,
                          image_size=(120, 160))
    roidb = ds.gt_roidb()
    loader = AnchorLoader(roidb, cfg, batch_images=2, shuffle=False,
                          num_workers=0)
    caches = build_caches(loader)
    assert sum(c.num_batches for c in caches) == len(loader)
    import pytest

    with pytest.raises(MemoryError):
        build_caches(loader, max_bytes=10)


@pytest.mark.slow
def test_fit_with_device_cache_matches_streaming(tmp_path):
    """fit(device_cache=True) with a shuffle=False loader must produce the
    SAME final weights as the streaming fit (bitwise) — the integration
    contract of the HBM epoch cache with the training driver."""
    from mx_rcnn_tpu.core.fit import fit
    from mx_rcnn_tpu.data.loader import AnchorLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset

    cfg = generate_config("tiny", "synthetic")
    cfg = cfg.replace_in("train", batch_images=2, rpn_pre_nms_top_n=64,
                         rpn_post_nms_top_n=16, batch_rois=8, max_gt_boxes=8,
                         rpn_batch_size=16, rpn_min_size=2)
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=8,
                          image_size=(120, 160))
    roidb = ds.gt_roidb()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    bh, bw = cfg.bucket.shapes[0]

    def train(device_cache):
        loader = AnchorLoader(roidb, cfg, batch_images=2, shuffle=False,
                              num_workers=0)
        state, tx = setup_training(model, cfg, key, (2, bh, bw, 3),
                                   steps_per_epoch=len(loader))
        return fit(model, cfg, state, tx, loader, 2, key,
                   device_cache=device_cache)

    s_stream = train(False)
    s_cached = train(True)
    assert int(s_stream.step) == int(s_cached.step) == 8
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s_stream.params, s_cached.params)


def test_fit_device_cache_rejects_multibucket(tmp_path):
    from mx_rcnn_tpu.core.fit import fit
    from mx_rcnn_tpu.data.loader import AnchorLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset

    cfg = generate_config("tiny", "synthetic")
    cfg = cfg.replace_in("train", batch_images=1)
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=4,
                          image_size=(120, 160))
    roidb = ds.gt_roidb()
    # mixed orientations → two buckets
    roidb[1]["height"], roidb[1]["width"] = roidb[1]["width"], \
        roidb[1]["height"]
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    loader = AnchorLoader(roidb, cfg, batch_images=1, shuffle=False,
                          num_workers=0)
    bh, bw = cfg.bucket.shapes[0]
    state, tx = setup_training(model, cfg, key, (1, bh, bw, 3),
                               steps_per_epoch=4)
    with pytest.raises(ValueError, match="bucket"):
        fit(model, cfg, state, tx, loader, 1, key, device_cache=True)


@pytest.mark.slow
def test_dp_cached_step_matches_dp_streaming(tmp_path):
    """Mesh x device_cache: the sharded-epoch cached step must reproduce
    the streaming DP step bitwise (shuffle off) on the 8-device mesh."""
    from mx_rcnn_tpu.data.device_cache import build_caches
    from mx_rcnn_tpu.data.loader import AnchorLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.parallel.dp import (device_mesh, make_dp_cached_step,
                                         make_dp_train_step, replicate,
                                         shard_batch)

    cfg = generate_config("tiny", "synthetic")
    cfg = cfg.replace_in("train", batch_images=1, rpn_pre_nms_top_n=64,
                         rpn_post_nms_top_n=16, batch_rois=8, max_gt_boxes=8,
                         rpn_batch_size=16, rpn_min_size=2)
    ds = SyntheticDataset("train", str(tmp_path), "", num_images=16,
                          image_size=(120, 160))
    roidb = ds.gt_roidb()
    mesh = device_mesh(8)
    # global batch = 8 devices x 1 image
    loader = AnchorLoader(roidb, cfg, batch_images=8, shuffle=False,
                          num_workers=0)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    bh, bw = cfg.bucket.shapes[0]
    state, tx = setup_training(model, cfg, key, (1, bh, bw, 3),
                               steps_per_epoch=len(loader))

    stream = make_dp_train_step(model, cfg, tx, mesh)
    s_stream = replicate(jax.tree.map(jnp.copy, state), mesh)
    for b in loader:
        s_stream, m_stream = stream(s_stream, shard_batch(b, mesh), key)

    cache = build_caches(loader, mesh=mesh)[0]
    cstep = make_dp_cached_step(model, cfg, tx, mesh, cache.num_batches,
                                shuffle=False)
    s_cache = replicate(state, mesh)
    idx = cache.index_handle()
    for _ in range(cache.num_batches):
        s_cache, idx, m_cache = cstep(s_cache, cache.data, idx, key)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s_stream.params, s_cache.params)
    np.testing.assert_array_equal(np.asarray(m_stream["loss"]),
                                  np.asarray(m_cache["loss"]))


def test_dp_cached_shuffle_regroups_within_shards():
    """Multi-chip shuffle semantics (r5): under shard_map with the
    P(None, data) epoch layout, the per-epoch image regroup must be
    SHARD-LOCAL — every device sees exactly its own shard's images once
    per epoch (the disclosed residual vs streaming DP: images never
    migrate across devices), deterministically given the replicated
    key."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mx_rcnn_tpu.parallel.dp import data_axes, device_mesh

    cfg, _model, _tx, state, key, _ = _tiny_setup(n_batches=0)
    mesh = device_mesh(2)
    axes = data_axes(mesh)
    nb, bi_global = 3, 4  # bi_local = 2 per device: real regrouping
    batches = [make_batch(cfg, bi_global, 64, 96, seed=s, raw=True)
               for s in range(nb)]
    # tag every image with a unique global id; device d's shard of batch
    # b holds images [d*bi_local, (d+1)*bi_local)
    for i, b in enumerate(batches):
        tags = np.asarray(b.gt_classes).copy()
        for j in range(bi_global):
            tags[j, :] = i * bi_global + j
        batches[i] = b._replace(gt_classes=jnp.asarray(tags))
    cache = DeviceEpochCache(
        batches, device=NamedSharding(mesh, P(None, axes)))

    def spy(state, batch, key):
        return state, {"tags": batch.gt_classes[:, 0]}

    cstep = jax.jit(jax.shard_map(
        make_cached_step(spy, nb, shuffle=True),
        mesh=mesh,
        in_specs=(P(), P(None, axes), P(), P()),
        out_specs=(P(), P(), P(axes)),  # concat per-device tags
        check_vma=False,
    ))
    bi_local = bi_global // mesh.size
    shard_of = {}  # device -> its staged image ids
    for d in range(mesh.size):
        shard_of[d] = sorted(b * bi_global + d * bi_local + j
                             for b in range(nb) for j in range(bi_local))
    runs = []
    for _run in range(2):  # determinism across identical runs
        s, idx = state, cache.index_handle()
        seen = {d: [] for d in range(mesh.size)}
        for _p in range(nb):
            s, idx, m = cstep(s, cache.data, idx, key)
            tags = np.asarray(m["tags"])  # (bi_global,) device-major
            for d in range(mesh.size):
                seen[d].extend(tags[d * bi_local:(d + 1) * bi_local]
                               .tolist())
        runs.append({d: list(v) for d, v in seen.items()})
        for d in range(mesh.size):
            assert sorted(seen[d]) == shard_of[d], (d, seen[d])
    assert runs[0] == runs[1]  # replicated key keeps devices in lockstep
