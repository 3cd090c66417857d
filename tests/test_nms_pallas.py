"""Pallas NMS kernel vs the jnp suppression sweep (the oracle).

The kernel must reproduce sequential greedy NMS decision-for-decision; on
CPU it runs only when the caller says interpret=True (correctness only —
compiled parity at K=6144/12032 is checked on the chip by chip_smoke.py).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.nms import nms, nms_mask


def _rand(rng, k):
    xy = rng.uniform(0, 200, (k, 2)).astype(np.float32)
    wh = rng.uniform(5, 80, (k, 2)).astype(np.float32)
    boxes = np.hstack([xy, xy + wh])
    scores = rng.uniform(size=k).astype(np.float32)
    return jnp.asarray(boxes), jnp.asarray(scores)


@pytest.mark.parametrize("k,tile", [(256, 128), (512, 128), (384, 128)])
def test_pallas_matches_jnp_nms_mask(k, tile):
    rng = np.random.RandomState(k)
    boxes, scores = _rand(rng, k)
    want = nms_mask(boxes, scores, 0.5, tile_size=tile, backend="jnp")
    got = nms_mask(boxes, scores, 0.5, tile_size=tile, backend="pallas",
                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_matches_jnp_nms_indices():
    rng = np.random.RandomState(7)
    boxes, scores = _rand(rng, 512)
    valid = jnp.asarray(rng.uniform(size=512) > 0.1)
    want_i, want_v = nms(boxes, scores, 0.7, 100, valid=valid,
                         tile_size=128, backend="jnp")
    got_i, got_v = nms(boxes, scores, 0.7, 100, valid=valid,
                       tile_size=128, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_pallas_dense_cluster():
    """Heavy-overlap chains exercise the within-tile fixed point across
    tile boundaries."""
    rng = np.random.RandomState(3)
    base = rng.uniform(0, 40, (16, 2))
    boxes = []
    for bx, by in base:
        for _ in range(16):
            j = rng.uniform(-3, 3, 2)
            boxes.append([bx + j[0], by + j[1], bx + 30 + j[0], by + 30 + j[1]])
    boxes = jnp.asarray(np.asarray(boxes, np.float32))
    scores = jnp.asarray(rng.uniform(size=len(boxes)).astype(np.float32))
    want = nms_mask(boxes, scores, 0.5, tile_size=128, backend="jnp")
    got = nms_mask(boxes, scores, 0.5, tile_size=128, backend="pallas",
                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _sweeps(boxes, alive, thr=0.5, tile=128):
    """(kernel, oracle) full keep masks over already score-sorted boxes."""
    from mx_rcnn_tpu.ops.nms import _suppression_sweep
    from mx_rcnn_tpu.ops.nms_pallas import suppression_sweep_pallas

    boxes, alive = jnp.asarray(boxes), jnp.asarray(alive)
    got = suppression_sweep_pallas(boxes, alive, thr, tile, interpret=True)
    want = _suppression_sweep(boxes, alive, thr, tile)
    return np.asarray(got), np.asarray(want)


def _disjoint(k):
    """k sorted boxes that overlap nothing: 10x10 on a 20-pixel grid."""
    gx, gy = np.arange(k) % 64, np.arange(k) // 64
    x1, y1 = 20.0 * gx, 20.0 * gy
    return np.stack([x1, y1, x1 + 9, y1 + 9], axis=1).astype(np.float32)


# what the (T, K) slab could not get wrong and the loops over column chunks
# can: (suppressor, victim) ranks at the ends of the loops' range and at the
# seams between chunks of every width (1024 halving down to the tile)
@pytest.mark.parametrize("k,pairs", [
    (1024, [(5, 1000)]),                    # tile 0 suppresses the last tile
    (1024, [(127, 1023), (128, 1022)]),     # last column of a chunk, first
    (640, [(255, 600), (256, 601)]),        # of the next, at several seams
    (640, [(511, 639), (512, 638)]),
    (384, [(0, 383), (127, 256), (255, 257)]),
    (256, [(127, 128), (0, 255)]),          # two tiles: one chunk, no more
    (128, [(0, 127), (3, 4)]),              # one tile: no loop runs at all
    (2304, [(1023, 2200), (1024, 2201), (2047, 2303), (2048, 2302),
            (2175, 2176), (1100, 1290)]),   # the 1024-wide chunks' seams
])
def test_pallas_sweep_suppressor_at_chunk_seams(k, pairs):
    boxes = _disjoint(k)
    for s, v in pairs:
        boxes[v] = boxes[s] + np.float32(1.0)   # IoU 0.68 with s alone
    got, want = _sweeps(boxes, np.ones(k, bool))
    np.testing.assert_array_equal(got, want)
    assert not want[[v for _, v in pairs]].any()
    assert want.sum() == k - len(pairs)


def test_pallas_sweep_dead_suppressor_across_tiles():
    """A box suppressed in tile 0 suppresses nothing later: the chunk's
    keep values, not its overlaps alone, decide."""
    k = 512
    boxes = _disjoint(k)
    boxes[100] = boxes[3] + np.float32(1.0)     # 3 kills 100 (IoU 0.68)
    boxes[400] = boxes[100] + np.float32(1.0)   # 100 overlaps 400; 3 not
    got, want = _sweeps(boxes, np.ones(k, bool))
    np.testing.assert_array_equal(got, want)
    assert not want[100] and want[400]


@pytest.mark.parametrize("k", [128, 256, 384, 640, 1152, 2432])
def test_pallas_sweep_matches_jnp_random(k):
    """K of one and two tiles, and multiples of the tile that are not
    multiples of 256, 512 or 1024 (at 2432 tile 15 starts at 1920 = 1024 +
    512 + 256 + 128: every loop width runs)."""
    rng = np.random.RandomState(100 + k)
    boxes, _ = _rand(rng, k)
    alive = rng.uniform(size=k) > 0.05
    got, want = _sweeps(np.asarray(boxes), alive)
    np.testing.assert_array_equal(got, want)
    assert 0 < want.sum() < alive.sum()


@pytest.mark.parametrize("tile_i", [1, 2, 4])
def test_pallas_sweep_is_causal(tile_i):
    """Boxes at or after tile i never reach keep[: i*T]."""
    k, t = 640, 128
    rng = np.random.RandomState(tile_i)
    boxes = np.asarray(_rand(rng, k)[0])
    alive = np.ones(k, bool)
    other = boxes.copy()
    other[tile_i * t:] = np.asarray(_rand(rng, k)[0])[tile_i * t:]
    other_alive = alive.copy()
    other_alive[tile_i * t + 7:] = False
    base, want = _sweeps(boxes, alive)
    np.testing.assert_array_equal(base, want)
    for b, a in ((other, alive), (boxes, other_alive)):
        got, want = _sweeps(b, a)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got[:tile_i * t], base[:tile_i * t])
        assert not np.array_equal(got, base)    # the change did reach later


def test_resolve_backend_guards(monkeypatch):
    """On a TPU 'auto' is the kernel, and an input the kernel cannot take
    is an error, never a quiet jnp; off-TPU 'auto' is the jnp sweep."""
    import importlib

    nms_mod = importlib.import_module("mx_rcnn_tpu.ops.nms")
    monkeypatch.setattr(nms_mod.jax, "default_backend", lambda: "tpu")
    r = nms_mod._resolve_backend
    assert r(None, 6144, 256) == "pallas"       # the 6000-box recipe
    assert r(None, 12032, 256) == "pallas"      # the 12000-box recipe
    assert r(None, 512, 128) == "pallas"
    assert r(None, 200, 200) == "jnp"           # smaller than one tile
    with pytest.raises(ValueError, match="cannot run in the Pallas"):
        r(None, 500, 100)                       # tile not lane-aligned
    with pytest.raises(ValueError, match="cannot run in the Pallas"):
        r(None, 40192, 256)                     # past the VMEM bound
    assert r("jnp", 12032, 256) == "jnp"        # explicit choice wins
    assert r("pallas", 500, 100) == "pallas"    # explicit choice wins
    monkeypatch.setattr(nms_mod.jax, "default_backend", lambda: "cpu")
    assert r(None, 12032, 256) == "jnp"         # no TPU -> jnp


def test_pallas_off_tpu_needs_explicit_interpret():
    """backend='pallas' on a CPU host raises unless the caller asked for
    the interpreter — it is never inferred from the platform."""
    boxes, scores = _rand(np.random.RandomState(0), 256)
    with pytest.raises(ValueError, match="interpret mode"):
        nms_mask(boxes, scores, 0.5, tile_size=128, backend="pallas")


@pytest.mark.parametrize("k", [6144, 12032])
def test_sweep_kernel_lowers_for_tpu_at_recipe_shapes(k):
    """The compiled (not interpreted) kernel lowers for the TPU platform
    at both recipe shapes, plain and under the train step's vmap, to
    exactly one Mosaic custom call.  Lowering runs on CPU; what libtpu
    does with the call is chip_smoke.py's to check."""
    import jax

    from mx_rcnn_tpu.ops.nms_pallas import suppression_sweep_pallas

    def sweep(b, a):
        return suppression_sweep_pallas(b, a, 0.7, 128, interpret=False)

    for fn, lead in ((sweep, ()), (jax.vmap(sweep), (2,))):
        text = jax.jit(fn).trace(
            jax.ShapeDtypeStruct(lead + (k, 4), jnp.float32),
            jax.ShapeDtypeStruct(lead + (k,), jnp.bool_),
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1
