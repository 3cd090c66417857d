"""The grouped product's Pallas kernels (``ops/gmm_pallas.py``) against
``lax.ragged_dot`` and its autodiff, the oracle and the off-TPU path of
``ops/moe.py``.  On CPU the kernels run in the interpreter, and only where
a test says ``interpret=True``; that Mosaic compiles them at the cell's
shapes and agrees on the chip is ``chip_smoke.py``'s to check.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import moe
from mx_rcnn_tpu.ops.gmm_pallas import _schedule, grouped_matmul

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the cell's widths over 8: 1856 / 8 = 232 and 2688 / 8 = 336 are no
# multiple of the 128-wide tiles the cases run at, as 1856 is none of 128
K, N, TILING = 336, 232, (128, 128, 128)
CAPACITY = 512

# assignments to each of four held experts; the rows up to CAPACITY are
# padding and join the last group (``held_assignments``)
CASES = {
    "k_and_n_off_the_tile": (128, 128, 128, 128),
    "group_boundaries_inside_a_row_tile": (100, 60, 200, 40),
    "an_expert_with_no_rows": (0, 200, 0, 200),
    "a_last_group_of_padding_rows_only": (150, 150, 100, 0),
    "all_rows_in_one_expert": (0, 0, 400, 0),
}


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _routed(counts, seed):
    """Tokens whose first choice falls on held expert ``e`` ``counts[e]``
    times, in shuffled order, and whose second choice is held by another
    chip."""
    rng = np.random.RandomState(seed)
    first = rng.permutation(np.repeat(np.arange(4), counts))
    idx = jnp.asarray(np.stack([first, np.full_like(first, 9)], 1), jnp.int32)
    weight = jnp.asarray(rng.uniform(0.5, 1.5, idx.shape), jnp.float32)
    return moe.held_assignments(idx, weight, (0, 4), CAPACITY), len(first)


def _vjp(fn, args, seed):
    out, pull = jax.vjp(fn, *args)
    ct = jax.random.normal(jax.random.PRNGKey(seed), out.shape, out.dtype)
    return (out,) + pull(ct)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_forward_and_both_cotangents(case):
    routed, tokens = _routed(CASES[case], seed=len(case))
    assert int(routed.group_sizes.sum()) == CAPACITY
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(tokens, K), jnp.float32)
    w_up = jnp.asarray(rng.randn(4, K, N) * 0.1, jnp.float32)
    w_down = jnp.asarray(rng.randn(4, N, K) * 0.1, jnp.float32)
    rows = jnp.where(routed.valid[:, None], x[routed.token], 0)

    # the product alone, in tiles that neither k nor n is a multiple of
    got = _vjp(lambda l, r: grouped_matmul(l, r, routed.group_sizes, TILING,
                                           True), (rows, w_up), 2)
    want = _vjp(lambda l, r: jax.lax.ragged_dot(
        l, r, routed.group_sizes, preferred_element_type=jnp.float32),
        (rows, w_up), 2)
    for name, g, w in zip(("out", "d_lhs", "d_rhs"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))
    for e, count in enumerate(np.asarray(routed.group_sizes)):
        if count == 0:   # written, and with zeros
            assert not np.asarray(got[2][e]).any()

    # the layer: cotangents of x, w_up and w_down through both products
    got = _vjp(lambda *a: moe.held_experts(a[0], routed, a[1], a[2],
                                           interpret=True),
               (x, w_up, w_down), 3)
    want = _vjp(lambda *a: moe.held_experts(a[0], routed, a[1], a[2]),
                (x, w_up, w_down), 3)
    for name, g, w in zip(("y", "d_x", "d_w_up", "d_w_down"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_bfloat16_rows_accumulate_in_float32():
    """The train step's dtypes: bfloat16 rows, float32 weights cast at the
    call, float32 result and weight gradient."""
    routed, tokens = _routed(CASES["group_boundaries_inside_a_row_tile"], 5)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(tokens, K), jnp.bfloat16)
    w_up = jnp.asarray(rng.randn(4, K, N) * 0.1, jnp.float32)
    w_down = jnp.asarray(rng.randn(4, N, K) * 0.1, jnp.float32)
    got = _vjp(lambda *a: moe.held_experts(a[0], routed, a[1], a[2],
                                           interpret=True),
               (x, w_up, w_down), 3)
    want = _vjp(lambda *a: moe.held_experts(a[0], routed, a[1], a[2]),
                (x, w_up, w_down), 3)
    assert [g.dtype for g in got] == [jnp.float32, jnp.bfloat16,
                                      jnp.float32, jnp.float32]
    for g, w in zip(got, want):
        # the oracle keeps the float32 cotangent whole where the kernel
        # rounds it to the rows' dtype, as the TPU's default precision does
        assert _rel(g, w) < 2e-2, _rel(g, w)


@pytest.mark.parametrize("rows,tm", [(300, 128), (48, 512), (1000, 256)])
def test_rows_that_are_no_multiple_of_the_tile_are_padded(rows, tm):
    rng = np.random.RandomState(rows)
    sizes = rng.multinomial(rows - 17, [0.25] * 4)   # 17 rows past the groups
    lhs = jnp.asarray(rng.randn(rows, 40), jnp.float32)
    rhs = jnp.asarray(rng.randn(4, 40, 24), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = _vjp(lambda l, r: grouped_matmul(l, r, gs, (tm, 128, 128), True),
               (lhs, rhs), 4)
    want = _vjp(lambda l, r: jax.lax.ragged_dot(
        l, r, gs, preferred_element_type=jnp.float32), (lhs, rhs), 4)
    assert not np.asarray(got[0][rows - 17:]).any()   # zeros, as the oracle
    for g, w in zip(got, want):
        assert g.shape == w.shape and _rel(g, w) < 1e-5


@pytest.mark.parametrize("seed", range(4))
def test_schedule_visits_every_tile_and_every_group_within_its_bound(seed):
    rng = np.random.RandomState(seed)
    e, tm, tiles = 8, 128, 12
    m = tm * tiles
    p = rng.dirichlet(np.full(e, 0.3))
    sizes = rng.multinomial(m - (seed % 2) * 300, p)
    sizes[rng.randint(e)] = 0
    if seed == 3:    # empty groups on aligned boundaries, at both ends
        sizes = np.array([0, 0, 256, 0, 1024, 128, 0, 0])
    group, tile, lo, hi = (np.asarray(a) for a in _schedule(
        jnp.asarray(sizes, jnp.int32), m, tm))
    assert len(group) == tiles + e - 1
    assert (np.diff(group) >= 0).all() and (np.diff(tile) >= 0).all()
    assert set(group) == set(range(e)) and set(tile) == set(range(tiles))
    # each row of a group is owned by exactly one visit
    owner = np.zeros(m, int)
    for g, t, a, b in zip(group, tile, lo, hi):
        a, b = max(a, t * tm), min(b, (t + 1) * tm)
        if b > a:
            owner[a:b] += 1
            ends = np.cumsum(sizes)
            assert ends[g] - sizes[g] <= a and b <= ends[g]
    assert (owner[:sizes.sum()] == 1).all() and not owner[sizes.sum():].any()


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_kernels_lower_for_tpu_at_the_cells_shapes(k, n):
    """The compiled (not interpreted) kernels lower for the TPU platform
    at the cell's shapes — 12288 rows, 8 experts, both products — each to
    one Mosaic custom call: the product, the rows' cotangent and the
    weights' (the transposed product, 2688 x 12288 x 1856 an expert).
    Lowering runs on CPU; what libtpu makes of the calls is
    ``chip_smoke.py``'s to check."""
    def loss(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, sizes).sum()

    args = (jax.ShapeDtypeStruct((12288, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((8, k, n), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.int32))
    for fn, calls in ((loss, 1),
                      (jax.value_and_grad(loss, argnums=(0, 1)), 3)):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == calls


def test_off_the_tpu_held_experts_takes_ragged_dot_without_being_told():
    routed, tokens = _routed(CASES["k_and_n_off_the_tile"], 0)
    args = (jnp.zeros((tokens, K), jnp.bfloat16), routed,
            jnp.zeros((4, K, N)), jnp.zeros((4, N, K)))
    assert jax.default_backend() != "tpu"
    text = str(jax.make_jaxpr(moe.held_experts)(*args))
    assert text.count("ragged_dot_general[") == 2
    assert "pallas_call" not in text
    told = str(jax.make_jaxpr(
        lambda *a: moe.held_experts(*a, interpret=True))(*args))
    assert told.count("pallas_call[") == 2 and "ragged_dot" not in told


def test_ragged_dot_is_called_in_one_place_of_the_package():
    calls = [path for path in glob.glob(
        os.path.join(REPO, "mx_rcnn_tpu", "**", "*.py"), recursive=True)
        for line in open(path) if "lax.ragged_dot(" in line]
    assert calls == [os.path.join(REPO, "mx_rcnn_tpu", "ops", "moe.py")]
