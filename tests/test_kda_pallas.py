"""The delta rule's triangular inverse as a Mosaic kernel
(``ops/kda_pallas.py``), run in the Pallas interpreter on the CPU against
the ``jnp`` form it replaces on a TPU (``ops/kda.py::inv_unit_lower_jnp``:
the tests' oracle), its hand-written backward against autodiff of that
form, the choice between the two, and the lowering for the TPU platform at
the shape of one part of the train step.

Tolerances are relative to the largest entry compared: both forms are
float32 throughout and differ in the order of their sums (substitution row
by row against products of blocks), 1e-5 at ``n = 64`` where entries span
four orders of magnitude.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import kda, kda_pallas


def _unit_lower(seed, b, n, scale=0.5):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(seed), (b, n, n)), -1)
    return jnp.eye(n) + scale * a


def _close(got, want, rel):
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * float(jnp.abs(want).max()))


@pytest.mark.parametrize("b", [3, 128, 300])
@pytest.mark.parametrize("n", [8, 16, 64])
def test_interpreted_kernel_is_the_jnp_inverse(n, b):
    """Batches that fill no lane tile, one, and two and a part: the padding
    matrices are cut off again."""
    m = _unit_lower(n + b, b, n)
    got = kda_pallas.inv_unit_lower(m, True)
    assert got.shape == m.shape and got.dtype == jnp.float32
    _close(got, kda.inv_unit_lower_jnp(m), 1e-5)
    # above the diagonal exact zeros, on it exact ones
    assert not np.triu(np.asarray(got), 1).any()
    assert (np.diagonal(np.asarray(got), axis1=1, axis2=2) == 1).all()


def test_leading_dimensions_are_the_batch():
    m = _unit_lower(1, 2 * 3 * 5, 16).reshape(2, 3, 5, 16, 16)
    _close(kda_pallas.inv_unit_lower(m, True), kda.inv_unit_lower_jnp(m),
           1e-5)


@pytest.mark.parametrize("n,b", [(8, 3), (16, 128), (64, 130)])
def test_backward_is_autodiff_of_the_jnp_form(n, b):
    """``dM = -tril(T^T dT T^T, -1)`` against ``jax.vjp`` through the
    product chain, both as functions of the part below the diagonal (the
    only part either may read); a cotangent with entries everywhere."""
    a = _unit_lower(n, b, n) - jnp.eye(n)
    cot = jax.random.normal(jax.random.PRNGKey(7), a.shape)

    def through(inverse):
        out, back = jax.vjp(
            lambda x: inverse(jnp.eye(n) + jnp.tril(x, -1)), a)
        return out, back(cot)[0]

    (t, got), (t_want, want) = (
        through(lambda m: kda_pallas.inv_unit_lower(m, True)),
        through(kda.inv_unit_lower_jnp))
    _close(t, t_want, 1e-5)
    _close(got, want, 1e-5)
    assert not np.triu(np.asarray(got)).any()


def test_only_the_part_below_the_diagonal_is_read():
    m = _unit_lower(3, 5, 16)
    junk = m + jnp.triu(jax.random.normal(jax.random.PRNGKey(4), m.shape))
    np.testing.assert_array_equal(kda_pallas.inv_unit_lower(junk, True),
                                  kda_pallas.inv_unit_lower(m, True))


@pytest.mark.parametrize("shape,dtype", [
    ((3, 12, 12), jnp.float32), ((3, 4, 4), jnp.float32),
    ((3, 16, 8), jnp.float32), ((3, 16, 16), jnp.bfloat16)],
    ids=["n12", "n4", "not_square", "bfloat16"])
def test_kernel_refuses_what_it_does_not_take(shape, dtype):
    with pytest.raises(ValueError, match="multiple of 8"):
        kda_pallas.inv_unit_lower(jnp.zeros(shape, dtype), True)


@pytest.mark.parametrize("n", [1, 2, 5, 12])
def test_the_choice_keeps_the_jnp_form_for_sizes_the_kernel_does_not_take(n):
    """Even when told to interpret: an ``n`` that is no multiple of 8."""
    m = _unit_lower(n, 3, n)
    assert not kda_pallas.takes(n)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda x: kda.inv_unit_lower(x, True))(m))
    np.testing.assert_array_equal(kda.inv_unit_lower(m, True),
                                  kda.inv_unit_lower_jnp(m))


def test_off_the_tpu_the_rule_takes_the_jnp_form_without_being_told():
    args = (jnp.zeros((1, 128, 2, 8)),) * 4 + (jnp.zeros((1, 128, 2)),)
    assert jax.default_backend() != "tpu" and kda_pallas.takes(64)
    text = str(jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, 64))(*args))
    assert "pallas_call" not in text and "dot_general" in text
    told = str(jax.make_jaxpr(
        lambda *a: kda.kda_chunked(*a, 64, interpret=True))(*args))
    assert "pallas_call" in told


def _kernels(text):
    """The Mosaic calls of a lowered program, by kernel name."""
    names = [re.findall(r'kernel_name = "([^"]*)"', call)
             for call in re.findall(r"stablehlo.custom_call @tpu_custom_call.*",
                                    text)]
    assert all(len(n) == 1 for n in names)
    return sorted(n[0] for n in names)


def test_the_rule_lowers_for_tpu_at_a_parts_shape(monkeypatch):
    """One part of the cell's train step — one sequence of 8192 positions,
    16 of the 32 heads, 128 wide, bfloat16: 2048 matrices of 64 x 64 — lowers
    for the TPU platform with the scores as one Mosaic call and the inverse
    as another, and with the cotangents adds the scores' backward kernel
    alone (the inverse's backward is two products, not a kernel, and no
    forward is run again).  The rule reads the platform at trace time, which
    here is the CPU: untold, it lowers with no Mosaic call at all; told, the
    test answers for the TPU.  Lowering runs on CPU; what libtpu makes of
    the calls is the chip's to say."""
    s, h, d = 8192, 16, 128

    def loss(q, k, v, g, beta):
        return kda.kda_chunked(q, k, v, g, beta, 64)[0].astype(
            jnp.float32).sum()

    half = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16)
    args = (half, half, half, jax.ShapeDtypeStruct((1, s, h, d), jnp.float32),
            jax.ShapeDtypeStruct((1, s, h), jnp.float32))
    fns = (loss, jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))

    def lowered(fn):
        # a new function each time: a trace cached before the platform
        # changed would be found again
        return jax.jit(lambda *a: fn(*a)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    for fn in fns:
        assert "tpu_custom_call" not in lowered(fn)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    forward = ["kda_inv_unit_lower", "kda_scores_fwd"]
    for fn, want in zip(fns, (forward, forward + ["kda_scores_bwd"])):
        text = lowered(fn)
        assert _kernels(text) == sorted(want)
        assert f"tensor<64x64x{s // 64 * h}xf32>" in text
        assert (f"-> (tensor<{s // 64 * h}x64x64xbf16>, "
                f"tensor<{s // 64 * h}x64x64xf32>)") in text


def test_backward_names_its_ops_for_the_solves_scope():
    """The transpose is traced outside the forward's scopes: the products
    carry the name themselves (the readers of scope ``kda_solve`` find them
    by it)."""
    m = _unit_lower(2, 3, 8)
    text = jax.jit(jax.grad(
        lambda x: kda_pallas.inv_unit_lower(x, True).sum())).lower(
            m).as_text(debug_info=True)
    assert text.count("stablehlo.dot_general") == 2
    named = re.findall(r'loc\("([^"]*/dot_general)"', text)
    assert named and all("kda_solve" in path.split("/")[-2] for path in named)
