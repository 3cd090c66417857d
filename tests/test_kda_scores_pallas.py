"""The delta rule's two score matrices as a Mosaic kernel pair
(``ops/kda_pallas.py::scores``), run in the Pallas interpreter on the CPU:
the forward against the ``jnp`` form it replaces on a TPU
(``ops/kda.py::_scores_jnp``), the hand-written backward against autodiff of
that form and of the scores' definition in float64, the rule through the
kernels against the recurrence one position at a time, the choice between
the two forms, and the scope the backward's ops carry.

Tolerances: in float32 both forms are the same float32 products summed in
another order (1e-5 of the largest entry).  In bfloat16 the forward's
operands are rounded the same way in both, so ``A_qk`` and ``A_kk`` agree to
bfloat16 rounding; the gradients are held to the float64 gradient of the
definition, where the kernels' ``dG`` keeps the diagonal pair's terms, which
cancel, from the same rounded operands on both sides (autodiff of the
``jnp`` form errs 5.5e-2 of ``dG``'s scale at the gate's bound there).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import kda, kda_pallas

from benchmark.reference import ling_flash as ref


def _unit(t):
    return t / jnp.linalg.norm(t, axis=-1, keepdims=True)


def _chunks(seed, decay, chunk=64, n=3, dk=128, dtype=jnp.bfloat16):
    """``n`` chunk-heads of ``q`` (scaled unit rows), ``k`` (unit rows) and
    the per-position log-decay ``g`` float32: ``"bound"`` the gate's lower
    bound at every position (-5: a sub-chunk's factors reach e^+-40),
    ``"zero"`` no decay, ``"spread"`` over (-5, 0)."""
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = (_unit(jax.random.normal(r[0], (n, chunk, dk))) * dk ** -0.5)
    k = _unit(jax.random.normal(r[1], (n, chunk, dk)))
    g = {"bound": jnp.full((n, chunk, dk), -5.0),
         "zero": jnp.zeros((n, chunk, dk)),
         "spread": -5.0 * jax.random.uniform(r[2], (n, chunk, dk)) ** 3}[decay]
    return q.astype(dtype), k.astype(dtype), g.astype(jnp.float32)


def _by_kernel(q, k, g, sub=16):
    """(A_qk, A_kk) of the running sums of ``g``, by the interpreted kernels."""
    return kda_pallas.scores(q, k, jnp.cumsum(g, axis=1), sub, True)


def _by_jnp(q, k, g, sub=16):
    """The same by ``kda._scores_jnp``, in the cut layout it takes, A_qk
    rounded to the operands' dtype as the rule does."""
    n, chunk, dk = q.shape
    cut = lambda t: t.reshape(1, n, 1, chunk // sub, sub, dk)  # noqa: E731
    gs = jnp.cumsum(cut(g), axis=4)
    total = gs[..., -1, :]
    a_qk, a_kk = kda._scores_jnp(cut(q), cut(k), gs,
                                 jnp.cumsum(total, axis=3) - total, sub)
    square = (n, chunk, chunk)
    return a_qk.reshape(square).astype(q.dtype), a_kk.reshape(square)


def _by_definition(q, k, g):
    """The sums ``q_i k_j exp(G_i - G_j)`` written out, in float64."""
    q, k = q.astype(jnp.float64), k.astype(jnp.float64)
    big = jnp.cumsum(g.astype(jnp.float64), axis=1)
    decay = jnp.exp(big[:, :, None, :] - big[:, None, :, :])
    pos = jnp.arange(q.shape[1])

    def masked(rows, keep):
        return jnp.einsum("nid,njd,nijd->nij", rows, k,
                          jnp.where(keep[..., None], decay, 0.0))

    return (masked(q, pos[:, None] >= pos[None, :]),
            masked(k, pos[:, None] > pos[None, :]))


def _close(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * np.abs(want).max())


def _projection(q, seed=3):
    """Random cotangents of both matrices, float32."""
    n, chunk, _ = q.shape
    r = jax.random.split(jax.random.PRNGKey(seed))
    return tuple(jax.random.normal(key, (n, chunk, chunk), jnp.float32)
                 for key in r)


def _grads(form, q, k, g, cot):
    """Gradients of the projection ``cot`` of both matrices for q, k and the
    per-position ``g`` (through the running sums)."""
    def loss(q, k, g):
        return sum(jnp.sum(a.astype(c.dtype) * c)
                   for a, c in zip(form(q, k, g), cot))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, g)


@pytest.mark.parametrize("chunk", [64, 32])
@pytest.mark.parametrize("decay", ["bound", "zero", "spread"])
def test_interpreted_scores_are_the_jnp_form(chunk, decay):
    """To bfloat16 rounding of ``A_qk``, whose entries the rule rounds; the
    masks exact: nothing above the diagonal, and ``A_kk`` nothing on it."""
    q, k, g = _chunks(chunk + len(decay), decay, chunk)
    (a_qk, a_kk), (w_qk, w_kk) = _by_kernel(q, k, g), _by_jnp(q, k, g)
    assert a_qk.dtype == jnp.bfloat16 and a_kk.dtype == jnp.float32
    assert a_qk.shape == a_kk.shape == (3, chunk, chunk)
    _close(a_qk, w_qk, 2 ** -8)
    _close(a_kk, w_kk, 2 ** -8)
    assert not np.triu(np.asarray(a_qk, np.float32), 1).any()
    assert not np.triu(np.asarray(a_kk)).any()


@pytest.mark.parametrize("chunk", [64, 32])
@pytest.mark.parametrize("decay", ["bound", "spread"])
def test_scores_backward_is_autodiff_of_the_jnp_form(chunk, decay):
    """Float32 operands, where both are the same products: ``dq``, ``dk``
    and ``dg`` through the running sums."""
    q, k, g = _chunks(7, decay, chunk, dtype=jnp.float32)
    cot = _projection(q)
    got, want = (_grads(_by_kernel, q, k, g, cot),
                 _grads(_by_jnp, q, k, g, cot))
    for name, a, b in zip("qkg", got, want):
        assert a.dtype == b.dtype, name
        _close(a, b, 1e-5)


@pytest.mark.parametrize("decay", ["bound", "zero", "spread"])
def test_scores_backward_in_bfloat16_is_the_definitions_gradient(decay):
    """bfloat16 operands, as on the chip, against the float64 gradient of
    the sums written out (module docstring)."""
    q, k, g = _chunks(11, decay)
    cot = _projection(q)
    got = _grads(_by_kernel, q, k, g, cot)
    jnp_form = _grads(_by_jnp, q, k, g, cot)
    with jax.enable_x64(True):
        want = _grads(_by_definition, q, k, g,
                      tuple(c.astype(jnp.float64) for c in cot))
    for name, a, b, c in zip("qkg", got, want, jnp_form):
        assert a.dtype == (g.dtype if name == "g" else q.dtype), name
        _close(a, b, 1.5e-2)
        # and never further from it than autodiff of the jnp form, beyond
        # a bfloat16 rounding of the scale
        scale = float(np.abs(np.asarray(b, np.float64)).max())
        err = lambda x: float(np.abs(np.asarray(x, np.float64)  # noqa: E731
                                     - np.asarray(b, np.float64)).max())
        assert err(a) <= err(c) + 2 ** -8 * scale, name


def _rule_inputs(seed, s=128, h=2, dk=128, dv=8, dtype=jnp.float32):
    r = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (_unit(jax.random.normal(r[0], (2, s, h, dk))).astype(dtype)
            * dk ** -0.5,
            _unit(jax.random.normal(r[1], (2, s, h, dk))).astype(dtype),
            jax.random.normal(r[2], (2, s, h, dv)).astype(dtype),
            -5.0 * jax.random.uniform(r[3], (2, s, h, dk)) ** 3,
            jax.nn.sigmoid(jax.random.normal(r[4], (2, s, h))))


def _recurrence(q, k, v, g, beta):
    return jax.vmap(lambda *a: ref.delta_rule(*a, 16))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        g, beta)


@pytest.mark.parametrize("dtype,fwd,bwd", [
    (jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 5e-2, 5e-2)],
    ids=["float32", "bfloat16"])
def test_the_rule_through_the_kernels_is_the_recurrence(dtype, fwd, bwd):
    """Keys 128 wide, which the scores kernel takes: the rule with both
    kernels interpreted against ``reference/ling_flash.py``'s rule one
    position at a time, output and the five gradients (tolerances as in
    ``test_ling_flash.py``'s rule tests)."""
    args = _rule_inputs(0, dtype=dtype)
    rule = functools.partial(kda.kda_chunked, chunk=64, interpret=True)
    text = str(jax.make_jaxpr(lambda *a: rule(*a)[0])(*args))
    assert "kda_scores_fwd" in text and "kda_inv_unit_lower" in text
    got = jax.jit(rule)(*args)[0]
    want = jax.jit(_recurrence)(*args)
    _close(got, want, fwd)
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a)[0].astype(jnp.float32) * cot),
        argnums=(0, 1, 2, 3, 4)))(*args)
    wants = jax.jit(jax.grad(lambda *a: jnp.sum(_recurrence(*a) * cot),
                             argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("qkvgb", grads, wants):
        assert a.dtype == args["qkvgb".index(name)].dtype, name
        _close(a, b, bwd)


def test_the_choice_takes_the_scores_kernel_only_where_it_fits():
    """Keys 128 wide and sub-chunks of 16 take it when told to interpret;
    keys 8 wide keep the ``jnp`` scores (and still take the inverse's
    kernel); off the TPU, untold, neither."""
    def jaxpr(dk, told, chunk=64):
        args = (jnp.zeros((1, 128, 2, dk)),) * 2 + (
            jnp.zeros((1, 128, 2, 8)), jnp.zeros((1, 128, 2, dk)),
            jnp.zeros((1, 128, 2)))
        return str(jax.make_jaxpr(lambda *a: kda.kda_chunked(
            *a, chunk, interpret=told))(*args))

    assert kda_pallas.scores_take(64, 16, 128, jnp.bfloat16)
    assert "kda_scores_fwd" in jaxpr(128, True)
    assert "kda_scores_fwd" in jaxpr(128, True, chunk=32)
    narrow = jaxpr(8, True)
    assert "kda_scores_fwd" not in narrow and "kda_inv_unit_lower" in narrow
    assert "pallas_call" not in jaxpr(128, False)


@pytest.mark.parametrize("chunk,sub,dk,dtype", [
    (64, 8, 128, jnp.bfloat16), (64, 16, 64, jnp.bfloat16),
    (256, 16, 128, jnp.bfloat16), (64, 16, 128, jnp.float16)],
    ids=["sub8", "keys64", "chunk256", "float16"])
def test_scores_kernel_refuses_what_it_does_not_take(chunk, sub, dk, dtype):
    assert not kda_pallas.scores_take(chunk, sub, dk, dtype)
    z = jnp.zeros((2, chunk, dk), dtype)
    with pytest.raises(ValueError, match="not what the scores kernel takes"):
        kda_pallas.scores(z, z, jnp.zeros((2, chunk, dk)), sub, True)


def test_scores_backward_names_its_ops_for_the_scores_scope():
    """The transpose is traced outside the forward's scopes: the backward
    kernel's call carries the name itself (the readers of scope
    ``kda_scores`` find it by it), lowered for the TPU platform as the
    train step is."""
    q, k, g = _chunks(2, "spread", 64, n=2)

    def loss(q, k, g):
        a_qk, a_kk = kda_pallas.scores(q, k, jnp.cumsum(g, axis=1), 16)
        return a_qk.astype(jnp.float32).sum() + a_kk.sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, g).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    # the forward's results are not needed: its inputs are the residuals
    assert text.count("tpu_custom_call") == 1 and "kda_scores_bwd" in text
    paths = re.findall(r'loc\("([^"]*_scores_bwd_call[^"]*)"', text)
    assert paths and all("transpose(jvp(kda_scores))/" in p for p in paths)
