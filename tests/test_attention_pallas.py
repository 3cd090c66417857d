"""The attention kernels (``ops/attention_pallas.py``) against the full
masked form and its autodiff, ``causal_gqa``'s choice between them and
the blocked path, and the checkpoints that keep the forward kernel's
``o`` and log-sum-exp (``KEEP_FLASH_RESIDUALS``).  On CPU the kernels run
in the interpreter, and only where a test says ``interpret=True``; that
Mosaic compiles them at the cell's shape and agrees on the chip is the
benchmark's ``correct`` to check.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import attention_pallas
from mx_rcnn_tpu.ops.attention import causal_gqa
from mx_rcnn_tpu.ops.attention_pallas import flash_causal_gqa

D = 128


def _full(q, k, v):
    """Every score formed, the mask added, one softmax a row: float32."""
    r, s = q.shape[2] // k.shape[2], q.shape[1]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    kr, vr = jnp.repeat(k, r, 2), jnp.repeat(v, r, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                    precision="highest") * q.shape[-1] ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vr,
                      precision="highest")


def _operands(seed, s, hq, hkv, dtype=jnp.float32, b=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(key, (b, s, h, D), dtype)
                 for key, h in zip(ks, (hq, hkv, hkv, hq)))


def _vjp(fn, q, k, v, ct):
    out, pull = jax.vjp(fn, q, k, v)
    return (out,) + pull(ct.astype(out.dtype))


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


# (query heads, key-value heads, (block_q, block_k)) at 512 positions:
# four key blocks a row, so rows skip the blocks above the diagonal, mask
# the one on it and take the ones below it whole
CASES = {
    "one_query_head_a_kv_head": (2, 2, (128, 128)),
    "four_query_heads_a_kv_head": (8, 2, (128, 128)),
    "sixteen_query_heads_a_kv_head": (16, 1, (128, 128)),
    "query_blocks_of_two_key_blocks": (4, 2, (256, 128)),
    "key_blocks_of_two_query_blocks": (4, 2, (128, 256)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_the_full_masked_form_and_its_three_cotangents(case):
    hq, hkv, blocks = CASES[case]
    q, k, v, ct = _operands(len(case), 512, hq, hkv)
    got = _vjp(lambda *a: flash_causal_gqa(*a, blocks, True), q, k, v, ct)
    want = _vjp(_full, q, k, v, ct)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_bfloat16_operands_accumulate_in_float32():
    """The train step's dtypes.  Against the full form in float32 on the
    same (rounded) operands, the kernels err by the probabilities' and the
    score cotangents' rounding to bfloat16 for the products and the
    results' own; accumulated in bfloat16, a row of hundreds of keys would
    err by tenths."""
    q, k, v, ct = _operands(7, 512, 8, 2, jnp.bfloat16)
    got = _vjp(lambda *a: flash_causal_gqa(*a, (128, 128), True),
               q, k, v, ct)
    want = _vjp(_full, q, k, v, ct)
    assert [g.dtype for g in got] == [jnp.bfloat16] * 4
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert _rel(g, w) < 2e-2, (name, _rel(g, w))
    # and no further from it than the blocked path is
    blocked = _vjp(lambda *a: causal_gqa(*a, 128), q, k, v, ct)
    for name, g, b, w in zip(("o", "dq", "dk", "dv"), got, blocked, want):
        assert _rel(g, w) < 2 * _rel(b, w) + 1e-3, (name, _rel(g, w),
                                                     _rel(b, w))


def test_causal_gqa_told_to_interpret_runs_the_kernels_at_their_blocks():
    s = max(attention_pallas.BLOCK_Q, attention_pallas.BLOCK_K) * 2
    q, k, v, ct = _operands(3, s, 2, 1, b=1)
    text = str(jax.make_jaxpr(
        lambda *a: causal_gqa(*a, interpret=True))(q, k, v))
    assert text.count("pallas_call[") == 1
    got = _vjp(lambda *a: causal_gqa(*a, interpret=True), q, k, v, ct)
    want = _vjp(_full, q, k, v, ct)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


@pytest.mark.parametrize("s,d", [
    pytest.param(attention_pallas.BLOCK_Q + 128, 128,
                 id="sequence_off_the_block"),
    pytest.param(attention_pallas.BLOCK_Q + 128, 64,
                 id="sequence_off_the_block_and_head_off_the_lanes"),
])
def test_shapes_the_kernels_do_not_tile_take_the_blocked_path(s, d):
    assert not attention_pallas.tiles(s, d)
    args = (jnp.zeros((1, s, 2, d)),) + (jnp.zeros((1, s, 1, d)),) * 2
    text = str(jax.make_jaxpr(
        lambda *a: causal_gqa(*a, 128, interpret=True))(*args))
    assert "pallas_call" not in text and "dot_general" in text
    with pytest.raises(ValueError, match="do not tile"):
        flash_causal_gqa(*args, None, True)
    # the blocked path's own demand stands as it was
    with pytest.raises(ValueError, match="no multiple of block_q"):
        causal_gqa(*(a[:, :s - 7] for a in args), 128, interpret=True)


@pytest.mark.parametrize("d,dv", [
    pytest.param(192, 128, id="latent_attention_192_against_128"),
    pytest.param(64, 64, id="head_size_64"),
])
def test_heads_off_the_lanes_enter_the_kernels_padded(d, dv):
    """Query/key and value widths are rounded up together to the lanes and
    padded with zeros, the queries rescaled so that the kernels' scale is
    the true width's; the kernels themselves take no such head."""
    s = 2 * attention_pallas.BLOCK_Q
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k = (jax.random.normal(key, (1, s, 2, d)) for key in ks[:2])
    v, ct = (jax.random.normal(key, (1, s, 2, dv)) for key in ks[2:])
    assert not attention_pallas.tiles(s, d)
    text = str(jax.make_jaxpr(
        lambda *a: causal_gqa(*a, interpret=True))(q, k, v))
    assert text.count("pallas_call[") == 1
    got = _vjp(lambda *a: causal_gqa(*a, interpret=True), q, k, v, ct)
    want = _vjp(_full, q, k, v, ct)
    assert got[0].shape == (1, s, 2, dv)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and _rel(g, w) < 1e-5, (name, _rel(g, w))
    # off the TPU and not told to interpret: the blocked path, to the same
    blocked = _vjp(lambda *a: causal_gqa(*a, 128), q, k, v, ct)
    for name, g, w in zip(("o", "dq", "dk", "dv"), blocked, want):
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_a_sequence_whose_dk_dv_outgrow_vmem_does_not_tile():
    """The backward keeps a key-value head's whole ``d k, d v`` in VMEM as
    float32: 4 MB at the cell's 8192 positions, too much past 32768."""
    assert attention_pallas.tiles(8192, D) and attention_pallas.tiles(32768, D)
    assert not attention_pallas.tiles(65536, D)
    assert not attention_pallas.tiles(32768, 2 * D)


def test_off_the_tpu_causal_gqa_takes_the_blocked_path_without_being_told():
    s = 2 * attention_pallas.BLOCK_Q
    args = (jnp.zeros((2, s, 32, D), jnp.bfloat16),
            jnp.zeros((2, s, 2, D), jnp.bfloat16),
            jnp.zeros((2, s, 2, D), jnp.bfloat16))
    assert jax.default_backend() != "tpu" and attention_pallas.tiles(s, D)
    text = str(jax.make_jaxpr(causal_gqa)(*args))
    assert "pallas_call" not in text and "dot_general" in text


def test_kernels_lower_for_tpu_at_the_cells_shape():
    """The compiled (not interpreted) kernels lower for the TPU platform at
    the cell's shape — 2 sequences of 8192, 32 query heads over 2, head
    size 128, bfloat16 — to one Mosaic custom call forward and two with
    the cotangents (the backward is one kernel), and nothing in the program has a (queries, keys) pair
    of axes: no block of float32 scores, as the blocked path's (2, 2, 16,
    256, 8192).  Lowering runs on CPU; what libtpu makes of the calls is
    the chip's to say."""
    def loss(q, k, v):
        return flash_causal_gqa(q, k, v).astype(jnp.float32).sum()

    args = (jax.ShapeDtypeStruct((2, 8192, 32, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 8192, 2, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 8192, 2, D), jnp.bfloat16))
    for fn, calls in ((loss, 1),
                      (jax.value_and_grad(loss, argnums=(0, 1, 2)), 2)):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == calls
        outside = "\n".join(line for line in text.splitlines()
                            if "tpu_custom_call" not in line)
        shapes = set(re.findall(r"tensor<([0-9x]+)xf32>", outside))
        assert shapes, "no float32 tensor found: the pattern is stale"
        for shape in shapes:
            dims = [int(n) for n in shape.split("x")]
            assert dims.count(8192) < 2, shape
            assert not (8192 in dims and 256 in dims), shape


def _mapped_loss(policy, interpret=False):
    """A loss over ``causal_gqa`` as the latent mixer runs it: one sequence
    at a time under ``lax.map``, each a ``jax.checkpoint``."""
    def one(row):
        q, k, v = (a[None] for a in row)
        return causal_gqa(q, k, v, interpret=interpret)[0]

    def loss(q, k, v):
        out = jax.lax.map(jax.checkpoint(one, policy=policy), (q, k, v))
        return (out.astype(jnp.float32) ** 2).sum()

    return loss


@pytest.mark.parametrize("policy,calls", [
    pytest.param(attention_pallas.KEEP_FLASH_RESIDUALS, 2,
                 id="keep_flash_residuals"),
    pytest.param(None, 3, id="default_policy"),
])
def test_a_checkpoint_under_the_policy_runs_the_forward_kernel_once(
        monkeypatch, policy, calls):
    """At the latent mixer's shape — two sequences of 8192 one at a time,
    32 heads, 192-wide queries and keys, 128-wide values, bfloat16 — the
    gradient lowers for the TPU to the forward kernel and the backward
    kernel where the checkpoint keeps ``o`` and the log-sum-exp, and to a
    second forward kernel in the backward where it keeps nothing."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = (jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16))
    fn = jax.value_and_grad(_mapped_loss(policy), argnums=(0, 1, 2))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == calls


def test_the_kept_residuals_give_the_recomputed_gradients_to_the_bit(
        monkeypatch):
    """In the interpreter, at blocks of 128 over 256 positions: the saved
    ``o`` and log-sum-exp are what the second forward call recomputes."""
    monkeypatch.setattr(attention_pallas, "BLOCK_Q", 128)
    monkeypatch.setattr(attention_pallas, "BLOCK_K", 128)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k = (jax.random.normal(key, (2, 256, 2, 192), jnp.bfloat16)
            for key in ks[:2])
    v = jax.random.normal(ks[2], (2, 256, 2, 128), jnp.bfloat16)
    grads = []
    for policy, calls in ((attention_pallas.KEEP_FLASH_RESIDUALS, 2),
                          (None, 3)):
        fn = jax.value_and_grad(_mapped_loss(policy, interpret=True),
                                argnums=(0, 1, 2))
        assert str(jax.make_jaxpr(fn)(q, k, v)).count("pallas_call[") == calls
        grads.append(jax.tree_util.tree_leaves(jax.jit(fn)(q, k, v)))
    for kept, again in zip(*grads):
        np.testing.assert_array_equal(np.asarray(kept, np.float32),
                                      np.asarray(again, np.float32))


def _remats(jaxpr):
    """Every checkpoint equation of a jaxpr, inside others too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _remats(sub)


# the checkpoints that wrap an attention call in each family's tiny stack:
# nemotron's every block (its attention block among them), the latent
# mixers of ling's pattern, and joyai's with its module's
WRAPPED = {
    "nemotron_h": lambda n: len(n.layer_pattern),
    "ling_flash": lambda n: n.layer_pattern.count("L"),
    "joyai_flash": lambda n: n.layer_pattern.count("L") + 1,
}


@pytest.mark.parametrize("family", sorted(WRAPPED))
def test_the_checkpoints_around_attention_keep_the_flash_residuals(
        monkeypatch, family):
    """The family's grad names ``KEEP_FLASH_RESIDUALS`` on exactly the
    checkpoints around its attention calls; on the CPU, where attention
    takes the blocked path and no residual is named, the gradient is the
    one without the policy, to the bit."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import build_model, ling_flash, nemotron_h

    cfg = generate_config(f"{family}_tiny", "synthetic_tokens")
    model = build_model(cfg)
    params, _ = model.init_variables(jax.random.PRNGKey(0))
    ids = np.random.RandomState(1).randint(0, 200, (2, 64)).astype(np.int32)

    def named_and_grads():
        """How often the grad names the policy, and the grad (a new
        function each time: ``jit`` would reuse its trace)."""
        grad = jax.grad(lambda p: model.apply({"params": p}, ids)[0])
        policies = [e.params["policy"]
                    for e in _remats(jax.make_jaxpr(grad)(params).jaxpr)]
        return (policies.count(attention_pallas.KEEP_FLASH_RESIDUALS),
                jax.tree_util.tree_leaves(jax.jit(grad)(params)))

    named, grads = named_and_grads()
    assert named == WRAPPED[family](cfg.network)
    for module in (ling_flash, nemotron_h):
        monkeypatch.setattr(module, "KEEP_FLASH_RESIDUALS", None)
    named, again = named_and_grads()
    assert named == 0
    for a, b in zip(grads, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
