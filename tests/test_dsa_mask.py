"""The sparse-attention kernels' packed choice
(``ops/sparse_attention_pallas.py``): the mask ``dsa_attend_fwd`` writes is
the choice, key for key, on every causal tile of the pair list; the
backward that reads it gives the numbers, to the bit, of a backward that
forms the choice again from the indexer's operands; and at the cell's shape
the backward's call takes the int32 mask and none of the indexer's
operands.  Pallas interpreter on the CPU at 512 positions, index scores on a
grid of halves (exact in any order of summation, ties many)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops import sparse_attention as sa
from mx_rcnn_tpu.ops import sparse_attention_pallas as sp
from mx_rcnn_tpu.ops.attention_pallas import _pairs

from tests.test_keye_vl2 import _operands

S, TOP_K, HKV = 512, 96, 2


def _forward(blocks, exact=True):
    """The choice and the forward kernel at ``blocks``: (operands, theta,
    cut, the kernel's (o, lse, attended, mask), the padded indexer's
    operands)."""
    bq, bk = blocks
    args = _operands(S, hq=4, hkv=HKV, d=128, heads=4, di=16, exact=exact)
    q, k, v, qi, w, ki = args
    heads, di = qi.shape[1:]
    qi_p = sp._padded(qi).reshape(S, heads * 128)
    ki_p, w_t = sp._padded(ki), w.T
    theta, cut, _, _ = sp.select(qi_p, ki_p, w_t, top_k=TOP_K, heads=heads,
                                 scale=di ** -0.5, bk=bk, interpret=True)
    out = sp.attend_fwd(q.reshape(S, -1), k.reshape(S, -1), v.reshape(S, -1),
                        qi_p, ki_p, w_t, theta, cut, hkv=HKV, heads=heads,
                        scale=di ** -0.5, bq=bq, bk=bk, interpret=True)
    return args, theta, cut, out, (qi_p, ki_p, w_t)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 512)],
                         ids=["128x128", "128x256", "256x512"])
def test_the_mask_is_the_choice(blocks):
    """Unpacked tile by tile along the pair list, the forward's mask is
    ``chosen_mask`` of the blocked path's scores under the choice's theta
    and cut on every causal tile, key for key (no key after its query);
    its column sums over a query's tiles are the keys the query attended
    to."""
    bq, bk = blocks
    (q, k, v, qi, w, ki), theta, cut, out, _ = _forward(blocks)
    attended, mask = out[2], out[3]
    qb, kb = _pairs(S, bq, bk)
    assert mask.shape == (qb.shape[0], bk // 32, bq)
    assert mask.dtype == jnp.int32
    got = np.zeros((S, S), bool)                            # (query, key)
    for t, (i, j) in enumerate(zip(np.asarray(qb), np.asarray(kb))):
        tile = np.asarray(sp._unpack(mask[t]))              # (bk, bq)
        got[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk] = tile.T
    pos = jnp.arange(S)
    want = sa.chosen_mask(sa.indexer_scores(qi, w, ki), theta[0],
                          cut[0].astype(jnp.int32), pos, pos)
    assert not np.triu(got, 1).any()
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_array_equal(got.sum(1), np.asarray(attended[0]))
    assert (got.sum(1) == np.minimum(np.arange(S) + 1, TOP_K)).all()


def test_packing_holds_each_key_of_a_tile():
    """Any (bk, bq) bool tile comes back from ``_pack`` and ``_unpack`` as
    it went in, the last of the 32 bits (an int32's sign) too."""
    tile = jax.random.bernoulli(jax.random.PRNGKey(3), 0.5, (512, 128))
    words = sp._pack(tile)
    assert words.shape == (16, 128) and words.dtype == jnp.int32
    assert bool(jnp.any(words < 0))
    np.testing.assert_array_equal(np.asarray(sp._unpack(words)),
                                  np.asarray(tile))


# ---- the backward as it was: the choice formed again from the indexer --------

def _formed_bwd_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, qi_ref, ki_ref, w_ref, theta_ref, cut_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, bq,
                       bk, d, r, last, scale, heads):
    t = pl.program_id(1)
    i, j = qb_ref[t], kb_ref[t]

    @pl.when(t == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _, chosen = sp._tile_mask(i, j, bq, bk, ki_ref, qi_ref, w_ref, theta_ref,
                              cut_ref, scale, heads)
    k, v = k_ref[...], v_ref[...]
    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)

    def head(h):
        q_r, do_r = q_ref[sp._head(h, d)], do_ref[sp._head(h, d)]
        s = jax.lax.dot_general(k, q_r, sp._NT,
                                preferred_element_type=jnp.float32)
        p = jnp.where(chosen, jnp.exp(s * (d ** -0.5) - lse_ref[h]), 0.0)
        dv_acc[rows, :] += jnp.dot(p.astype(do_r.dtype), do_r,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do_r, sp._NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[h])).astype(q_r.dtype)
        dk_acc[rows, :] += jnp.dot(ds, q_r,
                                   preferred_element_type=jnp.float32)
        dq_acc[h] += jax.lax.dot_general(k, ds, sp._TN,
                                         preferred_element_type=jnp.float32)

    sp._each_head(head, r)

    @pl.when(j == (i * bq + bq - 1) // bk)
    def _store_q():
        def one(h):
            dq = dq_acc[h] * (d ** -0.5)
            dq_ref[sp._head(h, d)] = dq.T.astype(dq_ref.dtype)

        sp._each_head(one, r)

    @pl.when(t == last)
    def _store_kv():
        dk_ref[...] = (dk_acc[...] * (d ** -0.5)).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _formed_bwd(q, k, v, do, lse, delta, qi, ki, w_t, theta, cut, *, hkv,
                heads, scale, bq, bk):
    s, hd = q.shape
    d = k.shape[1] // hkv
    r = hd // d // hkv
    qb, kb = _pairs(s, bq, bk)
    at = lambda g, t, qb, kb: (qb[t], kb[t])   # noqa: E731
    rows_q = pl.BlockSpec((bq, r * d), lambda g, t, qb, kb: (qb[t], g))
    rows_k = pl.BlockSpec((bk, d), lambda g, t, qb, kb: (kb[t], g))
    whole_k = pl.BlockSpec((s, d), lambda g, t, qb, kb: (0, g))
    stats = pl.BlockSpec((None, r, 1, bq),
                         lambda g, t, qb, kb: (g, 0, 0, qb[t]))
    return pl.pallas_call(
        lambda *refs: _formed_bwd_kernel(
            *refs, bq=bq, bk=bk, d=d, r=r, last=qb.shape[0] - 1, scale=scale,
            heads=heads),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(hkv, qb.shape[0]),
            in_specs=[rows_q, rows_k, rows_k, rows_q, stats, stats]
            + sp._indexer_specs(bq, bk, heads, at),
            out_specs=(rows_q, whole_k, whole_k),
            scratch_shapes=[pltpu.VMEM((r, d, bq), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32)]),
        interpret=True,
    )(qb, kb, q, k, v, do, lse, delta, qi, ki, w_t, theta, cut)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 256)],
                         ids=["128x128", "128x256"])
@pytest.mark.parametrize("exact", [True, False], ids=["exact", "random"])
def test_the_backward_reading_the_mask_is_the_formed_one_to_the_bit(blocks,
                                                                    exact):
    """``attend_bwd`` on the forward's mask gives dq, dk and dv equal, bit
    for bit, to a backward that forms each tile's choice again with
    ``_tile_mask`` from the indexer's operands, once a key-value head."""
    bq, bk = blocks
    (q, k, v, qi, _, _), theta, cut, out, (qi_p, ki_p, w_t) = _forward(
        blocks, exact)
    o, lse, _, mask = out
    s, hq, d = q.shape
    do = jax.random.normal(jax.random.PRNGKey(5), (s, hq * d))
    delta = jnp.sum((o * do).reshape(s, hq, d), -1).T.reshape(HKV, -1, 1, s)
    operands = (q.reshape(s, -1), k.reshape(s, -1), v.reshape(s, -1), do,
                lse, delta)
    read = sp.attend_bwd(*operands, mask, hkv=HKV, bq=bq, bk=bk,
                         interpret=True)
    heads, di = qi.shape[1:]
    formed = _formed_bwd(*operands, qi_p, ki_p, w_t, theta, cut, hkv=HKV,
                         heads=heads, scale=di ** -0.5, bq=bq, bk=bk)
    for a, b in zip(read, formed):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _operand_types(line):
    """The operand types of a lowered custom call's line."""
    return re.search(r"\} : \((.*?)\) ->", line).group(1).split(", ")


def test_at_the_cells_shape_the_backward_takes_the_mask_and_not_the_indexer():
    """At one sequence of 32768, 32 query heads over 4 of 128, an indexer
    of 16 heads of 64 choosing 2048: the gradient lowers for the TPU to the
    four kernels; ``dsa_attend_fwd`` returns the (2080, 16, 512) int32 mask
    and ``dsa_attend_bwd`` takes it, with none of the indexer's operands
    (its padded queries, 32768 x 2048 bf16); no (queries, keys) array lies
    outside the calls."""
    s, bf = 32768, jnp.bfloat16

    def loss(q, k, v, qi, w, ki):
        o, kl, _, _, _ = sp.sparse_attention(q, k, v, qi, w, ki, 2048)
        return jnp.sum(o.astype(jnp.float32)) + kl

    args = (jax.ShapeDtypeStruct((s, 32, 128), bf),
            jax.ShapeDtypeStruct((s, 4, 128), bf),
            jax.ShapeDtypeStruct((s, 4, 128), bf),
            jax.ShapeDtypeStruct((s, 16, 64), bf),
            jax.ShapeDtypeStruct((s, 16), jnp.float32),
            jax.ShapeDtypeStruct((s, 64), bf))
    text = jax.jit(jax.grad(loss, argnums=range(6))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            calls[re.search(r'kernel_name = "(\w+)"', line).group(1)] = line
    assert sorted(calls) == ["dsa_attend_bwd", "dsa_attend_fwd", "dsa_kl",
                             "dsa_select"]
    mask = "tensor<2080x16x512xi32>"
    assert calls["dsa_attend_fwd"].endswith(mask + ")")
    bwd = _operand_types(calls["dsa_attend_bwd"])
    assert mask in bwd
    assert "tensor<32768x2048xbf16>" not in bwd
    assert "tensor<32768x128xbf16>" not in bwd
    assert "tensor<16x32768xf32>" not in bwd
    assert "tensor<32768x2048xbf16>" in _operand_types(calls["dsa_kl"])
    outside = "\n".join(line for line in text.splitlines()
                        if "tpu_custom_call" not in line)
    assert "32768x32768" not in outside
