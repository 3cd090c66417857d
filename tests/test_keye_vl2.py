"""The ``keye_vl2`` family on the CPU at its tiny preset, float32: the
whole model's two losses, every gradient and one AdamW step against the
plain reference (``benchmark/reference/keye_vl2.py``); the sparse op against
dense causal attention where every key is chosen, and its choice against
``lax.top_k`` of its own scores; which leaves each loss reaches; the
softmax router; the share of the experts tied to the uncut layer with no
shared expert; ``route`` and ``GatedMoE`` tracing for the accepted families
to what they traced before; the Mosaic kernels in the interpreter against
the blocked path, and lowered for the TPU at the cell's shape; what a
checkpoint under ``KEEP_CHOICE`` keeps of them and runs again; the family
through ``train_net`` with its counters and scopes.

Tolerances as ``test_joyai_flash.py``: float32 outputs to 2e-5 of their
scale, gradients to 2e-4 (two orders of summation of the same products).
"""

import collections
import inspect
import re
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import joyai_flash, keye_vl2, ling_flash
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops import sparse_attention as sa
from mx_rcnn_tpu.ops import sparse_attention_pallas as sp
from mx_rcnn_tpu.ops.attention import causal_gqa

from benchmark.reference import keye_vl2 as ref


@pytest.fixture(autouse=True)
def _leave_no_spans():
    """Runs with ``obs.enabled`` leave their spans in the process-wide
    buffer, which later test files of the same worker read."""
    yield
    from mx_rcnn_tpu.obs import trace as obs_trace

    obs_trace.reset()


def _tiny():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    from bench_tiny_keye import tiny_keye_cell

    from benchmark.drivers import lm_train

    cell = tiny_keye_cell()
    return cell, lm_train.program_config(cell["config"], cell["traffic"],
                                         False)


def _model_and_weights(seed=7):
    from mx_rcnn_tpu.models import build_model

    cell, cfg = _tiny()
    model = build_model(cfg)
    assert isinstance(model, keye_vl2.KeyeVL2)
    return cell["config"], cfg, model, ref.make_weights(cell["config"], seed)


def _operands(s, hq=4, hkv=2, d=16, heads=4, di=16, seed=0, exact=False):
    """Random operands of the op; ``exact``: the indexer's operands on a
    grid of halves and quarters, so that every score is exact in float32
    whatever the order of its sums, and ties are many."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f = lambda i, shape: jax.random.normal(ks[i], shape)   # noqa: E731
    grid = (lambda x, m: jnp.round(x * m) / m) if exact else (
        lambda x, m: x)
    return (f(0, (s, hq, d)), f(1, (s, hkv, d)), f(2, (s, hkv, d)),
            grid(f(3, (s, heads, di)), 2), grid(f(4, (s, heads)), 4),
            grid(f(5, (s, di)), 2))


# ---- the sparse op ---------------------------------------------------------------

def test_with_every_key_chosen_the_op_is_causal_attention():
    """top_k no smaller than the sequence: every key at or before a query is
    chosen, and the output is ``causal_gqa``'s."""
    q, k, v, qi, w, ki = _operands(64)
    o, kl, touched, _, misses = sa.sparse_attention(q, k, v, qi, w, ki, 64,
                                                    16)
    want = causal_gqa(q[None], k[None], v[None], 16)[0]
    np.testing.assert_allclose(o, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))
    assert float(touched) == 1.0 and float(kl) > 0 and int(misses) == 0


@pytest.mark.parametrize("exact", [False, True], ids=["random", "ties"])
def test_the_choice_is_the_top_k_of_its_own_scores(exact):
    """Of query t the chosen keys are ``lax.top_k``'s ``top_k`` of the op's
    own scores over keys ``s <= t`` (the lower index first on a tie, and
    every key while there are fewer), never a key after the query."""
    s, top_k = 128, 24
    q, k, v, qi, w, ki = _operands(s, exact=exact)
    rows = tuple(range(s))
    _, _, _, chosen, misses = sa.sparse_attention(q, k, v, qi, w, ki, top_k,
                                                  32, rows)
    assert int(misses) == 0
    scores = sa.indexer_scores(qi, w, ki)
    pos = jnp.arange(s)
    masked = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    want = np.zeros((s, s), bool)
    for t in range(s):
        want[t, np.asarray(idx[t])[:min(top_k, t + 1)]] = True
    got = np.asarray(chosen, bool)
    assert not np.triu(got, 1).any()
    np.testing.assert_array_equal(got, want)
    if exact:
        # the grid makes ties across the threshold: the cut is exercised
        top = jnp.sort(masked, -1)[:, ::-1]
        assert int(jnp.sum(top[top_k:, top_k - 1] == top[top_k:, top_k])) > 5


def _loss_of(fn, ct):
    def loss(*a):
        o, kl, _, picks, misses = fn(*a)
        return jnp.sum(o * ct) + 0.7 * kl, (kl, picks, misses)
    return loss


@pytest.mark.parametrize("exact", [True, False], ids=["exact", "random"])
def test_the_kernels_in_the_interpreter_are_the_blocked_path(exact):
    """The four Mosaic kernels (choice, attention forward and backward, the
    indexer's loss and its gradient) in the Pallas interpreter against the
    blocked path: outputs, the loss, the choice and every gradient.  Where
    the indexer's scores are exact in both the choices are the same to the
    key and everything agrees to float32's rounding; on random operands the
    two orders of summation flip a near-tie now and then, and only the
    attention's side is held as closely."""
    s, top_k = 512, 96
    args = _operands(s, hq=4, hkv=2, d=128, heads=4, di=16, exact=exact)
    rows = tuple(range(0, s, 7))
    ct = jax.random.normal(jax.random.PRNGKey(9), (s, 4, 128))
    grads = {}
    for name, fn in (
            ("blocked", lambda *a: sa._blocked(*a, top_k, 64, rows)),
            ("kernels", lambda *a: sp.sparse_attention(
                *a, top_k, rows, True, (128, 128)))):
        (_, aux), g = jax.value_and_grad(_loss_of(fn, ct), argnums=range(6),
                                         has_aux=True)(*args)
        grads[name] = (aux, g)
    (kl_b, pick_b, miss_b), g_b = grads["blocked"]
    (kl_k, pick_k, miss_k), g_k = grads["kernels"]
    assert float(kl_k) == pytest.approx(float(kl_b), rel=1e-5)
    # each attended to what its own choice holds
    assert int(miss_b) == int(miss_k) == 0
    for name, a, b in zip(("q", "k", "v", "qi", "w", "ki"), g_b, g_k):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
        assert err < (1e-5 if exact or name in "qkv" else 5e-2), (name, err)
    if exact:
        np.testing.assert_array_equal(pick_k, pick_b)


def test_attention_that_masks_another_set_than_the_choice_counts_misses():
    """The forward kernel counts the keys each query attended to.  With the
    choice's own threshold every query attends to ``min(t + 1, top_k)``
    keys and none is counted; with the threshold raised a little past the
    first ``top_k`` queries (what scores formed in another order would do
    to a key on it), those queries lose their key on it and each is
    counted."""
    s, top_k, blocks = 256, 40, (128, 128)
    q, k, v, qi, w, ki = _operands(s, hq=4, hkv=2, d=128, heads=4, di=16)
    heads, di = qi.shape[1:]
    qi_p = sp._padded(qi).reshape(s, heads * 128)
    ki_p, w_t = sp._padded(ki), w.T
    theta, cut, _, _ = sp.select(qi_p, ki_p, w_t, top_k=top_k, heads=heads,
                                 scale=di ** -0.5, bk=128, interpret=True)
    pos = jnp.arange(s)

    def misses(theta):
        attended = sp.attend_fwd(
            q.reshape(s, -1), k.reshape(s, -1), v.reshape(s, -1), qi_p, ki_p,
            w_t, theta, cut, hkv=2, heads=heads, scale=di ** -0.5,
            bq=blocks[0], bk=blocks[1], interpret=True)[2]
        return int(sa.count_misses(attended[0], pos, top_k))

    assert misses(theta) == 0
    # a step of a millionth, not of one float32 step: a threshold of 0 (a
    # query whose indexer heads all read ReLU's zero on many keys) would
    # step to a subnormal, which flushing to zero reads as 0
    raised = jnp.where(pos >= top_k,
                       theta + 1e-6 * jnp.maximum(jnp.abs(theta), 1.0), theta)
    assert misses(raised) == s - top_k


def test_the_kernels_lower_for_the_tpu_at_the_cells_shape():
    """At one sequence of 32768, 32 query heads over 4 of 128, an indexer
    of 16 heads of 64 choosing 2048: the forward and its gradient lower for
    the TPU to the four kernels, the choice once and no (queries, keys)
    array outside them.  What libtpu makes of them is the chip's to say."""
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
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 4
    outside = "\n".join(line for line in text.splitlines()
                        if "tpu_custom_call" not in line)
    assert "32768x32768" not in outside


# the policy the checkpoint had before it kept the indexer loss's gradient
CHOICE_ALONE = jax.checkpoint_policies.save_only_these_names(
    sa.THETA, sa.CUT, sp.LSE_I)


def _mapped_loss(policy, top_k, interpret=False, blocks=None):
    """A loss over the kernels as the model runs them: one sequence at a
    time under ``lax.map``, each a ``jax.checkpoint`` under ``policy``."""
    def one(a):
        o, kl, _, _, _ = sp.sparse_attention(*a, top_k, (), interpret, blocks)
        return o, kl

    def loss(*a):
        o, kl = jax.lax.map(jax.checkpoint(one, policy=policy), a)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(kl)

    return loss


def _kernel_runs(text):
    """How often each Mosaic kernel of a lowered module runs, by its name:
    a private function's kernels as often as it is called (a loop's body
    once), since calls that lower alike share one function."""
    kernels, calls, name = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"\s*func\.func \w+ @([\w.$-]+)\(", line)
        if head:
            name = head.group(1)
            kernels[name], calls[name] = [], []
        elif name is not None:
            kernels[name] += re.findall(r'kernel_name = "(\w+)"', line)
            calls[name] += re.findall(r"(?:^|\s)(?:func\.)?call @([\w.$-]+)\(",
                                      line)

    def runs(f):
        out = collections.Counter(kernels[f])
        for g in calls[f]:
            out += runs(g)
        return out

    return dict(runs("main"))


@pytest.mark.parametrize("policy,kl_runs", [
    pytest.param(sa.KEEP_CHOICE, 1, id="keep_choice"),
    pytest.param(CHOICE_ALONE, 2, id="choice_alone"),
])
def test_a_checkpoint_under_the_policy_runs_the_loss_kernel_once(policy,
                                                                 kl_runs):
    """At the cell's shape (one sequence of 32768, 32 query heads over 4 of
    128, an indexer of 16 heads of 64 choosing 2048), under ``lax.map``
    and a checkpoint, the loss and its gradient lower for the TPU to five
    kernel calls where ``KEEP_CHOICE`` keeps the loss kernel's gradient —
    ``dsa_kl`` once — and to six where the checkpoint keeps the choice
    alone and runs ``dsa_kl`` again.  The forward kernel runs twice either
    way: the checkpoint does not keep ``o``."""
    s, bf = 32768, jnp.bfloat16
    args = (jax.ShapeDtypeStruct((1, s, 32, 128), bf),
            jax.ShapeDtypeStruct((1, s, 4, 128), bf),
            jax.ShapeDtypeStruct((1, s, 4, 128), bf),
            jax.ShapeDtypeStruct((1, s, 16, 64), bf),
            jax.ShapeDtypeStruct((1, s, 16), jnp.float32),
            jax.ShapeDtypeStruct((1, s, 64), bf))
    fn = jax.value_and_grad(_mapped_loss(policy, 2048), argnums=range(6))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _kernel_runs(text) == {"dsa_select": 1, "dsa_attend_fwd": 2,
                                  "dsa_kl": kl_runs, "dsa_attend_bwd": 1}


def _pallas_calls(jaxpr, name):
    """The ``pallas_call``s named ``name`` in a jaxpr, inside others too,
    each as often as the jaxpr holding it is an equation's."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub, name)
    return n


def test_the_kept_loss_gradient_is_the_recomputed_one_to_the_bit(capsys):
    """In the interpreter, at blocks of 128 over 256 positions: the
    gradient of every operand with the loss kernel's gradient kept equals
    the one whose checkpoint runs the kernel again, bit for bit; the jaxpr
    holds one ``dsa_kl`` call fewer; and the kept gradient of the indexer's
    queries and key has the indexer's width, not the kernels' 128 lanes."""
    s, top_k, blocks = 256, 40, (128, 128)
    args = tuple(a[None] for a in _operands(s, hq=4, hkv=2, d=128, heads=4,
                                            di=16))
    grads, calls = [], []
    for policy in (sa.KEEP_CHOICE, CHOICE_ALONE):
        fn = jax.value_and_grad(_mapped_loss(policy, top_k, True, blocks),
                                argnums=range(6))
        calls.append(_pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr, "dsa_kl"))
        grads.append(jax.tree_util.tree_leaves(jax.jit(fn)(*args)))
    assert calls == [1, 2]
    for kept, again in zip(*grads):
        np.testing.assert_array_equal(np.asarray(kept), np.asarray(again))

    def one(*a):
        o, kl, _, _, _ = sp.sparse_attention(*a, top_k, (), True, blocks)
        return jnp.sum(o ** 2) + kl

    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(one, policy=sa.KEEP_CHOICE), *(a[0] for a in args))
    # one line a residual: "f32[256,4,16] named '<name>' from ..."
    kept = sorted(line.split()[0] for line in capsys.readouterr().out
                  .splitlines() if f"named '{sp.KL_GRAD}'" in line)
    assert kept == sorted([f"f32[{s},4,16]", f"f32[4,{s}]", f"f32[{s},16]"])


def test_off_the_tpu_the_op_takes_the_blocked_path_without_being_told():
    args = _operands(512, d=128)
    text = str(jax.make_jaxpr(lambda *a: sa.sparse_attention(*a, 96))(*args))
    assert "pallas_call" not in text and "top_k" in text


# ---- the losses and what each reaches ----------------------------------------------

def test_losses_gradients_and_one_adamw_step_match_the_reference():
    from mx_rcnn_tpu.core.optim import make_optimizer
    from mx_rcnn_tpu.core.train import TokenBatch, TrainState, make_train_step

    from benchmark.drivers.lm_train import _adam_mu

    config, cfg, model, params = _model_and_weights()
    ids = np.random.RandomState(0).randint(0, 256, (2, 2, 64)).astype(np.int32)
    want = ref.reference_steps(config, config["optimizer"], params, list(ids))

    tx = make_optimizer(cfg, params, 100, base_lr=config["optimizer"]["lr"])
    state = TrainState(jnp.zeros((), jnp.int32), params, {}, tx.init(params))
    step = jax.jit(make_train_step(model, cfg, tx, mode="lm"))
    s1, m1 = step(state, TokenBatch(ids[0]), jax.random.PRNGKey(0))
    _, m2 = step(s1, TokenBatch(ids[1]), jax.random.PRNGKey(0))
    for got, w in ((m1["loss_main"], want["losses"][0]),
                   (m2["loss_main"], want["losses"][1]),
                   (m1["index_loss"], want["index_losses"][0])):
        assert abs(float(got) - w) < 1e-5 * w
    assert float(m1["loss"]) == pytest.approx(
        float(m1["loss_main"]) + float(m1["index_loss"]), rel=1e-6)
    mu = ref.tree_paths(_adam_mu(s1.opt_state))
    moved = ref.tree_paths(jax.tree.map(jnp.subtract, s1.params, params))
    assert set(mu) == set(want["grad_norm"])
    for k, g in want["grad_norm"].items():
        got = float(jnp.linalg.norm(mu[k])) / (1 - 0.9)
        assert abs(got - g) <= 1e-3 * g + 1e-9, k
        d = float(jnp.linalg.norm(moved[k]))
        assert abs(d - want["first_delta_norm"][k]) <= (
            2e-3 * want["first_delta_norm"][k] + 1e-9), k
    # the head norms and the indexer's weights and LayerNorm of two layers,
    # as vectors
    assert len(want["scan_grad"]) == 2 * 5
    vectors = ref.scan_grads(_adam_mu(s1.opt_state))
    for k, g in want["scan_grad"].items():
        got = np.asarray(vectors[k]) / (1 - 0.9)
        assert np.linalg.norm(got - g) <= 2e-3 * np.linalg.norm(g), k
    assert np.asarray(m1["moe_expert_rows"]).astype(int).tolist() == (
        want["counts"])
    np.testing.assert_array_equal(np.asarray(m1["dsa_chosen"]),
                                  want["chosen"])
    assert float(m1["moe_overflow"]) == 0.0


INDEXER = ("index_q_proj", "index_k_proj", "index_k_norm",
           "index_k_norm_bias", "index_weights_proj")


def test_each_loss_reaches_its_own_leaves():
    """The next-token loss gives the indexer's leaves no gradient (its input
    is detached and its choice not differentiable); the indexer's loss gives
    every other leaf none."""
    _, _, model, params = _model_and_weights()
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 64)),
                      jnp.int32)

    def part(name):
        return jax.grad(lambda p: model.apply({"params": p}, ids)[1][name])(
            params)

    for name, zero_where in (("loss_main", True), ("index_loss", False)):
        for path, g in ref.tree_paths(part(name)).items():
            indexer = path[-1] in INDEXER
            norm = float(jnp.linalg.norm(g))
            if indexer == zero_where:
                assert norm == 0.0, (name, path)
            elif path[-1] in INDEXER:
                assert norm > 0.0, (name, path)


# ---- the expert layer ---------------------------------------------------------------

def test_softmax_routing_renormalised_is_a_softmax_over_the_chosen_logits():
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    idx, weight = moe_ops.route(x, w, 0.0, 2, 1.0, True, scoring="softmax")
    logits = x @ w
    np.testing.assert_array_equal(idx, jax.lax.top_k(logits, 2)[1])
    np.testing.assert_allclose(
        weight, jax.nn.softmax(jnp.take_along_axis(logits, idx, -1), -1),
        rtol=1e-5)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        moe_ops.route(x, w, 0.0, 2, 1.0, True, scoring="tanh")


def _moe_setup():
    cell, _ = _tiny()
    config = dict(cell["config"], num_experts=16,
                  network=dict(cell["config"]["network"], first_expert=0))
    p = ref.make_weights(config, 3)["l1_mlp"]["mlp"]
    # residual writers start small: scale them up so that they count
    p = {k: v * (8.0 if "down" in k else 1.0) for k, v in p.items()}
    return config, p, jax.random.normal(jax.random.PRNGKey(8), (96, 64))


def _share(x, p, held):
    first, count = held
    idx, weight = moe_ops.route(x, p["router"], 0.0, 2, 1.0, True,
                                scoring="softmax")
    routed = moe_ops.held_assignments(
        idx, weight, held, moe_ops.row_capacity(x.shape[0], 2, 16, count, 4.0))
    cut = lambda name: p[name][first:first + count]  # noqa: E731
    return moe_ops.held_experts(
        x, routed, cut("experts_up"), cut("experts_down"),
        w_gate=cut("experts_gate")), routed


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The routed parts that all four shares of four of the 16 experts give
    (what the tiny preset holds, as the cell holds 16 of 128), with no
    shared expert anywhere, are the uncut reference layer's output, and the
    same for the gradient with respect to the input."""
    config, p, x = _moe_setup()

    def uncut(x):
        return ref._moe(config, p, x, jnp.dot, None)[0]

    def shares(x):
        return sum(_share(x, p, (first, 4))[0] for first in range(0, 16, 4))

    want = jax.jit(uncut)(x)
    np.testing.assert_allclose(jax.jit(shares)(x), want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    g_want = jax.jit(jax.grad(lambda x: jnp.sum(uncut(x) * cot)))(x)
    np.testing.assert_allclose(
        jax.jit(jax.grad(lambda x: jnp.sum(shares(x) * cot)))(x), g_want,
        rtol=2e-4, atol=2e-5 * float(jnp.abs(g_want).max()))
    routed = [_share(x, p, (first, 4))[1] for first in range(0, 16, 4)]
    assert int(sum(r.sizes.sum() for r in routed)) == x.shape[0] * 2
    assert ref._moe(config, p, x, jnp.dot, None)[1].tolist() == [
        int(c) for r in routed for c in r.sizes]


@pytest.mark.parametrize("capacity,align", [(192, 8), (192, 16), (64, 16)],
                         ids=["aligned", "wider", "short"])
def test_aligned_rows_are_the_packed_rows_with_each_tile_one_experts(
        capacity, align):
    """``held_assignments``' aligned layout keeps the packed layout's rows,
    each held expert's in order from a multiple of ``align``, at least one
    block of ``align`` rows an expert: every tile of ``align`` rows holds
    one expert's rows and the layer's output is the packed one's.  Where the
    blocks do not fit the capacity, the assignments left out are the
    overflow."""
    _, p, x = _moe_setup()
    held = (4, 4)
    idx, weight = moe_ops.route(x, p["router"], 0.0, 2, 1.0, True,
                                scoring="softmax")
    packed = moe_ops.held_assignments(idx, weight, held, capacity)
    aligned = moe_ops.held_assignments(idx, weight, held, capacity, align)
    assert aligned.token.shape == (capacity,)
    assert aligned.sizes.tolist() == packed.sizes.tolist()
    sizes = np.asarray(aligned.sizes)
    bounds = np.cumsum(np.asarray(aligned.group_sizes))
    assert bounds[-1] == capacity and not any(bounds[:-1] % align)
    valid, token = np.asarray(aligned.valid), np.asarray(aligned.token)
    assert int(aligned.overflow) == int(sizes.sum() - valid.sum())
    lo = 0
    for g, hi in enumerate(bounds):
        kept = valid[lo:hi]
        # an expert's rows come first in its blocks, then padding
        assert kept.sum() == min(sizes[g], hi - lo) and kept[:kept.sum()].all()
        lo = hi
    if int(aligned.overflow):
        return
    assert int(packed.overflow) == 0
    cut = lambda name: p[name][4:8]  # noqa: E731
    outs = [moe_ops.held_experts(x, r, cut("experts_up"),
                                 cut("experts_down"),
                                 w_gate=cut("experts_gate"))
            for r in (packed, aligned)]
    np.testing.assert_allclose(outs[1], outs[0], rtol=1e-6, atol=1e-7)
    assert sorted(token[valid]) == sorted(np.asarray(packed.token)[
        np.asarray(packed.valid)])


def test_aligned_rows_give_the_products_the_same_visits_for_any_routing():
    """At the grouped products' row tile, the aligned layout's visits
    (``gmm_pallas._schedule``) that compute a product are one a tile, each
    over the whole tile, for every routing, one that leaves held experts
    without rows among them: the products' work does not follow the data.
    The packed layout's count of such visits does."""
    from mx_rcnn_tpu.ops import gmm_pallas

    _, p, _ = _moe_setup()
    capacity, tm = 256, 16
    routings = []
    for seed in range(4):
        x = jax.random.normal(jax.random.PRNGKey(100 + seed), (96, 64))
        routings.append(moe_ops.route(x, p["router"], 0.0, 2, 1.0, True,
                                      scoring="softmax"))
    # every token to held experts 4 and 5 alone: 6 and 7 get no rows
    routings.append((jnp.tile(jnp.array([4, 5]), (96, 1)),
                     jnp.full((96, 2), 0.5)))
    visits = {"packed": set(), "aligned": set()}
    for idx, weight in routings:
        for name, align in (("packed", 0), ("aligned", tm)):
            routed = moe_ops.held_assignments(idx, weight, (4, 4), capacity,
                                              align)
            assert int(routed.overflow) == 0
            _, tile, lo, hi = map(np.asarray, gmm_pallas._schedule(
                routed.group_sizes, capacity, tm))
            row0 = tile * tm
            product = np.maximum(lo, row0) < np.minimum(hi, row0 + tm)
            visits[name].add(int(product.sum()))
            if align:
                assert ((lo[product] <= row0[product])
                        & (hi[product] >= row0[product] + tm)).all()
    assert visits["aligned"] == {capacity // tm}
    assert len(visits["packed"]) > 1


def _as_it_was(fn, old, new):
    """``fn``'s source with ``new`` put back to ``old``, compiled in its own
    module's namespace: the function as the parent commit had it."""
    source = textwrap.dedent(inspect.getsource(fn))
    for a, b in zip(new, old):
        assert a in source
        source = source.replace(a, b)
    space = dict(vars(inspect.getmodule(fn)))
    exec(compile(source, "<as it was>", "exec"), space)
    return space[fn.__name__]


ROUTE_NEW = ('''    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"router scoring is sigmoid or softmax, got "
                         f"{scoring!r}")
    scores = (jax.nn.softmax(logits, -1) if scoring == "softmax"
              else jax.nn.sigmoid(logits))''', '''groups: Optional[Tuple[int, int]] = None,
          scoring: str = "sigmoid"):''')
ROUTE_OLD = ('''    scores = jax.nn.sigmoid(logits)''',
             '''groups: Optional[Tuple[int, int]] = None):''')
HELD_NEW = ('''                     capacity: int, align: int = 0) -> Routed:
    """Sort the (T, k) assignments by held expert; keep ``capacity``.
    ``align`` > 0 lays each held expert's rows out from a multiple of
    ``align`` rows, in at least one such block (``_aligned``)."""''',
            '''    if align:
        return _aligned(order, weight.reshape(-1), sizes, k, capacity, align)
''')
HELD_OLD = ('''                     capacity: int) -> Routed:
    """Sort the (T, k) assignments by held expert; keep ``capacity``."""''',
            "")


@pytest.mark.parametrize("family", ["ling_flash_tiny", "joyai_flash_tiny"])
def test_route_and_gated_moe_trace_as_they_did_for_the_accepted_families(
        family, monkeypatch):
    """``route``'s and ``held_assignments``' new arguments and
    ``GatedMoE``'s optional shared expert leave the accepted families'
    expert layers what they were: their jaxprs equal the ones the parent's
    ``route`` and ``held_assignments`` give, argument for argument."""
    cfg = generate_config(family, "synthetic_tokens")
    build = (ling_flash if family.startswith("ling") else joyai_flash).build_lm
    net = build(cfg).net
    layer = ling_flash.GatedMoE(net, 0.01, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, net.hidden_size))
    params = layer.init(jax.random.PRNGKey(1), x)
    now = str(jax.make_jaxpr(layer.apply)(params, x))
    route, old = moe_ops.route, _as_it_was(moe_ops.route, ROUTE_OLD,
                                           ROUTE_NEW)
    old_held = _as_it_was(moe_ops.held_assignments, HELD_OLD, HELD_NEW)

    def parents_held(idx, weight, held, capacity, align):
        assert align == 0
        return old_held(idx, weight, held, capacity)

    # the parent's ``route`` takes no ``scoring``: a sigmoid router's call
    # names none
    monkeypatch.setattr(moe_ops, "route", old)
    monkeypatch.setattr(moe_ops, "held_assignments", parents_held)
    assert str(jax.make_jaxpr(layer.apply)(params, x)) == now
    assert set(params["params"]) >= {"shared_gate", "shared_up",
                                      "shared_down"}
    args = (x.reshape(64, -1), params["params"]["router"])
    for fn in (route, old):
        assert str(jax.make_jaxpr(lambda x, w: route(
            x, w, 0.0, 2, 2.5, True, (2, 1)))(*args)) == str(
            jax.make_jaxpr(lambda x, w: fn(x, w, 0.0, 2, 2.5, True, (2, 1)))(
                *args))


def test_the_family_has_no_shared_expert_and_routes_by_softmax():
    _, cfg = _tiny()
    net = keye_vl2.build_lm(cfg).net
    assert (net.scoring_func, net.moe_shared_expert_intermediate_size,
            net.routed_scaling_factor, net.n_group) == ("softmax", 0, 1.0, 0)
    layer = ling_flash.GatedMoE(net, 0.01, jnp.float32)
    x = jnp.zeros((1, 32, net.hidden_size))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"router", "experts_gate", "experts_up",
                           "experts_down"}


# ---- presets and the training path ----------------------------------------------------

def test_presets_build_what_they_name():
    cfg = generate_config("keye_vl2", "synthetic_tokens")
    n = cfg.network
    assert (n.hidden_size, n.num_attention_heads, n.num_key_value_heads,
            n.head_dim, n.indexer_num_heads, n.indexer_head_dim,
            n.indexer_topk, n.moe_intermediate_size, n.n_routed_experts,
            n.num_experts_per_tok, n.layer_pattern, n.vocab_size,
            n.rope_theta, n.router_scoring, n.norm_eps) == (
        2048, 32, 4, 128, 16, 64, 2048, 768, 128, 8, "S" * 48, 151936, 1e7,
        "softmax", 1e-6)
    assert (cfg.default.wd, cfg.default.clip_gradient, cfg.train.seq_len,
            cfg.default.e2e_lr) == (0.1, 1.0, 8192, 1e-6)
    assert keye_vl2.build_lm(cfg).net.num_layers == 48
    with pytest.raises(ValueError, match="letters S alone"):
        keye_vl2.build_lm(generate_config(
            "keye_vl2_tiny", "synthetic_tokens", network__layer_pattern="SL"))


def test_the_cells_parameter_count():
    """Six layers at published widths, 16 of 128 experts, an eighth of the
    vocabulary: 659.19 M parameters, counted from the program's own
    leaves."""
    from benchmark import run as bench_run
    from benchmark.drivers import lm_train
    from mx_rcnn_tpu.models import build_model

    cell = bench_run.load_cell("keye-vl2-a3b-6l-ep8.train-32k")
    cfg = lm_train.program_config(cell["config"], cell["traffic"], False)
    shapes = jax.eval_shape(build_model(cfg).init_variables,
                            jax.random.PRNGKey(0))[0]
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert n == 659_190_016


class _Record:
    def __init__(self):
        self.rows = []

    def event(self, kind, **fields):
        self.rows.append((kind, fields))


def test_train_net_trains_the_family_with_its_counters_and_spans():
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net

    cfg = generate_config("keye_vl2_tiny", "synthetic_tokens",
                          obs__enabled=True, train__shuffle=False)
    # tokens a model can learn: every row counts up in threes
    rows = ((np.arange(64)[None, :] * 3 + np.arange(32)[:, None]) % 256
            ).astype(np.int32)
    rec = _Record()
    state = train_net(cfg, prefix=None, end_epoch=1, seed=3, roidb=rows,
                      run_record=rec)
    assert int(state.step) == 16
    logs = [f for kind, f in rec.rows if kind == "log"]
    assert len(logs) == 4 and all(np.isfinite(f["loss"]) for f in logs)
    for f in logs:
        assert f["moe_overflow"] == 0.0
        assert 0.0 < f["moe_assignments_per_token"] < 2.0
        assert f["loss"] == pytest.approx(
            f["loss_main"] + f["index_loss"], rel=1e-5)
        assert f["tiles_touched_pct"] == 100.0
        assert f["index_loss"] > 0.0
    # the next-token loss is learned (the indexer's follows a target that
    # moves with the attention it learns)
    assert logs[-1]["loss_main"] < logs[0]["loss_main"] - 0.3
    names = {e["name"] for e in obs_trace.events()}
    assert {"setup.init", "train.data_wait", "train.dispatch", "train.sync",
            "train.log"} <= names


def test_the_step_names_its_scopes():
    from mx_rcnn_tpu.core.optim import make_optimizer
    from mx_rcnn_tpu.core.train import (TokenBatch, make_train_step,
                                        setup_training)
    from mx_rcnn_tpu.models import build_model

    _, cfg = _tiny()
    model = build_model(cfg)
    state = jax.eval_shape(lambda k: setup_training(
        model, cfg, k, (2, 8, 8, 3), 100)[0], jax.random.PRNGKey(0))
    tx = make_optimizer(cfg, state.params, 100)
    text = jax.jit(make_train_step(model, cfg, tx, mode="lm")).lower(
        state, TokenBatch(jax.ShapeDtypeStruct((2, 64), jnp.int32)),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    for scope in ("embed", "dsa/mixer", "dsa_index", "dsa_attend",
                  "dsa_index_loss", "moe/mlp/moe_route",
                  "moe/mlp/moe_experts", "moe_grouped", "lm_head",
                  "optimizer"):
        assert scope in text, scope
    assert "mla" not in text and "kda" not in text
