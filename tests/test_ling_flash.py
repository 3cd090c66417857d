"""The ``ling_flash`` family on the CPU at its tiny preset: each new op
against a plain form of itself (``benchmark/reference/ling_flash.py``: the
delta rule one position at a time, the full masked score matrix, the
router by sorting), the share of the experts tied to the uncut layer, the
whole model's step against the plain reference, and the family through
``train_net`` with its scopes and counters.

Tolerances: float32 comparisons hold to 2e-5 of the output's scale (two
orders of summation of the same float32 products; the chunked rule solves a
triangular system a chunk where the recurrence takes a step a position), and
gradients to 2e-4.  bfloat16 operands carry 8 bits of mantissa; the rule's
products sum a chunk of them and pass through the inverse, so its outputs
are held to 5e-2 of the output's scale.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import ling_flash
from mx_rcnn_tpu.ops import kda_pallas
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops.kda import inv_unit_lower, kda_chunked

from benchmark.reference import ling_flash as ref


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
    from bench_tiny_ling import tiny_ling_cell

    from benchmark.drivers import lm_train

    cell = tiny_ling_cell()
    return cell, lm_train.program_config(cell["config"], cell["traffic"],
                                         False)


# ---- the chunked delta rule ---------------------------------------------------

def _rule_inputs(seed, s=128, h=2, dk=8, dv=8, pinned=False):
    """q, k as the mixer hands them over (unit k, scaled unit q); the
    log-decay spread over (-5, 0), or with ``pinned`` within a hundredth of
    the lower bound at every position: 64 positions then sum to -320, which
    no float32 factor holds."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    u = jax.random.uniform(k[3], (2, s, h, dk))
    g = -5.0 * (1.0 - 0.01 * u if pinned else u ** 3)
    return (unit(jax.random.normal(k[0], (2, s, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(k[1], (2, s, h, dk))),
            jax.random.normal(k[2], (2, s, h, dv)), g,
            jax.nn.sigmoid(jax.random.normal(k[4], (2, s, h))))


def _recurrence(q, k, v, g, beta):
    return jax.vmap(lambda *a: ref.delta_rule(*a, 16))(q, k, v, g, beta)


@pytest.mark.parametrize("chunk,pinned,kernel", [
    (4, False, False), (16, False, False), (64, False, False),
    (16, True, False), (64, True, False), (64, False, True)],
    ids=["chunk4", "chunk16", "chunk64_sub16", "chunk16_at_the_bound",
         "chunk64_at_the_bound", "chunk64_interpreted_kernel"])
def test_chunked_delta_rule_is_the_recurrence(chunk, pinned, kernel):
    """``kernel``: the triangular inverse by the Mosaic kernel of
    ``ops/kda_pallas.py`` in the Pallas interpreter, its own backward in
    the gradients: what the rule runs on a TPU."""
    args = _rule_inputs(0, pinned=pinned)
    want = jax.jit(_recurrence)(*args)
    rule = functools.partial(kda_chunked, interpret=kernel)
    assert ("pallas_call" in str(jax.make_jaxpr(
        lambda *a: rule(*a, chunk))(*args))) == kernel
    got, g_min = jax.jit(rule, static_argnums=5)(*args, chunk)
    scale = float(jnp.abs(want).max())
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * scale)
    # the counter is the chunk's whole sum: beyond float32 at the bound
    total = args[3].reshape(2, -1, chunk, 2, 8).sum(2).min()
    assert float(g_min) == pytest.approx(float(total), rel=1e-5)
    assert (float(g_min) < -300) == (pinned and chunk == 64)
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a, chunk)[0] * cot),
        argnums=(0, 1, 2, 3, 4)))(*args)
    wants = jax.jit(jax.grad(lambda *a: jnp.sum(_recurrence(*a) * cot),
                             argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("qkvgb", grads, wants):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                   atol=2e-4 * float(jnp.abs(b).max()))


def test_chunked_delta_rule_in_bfloat16_stays_near_the_recurrence():
    q, k, v, g, beta = _rule_inputs(1, pinned=True)
    want = _recurrence(q, k, v, g, beta)
    half = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    got, _ = kda_chunked(half(q), half(k), half(v), g, beta, 64)
    assert got.dtype == jnp.bfloat16 and np.isfinite(
        np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=5e-2 * float(jnp.abs(want).max()))


def test_delta_rule_refuses_a_sequence_its_chunk_does_not_divide():
    with pytest.raises(ValueError, match="do not divide"):
        kda_chunked(*_rule_inputs(0, s=24), 16)
    with pytest.raises(ValueError, match="do not divide"):
        kda_chunked(*_rule_inputs(0, s=48), 24)


@pytest.mark.parametrize("n,kernel", [
    (n, kernel) for n in [1, 2, 5, 8, 16, 64] for kernel in (False, True)
    if not kernel or kda_pallas.takes(n)])
def test_inverse_of_unit_lower_triangular(n, kernel):
    """``kernel``: by the interpreted Mosaic kernel, for every ``n`` it
    takes; else the ``jnp`` form, which is what the choice makes off the
    TPU.  Both are float32 and as near the inverse (4e-7 of its largest
    entry, 585 at ``n = 64``); what differs is which equation keeps the
    small residual.  Substitution solves ``M T = I`` row by row — the
    equation the rule needs, ``M (T b) = b`` — and at ``n = 64`` leaves 6e-5
    there and 3.1e-4 in ``T M = I``; the product form leaves 1.9e-4 either
    way.  Each is held to the one tolerance in the equation it solves, and
    both to the inverse itself."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1)
    m = jnp.eye(n) + 0.5 * a
    assert ("pallas_call" in str(jax.make_jaxpr(
        lambda x: inv_unit_lower(x, kernel))(m))) == kernel
    inv = inv_unit_lower(m, kernel)
    np.testing.assert_allclose(m @ inv if kernel else inv @ m,
                               np.broadcast_to(np.eye(n), (3, n, n)),
                               atol=3e-4)
    want = np.linalg.inv(np.asarray(m, np.float64))
    np.testing.assert_allclose(inv, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


# ---- the mixers against the reference --------------------------------------------

def _mixer_setup(kind):
    cell, cfg = _tiny()
    config = cell["config"]
    layer = ref.pattern(config).index(kind)
    p = ref.make_weights(config, 11)[f"l{layer}_mix"]["mixer"]
    # away from their initial values, so that every parameter matters
    key = jax.random.PRNGKey(5)
    p = {k: v + 0.3 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
         * (v.ndim == 1) for i, (k, v) in enumerate(sorted(p.items()))}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    model = ling_flash.build_lm(cfg)
    return config, model.net, p, x


def test_kda_mixer_is_the_reference():
    config, n, p, x = _mixer_setup("K")
    mixer = ling_flash.KDAMixer(
        n.hidden_size, n.num_attention_heads, n.head_dim, n.conv_kernel,
        n.chunk_size, n.kda_lower_bound, n.norm_eps, 0.01, jnp.float32)
    got, g_min = jax.jit(lambda p, x: mixer.apply({"params": p}, x))(p, x)
    want = jax.jit(jax.vmap(lambda row: ref._kda(
        config, p, row, jnp.dot, None)))(x)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    assert -5.0 * 16 < float(g_min) < 0.0


def test_mla_is_the_reference():
    config, n, p, x = _mixer_setup("L")
    mixer = ling_flash.MLA(
        n.hidden_size, n.num_attention_heads, n.kv_lora_rank,
        n.qk_nope_head_dim, n.qk_rope_head_dim, n.v_head_dim, n.rope_theta,
        16, n.norm_eps, 0.01, jnp.float32)
    got = jax.jit(lambda p, x: mixer.apply({"params": p}, x))(p, x)
    want = jax.jit(jax.vmap(lambda row: ref._mla(
        config, p, row, jnp.dot, lambda t: t, None, block_q=64)))(x)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    # positions matter (use_mla_nope false), and so does the latent's norm
    for fault in ("no_rope", "no_latent_norm"):
        other = jax.vmap(lambda row: ref._mla(
            config, p, row, jnp.dot, lambda t: t, fault, block_q=64))(x)
        assert float(jnp.abs(other - want).max()) > 1e-2 * float(
            jnp.abs(want).max()), fault


def test_rotary_term_turns_pairs_by_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3, 8))
    got = ling_flash.rotary(x, 100.0)
    np.testing.assert_allclose(got[0], ref._turn(x[0], 100.0), atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


# ---- the group-limited router ---------------------------------------------------

def _routing_net(e=16, groups=4, kept=2, top_k=2):
    return {"n_group": groups, "topk_group": kept,
            "num_experts_per_tok": top_k}, e


def _scores(kind, t=96, e=16):
    s = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (t, e)))
    if kind == "tied":
        # few distinct values: ties inside groups, between groups' sums of
        # two and among the chosen; all equal in the last rows
        s = jnp.round(s * 3) / 3
        s = s.at[-8:].set(0.5)
    return s


@pytest.mark.parametrize("kind", ["distinct", "tied"])
def test_group_limited_choice_is_the_references(kind):
    net, e = _routing_net()
    scores = _scores(kind)
    limited = moe_ops.limit_to_groups(scores, 4, 2)
    got = jax.lax.top_k(limited, 2)[1]
    want = ref.choose(scores, net)
    np.testing.assert_array_equal(got, want)
    # two groups of four stay open a token, and the choice lies in them
    assert (np.isfinite(np.asarray(limited)).reshape(-1, 4, 4).all(-1).sum(-1)
            == 2).all()
    assert np.isfinite(np.take_along_axis(np.asarray(limited),
                                          np.asarray(got), -1)).all()
    # the limit binds: without it another expert is chosen somewhere
    free = ref.choose(scores, net, "no_group_limit")
    if kind == "distinct":
        assert (np.asarray(free) != np.asarray(want)).any()


def test_route_weights_are_the_chosen_scores_renormalised_and_scaled():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    idx, weight = moe_ops.route(x, w, 0.0, 2, 2.5, True, (4, 2))
    scores = jax.nn.sigmoid(x @ w)
    np.testing.assert_array_equal(
        idx, ref.choose(scores, _routing_net()[0]))
    np.testing.assert_allclose(weight.sum(-1), 2.5, rtol=1e-6)
    # no groups: the plain top-k the other family routes by
    plain, _ = moe_ops.route(x, w, 0.0, 2, 2.5, True)
    np.testing.assert_array_equal(plain, jax.lax.top_k(scores, 2)[1])


# ---- the expert layer's share ---------------------------------------------------

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
    idx, weight = moe_ops.route(x, p["router"], 0.0, 2, 2.5, True, (4, 2))
    routed = moe_ops.held_assignments(
        idx, weight, held, moe_ops.row_capacity(x.shape[0], 2, 16, count, 8.0))
    cut = lambda name: p[name][first:first + count]  # noqa: E731
    return moe_ops.held_experts(
        x, routed, cut("experts_up"), cut("experts_down"),
        w_gate=cut("experts_gate")), routed


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The routed parts that all shares give (each consecutive pair of the
    16 experts in turn) plus the shared expert counted once are the uncut
    reference layer's output, and the same for the gradient with respect
    to the input."""
    config, p, x = _moe_setup()

    def uncut(x):
        return ref._moe(config, p, x, jnp.dot, None)[0]

    def shares(x):
        total = moe_ops.swiglu_ffn(x, p["shared_gate"], p["shared_up"],
                                   p["shared_down"])
        for first in range(0, 16, 2):
            total = total + _share(x, p, (first, 2))[0]
        return total

    want = jax.jit(uncut)(x)
    np.testing.assert_allclose(jax.jit(shares)(x), want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    g_want = jax.jit(jax.grad(lambda x: jnp.sum(uncut(x) * cot)))(x)
    np.testing.assert_allclose(
        jax.jit(jax.grad(lambda x: jnp.sum(shares(x) * cot)))(x), g_want,
        rtol=2e-4, atol=2e-5 * float(jnp.abs(g_want).max()))
    # every assignment falls on exactly one share, none overflows
    routed = [_share(x, p, (first, 2))[1] for first in range(0, 16, 2)]
    assert int(sum(r.sizes.sum() for r in routed)) == x.shape[0] * 2
    assert all(int(r.overflow) == 0 for r in routed)


# ---- the whole model ---------------------------------------------------------------

def test_loss_gradients_and_one_adamw_step_match_the_reference():
    from mx_rcnn_tpu.core.optim import make_optimizer
    from mx_rcnn_tpu.core.train import TokenBatch, TrainState, make_train_step
    from mx_rcnn_tpu.models import build_model

    from benchmark.drivers.lm_train import _adam_mu

    cell, cfg = _tiny()
    config = cell["config"]
    params = ref.make_weights(config, 7)
    ids = np.random.RandomState(0).randint(0, 256, (2, 2, 64)).astype(np.int32)
    want = ref.reference_steps(config, config["optimizer"], params, list(ids))

    model = build_model(cfg)
    assert isinstance(model, ling_flash.LingFlash)
    tx = make_optimizer(cfg, params, 100, base_lr=config["optimizer"]["lr"])
    state = TrainState(jnp.zeros((), jnp.int32), params, {}, tx.init(params))
    step = jax.jit(make_train_step(model, cfg, tx, mode="lm"))
    s1, m1 = step(state, TokenBatch(ids[0]), jax.random.PRNGKey(0))
    _, m2 = step(s1, TokenBatch(ids[1]), jax.random.PRNGKey(0))
    # float32 on both sides: the loss to 1e-5, every leaf's gradient norm and
    # change to 1e-3 of itself (Adam divides by |g|, which rounding moves)
    assert abs(float(m1["loss"]) - want["losses"][0]) < 1e-5 * want["losses"][0]
    assert abs(float(m2["loss"]) - want["losses"][1]) < 1e-5 * want["losses"][1]
    mu = ref.tree_paths(_adam_mu(s1.opt_state))
    moved = ref.tree_paths(jax.tree.map(jnp.subtract, s1.params, params))
    assert set(mu) == set(want["grad_norm"])
    for k, g in want["grad_norm"].items():
        got = float(jnp.linalg.norm(mu[k])) / (1 - 0.9)
        assert abs(got - g) <= 1e-3 * g + 1e-9, k
        d = float(jnp.linalg.norm(moved[k]))
        assert abs(d - want["first_delta_norm"][k]) <= (
            2e-3 * want["first_delta_norm"][k] + 1e-9), k
    # the small vectors' gradients, as vectors
    assert len(want["scan_grad"]) == 2 * ref.pattern(config).count("K") + 3
    for k, g in want["scan_grad"].items():
        got = np.asarray(mu[k]) / (1 - 0.9)
        assert np.linalg.norm(got - g) <= 2e-3 * np.linalg.norm(g), k
    assert np.asarray(m1["moe_expert_rows"]).astype(int).tolist() == (
        want["counts"])
    assert float(m1["moe_overflow"]) == 0.0
    assert -80.0 < float(m1["kda_chunk_log_decay_min"]) < 0.0


def test_presets_build_what_they_name():
    cfg = generate_config("ling_flash", "synthetic_tokens")
    n = cfg.network
    assert (n.hidden_size, n.num_attention_heads * n.head_dim,
            n.n_routed_experts, n.num_experts_per_tok, n.n_group,
            n.topk_group, len(n.layer_pattern), n.first_k_dense_replace) == (
        2560, 4096, 512, 8, 8, 4, 42, 2)
    assert n.layer_pattern.count("L") == 7 and all(
        (c == "L") == ((i + 1) % 6 == 0)
        for i, c in enumerate(n.layer_pattern))
    assert (cfg.default.wd, cfg.default.clip_gradient, cfg.train.seq_len,
            cfg.default.e2e_lr) == (0.1, 1.0, 8192, 1e-6)
    tiny = generate_config("ling_flash_tiny", "synthetic_tokens").network
    assert tiny.layer_pattern == "KKLK" and tiny.first_k_dense_replace == 1
    with pytest.raises(ValueError, match="letters K and L"):
        ling_flash.build_lm(generate_config(
            "ling_flash_tiny", "synthetic_tokens",
            network__layer_pattern="KM"))


def test_the_family_table_is_the_one_switch():
    """Every family names what the four callers ask it for, and no module
    but the table tests the detector's name."""
    import os
    import re

    from mx_rcnn_tpu import families

    for name, fam in families.FAMILIES.items():
        assert callable(fam.get("build")) and callable(fam.get("optimizer"))
        assert (fam.source is None) == (fam.loader is None) == (
            fam.row == "image"), name
        if fam.source is not None:
            assert callable(fam.get("source")) and callable(fam.get("loader"))
    det = generate_config("resnet101", "coco")
    assert families.of(det) is families.FAMILIES["detector"]
    assert families.of(det).mode is None
    assert families.of(generate_config(
        "ling_flash_tiny", "synthetic_tokens")).mode == "lm"
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mx_rcnn_tpu")
    test = re.compile(r"family\s*[!=]=\s*[\"']")
    found = [os.path.join(base, f) for base, _, files in os.walk(root)
             for f in files if f.endswith(".py")
             and test.search(open(os.path.join(base, f)).read())]
    assert found == [], found


class _Record:
    def __init__(self):
        self.rows = []

    def event(self, kind, **fields):
        self.rows.append((kind, fields))


def test_train_net_trains_the_family_with_its_counters_and_spans():
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net

    cfg = generate_config("ling_flash_tiny", "synthetic_tokens",
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
    assert logs[-1]["loss"] < logs[0]["loss"] - 0.3
    for f in logs:
        assert f["moe_overflow"] == 0.0
        assert 0.0 < f["moe_assignments_per_token"] < 2.0
        assert -80.0 < f["kda_chunk_log_decay_min"] < 0.0
    names = {e["name"] for e in obs_trace.events()}
    assert {"setup.loader", "setup.init", "train.data_wait", "train.dispatch",
            "train.sync", "train.log", "stage.assemble",
            "stage.place"} <= names


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
    for scope in ("embed", "kda_mixer/mixer/", "kda_scan/kda_scores",
                  "kda_scan/kda_solve", "mla",
                  "dense_mlp", "moe/mlp/moe_route", "moe/mlp/moe_experts",
                  "moe_grouped", "lm_head", "optimizer"):
        assert scope in text, scope
