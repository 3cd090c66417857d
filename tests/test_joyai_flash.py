"""The ``joyai_flash`` family on the CPU at its tiny preset, float32: what
``MLA`` gained (a low-rank query, adjacent rotary pairs, no head norm, no
gate) against the plain reference (``benchmark/reference/joyai_flash.py``)
with ``ling_flash``'s form left bit for bit as it was; the ungrouped router;
the share of the experts tied to the uncut layer; the multi-token-prediction
module's shift shown token by token; the whole model's two losses, every
gradient and one AdamW step against the reference, whose module runs on
``S - 2`` positions where the program masks the last two of ``S``; and the
family through ``train_net`` with its scopes and counters.

Tolerances: as ``test_ling_flash.py``: float32 outputs to 2e-5 of their
scale, gradients to 2e-4 (two orders of summation of the same products).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import joyai_flash, ling_flash
from mx_rcnn_tpu.models.nemotron_h import rms_norm
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops.attention import causal_gqa

from benchmark.reference import joyai_flash as ref
from benchmark.reference import ling_flash as ling_ref


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
    from bench_tiny_joyai import tiny_joyai_cell

    from benchmark.drivers import lm_train

    cell = tiny_joyai_cell()
    return cell, lm_train.program_config(cell["config"], cell["traffic"],
                                         False)


def _moved(p, seed=5):
    """Vectors away from their initial ones, so that every scale matters."""
    key = jax.random.PRNGKey(seed)
    return {k: v + 0.3 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
            * (v.ndim == 1) for i, (k, v) in enumerate(sorted(p.items()))}


# ---- latent attention -------------------------------------------------------------

def _joyai_mla(n, **kw):
    return ling_flash.MLA(
        n.hidden_size, n.num_attention_heads, n.kv_lora_rank,
        n.qk_nope_head_dim, n.qk_rope_head_dim, n.v_head_dim, n.rope_theta,
        16, n.norm_eps, 0.01, jnp.float32, **kw)


def test_low_rank_query_mla_is_the_reference():
    cell, cfg = _tiny()
    config, n = cell["config"], joyai_flash.build_lm(cfg).net
    p = _moved(ref.make_weights(config, 11)["l1_mix"]["mixer"])
    # projections of a size at which the scores differ from key to key
    p = {k: v * (8.0 if v.ndim == 2 else 1.0) for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    mixer = _joyai_mla(n, q_rank=n.q_lora_rank, head_norms=False,
                       head_gate=False, interleave=True)
    assert set(mixer.init(jax.random.PRNGKey(0), x)["params"]) == set(p) == {
        "q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
        "kv_b_proj", "o_proj"}
    got = jax.jit(lambda p, x: mixer.apply({"params": p}, x))(p, x)

    def plain(fault, block_q=64):
        return jax.vmap(lambda row: ref._mla(
            config, p, row, jnp.dot, lambda t: t, fault, block_q))(x)

    want = jax.jit(lambda: plain(None))()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    # a block of queries that does not divide the sequence changes nothing
    np.testing.assert_allclose(plain(None, 24), want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    # the query's norm, the rotary term and its pairing each matter
    for fault in ("no_q_norm", "no_rope", "rope_halves"):
        assert float(jnp.abs(plain(fault) - want).max()) > 1e-2 * float(
            jnp.abs(want).max()), fault


def _ling_mla_as_it_was(p, x, n, block_q, eps):
    """``ling_flash.MLA.__call__`` before it had fields to choose from: the
    same calls in the same order, one sequence at a time."""
    heads, rank = n.num_attention_heads, n.kv_lora_rank
    nope, rope, vd = n.qk_nope_head_dim, n.qk_rope_head_dim, n.v_head_dim
    s, qk, dt = x.shape[1], nope + rope, jnp.float32

    def attend(x):
        b = x.shape[0]
        q = jnp.dot(x, p["q_proj"].astype(dt)).reshape(b, s, heads, qk)
        latent, k_r = jnp.split(jnp.dot(x, p["kv_a_proj"].astype(dt)),
                                [rank], -1)
        kv = jnp.dot(rms_norm(latent, p["kv_a_norm"], eps),
                     p["kv_b_proj"].astype(dt)).reshape(b, s, heads,
                                                        nope + vd)
        k_n, v = jnp.split(kv, [nope], -1)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None], (b, s, heads, rope))],
            -1)
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)

        def turned(t):
            return jnp.concatenate(
                [t[..., :nope], ling_flash.rotary(t[..., nope:],
                                                  n.rope_theta)], -1)

        o = causal_gqa(turned(q), turned(k), v, block_q)
        gate = jax.nn.sigmoid(jnp.dot(x, p["g_proj"].astype(dt),
                                      preferred_element_type=jnp.float32))
        o = o * gate[..., None].astype(dt)
        return jnp.dot(o.reshape(b, s, heads * vd), p["o_proj"].astype(dt))

    return jax.lax.map(jax.checkpoint(lambda row: attend(row[None])[0]), x)


def test_lings_mla_is_bit_for_bit_what_it_was():
    """The fields ``MLA`` gained stand at ``ling_flash``'s values: its
    parameters, its output and its gradients are the old form's, to the
    last bit, on the same seed."""
    cfg = generate_config("ling_flash_tiny", "synthetic_tokens")
    n = ling_flash.build_lm(cfg).net
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    mixer = ling_flash.MLA(
        n.hidden_size, n.num_attention_heads, n.kv_lora_rank,
        n.qk_nope_head_dim, n.qk_rope_head_dim, n.v_head_dim, n.rope_theta,
        16, n.norm_eps, 0.01, jnp.float32)
    p = mixer.init(jax.random.PRNGKey(3), x)["params"]
    assert set(p) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                      "q_norm", "k_norm", "g_proj", "o_proj"}
    p = _moved(p)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def both(f):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(f(p, x) * cot), argnums=(0, 1)))(p, x)

    (got, g_got), (want, g_want) = (
        both(lambda p, x: mixer.apply({"params": p}, x)),
        both(lambda p, x: _ling_mla_as_it_was(p, x, n, 16, n.norm_eps)))
    assert float(got) == float(want)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        mixer.apply({"params": p}, x),
        _ling_mla_as_it_was(p, x, n, 16, n.norm_eps))


def test_interleaved_rotary_is_the_halves_form_under_the_column_permutation():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3, 8))
    got = ling_flash.rotary_interleaved(x, 100.0)
    np.testing.assert_allclose(got[0], ref._turn_pairs(x[0], 100.0),
                               atol=1e-6)
    # channel 2j to j, 2j + 1 to j + R/2: the halves form on the permuted
    # columns, permuted back
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    halves = ling_flash.rotary(x[..., perm], 100.0)
    np.testing.assert_allclose(got[..., perm], halves, atol=1e-6)
    np.testing.assert_allclose(halves[0], ling_ref._turn(x[0][..., perm],
                                                         100.0), atol=1e-6)
    # and it is another function of the same columns
    assert float(jnp.abs(got - ling_flash.rotary(x, 100.0)).max()) > 0.1
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


# ---- the router and the expert layer's share -----------------------------------------

def test_route_ungrouped_is_one_group_of_all():
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    idx, weight = moe_ops.route(x, w, 0.0, 2, 2.5, True)
    one, w_one = moe_ops.route(x, w, 0.0, 2, 2.5, True, (1, 1))
    np.testing.assert_array_equal(idx, one)
    np.testing.assert_array_equal(weight, w_one)
    scores = jax.nn.sigmoid(x @ w)
    np.testing.assert_array_equal(idx, ling_ref._best(scores, 2))
    np.testing.assert_allclose(weight.sum(-1), 2.5, rtol=1e-6)
    # the stack asks for no groups where the configuration states one:
    # one top-k, the choice's, and none for a group's two best
    cell, cfg = _tiny()
    text = str(jax.make_jaxpr(lambda p, x: ling_flash.GatedMoE(
        joyai_flash.build_lm(cfg).net, 0.01, jnp.float32).apply(
            {"params": p}, x))(
        ref.make_weights(cell["config"], 1)["l1_mlp"]["mlp"],
        jnp.zeros((1, 64, 64))))
    assert text.count("top_k") == 1


def _moe_setup():
    cell, _ = _tiny()
    config = dict(cell["config"], n_routed_experts=16,
                  network=dict(cell["config"]["network"], first_expert=0))
    p = ref.make_weights(config, 3)["l1_mlp"]["mlp"]
    # residual writers start small: scale them up so that they count
    p = {k: v * (8.0 if "down" in k else 1.0) for k, v in p.items()}
    return config, p, jax.random.normal(jax.random.PRNGKey(8), (96, 64))


def _share(x, p, held):
    first, count = held
    idx, weight = moe_ops.route(x, p["router"], 0.0, 2, 2.5, True)
    routed = moe_ops.held_assignments(
        idx, weight, held, moe_ops.row_capacity(x.shape[0], 2, 16, count, 4.0))
    cut = lambda name: p[name][first:first + count]  # noqa: E731
    return moe_ops.held_experts(
        x, routed, cut("experts_up"), cut("experts_down"),
        w_gate=cut("experts_gate")), routed


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The routed parts that all four shares of four of the 16 experts give
    (what the tiny preset holds, as the cell holds 16 of 256) plus the
    shared expert counted once are the uncut reference layer's output, and
    the same for the gradient with respect to the input."""
    config, p, x = _moe_setup()

    def uncut(x):
        return ref._moe(config, p, x, jnp.dot, None)[0]

    def shares(x):
        total = moe_ops.swiglu_ffn(x, p["shared_gate"], p["shared_up"],
                                   p["shared_down"])
        for first in range(0, 16, 4):
            total = total + _share(x, p, (first, 4))[0]
        return total

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
    assert all(int(r.overflow) == 0 for r in routed)
    assert ref._moe(config, p, x, jnp.dot, None)[1].tolist() == [
        int(c) for r in routed for c in r.sizes]


# ---- the module's shift ------------------------------------------------------------------

def _model_and_weights(seed=7):
    from mx_rcnn_tpu.models import build_model

    cell, cfg = _tiny()
    model = build_model(cfg)
    assert isinstance(model, joyai_flash.JoyAIFlash)
    return cell["config"], cfg, model, ref.make_weights(cell["config"], seed)


def _hidden(model, params, ids):
    """(the stack's last residual stream, the module's, the losses): what
    the two heads' logits at a position are functions of."""
    (_, aux), state = model.apply({"params": params}, ids,
                                  capture_intermediates=True, mutable=True)
    taps = state["intermediates"]
    last = f"l{model.net.num_layers - 1}_mlp"
    return (taps[last]["__call__"][0][0], taps["mtp"]["mlp"]["__call__"][0][0],
            aux)


def test_the_module_at_position_i_reads_token_i_plus_1_and_is_held_to_i_plus_2():
    config, _, model, params = _model_and_weights()
    ids = np.random.RandomState(0).randint(0, 256, (2, 64)).astype(np.int32)
    main, mtp, aux = _hidden(model, params, ids)
    i = 30

    def changed(at):
        other = ids.copy()
        other[:, at] = (other[:, at] + 17) % 256
        return _hidden(model, params, other)

    # token i + 1: the module's hidden state at i moves, the stack's at i
    # does not (its logits at i are the next-token ones), nor anything
    # before i
    main1, mtp1, _ = changed(i + 1)
    np.testing.assert_array_equal(main1[:, :i + 1], main[:, :i + 1])
    np.testing.assert_array_equal(mtp1[:, :i], mtp[:, :i])
    assert float(jnp.abs(mtp1[:, i] - mtp[:, i]).max()) > 1e-3
    assert float(jnp.abs(main1[:, i + 1] - main[:, i + 1]).max()) > 1e-3
    # token i + 2: neither hidden state at i moves, the module's loss does,
    # by what the change of its target at i and its inputs after i make of it
    main2, mtp2, aux2 = changed(i + 2)
    np.testing.assert_array_equal(main2[:, :i + 2], main[:, :i + 2])
    np.testing.assert_array_equal(mtp2[:, :i + 1], mtp[:, :i + 1])
    assert float(aux2["mtp_loss"]) != float(aux["mtp_loss"])
    # the last token is the module's target at S - 3 and nothing else of it:
    # its loss moves by that position's cross-entropy alone
    s = ids.shape[1]
    _, mtp3, aux3 = changed(s - 1)
    np.testing.assert_array_equal(mtp3[:, :s - 2], mtp[:, :s - 2])
    logits = rms_norm(mtp[:, s - 3], params["mtp"]["shared_head_norm"],
                      1e-6) @ params["head"]
    other = (ids[:, s - 1] + 17) % 256
    pick = lambda t: jnp.take_along_axis(logits, t[:, None], -1)[:, 0]  # noqa: E731
    want = float(jnp.sum(pick(ids[:, s - 1]) - pick(other))) / (2 * (s - 2))
    assert float(aux3["mtp_loss"]) - float(aux["mtp_loss"]) == pytest.approx(
        want, rel=1e-3)
    assert abs(want) > 1e-4


def test_shared_embedding_and_head_take_the_sum_of_both_paths_gradients():
    _, _, model, params = _model_and_weights()
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 64)),
                      jnp.int32)

    def grad_of(pick):
        return jax.jit(jax.grad(lambda p: pick(*model.apply(
            {"params": p}, ids))))(params)

    whole = grad_of(lambda loss, aux: loss)
    main = grad_of(lambda loss, aux: aux["loss_main"])
    mtp = grad_of(lambda loss, aux: aux["mtp_loss"])
    lam = model.net.mtp_loss_weight
    assert lam == 0.3
    for leaf in ("embed", "head"):
        both = main[leaf] + lam * mtp[leaf]
        scale = float(jnp.abs(whole[leaf]).max())
        np.testing.assert_allclose(whole[leaf], both, rtol=0,
                                   atol=2e-5 * scale)
        # each path alone is short of it by the other's part
        assert float(jnp.abs(lam * mtp[leaf]).max()) > 0.05 * scale
        assert float(jnp.abs(main[leaf]).max()) > 0.05 * scale
    # the module's own leaves get nothing from the next-token loss
    assert all(float(jnp.abs(g).max()) == 0.0
               for g in jax.tree.leaves(main["mtp"]))


# ---- the whole model ---------------------------------------------------------------

def test_losses_gradients_and_one_adamw_step_match_the_reference():
    """The reference's module runs on ``S - 2`` positions; the program's on
    all ``S`` with the last two masked out of its loss."""
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
    assert abs(float(m1["loss"]) - want["losses"][0]) < 1e-5 * want["losses"][0]
    assert abs(float(m2["loss"]) - want["losses"][1]) < 1e-5 * want["losses"][1]
    mtp = want["mtp_losses"][0]
    assert abs(float(m1["mtp_loss"]) - mtp) < 1e-5 * mtp
    assert float(m1["loss_main"]) + 0.3 * float(m1["mtp_loss"]) == (
        pytest.approx(float(m1["loss"]), rel=1e-6))
    mu = ref.tree_paths(_adam_mu(s1.opt_state))
    moved = ref.tree_paths(jax.tree.map(jnp.subtract, s1.params, params))
    assert set(mu) == set(want["grad_norm"])
    for k, g in want["grad_norm"].items():
        got = float(jnp.linalg.norm(mu[k])) / (1 - 0.9)
        assert abs(got - g) <= 1e-3 * g + 1e-9, k
        d = float(jnp.linalg.norm(moved[k]))
        assert abs(d - want["first_delta_norm"][k]) <= (
            2e-3 * want["first_delta_norm"][k] + 1e-9), k
    # the small vectors' gradients, as vectors: two a latent block (three
    # layers and the module's), the module's three and the final norm, a
    # slice of the embedding and of the head
    assert len(want["scan_grad"]) == 2 * 4 + 4 + 2
    vectors = ref.scan_grads(_adam_mu(s1.opt_state))
    for k, g in want["scan_grad"].items():
        got = np.asarray(vectors[k]) / (1 - 0.9)
        assert got.shape == g.shape and g.size >= 24
        assert np.linalg.norm(got - g) <= 2e-3 * np.linalg.norm(g), k
    rows = np.asarray(m1["moe_expert_rows"]).astype(int)
    assert rows[:-1].tolist() == want["counts"][:-1]
    # the module's last two positions a sequence are routed and not held to
    # a target: at most top-2 assignments each
    extra = rows[-1] - np.asarray(want["counts"][-1])
    assert (extra >= 0).all() and extra.sum() <= 2 * 2 * 2
    assert float(m1["moe_overflow"]) == 0.0


def test_presets_build_what_they_name():
    cfg = generate_config("joyai_flash", "synthetic_tokens")
    n = cfg.network
    assert (n.hidden_size, n.num_attention_heads, n.q_lora_rank,
            n.kv_lora_rank, n.qk_nope_head_dim, n.qk_rope_head_dim,
            n.v_head_dim, n.intermediate_size, n.moe_intermediate_size,
            n.n_routed_experts, n.num_experts_per_tok, n.n_group,
            n.layer_pattern, n.first_k_dense_replace, n.vocab_size,
            n.num_nextn_predict_layers, n.rope_interleave) == (
        2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 256, 8, 1, "L" * 40, 1,
        129280, 1, True)
    assert (cfg.default.wd, cfg.default.clip_gradient, cfg.train.seq_len,
            cfg.default.e2e_lr) == (0.1, 1.0, 8192, 1e-6)
    model = joyai_flash.build_lm(cfg)
    assert (model.net.num_layers, model.net.mtp_loss_weight,
            model.net.qk_head_norms, model.net.head_gate) == (
        40, 0.3, False, False)
    tiny = generate_config("joyai_flash_tiny", "synthetic_tokens")
    # depth, the module and its weight are overridable, as the share is
    cut = joyai_flash.build_lm(generate_config(
        "joyai_flash_tiny", "synthetic_tokens", network__layer_pattern="LL",
        network__num_nextn_predict_layers=0, network__mtp_loss_weight=0.1))
    assert (cut.net.num_layers, cut.net.num_nextn_predict_layers) == (2, 0)
    loss, aux = cut.apply({"params": cut.init_variables(
        jax.random.PRNGKey(0))[0]}, jnp.zeros((1, 32), jnp.int32))
    assert "mtp_loss" not in aux and float(loss) == float(aux["loss_main"])
    assert aux["sizes"].shape == (1, 4)
    assert tiny.network.layer_pattern == "LLL"
    with pytest.raises(ValueError, match="letters L alone"):
        joyai_flash.build_lm(generate_config(
            "joyai_flash_tiny", "synthetic_tokens",
            network__layer_pattern="LK"))
    with pytest.raises(ValueError, match="0 or 1"):
        joyai_flash.build_lm(generate_config(
            "joyai_flash_tiny", "synthetic_tokens",
            network__num_nextn_predict_layers=2))


class _Record:
    def __init__(self):
        self.rows = []

    def event(self, kind, **fields):
        self.rows.append((kind, fields))


def test_train_net_trains_the_family_with_its_counters_and_spans():
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net

    cfg = generate_config("joyai_flash_tiny", "synthetic_tokens",
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
            f["loss_main"] + 0.3 * f["mtp_loss"], rel=1e-5)
    # both objectives are learned
    assert logs[-1]["loss_main"] < logs[0]["loss_main"] - 0.3
    assert logs[-1]["mtp_loss"] < logs[0]["mtp_loss"] - 0.3
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
    for scope in ("embed", "mla/mixer", "dense_mlp", "moe/mlp/moe_route",
                  "moe/mlp/moe_experts", "moe_grouped", "lm_head",
                  "optimizer", "mtp/mtp_combine", "mtp/mix/mla/mixer",
                  "mtp/mlp/moe/mlp/moe_route", "mtp/mtp_head"):
        assert scope in text, scope
    assert "kda" not in text
