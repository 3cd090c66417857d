"""The ``evabyte`` family on the CPU at its tiny preset, float32: the whole
model's heads' loss, head 0's loss, every gradient and one AdamW step
against the plain reference (``benchmark/reference/evabyte.py``), on the
blocked path and with the Mosaic kernels in the interpreter; the EVA op
against an explicit softmax over the concatenated key set at each window's
first and last position, against causal attention where one window holds
the sequence, and its key counts; each head's targets and masked tail; the
kernels lowered for the TPU at the cell's shape, and what a checkpoint
under ``KEEP_EVA_RESIDUALS`` keeps of them; the lm step of a stack with no
expert layer; the family through ``train_net`` with its counters and
scopes.

Tolerances as ``test_keye_vl2.py``: float32 outputs to 2e-5 of their scale,
gradients to 2e-4 (two orders of summation of the same products).
"""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import evabyte
from mx_rcnn_tpu.ops import eva
from mx_rcnn_tpu.ops import eva_pallas as ep
from mx_rcnn_tpu.ops.attention import causal_gqa

from benchmark.reference import evabyte as ref

# the interpreter's blocks at the tiny sizes: 16 summaries of 256 positions
# in chunks of 16, windows of 64
BLOCKS = (32, 16)


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
    from bench_tiny_evabyte import tiny_evabyte_cell

    from benchmark.drivers import lm_train

    cell = tiny_evabyte_cell()
    return cell, lm_train.program_config(cell["config"], cell["traffic"],
                                         False)


def _model_and_weights(seed=7):
    from mx_rcnn_tpu.models import build_model

    cell, cfg = _tiny()
    model = build_model(cfg)
    assert isinstance(model, evabyte.EvaByte)
    return cell["config"], cfg, model, ref.make_weights(cell["config"], seed)


def _operands(s=256, h=2, d=128, chunk=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (1, s, h, d)) for i in range(3))
    mu, phi = (jax.random.normal(ks[3 + i], (h, d)) for i in range(2))
    kbar, vbar = eva.pool_chunks(k, v, mu, phi, chunk)
    return q, k, v, kbar, vbar


def _summary_pct(s, window, chunk):
    """The share of the softmax's pairs that are summaries, in percent."""
    keys, summaries = eva.expected_keys(s, window, chunk)
    return 100.0 * summaries.sum() / keys.sum()


def _interpreted(*args, **kw):
    return eva.eva_attention(*args, blocks=BLOCKS, interpret=True, **kw)


# ---- the op ---------------------------------------------------------------------

@pytest.mark.parametrize("path", ["blocked", "interpreter"])
def test_the_op_is_one_softmax_over_the_windows_keys_and_earlier_summaries(
        path):
    """At each window's first and last position, the op's output is an
    explicit softmax over the window's keys up to the query and the
    summaries of every chunk before the window, concatenated."""
    window, chunk = 64, 16
    q, k, v, kbar, vbar = _operands()
    fn = _interpreted if path == "interpreter" else eva.eva_attention
    o = fn(q, k, v, kbar, vbar, window, chunk)[0]
    for start in range(0, 256, window):
        for t in (start, start + window - 1):
            n = start // chunk
            keys = jnp.concatenate([kbar[0, :n], k[0, start:t + 1]])
            vals = jnp.concatenate([vbar[0, :n], v[0, start:t + 1]])
            p = jax.nn.softmax(jnp.einsum("hd,khd->hk", q[0, t], keys)
                               / np.sqrt(128), -1)
            want = jnp.einsum("hk,khd->hd", p, vals)
            np.testing.assert_allclose(o[0, t], want, atol=2e-5, rtol=2e-5)


def test_the_pooling_is_a_softmax_over_each_chunk():
    q, k, v, _, _ = _operands(s=64)
    mu, phi = (jax.random.normal(jax.random.PRNGKey(i), (2, 128))
               for i in (8, 9))
    kbar, vbar = eva.pool_chunks(k, v, mu, phi, 16)
    c = 2
    kc, vc = k[0, 16 * c:16 * c + 16], v[0, 16 * c:16 * c + 16]
    wk = jax.nn.softmax(jnp.einsum("jhd,hd->jh", kc, mu) / np.sqrt(128), 0)
    wv = jax.nn.softmax(jnp.einsum("jhd,hd->jh", kc, phi) / np.sqrt(128), 0)
    np.testing.assert_allclose(kbar[0, c], jnp.einsum("jh,jhd->hd", wk, kc),
                               atol=2e-5)
    np.testing.assert_allclose(vbar[0, c], jnp.einsum("jh,jhd->hd", wv, vc),
                               atol=2e-5)


@pytest.mark.parametrize("path", ["blocked", "interpreter"])
def test_a_window_that_holds_the_sequence_is_causal_attention(path):
    q, k, v, kbar, vbar = _operands()
    fn = _interpreted if path == "interpreter" else eva.eva_attention
    o, pct, misses = fn(q, k, v, kbar, vbar, 256, 16)
    np.testing.assert_allclose(o, causal_gqa(q, k, v, 32), atol=2e-5)
    assert float(pct) == 0.0 and int(misses) == 0


@pytest.mark.parametrize("path", ["blocked", "interpreter"])
def test_the_key_counts_are_the_windows_and_the_summaries(path):
    """A query at its window's start takes itself and the window's start
    over 16 summaries; the counted share of summaries is the formula's and
    no row misses its count."""
    window, chunk = 64, 16
    keys, summaries = eva.expected_keys(256, window, chunk)
    for start in range(0, 256, window):
        assert keys[start] == 1 + start // chunk
        assert summaries[start] == start // chunk
    q, k, v, kbar, vbar = _operands()
    fn = _interpreted if path == "interpreter" else eva.eva_attention
    _, pct, misses = fn(q, k, v, kbar, vbar, window, chunk)
    assert float(pct) == pytest.approx(_summary_pct(256, window,
                                                            chunk))
    assert int(misses) == 0
    # at the cell's traffic: 30.4 % of the softmax's pairs are summaries
    assert _summary_pct(16384, 2048, 16) == pytest.approx(30.42,
                                                                  abs=0.01)


def test_the_pair_list_visits_each_querys_key_sets_once():
    """Every (query block, key block) pair holds at least one key a query
    of the block takes, and each query's keys are covered by the pairs of
    its block exactly once."""
    s, window, chunk, bq, bk = 256, 64, 16, 32, 16
    qi, kj, flags, lim = (np.asarray(a) for a in ep.pairs(s, window, chunk,
                                                           bq, bk))
    nsb = s // chunk // bk
    for i in range(s // bq):
        mine = np.flatnonzero(qi == i)
        assert flags[mine[0]] & ep.FIRST and flags[mine[-1]] & ep.LAST
        covered = collections.Counter()
        for p in mine:
            for lane in range(bk):
                if kj[p] < nsb:
                    if lane < lim[p]:
                        covered[("sum", kj[p] * bk + lane)] += 1
                else:
                    covered[("key", (kj[p] - nsb) * bk + lane)] += 1
        start = window * (i * bq // window)
        want = {("sum", c) for c in range(start // chunk)} | {
            ("key", j) for j in range(start, i * bq + bq)}
        assert set(covered) == want and set(covered.values()) == {1}


def test_the_interpreted_kernels_gradients_are_the_blocked_paths():
    q, k, v, kbar, vbar = _operands()

    def loss(fn):
        def f(*a):
            o = fn(*a, 64, 16)[0]
            return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))
        return jax.grad(f, argnums=range(5))(q, k, v, kbar, vbar)

    for got, want in zip(loss(_interpreted), loss(eva.eva_attention)):
        np.testing.assert_allclose(got, want, atol=2e-5 * float(
            jnp.abs(want).max()))


def test_off_the_tpu_the_op_takes_the_blocked_path_without_being_told():
    args = _operands()
    text = str(jax.make_jaxpr(lambda *a: eva.eva_attention(*a, 64, 16))(
        *args))
    assert "pallas_call" not in text


def _kernel_runs(text):
    """How often each Mosaic kernel of a lowered module runs, by its name:
    a private function's kernels as often as it is called."""
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


def _checkpointed_loss(policy, window=2048, **kw):
    def loss(q, k, v, mu, phi):
        def body(q, k, v, mu, phi):
            kbar, vbar = eva.pool_chunks(k, v, mu, phi, 16)
            return eva.eva_attention(q, k, v, kbar, vbar, window, 16, **kw)[0]
        o = jax.checkpoint(body, policy=policy)(q, k, v, mu, phi)
        return jnp.sum(o.astype(jnp.float32) ** 2)
    return loss


@pytest.mark.parametrize("policy,fwd_runs", [
    pytest.param(ep.KEEP_EVA_RESIDUALS, 1, id="keep_residuals"),
    pytest.param(jax.checkpoint_policies.nothing_saveable, 2,
                 id="keep_nothing"),
])
def test_the_kernels_lower_for_the_tpu_at_the_cells_shape(policy, fwd_runs):
    """At one sequence of 16384 bytes, 32 heads of 128, windows of 2048 and
    chunks of 16, the pooling, the op and its gradient under a checkpoint
    lower for the TPU to the two kernels, the forward once where
    ``KEEP_EVA_RESIDUALS`` keeps its output and log-sum-exp and twice where
    the checkpoint keeps nothing, and no (queries, keys) array outside
    them.  What libtpu makes of them is the chip's to say."""
    s, bf = 16384, jnp.bfloat16
    args = (jax.ShapeDtypeStruct((1, s, 32, 128), bf),) * 3 + (
        jax.ShapeDtypeStruct((32, 128), jnp.float32),) * 2
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        text = jax.jit(jax.value_and_grad(
            _checkpointed_loss(policy), argnums=range(5))).trace(
            *args).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        jax.default_backend = real
    assert _kernel_runs(text) == {"eva_attend_fwd": fwd_runs,
                                  "eva_attend_bwd": 1}
    outside = "\n".join(line for line in text.splitlines()
                        if "tpu_custom_call" not in line)
    assert "16384x17408" not in outside and "16384x16384" not in outside


def test_the_kept_residuals_give_the_recomputed_gradient_to_the_bit():
    s = 256
    q, k, v, _, _ = _operands(s)
    mu, phi = (jax.random.normal(jax.random.PRNGKey(i), (2, 128))
               for i in (3, 4))
    grads = []
    for policy in (ep.KEEP_EVA_RESIDUALS,
                   jax.checkpoint_policies.nothing_saveable):
        fn = _checkpointed_loss(policy, 64, blocks=BLOCKS, interpret=True)
        grads.append(jax.jit(jax.grad(fn, argnums=range(5)))(q, k, v, mu,
                                                             phi))
    for kept, again in zip(*grads):
        np.testing.assert_array_equal(np.asarray(kept), np.asarray(again))


# ---- the model --------------------------------------------------------------------

@pytest.mark.parametrize("path", ["blocked", "interpreter"])
def test_losses_gradients_and_one_adamw_step_match_the_reference(
        monkeypatch, path):
    from mx_rcnn_tpu.core.optim import make_optimizer
    from mx_rcnn_tpu.core.train import TokenBatch, TrainState, make_train_step

    from benchmark.drivers.lm_train import _adam_mu

    # the MLP in eight parts of the sequence, one after the other
    monkeypatch.setattr(evabyte, "MLP_TOKEN_CHUNK", 32)
    if path == "interpreter":
        monkeypatch.setattr(evabyte, "eva_attention", functools.partial(
            eva.eva_attention, blocks=BLOCKS, interpret=True))
    config, cfg, model, params = _model_and_weights()
    ids = np.random.RandomState(0).randint(0, 320, (2, 2, 256)).astype(
        np.int32)
    want = ref.reference_steps(config, config["optimizer"], params, list(ids))

    tx = make_optimizer(cfg, params, 100, base_lr=config["optimizer"]["lr"])
    state = TrainState(jnp.zeros((), jnp.int32), params, {}, tx.init(params))
    step = jax.jit(make_train_step(model, cfg, tx, mode="lm"))
    s1, m1 = step(state, TokenBatch(ids[0]), jax.random.PRNGKey(0))
    _, m2 = step(s1, TokenBatch(ids[1]), jax.random.PRNGKey(0))
    for got, w in ((m1["loss"], want["losses"][0]),
                   (m2["loss"], want["losses"][1]),
                   (m1["loss_main"], want["main_losses"][0])):
        assert abs(float(got) - w) < 2e-5 * w
    assert not any(k.startswith("moe_") for k in m1)
    assert int(m1["eva_count_misses"]) == 0
    assert float(m1["eva_summary_keys_pct"]) == pytest.approx(
        _summary_pct(256, 64, 16))
    mu = ref.tree_paths(_adam_mu(s1.opt_state))
    moved = ref.tree_paths(jax.tree.map(jnp.subtract, s1.params, params))
    assert set(mu) == set(want["grad_norm"])
    for k, g in want["grad_norm"].items():
        got = float(jnp.linalg.norm(mu[k])) / (1 - 0.9)
        assert abs(got - g) <= 2e-4 * g + 1e-9, k
        d = float(jnp.linalg.norm(moved[k]))
        assert abs(d - want["first_delta_norm"][k]) <= (
            2e-3 * want["first_delta_norm"][k] + 1e-9), k
    # mu and phi and the two norms of two layers, and the final norm
    assert len(want["scan_grad"]) == 2 * 4 + 1
    vectors = ref.scan_grads(_adam_mu(s1.opt_state))
    for k, g in want["scan_grad"].items():
        got = np.asarray(vectors[k]) / (1 - 0.9)
        assert np.linalg.norm(got - g) <= 2e-4 * np.linalg.norm(g), k


def test_at_bfloat16_compute_the_residual_stream_stays_float32():
    """``fp32_skip_add``: with the blocks computing in bfloat16, every layer
    hands on a float32 stream, most of whose values bfloat16 could not
    hold.  On seed-made weights no number of ``correct`` tells a stream
    rounded to bfloat16 from a sound one (the reference's
    ``bf16_residual``; PERF.md section 7), so the stream is checked here."""
    from mx_rcnn_tpu.models import build_model

    cell, _ = _tiny()
    cfg = generate_config("evabyte_tiny", "synthetic_tokens",
                          network__compute_dtype="bfloat16")
    model = build_model(cfg)
    params = ref.make_weights(cell["config"], 7)
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 320, (1, 256)),
                      jnp.int32)
    _, state = model.apply({"params": params}, ids,
                           capture_intermediates=True,
                           mutable=["intermediates"])
    for i in range(2):
        x = state["intermediates"][f"l{i}"]["__call__"][0][0]
        assert x.dtype == jnp.float32
        held = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
        assert np.mean(np.asarray(x) == held) < 0.1, i


def test_each_head_predicts_its_own_token_and_masks_its_tail(monkeypatch):
    """Head i's targets are the ids 1 + i ahead, its last 1 + i positions
    weigh nothing, the loss is the heads' mean of each head's mean over its
    scored positions and ``loss_main`` is head 0's."""
    _, _, model, params = _model_and_weights()
    b, s = 2, 256
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 320, (b, s)),
                      jnp.int32)
    seen = []

    def plain(h, w, targets, weights, chunk):
        logits = jnp.dot(h, w)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[:, None], -1)[:, 0]
        seen.append((np.asarray(targets), np.asarray(weights)))
        return jnp.sum(ce * weights)

    monkeypatch.setattr(evabyte, "chunked_cross_entropy", plain)
    loss, aux = model.apply({"params": params}, ids)
    assert len(seen) == 4
    for i, (targets, weights) in enumerate(seen):
        t, w = targets.reshape(b, s), weights.reshape(b, s)
        np.testing.assert_array_equal(t[:, :s - 1 - i],
                                      np.asarray(ids)[:, 1 + i:])
        assert (w[:, s - 1 - i:] == 0).all() and (w[:, :s - 1 - i] == 1).all()
    # the same sums, head by head, over their own counts
    sums = []
    monkeypatch.setattr(evabyte, "chunked_cross_entropy",
                        lambda *a: sums.append(plain(*a)) or sums[-1])
    model.apply({"params": params}, ids)
    per_head = [float(c) / (b * (s - 1 - i)) for i, c in enumerate(sums)]
    assert float(aux["loss_main"]) == pytest.approx(per_head[0], rel=1e-6)
    assert float(loss) == pytest.approx(np.mean(per_head), rel=1e-6)


def test_a_stack_without_an_expert_layer_reports_no_expert_counters():
    from mx_rcnn_tpu.core.train import TokenBatch, loss_and_metrics_lm

    _, cfg, model, params = _model_and_weights()
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 320, (1, 256)),
                      jnp.int32)
    loss, metrics = loss_and_metrics_lm(model, params, {}, TokenBatch(ids),
                                        jax.random.PRNGKey(0), cfg)
    assert set(metrics) == {"loss", "loss_main", "eva_summary_keys_pct",
                            "eva_count_misses"}
    assert float(metrics["loss"]) == float(loss)


def test_presets_build_what_they_name():
    cfg = generate_config("evabyte", "synthetic_tokens")
    n = cfg.network
    assert (n.hidden_size, n.num_attention_heads, n.num_key_value_heads,
            n.head_dim, n.intermediate_size, n.vocab_size, n.eva_window,
            n.eva_chunk, n.num_pred_heads, n.rope_theta, n.norm_eps,
            n.init_std, n.layer_pattern) == (
        4096, 32, 32, 128, 11008, 320, 2048, 16, 8, 1e5, 1e-5, 0.01275,
        "V" * 32)
    assert (cfg.default.wd, cfg.default.clip_gradient,
            cfg.default.e2e_lr) == (0.1, 1.0, 1e-6)
    assert evabyte.build_lm(cfg).net.num_layers == 32
    with pytest.raises(ValueError, match="letters V alone"):
        evabyte.build_lm(generate_config(
            "evabyte_tiny", "synthetic_tokens", network__layer_pattern="VS"))
    with pytest.raises(ValueError, match="as many key-value heads"):
        evabyte.build_lm(generate_config(
            "evabyte_tiny", "synthetic_tokens",
            network__num_key_value_heads=1))


def test_the_cells_parameter_count():
    """Four layers at published widths, the embedding, the final norm and
    eight heads: 821.37 M parameters, counted from the program's own
    leaves."""
    from benchmark import run as bench_run
    from benchmark.drivers import lm_train
    from mx_rcnn_tpu.models import build_model

    cell = bench_run.load_cell("evabyte-4l-pp8.train-16k")
    cfg = lm_train.program_config(cell["config"], cell["traffic"], False)
    shapes = jax.eval_shape(build_model(cfg).init_variables,
                            jax.random.PRNGKey(0))[0]
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    assert n == 4 * layer + 320 * 4096 + 8 * 4096 * 320 + 4096
    assert n == 821_366_784


class _Record:
    def __init__(self):
        self.rows = []

    def event(self, kind, **fields):
        self.rows.append((kind, fields))


def test_train_net_trains_the_family_with_its_counters_and_spans():
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net

    cfg = generate_config("evabyte_tiny", "synthetic_tokens",
                          obs__enabled=True, train__shuffle=False,
                          train__seq_len=128)
    # bytes a model can learn: every row counts up in threes
    rows = ((np.arange(128)[None, :] * 3 + np.arange(32)[:, None]) % 320
            ).astype(np.int32)
    rec = _Record()
    state = train_net(cfg, prefix=None, end_epoch=1, seed=3, roidb=rows,
                      run_record=rec)
    assert int(state.step) == 16
    logs = [f for kind, f in rec.rows if kind == "log"]
    assert len(logs) == 4 and all(np.isfinite(f["loss"]) for f in logs)
    for f in logs:
        assert f["eva_count_misses"] == 0.0
        assert f["eva_summary_keys_pct"] == pytest.approx(
            _summary_pct(128, 64, 16), rel=1e-6)
        assert not any(k.startswith("moe_") for k in f)
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
        state, TokenBatch(jax.ShapeDtypeStruct((2, 256), jnp.int32)),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    for scope in ("embed", "eva/mixer", "eva_pool", "eva_attend",
                  "dense_mlp", "lm_head", "optimizer"):
        assert scope in text, scope
    # no expert layer and no indexer: none of their scopes
    assert "moe_route" not in text and "dsa_" not in text
