"""The ``nemotron_h`` family on the CPU at its tiny preset: each new op
against a plain form of itself, the share of the experts tied to the uncut
layer, the whole model's step against the benchmark's plain reference
(``benchmark/reference/lm.py``), and the family through ``train_net``.

Tolerances: float32 comparisons hold to 2e-5 (two orders of summation of
the same float32 products; the chunked scan multiplies decays where the
recurrence multiplies step by step).  bfloat16 operands carry 8 bits of
mantissa, a relative rounding of 2^-9 = 0.002 an operand; the scan's
products sum a chunk of them, so its outputs are held to 3e-2 of the
output's scale.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops.attention import causal_gqa
from mx_rcnn_tpu.ops.ssd import ssd_scan

from benchmark.reference import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _leave_no_spans():
    """Runs with ``obs.enabled`` leave their spans in the process-wide
    buffer, which later test files of the same worker read."""
    yield
    from mx_rcnn_tpu.obs import trace as obs_trace

    obs_trace.reset()


def _scan_inputs(seed, s=64, h=4, p=8, g=2, n=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (2, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (2, s, h)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.5)),
            jax.random.normal(k[3], (2, s, g, n)),
            jax.random.normal(k[4], (2, s, g, n)))


def _sequential(x, dt, a, b, c):
    r = x.shape[2] // b.shape[2]
    return jax.vmap(lambda x, dt, b, c: lm.sequential_scan(
        x, dt, a, jnp.repeat(b, r, 1), jnp.repeat(c, r, 1), 16))(x, dt, b, c)


@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_scan_is_the_sequential_recurrence(chunk):
    args = _scan_inputs(0)
    want = jax.jit(_sequential)(*args)
    got = jax.jit(ssd_scan, static_argnums=5)(*args, chunk)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # gradients with respect to every input, through a fixed cotangent
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    g_want = jax.jit(jax.grad(lambda *a: jnp.sum(_sequential(*a) * cot),
                              argnums=(0, 1, 2, 3, 4)))(*args)
    g_got = jax.jit(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk) * cot),
                             argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-6)


def test_chunked_scan_in_bfloat16_stays_near_the_recurrence():
    x, dt, a, b, c = _scan_inputs(1)
    want = _sequential(x, dt, a, b, c)
    got = ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                   c.astype(jnp.bfloat16), 16)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    err = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2)))
    assert err < 3e-2 * scale, (err, scale)


def test_scan_refuses_a_sequence_its_chunk_does_not_divide():
    with pytest.raises(ValueError):
        ssd_scan(*_scan_inputs(0, s=24), 16)


@pytest.mark.parametrize("block_q", [8, 32])
def test_blocked_causal_gqa_is_the_full_masked_form(block_q):
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k[0], (2, 32, 4, 8))
    kk = jax.random.normal(k[1], (2, 32, 2, 8))
    v = jax.random.normal(k[2], (2, 32, 2, 8))

    def full(q, kk, v):
        kr, vr = jnp.repeat(kk, 2, 2), jnp.repeat(v, 2, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * 8 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)

    np.testing.assert_allclose(
        jax.jit(causal_gqa, static_argnums=3)(q, kk, v, block_q),
        full(q, kk, v), rtol=2e-5, atol=2e-5)
    g_got = jax.jit(jax.grad(
        lambda *a: jnp.sum(causal_gqa(*a, block_q) ** 2),
        argnums=(0, 1, 2)))(q, kk, v)
    g_want = jax.jit(jax.grad(lambda *a: jnp.sum(full(*a) ** 2),
                              argnums=(0, 1, 2)))(q, kk, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# ---- the share is tied to the model -------------------------------------------

def _moe_setup(seed=3, tokens=48, hidden=16, experts=8, width=12, top_k=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"router": jax.random.normal(k[0], (hidden, experts)),
         "experts_up": 0.3 * jax.random.normal(k[1], (experts, hidden, width)),
         "experts_down": 0.3 * jax.random.normal(k[2], (experts, width, hidden)),
         "shared_up": 0.3 * jax.random.normal(k[3], (hidden, 2 * width)),
         "shared_down": 0.3 * jax.random.normal(k[4], (2 * width, hidden))}
    x = jax.random.normal(k[5], (tokens, hidden))
    net = {"network": {"first_expert": 0}, "n_routed_experts": experts,
           "num_experts_per_tok": top_k, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    return net, p, x


def _share(x, p, held, top_k=2, capacity=None):
    """What the chip that holds ``held`` adds: its experts' part alone."""
    first, count = held
    idx, w = moe_ops.route(x, p["router"], 0.0, top_k, 2.5, True)
    cap = capacity or moe_ops.row_capacity(x.shape[0], top_k, 8, count, 8.0)
    routed = moe_ops.held_assignments(idx, w, held, cap)
    return moe_ops.held_experts(
        x, routed, p["experts_up"][first:first + count],
        p["experts_down"][first:first + count]), routed


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The routed parts that all shares give (each consecutive pair of the 8
    experts in turn) plus the shared expert counted once are the uncut
    reference layer's output, and the same for the gradient with respect to
    the input."""
    net, p, x = _moe_setup()
    mm = lambda a, w: jnp.dot(a, w)  # noqa: E731

    def uncut(x):
        return lm._moe(net, p, x, mm, None)[0]

    def shares(x):
        total = moe_ops.relu2_ffn(x, p["shared_up"], p["shared_down"])
        for first in range(0, 8, 2):
            total = total + _share(x, p, (first, 2))[0]
        return total

    np.testing.assert_allclose(jax.jit(shares)(x), jax.jit(uncut)(x),
                               rtol=2e-5, atol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    np.testing.assert_allclose(
        jax.jit(jax.grad(lambda x: jnp.sum(shares(x) * cot)))(x),
        jax.jit(jax.grad(lambda x: jnp.sum(uncut(x) * cot)))(x),
        rtol=2e-4, atol=2e-5)
    # every assignment falls on exactly one share
    sizes = [_share(x, p, (first, 2))[1].sizes for first in range(0, 8, 2)]
    assert int(sum(s.sum() for s in sizes)) == x.shape[0] * 2


def test_rows_beyond_the_capacity_are_counted_not_hidden():
    net, p, x = _moe_setup()
    full, routed = _share(x, p, (0, 4))
    assert int(routed.overflow) == 0
    # the groups cover the whole capacity: the last takes the empty rows
    assert int(routed.group_sizes.sum()) == routed.token.shape[0]
    assert (np.asarray(routed.group_sizes[:-1])
            == np.asarray(routed.sizes[:-1])).all()
    assert int(routed.valid.sum()) == int(routed.sizes.sum())
    cut, routed = _share(x, p, (0, 4), capacity=8)
    assert int(routed.overflow) == int(routed.sizes.sum()) - 8 > 0
    assert int(routed.group_sizes.sum()) == 8
    assert not np.allclose(cut, full)


def test_row_capacity_is_a_bound_or_a_stated_factor():
    # all experts held: the bound that cannot be exceeded
    assert moe_ops.row_capacity(100, 2, 8, 8, 2.0) == 200
    # the cell: twice 16384 x 6 x 8 / 128
    assert moe_ops.row_capacity(16384, 6, 128, 8, 2.0) == 12288


# ---- the whole tiny model against the plain reference -------------------------

def _tiny():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    from bench_tiny_lm import tiny_lm_cell

    from benchmark.drivers import lm_train

    cell = tiny_lm_cell()
    return cell, lm_train.program_config(cell["config"], cell["traffic"],
                                         False)


def test_loss_gradients_and_one_adamw_step_match_the_reference():
    from mx_rcnn_tpu.core.optim import make_optimizer
    from mx_rcnn_tpu.core.train import TokenBatch, TrainState, make_train_step
    from mx_rcnn_tpu.models import build_model

    cell, cfg = _tiny()
    config = cell["config"]
    params = lm.make_weights(config, 7)
    ids = np.random.RandomState(0).randint(0, 256, (2, 2, 64)).astype(np.int32)
    want = lm.reference_steps(config, config["optimizer"], params, list(ids))

    model = build_model(cfg)
    tx = make_optimizer(cfg, params, 100, base_lr=config["optimizer"]["lr"])
    state = TrainState(jnp.zeros((), jnp.int32), params, {}, tx.init(params))
    step = jax.jit(make_train_step(model, cfg, tx, mode="lm"))
    s1, m1 = step(state, TokenBatch(ids[0]), jax.random.PRNGKey(0))
    _, m2 = step(s1, TokenBatch(ids[1]), jax.random.PRNGKey(0))
    # float32 on both sides: the loss to 1e-5, every leaf's gradient norm and
    # change to 1e-3 of itself (Adam divides by |g|, which rounding moves)
    assert abs(float(m1["loss"]) - want["losses"][0]) < 1e-5 * want["losses"][0]
    assert abs(float(m2["loss"]) - want["losses"][1]) < 1e-5 * want["losses"][1]
    from benchmark.drivers.lm_train import _adam_mu

    mu = lm.tree_paths(_adam_mu(s1.opt_state))
    moved = lm.tree_paths(jax.tree.map(jnp.subtract, s1.params, params))
    for k, g in want["grad_norm"].items():
        got = float(jnp.linalg.norm(mu[k])) / (1 - 0.9)
        assert abs(got - g) <= 1e-3 * g + 1e-9, k
        d = float(jnp.linalg.norm(moved[k]))
        assert abs(d - want["first_delta_norm"][k]) <= (
            2e-3 * want["first_delta_norm"][k] + 1e-9), k
    assert np.asarray(m1["moe_expert_rows"]).astype(int).tolist() == (
        want["counts"])
    assert float(m1["moe_overflow"]) == 0.0
    assert set(lm.tree_paths(s1.params)) == set(want["grad_norm"])


def test_presets_build_what_they_name():
    cfg = generate_config("nemotron_h", "synthetic_tokens")
    n = cfg.network
    assert (n.hidden_size, n.mamba_num_heads * n.mamba_head_dim,
            n.ssm_state_size, n.n_routed_experts, n.num_experts_per_tok,
            len(n.layer_pattern)) == (2688, 4096, 128, 128, 6, 52)
    assert (cfg.default.wd, cfg.default.clip_gradient, cfg.train.seq_len) == (
        0.1, 1.0, 8192)
    # the detectors' presets are what they were
    det = generate_config("resnet101", "coco")
    assert det.network.family == "detector" and det.default.wd == 0.0005
    from mx_rcnn_tpu.models import FasterRCNN, build_model

    assert isinstance(build_model(generate_config("tiny", "synthetic")),
                      FasterRCNN)


def test_adamw_decays_matrices_alone_and_clips_the_whole_gradient():
    from mx_rcnn_tpu.core.optim import make_optimizer

    _, cfg = _tiny()
    params = {"w": jnp.ones((3, 3)), "v": jnp.ones((3,))}
    tx = make_optimizer(cfg, params, 10, base_lr=0.1)
    zero = jax.tree.map(jnp.zeros_like, params)
    upd, _ = tx.update(zero, tx.init(params), params)
    assert np.allclose(upd["w"], -0.1 * 0.1) and np.allclose(upd["v"], 0.0)
    big = jax.tree.map(lambda p: 100.0 * jnp.ones_like(p), params)
    _, state = tx.update(big, tx.init(params), params)
    mu = [s.mu for s in jax.tree.leaves(
        state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")][0]
    norm = np.sqrt(sum(float(jnp.sum(m * m)) for m in jax.tree.leaves(mu)))
    assert abs(norm / (1 - 0.9) - 1.0) < 1e-5   # clipped to a norm of 1


# ---- through train_net --------------------------------------------------------

def _structured_tokens(rows=32, s=64, vocab=256):
    return ((np.arange(s)[None, :] * 3 + np.arange(rows)[:, None]) % vocab
            ).astype(np.int32)


class _Record:
    def __init__(self):
        self.rows = []

    def event(self, kind, **fields):
        self.rows.append((kind, fields))


def test_train_net_trains_the_family_and_resumes(tmp_path):
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net

    cfg = generate_config("nemotron_h_tiny", "synthetic_tokens",
                          obs__enabled=True, train__shuffle=False)
    rec = _Record()
    prefix = str(tmp_path / "lm")
    state = train_net(cfg, prefix=prefix, end_epoch=1, seed=3,
                      roidb=_structured_tokens(), run_record=rec)
    assert int(state.step) == 16
    logs = [f for kind, f in rec.rows if kind == "log"]
    assert len(logs) == 4 and all(np.isfinite(f["loss"]) for f in logs)
    assert logs[-1]["loss"] < logs[0]["loss"] - 0.5
    for f in logs:
        assert f["moe_overflow"] == 0.0
        assert 0.0 < f["moe_assignments_per_token"] < 2.0
        assert f["moe_load_max_over_mean"] >= 1.0
    names = {e["name"] for e in obs_trace.events()}
    assert {"setup.loader", "setup.init", "train.data_wait", "train.dispatch",
            "train.sync", "train.log", "train.hooks", "train.snapshot",
            "stage.assemble", "stage.place"} <= names
    assert os.path.exists(f"{prefix}-0001.ckpt")
    # resumed: the second epoch starts from the first's snapshot
    rec2 = _Record()
    state2 = train_net(cfg, prefix=prefix, end_epoch=2, seed=3, resume=True,
                       roidb=_structured_tokens(), run_record=rec2)
    assert int(state2.step) == 32
    first = [f for kind, f in rec2.rows if kind == "log"][0]
    assert first["loss"] < logs[-1]["loss"]


def test_weights_are_handed_over_in_memory():
    from mx_rcnn_tpu.obs import trace as obs_trace
    from mx_rcnn_tpu.tools.train import train_net

    cell, cfg = _tiny()
    cfg = cfg.replace_in("obs", enabled=True)
    weights = lm.make_weights(cell["config"], 5)
    want = float(lm.batch_loss_and_grads(
        cell["config"], weights, _structured_tokens(2), grads=False)[0])
    rec = _Record()
    train_net(cfg, prefix=None, end_epoch=1, seed=1,
              roidb=_structured_tokens(4), init_from={"params": weights},
              run_record=rec, frequent=1)
    loads = [e for e in obs_trace.events() if e["name"] == "setup.load"]
    assert len(loads) == 1
    first = [f for kind, f in rec.rows if kind == "log"][0]
    assert abs(first["loss"] - want) < 1e-4 * want


def test_token_loader_order_shuffle_and_skip():
    from mx_rcnn_tpu.data.tokens import TokenLoader, load_token_source

    _, cfg = _tiny()
    src = _structured_tokens(10)
    plain = TokenLoader(src, cfg, 4, shuffle=False)
    assert len(plain) == 2
    rows = [b.ids for b in plain]
    assert np.array_equal(np.concatenate(rows), src[:8])
    plain.skip_next_batches(1)
    assert np.array_equal(next(iter(plain)).ids, src[4:8])
    mixed = TokenLoader(src, cfg, 4, shuffle=True, seed=2)
    mixed.set_epoch(1)
    a = np.concatenate([b.ids for b in mixed])
    mixed.set_epoch(1)
    assert np.array_equal(a, np.concatenate([b.ids for b in mixed]))
    assert not np.array_equal(a, src[:8])
    with pytest.raises(ValueError):
        TokenLoader(src[:, :32], cfg, 4)
    made = load_token_source(cfg, seed=4)
    assert made.shape[1] == 64 and made.max() < 256
    assert np.array_equal(made, load_token_source(cfg, seed=4))


def test_token_file_is_a_source(tmp_path):
    from mx_rcnn_tpu.data.tokens import load_token_source

    cfg = generate_config("nemotron_h_tiny", "tokens",
                          dataset__dataset_path=str(tmp_path))
    np.save(tmp_path / "train.npy", np.arange(64 * 3 + 5) % 256)
    ids = load_token_source(cfg)
    assert ids.shape == (3, 64) and int(ids[1, 0]) == 64
    np.save(tmp_path / "train.npy", np.full((2, 64), 999))
    with pytest.raises(ValueError):
        load_token_source(cfg)
