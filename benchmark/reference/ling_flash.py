"""The plain reference of the ``ling_flash`` family (Ling-3.0-flash-VL's
language stack): forward, loss, gradients and the first AdamW step in
straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time.

Independent of ``mx_rcnn_tpu/ops`` and ``models``: the gated delta rule is
the **recurrence one position at a time** (a scan step a position; blocks of
``chunk_size`` positions are a ``jax.checkpoint`` so that the backward pass
fits), latent attention forms the full masked score matrix of a block of
queries, the expert layer loops over the held experts with a dense mask,
and the router, the rotary term and the step are written here.  The
rounding, the RMSNorm, the AdamW step and the leaf norms are
``reference/lm.py``'s (plain code of the same kind, none of the program's).
It is given the same share as the program: the experts ``held`` of the
router's ``published.num_experts`` outputs, the vocabulary's slice, the
published layers ``network.first_layer`` onwards.

Equations (keys of the model's ``config.json``; departures are the
configuration file's ``assumed``); every block is ``x + f(RMSNorm(x))``:

* ``K`` (KDA): ``q = L2norm(silu(conv4(W_q x))) / sqrt(128)``, ``k =
  L2norm(silu(conv4(W_k x)))``, ``v = silu(conv4(W_v x))``; ``g = lower *
  sigmoid(exp(A_log_h) (W_f x + dt_bias))`` a channel, ``beta = sigmoid(W_b
  x)`` a head; ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``,
  ``o_t = S_t^T q_t``; out ``W_o [RMSNorm_head(o) sigmoid(W_g x)_h]``;
* ``L`` (MLA): ``q = W_q x`` in 32 x (128 | 64); ``[c | k_r] = W_kva x``;
  ``[k_n | v] = W_kvb RMSNorm(c)``; a learned RMSNorm over each head's 192
  of ``q`` and of ``[k_n | k_r]``; rotary on the last 64, ``k_r`` one a
  position for all heads; causal softmax of ``q . k / sqrt(192)``; the same
  head-wise gate; ``W_o``;
* MLP: dense SwiGLU in the first ``first_k_dense_replace`` layers held,
  else sigmoid router scores in float32, 8 groups scored by their two best,
  4 kept, top 8 among them, weights the chosen scores over their sum times
  ``routed_scaling_factor``, gated experts and one shared expert;
* final RMSNorm, untied head, mean next-token cross-entropy over the slice.

``precision``: 'float32' is the reference proper; 'float8' is the control,
every contraction's operands and output rounded to E5M2 by a plain cast
where the configuration states bfloat16 ('bfloat16' rounds to that, as a
witness).  ``fault`` plants one of ``FAULTS`` so that the comparison can be
shown to catch it.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference.lm import (_norms, _rms, _rounding,  # noqa: F401
                                    adamw_first_step, tree_paths)

FAULTS = ("no_carry", "no_decay", "beta_one", "no_group_limit", "no_rope",
          "no_latent_norm", "no_scale", "no_routed", "no_shared")
INIT_STD = 0.02
# the small vectors whose gradients are compared as vectors, not by their
# norm (``reference/ling_compare.py``): a KDA block's ``A_log`` and
# ``dt_bias`` read the rule's decay and its carried state and nothing else;
# the latent block's three norm scales read, channel by channel, what the
# rotary term and the latent's norm make of the scores (a rotation keeps
# every norm the leaf measures see)
KDA_LEAVES = ("A_log", "dt_bias")
LATENT_LEAVES = ("q_norm", "k_norm", "kv_a_norm")
SCAN_LEAVES = KDA_LEAVES + LATENT_LEAVES
# the fla layer's dt range (its constructor's defaults)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 0.1, 1e-4


# ---- weights ---------------------------------------------------------------

def pattern(net: Dict) -> str:
    """The mixers of the layers run here, by their published index:
    latent attention where ``(index + 1) % layer_group_size == 0``."""
    first = net["network"]["first_layer"]
    return "".join("L" if (first + i + 1) % net["layer_group_size"] == 0
                   else "K" for i in range(net["num_hidden_layers"]))


def held(net: Dict):
    """(first, count) of the router's ``published.num_experts`` outputs
    whose experts are held here."""
    return net["network"]["first_expert"], net["num_experts"]


def param_rows(net: Dict) -> List:
    """[(path, shape, init)] of every parameter, in a fixed order."""
    h, v = net["hidden_size"], net["vocab_size"]
    heads, hd = net["num_attention_heads"], net["head_dim"]
    inner, conv = heads * hd, net["short_conv_kernel_size"]
    rank, nope, rope, vd = (net["kv_lora_rank"], net["qk_nope_head_dim"],
                            net["qk_rope_head_dim"], net["v_head_dim"])
    count, f = held(net)[1], net["moe_intermediate_size"]
    fs, wide = net["moe_shared_expert_intermediate_size"], net[
        "intermediate_size"]
    rows = [(("embed",), (v, h), "normal")]
    for i, kind in enumerate(pattern(net)):
        mix, mlp = (f"l{i}_mix",), (f"l{i}_mlp",)
        rows.append((mix + ("norm",), (h,), "ones"))
        m = mix + ("mixer",)
        if kind == "K":
            rows += [(m + (f"{n}_proj",), (h, inner), "normal")
                     for n in "qkvf"]
            rows += [(m + (f"{n}_conv",), (conv, inner), "conv")
                     for n in "qkv"]
            rows += [(m + ("b_proj",), (h, heads), "normal"),
                     (m + ("g_proj",), (h, heads), "normal"),
                     (m + ("A_log",), (heads,), "a_log"),
                     (m + ("dt_bias",), (inner,), "dt_bias"),
                     (m + ("o_norm",), (hd,), "ones"),
                     (m + ("o_proj",), (inner, h), "out")]
        else:
            rows += [(m + ("q_proj",), (h, heads * (nope + rope)), "normal"),
                     (m + ("kv_a_proj",), (h, rank + rope), "normal"),
                     (m + ("kv_a_norm",), (rank,), "ones"),
                     (m + ("kv_b_proj",), (rank, heads * (nope + vd)),
                      "normal"),
                     (m + ("q_norm",), (nope + rope,), "ones"),
                     (m + ("k_norm",), (nope + rope,), "ones"),
                     (m + ("g_proj",), (h, heads), "normal"),
                     (m + ("o_proj",), (heads * vd, h), "out")]
        rows.append((mlp + ("norm",), (h,), "ones"))
        m = mlp + ("mlp",)
        if i < net["first_k_dense_replace"]:
            rows += [(m + ("gate",), (h, wide), "normal"),
                     (m + ("up",), (h, wide), "normal"),
                     (m + ("down",), (wide, h), "out")]
        else:
            rows += [(m + ("router",), (h, net["published"]["num_experts"]),
                      "normal"),
                     (m + ("experts_gate",), (count, h, f), "normal"),
                     (m + ("experts_up",), (count, h, f), "normal"),
                     (m + ("experts_down",), (count, f, h), "out"),
                     (m + ("shared_gate",), (h, fs), "normal"),
                     (m + ("shared_up",), (h, fs), "normal"),
                     (m + ("shared_down",), (fs, h), "out")]
    return rows + [(("final_norm",), (h,), "ones"),
                   (("head",), (h, v), "normal")]


def _leaf(key, shape, init, net):
    f32 = jnp.float32
    if init == "normal":
        return INIT_STD * jax.random.normal(key, shape, f32)
    if init == "out":   # residual writers, at the published depth
        return (INIT_STD / math.sqrt(2 * net["published"]["num_hidden_layers"])
                * jax.random.normal(key, shape, f32))
    if init == "conv":
        return jax.random.uniform(key, shape, f32, -0.5, 0.5)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if init == "dt_bias":
        lo, hi = math.log(DT_MIN), math.log(DT_MAX)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, f32)
                                 * (hi - lo) + lo), DT_FLOOR)
        return dt + jnp.log(-jnp.expm1(-dt))
    return jnp.ones(shape, f32)


def make_weights(net: Dict, seed) -> Dict:
    """The parameter tree from ``seed`` (an int or a traced int32), every
    leaf on a key of its own: ``fold_in(PRNGKey(seed), row index)``."""
    root, out = jax.random.PRNGKey(seed), {}
    for i, (path, shape, init) in enumerate(param_rows(net)):
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = _leaf(jax.random.fold_in(root, i), shape, init, net)
    return out


# ---- the model, one sequence ------------------------------------------------

def delta_rule(q, k, v, g, beta, block, fault=None):
    """The gated delta rule one position at a time, one sequence: q, k
    (S, H, K); v (S, H, V); g (S, H, K) log-decay; beta (S, H).  Returns o
    (S, H, V).  ``block`` positions are one ``jax.checkpoint``, which
    changes no number."""
    s, heads, dk = q.shape

    def step(state, inp):
        qt, kt, vt, gt, bt = inp
        if fault != "no_decay":
            state = state * jnp.exp(gt)[:, :, None]
        seen = jnp.sum(state * kt[:, :, None], 1)              # S^T k
        u = bt[:, None] * (vt - seen)
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * qt[:, :, None], 1)

    @jax.checkpoint
    def run_block(state, blk):
        if fault == "no_carry":
            state = jnp.zeros_like(state)
        return jax.lax.scan(step, state, blk)

    blocks = jax.tree.map(
        lambda t: t.reshape((s // block, block) + t.shape[1:]),
        (q, k, v, g, beta))
    _, o = jax.lax.scan(
        run_block, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), blocks)
    return o.reshape(s, heads, v.shape[-1])


def _conv_silu(x, w):
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + s] * w[i] for i in range(taps)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(net, p, x, mm, fault):
    s = x.shape[0]
    heads, hd = net["num_attention_heads"], net["head_dim"]
    split = lambda t: t.reshape(s, heads, hd)              # noqa: E731
    q = _unit(split(_conv_silu(mm(x, p["q_proj"]), p["q_conv"]))) * hd ** -0.5
    k = _unit(split(_conv_silu(mm(x, p["k_proj"]), p["k_conv"])))
    v = split(_conv_silu(mm(x, p["v_proj"]), p["v_conv"]))
    g = net["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * split(mm(x, p["f_proj"]) + p["dt_bias"]))
    beta = jax.nn.sigmoid(mm(x, p["b_proj"]))
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g, beta, net["network"]["chunk_size"], fault)
    o = _rms(o, p["o_norm"], net["rms_norm_eps"])
    o = o * jax.nn.sigmoid(mm(x, p["g_proj"]))[:, :, None]
    return mm(o.reshape(s, heads * hd), p["o_proj"])


def _turn(x, theta):
    """The rotary term on x (S, ..., R): channel j pairs with j + R/2."""
    s, r = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(r // 2) * 2.0 / r)
    ang = (jnp.arange(s)[:, None] * freq).reshape(
        (s,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _mla(net, p, x, mm, rnd, fault, block_q=512):
    s = x.shape[0]
    heads, rank = net["num_attention_heads"], net["kv_lora_rank"]
    nope, rope, vd = (net["qk_nope_head_dim"], net["qk_rope_head_dim"],
                      net["v_head_dim"])
    eps, theta = net["rms_norm_eps"], float(net["rope_theta"])
    q = mm(x, p["q_proj"]).reshape(s, heads, nope + rope)
    kva = mm(x, p["kv_a_proj"])
    latent, k_r = kva[:, :rank], kva[:, rank:]
    if fault != "no_latent_norm":
        latent = _rms(latent, p["kv_a_norm"], eps)
    kv = mm(latent, p["kv_b_proj"]).reshape(s, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_r[:, None, :], heads, axis=1)], -1)
    v = kv[..., nope:]
    q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if fault != "no_rope":
        q = jnp.concatenate([q[..., :nope], _turn(q[..., nope:], theta)], -1)
        k = jnp.concatenate([k[..., :nope], _turn(k[..., nope:], theta)], -1)
    block_q = min(block_q, s)

    @jax.checkpoint
    def one(qb, lo):
        scores = rnd(jnp.einsum("qhd,khd->hqk", rnd(qb), rnd(k))) * (
            nope + rope) ** -0.5
        mask = jnp.arange(s)[None, :] <= (lo + jnp.arange(block_q))[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return rnd(jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v)))

    out = jax.lax.map(lambda a: one(*a),
                      (q.reshape(s // block_q, block_q, heads, nope + rope),
                       jnp.arange(0, s, block_q)))
    out = out.reshape(s, heads, vd) * jax.nn.sigmoid(
        mm(x, p["g_proj"]))[:, :, None]
    return mm(out.reshape(s, heads * vd), p["o_proj"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _best(scores, k):
    """Indices of the ``k`` largest along the last axis, the lower index
    first among equals."""
    return jnp.argsort(-scores, axis=-1, stable=True)[..., :k]


def choose(scores, net, fault=None):
    """scores (S, E) -> idx (S, k): the group-limited choice."""
    groups, kept = net["n_group"], net["topk_group"]
    if fault != "no_group_limit":
        s, e = scores.shape
        by_group = scores.reshape(s, groups, e // groups)
        two = jnp.take_along_axis(by_group, _best(by_group, 2), -1).sum(-1)
        chosen = _best(two, kept)                             # (S, kept)
        open_ = jnp.zeros((s, groups), bool).at[
            jnp.arange(s)[:, None], chosen].set(True)
        scores = jnp.where(jnp.repeat(open_, e // groups, axis=1), scores,
                           -jnp.inf)
    return _best(scores, net["num_experts_per_tok"])


def _moe(net, p, x, mm, fault):
    """Returns (y, counts (held,)): the held experts' and the shared
    expert's part, and the assignments that fell on each held expert."""
    first, count = held(net)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(jnp.dot(x, p["router"]))
    idx = choose(scores, net, fault)
    w = jnp.take_along_axis(scores, idx, -1)
    if net["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        w = w * net["routed_scaling_factor"]
    y, counts = jnp.zeros_like(x), []
    for e in range(count):
        chosen = idx == first + e                     # (S, k) dense mask
        counts.append(chosen.sum())
        if fault != "no_routed":
            gate = jnp.sum(jnp.where(chosen, w, 0.0), -1)
            y = y + gate[:, None] * _swiglu(
                x, p["experts_gate"][e], p["experts_up"][e],
                p["experts_down"][e], mm)
    if fault != "no_shared":
        y = y + _swiglu(x, p["shared_gate"], p["shared_up"],
                        p["shared_down"], mm)
    return y, jnp.stack(counts)


def sequence_loss(net: Dict, params: Dict, ids, precision="float32",
                  fault=None):
    """(sum of the next-token cross-entropies of one sequence ``ids`` (S,),
    counts (expert layers, held))."""
    rnd = _rounding(precision)
    eps = net["rms_norm_eps"]

    def mm(a, w):
        return rnd(jnp.dot(rnd(a), rnd(w)))

    def residual(x, p, f):
        @jax.checkpoint
        def block(x, p):
            y, c = f(p, rnd(_rms(x, p["norm"], eps)))
            return rnd(x + y), c
        return block(x, p)

    with jax.default_matmul_precision("highest"):
        x = rnd(params["embed"][ids])
        counts = []
        for i, kind in enumerate(pattern(net)):
            if kind == "K":
                mixer = lambda p, h: (_kda(net, p["mixer"], h, mm, fault),  # noqa: E731
                                      None)
            else:
                mixer = lambda p, h: (_mla(net, p["mixer"], h, mm, rnd,  # noqa: E731
                                           fault), None)
            x, _ = residual(x, params[f"l{i}_mix"], mixer)
            if i < net["first_k_dense_replace"]:
                mlp = lambda p, h: (_swiglu(h, p["mlp"]["gate"], p["mlp"]["up"],  # noqa: E731
                                            p["mlp"]["down"], mm), None)
            else:
                mlp = lambda p, h: _moe(net, p["mlp"], h, mm, fault)  # noqa: E731
            x, c = residual(x, params[f"l{i}_mlp"], mlp)
            if c is not None:
                counts.append(c)
        h = rnd(_rms(x, params["final_norm"], eps))
        logits = mm(h[:-1], params["head"])
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(logits, ids[1:, None], -1)[:, 0]
        return jnp.sum(lse - picked), jnp.stack(counts)


# ---- the steps ---------------------------------------------------------------

def batch_loss_and_grads(net, params, ids, precision="float32", fault=None,
                         grads=True):
    """Mean next-token loss of the batch ``ids`` (B, S), its gradient (or
    None) and the per-expert counts, one sequence at a time."""
    b, s = ids.shape
    scale = 1.0 / (b * (s - 1))

    def one(p, row):
        total, counts = sequence_loss(net, p, row, precision, fault)
        return total * scale, counts

    fn = jax.jit(jax.value_and_grad(one, has_aux=True) if grads else one)
    add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                  donate_argnums=(0,))
    loss, counts, acc = 0.0, 0, None
    for row in ids:
        if grads:
            (l, c), g = fn(params, jnp.asarray(row))
            acc = g if acc is None else add(acc, g)
        else:
            l, c = fn(params, jnp.asarray(row))
        loss, counts = loss + float(l), counts + jax.device_get(c)
    return loss, acc, counts


def scan_grads(tree):
    """{path: leaf} of the vectors ``SCAN_LEAVES`` names in a gradient
    tree."""
    return {k: v for k, v in tree_paths(tree).items()
            if k[-1] in SCAN_LEAVES}


def reference_steps(net: Dict, opt: Dict, params: Dict, batches,
                    precision="float32", fault=None) -> Dict:
    """Follow the first two steps on ``batches`` ([(B, S) ids] x 2), as
    ``lm.reference_steps`` does: step 1's loss, clipped gradient and
    per-expert counts, its AdamW update, step 2's loss on the updated
    parameters.  Returns ``losses``, ``grad_norm`` and ``first_delta_norm``
    ({path: norm}), ``scan_grad`` ({path: the clipped gradient of a vector
    of ``SCAN_LEAVES``}), ``counts`` (expert layers x held, step 1)."""
    loss1, grads, counts = batch_loss_and_grads(
        net, params, batches[0], precision, fault)
    new, clipped = adamw_first_step(params, grads, opt)
    del grads
    grad_norm = _norms(clipped)
    scan_grad = jax.device_get(scan_grads(clipped))
    del clipped
    delta = _norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        new, params))
    loss2, _, _ = batch_loss_and_grads(net, new, batches[1], precision,
                                       fault, grads=False)
    return {"losses": [loss1, loss2], "grad_norm": grad_norm,
            "scan_grad": scan_grad, "first_delta_norm": delta,
            "counts": counts.tolist()}
