"""The plain reference of the ``keye_vl2`` family (Keye-VL-2.0's language
stack): forward, the next-token loss and every layer's indexer loss, their
gradients and the first AdamW step in straightforward ``jax.numpy``,
float32 under ``jax.default_matmul_precision("highest")``, one sequence at
a time.

Independent of ``mx_rcnn_tpu/``: attention forms the full masked score
matrix of a block of queries against the keys up to its group's end, the
indexer's rows of that block are scored in full and chosen with
``lax.top_k`` in float32, the expert layer loops over the held experts with
a dense mask and computes every held assignment (no row capacity), the
router sorts, the rotary term and the LayerNorm are written here.  The
RMSNorm, the SwiGLU, the sort, the AdamW step and the leaf norms are
``reference/lm.py``'s and ``reference/ling_flash.py``'s (plain code of the
same kind, none of the program's).  It is given the same share as the
program: the experts ``held`` of the router's ``published.num_experts``
outputs and the vocabulary's slice.

Equations (keys of the model's ``config.json`` and its ``sa_config``; the
DeepSeek-V3.2-Exp report's DeepSeek Sparse Attention); every block is ``x +
f(RMSNorm(x))``, no bias anywhere but the indexer's LayerNorm:

* attention: ``q = x W_q`` (32 x 128), ``k = x W_k``, ``v = x W_v`` (4 x
  128); an RMSNorm over each head's ``q`` and ``k``; the rotary term on all
  128 channels, channel j with j + 64, frequency ``rope_theta^(-2j/128)``;
  query head h reads key-value head h // 8; scale 128^-1/2; each query
  attends to its chosen keys ``S_t`` alone; ``W_o``;
* indexer, on ``x̄ = stop_gradient(x)``: ``qi = x̄ W_IQ`` (16 x 64), ``ki =
  LayerNorm(x̄ W_IK)`` (64), the rotary term on the first
  ``network.indexer_rope_dim`` channels of both, ``w = x̄ W_w / 4``;
  ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s] / 8)``; ``S_t`` the
  ``topk`` largest of ``I[t, s <= t]`` (every ``s <= t`` while there are
  fewer), ``lax.top_k``'s ties;
* the indexer's loss, a layer: the mean over positions of ``KL(p[t] ||
  softmax_{S_t} I[t])`` with ``p[t, s] = stop_gradient(sum_h P[t, h, s]) /
  32``, ``P`` the attention's probabilities;
* MLP: softmax router scores over all experts in float32, the top
  ``num_experts_per_tok`` by sorting, their scores over their sum, gated
  experts of ``moe_intermediate_size``, no shared expert;
* final RMSNorm, untied head: ``L_main`` the mean cross-entropy of positions
  ``0 .. S-2`` against token ``i + 1``; ``L = L_main + sum_layers L^I``.

Departures from the published description: the configuration file's
``assumed`` (the head norms, the indexer's details, its loss weight, no
balancing loss, no document reset, text positions only).

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

from benchmark.reference.ling_flash import _best, _swiglu, _turn
from benchmark.reference.lm import (_norms, _rms, _rounding,  # noqa: F401
                                    adamw_first_step, tree_paths)

FAULTS = ("dense", "topk_half", "no_weights", "no_relu", "target_one_head",
          "not_detached", "no_index_rope", "no_head_norms",
          "sigmoid_router", "no_renorm")
INIT_STD = 0.02
# the small vectors whose gradients are compared as vectors, not by their
# norm (``reference/keye_compare.py``): the attention's head norms read,
# channel by channel, whether they are there and what the rotary term makes
# of the scores; the indexer's head weights and its key's LayerNorm read
# its score's terms (the weights, the ReLU, its rotary term) and its target
HEAD_LEAVES = ("q_norm", "k_norm")
INDEX_LEAVES = ("index_weights_proj", "index_k_norm", "index_k_norm_bias")
SCAN_LEAVES = HEAD_LEAVES + INDEX_LEAVES
# queries a block of the attention, and groups of queries whose blocks see
# the keys up to the group's end: a block's scores are 32 x 128 x 32768
# float32 at most (0.5 GB)
BLOCK_Q = 64
GROUPS = 2
# tokens a chunk of the expert layer and of the head (each a function of
# one token alone)
TOKEN_CHUNK = 4096
# The reference is compiled for every run, and again for every planted fault:
# at the compiler's least effort for the executable's speed
# (``reference/joyai_flash.py``)
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0}


# ---- weights ---------------------------------------------------------------

def held(net: Dict):
    """(first, count) of the router's ``published.num_experts`` outputs
    whose experts are held here."""
    return net["network"]["first_expert"], net["num_experts"]


def sample_rows(s: int):
    """The query rows whose chosen keys are compared (the program hands out
    the same four)."""
    return tuple(sorted({s // 4 + 1, s // 2 + 3, (3 * s) // 4 + 5, s - 1}))


def _layer_rows(net: Dict, i: int) -> List:
    h, hq, hkv, d = (net["hidden_size"], net["num_attention_heads"],
                     net["num_key_value_heads"], net["head_dim"])
    sa = net["sa_config"]
    ji, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    count, f = held(net)[1], net["moe_intermediate_size"]
    m, e = (f"l{i}_mix", "mixer"), (f"l{i}_mlp", "mlp")
    return [((f"l{i}_mix", "norm"), (h,), "ones"),
            (m + ("q_proj",), (h, hq * d), "normal"),
            (m + ("k_proj",), (h, hkv * d), "normal"),
            (m + ("v_proj",), (h, hkv * d), "normal"),
            (m + ("q_norm",), (d,), "ones"),
            (m + ("k_norm",), (d,), "ones"),
            (m + ("o_proj",), (hq * d, h), "out"),
            (m + ("index_q_proj",), (h, ji * di), "normal"),
            (m + ("index_k_proj",), (h, di), "normal"),
            (m + ("index_k_norm",), (di,), "ones"),
            (m + ("index_k_norm_bias",), (di,), "zeros"),
            (m + ("index_weights_proj",), (h, ji), "normal"),
            ((f"l{i}_mlp", "norm"), (h,), "ones"),
            (e + ("router",), (h, net["published"]["num_experts"]),
             "normal"),
            (e + ("experts_gate",), (count, h, f), "normal"),
            (e + ("experts_up",), (count, h, f), "normal"),
            (e + ("experts_down",), (count, f, h), "out")]


def param_rows(net: Dict) -> List:
    """[(path, shape, init)] of every parameter, in a fixed order."""
    h, v = net["hidden_size"], net["vocab_size"]
    rows = [(("embed",), (v, h), "normal")]
    for i in range(net["num_hidden_layers"]):
        rows += _layer_rows(net, i)
    return rows + [(("final_norm",), (h,), "ones"),
                   (("head",), (h, v), "normal")]


def make_weights(net: Dict, seed) -> Dict:
    """The parameter tree from ``seed`` (an int or a traced int32), every
    leaf on a key of its own: ``fold_in(PRNGKey(seed), row index)``.
    Residual writers start at the published depth's scale."""
    root, out = jax.random.PRNGKey(seed), {}
    out_std = INIT_STD / math.sqrt(2 * net["published"]["num_hidden_layers"])
    for i, (path, shape, init) in enumerate(param_rows(net)):
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        if init in ("ones", "zeros"):
            node[path[-1]] = (jnp.ones if init == "ones" else jnp.zeros)(
                shape, jnp.float32)
        else:
            node[path[-1]] = (out_std if init == "out" else INIT_STD) * (
                jax.random.normal(jax.random.fold_in(root, i), shape,
                                  jnp.float32))
    return out


# ---- the model, one sequence ------------------------------------------------

def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _turn_part(x, theta, width):
    return jnp.concatenate([_turn(x[..., :width], theta), x[..., width:]], -1)


def top_k_of(net, fault=None):
    k = net["sa_config"]["topk"]
    return k // 2 if fault == "topk_half" else k


def _index_scores(qi, w, ki, mm_rnd, fault):
    """qi (Q, J, Di), w (Q, J), ki (K, Di) -> (Q, K)."""
    dots = mm_rnd(jnp.einsum("qjd,kd->qjk", mm_rnd(qi), mm_rnd(ki))) * (
        qi.shape[-1] ** -0.5)
    if fault != "no_relu":
        dots = jax.nn.relu(dots)
    return jnp.sum(dots * w[:, :, None], axis=1)


def _dsa(net, p, x, mm, rnd, fault, sample):
    """One attention block's mixer on one sequence -> (y, sum over
    positions of the indexer's KL, the chosen keys of the ``sample`` rows
    (len, S) bool)."""
    s = x.shape[0]
    hq, hkv, d = (net["num_attention_heads"], net["num_key_value_heads"],
                  net["head_dim"])
    sa = net["sa_config"]
    ji, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps, theta = net["rms_norm_eps"], float(net["rope_theta"])
    q = mm(x, p["q_proj"]).reshape(s, hq, d)
    k = mm(x, p["k_proj"]).reshape(s, hkv, d)
    v = mm(x, p["v_proj"]).reshape(s, hkv, d)
    if fault != "no_head_norms":
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = _turn(q, theta), _turn(k, theta)
    # query head h reads key-value head h // (hq / hkv)
    q = q.reshape(s, hkv, hq // hkv, d)

    xb = x if fault == "not_detached" else jax.lax.stop_gradient(x)
    qi = mm(xb, p["index_q_proj"]).reshape(s, ji, di)
    ki = _layer_norm(mm(xb, p["index_k_proj"]), p["index_k_norm"],
                     p["index_k_norm_bias"], eps)
    if fault != "no_index_rope":
        width = net["network"]["indexer_rope_dim"]
        qi, ki = _turn_part(qi, theta, width), _turn_part(ki, theta, width)
    w = (jnp.ones((s, ji)) if fault == "no_weights"
         else mm(xb, p["index_weights_proj"])) * ji ** -0.5
    top_k = top_k_of(net, fault)

    def chosen_of(scores, qpos):
        """The chosen keys of rows at ``qpos``: (Q, K) bool."""
        causal = jnp.arange(scores.shape[1])[None, :] <= qpos[:, None]
        if fault == "dense":
            return causal
        masked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
        _, idx = jax.lax.top_k(masked, min(top_k, scores.shape[1]))
        picked = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], idx].set(True)
        return picked & causal

    block = min(BLOCK_Q, s)
    size = s // math.gcd(s // block, GROUPS)
    outs, kl = [], 0.0
    for g in range(s // size):
        end = (g + 1) * size
        k_g, v_g, ki_g = k[:end], v[:end], ki[:end]

        @jax.checkpoint
        def one(qb, qib, wb, lo):
            qpos = lo + jnp.arange(block)
            scores = _index_scores(qib, wb, ki_g, rnd, fault)
            chosen = chosen_of(scores, qpos)
            logits = rnd(jnp.einsum("qgrd,kgd->grqk", rnd(qb),
                                    rnd(k_g))) * (d ** -0.5)
            probs = jax.nn.softmax(
                jnp.where(chosen[None, None], logits, -jnp.inf), axis=-1)
            o = rnd(jnp.einsum("grqk,kgd->qgrd", rnd(probs), rnd(v_g)))
            target = jax.lax.stop_gradient(
                probs[0, 0] if fault == "target_one_head"
                else probs.mean((0, 1)))
            logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
            live = chosen & (target > 0)
            part = jnp.sum(jnp.where(live, target * (
                jnp.log(jnp.where(live, target, 1.0))
                - jnp.where(live, logq, 0.0)), 0.0))
            return o, part

        def blocks(t):
            return t[g * size:end].reshape((size // block, block)
                                           + t.shape[1:])

        o, part = jax.lax.map(lambda a: one(*a), (
            blocks(q), blocks(qi), blocks(w),
            g * size + jnp.arange(0, size, block)))
        outs.append(o.reshape(size, hq * d))
        kl = kl + jnp.sum(part)
    rows = jnp.asarray(sample, jnp.int32)
    picks = chosen_of(_index_scores(qi[rows], w[rows], ki, rnd, fault), rows)
    return mm(jnp.concatenate(outs), p["o_proj"]), kl, picks


def _moe(net, p, x, mm, fault):
    """Returns (y, counts (held,)): the held experts' part and the
    assignments that fell on each held expert; ``TOKEN_CHUNK`` tokens at a
    time, each a ``jax.checkpoint`` (the layer is a function of each token
    alone), so that the experts' intermediates exist for one chunk at a
    time."""
    s = x.shape[0]
    chunk = min(TOKEN_CHUNK, s)
    y, counts = jax.lax.map(
        jax.checkpoint(lambda xc: _moe_rows(net, p, xc, mm, fault)),
        x.reshape(s // chunk, chunk, -1))
    return y.reshape(x.shape), counts.sum(0)


def _moe_rows(net, p, x, mm, fault):
    first, count = held(net)
    with jax.default_matmul_precision("highest"):
        logits = jnp.dot(x, p["router"])
    scores = (jax.nn.sigmoid(logits) if fault == "sigmoid_router"
              else jax.nn.softmax(logits, -1))
    idx = _best(scores, net["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, -1)
    if net["norm_topk_prob"] and fault != "no_renorm":
        w = w / (w.sum(-1, keepdims=True) + 1e-20)

    def one(y, held_expert):
        e, gate_w, up_w, down_w = held_expert
        chosen = idx == first + e                     # (S, k) dense mask
        gate = jnp.sum(jnp.where(chosen, w, 0.0), -1)
        return (y + gate[:, None] * _swiglu(x, gate_w, up_w, down_w, mm),
                chosen.sum())

    # one held expert after the other: a scan, so one body to compile
    return jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(count), p["experts_gate"], p["experts_up"],
        p["experts_down"]))


def sequence_losses(net: Dict, params: Dict, ids, precision="float32",
                    fault=None):
    """Of one sequence ``ids`` (S,): (sum of the next-token cross-entropies
    over positions 0 .. S-2, sum over layers and positions of the indexer's
    KL, counts (layers, held), the chosen keys of the sample rows (layers,
    rows, S))."""
    rnd = _rounding(precision)
    eps, s = net["rms_norm_eps"], ids.shape[0]
    sample = sample_rows(s)

    def mm(a, w):
        return rnd(jnp.dot(rnd(a), rnd(w)))

    with jax.default_matmul_precision("highest"):
        x = rnd(params["embed"][ids])
        kl, counts, picks = 0.0, [], []
        for i in range(net["num_hidden_layers"]):
            pm, pe = params[f"l{i}_mix"], params[f"l{i}_mlp"]

            @jax.checkpoint
            def layer(x, pm, pe):
                y, part, pick = _dsa(net, pm["mixer"],
                                     rnd(_rms(x, pm["norm"], eps)), mm, rnd,
                                     fault, sample)
                x = rnd(x + y)
                y, c = _moe(net, pe["mlp"], rnd(_rms(x, pe["norm"], eps)),
                            mm, fault)
                return rnd(x + y), part, c, pick

            x, part, c, pick = layer(x, pm, pe)
            kl, counts, picks = kl + part, counts + [c], picks + [pick]
        h = rnd(_rms(x, params["final_norm"], eps))
        # position t against token t + 1; the last position has none: a
        # chunk of logits at a time, each a ``jax.checkpoint``
        chunk = min(TOKEN_CHUNK, s)
        live = (jnp.arange(s) < s - 1).astype(jnp.float32)

        @jax.checkpoint
        def ce(args):
            hc, tc, wc = args
            logits = mm(hc, params["head"])
            return jnp.sum((jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, tc[:, None], -1)[:, 0]) * wc)

        main = jnp.sum(jax.lax.map(ce, (
            h.reshape(s // chunk, chunk, -1),
            jnp.roll(ids, -1).reshape(s // chunk, chunk),
            live.reshape(s // chunk, chunk))))
        return main, kl, jnp.stack(counts), jnp.stack(picks)


# ---- the steps ---------------------------------------------------------------

def batch_loss_and_grads(net, params, ids, precision="float32", fault=None,
                         grads=True):
    """Of the batch ``ids`` (B, S): ``L_main``, the indexer's loss summed
    over layers, the gradient of their sum (or None), the per-expert counts
    and the first sequence's chosen keys of the sample rows; one sequence
    at a time."""
    b, s = ids.shape

    def one(p, row):
        main, kl, counts, picks = sequence_losses(net, p, row, precision,
                                                  fault)
        main, kl = main / (b * (s - 1)), kl / (b * s)
        return main + kl, (main, kl, counts, picks)

    fn = jax.jit(jax.value_and_grad(one, has_aux=True) if grads else one,
                 compiler_options=QUICK_COMPILE)
    add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                  donate_argnums=(0,))
    main, index, counts, acc, first = 0.0, 0.0, 0, None, None
    for row in ids:
        if grads:
            (_, (m, kl, c, picks)), g = fn(params, jnp.asarray(row))
            acc = g if acc is None else add(acc, g)
        else:
            _, (m, kl, c, picks) = fn(params, jnp.asarray(row))
        main, index = main + float(m), index + float(kl)
        counts = counts + jax.device_get(c)
        if first is None:
            first = jax.device_get(picks)
    return main, index, acc, counts, first


def scan_grads(tree):
    """{path: leaf} of the vectors ``SCAN_LEAVES`` names in a gradient
    tree."""
    return {k: v for k, v in tree_paths(tree).items()
            if k[-1] in SCAN_LEAVES}


def reference_steps(net: Dict, opt: Dict, params: Dict, batches,
                    precision="float32", fault=None) -> Dict:
    """Follow the first two steps on ``batches`` ([(B, S) ids] x 2), as
    ``lm.reference_steps`` does: step 1's ``L_main`` and indexer loss, the
    clipped gradient of ``L`` and per-expert counts, its AdamW update, step
    2's ``L_main`` on the updated parameters.  Returns ``losses`` (of
    ``L_main``), ``index_losses`` (step 1's), ``grad_norm`` and
    ``first_delta_norm`` ({path: norm}), ``scan_grad`` ({path: the clipped
    gradient of a vector of ``SCAN_LEAVES``}), ``counts`` (layers x held,
    step 1), ``chosen`` (step 1's first sequence: layers x sample rows x S,
    0 or 1)."""
    main1, index1, grads, counts, chosen = batch_loss_and_grads(
        net, params, batches[0], precision, fault)
    new, clipped = adamw_first_step(params, grads, opt)
    del grads
    grad_norm = _norms(clipped)
    scan_grad = jax.device_get(scan_grads(clipped))
    del clipped
    delta = _norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        new, params))
    main2, _, _, _, _ = batch_loss_and_grads(net, new, batches[1], precision,
                                             fault, grads=False)
    return {"losses": [main1, main2], "index_losses": [index1],
            "grad_norm": grad_norm, "scan_grad": scan_grad,
            "first_delta_norm": delta, "counts": counts.tolist(),
            "chosen": chosen.astype("int8")}
