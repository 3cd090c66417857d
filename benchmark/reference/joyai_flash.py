"""The plain reference of the ``joyai_flash`` family (JoyAI-LLM-Flash,
DeepSeek-V3's modelling): forward, the two losses, their gradients and the
first AdamW step in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time.

Independent of ``mx_rcnn_tpu/``: latent attention forms the full masked
score matrix of a block of queries, the expert layer loops over the held
experts with a dense mask and computes every held assignment (no row
capacity), the router sorts, the rotary term and the multi-token-prediction
module are written here, and the module runs on its own ``S - 2`` positions.
The rounding, the RMSNorm, the SwiGLU, the sort, the AdamW step and the leaf
norms are ``reference/lm.py``'s and ``reference/ling_flash.py``'s (plain code
of the same kind, none of the program's).  It is given the same share as
the program: the experts ``held`` of the router's
``published.n_routed_experts`` outputs, the vocabulary's slice, the published
layers ``network.first_layer`` onwards and the module.

Equations (keys of the model's ``config.json``; arXiv:2405.04434 section 2.1,
arXiv:2412.19437 sections 2.1-2.2); every block is ``x + f(RMSNorm(x))``,
no bias anywhere:

* MLA: ``c_q = RMSNorm(W_qa x)`` (``q_lora_rank``), ``q = W_qb c_q`` in 32 x
  (128 | 64); ``[c_kv | k_r] = W_kva x``; ``[k_n | v] = W_kvb RMSNorm(c_kv)``;
  the rotary term on the 64 of ``q`` and on ``k_r`` (one a position for all
  heads), **adjacent channels paired** (``rope_interleave``: 2j with 2j + 1,
  frequency ``theta^(-2j/64)``); causal softmax of ``q . k / sqrt(192)``;
  ``W_o``.  No head norm, no gate;
* MLP: dense SwiGLU in layers before ``first_k_dense_replace``, else sigmoid
  router scores in float32, the top ``num_experts_per_tok`` of ``scores +
  bias`` among **all** experts (``n_group`` 1), weights the chosen scores
  over their sum times ``routed_scaling_factor``, gated experts and one
  shared expert;
* final RMSNorm ``norm_f``, untied head: ``L_main`` the mean cross-entropy of
  positions ``0 .. S-2`` against token ``i + 1``;
* the module, positions ``0 .. S-3``: ``h' = [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(h_i)] W_eh`` with ``h_i`` the stack's output **after**
  ``norm_f``, one more block of the kind above on those positions (rotary
  positions ``0 .. S-3``), ``RMSNorm_s``, **the same head**: ``L_mtp`` the mean
  cross-entropy against token ``i + 2``; ``Emb`` and the head are the
  stack's own arrays;
* ``L = L_main + network.mtp_loss_weight * L_mtp``.

Departures from the published description: the configuration file's
``assumed`` (the module's weight, which ``h_i`` it reads and the order of the
halves; the router's bias held at zero; no document reset).

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

FAULTS = ("mtp_shift_one", "mtp_embed_unshifted", "mtp_halves_swapped",
          "mtp_weight_0", "mtp_weight_1", "mtp_h_before_norm",
          "mtp_shared_cut", "no_q_norm", "rope_halves", "no_rope",
          "no_scale", "no_shared")
INIT_STD = 0.02
# the small vectors whose gradients are compared as vectors, not by their
# norm (``reference/joyai_compare.py``): every block's two latent norm
# scales read, channel by channel, the low-rank query's norm and what the
# rotary term and its pairing make of the scores (a rotation keeps every
# norm the leaf measures see); the module's three norm scales read its
# shift, the order of its halves and its weight; the stack's final norm,
# through which the module reads ``h``, reads whether it does
LATENT_LEAVES = ("q_a_norm", "kv_a_norm")
MTP_LEAVES = ("enorm", "hnorm", "shared_head_norm")
FINAL_LEAVES = ("final_norm",)
SCAN_LEAVES = LATENT_LEAVES + MTP_LEAVES + FINAL_LEAVES
# the two arrays both paths read, of which a slice stands for the whole:
# the gradient of the embedding's first rows and of the head's first
# columns, as vectors, are the sum of the two paths' (the leaf's norm moves
# by a few hundredths where the module's part is cut off).  256: at 16 a
# few of the embedding's rows, each the gradient of a position or two,
# carried the slice's norm and its reading swung from 0.011 to 0.062 with
# the seed
SHARED_LEAVES = ("embed", "head")
SHARED_SLICE = 256


# ---- weights ---------------------------------------------------------------

def held(net: Dict):
    """(first, count) of the router's ``published.n_routed_experts``
    outputs whose experts are held here."""
    return net["network"]["first_expert"], net["n_routed_experts"]


def _block_rows(net: Dict, mix, mlp, dense: bool) -> List:
    h, heads = net["hidden_size"], net["num_attention_heads"]
    q_rank, rank = net["q_lora_rank"], net["kv_lora_rank"]
    nope, rope, vd = (net["qk_nope_head_dim"], net["qk_rope_head_dim"],
                      net["v_head_dim"])
    m = mix + ("mixer",)
    rows = [(mix + ("norm",), (h,), "ones"),
            (m + ("q_a_proj",), (h, q_rank), "normal"),
            (m + ("q_a_norm",), (q_rank,), "ones"),
            (m + ("q_b_proj",), (q_rank, heads * (nope + rope)), "normal"),
            (m + ("kv_a_proj",), (h, rank + rope), "normal"),
            (m + ("kv_a_norm",), (rank,), "ones"),
            (m + ("kv_b_proj",), (rank, heads * (nope + vd)), "normal"),
            (m + ("o_proj",), (heads * vd, h), "out"),
            (mlp + ("norm",), (h,), "ones")]
    m = mlp + ("mlp",)
    if dense:
        wide = net["intermediate_size"]
        return rows + [(m + ("gate",), (h, wide), "normal"),
                       (m + ("up",), (h, wide), "normal"),
                       (m + ("down",), (wide, h), "out")]
    count, f = held(net)[1], net["moe_intermediate_size"]
    fs = net["n_shared_experts"] * f
    return rows + [
        (m + ("router",), (h, net["published"]["n_routed_experts"]),
         "normal"),
        (m + ("experts_gate",), (count, h, f), "normal"),
        (m + ("experts_up",), (count, h, f), "normal"),
        (m + ("experts_down",), (count, f, h), "out"),
        (m + ("shared_gate",), (h, fs), "normal"),
        (m + ("shared_up",), (h, fs), "normal"),
        (m + ("shared_down",), (fs, h), "out")]


def param_rows(net: Dict) -> List:
    """[(path, shape, init)] of every parameter, in a fixed order."""
    h, v = net["hidden_size"], net["vocab_size"]
    first = net["network"]["first_layer"]
    rows = [(("embed",), (v, h), "normal")]
    for i in range(net["num_hidden_layers"]):
        rows += _block_rows(net, (f"l{i}_mix",), (f"l{i}_mlp",),
                            first + i < net["first_k_dense_replace"])
    rows += [(("final_norm",), (h,), "ones"), (("head",), (h, v), "normal")]
    if net["num_nextn_predict_layers"]:
        rows += [(("mtp", "enorm"), (h,), "ones"),
                 (("mtp", "hnorm"), (h,), "ones"),
                 (("mtp", "eh_proj"), (2 * h, h), "normal"),
                 (("mtp", "shared_head_norm"), (h,), "ones")]
        rows += _block_rows(net, ("mtp", "mix"), ("mtp", "mlp"), False)
    return rows


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
        key = jax.random.fold_in(root, i)
        node[path[-1]] = (
            jnp.ones(shape, jnp.float32) if init == "ones" else
            (out_std if init == "out" else INIT_STD)
            * jax.random.normal(key, shape, jnp.float32))
    return out


# ---- the model, one sequence ------------------------------------------------

def _turn_pairs(x, theta):
    """The rotary term on x (S, ..., R): channel 2j pairs with 2j + 1."""
    s, r = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(r // 2) * 2.0 / r)
    ang = (jnp.arange(s)[:, None] * freq).reshape(
        (s,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     -1).reshape(x.shape)


def _mla(net, p, x, mm, rnd, fault, block_q=512):
    s = x.shape[0]
    heads, rank = net["num_attention_heads"], net["kv_lora_rank"]
    nope, rope, vd = (net["qk_nope_head_dim"], net["qk_rope_head_dim"],
                      net["v_head_dim"])
    eps, theta = net["rms_norm_eps"], float(net["rope_theta"])
    c_q = mm(x, p["q_a_proj"])
    if fault != "no_q_norm":
        c_q = _rms(c_q, p["q_a_norm"], eps)
    q = mm(c_q, p["q_b_proj"]).reshape(s, heads, nope + rope)
    kva = mm(x, p["kv_a_proj"])
    latent, k_r = _rms(kva[:, :rank], p["kv_a_norm"], eps), kva[:, rank:]
    kv = mm(latent, p["kv_b_proj"]).reshape(s, heads, nope + vd)
    k_n, v = kv[..., :nope], kv[..., nope:]
    q_r = q[..., nope:]
    if fault != "no_rope":
        turn = _turn if fault == "rope_halves" else _turn_pairs
        q_r, k_r = turn(q_r, theta), turn(k_r, theta)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    k = jnp.concatenate([k_n, jnp.repeat(k_r[:, None, :], heads, axis=1)],
                        -1)
    # whole blocks of queries: the rows past the last position see every
    # key and are cut off again
    block_q = min(block_q, s)
    n_blocks = -(-s // block_q)
    q = jnp.pad(q, ((0, n_blocks * block_q - s), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(qb, lo):
        scores = rnd(jnp.einsum("qhd,khd->hqk", rnd(qb), rnd(k))) * (
            nope + rope) ** -0.5
        mask = jnp.arange(s)[None, :] <= (lo + jnp.arange(block_q))[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return rnd(jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v)))

    out = jax.lax.map(lambda a: one(*a),
                      (q.reshape(n_blocks, block_q, heads, nope + rope),
                       jnp.arange(0, n_blocks * block_q, block_q)))
    return mm(out.reshape(n_blocks * block_q, heads * vd)[:s], p["o_proj"])


def _moe(net, p, x, mm, fault):
    """Returns (y, counts (held,)): the held experts' and the shared
    expert's part, and the assignments that fell on each held expert."""
    first, count = held(net)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(jnp.dot(x, p["router"]))
    # the score-correction bias is held at zero (``assumed``): the choice
    # is by the scores themselves, among all experts
    idx = _best(scores, net["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, -1)
    if net["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        w = w * net["routed_scaling_factor"]

    def one(y, held_expert):
        e, gate_w, up_w, down_w = held_expert
        chosen = idx == first + e                     # (S, k) dense mask
        gate = jnp.sum(jnp.where(chosen, w, 0.0), -1)
        return (y + gate[:, None] * _swiglu(x, gate_w, up_w, down_w, mm),
                chosen.sum())

    # one held expert after the other: a scan, so one body to compile
    y, counts = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(count), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    if fault != "no_shared":
        y = y + _swiglu(x, p["shared_gate"], p["shared_up"],
                        p["shared_down"], mm)
    return y, counts


def _layer(net, p_mix, p_mlp, x, dense, mm, rnd, fault):
    """One layer's two residual blocks -> (x, counts or None)."""
    eps = net["rms_norm_eps"]

    def residual(x, p, inner, f):
        @jax.checkpoint
        def block(x, p):
            y, c = f(p[inner], rnd(_rms(x, p["norm"], eps)))
            return rnd(x + y), c
        return block(x, p)

    x, _ = residual(x, p_mix, "mixer", lambda p, h: (
        _mla(net, p, h, mm, rnd, fault), None))
    if dense:
        return residual(x, p_mlp, "mlp", lambda p, h: (
            _swiglu(h, p["gate"], p["up"], p["down"], mm), None))
    return residual(x, p_mlp, "mlp", lambda p, h: _moe(net, p, h, mm, fault))


def _cross_entropy(h, head, targets, mm):
    logits = mm(h, head)
    return jnp.sum(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[:, None], -1)[:, 0])


def sequence_losses(net: Dict, params: Dict, ids, precision="float32",
                    fault=None):
    """Of one sequence ``ids`` (S,): (sum of the next-token cross-entropies
    over positions 0 .. S-2, sum of the module's over positions 0 .. S-3,
    counts (expert layers and the module's, held))."""
    rnd = _rounding(precision)
    eps, first = net["rms_norm_eps"], net["network"]["first_layer"]

    def mm(a, w):
        return rnd(jnp.dot(rnd(a), rnd(w)))

    with jax.default_matmul_precision("highest"):
        x = rnd(params["embed"][ids])
        counts = []
        for i in range(net["num_hidden_layers"]):
            x, c = _layer(net, params[f"l{i}_mix"], params[f"l{i}_mlp"], x,
                          first + i < net["first_k_dense_replace"], mm, rnd,
                          fault)
            if c is not None:
                counts.append(c)
        h = rnd(_rms(x, params["final_norm"], eps))
        main = _cross_entropy(h[:-1], params["head"], ids[1:], mm)
        if not net["num_nextn_predict_layers"]:
            return main, jnp.zeros(()), jnp.stack(counts)
        # ---- the module: positions 0 .. S-3 ---------------------------
        m = params["mtp"]
        table, head = params["embed"], params["head"]
        if fault == "mtp_shared_cut":
            table, head = (jax.lax.stop_gradient(table),
                           jax.lax.stop_gradient(head))
        nxt = ids[:-2] if fault == "mtp_embed_unshifted" else ids[1:-1]
        e = rnd(_rms(rnd(table[nxt]), m["enorm"], eps))
        seen = x if fault == "mtp_h_before_norm" else h
        g = rnd(_rms(seen[:-2], m["hnorm"], eps))
        halves = [g, e] if fault == "mtp_halves_swapped" else [e, g]
        y = mm(jnp.concatenate(halves, -1), m["eh_proj"])
        y, c = _layer(net, m["mix"], m["mlp"], y, False, mm, rnd, fault)
        counts.append(c)
        y = rnd(_rms(y, m["shared_head_norm"], eps))
        targets = ids[1:-1] if fault == "mtp_shift_one" else ids[2:]
        return (main, _cross_entropy(y, head, targets, mm),
                jnp.stack(counts))


def mtp_weight(net: Dict, fault=None) -> float:
    return {"mtp_weight_0": 0.0, "mtp_weight_1": 1.0}.get(
        fault, net["network"]["mtp_loss_weight"])


# ---- the steps ---------------------------------------------------------------

# The reference is compiled for every run, and again for every planted fault;
# how fast it then runs matters little.  At the compiler's least effort for
# the executable's speed the gradient program compiles in 21 s where it took
# 140 s (16 experts in a Python loop: 260 s), for the described v5e as on
# the chip; no number is computed otherwise.
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0}


def batch_loss_and_grads(net, params, ids, precision="float32", fault=None,
                         grads=True):
    """``L`` of the batch ``ids`` (B, S), its gradient (or None), the
    per-expert counts and the module's loss alone, one sequence at a
    time."""
    b, s = ids.shape
    lam = mtp_weight(net, fault)

    def one(p, row):
        main, mtp, counts = sequence_losses(net, p, row, precision, fault)
        mtp = mtp / (b * (s - 2))
        return main / (b * (s - 1)) + lam * mtp, (counts, mtp)

    fn = jax.jit(jax.value_and_grad(one, has_aux=True) if grads else one,
                 compiler_options=QUICK_COMPILE)
    add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                  donate_argnums=(0,))
    loss, mtp_loss, counts, acc = 0.0, 0.0, 0, None
    for row in ids:
        if grads:
            (l, (c, m)), g = fn(params, jnp.asarray(row))
            acc = g if acc is None else add(acc, g)
        else:
            l, (c, m) = fn(params, jnp.asarray(row))
        loss, mtp_loss = loss + float(l), mtp_loss + float(m)
        counts = counts + jax.device_get(c)
    return loss, acc, counts, mtp_loss


def scan_grads(tree):
    """{path: leaf} of the vectors ``SCAN_LEAVES`` names in a gradient
    tree, and the slices of ``SHARED_LEAVES``."""
    out = {k: v for k, v in tree_paths(tree).items()
           if k[-1] in SCAN_LEAVES}
    out["embed",] = tree["embed"][:SHARED_SLICE]
    out["head",] = tree["head"][:, :SHARED_SLICE]
    return out


def reference_steps(net: Dict, opt: Dict, params: Dict, batches,
                    precision="float32", fault=None) -> Dict:
    """Follow the first two steps on ``batches`` ([(B, S) ids] x 2), as
    ``lm.reference_steps`` does: step 1's loss ``L``, the module's loss
    alone, the clipped gradient and per-expert counts, its AdamW update,
    step 2's loss on the updated parameters.  Returns ``losses``,
    ``mtp_losses`` (step 1's), ``grad_norm`` and ``first_delta_norm``
    ({path: norm}), ``scan_grad`` ({path: the clipped gradient of a vector
    of ``SCAN_LEAVES``}), ``counts`` (expert layers and the module's x held,
    step 1)."""
    loss1, grads, counts, mtp1 = batch_loss_and_grads(
        net, params, batches[0], precision, fault)
    new, clipped = adamw_first_step(params, grads, opt)
    del grads
    grad_norm = _norms(clipped)
    scan_grad = jax.device_get(scan_grads(clipped))
    del clipped
    delta = _norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        new, params))
    loss2, _, _, _ = batch_loss_and_grads(net, new, batches[1], precision,
                                          fault, grads=False)
    return {"losses": [loss1, loss2], "mtp_losses": [mtp1],
            "grad_norm": grad_norm, "scan_grad": scan_grad,
            "first_delta_norm": delta, "counts": counts.tolist()}
