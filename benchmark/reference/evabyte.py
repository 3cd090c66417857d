"""The plain reference of the ``evabyte`` family (EvaByte's byte-level
stack): forward, the prediction heads' losses, their gradients and the
first AdamW step in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time.

Independent of ``mx_rcnn_tpu/``: the chunks are pooled here, and attention
forms the full masked score matrix of a block of queries against its
window's summaries and the window's keys, one softmax over both; the
norm, the rotary term, the SwiGLU, the heads' cross-entropies and the
AdamW step are written here or taken from ``reference/lm.py`` and
``reference/ling_flash.py`` (plain code of the same kind, none of the
program's).

Equations (keys of the model's ``config.json``); every block is ``x + f(N(x))``
on a float32 residual stream, ``N(x) = x / sqrt(mean(x^2) + eps) * (1 +
g)``, no bias anywhere:

* EVA attention, per head h (``num_attention_heads`` heads of ``D =
  hidden_size / num_attention_heads`` for queries, keys and values alike):
  ``q = R(x W_q)``, ``k = R(x W_k)``, ``v = x W_v``, ``R`` the rotary term on
  all D channels, channel j with j + D/2, frequency ``rope_theta^(-2j/D)``;
  positions in windows of ``W = window_size`` (``s(t) = W floor(t / W)``)
  and chunks of ``C = chunk_size``; for every chunk c
  ``kbar_c = sum_{j in c} softmax_j(mu_h . k_j / sqrt(D)) k_j`` and
  ``vbar_c = sum_{j in c} softmax_j(phi_h . k_j / sqrt(D)) v_j``; query t's
  softmax runs over ``q_t . k_j / sqrt(D)`` for ``s(t) <= j <= t`` and ``q_t .
  kbar_c / sqrt(D)`` for ``c < s(t) / C``, and takes ``v_j`` and ``vbar_c``
  by its weights; ``W_o``;
* MLP: SwiGLU of ``intermediate_size``;
* final ``N``, then ``num_pred_heads`` untied heads: head i's logits at t
  against token ``t + 1 + i``, positions ``0 .. S-2-i``; ``L`` the mean over
  heads of each head's mean cross-entropy, ``L_main`` head 0's.

Departures from the published description: the configuration file's
``assumed`` (the pooling's form, the rotary term before pooling, the heads'
equal weight, no document reset).

``precision``: 'float32' is the reference proper; 'float8' is the control,
every contraction's operands and output rounded to E5M2 by a plain cast
where the configuration states bfloat16 ('bfloat16' rounds to that, as a
witness).  ``fault`` plants one of ``FAULTS`` so that the comparison can be
shown to catch it: the summaries dropped; summaries of every chunk wholly
before the query's own chunk, its own window's too; a plain mean in place
of the learned pooling; head i's targets one token nearer (``t + i``); the
residual stream summed in bfloat16 (``bf16_residual``: the bfloat16
witness's arithmetic with the stream, which the configuration keeps in
float32, rounded to bfloat16 after the embedding and after each block's
add, as the other sequence families keep theirs).
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference.ling_flash import _turn
from benchmark.reference.lm import _norms, _rounding, tree_paths  # noqa: F401

FAULTS = ("no_summaries", "own_block", "mean_pool", "targets_off",
          "bf16_residual")
# the small vectors whose gradients are compared as vectors, not by their
# norm (``reference/evabyte_compare.py``): the pooling vectors read, channel
# by channel, how the chunks are summarised; the norms' scales read every
# block's output and, the final one, every head's target
EVA_LEAVES = ("adaptive_mu_k", "adaptive_phi")
NORM_LEAVES = ("attn_norm", "mlp_norm", "final_norm")
SCAN_LEAVES = EVA_LEAVES + NORM_LEAVES
# queries a block of the attention: a block's scores are 32 x 512 x (896 +
# 2048) float32 at most (0.2 GB)
BLOCK_Q = 512
# tokens a chunk of the MLP and of the heads (each a function of one token)
TOKEN_CHUNK = 4096
# compiled for every run, and again for every planted fault: at the
# compiler's least effort for the executable's speed
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0}


# ---- weights ---------------------------------------------------------------

def head_dim(net: Dict) -> int:
    return net["hidden_size"] // net["num_attention_heads"]


def param_rows(net: Dict) -> List:
    """[(path, shape, init)] of every parameter, in a fixed order."""
    h, v, f = net["hidden_size"], net["vocab_size"], net["intermediate_size"]
    heads, d = net["num_attention_heads"], head_dim(net)
    rows = [(("embed",), (v, h), "normal")]
    for i in range(net["num_hidden_layers"]):
        b, m, e = f"l{i}", (f"l{i}", "mixer"), (f"l{i}", "mlp")
        rows += [((b, "attn_norm"), (h,), "zeros"),
                 (m + ("q_proj",), (h, heads * d), "normal"),
                 (m + ("k_proj",), (h, heads * d), "normal"),
                 (m + ("v_proj",), (h, heads * d), "normal"),
                 (m + ("o_proj",), (heads * d, h), "normal"),
                 (m + ("adaptive_mu_k",), (heads, d), "pool"),
                 (m + ("adaptive_phi",), (heads, d), "pool"),
                 ((b, "mlp_norm"), (h,), "zeros"),
                 (e + ("gate",), (h, f), "normal"),
                 (e + ("up",), (h, f), "normal"),
                 (e + ("down",), (f, h), "normal")]
    return rows + [(("final_norm",), (h,), "zeros"),
                   (("heads",), (net["num_pred_heads"], h, v), "normal")]


def make_weights(net: Dict, seed) -> Dict:
    """The parameter tree from ``seed`` (an int or a traced int32), every
    leaf on a key of its own: ``fold_in(PRNGKey(seed), row index)``; every
    matrix normal with ``init_std``, the pooling vectors with the
    configuration's ``network.pool_init_std``, the norms' offsets 0."""
    root, out = jax.random.PRNGKey(seed), {}
    std = {"normal": net["init_std"],
           "pool": net["network"]["pool_init_std"]}
    for i, (path, shape, init) in enumerate(param_rows(net)):
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = (jnp.zeros(shape, jnp.float32) if init == "zeros"
                          else std[init] * jax.random.normal(
                              jax.random.fold_in(root, i), shape,
                              jnp.float32))
    return out


# ---- the model, one sequence ------------------------------------------------

def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def summaries(k, v, mu, phi, chunk, rnd, fault=None):
    """k, v (S, H, D) -> (kbar, vbar) (S / chunk, H, D)."""
    s, h, d = k.shape
    kc, vc = k.reshape(s // chunk, chunk, h, d), v.reshape(s // chunk,
                                                           chunk, h, d)

    def weights(vec):
        if fault == "mean_pool":
            return jnp.full(kc.shape[:3], 1.0 / chunk)
        return jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, rnd(vec))
                              / jnp.sqrt(d), axis=1)

    return (rnd(jnp.einsum("nch,nchd->nhd", weights(mu), kc)),
            rnd(jnp.einsum("nch,nchd->nhd", weights(phi), vc)))


def _summaries_seen(net, start, qpos, fault):
    """(summaries a window's block of queries is given, the mask of those
    each query sees (Q, n))."""
    window, chunk = net["window_size"], net["chunk_size"]
    if fault == "no_summaries":
        return 0, None
    if fault == "own_block":
        n = (start + window) // chunk
        return n, jnp.arange(n)[None, :] < (qpos // chunk)[:, None]
    n = start // chunk
    return n, jnp.ones((qpos.shape[0], n), bool)


def _eva(net, p, x, mm, rnd, fault):
    """One EVA block's mixer on one sequence x (S, hidden) -> y."""
    s = x.shape[0]
    heads, d = net["num_attention_heads"], head_dim(net)
    theta = float(net["rope_theta"])
    window, chunk = min(net["window_size"], s), net["chunk_size"]
    q = rnd(_turn(mm(x, p["q_proj"]).reshape(s, heads, d), theta))
    k = rnd(_turn(mm(x, p["k_proj"]).reshape(s, heads, d), theta))
    v = mm(x, p["v_proj"]).reshape(s, heads, d)
    kbar, vbar = summaries(k, v, p["adaptive_mu_k"], p["adaptive_phi"],
                           chunk, rnd, fault)
    block = min(BLOCK_Q, window)
    outs = []
    for start in range(0, s, window):
        n, _ = _summaries_seen(net, start, jnp.arange(1), fault)
        keys = jnp.concatenate([kbar[:n], k[start:start + window]])
        vals = jnp.concatenate([vbar[:n], v[start:start + window]])

        @jax.checkpoint
        def one(qb, lo):
            qpos = start + lo + jnp.arange(block)
            _, seen = _summaries_seen(net, start, qpos, fault)
            local = (start + jnp.arange(window))[None, :] <= qpos[:, None]
            mask = local if seen is None else jnp.concatenate([seen, local],
                                                               1)
            logits = rnd(jnp.einsum("qhd,khd->hqk", rnd(qb), rnd(keys))) / (
                jnp.sqrt(d))
            probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf),
                                   -1)
            return rnd(jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(vals)))

        qw = q[start:start + window].reshape(window // block, block, heads,
                                             d)
        outs.append(jax.lax.map(lambda a: one(*a), (
            qw, jnp.arange(0, window, block))).reshape(window, heads * d))
    return mm(jnp.concatenate(outs), p["o_proj"])


def _swiglu_chunked(x, p, mm):
    """The MLP ``TOKEN_CHUNK`` tokens at a time, each a checkpoint."""
    s = x.shape[0]
    chunk = min(TOKEN_CHUNK, s)

    @jax.checkpoint
    def one(xc):
        return mm(jax.nn.silu(mm(xc, p["gate"])) * mm(xc, p["up"]),
                  p["down"])

    return jax.lax.map(one, x.reshape(s // chunk, chunk, -1)).reshape(
        x.shape)


def sequence_losses(net: Dict, params: Dict, ids, precision="float32",
                    fault=None):
    """Of one sequence ``ids`` (S,): the sum of each head's cross-entropies
    over its positions, (num_pred_heads,)."""
    # the residual stream stays float32 (fp32_skip_add) but where the
    # planted fault rounds it, beside the bfloat16 witness's arithmetic
    residual = "bfloat16" if fault == "bf16_residual" else "float32"
    precision = "bfloat16" if fault == "bf16_residual" else precision
    stream = _rounding(residual)
    rnd = _rounding(precision)
    eps, s = net["rms_norm_eps"], ids.shape[0]

    def mm(a, w):
        return rnd(jnp.dot(rnd(a), rnd(w)))

    with jax.default_matmul_precision("highest"):
        x = stream(params["embed"][ids])
        for i in range(net["num_hidden_layers"]):
            @jax.checkpoint
            def layer(x, pl):
                x = stream(x + _eva(net, pl["mixer"],
                                    rnd(_norm(x, pl["attn_norm"], eps)), mm,
                                    rnd, fault))
                return stream(x + _swiglu_chunked(
                    rnd(_norm(x, pl["mlp_norm"], eps)), pl["mlp"], mm))

            x = layer(x, params[f"l{i}"])
        h = rnd(_norm(x, params["final_norm"], eps))
        chunk = min(TOKEN_CHUNK, s)
        sums = []
        for i in range(net["num_pred_heads"]):
            ahead = i if fault == "targets_off" else 1 + i
            live = (jnp.arange(s) < s - 1 - i).astype(jnp.float32)

            @jax.checkpoint
            def ce(args, w=params["heads"][i]):
                hc, tc, wc = args
                logits = mm(hc, w)
                return jnp.sum((jax.nn.logsumexp(logits, -1)
                                - jnp.take_along_axis(
                                    logits, tc[:, None], -1)[:, 0]) * wc)

            sums.append(jnp.sum(jax.lax.map(ce, (
                h.reshape(s // chunk, chunk, -1),
                jnp.roll(ids, -ahead).reshape(s // chunk, chunk),
                live.reshape(s // chunk, chunk)))))
        return jnp.stack(sums)


# ---- the steps ---------------------------------------------------------------

def batch_loss_and_grads(net, params, ids, precision="float32", fault=None,
                         grads=True):
    """Of the batch ``ids`` (B, S): ``L``, ``L_main`` and the gradient of
    ``L`` (or None); one sequence at a time."""
    b, s = ids.shape
    n = net["num_pred_heads"]
    count = b * (s - 1 - jnp.arange(n))

    def one(p, row):
        per_head = sequence_losses(net, p, row, precision, fault) / count
        return jnp.mean(per_head), per_head[0]

    fn = jax.jit(jax.value_and_grad(one, has_aux=True) if grads else one,
                 compiler_options=QUICK_COMPILE)
    add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                  donate_argnums=(0,))
    loss, main, acc = 0.0, 0.0, None
    for row in ids:
        if grads:
            (part, m), g = fn(params, jnp.asarray(row))
            acc = g if acc is None else add(acc, g)
        else:
            part, m = fn(params, jnp.asarray(row))
        loss, main = loss + float(part), main + float(m)
    return loss, main, acc


def first_step(params, grads, opt):
    """(new params, the gradient as Adam's moments get it): clip to the
    global norm, Adam from zero moments (step 1), decoupled weight decay on
    leaves of two or more axes (``lm.adamw_first_step``'s), the gradient
    given up for the clipped one so that three trees are held, not four."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]

    def step(params, grads):
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, opt["clip_global_norm"] / norm),
            grads)

        def leaf(p, g):
            upd = ((1 - b1) * g / (1 - b1)) / (
                jnp.sqrt((1 - b2) * g * g / (1 - b2)) + eps)
            if p.ndim >= 2:
                upd = upd + opt["wd"] * p
            return p - opt["lr"] * upd

        return jax.tree.map(leaf, params, grads), grads

    return jax.jit(step, donate_argnums=(1,))(params, grads)


def scan_grads(tree):
    """{path: leaf} of the vectors ``SCAN_LEAVES`` names in a gradient
    tree."""
    return {k: v for k, v in tree_paths(tree).items()
            if k[-1] in SCAN_LEAVES}


def _delta_norms(new, old):
    """{path: norm of new - old}, leaf by leaf, with no difference tree."""
    return {k: float(v) for k, v in jax.device_get(jax.jit(
        lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k])))
                      for k in a})(tree_paths(new), tree_paths(old))).items()}


def reference_steps(net: Dict, opt: Dict, params: Dict, batches,
                    precision="float32", fault=None) -> Dict:
    """Follow the first two steps on ``batches`` ([(B, S) ids] x 2): step
    1's ``L`` and ``L_main``, the clipped gradient of ``L``, its AdamW
    update, step 2's ``L`` on the updated parameters.  Returns ``losses``
    (of ``L``), ``main_losses`` (step 1's ``L_main``), ``grad_norm`` and
    ``first_delta_norm`` ({path: norm}), ``scan_grad`` ({path: the clipped
    gradient of a vector of ``SCAN_LEAVES``}) and, for ``compare_lm``, no
    expert counts."""
    loss1, main1, grads = batch_loss_and_grads(net, params, batches[0],
                                               precision, fault)
    new, clipped = first_step(params, grads, opt)
    del grads
    grad_norm = _norms(clipped)
    scan_grad = jax.device_get(scan_grads(clipped))
    del clipped
    delta = _delta_norms(new, params)
    loss2, _, _ = batch_loss_and_grads(net, new, batches[1], precision,
                                       fault, grads=False)
    return {"losses": [loss1, loss2], "main_losses": [main1],
            "grad_norm": grad_norm, "scan_grad": scan_grad,
            "first_delta_norm": delta, "counts": []}
