"""The plain reference of the ``nemotron_h`` family: forward, loss,
gradients and the first AdamW step in straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")``, one sequence at a time.

Independent of ``mx_rcnn_tpu/ops`` and ``models``: the state-space layer is
the **sequential recurrence over time** (one position a scan step; blocks of
``chunk_size`` positions are a ``jax.checkpoint`` so that the backward pass
fits), attention forms the full masked score matrix of a block of queries,
the expert layer loops over the held experts with a dense mask.  It is given
the same share as the program: the experts ``held`` of the router's
``n_routed_experts`` outputs, the vocabulary's slice.  What the absent
experts would add is left out here as there.

Equations (``config`` of the model's ``config.json``; departures are the
configuration file's ``assumed``): block i is ``x + mixer_i(RMSNorm(x))``;
``M``: in_proj -> z | xBC | dt, depthwise causal conv (4 taps, bias) + SiLU
on xBC, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t C_t + D x_t``,
RMSNorm in groups of ``y silu(z)``, out_proj; ``*``: causal grouped-query
attention, scale d^-1/2, no positional term; ``E``: sigmoid router scores in
float32, top-k by ``scores + bias`` (bias zero), weights the chosen scores
over their sum times ``routed_scaling_factor``, experts and the shared
expert ``down(relu(up x)^2)``; final RMSNorm, untied head, mean next-token
cross-entropy over the slice.

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

from benchmark.reference.step import tree_paths  # noqa: F401 (re-exported)

FAULTS = ("no_routed", "no_shared", "no_scale", "no_renorm", "no_decay",
          "no_carry")
INIT_STD = 0.02
# the state-space vectors, whose gradients read the scan and nothing else
SCAN_LEAVES = ("A_log", "dt_bias")


# ---- weights ---------------------------------------------------------------

def pattern(net: Dict) -> str:
    """The blocks run here: the first ``num_hidden_layers`` letters of the
    published pattern."""
    return net["hybrid_override_pattern"][:net["num_hidden_layers"]]


def held(net: Dict):
    """(first, count) of the router's ``published.n_routed_experts``
    outputs whose experts are held here."""
    return net["network"]["first_expert"], net["n_routed_experts"]


def param_rows(net: Dict) -> List:
    """[(path, shape, init)] of every parameter, in a fixed order.  ``net``
    is the configuration file: the model's ``config.json`` keys at the top
    level (the counts in ``reduced`` as held here), the uncut counts under
    ``published``."""
    h, v = net["hidden_size"], net["vocab_size"]
    rows = [(("embed",), (v, h), "normal")]
    inner = net["mamba_num_heads"] * net["mamba_head_dim"]
    conv_dim = inner + 2 * net["n_groups"] * net["ssm_state_size"]
    q_dim = net["num_attention_heads"] * net["head_dim"]
    kv_dim = net["num_key_value_heads"] * net["head_dim"]
    count = held(net)[1]
    for i, kind in enumerate(pattern(net)):
        b = (f"b{i}",)
        rows.append((b + ("norm",), (h,), "ones"))
        m = b + ("mixer",)
        if kind == "M":
            heads = net["mamba_num_heads"]
            rows += [(m + ("in_proj",), (h, inner + conv_dim + heads),
                      "normal"),
                     (m + ("conv_kernel",), (net["conv_kernel"], conv_dim),
                      "conv"),
                     (m + ("conv_bias",), (conv_dim,), "zeros"),
                     (m + ("A_log",), (heads,), "a_log"),
                     (m + ("D",), (heads,), "ones"),
                     (m + ("dt_bias",), (heads,), "dt_bias"),
                     (m + ("gate_norm",), (inner,), "ones"),
                     (m + ("out_proj",), (inner, h), "out")]
        elif kind == "*":
            rows += [(m + ("q_proj",), (h, q_dim), "normal"),
                     (m + ("k_proj",), (h, kv_dim), "normal"),
                     (m + ("v_proj",), (h, kv_dim), "normal"),
                     (m + ("o_proj",), (q_dim, h), "out")]
        elif kind == "E":
            f, fs = (net["moe_intermediate_size"],
                     net["moe_shared_expert_intermediate_size"])
            rows += [(m + ("router",),
                      (h, net["published"]["n_routed_experts"]), "normal"),
                     (m + ("experts_up",), (count, h, f), "normal"),
                     (m + ("experts_down",), (count, f, h), "out"),
                     (m + ("shared_up",), (h, fs), "normal"),
                     (m + ("shared_down",), (fs, h), "out")]
        else:
            raise ValueError(f"unknown block letter {kind!r}")
    return rows + [(("final_norm",), (h,), "ones"),
                   (("head",), (h, v), "normal")]


def _leaf(key, shape, init, net):
    f32 = jnp.float32
    if init == "normal":
        return INIT_STD * jax.random.normal(key, shape, f32)
    if init == "out":   # rescale_prenorm_residual, at the published depth
        return (INIT_STD / math.sqrt(2 * net["published"]["num_hidden_layers"])
                * jax.random.normal(key, shape, f32))
    if init == "conv":
        return jax.random.uniform(key, shape, f32, -0.5, 0.5)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if init == "dt_bias":
        lo, hi = math.log(net["time_step_min"]), math.log(net["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, f32)
                                 * (hi - lo) + lo), net["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    return jnp.ones(shape, f32) if init == "ones" else jnp.zeros(shape, f32)


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

def _rounding(precision):
    if precision == "float32":
        return lambda x: x
    bits = {"float8": (5, 2), "bfloat16": (8, 7)}[precision]
    return lambda x: jax.lax.reduce_precision(x, *bits)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def sequential_scan(xs, dt, a, bm, cm, block, fault=None):
    """The state-space recurrence one position at a time, one sequence:
    xs (S, H, P); dt (S, H), positive; a (H,), negative; bm, cm (S, H, N),
    every head's own B and C.  ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (outer)
    B_t``, ``y_t = h_t C_t``; returns y (S, H, P).  ``block`` positions are one
    ``jax.checkpoint``, which changes no number."""
    s, heads, hd = xs.shape

    def step(h, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.ones_like(dtt) if fault == "no_decay" else jnp.exp(dtt * a)
        h = (h * decay[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return h, jnp.sum(h * ct[:, None, :], -1)

    @jax.checkpoint
    def run_block(h, blk):
        if fault == "no_carry":
            h = jnp.zeros_like(h)
        return jax.lax.scan(step, h, blk)

    blocks = jax.tree.map(
        lambda t: t.reshape((s // block, block) + t.shape[1:]),
        (xs, dt, bm, cm))
    _, y = jax.lax.scan(
        run_block, jnp.zeros((heads, hd, bm.shape[-1]), jnp.float32), blocks)
    return y.reshape(s, heads, hd)


def _mamba(net, p, x, mm, fault):
    s = x.shape[0]
    heads, hd = net["mamba_num_heads"], net["mamba_head_dim"]
    g, n, k = net["n_groups"], net["ssm_state_size"], net["conv_kernel"]
    inner, block = heads * hd, net["chunk_size"]
    z, xbc, dt = jnp.split(mm(x, p["in_proj"]), [inner, 2 * inner + 2 * g * n
                                                 ], -1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[i:i + s] * p["conv_kernel"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_bias"])
    xs, bm, cm = jnp.split(xbc, [inner, inner + g * n], -1)
    xs = xs.reshape(s, heads, hd)
    bm = jnp.repeat(bm.reshape(s, g, n), heads // g, axis=1)
    cm = jnp.repeat(cm.reshape(s, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    y = sequential_scan(xs, dt, a, bm, cm, block, fault)
    y = y.reshape(s, heads, hd) + xs * p["D"][:, None]
    gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    normed = _rms(gated, 1.0, net["layer_norm_epsilon"]).reshape(s, inner)
    return mm(normed * p["gate_norm"], p["out_proj"])


def _attention(net, p, x, mm, rnd, block_q=512):
    s = x.shape[0]
    hq, hkv, d = (net["num_attention_heads"], net["num_key_value_heads"],
                  net["head_dim"])
    q = mm(x, p["q_proj"]).reshape(s, hq, d)
    k = jnp.repeat(mm(x, p["k_proj"]).reshape(s, hkv, d), hq // hkv, axis=1)
    v = jnp.repeat(mm(x, p["v_proj"]).reshape(s, hkv, d), hq // hkv, axis=1)
    block_q = min(block_q, s)

    @jax.checkpoint
    def one(qb, lo):
        scores = rnd(jnp.einsum("qhd,khd->hqk", rnd(qb), rnd(k))) * d ** -0.5
        mask = jnp.arange(s)[None, :] <= (lo + jnp.arange(block_q))[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return rnd(jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v)))

    out = jax.lax.map(lambda a: one(*a),
                      (q.reshape(s // block_q, block_q, hq, d),
                       jnp.arange(0, s, block_q)))
    return mm(out.reshape(s, hq * d), p["o_proj"])


def _ffn(x, up, down, mm):
    return mm(jnp.square(jax.nn.relu(mm(x, up))), down)


def _moe(net, p, x, mm, fault):
    """Returns (y, counts (held,)): the held experts' and the shared
    expert's part, and the assignments that fell on each held expert."""
    first, count = held(net)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(jnp.dot(x, p["router"]))
    _, idx = jax.lax.top_k(scores, net["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, -1)
    if net["norm_topk_prob"] and fault != "no_renorm":
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        w = w * net["routed_scaling_factor"]
    y, counts = jnp.zeros_like(x), []
    for e in range(count):
        chosen = idx == first + e                     # (S, k) dense mask
        counts.append(chosen.sum())
        if fault != "no_routed":
            gate = jnp.sum(jnp.where(chosen, w, 0.0), -1)
            y = y + gate[:, None] * _ffn(x, p["experts_up"][e],
                                         p["experts_down"][e], mm)
    if fault != "no_shared":
        y = y + _ffn(x, p["shared_up"], p["shared_down"], mm)
    return y, jnp.stack(counts)


def sequence_loss(net: Dict, params: Dict, ids, precision="float32",
                  fault=None):
    """(sum of the next-token cross-entropies of one sequence ``ids`` (S,),
    counts (expert layers, held))."""
    rnd = _rounding(precision)

    def mm(a, w):
        return rnd(jnp.dot(rnd(a), rnd(w)))

    with jax.default_matmul_precision("highest"):
        x = rnd(params["embed"][ids])
        counts = []
        for i, kind in enumerate(pattern(net)):
            p = params[f"b{i}"]

            @jax.checkpoint
            def block(x, p, kind=kind):
                normed = rnd(_rms(x, p["norm"], net["layer_norm_epsilon"]))
                if kind == "M":
                    y, c = _mamba(net, p["mixer"], normed, mm, fault), None
                elif kind == "*":
                    y, c = _attention(net, p["mixer"], normed, mm, rnd), None
                else:
                    y, c = _moe(net, p["mixer"], normed, mm, fault)
                return rnd(x + y), c

            x, c = block(x, p)
            if c is not None:
                counts.append(c)
        h = rnd(_rms(x, params["final_norm"], net["layer_norm_epsilon"]))
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


def adamw_first_step(params, grads, opt):
    """(new params, the gradient as Adam's moments get it): clip to the
    global norm, Adam from zero moments (step 1), decoupled weight decay on
    leaves of two or more axes."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]

    def step(params, grads):
        norm = jnp.sqrt(sum(jnp.sum(g * g)
                            for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, opt["clip_global_norm"] / norm),
            grads)

        def leaf(p, g):
            m_hat = (1 - b1) * g / (1 - b1)
            v_hat = (1 - b2) * g * g / (1 - b2)
            upd = m_hat / (jnp.sqrt(v_hat) + eps)
            if p.ndim >= 2:
                upd = upd + opt["wd"] * p
            return p - opt["lr"] * upd

        return jax.tree.map(leaf, params, grads), grads

    return jax.jit(step)(params, grads)


def _norms(tree):
    return {k: float(v) for k, v in jax.device_get(jax.jit(
        lambda t: {k: jnp.sqrt(jnp.sum(v * v))
                   for k, v in tree_paths(t).items()})(tree)).items()}


def scan_grads(tree):
    """{path: leaf} of the state-space vectors (``SCAN_LEAVES``) of a
    gradient tree: small, and what reads the scan's decay and carry."""
    return {k: v for k, v in tree_paths(tree).items()
            if k[-1] in SCAN_LEAVES}


def reference_steps(net: Dict, opt: Dict, params: Dict, batches,
                    precision="float32", fault=None) -> Dict:
    """Follow the first two steps on ``batches`` ([(B, S) ids] x 2): step
    1's loss, clipped gradient and per-expert counts, its AdamW update, and
    step 2's loss on the updated parameters.  Returns ``losses``,
    ``grad_norm`` and ``first_delta_norm`` ({path: norm}), ``scan_grad``
    ({path: the clipped gradient of a state-space vector}), ``counts``
    (expert layers x held, step 1)."""
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
