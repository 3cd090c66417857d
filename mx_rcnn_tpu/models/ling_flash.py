"""The ``ling_flash`` family: the language stack of Ling-3.0-flash-VL
(inclusionAI), every width and the layer pattern read from ``cfg.network``
(``build_lm``).  No vision tower, no multi-token-prediction module.

Layer ``i`` is two residual blocks, ``x <- x + mixer_i(RMSNorm(x))`` and
``x <- x + mlp_i(RMSNorm(x))``.  The mixer is the pattern's letter: ``K`` a
gated delta rule with a per-channel decay (Kimi Delta Attention,
``ops/kda.py``), ``L`` latent attention (DeepSeek-V2's MLA: a compressed
key-value latent, a rotary term on part of each head, query/key width 192
against value width 128; ``ops/attention.py``).  The MLP of the first
``first_k_dense_replace`` layers is a dense SwiGLU, of the others a
routed-expert layer with a group-limited sigmoid router that computes the
part of its held experts plus the shared expert (``ops/moe.py``).  Both
mixers end in a head-wise sigmoid gate before their output projection.
``MLA``, ``DenseMLP``, ``GatedMoE`` and ``Block`` are also the
``joyai_flash`` family's (``models/joyai_flash.py``), whose latent attention
differs in four fields of ``MLA``.
After the last layer a final RMSNorm and an untied head; the loss is the
mean next-token cross-entropy over the vocabulary held here.  Activations
and the residual stream are in ``dtype`` (bfloat16 on the chip), parameters
float32.  Every MLP block is a ``jax.checkpoint``; a mixer is one a sequence
(a KDA mixer one a sequence and group of heads).

Named scopes (one name whatever implements them): ``embed``; ``kda_mixer``
with ``kda_scan`` inside; ``mla``; ``dense_mlp``; ``moe`` with
``moe_route`` and ``moe_experts`` inside (``moe_grouped`` inside that);
``lm_head`` (final norm, head, loss).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.nemotron_h import (INIT_STD, _dt_bias_init, _normal,
                                           _a_log_init,
                                           chunked_cross_entropy, rms_norm)
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops.attention import KEEP_FLASH_RESIDUALS, causal_gqa
from mx_rcnn_tpu.ops.kda import kda_chunked

# the stage a block's device time is found under, by its letter
SCOPES = {"K": "kda_mixer", "L": "mla", "D": "dense_mlp", "E": "moe"}
L2_EPS = 1e-6
# groups of heads a KDA mixer runs one after the other (``KDAMixer``)
HEAD_GROUPS = 2


def _conv_init(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


def short_conv(x, w):
    """Depthwise causal convolution, no bias: tap ``i`` of ``w`` (K, C)
    reads position ``t - (K - 1 - i)`` of ``x`` (B, S, C)."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * w[i].astype(x.dtype) for i in range(k))


def l2_normalise(x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True)
                                + L2_EPS)).astype(x.dtype)


def _turns(x, theta: float):
    """cos and sin of the angle pair ``j`` of x (B, S, ..., R) turns by at
    position ``t`` of axis 1, ``t * theta^(-2j/R)``, in float32 and shaped
    to broadcast against (B, S, ..., R/2)."""
    s, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    shape = (1, s) + (1,) * (x.ndim - 3) + (r // 2,)
    return jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)


def rotary(x, theta: float):
    """Rotary position term over the last axis of x (B, S, ..., R), the
    pairs (j, j + R/2) (rotate-half: ``ling_flash``), position ``t`` of
    axis 1, angles in float32."""
    cos, sin = _turns(x, theta)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def rotary_interleaved(x, theta: float):
    """``rotary`` with the adjacent channels (2j, 2j + 1) as pair ``j``
    (``rope_interleave``: ``joyai_flash``): the same function of the
    columns permuted 2j -> j, 2j + 1 -> j + R/2."""
    cos, sin = _turns(x, theta)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2,
                                                          2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape).astype(x.dtype)


class KDAMixer(nn.Module):
    hidden: int
    heads: int
    head_dim: int
    conv_kernel: int
    chunk: int
    lower_bound: float
    eps: float
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        """Returns (y, the step's most negative within-chunk cumulative
        log-decay)."""
        s = x.shape[1]
        h, d = self.heads, self.head_dim
        inner = h * d
        proj = {n: self.param(f"{n}_proj", _normal(INIT_STD),
                              (self.hidden, inner)) for n in "qkvf"}
        conv = {n: self.param(f"{n}_conv", _conv_init,
                              (self.conv_kernel, inner)) for n in "qkv"}
        w_b = self.param("b_proj", _normal(INIT_STD), (self.hidden, h))
        w_g = self.param("g_proj", _normal(INIT_STD), (self.hidden, h))
        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        o_norm = self.param("o_norm", nn.initializers.ones, (d,))
        w_o = self.param("o_proj", _normal(self.out_std), (inner, self.hidden))

        def mix(x, w):
            """One sequence (S, hidden) through one group of heads, whose
            slices of the parameters are ``w`` -> (the group's part of y
            (S, hidden), its log-decay minimum): no parameter is made in
            here."""
            hg = w["A_log"].shape[0]

            def mixed(n):
                y = jax.nn.silu(short_conv(
                    jnp.dot(x, w[n + "_proj"].astype(self.dtype))[None],
                    w[n + "_conv"]))
                return y.reshape(1, s, hg, d)

            q = l2_normalise(mixed("q")) * (d ** -0.5)
            k, v = l2_normalise(mixed("k")), mixed("v")
            f = jnp.dot(x, w["f_proj"].astype(self.dtype),
                        preferred_element_type=jnp.float32)
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.exp(w["A_log"])[:, None]
                * (f + w["dt_bias"]).reshape(1, s, hg, d))
            beta = jax.nn.sigmoid(jnp.dot(x, w["b_proj"].astype(self.dtype),
                                          preferred_element_type=jnp.float32))
            with jax.named_scope("kda_scan"):
                o, g_min = kda_chunked(q, k, v, g, beta[None], self.chunk)
            gate = jax.nn.sigmoid(jnp.dot(
                x, w["g_proj"].astype(self.dtype),
                preferred_element_type=jnp.float32))
            o = rms_norm(o[0], o_norm, self.eps) * gate[..., None].astype(
                self.dtype)
            return jnp.dot(o.reshape(s, hg * d),
                           w["o_proj"].astype(self.dtype)), g_min

        # The heads are independent up to the output projection's sum.  One
        # sequence and one group of heads at a time, each a
        # ``jax.checkpoint``: the rule's float32 decays, its scores and the
        # inverse (some six times the bytes of q, k, v and g together) then
        # exist for one such part at a time, forward and backward (a mixer's
        # block is not wrapped in ``nn.remat`` again: ``LingFlash``).
        groups = HEAD_GROUPS if h % HEAD_GROUPS == 0 else 1

        def by_group(t, axis):
            """The head axis (or the channels of all heads) cut in groups,
            the group axis first."""
            shape = t.shape[:axis] + (groups, -1) + t.shape[axis + 1:]
            return jnp.moveaxis(t.reshape(shape), axis, 0)

        w = {f"{n}_proj": by_group(proj[n], 1) for n in "qkvf"}
        w.update({f"{n}_conv": by_group(conv[n], 1) for n in "qkv"})
        w.update(b_proj=by_group(w_b, 1), g_proj=by_group(w_g, 1),
                 A_log=by_group(a_log, 0), dt_bias=by_group(dt_bias, 0),
                 o_proj=by_group(w_o, 0))
        part = jax.checkpoint(mix)
        y, g_min = jax.lax.map(
            lambda wg: jax.lax.map(lambda row: part(row, wg), x), w)
        return y.sum(0).astype(self.dtype), jnp.min(g_min)


class MLA(nn.Module):
    """Latent attention (DeepSeek-V2's MLA).  The last four fields are what
    the published configurations differ in beside their widths, at
    ``ling_flash``'s values: ``q_rank`` the width of a low-rank query
    (``q_lora_rank``: ``q_a``, an RMSNorm, ``q_b``; None: one full-rank
    ``q_proj``), ``head_norms`` a learned RMSNorm over each head's query
    and key before the rotary term, ``head_gate`` a sigmoid scalar a head
    on the output, ``interleave`` the rotary term's pairing (``rotary`` or
    ``rotary_interleaved``).
    ``joyai_flash`` has a low-rank query, adjacent pairs, no head norm and
    no gate."""
    hidden: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    block_q: int
    eps: float
    out_std: float
    dtype: Any
    q_rank: Optional[int] = None
    head_norms: bool = True
    head_gate: bool = True
    interleave: bool = False

    @nn.compact
    def __call__(self, x):
        s = x.shape[1]
        h, qk = self.heads, self.nope + self.rope
        if self.q_rank:
            w_qa = self.param("q_a_proj", _normal(INIT_STD),
                              (self.hidden, self.q_rank))
            qa_norm = self.param("q_a_norm", nn.initializers.ones,
                                 (self.q_rank,))
            w_q = self.param("q_b_proj", _normal(INIT_STD),
                             (self.q_rank, h * qk))
        else:
            w_q = self.param("q_proj", _normal(INIT_STD),
                             (self.hidden, h * qk))
        w_a = self.param("kv_a_proj", _normal(INIT_STD),
                         (self.hidden, self.kv_rank + self.rope))
        a_norm = self.param("kv_a_norm", nn.initializers.ones, (self.kv_rank,))
        w_b = self.param("kv_b_proj", _normal(INIT_STD),
                         (self.kv_rank, h * (self.nope + self.v_dim)))
        if self.head_norms:
            q_norm = self.param("q_norm", nn.initializers.ones, (qk,))
            k_norm = self.param("k_norm", nn.initializers.ones, (qk,))
        if self.head_gate:
            w_g = self.param("g_proj", _normal(INIT_STD), (self.hidden, h))
        w_o = self.param("o_proj", _normal(self.out_std),
                         (h * self.v_dim, self.hidden))

        def attend(x):
            """One or more sequences (b, S, hidden) -> (b, S, hidden): no
            parameter is made in here."""
            b = x.shape[0]
            q_in = x
            if self.q_rank:
                q_in = rms_norm(jnp.dot(x, w_qa.astype(self.dtype)), qa_norm,
                                self.eps)
            q = jnp.dot(q_in, w_q.astype(self.dtype)).reshape(b, s, h, qk)
            latent, k_r = jnp.split(jnp.dot(x, w_a.astype(self.dtype)),
                                    [self.kv_rank], -1)
            kv = jnp.dot(rms_norm(latent, a_norm, self.eps),
                         w_b.astype(self.dtype)).reshape(
                             b, s, h, self.nope + self.v_dim)
            k_n, v = jnp.split(kv, [self.nope], -1)
            # one rotary key part a position, shared by every head
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r[:, :, None],
                                       (b, s, h, self.rope))], -1)
            if self.head_norms:
                q = rms_norm(q, q_norm, self.eps)
                k = rms_norm(k, k_norm, self.eps)

            turn = rotary_interleaved if self.interleave else rotary

            def turned(t):
                return jnp.concatenate(
                    [t[..., :self.nope], turn(t[..., self.nope:],
                                              self.theta)], -1)

            o = causal_gqa(turned(q), turned(k), v, self.block_q)
            if self.head_gate:
                gate = jax.nn.sigmoid(jnp.dot(
                    x, w_g.astype(self.dtype),
                    preferred_element_type=jnp.float32))
                o = o * gate[..., None].astype(self.dtype)
            return jnp.dot(o.reshape(b, s, h * self.v_dim),
                           w_o.astype(self.dtype))

        # one sequence at a time, each a ``jax.checkpoint``, as the KDA
        # mixer runs and for its reason: the padded operands of the
        # kernels, their cotangents and the float32 of the head norms and
        # the rotary term are 4 GB for two sequences of 8192.  The
        # checkpoint keeps the flash kernel's ``o`` and log-sum-exp (135 MB
        # a sequence), so that the backward does not run the forward
        # kernel again; off the kernels it keeps nothing
        y = jax.lax.map(jax.checkpoint(lambda row: attend(row[None])[0],
                                       policy=KEEP_FLASH_RESIDUALS), x)
        return y


class DenseMLP(nn.Module):
    hidden: int
    width: int
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        w_gate = self.param("gate", _normal(INIT_STD), (h, self.width))
        w_up = self.param("up", _normal(INIT_STD), (h, self.width))
        w_down = self.param("down", _normal(self.out_std), (self.width, h))
        return moe_ops.swiglu_ffn(x.reshape(b * s, h), w_gate, w_up,
                                  w_down).astype(self.dtype).reshape(b, s, h)


# what ``GatedMoE`` reads of a family's ``Dims`` beside the fields every such
# ``Dims`` has, with the value of a family that has no such field: the
# router's scores (``ops/moe.py::route``) and the held rows' alignment
# (``ops/moe.py::held_assignments``; ``keye_vl2`` sets both)
MOE_DEFAULTS = {"scoring_func": "sigmoid", "moe_group_rows": 0}


def moe_option(net, name: str):
    return getattr(net, name, MOE_DEFAULTS[name])


class GatedMoE(nn.Module):
    net: Any        # Dims
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        """Returns (y, sizes (count,), overflow ()): the layer's output, the
        assignments to each held expert and the rows not computed."""
        n = self.net
        b, s, h = x.shape
        count, f = n.experts_held[1], n.moe_intermediate_size
        # no shared expert where its width is 0 (``keye_vl2``)
        fs = n.moe_shared_expert_intermediate_size
        w_r = self.param("router", _normal(INIT_STD), (h, n.n_routed_experts))
        w_gate = self.param("experts_gate", _normal(INIT_STD), (count, h, f))
        w_up = self.param("experts_up", _normal(INIT_STD), (count, h, f))
        w_down = self.param("experts_down", _normal(self.out_std),
                            (count, f, h))
        if fs:
            s_gate = self.param("shared_gate", _normal(INIT_STD), (h, fs))
            s_up = self.param("shared_up", _normal(INIT_STD), (h, fs))
            s_down = self.param("shared_down", _normal(self.out_std),
                                (fs, h))
        flat = x.reshape(b * s, h)
        with jax.named_scope("moe_route"):
            # the expert bias is held at zero (no balancing update is part
            # of the configuration); the scoring is named only where it is
            # not the default sigmoid, so that a sigmoid router's call keeps
            # the signature that stand-ins for ``route`` of that signature
            # (the benchmark's planted faults) take
            scoring = moe_option(n, "scoring_func")
            idx, weight = moe_ops.route(
                flat, w_r, 0.0, n.num_experts_per_tok,
                n.routed_scaling_factor, n.norm_topk_prob,
                # one group of all is no limit: the plain top-k
                (n.n_group, n.topk_group) if n.n_group > 1 else None,
                **({} if scoring == "sigmoid" else {"scoring": scoring}))
            routed = moe_ops.held_assignments(
                idx, weight, tuple(n.experts_held), moe_ops.row_capacity(
                    b * s, n.num_experts_per_tok, n.n_routed_experts, count,
                    n.moe_capacity_factor), moe_option(n, "moe_group_rows"))
        with jax.named_scope("moe_experts"):
            y = moe_ops.held_experts(flat, routed, w_up, w_down,
                                     w_gate=w_gate)
            if fs:
                y = y + moe_ops.swiglu_ffn(flat, s_gate, s_up, s_down)
        return (y.astype(self.dtype).reshape(b, s, h), routed.sizes,
                routed.overflow)


class Block(nn.Module):
    """x + f(RMSNorm(x)) for the letter's f; returns (x, sizes, overflow,
    log-decay minimum) with empty counters where f has none."""
    kind: str
    net: Any        # Dims
    dtype: Any

    @nn.compact
    def __call__(self, x):
        n = self.net
        scale = self.param("norm", nn.initializers.ones, (n.hidden_size,))
        if self.kind not in SCOPES:
            raise ValueError(f"unknown block letter {self.kind!r}")
        out_std = INIT_STD / math.sqrt(2 * n.init_layers)
        sizes = jnp.zeros((n.experts_held[1],), jnp.int32)
        overflow = jnp.zeros((), jnp.int32)
        g_min = jnp.zeros((), jnp.float32)
        with jax.named_scope(SCOPES[self.kind]):
            normed = rms_norm(x, scale, n.norm_eps)
            if self.kind == "K":
                y, g_min = KDAMixer(
                    n.hidden_size, n.num_attention_heads, n.head_dim,
                    n.conv_kernel, n.chunk_size, n.kda_lower_bound,
                    n.norm_eps, out_std, self.dtype, name="mixer")(normed)
            elif self.kind == "L":
                y = MLA(n.hidden_size, n.num_attention_heads, n.kv_lora_rank,
                        n.qk_nope_head_dim, n.qk_rope_head_dim, n.v_head_dim,
                        n.rope_theta, n.attn_block_q, n.norm_eps, out_std,
                        self.dtype, n.q_lora_rank, n.qk_head_norms,
                        n.head_gate, n.rope_interleave,
                        name="mixer")(normed)
            elif self.kind == "D":
                y = DenseMLP(n.hidden_size, n.intermediate_size, out_std,
                             self.dtype, name="mlp")(normed)
            else:
                y, sizes, overflow = GatedMoE(n, out_std, self.dtype,
                                              name="mlp")(normed)
            return x + y, sizes, overflow, g_min


class LingFlash(nn.Module):
    """The stack; ``__call__(ids)`` is the training forward: the loss, the
    routed-expert counters and the KDA layers' log-decay minimum."""
    net: Any        # Dims
    dtype: Any = jnp.bfloat16
    loss_chunk: int = 4096

    @nn.compact
    def __call__(self, ids) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        n = self.net
        b, s = ids.shape
        table = self.param("embed", _normal(INIT_STD),
                           (n.vocab_size, n.hidden_size))
        with jax.named_scope("embed"):
            x = table[ids].astype(self.dtype)
        sizes, overflow, g_min = [], [], []
        for i, mixer in enumerate(n.layer_pattern):
            mlp = "D" if i < n.first_k_dense_replace else "E"
            # a mixer checkpoints itself, a sequence (and for KDA a group
            # of heads) at a time
            x, _, _, gm = Block(mixer, n, self.dtype, name=f"l{i}_mix")(x)
            x, sz, ov, _ = nn.remat(Block)(mlp, n, self.dtype,
                                           name=f"l{i}_mlp")(x)
            if mixer == "K":
                g_min.append(gm)
            if mlp == "E":
                sizes.append(sz)
                overflow.append(ov)
        final = self.param("final_norm", nn.initializers.ones,
                           (n.hidden_size,))
        w_head = self.param("head", _normal(INIT_STD),
                            (n.hidden_size, n.vocab_size))
        with jax.named_scope("lm_head"):
            h = rms_norm(x, final, n.norm_eps).reshape(b * s, -1)
            # position t predicts ids[t + 1]; the last position has no target
            targets = jnp.roll(ids, -1, axis=1).reshape(-1)
            live = (jnp.arange(s) < s - 1).astype(jnp.float32)
            weights = jnp.broadcast_to(live, (b, s)).reshape(-1)
            loss = chunked_cross_entropy(
                h, w_head, targets, weights, self.loss_chunk) / (b * (s - 1))
        return loss, {"sizes": jnp.stack(sizes),
                      "overflow": jnp.stack(overflow),
                      "kda_chunk_log_decay_min": jnp.min(jnp.stack(g_min))}

    def init_variables(self, key):
        """(params, batch_stats) from one traced init on a sequence just
        long enough for every block; this family keeps no statistics."""
        s = math.lcm(self.net.chunk_size, self.net.attn_block_q)
        variables = self.init(key, jnp.zeros((1, s), jnp.int32))
        return variables["params"], {}


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the stack reads of ``cfg.network``, under the same names."""
    layer_pattern: str
    first_k_dense_replace: int
    hidden_size: int
    vocab_size: int
    norm_eps: float
    init_layers: int
    intermediate_size: int
    num_attention_heads: int
    head_dim: int
    conv_kernel: int
    chunk_size: int
    kda_lower_bound: float
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    attn_block_q: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    moe_capacity_factor: float
    # ``MLA``'s last four fields, at this family's values
    q_lora_rank: Optional[int] = None
    qk_head_norms: bool = True
    head_gate: bool = True
    rope_interleave: bool = False


def build_lm(cfg: Config, quant_phase: str = "apply") -> LingFlash:
    """The family table's builder (``families.py``); a sequence family has
    no quantized form and ``quant_phase`` is not read."""
    from mx_rcnn_tpu.config import validate_dtype_string

    validate_dtype_string(cfg.network.compute_dtype, "network__compute_dtype")
    n = cfg.network
    if not n.layer_pattern or set(n.layer_pattern) - set("KL"):
        raise ValueError(f"a ling_flash pattern is letters K and L, got "
                         f"{n.layer_pattern!r}")
    if "K" not in n.layer_pattern or (
            n.first_k_dense_replace >= len(n.layer_pattern)):
        raise ValueError("a ling_flash pattern holds at least one 'K' layer "
                         "and one routed-expert layer")
    dims = Dims(
        layer_pattern=n.layer_pattern,
        first_k_dense_replace=n.first_k_dense_replace,
        hidden_size=n.hidden_size, vocab_size=n.vocab_size,
        norm_eps=n.norm_eps, init_layers=n.init_layers,
        intermediate_size=n.intermediate_size,
        num_attention_heads=n.num_attention_heads, head_dim=n.head_dim,
        conv_kernel=n.conv_kernel, chunk_size=n.chunk_size,
        kda_lower_bound=n.kda_lower_bound, kv_lora_rank=n.kv_lora_rank,
        qk_nope_head_dim=n.qk_nope_head_dim,
        qk_rope_head_dim=n.qk_rope_head_dim, v_head_dim=n.v_head_dim,
        rope_theta=n.rope_theta, attn_block_q=n.attn_block_q,
        n_routed_experts=n.n_routed_experts,
        experts_held=tuple(n.experts_held),
        num_experts_per_tok=n.num_experts_per_tok, n_group=n.n_group,
        topk_group=n.topk_group,
        moe_intermediate_size=n.moe_intermediate_size,
        moe_shared_expert_intermediate_size=(
            n.moe_shared_expert_intermediate_size),
        routed_scaling_factor=n.routed_scaling_factor,
        norm_topk_prob=n.norm_topk_prob,
        moe_capacity_factor=n.moe_capacity_factor)
    return LingFlash(
        net=dims,
        dtype=(jnp.bfloat16 if n.compute_dtype == "bfloat16"
               else jnp.float32))
