"""The ``nemotron_h`` family: a hybrid stack of Mamba-2, attention and
routed-expert blocks (NVIDIA-Nemotron-3-Nano's ``model_type``), every width
and the letter pattern read from ``cfg.network`` (``build_lm``).

Block ``i`` is ``x <- x + mixer_i(RMSNorm(x))`` with the mixer the pattern's
letter names: ``M`` a Mamba-2 mixer (``ops/ssd.py``), ``*`` causal
grouped-query attention without a positional term (``ops/attention.py``),
``E`` a routed-expert layer that computes the part of its held experts plus
the shared expert (``ops/moe.py``).  After the last block a final RMSNorm
and an untied head; the loss is the mean next-token cross-entropy over the
vocabulary held here.  Activations and the residual stream are in
``dtype`` (bfloat16 on the chip), parameters float32.  Every block is a
``jax.checkpoint``: the backward pass recomputes a block from its input,
all but the flash kernel's ``o`` and log-sum-exp (``KEEP_FLASH_RESIDUALS``).

Named scopes (one name whatever implements them): ``embed``; ``ssm_mixer``
with ``ssd_scan`` inside; ``attention``; ``moe`` with ``moe_route`` and
``moe_experts`` inside; ``lm_head`` (final norm, head, loss).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops.attention import KEEP_FLASH_RESIDUALS, causal_gqa
from mx_rcnn_tpu.ops.ssd import ssd_scan

INIT_STD = 0.02
# the stage a block's device time is found under, by its letter
SCOPES = {"M": "ssm_mixer", "*": "attention", "E": "moe"}


def _normal(std):
    return nn.initializers.normal(stddev=std)


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _a_log_init(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias_init(key, shape, lo=1e-3, hi=0.1, floor=1e-4):
    """Inverse softplus of dt ~ exp(U(log lo, log hi)), as Mamba-2 starts
    it (the configuration's time_step_min / max / floor)."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo))
                             + math.log(lo)), floor)
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    hidden: int
    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int
    chunk: int
    eps: float
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        inner, gn = self.heads * self.head_dim, self.groups * self.state
        conv_dim = inner + 2 * gn
        w_in = self.param("in_proj", _normal(INIT_STD),
                          (self.hidden, inner + conv_dim + self.heads))
        w_conv = self.param(
            "conv_kernel", lambda k, sh: jax.random.uniform(
                k, sh, jnp.float32, -0.5, 0.5), (self.conv_kernel, conv_dim))
        b_conv = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        a_log = self.param("A_log", _a_log_init, (self.heads,))
        d_skip = self.param("D", nn.initializers.ones, (self.heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (self.heads,))
        g_norm = self.param("gate_norm", nn.initializers.ones, (inner,))
        w_out = self.param("out_proj", _normal(self.out_std),
                           (inner, self.hidden))
        zxbcdt = jnp.dot(x, w_in.astype(self.dtype))
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], -1)
        # depthwise causal convolution: tap k reads position t - (K-1-k)
        k = self.conv_kernel
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(padded[:, i:i + s] * w_conv[i].astype(self.dtype)
                   for i in range(k)) + b_conv.astype(self.dtype)
        xbc = jax.nn.silu(conv)
        xs, bm, cm = jnp.split(xbc, [inner, inner + gn], -1)
        xs = xs.reshape(b, s, self.heads, self.head_dim)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(xs, dt, -jnp.exp(a_log),
                         bm.reshape(b, s, self.groups, self.state),
                         cm.reshape(b, s, self.groups, self.state),
                         self.chunk)
        y = y + xs * d_skip.astype(self.dtype)[:, None]
        # gated RMSNorm in `groups` groups of the inner width
        gated = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(
            b, s, self.groups, inner // self.groups)
        normed = rms_norm(gated, 1.0, self.eps).reshape(b, s, inner)
        normed = normed * g_norm.astype(self.dtype)
        return jnp.dot(normed, w_out.astype(self.dtype))


class Attention(nn.Module):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    block_q: int
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        q_dim, kv_dim = self.heads * self.head_dim, self.kv_heads * self.head_dim
        w_q = self.param("q_proj", _normal(INIT_STD), (self.hidden, q_dim))
        w_k = self.param("k_proj", _normal(INIT_STD), (self.hidden, kv_dim))
        w_v = self.param("v_proj", _normal(INIT_STD), (self.hidden, kv_dim))
        w_o = self.param("o_proj", _normal(self.out_std), (q_dim, self.hidden))
        q = jnp.dot(x, w_q.astype(self.dtype)).reshape(
            b, s, self.heads, self.head_dim)
        k = jnp.dot(x, w_k.astype(self.dtype)).reshape(
            b, s, self.kv_heads, self.head_dim)
        v = jnp.dot(x, w_v.astype(self.dtype)).reshape(
            b, s, self.kv_heads, self.head_dim)
        o = causal_gqa(q, k, v, self.block_q)
        return jnp.dot(o.reshape(b, s, q_dim), w_o.astype(self.dtype))


class MoE(nn.Module):
    hidden: int
    n_experts: int
    held: Tuple[int, int]
    top_k: int
    width: int
    shared_width: int
    scale: float
    norm_topk: bool
    capacity_factor: float
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        """Returns (y, sizes (count,), overflow ()): the layer's output, the
        assignments to each held expert and the rows not computed."""
        b, s, h = x.shape
        count = self.held[1]
        w_r = self.param("router", _normal(INIT_STD), (h, self.n_experts))
        w_up = self.param("experts_up", _normal(INIT_STD),
                          (count, h, self.width))
        w_down = self.param("experts_down", _normal(self.out_std),
                            (count, self.width, h))
        s_up = self.param("shared_up", _normal(INIT_STD),
                          (h, self.shared_width))
        s_down = self.param("shared_down", _normal(self.out_std),
                            (self.shared_width, h))
        flat = x.reshape(b * s, h)
        with jax.named_scope("moe_route"):
            # the score-correction bias is held at zero (no balancing
            # update is part of the configuration)
            idx, weight = moe_ops.route(flat, w_r, 0.0, self.top_k,
                                        self.scale, self.norm_topk)
            routed = moe_ops.held_assignments(
                idx, weight, self.held, moe_ops.row_capacity(
                    b * s, self.top_k, self.n_experts, count,
                    self.capacity_factor))
        with jax.named_scope("moe_experts"):
            y = moe_ops.held_experts(flat, routed, w_up, w_down)
            y = y + moe_ops.relu2_ffn(flat, s_up, s_down)
        return (y.astype(self.dtype).reshape(b, s, h), routed.sizes,
                routed.overflow)


class Block(nn.Module):
    """x + mixer(RMSNorm(x)); returns (x, sizes, overflow) with empty
    counters for a block that routes nothing."""
    kind: str
    net: Any        # Dims
    dtype: Any

    @nn.compact
    def __call__(self, x):
        n = self.net
        scale = self.param("norm", nn.initializers.ones, (n.hidden_size,))
        if self.kind not in SCOPES:
            raise ValueError(f"unknown block letter {self.kind!r}")
        # the block's stage: its norm, its mixer and the residual sum
        with jax.named_scope(SCOPES[self.kind]):
            normed = rms_norm(x, scale, n.norm_eps)
            y, sizes, overflow = self._mixer(normed)
            return x + y, sizes, overflow

    def _mixer(self, normed):
        n = self.net
        out_std = INIT_STD / math.sqrt(2 * n.init_layers)
        sizes = jnp.zeros((n.experts_held[1],), jnp.int32)
        overflow = jnp.zeros((), jnp.int32)
        if self.kind == "M":
            y = MambaMixer(
                n.hidden_size, n.mamba_num_heads, n.mamba_head_dim,
                n.ssm_state_size, n.ssm_groups, n.conv_kernel, n.chunk_size,
                n.norm_eps, out_std, self.dtype, name="mixer")(normed)
        elif self.kind == "*":
            y = Attention(
                n.hidden_size, n.num_attention_heads, n.num_key_value_heads,
                n.head_dim, n.attn_block_q, out_std, self.dtype,
                name="mixer")(normed)
        elif self.kind == "E":
            y, sizes, overflow = MoE(
                n.hidden_size, n.n_routed_experts, tuple(n.experts_held),
                n.num_experts_per_tok, n.moe_intermediate_size,
                n.moe_shared_expert_intermediate_size,
                n.routed_scaling_factor, n.norm_topk_prob,
                n.moe_capacity_factor, out_std, self.dtype,
                name="mixer")(normed)
        return y, sizes, overflow


def chunked_cross_entropy(h, w_head, targets, weights, chunk: int):
    """Sum over tokens of weights * CE(h @ w_head, targets), the logits in
    float32 and never whole: ``chunk`` tokens at a time, each chunk a
    ``jax.checkpoint``.  h (T, H); targets, weights (T,)."""
    t = h.shape[0]
    chunk = min(chunk, t)
    n = t // chunk
    if n * chunk != t:
        raise ValueError(f"{t} tokens are no multiple of the chunk {chunk}")

    @jax.checkpoint
    def one(total, inp):
        hc, tc, wc = inp
        logits = jnp.dot(hc, w_head.astype(hc.dtype),
                         preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return total + jnp.sum((lse - picked) * wc), None

    total, _ = jax.lax.scan(
        one, jnp.zeros((), jnp.float32),
        (h.reshape(n, chunk, -1), targets.reshape(n, chunk),
         weights.reshape(n, chunk)))
    return total


class NemotronH(nn.Module):
    """The stack; ``__call__(ids)`` is the training forward: the loss and
    the routed-expert counters."""
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
        sizes, overflow = [], []
        for i, kind in enumerate(n.layer_pattern):
            # each block recomputed in the backward but for the flash
            # kernel's ``o`` and log-sum-exp, which an attention block on
            # the kernels keeps (0.14 GB): the backward kernel reads them
            # and the forward kernel runs once; other blocks name nothing
            x, sz, ov = nn.remat(Block, policy=KEEP_FLASH_RESIDUALS)(
                kind, n, self.dtype, name=f"b{i}")(x)
            if kind == "E":
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
                      "overflow": jnp.stack(overflow)}

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
    hidden_size: int
    vocab_size: int
    norm_eps: float
    init_layers: int
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    ssm_groups: int
    conv_kernel: int
    chunk_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    attn_block_q: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    moe_capacity_factor: float


def build_lm(cfg: Config, quant_phase: str = "apply") -> NemotronH:
    """The family table's builder (``families.py``); a sequence family has
    no quantized form and ``quant_phase`` is not read."""
    from mx_rcnn_tpu.config import validate_dtype_string

    validate_dtype_string(cfg.network.compute_dtype, "network__compute_dtype")
    if "E" not in cfg.network.layer_pattern:
        raise ValueError("a nemotron_h pattern holds at least one 'E' block")
    n = cfg.network
    dims = Dims(
        layer_pattern=n.layer_pattern, hidden_size=n.hidden_size,
        vocab_size=n.vocab_size, norm_eps=n.norm_eps,
        init_layers=n.init_layers, mamba_num_heads=n.mamba_num_heads,
        mamba_head_dim=n.mamba_head_dim, ssm_state_size=n.ssm_state_size,
        ssm_groups=n.ssm_groups, conv_kernel=n.conv_kernel,
        chunk_size=n.chunk_size, num_attention_heads=n.num_attention_heads,
        num_key_value_heads=n.num_key_value_heads, head_dim=n.head_dim,
        attn_block_q=n.attn_block_q, n_routed_experts=n.n_routed_experts,
        experts_held=tuple(n.experts_held),
        num_experts_per_tok=n.num_experts_per_tok,
        moe_intermediate_size=n.moe_intermediate_size,
        moe_shared_expert_intermediate_size=(
            n.moe_shared_expert_intermediate_size),
        routed_scaling_factor=n.routed_scaling_factor,
        norm_topk_prob=n.norm_topk_prob,
        moe_capacity_factor=n.moe_capacity_factor)
    return NemotronH(
        net=dims,
        dtype=(jnp.bfloat16 if cfg.network.compute_dtype == "bfloat16"
               else jnp.float32))
