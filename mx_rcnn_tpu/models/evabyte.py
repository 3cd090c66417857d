"""The ``evabyte`` family: EvaByte's byte-level stack, every width and the
depth read from ``cfg.network`` (``build_lm``).

Layer ``i`` is two residual blocks, ``x <- x + EVA_i(N(x))`` and ``x <- x +
MLP_i(N(x))``, on a float32 residual stream (``fp32_skip_add``); ``N(x) = x
/ rms(x) * (1 + g)`` (``norm_add_unit_offset``, ``g`` starting at 0), its
output in ``dtype`` (bfloat16 on the chip: ``fp32_ln`` false):

* ``EVA``: q, k, v projections (as many heads for keys as for queries, no
  bias), the rotary term on all channels (halves paired,
  ``ling_flash.rotary``) of q and k, then ``ops/eva.py``: each chunk's
  keys and values pooled by a learned vector a head (``adaptive_mu_k``,
  ``adaptive_phi``), and one softmax a query over its window's exact keys
  and the summaries of every chunk before its window; ``o_proj``;
* ``MLP``: SwiGLU of ``intermediate_size`` over the sequence in parts of
  ``MLP_TOKEN_CHUNK`` tokens, one after the other, each an ``nn.remat``.

After the last layer a final ``N`` and ``num_pred_heads`` untied heads:
head ``i`` predicts the token ``1 + i`` ahead, its logits in float32
(``fp32_logits``), its last ``1 + i`` positions without a target.  The loss
is the heads' mean cross-entropy; ``loss_main`` is head 0's.

A layer is one ``nn.remat`` under ``KEEP_EVA_RESIDUALS``: the backward
recomputes the layer from its input but keeps the attention kernel's ``o``
and log-sum-exp, so that the forward kernel runs once a layer.  Parameters
are float32.

Named scopes: ``embed``; ``eva`` (the attention block whole: its norm,
projections, rotary term) with ``eva_pool`` (the chunk summaries) and
``eva_attend`` (the softmax over both key sets) inside; ``dense_mlp`` (the
MLP block whole); ``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.ling_flash import rotary
from mx_rcnn_tpu.models.nemotron_h import _normal, chunked_cross_entropy
from mx_rcnn_tpu.ops import moe as moe_ops
from mx_rcnn_tpu.ops.eva import KEEP_EVA_RESIDUALS, eva_attention, pool_chunks

# the pooling vectors' starting spread: the logits mu . k / sqrt(d) then
# spread by ~0.8 over a chunk, so that the pooling weights are uneven (at 0
# the pooling is a plain mean)
POOL_INIT_STD = 1.0
# tokens a part of the dense MLP, the parts run one after the other: a
# part's (tokens, intermediate) float32 products are 0.18 GB at 4096
MLP_TOKEN_CHUNK = 4096


def unit_offset_norm(x, g, eps: float, dtype):
    """x / rms(x) * (1 + g), from float32, in ``dtype``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + g)).astype(dtype)


class EvaMixer(nn.Module):
    """``__call__(u)`` (B, S, hidden) -> (the block's float32 output, the
    summary share of the softmax's pairs in percent, rows that took another
    number of keys)."""
    net: Any        # Dims
    dtype: Any

    @nn.compact
    def __call__(self, u):
        n = self.net
        b, s, hidden = u.shape
        h, d = n.num_attention_heads, n.head_dim
        std = n.init_std
        w_q = self.param("q_proj", _normal(std), (hidden, h * d))
        w_k = self.param("k_proj", _normal(std), (hidden, h * d))
        w_v = self.param("v_proj", _normal(std), (hidden, h * d))
        w_o = self.param("o_proj", _normal(std), (h * d, hidden))
        mu = self.param("adaptive_mu_k", _normal(POOL_INIT_STD), (h, d))
        phi = self.param("adaptive_phi", _normal(POOL_INIT_STD), (h, d))

        def proj(w):
            return jnp.dot(u, w.astype(self.dtype)).reshape(b, s, h, d)

        q = rotary(proj(w_q), n.rope_theta)
        k = rotary(proj(w_k), n.rope_theta)
        v = proj(w_v)
        with jax.named_scope("eva_pool"):
            kbar, vbar = pool_chunks(k, v, mu, phi, n.eva_chunk)
        with jax.named_scope("eva_attend"):
            o, pct, misses = eva_attention(q, k, v, kbar, vbar, n.eva_window,
                                           n.eva_chunk, n.attn_block_q)
        y = jnp.dot(o.reshape(b, s, h * d), w_o.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        return y, pct, misses


class SwiGLU(nn.Module):
    hidden: int
    width: int
    std: float
    dtype: Any

    @nn.compact
    def __call__(self, u):
        b, s, h = u.shape
        w_gate = self.param("gate", _normal(self.std), (h, self.width))
        w_up = self.param("up", _normal(self.std), (h, self.width))
        w_down = self.param("down", _normal(self.std), (self.width, h))
        return moe_ops.swiglu_ffn(u.reshape(b * s, h), w_gate, w_up,
                                  w_down).reshape(b, s, h)


class Layer(nn.Module):
    """``__call__(x)`` float32 (B, S, hidden) -> (x, the mixer's two
    counters)."""
    net: Any        # Dims
    dtype: Any

    @nn.compact
    def __call__(self, x):
        n = self.net
        g_attn = self.param("attn_norm", nn.initializers.zeros,
                            (n.hidden_size,))
        g_mlp = self.param("mlp_norm", nn.initializers.zeros,
                           (n.hidden_size,))
        with jax.named_scope("eva"):
            y, pct, misses = EvaMixer(n, self.dtype, name="mixer")(
                unit_offset_norm(x, g_attn, n.norm_eps, self.dtype))
            x = x + y
        with jax.named_scope("dense_mlp"):
            x = x + self.mlp(unit_offset_norm(x, g_mlp, n.norm_eps,
                                              self.dtype))
        return x, pct, misses

    def mlp(self, u):
        """The MLP on the sequence's tokens in parts of ``MLP_TOKEN_CHUNK``
        consecutive tokens (the whole sequence where it is longer), one
        after the other, each an ``nn.remat``: the MLP is a function
        of each token alone, and its (tokens, intermediate) float32
        products exist for one part at a time."""
        n = self.net
        block = nn.remat(SwiGLU)(n.hidden_size, n.intermediate_size,
                                 n.init_std, self.dtype, name="mlp")
        s = u.shape[1]
        chunk = min(MLP_TOKEN_CHUNK, s)
        if s % chunk:
            raise ValueError(f"sequence {s} is no multiple of the MLP's "
                             f"part {chunk}")
        outs = []
        for lo in range(0, s, chunk):
            part = u[:, lo:lo + chunk]
            if outs:
                # the next part's input waits for the last part's output:
                # unordered, the compiler runs the parts side by side
                part, _ = jax.lax.optimization_barrier((part, outs[-1]))
            outs.append(block(part))
        return jnp.concatenate(outs, 1)


class EvaByte(nn.Module):
    """The stack; ``__call__(ids)`` is the training forward: the heads'
    mean loss and the step's counters."""
    net: Any        # Dims
    dtype: Any = jnp.bfloat16
    loss_chunk: int = 4096

    @nn.compact
    def __call__(self, ids) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        n = self.net
        b, s = ids.shape
        std = n.init_std
        table = self.param("embed", _normal(std),
                           (n.vocab_size, n.hidden_size))
        with jax.named_scope("embed"):
            x = table[ids]                                   # float32
        layer = nn.remat(Layer, policy=KEEP_EVA_RESIDUALS)
        pcts, misses = [], []
        for i in range(n.num_layers):
            x, pct, ms = layer(n, self.dtype, name=f"l{i}")(x)
            pcts.append(pct)
            misses.append(ms)
        g_final = self.param("final_norm", nn.initializers.zeros,
                             (n.hidden_size,))
        heads = self.param("heads", _normal(std),
                           (n.num_pred_heads, n.hidden_size, n.vocab_size))
        with jax.named_scope("lm_head"):
            h = unit_offset_norm(x, g_final, n.norm_eps,
                                 self.dtype).reshape(b * s, -1)
            losses = []
            for i in range(n.num_pred_heads):
                # head i at position t predicts ids[t + 1 + i]; the last
                # 1 + i positions have no target
                targets = jnp.roll(ids, -(1 + i), axis=1).reshape(-1)
                live = (jnp.arange(s) < s - 1 - i).astype(jnp.float32)
                weights = jnp.broadcast_to(live, (b, s)).reshape(-1)
                losses.append(chunked_cross_entropy(
                    h, heads[i], targets, weights, self.loss_chunk)
                    / (b * (s - 1 - i)))
            loss = sum(losses) / len(losses)
        return loss, {"loss_main": losses[0],
                      "eva_summary_keys_pct": sum(pcts) / len(pcts),
                      "eva_count_misses": sum(misses)}

    def init_variables(self, key):
        """(params, batch_stats) from one traced init on a sequence of one
        window; this family keeps no statistics."""
        s = max(self.net.eva_window, self.net.num_pred_heads + 1)
        variables = self.init(key, jnp.zeros((1, s), jnp.int32))
        return variables["params"], {}


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the stack reads of ``cfg.network``."""
    num_layers: int
    hidden_size: int
    vocab_size: int
    norm_eps: float
    init_std: float
    num_attention_heads: int
    head_dim: int
    rope_theta: float
    eva_window: int
    eva_chunk: int
    attn_block_q: int
    intermediate_size: int
    num_pred_heads: int


def build_lm(cfg: Config, quant_phase: str = "apply") -> EvaByte:
    """The family table's builder (``families.py``); a sequence family has
    no quantized form and ``quant_phase`` is not read."""
    from mx_rcnn_tpu.config import validate_dtype_string

    validate_dtype_string(cfg.network.compute_dtype, "network__compute_dtype")
    n = cfg.network
    if not n.layer_pattern or set(n.layer_pattern) != {"V"}:
        raise ValueError(f"an evabyte pattern is letters V alone (an EVA "
                         f"layer each), got {n.layer_pattern!r}")
    if n.num_key_value_heads != n.num_attention_heads:
        raise ValueError("EVA layers have as many key-value heads as query "
                         "heads")
    if n.eva_window % n.eva_chunk or n.num_pred_heads < 1:
        raise ValueError("the window is a multiple of the chunk, and there "
                         "is at least one prediction head")
    dims = Dims(
        num_layers=len(n.layer_pattern), hidden_size=n.hidden_size,
        vocab_size=n.vocab_size, norm_eps=n.norm_eps, init_std=n.init_std,
        num_attention_heads=n.num_attention_heads, head_dim=n.head_dim,
        rope_theta=n.rope_theta, eva_window=n.eva_window,
        eva_chunk=n.eva_chunk, attn_block_q=n.attn_block_q,
        intermediate_size=n.intermediate_size,
        num_pred_heads=n.num_pred_heads)
    return EvaByte(
        net=dims,
        dtype=(jnp.bfloat16 if n.compute_dtype == "bfloat16"
               else jnp.float32))
