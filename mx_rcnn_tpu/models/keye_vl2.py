"""The ``keye_vl2`` family: the language stack of Keye-VL-2.0 (Kwai-Keye),
every width and the depth read from ``cfg.network`` (``build_lm``).  No
vision tower: text positions alone, under which the three sections of its
multimodal rotary term carry one position and it is the 1-D term.

Layer ``i`` is two residual blocks, ``x <- x + DSA_i(RMSNorm(x))`` and ``x
<- x + MoE_i(RMSNorm(x))``:

* ``DSA``: grouped-query attention (32 query heads over 4 key-value heads
  of 128), an RMSNorm over each head's query and key, the rotary term on
  all 128 channels (halves paired, ``ling_flash.rotary``), each query
  attending to the keys an indexer chooses (``ops/sparse_attention.py``).
  The indexer reads the block's normed input with its gradient stopped:
  16 query heads of 64 (``index_q_proj``), one key head of 64 through a
  LayerNorm (``index_k_proj``, ``index_k_norm``), the rotary term on the
  first ``indexer_rope_dim`` channels of both, and a weight a query head
  (``index_weights_proj``, times 16^-1/2).  Its loss, the KL divergence of
  its softmax over the chosen keys from the main attention's head-summed
  probabilities, is added to the step's loss, one mean over positions a
  layer: the next-token loss never reaches the indexer, and its own loss
  reaches nothing else;
* ``MoE``: ``ling_flash.GatedMoE`` (softmax router over all experts, the
  top ``num_experts_per_tok`` renormalised, gated experts, no shared
  expert), in ``ling_flash.Block``, over the sequence in parts of
  ``moe_token_chunk`` tokens (``KeyeVL2.expert_block``).

After the last layer a final RMSNorm and an untied head give the next-token
loss.  Activations and the residual stream are in ``dtype`` (bfloat16 on
the chip), parameters float32.  An attention block is a ``jax.checkpoint``
a sequence that keeps the choice, a threshold and a cut a query, and on
the kernels' path the indexer loss's gradient (``KEEP_CHOICE``); an MoE
block is an ``nn.remat``.

Named scopes: ``embed``; ``dsa`` (the attention block whole) with
``dsa_index`` (the indexer's projections, scores and choice),
``dsa_attend`` (attention over the chosen keys) and ``dsa_index_loss`` (the
target and the KL) inside; ``moe`` with ``moe_route``, ``moe_experts``
(``moe_grouped`` inside); ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.ling_flash import Block, rotary
from mx_rcnn_tpu.models.nemotron_h import (INIT_STD, _normal,
                                           chunked_cross_entropy, rms_norm)
from mx_rcnn_tpu.ops.sparse_attention import KEEP_CHOICE, sparse_attention


def sample_rows(s: int):
    """The query rows whose chosen keys the step hands out: four positions
    spread over the sequence, the last among them."""
    return tuple(sorted({s // 4 + 1, s // 2 + 3, (3 * s) // 4 + 5, s - 1}))


def layer_norm(x, weight, bias, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * weight + bias).astype(
        x.dtype)


def rotary_part(x, theta: float, width: int):
    """The rotary term on the first ``width`` channels of x's last axis."""
    return jnp.concatenate([rotary(x[..., :width], theta), x[..., width:]],
                           -1)


class DSA(nn.Module):
    """Attention over the keys the indexer chooses; ``__call__(x)`` (B, S,
    hidden) -> (y, the layer's indexer loss (mean over positions), the
    share of causal tiles touched, the first sequence's chosen keys of
    ``sample_rows``, the queries whose attention took another number of
    keys than the choice holds)."""
    net: Any        # Dims
    out_std: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        n = self.net
        b, s, h = x.shape
        hq, hkv, d = n.num_attention_heads, n.num_key_value_heads, n.head_dim
        ji, di = n.indexer_num_heads, n.indexer_head_dim
        w_q = self.param("q_proj", _normal(INIT_STD), (h, hq * d))
        w_k = self.param("k_proj", _normal(INIT_STD), (h, hkv * d))
        w_v = self.param("v_proj", _normal(INIT_STD), (h, hkv * d))
        q_norm = self.param("q_norm", nn.initializers.ones, (d,))
        k_norm = self.param("k_norm", nn.initializers.ones, (d,))
        w_o = self.param("o_proj", _normal(self.out_std), (hq * d, h))
        w_iq = self.param("index_q_proj", _normal(INIT_STD), (h, ji * di))
        w_ik = self.param("index_k_proj", _normal(INIT_STD), (h, di))
        ik_w = self.param("index_k_norm", nn.initializers.ones, (di,))
        ik_b = self.param("index_k_norm_bias", nn.initializers.zeros, (di,))
        w_w = self.param("index_weights_proj", _normal(INIT_STD), (h, ji))
        rows = sample_rows(s)

        def attend(x):
            """One sequence (S, hidden): no parameter is made in here."""
            def proj(t, w):
                return jnp.dot(t, w.astype(self.dtype))

            with jax.named_scope("dsa_index"):
                xb = jax.lax.stop_gradient(x)
                qi = rotary_part(proj(xb, w_iq).reshape(1, s, ji, di),
                                 n.rope_theta, n.indexer_rope_dim)[0]
                ki = rotary_part(layer_norm(proj(xb, w_ik), ik_w, ik_b,
                                            n.norm_eps)[None],
                                 n.rope_theta, n.indexer_rope_dim)[0]
                wi = jnp.dot(xb, w_w.astype(self.dtype),
                             preferred_element_type=jnp.float32) * ji ** -0.5
            q = rms_norm(proj(x, w_q).reshape(1, s, hq, d), q_norm,
                         n.norm_eps)
            k = rms_norm(proj(x, w_k).reshape(1, s, hkv, d), k_norm,
                         n.norm_eps)
            q, k = rotary(q, n.rope_theta)[0], rotary(k, n.rope_theta)[0]
            v = proj(x, w_v).reshape(s, hkv, d)
            o, kl, touched, chosen, misses = sparse_attention(
                q, k, v, qi, wi, ki, n.indexer_topk, n.attn_block_q, rows)
            return (proj(o.reshape(s, hq * d), w_o), kl, touched, chosen,
                    misses)

        y, kl, touched, chosen, misses = jax.lax.map(
            jax.checkpoint(attend, policy=KEEP_CHOICE), x)
        return (y, jnp.sum(kl) / (b * s), jnp.mean(touched), chosen[0],
                jnp.sum(misses))


class AttentionBlock(nn.Module):
    """x + DSA(RMSNorm(x)) under scope ``dsa``; returns (x, and ``DSA``'s
    counters)."""
    net: Any        # Dims
    dtype: Any

    @nn.compact
    def __call__(self, x):
        n = self.net
        scale = self.param("norm", nn.initializers.ones, (n.hidden_size,))
        out_std = INIT_STD / math.sqrt(2 * n.init_layers)
        with jax.named_scope("dsa"):
            y, *counters = DSA(n, out_std, self.dtype, name="mixer")(
                rms_norm(x, scale, n.norm_eps))
            return (x + y, *counters)


class KeyeVL2(nn.Module):
    """The stack; ``__call__(ids)`` is the training forward: the loss
    (next-token loss plus every layer's indexer loss), its parts and the
    counters."""
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
        sizes, overflow, kls, touched, chosen, misses = [], [], [], [], [], []
        for i in range(n.num_layers):
            # an attention block checkpoints itself, a sequence at a time
            x, kl, tt, ch, ms = AttentionBlock(n, self.dtype,
                                               name=f"l{i}_mix")(x)
            x, sz, ov = self.expert_block(x, i)
            sizes.append(sz)
            overflow.append(ov)
            kls.append(kl)
            touched.append(tt)
            chosen.append(ch)
            misses.append(ms)
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
            loss_main = chunked_cross_entropy(
                h, w_head, targets, weights, self.loss_chunk) / (b * (s - 1))
        index_loss = sum(kls)
        return loss_main + index_loss, {
            "sizes": jnp.stack(sizes), "overflow": jnp.stack(overflow),
            "loss_main": loss_main, "index_loss": index_loss,
            "tiles_touched_pct": 100.0 * jnp.mean(jnp.stack(touched)),
            "dsa_chosen": jnp.stack(chosen),
            "dsa_count_misses": sum(misses)}

    def expert_block(self, x, i: int):
        """Layer ``i``'s ``ling_flash.Block("E")`` (an ``nn.remat``) on the
        sequence's tokens in parts of ``moe_token_chunk`` consecutive
        tokens, one after the other (the whole sequence where it is 0 or
        longer):
        the layer is a function of each token alone, and a part's held
        rows are sorted, kept and computed on their own, so that the row
        capacity (``moe_capacity_factor`` of a part's even share) is
        memory for one part at a time.  Returns (x, assignments to each
        held expert, rows not computed), summed over the parts."""
        n = self.net
        block = nn.remat(Block)("E", n, self.dtype, name=f"l{i}_mlp")
        s = x.shape[1]
        chunk = min(n.moe_token_chunk or s, s)
        if s % chunk:
            raise ValueError(f"sequence {s} is no multiple of the expert "
                             f"layer's part {chunk}")
        outs, sizes, overflow = [], 0, 0
        for lo in range(0, s, chunk):
            xp = x[:, lo:lo + chunk]
            if outs:
                # one part after the other: the next part's input waits for
                # the last part's output, so that one part's rows exist at
                # a time (unordered, the compiler runs the parts side by
                # side)
                xp, _ = jax.lax.optimization_barrier((xp, outs[-1]))
            y, sz, ov, _ = block(xp)
            outs.append(y)
            sizes, overflow = sizes + sz, overflow + ov
        return jnp.concatenate(outs, 1), sizes, overflow

    def init_variables(self, key):
        """(params, batch_stats) from one traced init on a sequence of one
        attention block; this family keeps no statistics."""
        variables = self.init(
            key, jnp.zeros((1, self.net.attn_block_q), jnp.int32))
        return variables["params"], {}


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the stack and ``ling_flash``'s expert block read of
    ``cfg.network``, under the same names."""
    num_layers: int
    hidden_size: int
    vocab_size: int
    norm_eps: float
    init_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    attn_block_q: int
    indexer_num_heads: int
    indexer_head_dim: int
    indexer_rope_dim: int
    indexer_topk: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_capacity_factor: float
    norm_topk_prob: bool
    # tokens a part of the expert layer (0: the whole sequence)
    moe_token_chunk: int = 0
    # what ``ling_flash.GatedMoE`` reads beside (``ling_flash.MOE_DEFAULTS``)
    scoring_func: str = "softmax"
    moe_group_rows: int = 0
    # what ``GatedMoE`` reads beside, at this family's values: no shared
    # expert, no scaling, no group limit
    moe_shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    n_group: int = 0
    topk_group: int = 0


def build_lm(cfg: Config, quant_phase: str = "apply") -> KeyeVL2:
    """The family table's builder (``families.py``); a sequence family has
    no quantized form and ``quant_phase`` is not read."""
    from mx_rcnn_tpu.config import validate_dtype_string

    validate_dtype_string(cfg.network.compute_dtype, "network__compute_dtype")
    n = cfg.network
    if not n.layer_pattern or set(n.layer_pattern) != {"S"}:
        raise ValueError(f"a keye_vl2 pattern is letters S alone (a sparse "
                         f"attention layer each), got {n.layer_pattern!r}")
    if n.num_attention_heads % n.num_key_value_heads:
        raise ValueError("query heads are no multiple of key-value heads")
    if n.indexer_rope_dim % 2 or n.indexer_rope_dim > n.indexer_head_dim:
        raise ValueError("the indexer's rotary width is even and within "
                         "its head")
    dims = Dims(
        num_layers=len(n.layer_pattern), hidden_size=n.hidden_size,
        vocab_size=n.vocab_size, norm_eps=n.norm_eps,
        init_layers=n.init_layers,
        num_attention_heads=n.num_attention_heads,
        num_key_value_heads=n.num_key_value_heads, head_dim=n.head_dim,
        rope_theta=n.rope_theta, attn_block_q=n.attn_block_q,
        indexer_num_heads=n.indexer_num_heads,
        indexer_head_dim=n.indexer_head_dim,
        indexer_rope_dim=n.indexer_rope_dim, indexer_topk=n.indexer_topk,
        n_routed_experts=n.n_routed_experts,
        experts_held=tuple(n.experts_held),
        num_experts_per_tok=n.num_experts_per_tok,
        moe_intermediate_size=n.moe_intermediate_size,
        moe_capacity_factor=n.moe_capacity_factor,
        norm_topk_prob=n.norm_topk_prob, scoring_func=n.router_scoring,
        moe_token_chunk=n.moe_token_chunk, moe_group_rows=n.moe_group_rows)
    return KeyeVL2(
        net=dims,
        dtype=(jnp.bfloat16 if n.compute_dtype == "bfloat16"
               else jnp.float32))
