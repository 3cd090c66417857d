"""The ``joyai_flash`` family: JoyAI-LLM-Flash (jdopensource; DeepSeek-V3's
modelling), every width and the depth read from ``cfg.network``
(``build_lm``).

Layer ``i`` is two residual blocks, ``x <- x + MLA_i(RMSNorm(x))`` and ``x
<- x + mlp_i(RMSNorm(x))``: every mixer is latent attention with a low-rank
query (``q_a``, an RMSNorm, ``q_b``), a rotary term on adjacent channel
pairs, no head norm and no gate; the MLP of the first
``first_k_dense_replace`` layers is a dense SwiGLU, of the others a
routed-expert layer whose sigmoid router chooses among all experts.  The
three are ``models/ling_flash.py``'s classes (``MLA``, ``DenseMLP``,
``GatedMoE`` behind its ``Block``).  After the last layer a final RMSNorm
and an untied head give the next-token loss ``L_main``; then the
multi-token-prediction module (arXiv:2412.19437 section 2.2): at position
``i`` the embedding of token ``i + 1`` and the stack's normed output ``h_i``,
each through an RMSNorm of its own, side by side (the embedding's half
first) through ``eh_proj`` (2 hidden -> hidden), one more layer of the kind
above, a norm, and **the stack's own head**: ``L_mtp`` is the mean
cross-entropy against token ``i + 2``.  Embedding and head are one array
each, read by both paths.  The loss is ``L_main + mtp_loss_weight * L_mtp``.

The module runs on all ``S`` positions, the last two (whose shifted inputs
are rolled in from the sequence's start) left out of its loss: under the
causal mask positions ``0 .. S-3`` see nothing of them, and ``S`` tiles the
attention kernels where ``S - 2`` does not.  They are routed with the rest:
the module's expert counters hold two tokens a sequence more than the loss.

Activations and the residual stream are in ``dtype`` (bfloat16 on the chip),
parameters float32.  Every MLP block is a ``jax.checkpoint``, a mixer is one
a sequence (``MLA``).

Named scopes: ``embed``; ``mla``; ``dense_mlp``; ``moe`` with ``moe_route``,
``moe_experts`` (``moe_grouped`` inside) inside; ``lm_head`` (final norm,
head, ``L_main``); the whole module under ``mtp``: ``mtp_combine`` (the two
norms, ``eh_proj``), its block's own ``mla`` and ``moe``, ``mtp_head`` (its
norm, the head's second pass, ``L_mtp``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.ling_flash import Block
from mx_rcnn_tpu.models.nemotron_h import (INIT_STD, _normal,
                                           chunked_cross_entropy, rms_norm)


def shifted_loss(h, w_head, ids, shift: int, chunk: int):
    """Mean over positions ``0 .. S-1-shift`` of CE(h_i W_head,
    ids[i + shift]); h (B, S, hidden), the other positions' rows unread."""
    b, s = ids.shape
    live = (jnp.arange(s) < s - shift).astype(jnp.float32)
    total = chunked_cross_entropy(
        h.reshape(b * s, -1), w_head, jnp.roll(ids, -shift, 1).reshape(-1),
        jnp.broadcast_to(live, (b, s)).reshape(-1), chunk)
    return total / (b * (s - shift))


class MTP(nn.Module):
    """One multi-token-prediction module; ``__call__(h, ids, table,
    w_head)`` -> (``L_mtp``, its expert layer's sizes and overflow)."""
    net: Any        # Dims
    dtype: Any
    loss_chunk: int

    @nn.compact
    def __call__(self, h, ids, table, w_head):
        n = self.net
        e_norm = self.param("enorm", nn.initializers.ones, (n.hidden_size,))
        h_norm = self.param("hnorm", nn.initializers.ones, (n.hidden_size,))
        w_eh = self.param("eh_proj", _normal(INIT_STD),
                          (2 * n.hidden_size, n.hidden_size))
        s_norm = self.param("shared_head_norm", nn.initializers.ones,
                            (n.hidden_size,))
        with jax.named_scope("mtp"):
            with jax.named_scope("mtp_combine"):
                nxt = table[jnp.roll(ids, -1, 1)].astype(self.dtype)
                both = jnp.concatenate(
                    [rms_norm(nxt, e_norm, n.norm_eps),
                     rms_norm(h, h_norm, n.norm_eps)], -1)
                x = jnp.dot(both, w_eh.astype(self.dtype))
            x, _, _, _ = Block("L", n, self.dtype, name="mix")(x)
            x, sizes, overflow, _ = nn.remat(Block)(
                "E", n, self.dtype, name="mlp")(x)
            with jax.named_scope("mtp_head"):
                loss = shifted_loss(rms_norm(x, s_norm, n.norm_eps), w_head,
                                    ids, 2, self.loss_chunk)
        return loss, sizes, overflow


class JoyAIFlash(nn.Module):
    """The stack and its module; ``__call__(ids)`` is the training forward:
    the weighted loss, its two parts and the routed-expert counters (the
    stack's expert layers, then the module's)."""
    net: Any        # Dims
    dtype: Any = jnp.bfloat16
    loss_chunk: int = 4096

    @nn.compact
    def __call__(self, ids) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        n = self.net
        table = self.param("embed", _normal(INIT_STD),
                           (n.vocab_size, n.hidden_size))
        with jax.named_scope("embed"):
            x = table[ids].astype(self.dtype)
        sizes, overflow = [], []
        for i in range(n.num_layers):
            mlp = "D" if i < n.first_k_dense_replace else "E"
            # a mixer checkpoints itself, a sequence at a time
            x, _, _, _ = Block("L", n, self.dtype, name=f"l{i}_mix")(x)
            x, sz, ov, _ = nn.remat(Block)(mlp, n, self.dtype,
                                           name=f"l{i}_mlp")(x)
            if mlp == "E":
                sizes.append(sz)
                overflow.append(ov)
        final = self.param("final_norm", nn.initializers.ones,
                           (n.hidden_size,))
        w_head = self.param("head", _normal(INIT_STD),
                            (n.hidden_size, n.vocab_size))
        with jax.named_scope("lm_head"):
            h = rms_norm(x, final, n.norm_eps)
            loss = loss_main = shifted_loss(h, w_head, ids, 1,
                                            self.loss_chunk)
        aux = {"loss_main": loss_main}
        if n.num_nextn_predict_layers:
            aux["mtp_loss"], sz, ov = MTP(n, self.dtype, self.loss_chunk,
                                          name="mtp")(h, ids, table, w_head)
            loss = loss_main + n.mtp_loss_weight * aux["mtp_loss"]
            sizes.append(sz)
            overflow.append(ov)
        return loss, dict(aux, sizes=jnp.stack(sizes),
                          overflow=jnp.stack(overflow))

    def init_variables(self, key):
        """(params, batch_stats) from one traced init on a sequence of one
        attention block; this family keeps no statistics."""
        variables = self.init(
            key, jnp.zeros((1, self.net.attn_block_q), jnp.int32))
        return variables["params"], {}


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the stack and ``ling_flash``'s blocks read of ``cfg.network``,
    under the same names; the last two are the family's own."""
    num_layers: int
    first_k_dense_replace: int
    hidden_size: int
    vocab_size: int
    norm_eps: float
    init_layers: int
    intermediate_size: int
    num_attention_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rope_interleave: bool
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
    num_nextn_predict_layers: int
    mtp_loss_weight: float
    qk_head_norms: bool = False
    head_gate: bool = False


def build_lm(cfg: Config, quant_phase: str = "apply") -> JoyAIFlash:
    """The family table's builder (``families.py``); a sequence family has
    no quantized form and ``quant_phase`` is not read."""
    from mx_rcnn_tpu.config import validate_dtype_string

    validate_dtype_string(cfg.network.compute_dtype, "network__compute_dtype")
    n = cfg.network
    if not n.layer_pattern or set(n.layer_pattern) != {"L"}:
        raise ValueError(f"a joyai_flash pattern is letters L alone, got "
                         f"{n.layer_pattern!r}")
    if n.first_k_dense_replace >= len(n.layer_pattern):
        raise ValueError("a joyai_flash stack holds at least one "
                         "routed-expert layer")
    if n.num_nextn_predict_layers not in (0, 1):
        raise ValueError(f"0 or 1 multi-token-prediction module, got "
                         f"{n.num_nextn_predict_layers}")
    dims = Dims(
        num_layers=len(n.layer_pattern),
        first_k_dense_replace=n.first_k_dense_replace,
        hidden_size=n.hidden_size, vocab_size=n.vocab_size,
        norm_eps=n.norm_eps, init_layers=n.init_layers,
        intermediate_size=n.intermediate_size,
        num_attention_heads=n.num_attention_heads,
        q_lora_rank=n.q_lora_rank or None, kv_lora_rank=n.kv_lora_rank,
        qk_nope_head_dim=n.qk_nope_head_dim,
        qk_rope_head_dim=n.qk_rope_head_dim, v_head_dim=n.v_head_dim,
        rope_theta=n.rope_theta, rope_interleave=n.rope_interleave,
        attn_block_q=n.attn_block_q, n_routed_experts=n.n_routed_experts,
        experts_held=tuple(n.experts_held),
        num_experts_per_tok=n.num_experts_per_tok, n_group=n.n_group,
        topk_group=n.topk_group,
        moe_intermediate_size=n.moe_intermediate_size,
        moe_shared_expert_intermediate_size=(
            n.moe_shared_expert_intermediate_size),
        routed_scaling_factor=n.routed_scaling_factor,
        norm_topk_prob=n.norm_topk_prob,
        moe_capacity_factor=n.moe_capacity_factor,
        num_nextn_predict_layers=n.num_nextn_predict_layers,
        mtp_loss_weight=n.mtp_loss_weight)
    return JoyAIFlash(
        net=dims,
        dtype=(jnp.bfloat16 if n.compute_dtype == "bfloat16"
               else jnp.float32))
