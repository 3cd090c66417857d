"""The ``ling_flash`` family (Ling-3.0-flash-VL's language stack): layers of
a KDA (``K``) or a latent-attention (``L``) mixer, each followed by a dense
SwiGLU or a routed-expert MLP.  What the benchmark knows of the family's
operation count is here: its layer table and (``STAGES``) its step's scopes.
The plain reference is ``benchmark/reference/ling_flash.py``.

One sample is one sequence of ``traffic.seq_len`` tokens; the rows are laid
out as ``families/nemotron_h.py`` lays its own (a per-token row runs
``times`` = tokens a sample, a weight matrix is read once a sequence, a
product of two activations is ``grad: both``, the held experts' rows run at
the expected 8 x 8 / 512 assignments a token, the optimizer is no row), and
the rows of a plain product, of a pointwise layer and of the experts'
gather are that file's.  This file's own are the chunked delta rule, the
latent projections and the causal scores of heads whose query/key width
(192) is not their value width (128).
"""

from __future__ import annotations

from benchmark.families.nemotron_h import (GROUPED, WIDTH, dense_row,
                                           gather_bytes, pointwise_row)
from benchmark.reference.ling_flash import held, pattern

STAGES = ("embed", "kda_mixer", "mla", "dense_mlp", "moe", GROUPED,
          "lm_head", "optimizer", "grad_sync")


def delta_rule_flops(heads, dk, dv, chunk):
    """One token of the chunked gated delta rule (the WY form), a
    multiply-add two: its row of the two score matrices ``A_qk`` and
    ``A_kk`` against the (chunk + 1) / 2 keys of its chunk at or before it;
    its row of the unit-lower-triangular solve for ``W`` (dk wide) and ``U``
    (dv wide), (chunk - 1) / 2 rows before it; ``W S``, ``(q exp G) S`` and
    its part of the state ``k^T u``, dk x dv each; ``A_qk U``."""
    seen = (chunk + 1) / 2
    return 2.0 * heads * (2 * seen * dk + (chunk - 1) / 2 * (dk + dv)
                          + 3 * dk * dv + seen * dv)


def delta_rule_bytes(heads, dk, dv, chunk):
    """q, k, v in and o out, the float32 log-decay of every key channel,
    beta, and the float32 state a chunk writes and the next reads, over the
    chunk's tokens."""
    return (WIDTH * heads * (2 * dk + 2 * dv) + 4 * heads * (dk + 1)
            + 2 * 4 * heads * dk * dv / chunk)


def latent_scores_flops(heads, qk, vd, tokens):
    """One query of causal attention whose scores are ``qk`` wide and whose
    values ``vd``: q.k and p.v against the (tokens + 1) / 2 keys it sees on
    average."""
    return 2.0 * heads * (qk + vd) * (tokens + 1) / 2


def latent_scores_bytes(heads, qk, vd):
    """q and k of every head in, v in, o out."""
    return float(WIDTH * heads * (2 * qk + 2 * vd))


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one sequence."""
    c, t = config, traffic["seq_len"]
    h, v = c["hidden_size"], c["vocab_size"]
    heads, hd = c["num_attention_heads"], c["head_dim"]
    inner, chunk = heads * hd, c["network"]["chunk_size"]
    rank, nope, rope, vd = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                            c["qk_rope_head_dim"], c["v_head_dim"])
    count, width = held(c)[1], c["published"]["num_experts"]
    per_token = c["num_experts_per_tok"] * count / width
    f, fs = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    rows = [{"name": "embed", "scope": "embed", "times": t, "grad": "weight",
             "flops": 0.0, "bytes": float(4 * h + WIDTH * h)}]
    for i, kind in enumerate(pattern(c)):
        b = f"l{i}"
        if kind == "K":
            s = "kda_mixer"
            rows += [pointwise_row(f"{b}.mix_norm", s, h, 4, 2, t)]
            rows += [dense_row(f"{b}.{n}_proj", s, h, inner, t)
                     for n in "qkvf"]
            rows += [
                dense_row(f"{b}.b_proj", s, h, heads, t),
                dense_row(f"{b}.g_proj", s, h, heads, t),
                # three convolutions with SiLU, two L2 norms, the gate
                pointwise_row(f"{b}.conv", s, 3 * inner,
                              2 * c["short_conv_kernel_size"] + 4, 2, t),
                pointwise_row(f"{b}.unit_gate", s, 3 * inner, 6, 2, t),
                {"name": f"{b}.delta_rule", "scope": s, "times": t,
                 "grad": "both",
                 "flops": delta_rule_flops(heads, hd, hd, chunk),
                 "bytes": delta_rule_bytes(heads, hd, hd, chunk)},
                pointwise_row(f"{b}.gated_norm", s, inner, 8, 2, t),
                dense_row(f"{b}.o_proj", s, inner, h, t)]
        else:
            s = "mla"
            rows += [
                pointwise_row(f"{b}.mix_norm", s, h, 4, 2, t),
                dense_row(f"{b}.q_proj", s, h, heads * (nope + rope), t),
                dense_row(f"{b}.kv_a_proj", s, h, rank + rope, t),
                pointwise_row(f"{b}.kv_a_norm", s, rank, 4, 2, t),
                dense_row(f"{b}.kv_b_proj", s, rank, heads * (nope + vd), t),
                # the two head norms and the rotary term
                pointwise_row(f"{b}.qk_norm_rotary", s,
                              2 * heads * (nope + rope), 8, 2, t),
                {"name": f"{b}.scores", "scope": s, "times": t,
                 "grad": "both",
                 "flops": latent_scores_flops(heads, nope + rope, vd, t),
                 "bytes": latent_scores_bytes(heads, nope + rope, vd)},
                dense_row(f"{b}.g_proj", s, h, heads, t),
                dense_row(f"{b}.o_proj", s, heads * vd, h, t)]
        if i < c["first_k_dense_replace"]:
            s, wide = "dense_mlp", c["intermediate_size"]
            rows += [pointwise_row(f"{b}.mlp_norm", s, h, 4, 2, t),
                     dense_row(f"{b}.gate", s, h, wide, t),
                     dense_row(f"{b}.up", s, h, wide, t),
                     pointwise_row(f"{b}.swiglu", s, wide, 5, 3, t),
                     dense_row(f"{b}.down", s, wide, h, t)]
            continue
        s = "moe"
        rows += [
            pointwise_row(f"{b}.mlp_norm", s, h, 4, 2, t),
            dense_row(f"{b}.router", s, h, width, t),
            {"name": f"{b}.gather_scatter", "scope": s, "times": t,
             "grad": "input", "flops": 0.0,
             "bytes": gather_bytes(h, per_token)}]
        # a held expert's matrices are read once a sequence, by the rows
        # routed to it: 1 / count of the held rows each
        rows += [dense_row(f"{b}.experts_{n}", s, cin, cout,
                           t * per_token / count, times=t * per_token)
                 for n, cin, cout in (("gate", h, f), ("up", h, f),
                                      ("down", f, h))]
        rows += [dense_row(f"{b}.shared_{n}", s, cin, cout, t)
                 for n, cin, cout in (("gate", h, fs), ("up", h, fs),
                                      ("down", fs, h))]
    return rows + [
        pointwise_row("final_norm", "lm_head", h, 4, 2, t),
        dense_row("head", "lm_head", h, v, t, times=t - 1),
        {"name": "loss", "scope": "lm_head", "times": t - 1, "grad": "input",
         "flops": 4.0 * v, "bytes": 0.0}]
