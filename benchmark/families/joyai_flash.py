"""The ``joyai_flash`` family (JoyAI-LLM-Flash, DeepSeek-V3's modelling):
layers of a latent-attention mixer with a low-rank query, each followed by a
dense SwiGLU or a routed-expert MLP, and after the last layer a
multi-token-prediction module that shares the stack's embedding and head.
What the benchmark knows of the family's operation count is here: its layer
table and (``STAGES``) its step's scopes.  The plain reference is
``benchmark/reference/joyai_flash.py``.

One sample is one sequence of ``traffic.seq_len`` tokens; the rows are laid
out as ``families/nemotron_h.py`` and ``families/ling_flash.py`` lay theirs
(a per-token row runs ``times`` = tokens a sample, a weight matrix is read
once a sequence, a product of two activations is ``grad: both``, the held
experts' rows run at the expected 8 x 16 / 256 assignments a token, the
optimizer is no row), with their row helpers and ``ling_flash``'s causal
scores of heads whose query/key width (192) is not their value width (128).
The module's rows run on the ``S - 2`` positions its loss holds — what the
algorithm requires; the program's two masked positions are padding — its
block's under the block's own scopes ``mla`` and ``moe`` (no op lies under
two stages), its head's pass under ``mtp_head``.  Every row also states the
``params`` it owns (the second pass of the head and the module's embedding
own none), so that the table's count can be held to the model's.
"""

from __future__ import annotations

from benchmark.families.ling_flash import (latent_scores_bytes,
                                           latent_scores_flops)
from benchmark.families.nemotron_h import (GROUPED, WIDTH, dense_row,
                                           gather_bytes, pointwise_row)
from benchmark.reference.joyai_flash import held

STAGES = ("embed", "mla", "dense_mlp", "moe", GROUPED, "lm_head",
          "optimizer", "grad_sync", "mtp_combine", "mtp_head")


def _dense(name, scope, cin, cout, tokens, times=None, copies=1, owns=True):
    """``dense_row`` with the parameters the row owns: ``copies`` matrices
    (a held expert each), none where another row owns them."""
    return dict(dense_row(name, scope, cin, cout, tokens, times),
                params=cin * cout * copies if owns else 0)


def _norm(name, scope, width, tokens):
    return dict(pointwise_row(name, scope, width, 4, 2, tokens), params=width)


def latent_block(c, b, t):
    """The rows of one latent-attention block on ``t`` positions."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    q_rank, rank = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    s = "mla"
    return [
        _norm(f"{b}.mix_norm", s, h, t),
        _dense(f"{b}.q_a_proj", s, h, q_rank, t),
        _norm(f"{b}.q_a_norm", s, q_rank, t),
        _dense(f"{b}.q_b_proj", s, q_rank, heads * (nope + rope), t),
        _dense(f"{b}.kv_a_proj", s, h, rank + rope, t),
        _norm(f"{b}.kv_a_norm", s, rank, t),
        _dense(f"{b}.kv_b_proj", s, rank, heads * (nope + vd), t),
        # the rotary term on every head's query part and the one key part
        dict(pointwise_row(f"{b}.rotary", s, (heads + 1) * rope, 6, 2, t),
             params=0),
        {"name": f"{b}.scores", "scope": s, "times": t, "grad": "both",
         "flops": latent_scores_flops(heads, nope + rope, vd, t),
         "bytes": latent_scores_bytes(heads, nope + rope, vd), "params": 0},
        _dense(f"{b}.o_proj", s, heads * vd, h, t)]


def expert_layer(c, b, t):
    """The rows of one routed-expert block on ``t`` positions."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    fs = c["n_shared_experts"] * f
    count, width = held(c)[1], c["published"]["n_routed_experts"]
    per_token = c["num_experts_per_tok"] * count / width
    s = "moe"
    rows = [
        _norm(f"{b}.mlp_norm", s, h, t),
        _dense(f"{b}.router", s, h, width, t),
        {"name": f"{b}.gather_scatter", "scope": s, "times": t,
         "grad": "input", "flops": 0.0, "bytes": gather_bytes(h, per_token),
         "params": 0}]
    # a held expert's matrices are read once a sequence, by the rows routed
    # to it: 1 / count of the held rows each
    rows += [_dense(f"{b}.experts_{n}", s, cin, cout, t * per_token / count,
                    times=t * per_token, copies=count)
             for n, cin, cout in (("gate", h, f), ("up", h, f),
                                  ("down", f, h))]
    return rows + [_dense(f"{b}.shared_{n}", s, cin, cout, t)
                   for n, cin, cout in (("gate", h, fs), ("up", h, fs),
                                        ("down", fs, h))]


def head_pass(c, prefix, scope, t, owns):
    """The head's product and the loss on ``t`` positions."""
    h, v = c["hidden_size"], c["vocab_size"]
    return [_dense(f"{prefix}head", scope, h, v, t, owns=owns),
            {"name": f"{prefix}loss", "scope": scope, "times": t,
             "grad": "input", "flops": 4.0 * v, "bytes": 0.0, "params": 0}]


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one sequence."""
    c, t = config, traffic["seq_len"]
    h, v, wide = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    first = c["network"]["first_layer"]
    gather = {"times": t, "grad": "weight", "flops": 0.0,
              "bytes": float(4 * h + WIDTH * h)}
    rows = [dict(gather, name="embed", scope="embed", params=v * h)]
    for i in range(c["num_hidden_layers"]):
        b = f"l{i}"
        rows += latent_block(c, b, t)
        if first + i < c["first_k_dense_replace"]:
            s = "dense_mlp"
            rows += [_norm(f"{b}.mlp_norm", s, h, t),
                     _dense(f"{b}.gate", s, h, wide, t),
                     _dense(f"{b}.up", s, h, wide, t),
                     dict(pointwise_row(f"{b}.swiglu", s, wide, 5, 3, t),
                          params=0),
                     _dense(f"{b}.down", s, wide, h, t)]
        else:
            rows += expert_layer(c, b, t)
    # the final norm sees every position, the head all but the last
    rows += [_norm("final_norm", "lm_head", h, t)] + head_pass(
        c, "", "lm_head", t - 1, True)
    if c["num_nextn_predict_layers"]:
        m, s = t - 2, "mtp_combine"
        rows += [dict(gather, name="mtp.embed", scope=s, times=m, params=0),
                 _norm("mtp.enorm", s, h, m), _norm("mtp.hnorm", s, h, m),
                 _dense("mtp.eh_proj", s, 2 * h, h, m)]
        rows += latent_block(c, "mtp", m) + expert_layer(c, "mtp", m)
        rows += [_norm("mtp.shared_head_norm", "mtp_head", h, m)] + head_pass(
            c, "mtp.", "mtp_head", m, False)
    return rows
