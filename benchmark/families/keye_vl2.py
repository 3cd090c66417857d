"""The ``keye_vl2`` family (Keye-VL-2.0's language stack): layers of
grouped-query attention over the keys a learned indexer chooses (DeepSeek
Sparse Attention), each followed by a softmax-routed expert MLP with no
shared expert.  What the benchmark knows of the family's operation count is
here: its layer table and (``STAGES``) its step's scopes.  The plain
reference is ``benchmark/reference/keye_vl2.py``.

One sample is one sequence of ``traffic.seq_len`` tokens; the rows are laid
out as ``families/nemotron_h.py`` lays its own (a per-token row runs
``times`` = tokens a sample, a weight matrix is read once a sequence, a
product of two activations is ``grad: both``, the held experts' rows run at
the expected 8 x 16 / 128 assignments a token, the optimizer is no row).
The attention block counts the **sparse** work the algorithm requires: the
indexer scores every causal pair (``t + 1`` keys a query, forward only: the
choice takes no gradient), while attention, the indexer's loss and its
gradient see the chosen keys alone (``min(t + 1, topk)`` a query).  The
indexer's projections read the block's input with its gradient stopped, so
they give a gradient to their weights and none to their input.  Every row
also states the ``params`` it owns, so that the table's count can be held to
the model's.
"""

from __future__ import annotations

from benchmark.families.nemotron_h import (GROUPED, WIDTH, dense_row,
                                           gather_bytes, pointwise_row)
from benchmark.reference.keye_vl2 import held

STAGES = ("embed", "dsa", "moe", GROUPED, "lm_head", "optimizer",
          "grad_sync")


def _dense(name, scope, cin, cout, tokens, times=None, copies=1,
           grad="both"):
    """``dense_row`` with the parameters the row owns: ``copies`` matrices
    (a held expert each)."""
    return dict(dense_row(name, scope, cin, cout, tokens, times, grad),
                params=cin * cout * copies)


def _norm(name, scope, width, tokens, params=None):
    return dict(pointwise_row(name, scope, width, 4, 2, tokens),
                params=width if params is None else params)


def mean_keys(t: int, top_k: int) -> float:
    """Keys a query attends to, on average over positions ``0 .. t-1``:
    ``min(position + 1, top_k)``."""
    k = min(top_k, t)
    return (k * (k + 1) / 2 + (t - k) * k) / t


def index_scores_flops(heads, width, keys):
    """One query's indexer scores against ``keys`` keys: a product a head,
    its ReLU and its weighted sum."""
    return 2.0 * heads * width * keys + 3.0 * heads * keys


def attention_flops(heads, head_dim, keys):
    """One query's q.k and p.v against ``keys`` keys."""
    return 2.0 * 2 * heads * head_dim * keys


def attention_block(c, b, t):
    """The rows of one sparse attention block on ``t`` positions."""
    h, hq, hkv, d = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    sa = c["sa_config"]
    ji, di, top_k = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                     sa["topk"])
    rope = c["network"]["indexer_rope_dim"]
    s = "dsa"
    every, chosen = (t + 1) / 2, mean_keys(t, top_k)
    return [
        _norm(f"{b}.mix_norm", s, h, t),
        _dense(f"{b}.q_proj", s, h, hq * d, t),
        _dense(f"{b}.k_proj", s, h, hkv * d, t),
        _dense(f"{b}.v_proj", s, h, hkv * d, t),
        _norm(f"{b}.head_norms", s, (hq + hkv) * d, t, params=2 * d),
        dict(pointwise_row(f"{b}.rotary", s, (hq + hkv) * d, 6, 2, t),
             params=0),
        # the indexer reads the input with its gradient stopped
        _dense(f"{b}.index_q_proj", s, h, ji * di, t, grad="weight"),
        _dense(f"{b}.index_k_proj", s, h, di, t, grad="weight"),
        _norm(f"{b}.index_k_norm", s, di, t, params=2 * di),
        _dense(f"{b}.index_weights_proj", s, h, ji, t, grad="weight"),
        dict(pointwise_row(f"{b}.index_rotary", s, (ji + 1) * rope, 6, 2,
                           t), params=0),
        # every causal pair scored, for the choice alone
        {"name": f"{b}.index_scores", "scope": s, "times": t, "grad": "none",
         "flops": index_scores_flops(ji, di, every),
         "bytes": float(WIDTH * (ji * di + di) + 4 * ji), "params": 0},
        # the chosen pairs: the indexer's loss takes their scores again and
        # gives them a gradient
        {"name": f"{b}.index_loss_scores", "scope": s, "times": t,
         "grad": "both", "flops": index_scores_flops(ji, di, chosen),
         "bytes": float(WIDTH * (ji * di + di) + 4 * ji), "params": 0},
        {"name": f"{b}.attention", "scope": s, "times": t, "grad": "both",
         "flops": attention_flops(hq, d, chosen),
         "bytes": float(WIDTH * (2 * hq * d + 2 * hkv * d)), "params": 0},
        # the target: the heads' probabilities summed, the log-softmax of
        # the scores and the KL's terms, a chosen pair each
        {"name": f"{b}.index_target", "scope": s, "times": t,
         "grad": "input", "flops": float((hq + 8) * chosen), "bytes": 0.0,
         "params": 0},
        _dense(f"{b}.o_proj", s, hq * d, h, t)]


def expert_layer(c, b, t):
    """The rows of one routed-expert block on ``t`` positions."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    count, width = held(c)[1], c["published"]["num_experts"]
    per_token = c["num_experts_per_tok"] * count / width
    s = "moe"
    rows = [
        _norm(f"{b}.mlp_norm", s, h, t),
        _dense(f"{b}.router", s, h, width, t),
        dict(pointwise_row(f"{b}.softmax", s, width, 5, 2, t), params=0),
        {"name": f"{b}.gather_scatter", "scope": s, "times": t,
         "grad": "input", "flops": 0.0, "bytes": gather_bytes(h, per_token),
         "params": 0}]
    # a held expert's matrices are read once a sequence, by the rows routed
    # to it: 1 / count of the held rows each
    return rows + [_dense(f"{b}.experts_{n}", s, cin, cout,
                          t * per_token / count, times=t * per_token,
                          copies=count)
                   for n, cin, cout in (("gate", h, f), ("up", h, f),
                                        ("down", f, h))]


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one sequence."""
    c, t = config, traffic["seq_len"]
    h, v = c["hidden_size"], c["vocab_size"]
    rows = [{"name": "embed", "scope": "embed", "times": t, "grad": "weight",
             "flops": 0.0, "bytes": float(4 * h + WIDTH * h),
             "params": v * h}]
    for i in range(c["num_hidden_layers"]):
        rows += attention_block(c, f"l{i}", t) + expert_layer(c, f"l{i}", t)
    # the final norm sees every position, the head all but the last
    return rows + [
        _norm("final_norm", "lm_head", h, t),
        _dense("head", "lm_head", h, v, t, times=t - 1),
        {"name": "loss", "scope": "lm_head", "times": t - 1,
         "grad": "input", "flops": 4.0 * v, "bytes": 0.0, "params": 0}]


# what each Mosaic kernel of ``ops/sparse_attention_pallas.py`` does of the
# table's rows, as (row name's ending, passes): the choice scores every
# causal pair once; attention's forward is one pass of its row and its
# backward the two others; the indexer's loss forms its scores, target and
# KL and both of their gradients' products
KERNEL_ROWS = {
    "dsa_select": ((".index_scores", 1),),
    "dsa_attend_fwd": ((".attention", 1),),
    "dsa_attend_bwd": ((".attention", 2),),
    "dsa_kl": ((".index_loss_scores", 3), (".index_target", 2)),
}


def kernel_least_seconds(layers, peak, kernel: str) -> float:
    """Least time the chip could take for the work of ``kernel`` on one
    sample: row by row the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    from benchmark import flops

    total = 0.0
    for row in layers:
        for ending, passes in KERNEL_ROWS[kernel]:
            if row["name"].endswith(ending):
                n = passes * row["times"]
                total += max(flops.forward_flops(row) * n / peak["flops_per_s"],
                             flops.forward_bytes(row) * n / peak["bytes_per_s"])
    return total
