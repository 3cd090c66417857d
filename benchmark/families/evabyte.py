"""The ``evabyte`` family (EvaByte): layers of EVA attention (exact keys
inside a window, one pooled key and value for each earlier chunk, one
softmax) and a dense SwiGLU on a float32 residual stream, then several
byte-prediction heads.  What the benchmark knows of the family's operation
count is here: its layer table and (``STAGES``) its step's scopes.  The
plain reference is ``benchmark/reference/evabyte.py``.

One sample is one sequence of ``traffic.seq_len`` bytes; the rows are laid
out as ``families/nemotron_h.py`` lays its own (a per-token row runs
``times`` = tokens a sample, a weight matrix is read once a sequence, a
product of two activations is ``grad: both``, the optimizer is no row).
The attention block counts the keys the algorithm gives each query: the
exact keys of its window up to itself, ``t - s(t) + 1``, and the summaries
of the chunks before its window, ``s(t) / chunk`` (``mean_keys``), each a
row of its own; the pooling is a row of its own, bound by its bytes.
Every row also states the ``params`` it owns, so that the table's count can
be held to the model's.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.nemotron_h import WIDTH, dense_row, pointwise_row

STAGES = ("embed", "eva", "dense_mlp", "lm_head", "optimizer", "grad_sync")


def _dense(name, scope, cin, cout, tokens, times=None, grad="both"):
    return dict(dense_row(name, scope, cin, cout, tokens, times, grad),
                params=cin * cout)


def _norm(name, scope, width, tokens):
    return dict(pointwise_row(name, scope, width, 4, 2, tokens), params=width)


def mean_keys(t: int, window: int, chunk: int):
    """(exact keys, summaries) a query takes, on average over positions
    ``0 .. t-1``."""
    pos = np.arange(t)
    start = window * (pos // min(window, t))
    return float(np.mean(pos - start + 1)), float(np.mean(start // chunk))


def attention_flops(heads, head_dim, keys):
    """One query's q.k and p.v against ``keys`` keys."""
    return 2.0 * 2 * heads * head_dim * keys


def eva_block(c, b, t):
    """The rows of one EVA attention block on ``t`` positions."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    d, chunk = h // heads, c["chunk_size"]
    local, summaries = mean_keys(t, c["window_size"], chunk)
    s = "eva"
    return [
        _norm(f"{b}.attn_norm", s, h, t),
        _dense(f"{b}.q_proj", s, h, h, t),
        _dense(f"{b}.k_proj", s, h, h, t),
        _dense(f"{b}.v_proj", s, h, h, t),
        dict(pointwise_row(f"{b}.rotary", s, 2 * h, 6, 2, t), params=0),
        # each position's two pooling logits (mu . k, phi . k), their
        # softmax over the chunk and its share of the two weighted sums:
        # k and v read, the summaries written once a chunk
        {"name": f"{b}.eva_pool", "scope": s, "times": t, "grad": "both",
         "flops": float(heads * (8 * d + 10)),
         "bytes": float(WIDTH * 2 * h * (1 + 1 / chunk)),
         "params": 2 * heads * d},
        {"name": f"{b}.eva_local", "scope": s, "times": t, "grad": "both",
         "flops": attention_flops(heads, d, local),
         "bytes": float(WIDTH * 4 * h), "params": 0},
        # the summaries are read again by every query block of a window;
        # counted once, as the least a kernel must move
        {"name": f"{b}.eva_summaries", "scope": s, "times": t,
         "grad": "both", "flops": attention_flops(heads, d, summaries),
         "bytes": float(WIDTH * 2 * h / chunk), "params": 0},
        _dense(f"{b}.o_proj", s, h, h, t)]


def mlp_block(c, b, t):
    """The rows of one dense SwiGLU block on ``t`` positions."""
    h, f = c["hidden_size"], c["intermediate_size"]
    s = "dense_mlp"
    return [_norm(f"{b}.mlp_norm", s, h, t),
            _dense(f"{b}.gate", s, h, f, t),
            _dense(f"{b}.up", s, h, f, t),
            dict(pointwise_row(f"{b}.swiglu", s, f, 5, 3, t), params=0),
            _dense(f"{b}.down", s, f, h, t)]


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one sequence."""
    c, t = config, traffic["seq_len"]
    h, v = c["hidden_size"], c["vocab_size"]
    rows = [{"name": "embed", "scope": "embed", "times": t, "grad": "weight",
             "flops": 0.0, "bytes": float(4 * h + 4 * h), "params": v * h}]
    for i in range(c["num_hidden_layers"]):
        rows += eva_block(c, f"l{i}", t) + mlp_block(c, f"l{i}", t)
    rows.append(_norm("final_norm", "lm_head", h, t))
    # head i scores the positions that have a token 1 + i ahead
    for i in range(c["num_pred_heads"]):
        rows += [_dense(f"head{i}", "lm_head", h, v, t, times=t - 1 - i),
                 {"name": f"loss{i}", "scope": "lm_head", "times": t - 1 - i,
                  "grad": "input", "flops": 4.0 * v, "bytes": 0.0,
                  "params": 0}]
    return rows


# what each Mosaic kernel of ``ops/eva_pallas.py`` does of the table's rows,
# as (row name's ending, passes): the forward is one pass of the softmax's
# two key sets, the backward the two others
KERNEL_ROWS = {
    "eva_attend_fwd": ((".eva_local", 1), (".eva_summaries", 1)),
    "eva_attend_bwd": ((".eva_local", 2), (".eva_summaries", 2)),
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
