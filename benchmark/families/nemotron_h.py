"""The ``nemotron_h`` family (NVIDIA-Nemotron-3-Nano's ``model_type``): a
hybrid stack of Mamba-2 (``M``), attention (``*``) and routed-expert (``E``)
blocks.  What the benchmark knows of the family's operation count is here:
its layer table and (``STAGES``) its step's scopes.  The plain reference is
``benchmark/reference/lm.py``.

One sample is one sequence of ``traffic.seq_len`` tokens.  A per-token row
runs ``times`` = tokens a sample and states its own ``flops`` and ``bytes``
for one token: the operands and the result at 2 bytes an element, a weight
matrix once a *sequence* (its bytes over the tokens), not once a token.  A
product of two activations (the scan, the scores) has two operands to give
a gradient to and so counts as ``grad: both``.  The
held experts' rows run at the expected 6 x 8 / 128 assignments a token.  The
optimizer is no row: it is no model operation.
"""

from __future__ import annotations

from benchmark.reference.lm import held, pattern

# the compiler names the grouped products' kernels itself and drops the
# name path: they are a stage of their own, read with ``moe`` by its readers
GROUPED = "ragged-dot-none"
STAGES = ("embed", "ssm_mixer", "attention", "moe", GROUPED, "lm_head",
          "optimizer", "grad_sync")
WIDTH = 2   # bytes of a bfloat16 element


def dense_row(name, scope, cin, cout, tokens, times=None, grad="both"):
    """A (cin, cout) product applied to every token of a sequence."""
    return {"name": name, "scope": scope, "times": tokens if times is None
            else times, "grad": grad, "flops": 2.0 * cin * cout,
            "bytes": WIDTH * (cin + cout + cin * cout / tokens)}


def pointwise_row(name, scope, width, ops, passes, tokens, grad="input"):
    """An elementwise or normalising layer over ``width`` channels: ``ops``
    operations a channel, ``passes`` arrays of the width read or written."""
    return {"name": name, "scope": scope, "times": tokens, "grad": grad,
            "flops": float(ops * width), "bytes": float(WIDTH * passes * width)}


def scan_flops(heads, head_dim, state, groups, chunk):
    """One token of the chunked scan: C.B of its chunk (a group's), the
    masked product with x, its part of the chunk's state, and the carried
    state read by C; a multiply-add is two."""
    inner = heads * head_dim
    return 2.0 * (chunk * state * groups + chunk * inner + 2 * inner * state)


def scan_bytes(heads, head_dim, state, groups, chunk):
    """x in, y out, B, C and dt of the token, and the float32 state a chunk
    writes and the next reads, over the chunk's tokens."""
    inner = heads * head_dim
    return (WIDTH * (2 * inner + 2 * groups * state) + 4 * heads
            + 2 * 4 * inner * state / chunk)


def scores_flops(heads, head_dim, tokens):
    """One query of causal attention: q.k and p.v against the (tokens + 1)
    / 2 keys it sees on average."""
    return 2.0 * 2 * heads * head_dim * (tokens + 1) / 2


def gather_bytes(hidden, rows_per_token):
    """The held experts' rows gathered in (bfloat16 in, bfloat16 out) and
    their float32 results scatter-added (read, add, write)."""
    return rows_per_token * hidden * (2 * WIDTH + 3 * 4)


def layers(config, traffic):
    """The whole layer table (``benchmark/flops.py``) of one sequence."""
    c, t = config, traffic["seq_len"]
    h, v = c["hidden_size"], c["vocab_size"]
    heads, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n, chunk = c["n_groups"], c["ssm_state_size"], c["chunk_size"]
    inner, conv_dim = heads * hd, heads * hd + 2 * g * n
    aq, akv, ad = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    count, width = held(c)[1], c["published"]["n_routed_experts"]
    per_token = c["num_experts_per_tok"] * count / width
    rows = [{"name": "embed", "scope": "embed", "times": t, "grad": "weight",
             "flops": 0.0, "bytes": float(4 * h + WIDTH * h)}]
    for i, kind in enumerate(pattern(c)):
        b = f"b{i}"
        if kind == "M":
            s = "ssm_mixer"
            rows += [
                pointwise_row(f"{b}.norm", s, h, 4, 2, t),
                dense_row(f"{b}.in_proj", s, h, inner + conv_dim + heads, t),
                pointwise_row(f"{b}.conv", s, conv_dim,
                              2 * c["conv_kernel"] + 4, 2, t),
                {"name": f"{b}.scan", "scope": s, "times": t, "grad": "both",
                 "flops": scan_flops(heads, hd, n, g, chunk),
                 "bytes": scan_bytes(heads, hd, n, g, chunk)},
                pointwise_row(f"{b}.gated_norm", s, inner, 10, 3, t),
                dense_row(f"{b}.out_proj", s, inner, h, t)]
        elif kind == "*":
            s = "attention"
            rows += [
                pointwise_row(f"{b}.norm", s, h, 4, 2, t),
                dense_row(f"{b}.q_proj", s, h, aq * ad, t),
                dense_row(f"{b}.k_proj", s, h, akv * ad, t),
                dense_row(f"{b}.v_proj", s, h, akv * ad, t),
                {"name": f"{b}.scores", "scope": s, "times": t,
                 "grad": "both", "flops": scores_flops(aq, ad, t),
                 "bytes": float(WIDTH * (2 * aq * ad + 2 * akv * ad))},
                dense_row(f"{b}.o_proj", s, aq * ad, h, t)]
        else:
            s = "moe"
            f, fs = (c["moe_intermediate_size"],
                     c["moe_shared_expert_intermediate_size"])
            rows += [
                pointwise_row(f"{b}.norm", s, h, 4, 2, t),
                dense_row(f"{b}.router", s, h, width, t),
                {"name": f"{b}.gather_scatter", "scope": s, "times": t,
                 "grad": "input", "flops": 0.0,
                 "bytes": gather_bytes(h, per_token)},
                # a held expert's matrices are read once a sequence, by
                # the rows routed to it: 1 / count of the held rows each
                dense_row(f"{b}.experts_up", s, h, f, t * per_token / count,
                          times=t * per_token),
                dense_row(f"{b}.experts_down", s, f, h, t * per_token / count,
                          times=t * per_token),
                dense_row(f"{b}.shared_up", s, h, fs, t),
                dense_row(f"{b}.shared_down", s, fs, h, t)]
    return rows + [
        pointwise_row("final_norm", "lm_head", h, 4, 2, t),
        dense_row("head", "lm_head", h, v, t, times=t - 1),
        {"name": "loss", "scope": "lm_head", "times": t - 1, "grad": "input",
         "flops": 4.0 * v, "bytes": 0.0}]
