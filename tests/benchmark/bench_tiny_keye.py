"""The ``keye_train`` cell cut to a size a CPU test run can hold: the
program's ``keye_vl2_tiny`` preset (hidden 64, two layers of attention over
4 query heads and 2 key-value heads of 16, an indexer of 4 heads of 16
choosing 16 keys a query, 16 experts of which 4 are held, top 2, a
vocabulary of 256), sequences of 64 tokens.  Built from the real cell's own
files, so the harness under test is the one the chip runs; the
configuration's keys keep their names and take the preset's values."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

CELL = "keye-vl2-a3b-6l-ep8.train-32k"
TINY = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
    sa_config={"indexer_head_dim": 16, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 16})


def tiny_keye_cell(name: str = CELL) -> dict:
    cell = bench_run.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    config.update(TINY)
    config["published"] = {"num_hidden_layers": 2, "num_experts": 16,
                           "vocab_size": 256}
    config["network"].update(first_expert=4, compute_dtype="float32",
                             indexer_rope_dim=8)
    config["optimizer"]["lr"] = 1e-3
    config["program"] = {
        "network": "keye_vl2_tiny", "dataset": "synthetic_tokens",
        "overrides": {"train__shuffle": False, "default__frequent": 2}}
    traffic.update({"seq_len": 64, "per_chip_batch": 2,
                    "sequences_per_chip": 8, "warmup_steps": 4,
                    "epoch_steps": 100})
    # the cell's loss limits are set over 32768 positions a step; over this
    # cell's 126, bfloat16 rounding alone moves the loss by up to 2.4e-4,
    # as much as float8 does: here they are the other sequence cells' limit
    cell["check"]["limits"].update(loss_s1=0.005, loss_s2=0.005)
    return cell
