"""The ``ling_train`` cell cut to a size a CPU test run can hold: the
program's ``ling_flash_tiny`` preset (hidden 64, layers KDA+dense, KDA+E,
MLA+E, KDA+E, 16 experts in 4 groups of which 2 are kept and 2 experts held,
top 2, a vocabulary of 256), sequences of 64 tokens.  Built from the real
cell's own files, so the harness under test is the one the chip runs; the
configuration's keys keep their names and take the preset's values."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

CELL = "ling3-flash-6l-ep64.train-8k"
TINY = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=4,
    first_k_dense_replace=1, layer_group_size=3, intermediate_size=96,
    num_attention_heads=4, head_dim=16, short_conv_kernel_size=4,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=2, num_experts_per_tok=2, n_group=4, topk_group=2,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32)


def tiny_ling_cell(name: str = CELL) -> dict:
    cell = bench_run.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    config.update(TINY)
    config["published"] = {"num_hidden_layers": 4, "first_k_dense_replace": 1,
                           "num_experts": 16, "vocab_size": 256}
    # published layers 0-3 of a period of three: K K L K
    config["network"].update(first_layer=0, first_expert=4,
                              compute_dtype="float32", chunk_size=16)
    config["optimizer"]["lr"] = 1e-3
    config["program"] = {
        "network": "ling_flash_tiny", "dataset": "synthetic_tokens",
        "overrides": {"train__shuffle": False, "default__frequent": 2}}
    traffic.update({"seq_len": 64, "per_chip_batch": 2,
                    "sequences_per_chip": 8, "warmup_steps": 4,
                    "epoch_steps": 100})
    return cell
