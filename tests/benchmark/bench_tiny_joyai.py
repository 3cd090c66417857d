"""The ``joyai_train`` cell cut to a size a CPU test run can hold: the
program's ``joyai_flash_tiny`` preset (hidden 64, layers MLA+dense, MLA+E,
MLA+E and the multi-token-prediction module, a low-rank query of 40, 16
experts of which 4 are held, top 2, a vocabulary of 256), sequences of 64
tokens.  Built from the real cell's own files, so the harness under test is
the one the chip runs; the configuration's keys keep their names and take
the preset's values."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

CELL = "joyai-flash-5l-mtp-ep16.train-8k"
TINY = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=96, num_attention_heads=4,
    q_lora_rank=40, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
    num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1)


def tiny_joyai_cell(name: str = CELL) -> dict:
    cell = bench_run.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    config.update(TINY)
    config["published"] = {"num_hidden_layers": 3, "n_routed_experts": 16,
                           "vocab_size": 256}
    config["network"].update(first_expert=4, compute_dtype="float32")
    config["optimizer"]["lr"] = 1e-3
    config["program"] = {
        "network": "joyai_flash_tiny", "dataset": "synthetic_tokens",
        "overrides": {"train__shuffle": False, "default__frequent": 2}}
    traffic.update({"seq_len": 64, "per_chip_batch": 2,
                    "sequences_per_chip": 8, "warmup_steps": 4,
                    "epoch_steps": 100})
    return cell
