"""The ``lm_train`` cell cut to a size a CPU test run can hold: the
program's ``nemotron_h_tiny`` preset (hidden 64, pattern ME*E, 8 experts of
which 2 are held, top 2, a vocabulary of 256), sequences of 64 tokens.  Built
from the real cell's own files, so the harness under test is the one the
chip runs; the configuration's keys keep their names and take the preset's
values."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

TINY = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=4,
    hybrid_override_pattern="ME*E", mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=2, num_experts_per_tok=2, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96)


def tiny_lm_cell(name: str = "nemotron3-nano-9l-ep16.train-8k") -> dict:
    cell = bench_run.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    config.update(TINY)
    config["published"] = {"num_hidden_layers": 4, "n_routed_experts": 8,
                           "vocab_size": 256}
    config["network"].update(first_expert=2, compute_dtype="float32")
    config["optimizer"]["lr"] = 1e-3
    config["program"] = {
        "network": "nemotron_h_tiny", "dataset": "synthetic_tokens",
        "overrides": {"train__shuffle": False, "default__frequent": 2}}
    traffic.update({"seq_len": 64, "per_chip_batch": 2,
                    "sequences_per_chip": 8, "warmup_steps": 4,
                    "epoch_steps": 100})
    return cell
