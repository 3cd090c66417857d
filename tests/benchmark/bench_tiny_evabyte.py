"""The ``evabyte_train`` cell cut to a size a CPU test run can hold: the
program's ``evabyte_tiny`` preset (hidden 256, two layers of EVA attention
over 2 heads of 128 in windows of 64 and chunks of 16, an MLP of 384, 4
prediction heads, the vocabulary's 320 byte ids), sequences of 256 bytes:
four windows, so that summaries and their absence are both there.  Built
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

CELL = "evabyte-4l-pp8.train-16k"
TINY = dict(
    hidden_size=256, num_hidden_layers=2, num_attention_heads=2,
    num_key_value_heads=2, intermediate_size=384, window_size=64,
    chunk_size=16, num_pred_heads=4, init_std=0.0625)


def tiny_evabyte_cell(name: str = CELL) -> dict:
    cell = bench_run.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    config.update(TINY)
    config["published"] = {"num_hidden_layers": 2}
    config["network"].update(compute_dtype="float32")
    config["optimizer"]["lr"] = 1e-3
    config["program"] = {
        "network": "evabyte_tiny", "dataset": "synthetic_tokens",
        "overrides": {"train__shuffle": False, "default__frequent": 2}}
    traffic.update({"seq_len": 256, "per_chip_batch": 2,
                    "sequences_per_chip": 8, "warmup_steps": 4,
                    "epoch_steps": 100})
    # the cell's loss limits are set over 16384 positions a step; over this
    # cell's 2 x 256 they are the other sequence cells' tiny limit
    cell["check"]["limits"].update(loss_s1=0.005, loss_s2=0.005,
                                   loss_main_s1=0.005)
    return cell
