"""Test harness configuration.

All tests run on CPU with 8 virtual XLA devices — the TPU-native answer to
"test multi-chip without a cluster" (SURVEY.md §4): sharding/collective
code is exercised on a real 8-device mesh, just a slow one.

Must set the env vars before the first ``import jax`` anywhere.
"""

import os

# Overwrite, not setdefault: unit tests run on the virtual 8-device CPU
# mesh whatever platform the environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from mx_rcnn_tpu import runtime  # noqa: E402

# Persistent XLA compilation cache: the slow tier is compile-dominated
# (training-loop tests re-jit the same tiny programs every run), so a
# warm cache cuts repeat `make test-all` wall-clock several-fold.  Placed
# by the program's one rule (runtime.py): $JAX_COMPILATION_CACHE_DIR when
# set, else the fixed <checkout>/.jax_cache; delete the dir to force cold.
_cache_dir = runtime.enable_compile_cache(min_compile_s=0.5)
# subprocess tests (stage CLIs, supervisor children, graft dryruns) start
# fresh interpreters that never read this conftest — the env vars route
# them to the same cache WITH the same thresholds (the dir alone would
# leave children at jax's 1.0 s min-compile-time default and skip exactly
# the tiny programs this suite compiles).  Exception: the jax.distributed
# multihost workers strip the cache dir (tools/multihost_demo.py —
# cache-hit ranks racing compile-miss ranks deadlocked the
# collective-init barrier).
os.environ.setdefault(runtime.CACHE_ENV, _cache_dir)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def shrink_tiny_cfg(cfg):
    """Shared miniature-e2e hyperparameters for the tiny network on a
    128x160 canvas (used by test_fit_e2e and test_e2e_formats — keep the
    two e2e suites on one tuning)."""
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=1024,
                         rpn_post_nms_top_n=300, batch_rois=128,
                         max_gt_boxes=8, flip=False)
    cfg = cfg.replace_in("test", rpn_pre_nms_top_n=1024,
                         rpn_post_nms_top_n=100)
    cfg = cfg.replace_in("bucket", scale=128, max_size=160,
                         shapes=((128, 160), (160, 128)))
    return cfg


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: training-loop / subprocess / e2e tests excluded from the "
        "quick tier (run with `make test-all` or `-m slow`)")
    config.addinivalue_line(
        "markers",
        "gate: the two multi-minute end-metric gates (30-epoch gauntlet "
        "seed-0 train-from-scratch, 16-device hierarchical dryrun) — "
        "excluded from `make test-all` so the full tier stays "
        "independently re-runnable in ~15 min on one core; run with "
        "`make test-gate`")
