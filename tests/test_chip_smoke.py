"""Bring-up contract on CPU: ``chip_smoke.py`` refuses a
machine without a TPU (non-zero, no result line, before any compile), the
smoke's own function passes its rehearsal at the tiny network's size, and
the compile cache lands where the one rule in ``mx_rcnn_tpu/runtime.py``
says.  What only the chip can show — Mosaic compiling the kernel, parity at
K=6144/12032, ``peak_bytes_in_use`` — is ``chip_smoke.py``'s own run.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from mx_rcnn_tpu import runtime  # noqa: E402


def _tiny_argv(workdir) -> list:
    """``chip_smoke.chip_train_argv`` cut to the tiny network on a 128x160
    canvas (the miniature recipe of conftest.shrink_tiny_cfg): same flags,
    same 16 images -> 8 steps."""
    root = os.path.join(str(workdir), "data")
    return [
        "--network", "tiny", "--dataset", "synthetic_stream",
        "--root_path", root,
        "--dataset_path", os.path.join(root, "synthetic_stream"),
        "--dataset_kw", repr({"num_images": 16, "image_size": (128, 160),
                              "max_objects": 3}),
        "--prefix", os.path.join(str(workdir), "model", "smoke"),
        "--end_epoch", "1", "--frequent", "1", "--no_flip", "--seed", "0",
        "--batch_images", "2", "--lr", "0.001",
        "--set", "bucket__scale=128", "--set", "bucket__max_size=160",
        "--set", "bucket__shapes=((128, 160), (160, 128))",
        "--set", "train__rpn_pre_nms_top_n=1024",
        "--set", "train__rpn_post_nms_top_n=300",
        "--set", "train__max_gt_boxes=8",
        "--set", "test__rpn_pre_nms_top_n=1024",
        "--set", "test__rpn_post_nms_top_n=100",
    ]


def _run(cmd, *, cwd=REPO, env_drop=(), env_set=None, timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_set or {})
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.fixture
def keep_cache_thresholds():
    """``run_smoke`` arms the cache the way the entry points do (every
    program cached); put the suite's own threshold back afterwards."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    yield
    jax.config.update(name, before)


def test_smoke_rehearsal_passes_at_tiny_size(tmp_path,
                                             keep_cache_thresholds):
    """The function ``chip_smoke.py`` runs on the chip, called here with
    the platform it should expect passed in: every phase (kernel parity in
    the interpreter, 8 trainer steps through ``train_net``, verified epoch
    checkpoint, one test-mode batch, step HLO check) passes on CPU."""
    out = chip_smoke.run_smoke(_tiny_argv(tmp_path), expect_platform="cpu",
                               parity_sizes=(256,))
    assert out["ok"] is True
    assert out["device"] == {"platform": "cpu", "kind": "cpu",
                             "count": out["device_count"]}
    assert out["steps"] == 8
    assert out["checkpoint"] == {"epoch": 1, "step": 8, "verified": True}
    assert out["nms_backend"] == "jnp"          # 'auto' off-TPU
    assert out["tpu_custom_calls_in_step"] == 0  # nothing Mosaic on CPU
    assert [(p["k"], p["equal"]) for p in out["nms_parity"]] == [(256, True)]
    assert out["test_mode"]["scores_shape"] == [1, 100, 81]
    assert out["compile_cache_dir"] == runtime.compile_cache_dir()
    assert out["last_loss"] < out["first_loss"]
    json.dumps(out)  # the record is one JSON object


def test_last_stdout_line_is_exactly_ok_and_device(monkeypatch, capsys):
    """What ``main`` prints, with the run itself stubbed: the record on one
    line, then as the last line an object with exactly ``ok`` and
    ``device`` = exactly ``platform``/``kind`` (text) and ``count`` (int) —
    the driver's chip check refuses any other key (PR 21's first refusal:
    the whole record was the last line)."""
    record = {"ok": True, "steps": 8, "peak_bytes_in_use": 1,
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1}}
    seen = {}

    def fake_run(argv, *, expect_platform, parity_sizes):
        seen.update(platform=expect_platform, sizes=tuple(parity_sizes),
                    network=argv[argv.index("--network") + 1])
        return dict(record)

    monkeypatch.setattr(chip_smoke, "run_smoke", fake_run)
    assert chip_smoke.main() == 0
    assert seen == {"platform": "tpu", "sizes": (6144, 12032),
                    "network": "resnet101"}
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0]) == {"smoke_record": record}
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": record["device"]}
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["ok"] is True and type(last["device"]["count"]) is int


def test_smoke_refuses_other_platform_before_compiling(tmp_path):
    """Expecting a TPU on a CPU host: non-zero exit naming what JAX found,
    with nothing lowered and nothing written."""
    from mx_rcnn_tpu.serve.metrics import LoweringCounter

    with LoweringCounter() as lowered:
        with pytest.raises(SystemExit) as exc:
            chip_smoke.run_smoke(_tiny_argv(tmp_path), expect_platform="tpu",
                                 parity_sizes=(256,))
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert lowered.n == 0
    assert os.listdir(tmp_path) == []


def test_chip_smoke_script_without_tpu_exits_nonzero_with_no_result():
    res = _run([sys.executable, "chip_smoke.py"])
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "JAX found 'cpu'" in res.stderr


def test_chip_smoke_script_alone_in_a_directory_fails(tmp_path):
    """The script with nothing else of the repo beside it: non-zero, no
    result (it imports the package, it does not carry a copy)."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
               env_drop=("PYTHONPATH",))
    assert res.returncode != 0
    assert res.stdout.strip() == ""


_RESOLVE = (
    "import os, json; from mx_rcnn_tpu import runtime; "
    "before = os.environ.get(runtime.CACHE_ENV); "
    "d = runtime.enable_compile_cache(); import jax; "
    "jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready(); "
    "print(json.dumps({'dir': d, 'config': jax.config.jax_compilation_cache_dir,"
    " 'before': before, 'after': os.environ.get(runtime.CACHE_ENV),"
    " 'entries': len(os.listdir(d))}))")


def test_cache_env_set_wins_and_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache is written there, the
    variable is not rewritten, and nothing of the child's appears in the
    checkout (other xdist workers compile into the default directory
    meanwhile, so only the names the child wrote are looked for)."""
    placed = str(tmp_path / "placed")
    default = runtime.DEFAULT_CACHE_DIR
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    res = _run([sys.executable, "-c", _RESOLVE],
               env_set={runtime.CACHE_ENV: placed})
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == placed
    assert out["before"] == out["after"] == placed
    assert out["entries"] >= 1
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert not (after - before) & set(os.listdir(placed))


def test_cache_env_unset_is_one_fixed_gitignored_path_in_the_checkout():
    """Variable unset: two processes resolve the same fixed directory
    inside the checkout, and git ignores it."""
    outs = []
    for _ in range(2):
        res = _run([sys.executable, "-c", _RESOLVE],
                   env_drop=(runtime.CACHE_ENV,))
        assert res.returncode == 0, res.stderr
        outs.append(json.loads(res.stdout.strip().splitlines()[-1]))
    want = os.path.join(REPO, ".jax_cache")
    assert [o["dir"] for o in outs] == [want, want]
    assert outs[0]["config"] == want and outs[0]["after"] is None
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()


def test_cache_resolver_precedence(monkeypatch):
    """env > a store's bundled directory > the in-checkout default."""
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    assert runtime.compile_cache_dir() == runtime.DEFAULT_CACHE_DIR
    assert runtime.compile_cache_dir("/store/xla_cache") == "/store/xla_cache"
    monkeypatch.setenv(runtime.CACHE_ENV, "/placed")
    assert runtime.compile_cache_dir() == "/placed"
    assert runtime.compile_cache_dir("/store/xla_cache") == "/placed"
