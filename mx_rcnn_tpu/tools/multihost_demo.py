"""Two-process multi-host training demonstration (and its launcher).

Reference: the reference's multi-machine story is MXNet
``kvstore='dist_sync'`` (a parameter server, present but unexercised —
SURVEY.md §5.8).  This tool actually RUNS the multi-host path: N processes
(one per simulated host, 2 CPU devices each by default) initialize
``jax.distributed``, build the global ``(dcn, ici)`` mesh, and train the
tiny Faster R-CNN end-to-end step with gradients pmean'd across processes
over Gloo — the same program a TPU pod runs with one process per host and
ICI/DCN in place of Gloo.

Worker mode (one per process):
  python -m mx_rcnn_tpu.tools.multihost_demo --process_id I \\
      --num_processes N [--coordinator HOST:PORT] [--steps K]

Launcher mode (spawns N local workers, checks their losses agree):
  python -m mx_rcnn_tpu.tools.multihost_demo --launch N
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys

import numpy as np

LOSS_RE = re.compile(r"\[p(\d+)\] step (\d+) loss ([0-9.]+)")


def worker(args) -> None:
    # ORDER MATTERS: distributed init must precede ANY backend
    # initialization.  Importing mx_rcnn_tpu is safe (the package keeps no
    # module-level jnp constants for exactly this reason), but platform
    # pinning still comes first.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from mx_rcnn_tpu.parallel import multihost

    multihost.initialize(args.coordinator, args.num_processes,
                         args.process_id, local_devices=args.local_devices)

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.train import setup_training
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.parallel.dp import make_dp_train_step
    from mx_rcnn_tpu.data.synthetic import make_batch

    pid = jax.process_index()
    print(f"[p{pid}] devices: local={jax.local_device_count()} "
          f"global={jax.device_count()}", flush=True)

    size = 128
    cfg = generate_config("tiny", "PascalVOC")
    cfg = cfg.replace_in("train", rpn_pre_nms_top_n=256,
                         rpn_post_nms_top_n=64, batch_rois=32,
                         max_gt_boxes=8, rpn_min_size=2, batch_images=1)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)

    # identical seed on every host → bit-identical init; replicate_global
    # then lifts the host-local copies into one logically-shared tree
    state, tx = setup_training(model, cfg, key, (1, size, size, 3),
                               steps_per_epoch=1000)
    mesh = multihost.global_mesh()
    step = make_dp_train_step(model, cfg, tx, mesh)
    state = multihost.replicate_global(jax.device_get(state), mesh)

    # the GLOBAL batch is deterministic; each host materializes only its
    # local slice (local device count x batch_images images)
    n_global = mesh.size * cfg.train.batch_images
    full = make_batch(cfg, n_global, size, size, seed=7)
    per = n_global // args.num_processes
    lo, hi = pid * per, (pid + 1) * per
    local = jax.tree.map(lambda x: np.asarray(x)[lo:hi], full)
    batch = multihost.global_batch(local, mesh)

    for s in range(args.steps):
        state, metrics = step(state, batch, key)
        loss = float(np.asarray(jax.device_get(metrics["loss"])))
        print(f"[p{pid}] step {s} loss {loss:.6f}", flush=True)
    print(f"[p{pid}] done", flush=True)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def launch(n: int, steps: int, local_devices: int = 2) -> int:
    """Spawn ``n`` local worker processes; verify every process reports the
    same per-step loss (the gradients were truly synchronized)."""
    port = _free_port()
    procs = []
    outs = []
    ok = True
    # no shared compilation cache for the workers: with jax.distributed,
    # ranks that HIT the cache race ahead of ranks that compile, and the
    # collective-init barrier can time the stragglers out (reproduced
    # when the test conftest exported JAX_COMPILATION_CACHE_DIR to
    # subprocesses — every rank compiles, or none)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    try:
        for i in range(n):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "mx_rcnn_tpu.tools.multihost_demo",
                 "--process_id", str(i), "--num_processes", str(n),
                 "--local_devices", str(local_devices),
                 "--coordinator", f"localhost:{port}",
                 "--steps", str(steps)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env))
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
            if p.returncode != 0:
                ok = False
    finally:
        # distributed init is a barrier: one wedged worker blocks the rest
        # forever — never leak them (or the coordinator port) on timeout
        for p in procs:
            if p.poll() is None:
                p.kill()
    losses = {}
    for out in outs:
        for pid, s, loss in LOSS_RE.findall(out):
            losses.setdefault(int(s), {})[int(pid)] = float(loss)
    for s, by_pid in sorted(losses.items()):
        vals = sorted(by_pid.values())
        agree = len(by_pid) == n and abs(vals[-1] - vals[0]) < 1e-5
        print(f"step {s}: losses {by_pid} "
              f"{'AGREE' if agree else 'MISMATCH'}")
        ok = ok and agree
    ok = ok and len(losses) == steps
    if not ok:
        for i, out in enumerate(outs):
            print(f"--- worker {i} output ---\n{out}")
    print("MULTIHOST DEMO:", "OK" if ok else "FAILED")
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--launch", type=int, default=None,
                   help="spawn N local workers and verify agreement")
    p.add_argument("--process_id", type=int, default=0)
    p.add_argument("--num_processes", type=int, default=2)
    p.add_argument("--coordinator", default="localhost:19876")
    p.add_argument("--local_devices", type=int, default=2)
    p.add_argument("--steps", type=int, default=3)
    args = p.parse_args(argv)
    if args.launch:
        return launch(args.launch, args.steps,
                      local_devices=args.local_devices)
    worker(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
