"""Multi-process (multi-host) data parallelism helpers.

Reference: MXNet ``kvstore='dist_sync'`` — the reference's parameter-server
pass-through for multi-machine training (SURVEY.md §5.8; present as a flag,
never exercised by its scripts).  The TPU-native equivalent is
``jax.distributed``: every host runs the SAME SPMD program over the global
``(dcn, ici)`` mesh (``parallel/dp.py — device_mesh``), gradients pmean
over both axes, and XLA routes the per-slice reduction over ICI and the
small cross-host exchange over DCN — no parameter server, no separate
communication library.

The pieces here are the host-boundary glue the single-process path does
not need: assembling process-local numpy shards into global arrays, and
replicating host-identical values (states initialized from the same seed)
across processes.  ``tools/multihost_demo.py`` wires them into a runnable
two-process demonstration on CPU devices; the same calls serve a real
multi-host TPU pod (one process per host, ``jax.distributed.initialize``
with the pod coordinator).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.experimental import multihost_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mx_rcnn_tpu.parallel.dp import data_axes


def initialize(coordinator: str, num_processes: int, process_id: int,
               local_devices: Optional[int] = None) -> None:
    """``jax.distributed.initialize`` wrapper (one process per host).

    ``local_devices``: with CPU devices, pins the per-process device count
    (the multi-host-without-a-cluster test rig); on real TPU hosts leave
    None — the runtime discovers the local chips.
    """
    import os

    if local_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_devices}").strip()
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # CPU cross-process computations need a collectives backend; the
        # default CPU client has none and every multi-process program
        # fails with "Multiprocess computations aren't implemented on the
        # CPU backend".  Gloo ships in jaxlib; must be selected BEFORE
        # the backend initializes (harmless on TPU — guard on platform).
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(dcn_size: Optional[int] = None) -> Mesh:
    """Hierarchical mesh over ALL processes' devices; ``dcn_size`` defaults
    to the process count (one slice per host — jax.devices() orders
    devices host-major, matching the host-outermost reshape)."""
    from mx_rcnn_tpu.parallel.dp import device_mesh

    if dcn_size is None:
        dcn_size = jax.process_count()
    return device_mesh(dcn_size=dcn_size)


def global_batch(batch, mesh: Mesh, accum: bool = False):
    """Assemble each process's LOCAL batch shard into global arrays sharded
    over the mesh's data axes (the multi-host analog of
    ``dp.shard_batch``).  Every process passes only its own images.

    ``accum=True``: the batch carries a leading microbatch axis
    (grad-accumulation — ft/elastic.py) and the image axis is axis 1, so
    the spec becomes ``P(None, data_axes)`` (the multi-host analog of
    ``dp.shard_accum_batch``)."""
    spec = P(None, data_axes(mesh)) if accum else P(data_axes(mesh))
    return jax.tree.map(
        lambda x: multihost_utils.host_local_array_to_global_array(
            np.asarray(x), mesh, spec),
        batch)


def local_image_slice(batch, accum: bool = False):
    """This process's contiguous slice of a GLOBAL batch's image axis
    (axis 0, or axis 1 for accumulation batches): processes iterate the
    same deterministic loader and each feeds rows
    ``[pid * per, (pid + 1) * per)`` into :func:`global_batch`.

    FALLBACK path since r7: it slices a batch every process fully
    DECODED, so decode work is duplicated N-fold.  The fit loop now
    prefers loader row shards (``data/loader.py — set_shard``, wired by
    ``tools/train.py`` from the process topology), where each process
    decodes only its own rows — same rows, same bytes, 1/N the decode
    (docs/DATA.md).  This slice remains for loaders without shard
    support; either way the assembled global batch is bit-identical to
    the single-process one, which is what keeps elastic resumes
    on-recipe."""
    pid, n = jax.process_index(), jax.process_count()
    axis = 1 if accum else 0

    def sl(x):
        x = np.asarray(x)
        per = x.shape[axis] // n
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(pid * per, (pid + 1) * per)
        return x[tuple(idx)]

    return jax.tree.map(sl, batch)


def replicate_global(tree, mesh: Mesh):
    """Replicate host-identical values across every process/device (states
    initialized from one seed are bit-identical on every host — asserted
    cheaply via a checksum in the demo).

    Leaves route through a jax-OWNED single-device copy
    (``jnp.array(..., copy=True)``) before global assembly: restored
    states arrive as numpy views of one shared msgpack buffer, the DP
    step DONATES this tree, and ``host_local_array_to_global_array`` can
    zero-copy a host buffer WITHOUT holding a reference — passing it a
    temporary numpy copy segfaults once the copy is freed, and passing
    the caller's view risks donated-buffer aliasing (``parallel/dp.py —
    own_leaves`` has the full story).  Per-device buffers built from an
    owned jax array are safe on both counts."""
    import jax.numpy as jnp

    sharding = NamedSharding(mesh, P())

    def rep(x):
        owned = jnp.array(np.asarray(x), copy=True)
        local = [jax.device_put(owned, d) for d in mesh.local_devices]
        return jax.make_array_from_single_device_arrays(
            owned.shape, sharding, local)

    return jax.tree.map(rep, tree)
