"""HBM-resident epoch cache: feed training entirely from device memory.

No reference equivalent — the reference streams every batch host→device
each step (``rcnn/core/loader.py`` + MXNet IO), which is the right call
when the interconnect is PCIe and the host has cores to spare.  On a
TPU whose host cannot decode and transfer a batch inside one ~27 ms train
step, per-step transfers dominate.

The TPU-native answer for RAM-scale datasets (benchmarks, VOC-sized sets,
synthetic suites): stage ONE epoch of already-assembled batches in HBM
(uint8 images keep it 4x smaller — 32 batches of 2x608x1024 ≈ 120 MB),
then let each step GATHER its batch from the resident buffer with an
index derived on device.  Steady-state host↔device traffic per step: one
dispatch, zero data bytes.  Whether the streaming loader + staging thread
already holds device rate on a chip host — in which case this module goes
— is not measured (ROADMAP S7/D7).

Shuffle semantics (r5 — closes the r2-r4 disclosed deviation): the epoch
is staged as batches but gathered at IMAGE granularity — each step slices
``batch_images`` image indices out of a per-epoch on-device permutation
of ALL images, so batch COMPOSITION re-randomizes every epoch exactly
like the streaming loader's in-bucket regrouping (rounds 2-4 permuted
batch ORDER only, with composition frozen at staging).  ``shuffle=False``
replays the staged batches verbatim (bitwise contract vs streaming).
Residual deviation (multi-chip only, disclosed): the mesh layout shards
each staged batch's image axis, so regrouping happens WITHIN a device's
shard — images never migrate across devices between epochs, where the
streaming path's global regroup would move them.  Single-chip semantics
are now exactly the streaming loader's.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class DeviceEpochCache:
    """One bucket's epoch of batches, stacked and resident on device.

    ``data`` is the batch pytree with a leading ``num_batches`` axis.
    Multi-bucket datasets build one cache per bucket
    (:func:`build_caches`).
    """

    def __init__(self, batches: List, device=None):
        if not batches:
            raise ValueError("empty batch list")
        shapes = {tuple(b.images.shape) for b in batches}
        if len(shapes) > 1:
            raise ValueError(f"mixed bucket shapes in one cache: {shapes}")
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        self.num_batches = len(batches)
        # ``device`` may be a Device or a Sharding (multi-chip: shard each
        # batch's image axis over the mesh — axis 1 of the stacked layout)
        self.data = (jax.device_put(stacked, device) if device is not None
                     else jax.device_put(stacked))
        self.nbytes = sum(x.nbytes for x in jax.tree.leaves(stacked))

    def index_handle(self) -> jnp.ndarray:
        """A fresh device-resident step counter for :func:`make_cached_step`
        (int32 scalar; carried through the step so the host never ships an
        index)."""
        return jnp.zeros((), jnp.int32)


def build_caches(loader, max_bytes: int = 4 << 30,
                 mesh=None) -> List[DeviceEpochCache]:
    """Materialize one epoch from ``loader`` and upload it, grouped by
    bucket shape.  Raises if the epoch exceeds ``max_bytes`` (caller falls
    back to the streaming loader).  With ``mesh``, each batch's image axis
    is sharded over the mesh's data axes (every device holds its slice of
    every batch — the multi-chip layout for :func:`make_dp_cached_step`),
    and ``max_bytes`` bounds the PER-DEVICE footprint."""
    placement = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mx_rcnn_tpu.parallel.dp import data_axes

        placement = NamedSharding(mesh, P(None, data_axes(mesh)))
        max_bytes *= mesh.size
    by_shape = {}
    total = 0
    for b in loader:
        by_shape.setdefault(tuple(b.images.shape), []).append(b)
        total += sum(x.nbytes for x in jax.tree.leaves(b))
        if total > max_bytes:
            raise MemoryError(
                f"epoch exceeds device cache budget ({total} > {max_bytes} "
                f"bytes); use the streaming loader")
    return [DeviceEpochCache(bs, device=placement)
            for bs in by_shape.values()]


def make_cached_step(base_step: Callable, num_batches: int,
                     shuffle: bool = True) -> Callable:
    """Wrap a ``(state, batch, key) -> (state, metrics)`` train step into a
    ``(state, data, idx, key) -> (state, idx', metrics)`` step that gathers
    its batch from a resident :class:`DeviceEpochCache` epoch.

    ``idx`` is the cache's device-resident step counter
    (:meth:`DeviceEpochCache.index_handle`).  With ``shuffle`` the batch
    at position ``p = idx % num_batches`` of epoch ``e`` is the images at
    ``perm_e[p*bi : (p+1)*bi]`` for a per-epoch device permutation of ALL
    staged images — composition re-randomizes every epoch (module
    docstring); ``shuffle=False`` replays staged batch ``p`` verbatim.
    Jit with ``donate_argnums=(0, 2)`` — state and counter update in
    place; the epoch data is a non-donated resident buffer.
    """

    def step(state, data, idx, key):
        pos = jnp.mod(idx, num_batches)
        if shuffle:
            epoch = idx // num_batches
            # tag the permutation stream so it never collides with the
            # train step's fold_in(key, state.step) stream (epoch e and
            # step s=e would otherwise share a key)
            perm_key = jax.random.fold_in(
                jax.random.fold_in(key, 0x5A5A5A5), epoch)
            # IMAGE-granular gather: slice this step's batch_images out
            # of a per-epoch permutation of all images, so composition
            # re-randomizes each epoch (module docstring).  Leaf shapes
            # are (num_batches, bi, ...) — under shard_map these are the
            # LOCAL shapes, so the flatten+gather stays shard-local.
            bi = jax.tree.leaves(data)[0].shape[1]
            perm = jax.random.permutation(perm_key, num_batches * bi)
            img_idx = jax.lax.dynamic_slice(perm, (pos * bi,), (bi,))
            batch = jax.tree.map(
                lambda x: x.reshape((x.shape[0] * x.shape[1],)
                                    + x.shape[2:])[img_idx],
                data)
        else:
            batch = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, pos,
                                                       keepdims=False),
                data)
        new_state, metrics = base_step(state, batch, key)
        return new_state, idx + 1, metrics

    return step
