"""Data-parallel training over a device mesh.

Replaces MXNet ``kvstore='device'`` (ref ``train_end2end.py`` passes the
ctx list + kvstore into ``MutableModule.fit``; MXNet pushes/pulls each
gradient array through the KVStore).  Here the whole step — forward,
backward, ``lax.pmean`` gradient sync over ICI, SGD update — is one XLA
program per device, built with ``jax.shard_map`` over a 1-D ``('data',)``
mesh (single host/slice) or a 2-D ``('dcn', 'ici')`` mesh (multi-host, see
:func:`device_mesh`):

* batch leaves are sharded on their leading (image) axis,
* params / optimizer state are replicated (every device applies the same
  psum-averaged update, so replicas stay bit-identical),
* per-image RNG is decorrelated across shards by folding in the device's
  mesh position.

``BATCH_IMAGES`` keeps the reference's per-device meaning (SURVEY.md §2:
"BATCH_IMAGES is per GPU"): a global batch of ``n_devices × batch_images``
feeds the mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.core.train import Batch, TrainState, make_train_step
from mx_rcnn_tpu.models.faster_rcnn import FasterRCNN


def device_mesh(n_devices: Optional[int] = None,
                devices: Optional[Sequence[jax.Device]] = None,
                dcn_size: int = 1) -> Mesh:
    """Data-parallel mesh over the first ``n_devices`` devices.

    ``dcn_size=1`` (single host/slice): 1-D ``('data',)`` mesh — gradient
    pmean rides ICI only.

    ``dcn_size>1`` (multi-host/multi-slice): 2-D ``('dcn', 'ici')`` mesh
    with the slow inter-host axis OUTERMOST, so XLA decomposes the gradient
    all-reduce hierarchically — reduce-scatter/all-gather inside each slice
    over ICI, then one small cross-slice all-reduce over DCN — instead of a
    flat ring across the slow links.  This is the scaling analog of the
    reference's unused ``kvstore='dist_sync'`` parameter server (SURVEY.md
    §5.8), expressed as mesh axes instead of a server process.  On a real
    multi-host deployment call ``jax.distributed.initialize()`` first and
    pass ``jax.devices()`` (globally ordered host-major, which matches the
    host-outermost reshape here).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    devices = np.asarray(devices)
    if dcn_size <= 1:
        return Mesh(devices, ("data",))
    if len(devices) % dcn_size != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible by dcn_size={dcn_size}")
    return Mesh(devices.reshape(dcn_size, -1), ("dcn", "ici"))


def data_axes(mesh: Mesh):
    """The mesh axis name(s) the batch is sharded (and grads reduced) over."""
    return mesh.axis_names if len(mesh.axis_names) > 1 else mesh.axis_names[0]


def own_leaves(tree):
    """Force every numpy leaf into a PRIVATE jax-owned copy before it
    feeds a DONATING step.  On the CPU backend, device placement of a
    numpy array can be zero-copy — and a checkpoint restore
    (``flax.serialization.msgpack_restore``) hands back numpy leaves
    that are views of one shared buffer.  Donating such a buffer lets
    XLA recycle memory the host side still owns: the elastic storm
    caught epoch checkpoints committing float-garbage ``step`` values
    in the first generation trained after a live resize (docs/FT.md
    "Elasticity" — the third CPU aliasing bug in this family, after the
    two ``ft/`` found in PR 3).  ``jnp.array(..., copy=True)`` contracts
    a private copy; jax Arrays pass through untouched."""
    return jax.tree.map(
        lambda x: jnp.array(x, copy=True)
        if isinstance(x, np.ndarray) else x, tree)


def replicate(tree, mesh: Mesh):
    """Place a pytree fully-replicated on the mesh (numpy leaves forced
    to private jax-owned copies first — see :func:`own_leaves`; the DP
    step donates this state)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(own_leaves(tree), sharding)


def shard_batch(batch: Batch, mesh: Mesh) -> Batch:
    """Shard every batch leaf along its leading (image) axis."""
    sharding = NamedSharding(mesh, P(data_axes(mesh)))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def stack_microbatches(batches: Sequence[Batch]):
    """Stack ``grad_accum`` consecutive loader batches into one
    accumulation batch: leaves ``(N, ...) -> (grad_accum, N, ...)``.
    Host-side numpy (the loader hands over host arrays); placement
    happens in :func:`shard_accum_batch` / the jitted step."""
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *batches)


def shard_accum_batch(batch: Batch, mesh: Mesh) -> Batch:
    """Shard an accumulation batch (leading microbatch axis, images on
    axis 1): microbatches replicated in sequence, images sharded —
    ``P(None, data_axes)``, matching ``make_dp_train_step``'s
    ``grad_accum > 1`` in_spec."""
    sharding = NamedSharding(mesh, P(None, data_axes(mesh)))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def _folded_step(model: FasterRCNN, cfg: Config, tx, axes, mode: str,
                 grad_accum: int = 1):
    """The per-shard step body shared by the streaming and cached DP paths:
    decorrelates per-image sampling RNG across mesh positions.  For a 2-D
    (dcn, ici) mesh ``axis_index`` over both axes is the linearized
    position, so an N-device run gives identical per-image keys regardless
    of the mesh factorization."""
    base = make_train_step(model, cfg, tx, axis_name=axes, mode=mode,
                           grad_accum=grad_accum)

    # graphlint: jit (runs under shard_map built by the two factories below)
    def shard_fn(state: TrainState, batch: Batch, key: jax.Array):
        key = jax.random.fold_in(key, jax.lax.axis_index(axes))
        return base(state, batch, key)

    return shard_fn


def make_dp_train_step(model: FasterRCNN, cfg: Config, tx, mesh: Mesh,
                       mode: str = "e2e", grad_accum: int = 1):
    """Jitted SPMD train step over ``mesh``.

    Takes (replicated state, sharded batch, replicated key); returns
    (replicated state, replicated metrics).  Gradient sync is the
    ``lax.pmean`` over ALL of the mesh's axes (``'data'``, or
    ``('dcn', 'ici')`` for a hierarchical mesh) inside
    ``core.train.make_train_step``.

    ``grad_accum > 1`` (the elastic shrink path): the batch carries a
    leading microbatch axis — microbatches stay whole (``None``) and the
    image axis (now axis 1) shards, so each device accumulates over ITS
    slice of every microbatch and the pmean after accumulation yields the
    effective-global-batch gradient in one collective per optimizer step.
    """
    axes = data_axes(mesh)
    shard_fn = _folded_step(model, cfg, tx, axes, mode,
                            grad_accum=grad_accum)

    batch_spec = P(axes) if grad_accum <= 1 else P(None, axes)
    # check_vma off: the RNG fold_in of axis_index is deliberately
    # replica-varying
    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    # donate the replicated state: in-place HBM update, no per-step copy
    return jax.jit(sharded, donate_argnums=(0,))


def make_dp_cached_step(model: FasterRCNN, cfg: Config, tx, mesh: Mesh,
                        num_batches: int, shuffle: bool = True,
                        mode: str = "e2e"):
    """SPMD train step fed from a mesh-sharded HBM epoch cache
    (``data/device_cache.py`` with ``build_caches(..., mesh=mesh)``).

    Signature matches ``make_cached_step``'s wrapping —
    ``(replicated state, sharded epoch data, replicated idx, replicated
    key) -> (state, idx+1, metrics)`` — but runs under ``shard_map``: each
    device gathers ITS slice of the selected batch from its local shard
    (the epoch is laid out ``P(None, data_axes)``, so the image-granular
    gather stays shard-local — inside shard_map the leaves carry LOCAL
    shapes and the per-epoch regroup permutes each device's own images),
    RNG decorrelates per mesh position, and gradients pmean over all mesh
    axes inside the step.  The epoch permutation draws from the
    replicated key, so devices stay in lockstep.  Disclosed residual vs
    streaming DP: images never migrate across devices between epochs
    (data/device_cache.py module docstring).
    """
    from mx_rcnn_tpu.data.device_cache import make_cached_step

    axes = data_axes(mesh)
    cached = make_cached_step(_folded_step(model, cfg, tx, axes, mode),
                              num_batches, shuffle=shuffle)
    sharded = jax.shard_map(
        cached,
        mesh=mesh,
        in_specs=(P(), P(None, axes), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,  # replica-varying RNG fold, as above
    )
    return jax.jit(sharded, donate_argnums=(0, 2))
