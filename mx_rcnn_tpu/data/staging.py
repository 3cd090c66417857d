"""Double-buffered host→device staging for the training input plane.

Reference: none — the reference's synchronous loader hands host arrays to
``module.forward`` and eats the transfer inside the step.  Pre-r7 this
framework did the JAX analog: the fit loop called ``next(batch_iter)``
(host numpy, overlapped assembly via ``loader._prefetched``) and let jit
argument transfer move the bytes host→device INSIDE the step dispatch —
so every step paid the transfer on the critical path, and the only way
``data_wait_frac ~ 0`` held was the HBM-resident epoch cache
(``data/device_cache.py``), which requires the dataset to fit in device
memory (the docs/PERF.md "HBM-resident" asterisk).

:class:`DeviceStager` removes that requirement: ONE daemon thread pulls
assembled batches from the source iterator, applies ``place`` (a plain
``jax.device_put`` on a single device, or the mesh sharding placement
from ``parallel/dp.py``) and keeps up to ``depth`` DEVICE-RESIDENT
batches in a bounded queue.  The fit loop's ``next()`` then returns an
already-placed batch in ~queue-pop time; assembly and transfer of batch
k+1 overlap step k.  ``depth=2`` is classic double buffering; each slot
costs one batch of device memory (uint8 images keep that small).

Semantics are strictly pass-through: same batches, same order, same
values — the stager only changes WHERE the ``device_put`` happens
(pinned by tests/test_streaming.py).  Exceptions from the source
iterator or the placement re-raise in the consumer; early abandonment
(consumer ``close()``) releases the worker without draining the epoch.

Obs (``cfg.obs.enabled``): ``loader.staged_batches`` counts placed
batches, ``loader.stage_put_ms`` the worker-side assemble+place time,
``loader.stage_hits``/``loader.stage_misses`` whether the consumer found
a batch ready (hit = the overlap did its job; the data-smoke gate
asserts hits > 0), and the ``loader.stage_depth`` gauge the occupancy
at each pop.  With span collection on (``obs/trace.py``) the worker also
leaves three spans per batch on its own thread — ``stage.assemble`` (the
``next`` on the source), ``stage.place`` and ``stage.put_wait`` (blocked
on the full queue) — each with ``seq=``, the batch's ordinal in the
epoch, which is the fit loop's step within it: what the input plane did
while the loop sat in ``train.data_wait``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator

from mx_rcnn_tpu.obs import trace as obs_trace

_END = object()


class DeviceStager:
    """Stage ``place(batch)`` results from a background thread, ``depth``
    batches ahead of the consumer.

    Args:
      source: iterable of host batches (a loader iterator, possibly
        wrapped in grad-accum stacking).
      place: host batch -> device-resident batch (``jax.device_put`` or a
        mesh-sharding placement).  Runs ONLY on the worker thread.
      depth: max device-resident batches in flight (>= 1).
      rec: an ``obs/metrics.py`` Registry, or None (the default) to keep
        the hot path metric-free.
      first_seq: the ordinal of the first batch ``source`` yields (the
        spans' ``seq``): 1 at an epoch's start, one past the batches a
        mid-epoch resume skipped.
    """

    def __init__(self, source: Iterable, place: Callable, depth: int = 2,
                 rec=None, first_seq: int = 1):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(int(depth), 1))
        self._rec = rec
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, args=(iter(source), place, int(first_seq)),
            name="device-stager", daemon=True)
        self._thread.start()

    def _run(self, it: Iterator, place: Callable, seq: int) -> None:
        try:
            while not self._closed:
                t0 = time.perf_counter()
                try:
                    with obs_trace.span("stage.assemble", seq=seq):
                        batch = next(it)
                except StopIteration:
                    break
                with obs_trace.span("stage.place", seq=seq):
                    placed = place(batch)
                if self._rec is not None:
                    self._rec.inc("loader.staged_batches")
                    self._rec.observe("loader.stage_put_ms",
                                      (time.perf_counter() - t0) * 1e3)
                with obs_trace.span("stage.put_wait", seq=seq):
                    self._put(placed)
                seq += 1
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            self._put(e)
            return
        self._put(_END)

    def _put(self, item) -> None:
        # bounded put that gives up when the consumer closed mid-epoch —
        # a plain blocking put would leave the thread wedged forever on a
        # full queue nobody drains
        while not self._closed:
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def __iter__(self) -> Iterator:
        while True:
            if self._rec is not None:
                try:
                    item = self._q.get_nowait()
                    waited = False
                except queue.Empty:
                    item = self._q.get()
                    waited = True
            else:
                item = self._q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            if self._rec is not None:
                # counted only for REAL batches: the end-of-epoch
                # sentinel pop must not skew the hit rate
                self._rec.inc("loader.stage_misses" if waited
                              else "loader.stage_hits")
                self._rec.set_gauge("loader.stage_depth", self._q.qsize())
            yield item

    def close(self) -> None:
        """Release the worker (early abandonment or normal epoch end);
        idempotent.  Queued device batches are dropped on the floor —
        device buffers free with their last reference."""
        self._closed = True
        while True:  # unblock a worker parked on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
