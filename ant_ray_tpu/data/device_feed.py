"""Device-feed: prefetched, double-buffered host→device batch delivery.

The ingest gap this closes (T3, arxiv 2401.16677 — fine-grained overlap
of data movement with compute): ``iter_batches`` stops at host numpy
batches, so every training step pays collate + host→HBM transfer on the
critical path.  ``iter_device_batches`` moves both off it:

* a background **producer thread** pulls blocks, collates rows into
  contiguous fixed-shape arrays (the tail batch pads to ``batch_size``
  so a jitted step never recompiles), and issues **async**
  ``jax.device_put`` against the consumer's sharding — the host→HBM DMA
  for batch N+1 overlaps the step compute for batch N;
* a **bounded queue** (``prefetch_batches`` deep — 2 is classic double
  buffering) backpressures the producer so at most that many batches
  are in flight in HBM;
* the producer never blocks on transfer completion — the consumer's
  step dereferences the arrays, which is where XLA sequences the
  dependency.

``prefetch_batches=0`` is the synchronous baseline (collate + transfer
+ completion inline in the consumer's loop); it exists so the overlap
is observable — ``benchmarks/microbench.py``'s ``data_device_feed``
workload reports the consumer starve-fraction for both modes.

Every stage is timed into ``DeviceFeed.stats`` (block-wait, collate,
transfer-issue, consumer-starve), surfaced through
``DataIterator.stats()["device_feed"]``.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from typing import Any, Callable

import numpy as np

from ant_ray_tpu._private.jax_utils import trace_annotation

_END = ("end", None)


def _import_jax_or_none():
    try:
        from ant_ray_tpu._private.jax_utils import import_jax  # noqa: PLC0415

        return import_jax()
    except Exception:  # noqa: BLE001 — host-only rigs feed numpy batches
        return None


def default_collate(batch) -> dict:
    """Numpy batch (dict of columns) → dict of contiguous numpy arrays.

    A list-block column of dict rows explodes into one array per key
    (the ``from_items([{...}])`` path).  Columns that stay object-dtype
    cannot form a fixed-shape device array — pass a ``collate_fn``."""
    if not isinstance(batch, dict):
        batch = {"value": batch}
    out: dict = {}
    for key, col in batch.items():
        arr = np.asarray(col)
        if arr.dtype == object:
            rows = list(col)
            if rows and all(isinstance(r, dict) for r in rows):
                for k in rows[0]:
                    sub = np.asarray([r[k] for r in rows])
                    if sub.dtype == object:
                        raise TypeError(
                            f"row key {k!r} is ragged/non-numeric; pass "
                            "a collate_fn that produces fixed-shape "
                            "arrays")
                    out[k] = np.ascontiguousarray(sub)
                continue
            raise TypeError(
                f"column {key!r} is not dense (dtype=object); pass a "
                "collate_fn that maps the numpy batch to fixed-shape "
                "arrays")
        out[key] = np.ascontiguousarray(arr)
    return out


def pad_to_batch(tree: dict, batch_size: int, pad_value=0):
    """Pad every array's leading dim to ``batch_size`` (returns
    ``(padded_tree, n_padding_rows)``).  Fixed shapes are the contract
    that keeps a jitted step at one compilation across the epoch."""
    n = None
    for leaf in tree.values():
        n = leaf.shape[0] if n is None else min(n, leaf.shape[0])
    if n is None or n >= batch_size:
        return tree, 0
    pad = batch_size - n
    out = {
        k: np.concatenate(
            [a, np.full((pad,) + a.shape[1:], pad_value, dtype=a.dtype)])
        for k, a in tree.items()
    }
    return out, pad


class DeviceFeed:
    """One epoch of device-batch delivery over a block stream.

    ``blocks_fn`` yields blocks (one pass); iterate the feed once.
    ``sharding`` may be a ``jax.sharding.Sharding`` / device, or a
    callable resolved lazily in the consuming process — called as
    ``sharding(rank, world)`` (falling back to no-args) so the trainer
    can forward per-worker shardings without shipping device handles.
    """

    def __init__(self, blocks_fn: Callable, *, batch_size: int,
                 prefetch_batches: int = 2, sharding: Any = None,
                 collate_fn: Callable | None = None,
                 drop_last: bool = False, pad_value=0,
                 rank: int = 0, world: int = 1):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive: {batch_size}")
        self._blocks_fn = blocks_fn
        self._batch_size = batch_size
        self._prefetch = max(0, int(prefetch_batches))
        self._sharding = sharding
        self._collate = collate_fn or default_collate
        self._drop_last = drop_last
        self._pad_value = pad_value
        self._rank = rank
        self._world = world
        self._jax = _import_jax_or_none()
        self.thread: threading.Thread | None = None
        self.stats: dict = {
            "batch_size": batch_size,
            "prefetch_batches": self._prefetch,
            "batches": 0,
            "tail_padded_rows": 0,
            "block_wait_s": 0.0,
            "collate_s": 0.0,
            "transfer_issue_s": 0.0,
            "consumer_starve_s": 0.0,
            "consumer_wall_s": 0.0,
            "consumer_starve_fraction": 0.0,
        }

    # ---- producer stages

    def _resolved_sharding(self):
        sharding = self._sharding
        if callable(sharding) and not hasattr(sharding, "device_set"):
            try:
                sharding = sharding(self._rank, self._world)
            except TypeError:
                sharding = sharding()
        return sharding

    def _timed_blocks(self):
        it = iter(self._blocks_fn())
        while True:
            t0 = time.perf_counter()
            try:
                block = next(it)
            except StopIteration:
                return
            self.stats["block_wait_s"] += time.perf_counter() - t0
            yield block

    def _host_batches(self):
        from ant_ray_tpu.data.block import batches_from_blocks  # noqa: PLC0415

        for batch in batches_from_blocks(self._timed_blocks(),
                                         self._batch_size, "numpy",
                                         self._drop_last):
            t0 = time.perf_counter()
            tree = self._collate(batch)
            tree, padded = pad_to_batch(tree, self._batch_size,
                                        self._pad_value)
            self.stats["collate_s"] += time.perf_counter() - t0
            self.stats["tail_padded_rows"] += padded
            yield tree

    def _to_device(self, tree, sharding):
        if self._jax is None:
            return tree            # host-only rig: numpy batches
        t0 = time.perf_counter()
        if sharding is None:
            out = self._jax.device_put(tree)
        else:
            out = self._jax.device_put(tree, sharding)
        # No block_until_ready: device_put is dispatched async; the DMA
        # runs while the consumer computes on the previous batch.
        self.stats["transfer_issue_s"] += time.perf_counter() - t0
        return out

    def _produce(self, q: _queue.Queue, stop: threading.Event,
                 sharding) -> None:
        try:
            for tree in self._host_batches():
                if stop.is_set():
                    return
                if not self._put(q, stop, ("batch",
                                           self._to_device(tree, sharding))):
                    return
            self._put(q, stop, _END)
        except BaseException as e:  # noqa: BLE001 — re-raised by consumer
            self._put(q, stop, ("error", e))

    @staticmethod
    def _put(q: _queue.Queue, stop: threading.Event, item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    # ---- consumer

    def __iter__(self):
        sharding = self._resolved_sharding()
        wall0 = time.perf_counter()
        try:
            if self._prefetch == 0:
                yield from self._iter_sync(sharding)
            else:
                yield from self._iter_prefetched(sharding)
        finally:
            wall = time.perf_counter() - wall0
            self.stats["consumer_wall_s"] = wall
            self.stats["consumer_starve_fraction"] = (
                self.stats["consumer_starve_s"] / wall if wall > 0 else 0.0)

    def _iter_sync(self, sharding):
        """prefetch_batches=0: the blocking baseline — collate, transfer
        AND completion all on the consumer's critical path."""
        gen = self._host_batches()
        while True:
            t0 = time.perf_counter()
            with trace_annotation("feed:wait"):
                try:
                    tree = next(gen)
                except StopIteration:
                    return
                dev = self._to_device(tree, sharding)
                if self._jax is not None:
                    self._jax.block_until_ready(dev)
            self.stats["consumer_starve_s"] += time.perf_counter() - t0
            self.stats["batches"] += 1
            yield dev

    def _iter_prefetched(self, sharding):
        q: _queue.Queue = _queue.Queue(maxsize=self._prefetch)
        stop = threading.Event()
        self.thread = threading.Thread(
            target=self._produce, args=(q, stop, sharding),
            daemon=True, name="device-feed-producer")
        self.thread.start()
        try:
            while True:
                # `feed:wait` in a profiler trace is the interval that
                # feeds consumer_starve_s.
                t0 = time.perf_counter()
                with trace_annotation("feed:wait"):
                    kind, payload = q.get()
                self.stats["consumer_starve_s"] += time.perf_counter() - t0
                if kind == "end":
                    return
                if kind == "error":
                    raise payload
                self.stats["batches"] += 1
                yield payload
        finally:
            # Early consumer exit (or normal end): release the producer
            # from a full queue and join it.
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except _queue.Empty:
                pass
            self.thread.join(timeout=5.0)
