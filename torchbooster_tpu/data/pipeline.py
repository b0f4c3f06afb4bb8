"""Host loading pipeline: shard → decode → batch → prefetch to device.

TPU-native replacement for the reference's torch DataLoader stack
(ref config.py:348-379 LoaderConfig.make + distributed.py:78-98
data_sampler + config.py:486-525 iterable modulo-sharding):

- per-process index sharding replaces DistributedSampler (each host
  loads only its slice of the global batch),
- worker *threads* decode concurrently by default (numpy decode
  releases the GIL); ``workers="process"`` brings the reference's
  worker-process model back for python-heavy transforms that hold it,
- ``prefetch_to_device`` overlaps host decode with device compute and
  lands batches already sharded over the mesh's data axes — replacing
  the reference's per-step blocking ``.to("cuda")`` (ref
  config.py:174-175, SURVEY §3.3 H2D note),
- ``drop_last`` defaults True: static shapes, no remainder recompiles
  (SURVEY §7 dynamic-shapes note).

``batch_size`` is the **global** batch: each process yields
``batch_size // process_count`` examples per step and the device array
spans hosts (multi-host assembly via
``jax.make_array_from_process_local_data``). The reference's DDP
convention was per-rank batch size; global is the mesh-world unit.
"""
from __future__ import annotations

import collections
import multiprocessing
import os
import queue
import sys
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Any, Callable, Iterable, Iterator, Sequence

import jax
import numpy as np

from torchbooster_tpu import distributed as dist
from torchbooster_tpu.dataset import IterableDataset


def default_collate(examples: Sequence[Any]) -> Any:
    """Stack a list of examples into a batch pytree (the torch
    default_collate contract, numpy-valued)."""
    first = examples[0]
    if isinstance(first, dict):
        return {k: default_collate([e[k] for e in examples]) for k in first}
    if isinstance(first, tuple) and hasattr(first, "_fields"):  # namedtuple
        return type(first)(*(default_collate(col) for col in zip(*examples)))
    if isinstance(first, (tuple, list)):
        return type(first)(default_collate(col) for col in zip(*examples))
    return np.stack([np.asarray(e) for e in examples])


class SizedIterable(IterableDataset):
    """Iterable with a declared length + optional acceptance filter
    (ref IterableSizeableDataset config.py:470-483). ``size`` is the
    *pre-filter* count — an upper bound when a filter is set, exactly
    like the reference's NUM_LINES-derived sizes; ``None`` means
    unsized (``len()`` raises)."""

    def __init__(self, iterable: Iterable, size: int | None,
                 acceptance_fn: Callable[[Any], bool] | None = None):
        self.iterable = iterable
        self.size = size
        self.acceptance_fn = acceptance_fn

    def __len__(self) -> int:
        if self.size is None:
            raise TypeError("unsized iterable dataset has no len()")
        return self.size

    def __iter__(self) -> Iterator[Any]:
        for item in self.iterable:
            if self.acceptance_fn is None or self.acceptance_fn(item):
                yield item


class ShardedIterable(IterableDataset):
    """Modulo-shard a stream across processes: yield items where
    ``(i + shift) % mod == 0`` (ref DistributedIterableSizeableDataset
    config.py:486-525, with shift/mod from process topology — worker
    threads here share one iterator, so no worker term)."""

    def __init__(self, base: Iterable, shift: int | None = None,
                 mod: int | None = None):
        self.base = base
        self.shift = dist.get_rank() if shift is None else shift
        self.mod = dist.get_world_size() if mod is None else mod

    def __len__(self) -> int:
        # exact count of i in [0, n) with (i + shift) % mod == 0:
        # first match is (-shift) % mod, then every mod-th item
        n = len(self.base)
        first = (-self.shift) % self.mod
        return max(0, -(-(n - first) // self.mod)) if first < n else 0

    def __iter__(self) -> Iterator[Any]:
        for i, item in enumerate(self.base):
            if (i + self.shift) % self.mod == 0:
                yield item


# worker-process state, set once per process by the pool initializer
# (shipping the dataset per task would re-pickle it every batch)
_WORKER: dict = {}


def _worker_init(dataset: Any, collate_fn: Callable) -> None:
    # a chip belongs to one process: whatever JAX work the dataset's
    # code does in a loader child runs on the CPU, never on the device
    # of the process this child feeds
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")
    _WORKER["dataset"] = dataset
    _WORKER["collate"] = collate_fn


def _worker_assemble(chunk: list[int]) -> Any:
    dataset, collate = _WORKER["dataset"], _WORKER["collate"]
    fetch_many = getattr(dataset, "__getitems__", None)
    if fetch_many is not None:
        return collate(fetch_many(chunk))
    return collate([dataset[i] for i in chunk])


class DataLoader:
    """Map/iterable dataset → batches of host numpy pytrees.

    One epoch = one pass; iterate repeatedly (or wrap in
    :func:`torchbooster_tpu.utils.iter_loader`) for epoch tracking.
    Shuffling reshuffles every epoch with ``seed + epoch`` — the
    sampler-epoch contract of the reference's DistributedSampler
    (ref distributed.py:78-98).

    ``workers``: "thread" (default — numpy decode releases the GIL) or
    "process" (the reference's worker-process model, ref
    config.py:371-379, for python-heavy per-item transforms that hold
    the GIL and would starve the chip; dataset + collate_fn must
    pickle). Process workers SNAPSHOT the dataset and collate_fn when
    the pool first starts and keep that copy across epochs — mutate
    the dataset between epochs only in thread mode, or call
    :meth:`close` first so the next epoch re-pickles it."""

    def __init__(
        self,
        dataset: Any,
        batch_size: int = 32,
        shuffle: bool = True,
        distributed: bool = False,
        drop_last: bool = True,
        num_workers: int = 0,
        prefetch: int = 2,
        collate_fn: Callable | None = None,
        seed: int = 0,
        workers: str = "thread",
    ):
        if workers not in ("thread", "process"):
            raise ValueError(f"workers={workers!r}: 'thread' or 'process'")
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.distributed = distributed
        self.drop_last = drop_last
        self.num_workers = num_workers
        self.prefetch = max(prefetch, 1)
        self.collate_fn = collate_fn or default_collate
        self.seed = seed
        self.workers = workers
        self.epoch = 0
        self._pool: ProcessPoolExecutor | None = None

        world = dist.get_world_size() if distributed else 1
        if batch_size % world:
            raise ValueError(
                f"global batch_size {batch_size} not divisible by "
                f"process count {world}")
        self.local_batch = batch_size // world
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable and distributed and not isinstance(
                dataset, ShardedIterable):
            self.dataset = ShardedIterable(dataset)

    def __len__(self) -> int:
        n = len(self.dataset)
        if self._iterable:
            if self.drop_last:
                return n // self.local_batch
            return -(-n // self.local_batch)
        world = dist.get_world_size() if self.distributed else 1
        per_process = n // world if self.drop_last else -(-n // world)
        if self.drop_last:
            return per_process // self.local_batch
        return -(-per_process // self.local_batch)

    def _epoch_indices(self) -> np.ndarray:
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            order = rng.permutation(n)
        else:
            order = np.arange(n)
        if self.distributed:
            world, rank = dist.get_world_size(), dist.get_rank()
            # strided shard, equalized length (DistributedSampler contract)
            per = n // world if self.drop_last else -(-n // world)
            order = np.resize(order, per * world)[rank::world] \
                if not self.drop_last else order[:per * world][rank::world]
        return order

    def _batches_of_indices(self) -> Iterator[np.ndarray]:
        order = self._epoch_indices()
        limit = (len(order) // self.local_batch) * self.local_batch \
            if self.drop_last else len(order)
        for start in range(0, limit, self.local_batch):
            chunk = order[start:start + self.local_batch]
            if self.drop_last and len(chunk) < self.local_batch:
                return
            yield chunk

    def _process_pool(self) -> ProcessPoolExecutor:
        """Lazily started, reused across epochs (spawn, not fork: a
        forked copy of a process with a live device runtime can deadlock
        on inherited locks)."""
        if self._pool is None:
            self._pool = ProcessPoolExecutor(
                self.num_workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_worker_init,
                initargs=(self.dataset, self.collate_fn))
        return self._pool

    def close(self) -> None:
        """Retire worker processes (thread mode has nothing to close)."""
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None

    def __del__(self):  # best-effort; close() is the explicit path
        try:
            self.close()
        except Exception:  # pragma: no cover - interpreter teardown
            pass

    def _map_iter(self) -> Iterator[Any]:
        fetch = self.dataset.__getitem__
        fetch_many = getattr(self.dataset, "__getitems__", None)
        if fetch_many is not None:
            # batched-fetch protocol: one storage gather per batch
            def assemble(chunk):
                return self.collate_fn(fetch_many([int(i) for i in chunk]))
        else:
            def assemble(chunk):
                return self.collate_fn([fetch(int(i)) for i in chunk])
        if self.num_workers > 0:
            if self.workers == "process":
                pool = self._process_pool()
                submit_one = lambda chunk: pool.submit(  # noqa: E731
                    _worker_assemble, [int(i) for i in chunk])
            else:
                pool = ThreadPoolExecutor(self.num_workers)
                submit_one = lambda chunk: pool.submit(  # noqa: E731
                    assemble, chunk)
            try:
                pending: collections.deque = collections.deque()
                depth = self.prefetch + 1
                for chunk in self._batches_of_indices():
                    pending.append(submit_one(chunk))
                    if len(pending) >= depth:
                        yield pending.popleft().result()
                while pending:
                    yield pending.popleft().result()
            finally:
                if self.workers == "thread":
                    pool.shutdown()
        else:
            for chunk in self._batches_of_indices():
                yield assemble(chunk)

    def _iterable_iter(self) -> Iterator[Any]:
        buffer: list[Any] = []
        for item in self.dataset:
            buffer.append(item)
            if len(buffer) == self.local_batch:
                yield self.collate_fn(buffer)
                buffer = []
        if buffer and not self.drop_last:
            yield self.collate_fn(buffer)

    def __iter__(self) -> Iterator[Any]:
        iterator = self._iterable_iter() if self._iterable else self._map_iter()
        yield from iterator
        self.epoch += 1


def _place_global(batch: Any, mesh) -> Any:
    """Host batch (this process's slice) → global device array sharded
    over the mesh's data axes."""
    if jax.process_count() == 1:
        return dist.shard_batch(batch, mesh)

    def place(leaf: Any) -> Any:
        arr = np.asarray(leaf)
        sharding = dist.batch_sharding(mesh, max(arr.ndim, 1))
        return jax.make_array_from_process_local_data(sharding, arr)

    return jax.tree.map(place, batch)


def prefetch_to_device(loader: Iterable, mesh=None, size: int = 2
                       ) -> Iterator[Any]:
    """Overlap host loading with device compute: keep ``size`` batches
    in flight on device ahead of the consumer (the pipelined analogue of
    pin_memory + async .to(device); SURVEY §3.3). A background thread
    feeds a bounded queue so decode/augment never blocks the step."""
    from torchbooster_tpu.observability import get_registry

    if mesh is None:
        mesh = dist.get_mesh()
    q: queue.Queue = queue.Queue(maxsize=size)
    sentinel = object()
    stop = threading.Event()
    error: list[BaseException] = []
    # pipeline telemetry: batches produced + how long the producer sat
    # blocked on a full queue (≈0 when the device is the bottleneck —
    # the healthy state; growing wait time means host decode is
    # OUTRUNNING the chip and prefetch depth is just masking it, while
    # a starved consumer shows up as the step-time histogram instead)
    reg = get_registry()
    batches_ctr = reg.counter("data_batches_total",
                              "batches placed on device by prefetch")
    wait_hist = reg.histogram("data_producer_wait_seconds",
                              "producer time blocked on a full queue")

    def producer() -> None:
        try:
            for batch in loader:
                placed = _place_global(batch, mesh)
                t_wait = time.perf_counter()
                while not stop.is_set():
                    try:
                        q.put(placed, timeout=0.1)
                        batches_ctr.inc()
                        wait_hist.observe(
                            time.perf_counter() - t_wait)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as exc:  # propagate into consumer
            error.append(exc)
        finally:
            # the sentinel must use the same stop-aware blocking put as
            # batches: put_nowait on a full queue would drop it and leave
            # the consumer blocked on q.get() forever
            while not stop.is_set():
                try:
                    q.put(sentinel, timeout=0.1)
                    break
                except queue.Full:
                    continue

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                if error:
                    raise error[0]
                return
            yield item
    finally:
        # consumer stopped early (break/exception/GeneratorExit): unblock
        # and retire the producer so neither the thread nor its device
        # batches outlive this generator
        stop.set()
        while not q.empty():
            try:
                q.get_nowait()
            except queue.Empty:
                break
        thread.join(timeout=5.0)


__all__ = ["DataLoader", "ShardedIterable", "SizedIterable",
           "default_collate", "prefetch_to_device"]
