"""Per-host sharded batching — replaces DataLoader + DistributedSampler.

Under SPMD there is one process per host (not per chip), so the reference's
two process boundaries (mp.spawn rank procs + 8 DataLoader workers each,
multi_gpu_trainer.py:63,212-219) collapse into this loader: each host decodes
only its shard of the global index order and feeds a host-local numpy batch;
pjit/shard_map then treats the per-host batches as one global batch sharded on
the 'data' mesh axis.

Sharding semantics mirror torch DistributedSampler exactly
(multi_gpu_trainer.py:61-64):

* train: per-epoch permutation from seed 42 (+epoch), drop_last — the global
  sample count is ⌊len/world⌋·world and shard r takes indices [r::world];
* eval: no shuffle, wrap-around (tiled) padding so every shard sees the same
  batch count even when the dataset is smaller than the shard count (torch
  tiles its index list the same way; upstream eval divides by the padded
  count, we keep that). ``pad_final_batch`` additionally rounds the LAST
  batch up to full size by wrapping — required because batches are placed
  with their leading dim sharded over the 'data' mesh axis, which needs even
  divisibility (a GPU ragged tail has no SPMD equivalent); the duplicate
  samples bias the epoch-mean val loss negligibly and deterministically.

Decode is overlapped with device compute by a thread pool that parallelizes
*within* a batch plus a bounded prefetch queue, so at most ``prefetch + 1``
decoded batches exist at any time regardless of dataset size (PIL decode
releases the GIL; this replaces the reference's 8 worker processes).
"""

from __future__ import annotations

import itertools
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional

import numpy as np

from ddim_cold_tpu.obs import spans
from ddim_cold_tpu.utils import faults


class ShardedLoader:
    """Iterable over host-local batches of ``(noisy, target, t)`` numpy arrays."""

    def __init__(
        self,
        dataset,
        batch_size: int,
        *,
        shuffle: bool,
        seed: int = 42,
        drop_last: bool = True,
        shard_index: int = 0,
        shard_count: int = 1,
        num_threads: int = 8,
        prefetch: int = 2,
        pad_final_batch: bool = False,
        raw: bool = False,
    ):
        if raw and not hasattr(dataset, "get_raw_batch"):
            raise ValueError(
                f"raw=True needs dataset.get_raw_batch; {type(dataset).__name__} "
                "does not implement the device-side corruption contract")
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.shard_index = shard_index
        self.shard_count = shard_count
        self.num_threads = num_threads
        self.prefetch = prefetch
        self.pad_final_batch = pad_final_batch
        self.raw = raw
        self.epoch = 0

    def set_epoch(self, epoch: int) -> None:
        """Reseed the epoch shuffle (mirrors DistributedSampler.set_epoch)."""
        self.epoch = epoch
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def _shard_indices(self) -> np.ndarray:
        n = len(self.dataset)
        if self.shuffle:
            indices = np.random.RandomState(self.seed + self.epoch).permutation(n)
        else:
            indices = np.arange(n)
        world = self.shard_count
        if self.drop_last:
            total = (n // world) * world
            indices = indices[:total]
        else:
            total = -(-n // world) * world  # ceil to a multiple of world
            if total > n:
                indices = np.resize(indices, total)  # tiled wrap-around pad
        return indices[self.shard_index :: world]

    def __len__(self) -> int:
        per_shard = len(self._shard_indices())
        if self.drop_last:
            return per_shard // self.batch_size
        return -(-per_shard // self.batch_size)

    def _batches(self) -> list[np.ndarray]:
        indices = self._shard_indices()
        nb = len(self)
        if self.pad_final_batch and nb * self.batch_size > len(indices):
            indices = np.resize(indices, nb * self.batch_size)
        return [indices[i * self.batch_size : (i + 1) * self.batch_size]
                for i in range(nb)]

    def _collate(self, items):
        noisy = np.stack([it[0] for it in items])
        target = np.stack([it[1] for it in items])
        t = np.asarray([it[2] for it in items], dtype=np.int32)
        return noisy, target, t

    def _make_batch(self, idxs: np.ndarray, pool: Optional[ThreadPoolExecutor] = None):
        # chaos hook: covers the threaded and unthreaded iteration paths
        # alike (an injected raise here surfaces at the consumer's next(),
        # exactly like a real decode failure would)
        faults.fire("data.next", tag=f"epoch:{self.epoch}|")
        if self.raw:  # (base, t) only — corruption happens on device (in-jit)
            return self.dataset.get_raw_batch(
                idxs, num_threads=max(1, self.num_threads), pool=pool)
        # native fast path: the dataset assembles the whole batch in C++
        # threads (decode/resize/degrade/collate outside the GIL); None means
        # "not available for this batch" → per-item python path.
        get_batch = getattr(self.dataset, "get_batch", None)
        if get_batch is not None:
            batch = get_batch(idxs, num_threads=max(1, self.num_threads), pool=pool)
            if batch is not None:
                return batch
        if pool is None:
            items = [self.dataset[int(i)] for i in idxs]
        else:
            items = list(pool.map(self.dataset.__getitem__, [int(i) for i in idxs]))
        return self._collate(items)

    def __iter__(self) -> Iterator:
        batches = self._batches()
        epoch = self.epoch
        if self.num_threads <= 1:
            # no pipeline, the same span: a reader finds this loader's
            # decode work under one name, threaded or not
            trace = spans.new_trace_id()
            for i, b in enumerate(batches):
                with spans.layer("data/decode/work", trace_id=trace, batch=i,
                                 epoch=epoch):
                    batch = self._make_batch(b)
                yield batch
            return

        # one producer thread decodes batch-by-batch (items fan out over the
        # pool); the bounded queue caps live memory at prefetch+1 batches and
        # an abandoned iterator stops decoding within one batch.
        with ThreadPoolExecutor(self.num_threads) as pool:
            yield from _background_map(
                batches, lambda b: self._make_batch(b, pool), self.prefetch,
                "decode", epoch=epoch)


def _background_map(items, fn, depth: int, stage: Optional[str] = None,
                    waits: bool = False, **attrs):
    """Yield ``fn(item)`` with the mapping running ``depth`` items ahead in a
    producer thread (bounded queue). Exceptions from ``fn`` or the iterator
    surface at the consuming ``next()``; abandoning the generator (break/
    close) stops the producer within one item. Shared machinery for the
    decode pipeline (ShardedLoader, ``stage`` "decode"), the H2D overlap
    (device_prefetch, "place") and the engine's batch assembly (no stage:
    it has its own ``engine/assemble`` span).

    One call is one pipeline. With a ``stage`` it records layer spans that
    share one trace id on both threads (that of the layer span open where
    the consumer starts it, else a new one): ``data/<stage>/work`` around
    ``fn(item)`` and, with ``waits``, ``data/<stage>/get_wait`` while the
    consumer is blocked on the empty queue (none when the item was ready).
    ``batch`` is the item's index in this pipeline; ``attrs`` ride on every
    span.
    """
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    cur = spans.current()
    trace = cur.trace_id if cur else spans.new_trace_id()

    def span(part: str, i: int):
        return spans.layer(f"data/{stage}/{part}", trace_id=trace, batch=i,
                           **attrs)

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def work(it, i: int):
        if stage is None:
            return fn(it)
        with span("work", i):
            return fn(it)

    def producer():
        try:
            for i, it in enumerate(items):
                if stop.is_set() or not put(work(it, i)):
                    return
            put(None)
        except BaseException as e:  # noqa: BLE001 — worker thread: ANY error (incl. KeyboardInterrupt) must surface to the consumer
            put(e)

    def get(i: int):
        if not waits:
            return q.get()
        try:
            return q.get_nowait()
        except queue.Empty:
            pass
        with span("get_wait", i):
            return q.get()

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    try:
        for i in itertools.count():
            item = get(i)
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # unblock a producer waiting on a full queue, then reap it
        while thread.is_alive():
            try:
                q.get_nowait()
            except queue.Empty:
                pass
            thread.join(timeout=0.2)


def group_batches(batches, n: int):
    """Stack every ``n`` successive batches along a new leading axis — the
    host-side half of ``make_train_step(steps_per_dispatch=n)``: one grouped
    batch becomes one dispatch running n optimizer steps on device. A
    trailing partial group (< n batches at epoch end) is dropped, mirroring
    ``drop_last`` semantics — callers that must see every sample should size
    epochs divisible by n or flush the tail with a 1-step fn."""
    if n <= 1:
        yield from batches
        return
    import jax

    buf = []
    for b in batches:
        buf.append(b)
        if len(buf) == n:
            yield jax.tree.map(lambda *xs: np.stack(xs), *buf)
            buf = []


def device_prefetch(batches, place, depth: int = 2,
                    stage: Optional[str] = "place"):
    """Yield ``place(batch)`` for each host batch, with the placement (the
    host→device copy) running ``depth`` batches ahead in a background thread.

    On network-attached TPU hosts ``jax.device_put`` blocks on the upload RPC,
    so an unprefetched loop serializes transfer and compute; this overlaps
    them (the JAX client is thread-safe for placement).

    Recorded as the data layer's ``stage`` (``data/place/work`` a batch, and
    ``data/place/get_wait`` while the consuming loop waits for one), with the
    loader's ``epoch`` where ``batches`` is one. A caller whose ``place`` is
    not a placement (the engine's batch assembly) passes ``stage=None`` and
    records its own spans.
    """
    epoch = getattr(batches, "epoch", None)
    attrs = {} if epoch is None else {"epoch": epoch}
    return _background_map(batches, place, depth, stage,
                           waits=stage is not None, **attrs)
