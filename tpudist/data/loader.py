"""Host-side batch assembly and device staging.

TPU-native replacement for ``DataLoader(pin_memory=True)`` + in-loop
``.cuda()`` copies (/root/reference/main.py:54-63,98-99). The reference's
synchronous per-step H2D copy sits on the critical path (SURVEY.md §7 "hard
parts" #1); here batches are assembled from an in-memory numpy dataset
(vectorized gather — optionally via the C++ batcher in tpudist/csrc) and
staged onto the mesh with ``shard_batch``, with an N-deep prefetch queue so
the copy for step k+1 overlaps the compute of step k.
"""

from __future__ import annotations

import collections
import itertools
import threading
from typing import Callable, Iterator, Mapping

import numpy as np

from tpudist.data.sampler import DistributedSampler


class SampledLoader:
    """The shared iterator contract of every tpudist loader.

    Subclasses set ``sampler``, ``batch_size``, ``drop_remainder`` and
    implement ``_gather_batch(indices, start)`` (``start`` = the batch's
    position in the epoch's index stream, for position-keyed augmentation).
    This base provides ``__len__`` / ``__iter__`` / ``iter_from`` — one
    implementation of the drop-remainder and mid-epoch-resume math shared by
    the array-backed, image-folder, and token-window loaders.
    """

    sampler: DistributedSampler
    batch_size: int
    drop_remainder: bool

    def __len__(self) -> int:
        n = self.sampler.num_samples
        return (
            n // self.batch_size
            if self.drop_remainder
            else -(-n // self.batch_size)
        )

    def _gather_batch(self, indices: np.ndarray, start: int) -> dict:
        raise NotImplementedError

    def probe(self) -> dict:
        """A one-SAMPLE batch for shape/dtype inspection — lets ``fit`` learn
        the element spec without gathering (for the image loader: decoding)
        a full per-process batch that the epoch loop will re-gather anyway."""
        return self._gather_batch(self.sampler.epoch_indices()[:1], 0)

    def __iter__(self) -> Iterator[dict]:
        return self.iter_from(0)

    def iter_from(self, start_batch: int) -> Iterator[dict]:
        """Iterate this epoch starting at batch ``start_batch`` — index-level
        skip for mid-epoch resume (no gather/transform work for the skipped
        batches, unlike islice over __iter__)."""
        indices = self.sampler.epoch_indices()
        limit = len(self) * self.batch_size if self.drop_remainder else len(indices)
        for start in range(start_batch * self.batch_size, limit, self.batch_size):
            yield self._gather_batch(indices[start : start + self.batch_size], start)


class DataLoader(SampledLoader):
    """Iterates minibatches of an array-backed dataset for one epoch.

    ``dataset`` is a mapping of name → numpy array, all with equal leading
    dimension (e.g. ``{"image": (N,32,32,3) uint8, "label": (N,) int32}``).
    A ``DistributedSampler`` supplies this rank's index shard; batches are
    gathered host-side and handed to ``transform`` (e.g. uint8→float32
    normalization, augmentation) before staging.

    Matches the reference loader's contract: ``shuffle=False`` at the loader
    (the sampler owns shuffling, /root/reference/main.py:56-58) and
    ``drop_last=False`` → final short batch is dropped only if
    ``drop_remainder`` (pjit needs static shapes, so the default drops the
    ragged tail — with the sampler's padding this loses < one batch/epoch).
    """

    def __init__(
        self,
        dataset: Mapping[str, np.ndarray],
        batch_size: int,
        sampler: DistributedSampler | None = None,
        transform: Callable[[dict], dict] | None = None,
        drop_remainder: bool = True,
        native: bool = True,
    ):
        sizes = {k: len(v) for k, v in dataset.items()}
        if len(set(sizes.values())) != 1:
            raise ValueError(f"ragged dataset arrays: {sizes}")
        self.dataset = dict(dataset)
        self.size = next(iter(sizes.values()))
        self.batch_size = batch_size
        self.sampler = sampler or DistributedSampler(
            self.size, num_replicas=1, rank=0, shuffle=False
        )
        self.transform = transform
        self.drop_remainder = drop_remainder
        # native=True routes batch assembly through the C++ core (parallel
        # gather fused with the ToTensor conversion, tpudist/csrc/batcher.cpp)
        # when the library is available and the transform supports it; the
        # numpy path below is the always-available fallback
        self.native = native

    def _gather_batch(self, idx: np.ndarray, start: int) -> dict:
        if self.native:
            from tpudist.data.native import native_batch

            batch = native_batch(self.dataset, idx, self.transform)
            if batch is not None:
                return batch
        batch = {k: v[idx] for k, v in self.dataset.items()}
        if self.transform is not None:
            batch = self.transform(batch)
        return batch


def prefetch_to_mesh(iterator, mesh, *, depth: int = 2, stage_fn=None,
                     stop_check=None, stop_poll_s: float = 0.5, tracer=None):
    """Stage host batches onto the device mesh ``depth`` steps ahead.

    The replacement for pinned-memory + synchronous ``.cuda()``: device_put
    is async in JAX, so keeping ``depth`` batches in flight overlaps host
    gather + H2D DMA with on-device compute. A background thread runs the
    host-side gather/transform so it too leaves the critical path.

    ``stage_fn`` overrides the default flat-batch sharding (used e.g. by the
    grad-accumulation path, which folds a microbatch dim in first).

    ``stop_check`` (polled every ``stop_poll_s`` while the consumer waits
    on the producer): returning True ends the stream EARLY — already
    staged batches still drain, then the generator finishes as if the
    epoch ended. fit() passes its preemption flag here: a SIGTERM landing
    while the input pipeline is STALLED (a wedged data source, realistic
    at exactly preemption time) must still reach the graceful
    emergency-checkpoint path instead of blocking in a timeout-less wait
    until the scheduler's SIGKILL.

    Three spans (docs/OBSERVABILITY.md §8; rows too with ``tracer``), each
    tagged with the batch's ordinal in this stream: ``input/produce`` on
    the producer thread (one ``next()`` of ``iterator``: gather + host
    transforms), ``input/wait`` (the consumer blocked on the producer) and
    ``input/stage`` (``stage_fn``) on the consumer's.
    """
    from tpudist.mesh import shard_batch
    from tpudist.telemetry.trace import span

    queue: collections.deque = collections.deque()
    host_q: collections.deque = collections.deque()
    lock = threading.Condition()
    DONE = object()
    abandoned = False  # set when the consumer drops the generator early

    def _producer():
        try:
            batches = iter(iterator)
            for n in itertools.count():
                with span("input/produce", tracer=tracer, batch=n) as s:
                    item = next(batches, DONE)
                    if item is DONE:
                        s.ends_stream()
                        return
                with lock:
                    while len(host_q) >= depth + 1 and not abandoned:
                        lock.wait()
                    if abandoned:
                        return
                    host_q.append(item)
                    lock.notify_all()
        except BaseException as e:  # surface loader errors to the consumer
            with lock:
                host_q.append(e)
                lock.notify_all()
        finally:
            with lock:
                host_q.append(DONE)
                lock.notify_all()

    thread = threading.Thread(target=_producer, daemon=True)
    thread.start()

    def _next_host():
        """Next host item, the producer's error object, or DONE. Producer
        errors are RETURNED (so the consumer can defer them behind staged
        batches); exceptions raised here — e.g. a KeyboardInterrupt during
        the wait — propagate immediately. With ``stop_check``, a stalled
        wait polls the flag and reports DONE on a stop — the producer
        thread is retired by the generator's finally."""
        with lock:
            while not host_q:
                if stop_check is not None and stop_check():
                    return DONE
                lock.wait(None if stop_check is None else stop_poll_s)
            item = host_q.popleft()
            lock.notify_all()
        return item

    if stage_fn is None:
        stage_fn = lambda b: shard_batch(b, mesh)

    try:
        finished = False
        pending_err: BaseException | None = None
        n = 0  # ordinal of the next batch to stage
        while True:
            while not finished and pending_err is None and len(queue) < depth:
                with span("input/wait", tracer=tracer, batch=n) as s:
                    item = _next_host()
                    if item is DONE:
                        s.ends_stream()
                if item is DONE:
                    finished = True
                elif isinstance(item, BaseException):
                    # deliver every batch staged BEFORE the loader died, then
                    # the error — the already-good work (e.g. a step that
                    # crosses a checkpoint boundary) isn't discarded with it.
                    # Only producer-delivered errors defer; a KeyboardInterrupt
                    # in THIS thread propagates from _next_host immediately.
                    pending_err = item
                else:
                    with span("input/stage", tracer=tracer, batch=n):
                        queue.append(stage_fn(item))
                    n += 1
            if queue:
                yield queue.popleft()
            elif pending_err is not None:
                raise pending_err
            else:
                return
    finally:
        # unblock and retire the producer if the consumer bailed mid-epoch
        with lock:
            abandoned = True
            host_q.clear()
            lock.notify_all()
