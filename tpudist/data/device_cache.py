"""Device-resident dataset cache: the input pipeline for staging-bound runs.

The reference stages every batch host→device inside the timed step
(/root/reference/main.py:98-99). That is fine when host decode and the
staging path keep up with the device; when they do not, the *pipeline*
becomes the benchmark.

The TPU-native fix (MLPerf-style) is to stop shipping pixels per step:

1. stage the WHOLE uint8 dataset to HBM **once, at bring-up** (it removes
   per-step pixel traffic entirely). CIFAR-100 is 150 MB — noise against
   16 GB HBM;
2. per step, ship only the sampler's **indices** (a few KB) and gather the
   batch in-graph (``jnp.take``), fused by XLA straight into the normalize
   + first-conv read.

The loader yields ``{input_key: indices, label_key: labels}`` and exposes
:meth:`input_transform` — the in-graph ``indices → normalized images``
function to pass to ``make_train_step(input_transform=...)`` /
``evaluate(input_transform=...)``. The per-epoch shuffle is the SAME
``DistributedSampler`` order as the host loaders (seed+epoch permutation),
so switching loaders does not change the data order.

Multi-process: every process stages the full replicated cache (one
pre-compile H2D each) and ships its own rank's index shard per step; the
gather stays collective-free because the cache is replicated.
"""

from __future__ import annotations

import functools
import logging
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from tpudist import mesh as mesh_lib
from tpudist.data.sampler import DistributedSampler

logger = logging.getLogger(__name__)

# the slice budget of every staging path: no single transfer (and no
# pinned host copy of one) is larger than this many bytes. Module-level so
# the regression tests can tighten it and prove the multi-process rotation
# path really chunks.
_CHUNK_BYTES = 64 * 1024 * 1024


def _chunked_device_put(
    images: np.ndarray, sharding, *, in_place: bool = False
) -> jax.Array:
    """One H2D of a large array in ~64 MB slices — bounded transfers,
    at no cost on a local DMA path. Two assembly modes, each matched to
    WHEN it runs:

    - default (``in_place=False``): all slices transfer FIRST, then one
      ``concatenate`` compiles/executes. Transient device footprint is 2×
      the array, and no jitted write is interleaved with the transfers —
      the DeviceCachedLoader constructor's contract.
    - ``in_place=True``: each slice is written into a DONATED device
      buffer (``dynamic_update_slice``), high-water mark ONE buffer plus
      one slice. For mid-training staging (RotatingDeviceCache), where
      compiled programs have already run — the link is whatever it is —
      and shard-sized HBM headroom is the scarce resource."""
    row_bytes = max(images[:1].nbytes, 1)
    rows_per_chunk = max(_CHUNK_BYTES // row_bytes, 1)
    n = images.shape[0]
    if n <= rows_per_chunk:
        return jax.device_put(images, sharding)
    if not in_place:
        pieces = [
            jax.device_put(images[lo: lo + rows_per_chunk], sharding)
            for lo in range(0, n, rows_per_chunk)
        ]
        # enforce the documented order: device_put is async, so without
        # this the concatenate (the process's first compiled program)
        # would dispatch while slices are still streaming on the
        # pre-compile link
        jax.block_until_ready(pieces)
        return jnp.concatenate(pieces, axis=0)
    init, write = _assembly_fns(images.shape, images.dtype.str, sharding)
    buf = init()
    for lo in range(0, n, rows_per_chunk):
        piece = jax.device_put(images[lo: lo + rows_per_chunk], sharding)
        buf = write(buf, piece, lo)
    return buf


def _chunked_replicated_put(x: np.ndarray, sharding) -> jax.Array:
    """Multi-process-safe chunked staging of a REPLICATED value.

    ``put_sharded``'s multi-process path
    (``make_array_from_process_local_data``) issues ONE full-shard
    ``device_put`` per device — for a GB-scale rotation shard that is
    exactly the single hundreds-of-MB transfer the ~64 MB
    ``_chunked_device_put`` bound exists to prevent. This constructor
    keeps BOTH disciplines at once:

    - **chunked**: per addressable device, the full value is assembled in
      ~64 MB slices into a donated single-device buffer
      (``_chunked_device_put(..., in_place=True)`` under a
      ``SingleDeviceSharding``);
    - **local-only** (the 2-process-deadlock fix, see ``_stage``): every
      operation here is either a transfer or a single-device,
      collective-free compiled program — nothing lockstep, so per-process
      issue orders may diverge freely while the main thread runs
      collective train steps. The final
      ``make_array_from_single_device_arrays`` is metadata-only.
    """
    from jax.sharding import SingleDeviceSharding

    bufs = [
        _chunked_device_put(x, SingleDeviceSharding(d), in_place=True)
        for d in sorted(sharding.addressable_devices, key=lambda d: d.id)
    ]
    return jax.make_array_from_single_device_arrays(x.shape, sharding, bufs)


@functools.lru_cache(maxsize=64)  # 8 local devices x a few shard shapes
def _assembly_fns(shape: tuple, dtype_str: str, sharding):
    """Jitted (zeros-init, donated-write) pair for in-place assembly,
    cached per (shape, dtype, sharding): jit's executable cache keys on
    the function object, so fresh lambdas per shard would re-compile the
    same two programs on every rotation (measured: 2 compiles per call)."""
    dtype = np.dtype(dtype_str)
    init = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)
    write = jax.jit(
        lambda b, piece, lo: jax.lax.dynamic_update_slice(
            b, piece, (lo,) + (0,) * (b.ndim - 1)
        ),
        donate_argnums=0,
        out_shardings=sharding,
    )
    return init, write


class DeviceCachedLoader:
    """Iterable of index batches over an HBM-cached dataset.

    Parameters
    ----------
    dataset: mapping with the image array (any dtype; uint8 recommended —
        4× smaller to stage) and per-row labels.
    batch_size: per-process batch (rows this process contributes per step).
    mesh: the device mesh the cache is replicated over.
    sampler: optional pre-built DistributedSampler (defaults to a
        shuffle-on sampler over this process's rank).
    drop_remainder: drop the ragged tail (training default True).
    stage_in_place: assemble the cache with the 1×-transient donated-buffer
        mode instead of the default transfer-all-then-concatenate (which
        transiently holds 2× the array). Turn on for datasets near HBM
        capacity (see ``_chunked_device_put``).
    """

    def __init__(
        self,
        dataset: Mapping[str, np.ndarray],
        batch_size: int,
        *,
        mesh=None,
        sampler: DistributedSampler | None = None,
        input_key: str = "image",
        label_key: str = "label",
        drop_remainder: bool = True,
        seed: int = 0,
        stage_in_place: bool = False,
    ):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh()
        self.batch_size = batch_size
        self.input_key = input_key
        self.label_key = label_key
        self.drop_remainder = drop_remainder
        images = np.ascontiguousarray(dataset[input_key])
        n = images.shape[0]
        self.sampler = sampler or DistributedSampler(
            n,
            num_replicas=jax.process_count(),
            rank=jax.process_index(),
            seed=seed,
        )
        # labels stay host-side: they ride each index batch (a few KB) so the
        # loss path needs no second gather
        self._labels = np.ascontiguousarray(dataset[label_key])
        # ONE H2D of the full set, replicated over the mesh. Done eagerly at
        # construction — build the loader BEFORE the first compiled program
        # (e.g. before create_train_state). Chunked via
        # _chunked_device_put (bounded transfers).
        self._cache = _chunked_device_put(
            images, mesh_lib.replicated_sharding(self.mesh),
            in_place=stage_in_place,
        )
        self._img_shape = images.shape[1:]

    def __len__(self) -> int:
        n = self.sampler.num_samples
        if self.drop_remainder:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def probe(self) -> dict:
        """Shape/dtype probe for fit()'s init. Returns an IMAGE-shaped f32
        row (not an index row): fit derives the model's init input from the
        probe, and the model sees post-gather images — float32 so init never
        feeds raw integer pixels to a float conv."""
        return {
            self.input_key: np.zeros((1, *self._img_shape), np.float32),
            self.label_key: self._labels[:1],
        }

    def input_transform(self, post=None):
        """The in-graph ``indices → images`` gather to pass as
        ``make_train_step(input_transform=...)``; ``post`` (e.g.
        :func:`tpudist.data.transforms.device_normalize`, or a
        ``device_compose`` chain with in-graph augmentation) is applied to
        the gathered batch inside the same program. A ``post`` declaring
        ``wants_step`` propagates: the composite receives the step counter
        and hands it through (the augmentation-randomness contract).

        The cache array reaches the compiled program as a REAL argument —
        every batch this loader yields carries it under ``"_cache"`` and the
        transform declares ``wants_batch`` (the make_train_step/evaluate
        contract). Capturing it in the closure instead would lower the
        whole dataset as an HLO literal: a bloated compile and a
        duplicated copy in device memory."""
        post_wants_step = getattr(post, "wants_step", False)

        def run(indices, batch, step=None):
            gathered = jnp.take(batch["_cache"], indices, axis=0)
            if post is None:
                return gathered
            return post(gathered, step) if post_wants_step else post(gathered)

        run.wants_batch = True
        run.wants_step = post_wants_step
        return run

    def _index_batches(self):
        order = self.sampler.epoch_indices()
        n = len(order)
        end = n - n % self.batch_size if self.drop_remainder else n
        for lo in range(0, end, self.batch_size):
            yield order[lo : lo + self.batch_size]

    def iter_from(self, start_batch: int):
        for i, idx in enumerate(self._index_batches()):
            if i < start_batch:
                continue
            yield self._make_batch(idx)

    def _make_batch(self, idx: np.ndarray) -> dict:
        return {
            self.input_key: np.ascontiguousarray(idx.astype(np.int32)),
            self.label_key: np.ascontiguousarray(self._labels[idx]),
            # the HBM cache rides along as a device array (stage() and
            # _padded_batches pass jax.Arrays through) so the in-graph
            # gather sees it as a jit argument, not a baked-in literal
            "_cache": self._cache,
        }

    def __iter__(self):
        for idx in self._index_batches():
            yield self._make_batch(idx)


class RotatingDeviceCache:
    """Device cache for datasets LARGER than HBM: the set is split into
    row-shards, and while the step consumes shard ``k`` from HBM, shard
    ``k+1`` stages in the background (host memmap read + chunked H2D on a
    staging thread), so the per-step path stays index-only. HBM residency:
    two shards held by the loader, and the consumer's in-flight batch can
    transiently pin a third around a shard transition — size
    ``shard_rows`` for at most THREE shard buffers against free HBM.

    This is the streaming complement to :class:`DeviceCachedLoader`:
    a packed ImageNet-1k at 224² is ~193 GB against
    16 GB HBM, but a 2–4 GB shard stages while the chip
    trains through the previous one (shard of R rows buys
    ``R/rate`` seconds of compute against ``R·row_bytes/bandwidth``
    seconds of transfer: a link faster than ``rate·row_bytes``
    keeps the rotation ahead, the same requirement as direct
    streaming, but paid OFF the critical path and with in-graph
    gather/augment/normalize like the resident cache).

    Shuffle semantics, stated plainly: rotation trades the sampler's
    GLOBAL per-epoch permutation for the standard windowed approximation —
    shard ORDER is permuted per epoch and rows shuffle WITHIN the resident
    shard (window = shard_rows, vastly larger than typical shuffle-buffer
    windows). Coverage: when ``shard_rows`` divides the dataset, every row
    is visited exactly once per epoch; otherwise the ragged TAIL shard is
    dropped (static shapes — the compiled program sees one
    ``[shard_rows, ...]`` cache operand), so up to ``shard_rows - 1``
    rows sit out each epoch. The dropped rows are a fresh random subset
    per epoch (the (seed, epoch)-keyed permutation runs before sharding),
    so over a run every row still trains — the same expectation-level
    coverage as shuffle-buffer pipelines; a warning is logged at
    construction when the tail exists. The (seed, epoch) keying keeps the
    plan deterministic and resumable. Recipes that need the exact global
    permutation use the host loaders or the fully-resident cache.

    Works straight off a :func:`tpudist.data.packed.load_packed` memmap:
    each shard's rows are materialized host-side only transiently for the
    H2D copy.

    Multi-process: the (seed, epoch) plan is global and identical on every
    process, each process stages the SAME shard pixels (the cache operand
    is replicated, like :class:`DeviceCachedLoader`'s), and per batch each
    process contributes its rank's stride of the global within-shard
    order — the DistributedSampler disjointness contract at the batch
    level. Staging OVERLAP is single-process only: multi-process runs
    stage inline at shard boundaries (no extra device-work-issuing
    thread — the measured deadlock and the threading-shape reasoning are
    in ``_iter_impl``).
    """

    def __init__(
        self,
        dataset: Mapping[str, np.ndarray],
        batch_size: int,
        *,
        shard_rows: int,
        mesh=None,
        input_key: str = "image",
        label_key: str = "label",
        seed: int = 0,
        rank: int | None = None,
        num_replicas: int | None = None,
    ):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh()
        self.batch_size = batch_size  # per-process rows per step
        self.input_key = input_key
        self.label_key = label_key
        self.seed = seed
        self._images = dataset[input_key]  # memmap-friendly: sliced per shard
        self._labels = np.ascontiguousarray(dataset[label_key])
        self._n = self._images.shape[0]
        self._rank = rank if rank is not None else jax.process_index()
        self._world = (
            num_replicas if num_replicas is not None else jax.process_count()
        )
        self._global_batch = batch_size * self._world
        shard_rows = min(shard_rows, self._n)
        if shard_rows % self._global_batch:
            raise ValueError(
                f"shard_rows {shard_rows} must divide by the global batch "
                f"{self._global_batch} (a batch never spans two resident "
                "shards)"
            )
        self.shard_rows = shard_rows
        if self._n % shard_rows:
            logger.warning(
                "RotatingDeviceCache: dataset rows (%d) are not a multiple "
                "of shard_rows (%d); the ragged tail shard is dropped, so "
                "%d randomly-chosen rows (a fresh subset per epoch) sit "
                "out each epoch", self._n, shard_rows, self._n % shard_rows,
            )
        self.epoch = 0
        # fit() drives per-epoch reshuffle via loader.sampler.set_epoch();
        # the rotation owns its epoch keying, so it is its own "sampler"
        self.sampler = self
        self._sharding = mesh_lib.replicated_sharding(self.mesh)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        # whole shards only, ragged tail shard dropped (static shapes: the
        # compiled program sees ONE [shard_rows, ...] cache operand)
        return (self._n // self.shard_rows) * (
            self.shard_rows // self._global_batch
        )

    def probe(self) -> dict:
        return {
            self.input_key: np.zeros(
                (1, *self._images.shape[1:]), np.float32
            ),
            self.label_key: self._labels[:1],
        }

    # same in-graph contract as DeviceCachedLoader (the "_cache" operand)
    input_transform = DeviceCachedLoader.input_transform

    def _epoch_plan(self):
        """(shards, orders): global row ids per shard (sorted — sequential
        memmap reads) and the within-shard shuffle, identical on every
        process by (seed, epoch) construction."""
        rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([self.seed, self.epoch])
        ))
        order = rng.permutation(self._n)
        n_shards = self._n // self.shard_rows
        shards = [
            np.sort(order[s * self.shard_rows:(s + 1) * self.shard_rows])
            for s in range(n_shards)
        ]
        orders = [rng.permutation(self.shard_rows) for _ in range(n_shards)]
        return shards, orders

    def _stage(self, shard_global_rows: np.ndarray):
        """Gather one shard's pixels from the (mem-mapped) source and put
        them on device (single-process: chunked, in-place-assembled —
        transport-hang guard + HBM high-water discipline; runs on the
        dedicated staging thread, both the host read and the H2D off the
        critical path).

        Multi-process: LOCAL-ONLY construction, now ALSO chunked —
        ``_chunked_replicated_put`` assembles the replicated shard
        per-device in ~64 MB slices (every process holds the identical
        full value, so assembly needs no cross-process transfer, and the
        slicing keeps the documented transport-hang guard that a single
        full-shard ``device_put`` per device — the old ``put_sharded``
        route — bypassed). A raw cross-process ``device_put`` of the
        replicated shard is a lockstep operation, and one issued off the
        main thread raced the step loop's collectives into a reproducible
        2-process deadlock (both ranks asleep; the host loaders never
        deadlock precisely because their staging is this same local-only
        constructor)."""
        pixels = np.ascontiguousarray(self._images[shard_global_rows])
        if jax.process_count() > 1:
            cache = _chunked_replicated_put(pixels, self._sharding)
        else:
            cache = _chunked_device_put(pixels, self._sharding, in_place=True)
        return cache, self._labels[shard_global_rows]

    def iter_from(self, start_batch: int):
        """Mid-epoch resume at the batch level (shards before the target
        batch are skipped without staging)."""
        per_shard = self.shard_rows // self._global_batch
        first_shard = start_batch // per_shard
        skip = start_batch - first_shard * per_shard
        for i, batch in enumerate(self._iter_impl(first_shard)):
            if i >= skip:
                yield batch

    def __iter__(self):
        return self._iter_impl(0)

    def _stage_async(self, shard_global_rows: np.ndarray):
        """Run :meth:`_stage` on a DAEMON thread (a ThreadPoolExecutor's
        non-daemon worker would be joined at interpreter exit — a stage
        in flight on a hung transfer would then hang process shutdown
        instead of letting the original error kill the run); returns a
        one-slot queue carrying (ok, value_or_exception)."""
        import queue
        import threading

        out: queue.Queue = queue.Queue(1)

        def work():
            try:
                out.put((True, self._stage(shard_global_rows)))
            except BaseException as e:  # surfaced at .get() in the iterator
                out.put((False, e))

        threading.Thread(target=work, daemon=True).start()
        return out

    @staticmethod
    def _resolve(pending):
        ok, value = pending.get()
        if not ok:
            raise value
        return value

    def _iter_impl(self, start_shard: int):
        shards, orders = self._epoch_plan()
        shards, orders = shards[start_shard:], orders[start_shard:]
        if not shards:
            return
        # Single-process: dedicated staging thread — the next shard's
        # memmap gather AND its H2D both run there, overlapping the whole
        # current shard's stepping. Multi-process: stage INLINE in this
        # iterator (no extra thread). Measured hazard, not theory: with
        # the staging thread, a 2-process XLA:CPU world deadlocked
        # reproducibly (both ranks asleep after compile) — three
        # concurrent device-work issuers per process (staging thread's
        # puts, the prefetch producer thread that drives this iterator
        # under fit(), and the main thread's compiled steps whose
        # collectives run in lockstep) let per-process orders diverge.
        # Staging inline collapses rotation to the exact threading shape
        # of the host-loader path — ONE producer thread issuing transfers
        # plus the main thread issuing programs — which multi-process
        # worlds demonstrably sustain (tests/test_multiproc_fit.py, and
        # tests/test_multiproc_rotation.py drives THIS path through
        # fit()+prefetch end-to-end). The cost is a staging stall per
        # shard boundary; the per-step path stays index-only either way.
        overlap = jax.process_count() == 1
        pending = self._stage_async(shards[0]) if overlap else None
        for s in range(len(shards)):
            if overlap:
                cache, labels = self._resolve(pending)
                if s + 1 < len(shards):
                    pending = self._stage_async(shards[s + 1])
            else:
                cache, labels = self._stage(shards[s])
            order = orders[s]
            for lo in range(0, self.shard_rows, self._global_batch):
                window = order[lo:lo + self._global_batch]
                # this process's stride of the global batch (disjoint
                # across ranks, union = the window)
                idx = window[self._rank::self._world]
                yield {
                    self.input_key: np.ascontiguousarray(
                        idx.astype(np.int32)
                    ),
                    self.label_key: np.ascontiguousarray(labels[idx]),
                    "_cache": cache,
                }
