"""Multi-host bring-up and host-side collectives.

TPU-native replacement for the reference's L6 layer
(``dist.init_process_group(backend='nccl', init_method='env://')`` +
``torch.cuda.set_device``, /root/reference/main.py:34-37) and for the
out-of-graph ``reduce_loss`` helper (/root/reference/main.py:16-20).

The ``env://`` contract is preserved: the same environment variables the
reference's launcher sets (``MASTER_ADDR``, ``MASTER_PORT``, ``RANK``,
``WORLD_SIZE``) drive :func:`jax.distributed.initialize`, so the README's
multi-node launch recipes (/root/reference/README.md:17-35) translate 1:1 —
one tpudist process per TPU host instead of one per GPU.
"""

from __future__ import annotations

import dataclasses
import logging
import os

import jax
import numpy as np

from tpudist.utils.cache import place_compile_cache

logger = logging.getLogger(__name__)

_initialized = False


@dataclasses.dataclass(frozen=True)
class DistributedContext:
    """World description after bring-up.

    The reference's ``global_rank``/``world_size`` (/root/reference/main.py:36-37)
    count *GPU processes*; on TPU one process drives several chips, so both
    views are exposed:

    - ``process_index``/``process_count``: host-level (launcher) ranks.
    - ``global_rank``/``world_size``: replica-level — ``world_size`` is the
      total device count (the data-parallel degree, matching the reference's
      meaning of "number of workers"), ``global_rank`` is the first replica id
      owned by this process. Rank-0 logging guards (`main.py:107,113`) map to
      ``is_chief``.
    """

    process_index: int
    process_count: int
    global_rank: int
    world_size: int
    local_device_count: int
    coordinator: str | None

    @property
    def is_chief(self) -> bool:
        return self.process_index == 0


def init_from_env(*, allow_single_process: bool = True) -> DistributedContext:
    """Form the world from the ``env://`` contract.

    Reads ``MASTER_ADDR``/``MASTER_PORT`` (coordinator), ``RANK`` (process
    rank) and ``WORLD_SIZE`` (process count) — the exact variables
    ``torch.distributed.launch`` exports for the reference
    (/root/reference/README.md:28, SURVEY.md §2.2/§2.3). With
    ``WORLD_SIZE`` ≤ 1 or absent, runs single-process (all local devices).
    """
    global _initialized
    # persistent XLA compile cache: the first compile of a train step is
    # tens of seconds on TPU; restarts and checkpoint resumes skip it
    place_compile_cache()

    nproc = int(os.environ.get("WORLD_SIZE", "1"))
    rank = int(os.environ.get("RANK", "0"))
    if nproc > 1:
        addr = os.environ.get("MASTER_ADDR", "127.0.0.1")
        port = os.environ.get("MASTER_PORT", "29500")
        coordinator = f"{addr}:{port}"
        if not _initialized:
            # Rank 0 hosts the coordination service — the TCPStore analogue
            # (SURVEY.md §2.3): all processes rendezvous here, then XLA forms
            # the global device topology.
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=nproc,
                process_id=rank,
            )
            _initialized = True
    else:
        coordinator = None
        if not allow_single_process:
            raise RuntimeError("WORLD_SIZE>1 required")

    local = jax.local_device_count()
    ctx = DistributedContext(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        global_rank=jax.process_index() * local,
        world_size=jax.device_count(),
        local_device_count=local,
        coordinator=coordinator,
    )
    logger.info("tpudist world: %s", ctx)
    return ctx


def reduce_loss(value, ctx: DistributedContext | None = None) -> float:
    """Global mean of a per-process scalar — the reference's ``reduce_loss``
    (/root/reference/main.py:16-20: ``dist.reduce(dst=0)`` then ÷ world_size).

    Under pjit the in-graph loss is *already* the global-batch mean, so the
    common caller passes it straight through; this host-level path exists for
    out-of-graph scalars (e.g. per-host timing) and for parity with the
    reference's post-step reduce. Unlike the reference (whose non-dst ranks
    hold garbage after ``dist.reduce``), every process gets the mean.
    """
    value = float(np.asarray(value))
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.asarray(value, np.float32))
    return float(np.mean(gathered))


def verify_replicas(tree, *, atol: float = 0.0) -> None:
    """Assert every process holds identical values for ``tree`` — the
    TPU-native version of DDP's wrap-time parameter-consistency check
    (/root/reference/main.py:83 verifies ranks agree before training).

    Cheap: one float64 checksum per process is allgathered, not the params.
    Raises ``RuntimeError`` naming the divergent processes on mismatch.
    """
    if jax.process_count() == 1:
        return

    import jax.numpy as jnp

    # one jitted tree-sum (not a dispatch per leaf); works on sharded global
    # arrays — the reduction is compiled as a single program
    @jax.jit
    def _tree_checksum(t):
        leaves = [
            jnp.sum(jnp.asarray(x, jnp.float32))
            for x in jax.tree_util.tree_leaves(t)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
        ]
        return jnp.sum(jnp.stack(leaves)) if leaves else jnp.zeros(())

    checksum = float(_tree_checksum(tree))
    from jax.experimental import multihost_utils

    gathered = np.asarray(
        multihost_utils.process_allgather(np.asarray(checksum, np.float64))
    ).reshape(-1)
    bad = [i for i, v in enumerate(gathered) if abs(v - gathered[0]) > atol]
    if bad:
        raise RuntimeError(
            f"replica init-sync check failed: processes {bad} diverge from "
            f"process 0 (checksums {gathered.tolist()}); all processes must "
            "build the initial state from the same seed"
        )


def barrier(name: str = "barrier") -> None:
    """Cross-process barrier (used e.g. by the rank-0 dataset-download guard,
    fixing the reference's download race noted in SURVEY.md §5)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
