"""Paged KV cache: a shared block pool with per-slot block tables.

The contiguous slot pool (:mod:`tpudist.serve.slots`) reserves a
worst-case ``[max_slots, H, max_seq_len, dh]`` cache — every slot pays
``max_seq_len`` whether its request is 20 tokens or 400. Under the
long-tail budgets real chat traffic has (say 16+Exp(80) tokens), most of
the bytes each decode step's attention window
COULD cover are never written, yet they bound how many requests fit a
chip. This module replaces that layout with the vLLM-style paged one,
grounded in the Gemma-on-TPU serving comparison (PAPERS.md,
arxiv 2605.25645):

- **one pool per layer** — ``[n_blocks, H_kv, block_size, dh]``
  (:func:`paged_cache` builds the tree by re-shaping the model's
  contiguous ``init_cache`` leaves, so the flax cache collection's
  structure is untouched and no model init path is needed);
- **per-slot block tables** — host-side ``[max_slots, max_blocks]`` maps
  from logical block index to physical pool block, fed to the compiled
  decode step each tick (``tpudist.ops.decode.cached_kv(block_tables=)``);
  a slot allocates its next block only when its cursor crosses a block
  boundary, so HBM holds **Σ(actual lengths)** rounded up to the block
  and the engine admits far more concurrent requests per chip;
- **refcounted blocks + prefix cache** — physical blocks are refcounted
  (:class:`BlockPool`); completed prompt-prefix blocks are content-hashed
  by their token ids (:class:`PrefixCache`) so N requests sharing a
  system prompt map the SAME physical blocks and pay prefill once. The
  divergence point is block-granular copy-on-write by construction: only
  FULL blocks whose tokens match exactly are shared, a shared block is
  never written again (decode writes always land in the slot's private
  suffix), and the first divergent/partial block is private from the
  start — so there is no write-fault machinery to get wrong.

Physical block 0 is a reserved GARBAGE block, never allocated: inactive
decode rows carry all-zero tables and positions, so their masked
ride-along writes land in block 0 where no live table ever points.

Lifecycle invariants (pinned by the refcount torture test):

- ``refcount[b] == (#live slot tables containing b) + (1 if the prefix
  cache holds b)``;
- a block returns to the free list exactly when its refcount hits 0 —
  releasing a slot cannot free a block the prefix cache (or another
  slot, via a shared prefix) still holds;
- prefix-cache entries form hash CHAINS (entry i's hash folds entry
  i-1's); eviction only takes LRU **leaves** whose block no slot maps,
  so a cached chain is never broken in the middle (a mid-chain hole
  would orphan its descendants' refcounts forever).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


#: reserved garbage block: inactive rows' tables point here; never allocated
GARBAGE_BLOCK = 0


def paged_cache(model, n_blocks: int, block_size: int, kv_sharding=None):
    """The device-side block pool: the model's contiguous decode-cache
    tree (``init_cache`` shapes at batch 1) with every 4-D
    ``[1, H, max_len, dh]`` K/V leaf re-shaped to
    ``[n_blocks, H, block_size, dh]``. Scalar cursor leaves keep their
    (unused in paged mode, but structure-preserving) zeros — the same
    tree-structure discipline that lets one donated pytree flow through
    the compiled decode step.

    ``kv_sharding``: optional :class:`jax.sharding.NamedSharding` for the
    4-D pool leaves — the multi-chip engine shards the pool on the
    KV-head dim (``[n_blocks, H_kv/T, block_size, dh]`` per chip,
    ``P(None, 'tensor', None, None)``); scalar leaves stay replicated.
    Host block tables are NOT affected — all chips see the same logical
    pool, each holding its own head slice of every block."""
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32),
            train=False, decode=True,
        )
    )["cache"]

    rep = None
    if kv_sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(kv_sharding.mesh, PartitionSpec())

    def build(leaf):
        if len(leaf.shape) == 4:
            buf = jnp.zeros(
                (n_blocks, leaf.shape[1], block_size, leaf.shape[3]),
                leaf.dtype,
            )
            return buf if kv_sharding is None else jax.device_put(
                buf, kv_sharding
            )
        buf = jnp.zeros(leaf.shape, leaf.dtype)
        # scalar cursors commit replicated on the same mesh — a leaf left
        # on one device would make the decode step's AOT lowering mix
        # device sets
        return buf if rep is None else jax.device_put(buf, rep)

    return jax.tree_util.tree_map(build, shapes)


@partial(jax.jit, donate_argnums=(0,), static_argnames=("block_size",))
def scatter_blocks(pool, row_cache, table, start, end, *, block_size):
    """Scatter a contiguous batch-1 prefill cache's K/V into the pool
    blocks ``table[start:end]`` (each block ``j`` takes rows
    ``[j·bs, (j+1)·bs)`` of the row cache). ``start``/``end`` are traced
    scalars — ONE compiled program serves every (hit length, prompt
    length) pair. The pool is donated (in-place per-block
    dynamic_update_slices); blocks outside ``[start, end)`` — shared
    prefix-cache hits in particular — are never touched."""
    start = jnp.asarray(start, jnp.int32)
    end = jnp.asarray(end, jnp.int32)
    table = jnp.asarray(table, jnp.int32)

    def per_leaf(p, row):
        if getattr(row, "ndim", 0) != 4 or p.ndim != 4:
            return p

        def body(j, acc):
            src = jax.lax.dynamic_slice(
                row, (0, 0, j * block_size, 0),
                (1, row.shape[1], block_size, row.shape[3]),
            )
            return jax.lax.dynamic_update_slice(
                acc, src.astype(acc.dtype), (table[j], 0, 0, 0)
            )

        return jax.lax.fori_loop(start, end, body, p)

    return jax.tree_util.tree_map(per_leaf, pool, row_cache)


@jax.jit
def gather_prefix(pool, table):
    """The inverse view for prefix-cache hits: assemble a contiguous
    batch-1 cache tree from the pool blocks ``table`` maps (one gather
    per layer, fixed shape — one compile). Blocks past the hit length map
    the garbage block; their bytes sit above the prefill cursor where the
    causal mask never admits them, so no zeroing is needed. The caller
    (the engine's admission path) resumes chunked prefill on the result
    at the hit length, paying the model forward only for the suffix."""
    table = jnp.asarray(table, jnp.int32)

    def per_leaf(p):
        if p.ndim != 4:
            return jnp.zeros(p.shape, p.dtype)
        mb = table.shape[0]
        g = p[table]  # [mb, H, bs, dh]
        return g.transpose(1, 0, 2, 3).reshape(
            1, p.shape[1], mb * p.shape[2], p.shape[3]
        )

    return jax.tree_util.tree_map(per_leaf, pool)


class BlockPool:
    """Host-side physical-block accounting: a free list plus per-block
    refcounts. Pure bookkeeping — the device tree lives with
    :class:`PagedSlotPool`. Block 0 (:data:`GARBAGE_BLOCK`) is reserved."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(
                f"n_blocks must be >= 2 (one is the garbage block), got "
                f"{n_blocks}"
            )
        self.n_blocks = n_blocks
        self.refcount = np.zeros(n_blocks, np.int32)
        self._free: collections.deque[int] = collections.deque(
            range(1, n_blocks)
        )

    @property
    def n_usable(self) -> int:
        return self.n_blocks - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_usable - self.n_free

    @property
    def occupancy(self) -> float:
        return self.n_used / self.n_usable

    def alloc(self) -> int | None:
        """Take a free block (refcount 1) or ``None`` when the pool is
        dry — the caller then evicts/preempts; allocation itself never
        raises so admission control can probe."""
        if not self._free:
            return None
        b = self._free.popleft()
        self.refcount[b] = 1
        return b

    def incref(self, block: int) -> None:
        if block == GARBAGE_BLOCK or self.refcount[block] <= 0:
            raise RuntimeError(f"incref of unallocated block {block}")
        self.refcount[block] += 1

    def decref(self, block: int) -> None:
        """Drop one reference; the block returns to the free list exactly
        at refcount 0 (a double-free raises — the torture test's bar)."""
        if block == GARBAGE_BLOCK or self.refcount[block] <= 0:
            raise RuntimeError(f"decref of free block {block} (double free)")
        self.refcount[block] -= 1
        if self.refcount[block] == 0:
            self._free.append(block)


@dataclasses.dataclass
class _PrefixEntry:
    block: int
    parent: bytes | None
    children: int
    last_use: int


class PrefixCache:
    """Content-addressed prompt-prefix blocks: chain hash → physical
    block. Entry ``i``'s key folds entry ``i-1``'s digest with block
    ``i``'s token bytes, so a lookup walks the prompt's full blocks until
    the first miss — a hit can only be an exact token-prefix match.

    The cache holds ONE pool reference per entry; slots sharing the block
    hold their own. Eviction (:meth:`evict`) frees LRU chain LEAVES whose
    block no slot maps (pool refcount == 1), never mid-chain blocks.
    Hit/lookup accounting lives with :class:`ServeStats` (the engine
    reports per COMMITTED admission — one home for the hit rate)."""

    def __init__(self, pool: BlockPool, block_size: int):
        self.pool = pool
        self.block_size = block_size
        self._entries: dict[bytes, _PrefixEntry] = {}
        self._tick = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _chain(self, tokens: np.ndarray) -> list[bytes]:
        """Chain digests for every FULL block of ``tokens``."""
        bs = self.block_size
        digests, prev = [], b""
        for j in range(len(tokens) // bs):
            h = hashlib.blake2b(digest_size=16)
            h.update(prev)
            h.update(np.ascontiguousarray(
                tokens[j * bs:(j + 1) * bs], np.int32).tobytes())
            prev = h.digest()
            digests.append(prev)
        return digests

    def lookup(self, tokens: np.ndarray, max_tokens: int) -> list[int]:
        """Physical blocks of the longest cached full-block prefix of
        ``tokens``, capped at ``max_tokens`` (the engine caps at
        ``len(prompt) - 1``: the last prompt token must always re-run so
        prefill yields its logits). Touches matched entries' LRU
        clocks."""
        self._tick += 1
        usable = tokens[: max(int(max_tokens), 0)]
        hits: list[int] = []
        chain = self._chain(np.asarray(usable))
        for digest in chain:
            e = self._entries.get(digest)
            if e is None:
                break
            e.last_use = self._tick
            hits.append(e.block)
        return hits

    def insert(self, tokens: np.ndarray, blocks, n_known: int) -> None:
        """Register the full blocks of ``tokens`` beyond the first
        ``n_known`` (the lookup's hits, already cached) under their chain
        hashes, taking one pool reference each. ``blocks[j]`` is the
        slot's physical block for logical block ``j`` — freshly written by
        the prefill scatter and never written again (decode appends past
        the prompt), which is what makes sharing them safe."""
        self._tick += 1
        chain = self._chain(np.asarray(tokens))
        for j in range(n_known, len(chain)):
            digest = chain[j]
            if digest in self._entries:
                # already cached by a racing admission this drain — the
                # slot keeps its private copy; no second cache ref
                continue
            parent = chain[j - 1] if j else None
            self.pool.incref(int(blocks[j]))
            self._entries[digest] = _PrefixEntry(
                int(blocks[j]), parent, 0, self._tick
            )
            if parent is not None and parent in self._entries:
                self._entries[parent].children += 1

    def evict(self, need: int) -> int:
        """Free up to ``need`` blocks by dropping LRU leaf entries whose
        block only the cache still references; returns how many were
        freed. Dropping a leaf may expose its parent as the next leaf —
        the loop walks chains tail-first, never breaking one mid-chain."""
        freed = 0
        while freed < need:
            best = None
            for digest, e in self._entries.items():
                if e.children:
                    continue
                if self.pool.refcount[e.block] != 1:
                    continue  # a live slot still maps it
                if best is None or e.last_use < self._entries[best].last_use:
                    best = digest
            if best is None:
                return freed
            e = self._entries.pop(best)
            if e.parent is not None and e.parent in self._entries:
                self._entries[e.parent].children -= 1
            self.pool.decref(e.block)
            freed += 1
        return freed


class PagedSlotPool:
    """The paged counterpart of :class:`tpudist.serve.slots.SlotPool`:
    same slot bookkeeping surface (``positions``/``active``/``n_active``/
    ``n_free``/``advance``/``release``), but ``cache`` is the shared
    block pool and each slot owns a block TABLE instead of a contiguous
    row. The engine feeds ``tables[:, :]`` to the compiled decode step
    alongside the per-slot cursors.

    ``utilization`` reports BLOCK-pool occupancy, not active/max_slots:
    under block-budget admission the slot count no longer measures free
    capacity (16 slots can be "free" while the pool is byte-full, and
    vice versa) — the contiguous :class:`SlotPool`'s slot-count property
    would overstate it. The engine's ``serve`` rows keep the old
    ``slot_utilization`` field with its old slot-count meaning and add
    ``pool_occupancy`` for this number (docs/OBSERVABILITY.md §1).
    """

    def __init__(self, model, max_slots: int, *, n_blocks: int,
                 block_size: int, prefix_cache: bool = True,
                 kv_sharding=None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if not hasattr(model, "init_cache"):
            raise ValueError(
                f"{type(model).__name__} has no init_cache hook (the decode "
                "contract tpudist.serve requires); GPT-2 and Llama carry it"
            )
        if block_size < 1 or model.max_seq_len % block_size:
            raise ValueError(
                f"block_size {block_size} must divide max_seq_len "
                f"{model.max_seq_len} (tables and the prefill scatter "
                "assume whole blocks)"
            )
        self.max_slots = max_slots
        self.max_seq_len = model.max_seq_len
        self.block_size = block_size
        self.max_blocks = model.max_seq_len // block_size
        self.blocks = BlockPool(n_blocks)
        self.prefix = (
            PrefixCache(self.blocks, block_size) if prefix_cache else None
        )
        self.cache = paged_cache(model, n_blocks, block_size, kv_sharding)
        self.tables = np.zeros((max_slots, self.max_blocks), np.int32)
        self.fill = np.zeros(max_slots, np.int32)  # table entries in use
        self.positions = np.zeros(max_slots, np.int32)
        self.active = np.zeros(max_slots, bool)
        self._free: collections.deque[int] = collections.deque(
            range(max_slots)
        )

    # -- capacity ----------------------------------------------------------

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def utilization(self) -> float:
        """BLOCK occupancy (the byte truth), NOT active/max_slots — see
        the class docstring for why the slot-count reading is wrong under
        paged admission.

        On a tensor-sharded engine (``ServeEngine(mesh=...)``) this is a
        PER-CHIP reading: the pool shards on the KV-head dim, so every
        chip maps the same block set (one host-side ``BlockPool``, one
        table) and occupancy is identical on all T chips — the fraction
        reported here is of each chip's ``n_blocks × bytes/T`` slice, not
        of the aggregate. The ``serve`` rows label it with
        ``tensor_world`` so readers can do the aggregate math
        (docs/OBSERVABILITY.md §1)."""
        return self.blocks.occupancy

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)

    def free_after_evict(self) -> int:
        """Blocks available to a new allocation if every evictable
        prefix-cache leaf were dropped — what admission budgets against."""
        free = self.blocks.n_free
        if self.prefix is None:
            return free
        # every cache-only block (refcount 1) is transitively evictable: a
        # slot mapping a chain's block necessarily maps its whole prefix
        # (its table holds the consecutive blocks), so refcount 1 on any
        # entry implies refcount 1 on all its descendants — the eviction
        # loop reaches them leaves-first
        return free + sum(
            1 for e in self.prefix._entries.values()
            if self.blocks.refcount[e.block] == 1
        )

    # -- slot lifecycle ----------------------------------------------------

    def insert(self, row_cache, true_len: int, *, prompt=None,
               hit_blocks=()) -> int:
        """Admit a prefilled request: take a slot, map ``hit_blocks``
        (shared prefix, one ref each), allocate private blocks for the
        rest of ``true_len`` tokens, scatter the row cache's K/V into the
        PRIVATE blocks only, and (when a prompt is given and the prefix
        cache is on) register the prompt's full blocks for future
        sharing. The caller verified the block budget; an allocation
        failure here is an admission bug and raises."""
        if not self._free:
            raise RuntimeError("slot pool exhausted (admission bug)")
        if not 0 < true_len <= self.max_seq_len:
            raise ValueError(
                f"prefix length {true_len} outside (0, {self.max_seq_len}]"
            )
        n_hit = len(hit_blocks)
        n_need = self.blocks_for(true_len)
        if n_hit > n_need:
            raise ValueError(f"hit blocks {n_hit} exceed prefix {true_len}")
        slot = self._free.popleft()
        table = np.zeros(self.max_blocks, np.int32)
        for j, b in enumerate(hit_blocks):
            self.blocks.incref(int(b))
            table[j] = int(b)
        for j in range(n_hit, n_need):
            b = self.blocks.alloc()
            if b is None:  # roll back to stay leak-free before raising
                for jj in range(j):
                    self.blocks.decref(int(table[jj]))
                self._free.appendleft(slot)
                raise RuntimeError(
                    "block pool exhausted mid-insert (admission bug)"
                )
            table[j] = b
        if n_need > n_hit:
            self.cache = scatter_blocks(
                self.cache, row_cache, jnp.asarray(table),
                n_hit, n_need, block_size=self.block_size,
            )
        self.tables[slot] = table
        self.fill[slot] = n_need
        self.positions[slot] = true_len
        self.active[slot] = True
        if self.prefix is not None and prompt is not None:
            self.prefix.insert(prompt, table, n_hit)
        return slot

    def needs_block(self, slot: int) -> bool:
        """True when the slot's next write position falls past its mapped
        blocks — the engine must ``ensure_next`` (or preempt) before
        dispatching this slot."""
        return int(self.positions[slot]) // self.block_size >= int(
            self.fill[slot]
        )

    def ensure_next(self, slot: int) -> bool:
        """Map the slot's next block; ``False`` when the pool is dry (the
        engine then evicts prefix leaves or preempts a victim)."""
        if not self.needs_block(slot):
            return True
        b = self.blocks.alloc()
        if b is None:
            return False
        self.tables[slot, self.fill[slot]] = b
        self.fill[slot] += 1
        return True

    def ensure_to(self, slot: int, n_tokens: int) -> bool:
        """Map blocks until the slot's table covers ``n_tokens`` positions
        (clamped to the table's extent); ``False`` when the pool runs dry
        mid-way (already-mapped blocks stay mapped — the engine escalates
        and retries). The SPECULATIVE dispatch path: a verify sweep writes
        up to ``spec_k + 1`` tokens past a cursor the host only knows one
        fetch late, so the engine maps the whole conservative window at
        once instead of one ``ensure_next`` per emitted token."""
        need = min(self.blocks_for(n_tokens), self.max_blocks)
        while int(self.fill[slot]) < need:
            b = self.blocks.alloc()
            if b is None:
                return False
            self.tables[slot, self.fill[slot]] = b
            self.fill[slot] += 1
        return True

    def advance(self, slot: int) -> None:
        """One decode step wrote this slot's token at its cursor; bump it."""
        self.positions[slot] += 1

    def release(self, slot: int) -> None:
        """Drop the slot's reference on every mapped block (shared prefix
        blocks survive under the cache's or other slots' refs) and recycle
        the slot."""
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} released twice")
        for j in range(int(self.fill[slot])):
            self.blocks.decref(int(self.tables[slot, j]))
        self.tables[slot] = 0
        self.fill[slot] = 0
        self.positions[slot] = 0
        self.active[slot] = False
        self._free.append(slot)

    def evict_prefix(self, need: int) -> int:
        return 0 if self.prefix is None else self.prefix.evict(need)

    def gather_row(self, hit_blocks) -> object:
        """Contiguous batch-1 cache view of a prefix-cache hit (pads the
        table with the garbage block; the bytes above the hit cursor are
        never attended) — the admission path resumes chunked prefill on
        it. Scalar cursor leaves are re-created HOST-side with one buffer
        each: inside the jitted gather XLA CSEs the identical scalar
        zeros into one output buffer, and the chunk programs then donate
        that buffer twice (a hard runtime error)."""
        table = np.zeros(self.max_blocks, np.int32)
        table[: len(hit_blocks)] = hit_blocks
        row = gather_prefix(self.cache, jnp.asarray(table))
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, l.dtype) if l.ndim != 4 else l, row
        )


def draft_equivalent_blocks(model, draft_model, max_slots: int,
                            block_size: int) -> int:
    """How many TARGET-model KV blocks the draft pool's bytes buy — the
    equal-HBM handicap for a speculative-vs-autoregressive A/B: the
    speculative engine allocates a full
    contiguous draft SlotPool on top of its paged target pool, so the
    honest baseline gives the plain engine that many EXTRA target blocks
    instead. Rounds up (the baseline gets the benefit of the doubt)."""
    from tpudist.serve.spec import cache_bytes

    per_token = cache_bytes(model, 1) // model.max_seq_len
    draft = cache_bytes(draft_model, max_slots)
    return -(-draft // max(per_token * block_size, 1))
