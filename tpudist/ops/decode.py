"""Autoregressive KV-cache machinery for decode-mode attention.

No reference counterpart (the reference trains a CNN); this serves the LM
families' generation path (:mod:`tpudist.generate`). TPU-first shape
discipline: the cache is a fixed head-major ``[B, H, max_len, dh]`` buffer
updated with ``dynamic_update_slice`` and attention masks are computed
against the full buffer — everything static-shaped, so one compiled step
serves every position and ``lax.scan`` drives the whole generation loop
in-graph. Head-major layout is deliberate: each (batch, head) pair's
``[S, dh]`` cache panel is contiguous, which is exactly the tile the fused
kernel DMAs per grid step (Pallas TPU blocks must keep their trailing two
dims whole or 8/128-aligned — a seq-major layout cannot slice one head
without violating that).

Two attention paths over the cache (:func:`decode_attention` dispatches):

- ``xla``: the dense oracle — einsum scores over the full buffer with the
  slot mask; ~10 small kernels per layer per token.
- ``fused``: ONE Pallas launch per layer (:func:`_fused_decode_attention`)
  computing scores + slot mask + softmax + value mix for every head. A
  small-batch decode step is a long chain of short kernels; collapsing the
  ~6-kernel attention chain into one launch attacks the kernel-count term
  directly (no served cell: not measured on the chip). Grid is (batch,): each step DMAs the row's whole contiguous
  [H_kv, S, dh] K/V — the mandatory cache read — and loops heads
  in-kernel, so the kernel rides the byte floor with no score/prob
  intermediates in HBM and no per-head grid overhead (the per-(b, h)
  grid variant pays the grid's fixed cost per head; see the function
  docstring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops import backend

NEG_INF = float(np.finfo(np.float32).min)

# At serving batch XLA's batched attention GEMMs beat the kernel's per-row
# head loop, while at latency batch the kernel's single launch beats XLA's
# ~6-kernel chain; the dispatcher falls back to the dense path above this
# bound (an earlier setup's crossover; no served cell: not measured on the
# chip).
FUSED_MAX_BATCH = 16


def cached_kv(module, k, v, max_len: int, pre_update=None, positions=None,
              block_tables=None):
    """Append this step's K/V into the module's decode cache.

    Must be called inside a flax module's ``__call__`` (it creates
    ``cache`` collection variables). ``k``/``v``: ``[B, s, H, dh]`` for the
    current step — ``s`` is 1 during sampling; larger chunks are
    first-class (the returned mask is causal WITHIN the chunk: slot ``t``
    attendable by chunk row ``i`` iff ``t <= pos + i``), and
    ``tpudist.generate``'s bulk prefill relies on exactly that, feeding
    the whole prompt as one chunk.

    ``pre_update(k, v, position) -> (k, v)`` runs before the write with the
    step's absolute position — RoPE models rotate keys here so the cache
    holds position-encoded keys.

    ``positions`` switches to slot-pooled decode (``tpudist.serve``): a
    ``[B]`` int32 vector of PER-ROW absolute positions. Each row's K/V is
    scattered at its own cursor and the mask is per-row AND causal within
    the chunk (``slot <= pos_b + i``) — the shape discipline that lets
    requests at different sequence lengths share one compiled decode
    step. ``s > 1`` is the speculative-decoding VERIFY sweep
    (``tpudist.serve.spec``): row ``b``'s chunk entries land at
    ``pos_b .. pos_b + s - 1``, and entries past ``max_len`` self-clamp
    (their one-hot is empty — nothing is written, and the engine's
    acceptance cap guarantees such tail entries are never consumed). The
    module's scalar ``cache_index`` is neither read nor advanced (the
    engine owns per-slot lengths), but it stays declared so the cache
    tree's structure is identical in both modes — a jit'd loop can donate
    the same cache pytree through either path.

    ``block_tables`` (with ``positions``) switches to PAGED decode
    (``tpudist.serve.blocks``): the cache variables hold the SHARED block
    pool ``[n_blocks, H, block_size, dh]`` (built by
    :func:`tpudist.serve.blocks.paged_cache` and passed in — there is no
    init path for it), and ``block_tables`` is a ``[B, max_blocks]`` int32
    map from each row's logical block index to its physical pool block.
    Row ``b``'s K/V is written at
    ``(table[b, pos_b // block_size], pos_b % block_size)``, and the
    return switches to ``(k_pool, v_pool, block_tables, positions)`` for
    :func:`paged_decode_attention`. HBM then holds Σ(actual lengths)
    instead of ``B × max_len`` — the long-tail serving win (docs/SERVING.md
    "Paged memory").

    Returns ``(keys, values, mask, position)``: the full head-major
    ``[B, H, max_len, dh]`` cache buffers, a ``[1, 1, s, max_len]``
    (scalar mode) or ``[B, 1, 1, max_len]`` (per-row mode) attention mask
    over valid (already-written) slots, and the position(s) where this
    step was written (for RoPE / learned-position lookup). Feed the
    buffers to :func:`decode_attention` — they are NOT in the models'
    ``[B, S, H, dh]`` activation layout.
    """
    b, s, h, dh = k.shape
    # the init trace only CREATES the cache (shape/dtype); mutating there
    # would hand callers a cache already advanced past position 0
    initialized = module.has_variable("cache", "cached_key")
    if block_tables is not None and not initialized:
        raise ValueError(
            "paged decode has no init path: build the block pool with "
            "tpudist.serve.blocks.paged_cache and pass it in as the "
            "'cache' collection"
        )
    ck = module.variable(
        "cache", "cached_key", jnp.zeros, (b, h, max_len, dh), k.dtype
    )
    cv = module.variable(
        "cache", "cached_value", jnp.zeros, (b, h, max_len, dh), v.dtype
    )
    ci = module.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
    )
    if block_tables is not None:
        if positions is None:
            raise ValueError("paged decode needs per-row positions")
        pool_k, pool_v = ck.value, cv.value  # [N, H_kv, bs, dh]
        bs_blk = pool_k.shape[2]
        pos = jnp.asarray(positions, jnp.int32)
        bt = jnp.asarray(block_tables, jnp.int32)
        mb = bt.shape[1] if bt.ndim == 2 else 0
        if pos.shape != (b,):
            raise ValueError(f"positions must be [{b}], got {pos.shape}")
        if bt.ndim != 2 or bt.shape[0] != b:
            raise ValueError(
                f"block_tables must be [{b}, max_blocks], got {bt.shape}"
            )
        if pre_update is not None:
            k, v = pre_update(k, v, pos)
        kt = k.astype(pool_k.dtype).transpose(0, 2, 1, 3)  # [B, H_kv, s, dh]
        vt = v.astype(pool_v.dtype).transpose(0, 2, 1, 3)

        # B×s sequential single-(block,offset) dynamic_update_slices
        # carried through a fori_loop: each updates a [1, H, 1, dh] sliver
        # of the donated pool in place. A gather-scatter
        # (`.at[blk, :, off, :]`) would block XLA's in-place path and copy
        # the WHOLE pool per layer per step — the exact copy the paged
        # layout exists to avoid (the same measurement that shaped the
        # contiguous one-hot write). Chunk entries past the table's
        # logical extent (the speculative verify tail of a near-end row)
        # redirect to block 0 — the reserved garbage block
        # (tpudist.serve.blocks.GARBAGE_BLOCK); unmapped mid-table entries
        # are already 0 in the engine's tables.
        def write(n, pools):
            pk, pv = pools
            i, j = n // s, n % s
            p = pos[i] + j
            lb = p // bs_blk
            blk = jnp.where(lb < mb, bt[i, jnp.minimum(lb, mb - 1)], 0)
            start = (blk, 0, p % bs_blk, 0)
            sk = jax.lax.dynamic_slice_in_dim(kt[i], j, 1, axis=1)[None]
            sv = jax.lax.dynamic_slice_in_dim(vt[i], j, 1, axis=1)[None]
            pk = jax.lax.dynamic_update_slice(pk, sk, start)
            pv = jax.lax.dynamic_update_slice(pv, sv, start)
            return pk, pv

        pool_k, pool_v = jax.lax.fori_loop(0, b * s, write, (pool_k, pool_v))
        ck.value, cv.value = pool_k, pool_v
        return pool_k, pool_v, bt, pos
    if positions is not None:
        pos = jnp.asarray(positions, jnp.int32)
        if pos.shape != (b,):
            raise ValueError(f"positions must be [{b}], got {pos.shape}")
        if pre_update is not None:
            k, v = pre_update(k, v, pos)
        if initialized:
            # per-row write as a one-hot select (one per chunk entry), NOT
            # a gather-scatter (`.at[arange, :, pos, :].set`): XLA updates
            # the select in-place on the donated buffer and fuses it,
            # while the scatter blocks the in-place path and copies every
            # layer's full [B, H, max_len, dh] buffer. An
            # entry at pos + i >= max_len has an all-false one-hot: the
            # write self-clamps (nothing lands, nothing is clobbered).
            kt = k.transpose(0, 2, 1, 3)  # [B, H, s, dh]
            vt = v.transpose(0, 2, 1, 3)
            for i in range(s):
                onehot = (
                    jnp.arange(max_len)[None, :] == (pos + i)[:, None]
                )[:, None, :, None]  # [B, 1, max_len, 1]
                ck.value = jnp.where(onehot, kt[:, :, i : i + 1], ck.value)
                cv.value = jnp.where(onehot, vt[:, :, i : i + 1], cv.value)
        slots = jnp.arange(max_len)[None, None, None, :]
        # causal within the chunk, per-row: slot t attendable by row b's
        # chunk entry i iff t <= pos_b + i
        rows = pos[:, None, None, None] + jnp.arange(s)[None, None, :, None]
        mask = slots <= rows  # [B, 1, s, max_len]
        return ck.value, cv.value, mask, pos
    pos = ci.value
    if pre_update is not None:
        k, v = pre_update(k, v, pos)
    if initialized:
        kt = jnp.transpose(k, (0, 2, 1, 3))  # [B, H, s, dh]
        vt = jnp.transpose(v, (0, 2, 1, 3))
        ck.value = jax.lax.dynamic_update_slice(ck.value, kt, (0, 0, pos, 0))
        cv.value = jax.lax.dynamic_update_slice(cv.value, vt, (0, 0, pos, 0))
        ci.value = pos + s
    # slot t is attendable by step row i iff t <= pos + i (causal over the
    # buffer; unwritten slots are masked out entirely)
    slots = jnp.arange(max_len)[None, None, None, :]
    rows = pos + jnp.arange(s)[None, None, :, None]
    mask = slots <= rows
    return ck.value, cv.value, mask, pos


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, sm_scale, h, ratio):
    """One grid step = one batch row: all ``h`` query heads against this
    row's whole cache block [H_kv, S, dh]; slots past the write position
    are masked. Scores and probs live only in VMEM/registers. The head
    loop is a fori_loop (one head's code compiled, per-head VMEM scratch
    reused — the grouping that kept the vmem attention kernel off the
    grid-overhead cliff applies doubly here, where per-head compute is a
    single [1, S] softmax)."""
    pos = pos_ref[0]

    def one(i, _):
        q = q_ref[i]  # [1, dh]
        k = k_ref[i // ratio]  # [S, dh]
        v = v_ref[i // ratio]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [1, S]
        kp = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kp <= pos, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[i] = (o / l).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, h, one, 0)


def _fused_decode_attention(q, keys, values, pos):
    """q ``[B, 1, H, dh]`` (activation layout), keys/values
    ``[B, H_kv, S, dh]`` (the head-major cache buffers), ``pos`` scalar
    int32 → ``[B, 1, H, dh]``. GQA reads each K/V head once per query
    group straight from the grouped layout.

    Grid is (batch,): one step DMAs the row's whole [H_kv, S, dh] K/V
    (contiguous) and loops heads in-kernel. A per-(b, h) grid is 1536
    tiny grid steps at batch 128 and GPT-2 124M's 12 heads, each paying the
    grid's fixed cost; one step per row with the heads looped in-kernel
    amortizes it into DMA-sized work items.
    """
    b, s_q, h, dh = q.shape
    h_kv, s_len = keys.shape[1], keys.shape[2]
    if s_q != 1:
        raise NotImplementedError("fused decode attention is single-token")
    if b > FUSED_MAX_BATCH:
        raise NotImplementedError(
            f"batch {b} > {FUSED_MAX_BATCH}: above the measured crossover "
            "the dense path's batched GEMMs win — dispatcher falls back"
        )
    if h % h_kv:
        raise NotImplementedError(f"q heads {h} not a multiple of kv {h_kv}")
    ratio = h // h_kv
    sm_scale = 1.0 / float(np.sqrt(dh))
    # [B,1,H,dh] -> [B,H,1,dh] moves a singleton: a free reshape, no copy
    qt = q.reshape(b, h, 1, dh)
    # None squeezes the batch dim out of the kernel refs, so the blocks
    # keep their trailing [.., S|1, dh] dims whole — Mosaic-tileable
    q_spec = pl.BlockSpec((None, h, 1, dh), lambda b, *_: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, h_kv, s_len, dh), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, sm_scale=sm_scale, h=h, ratio=ratio
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=backend.interpret(),
    )(jnp.asarray(pos, jnp.int32).reshape(1), qt, keys, values)
    return out.reshape(b, s_q, h, dh)


def decode_attention(q, keys, values, mask, pos, *, impl: str = "fused",
                     bias=None, scale=None):
    """Single-token attention over the cache buffers from :func:`cached_kv`
    (``q`` in activation layout ``[B, s, H, dh]``, keys/values head-major
    ``[B, H_kv, max_len, dh]``).

    ``impl="fused"`` runs the one-launch Pallas kernel (falling back to the
    dense path when its constraints don't hold — multi-token chunks,
    ragged head ratios, K/V panels past the VMEM bound); ``impl="xla"``
    is the dense oracle the fused kernel is tested against. Both implement
    the same function: attention over slots ``<= pos`` (+ row offset for
    multi-token chunks, via ``mask``).

    ``bias``: optional additive score bias broadcastable to
    ``[B, H, s, max_len]`` (T5's relative position bias) — dense path
    only (the fused kernel takes none). ``scale`` overrides the default
    ``1/sqrt(dh)`` (T5 uses 1.0).
    """
    # explicit applicability predicate, not try/except NotImplementedError:
    # Pallas itself raises NotImplementedError for unsupported op/platform
    # combinations, and swallowing those would silently run the dense path
    # while the documents claim the fused kernel. The VMEM bound: one
    # grid step stages a row's whole [H_kv, S, dh] K and V panels (double-
    # buffered by the pipeline), so large-cache geometries (e.g. h_kv=8,
    # S=8192, dh=128 bf16 = 32 MB K+V) must take the dense path instead
    # of failing Mosaic's VMEM check at compile time.
    kv_panel_bytes = (
        2 * keys.shape[1] * keys.shape[2] * keys.shape[3] * keys.dtype.itemsize
    )
    fused_ok = (
        bias is None
        and scale is None
        and q.shape[1] == 1
        and q.shape[0] <= FUSED_MAX_BATCH
        and q.shape[2] % keys.shape[1] == 0
        and kv_panel_bytes <= 6 * 1024 * 1024  # ×2 pipeline buffers ≤ ~12 MB
        # per-row positions (slot-pooled decode, tpudist.serve) take the
        # dense path: the kernel prefetches ONE scalar write cursor, and
        # the serving batch sits above the fused crossover anyway
        and jnp.ndim(pos) == 0
    )
    if impl == "fused" and fused_ok:
        return _fused_decode_attention(q, keys, values, pos)
    if keys.shape[1] != q.shape[2]:
        from tpudist.ops.attention import repeat_kv

        # head_axis=1: the cache is head-major (one home for the ratio math)
        keys, values = repeat_kv(q, keys, values, head_axis=1)
    # dense oracle over the head-major cache: f32 scores, slot mask, softmax
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    logits = jnp.einsum(
        "bqhd,bhkd->bhqk", q, keys, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bqhd", probs, values)


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, bs, h, ratio, sm_scale):
    """One grid step = (batch row b, logical block j): online-softmax
    accumulation over the row's block-table walk. The k/v BlockSpec index
    map already resolved logical j to the row's PHYSICAL pool block (and
    clamped past-the-cursor j to the last needed block, so trailing grid
    steps re-map the same block and the pipeline issues NO new DMA for
    them — the bytes read per row are ceil((pos+1)/bs) blocks, not
    max_blocks). Scratch (m, l, acc) persists across j within a row; the
    normalized output is (re)written at every valid j, so the last valid
    block leaves the final answer in the revisited output block."""
    b_i = pl.program_id(0)
    j = pl.program_id(1)
    s_q = q_ref.shape[1]
    pos = pos_ref[b_i]
    # the chunk's LAST query row (pos + s_q - 1) bounds the block walk —
    # for the single-token case this is the old pos // bs
    last = (pos + s_q - 1) // bs

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= last)
    def _block():
        def one(i, _):
            q = q_ref[i]  # [s_q, dh]
            k = k_ref[i // ratio]  # [bs, dh]
            v = v_ref[i // ratio]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [s_q, bs]
            kp = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
            # causal within the chunk: query row r attends slots <= pos + r
            rq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(kp <= pos + rq, s, NEG_INF)
            m_prev = m_ref[i]  # [s_q]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_new = alpha * l_ref[i] + jnp.sum(p, axis=-1)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [s_q, dh]
            acc_new = alpha[:, None] * acc_ref[i] + pv
            m_ref[i], l_ref[i], acc_ref[i] = m_new, l_new, acc_new
            # block 0 has at least one unmasked slot for EVERY query row
            # (slot 0 <= pos + r always), so after the j=0 step l > 0 for
            # all rows — no guard needed. Later blocks fully masked for an
            # early row contribute exp(NEG_INF - m) = 0 and leave its
            # running stats unchanged.
            o_ref[i] = (acc_new / l_new[:, None]).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, h, one, 0)


def _paged_fused_attention(q, k_pool, v_pool, block_tables, positions):
    """q ``[B, 1, H, dh]``, pools ``[n_blocks, H_kv, bs, dh]``,
    ``block_tables [B, max_blocks]``, ``positions [B]`` → ``[B, 1, H, dh]``.

    Grid is (batch, max_blocks) with the block table and positions as
    scalar prefetch: the k/v index map reads the row's table to DMA the
    right PHYSICAL block per logical step, clamping logical blocks past
    the row's cursor to its last needed block — Pallas skips the DMA when
    a revisited index maps the same block, so a row at length L reads
    ceil((L+1)/bs) blocks and the kernel's HBM traffic is Σ(actual
    lengths), the byte roofline the paged layout buys (vs the dense
    path's B × max_len gather). Per-block online softmax in VMEM scratch;
    heads loop in-kernel (the grouping that keeps grid steps DMA-sized,
    same as the contiguous fused kernel); GQA reads each K/V head once
    per query group from the grouped pool layout."""
    b, s_q, h, dh = q.shape
    h_kv, bs = k_pool.shape[1], k_pool.shape[2]
    mb = block_tables.shape[1]
    if h % h_kv:
        raise NotImplementedError(f"q heads {h} not a multiple of kv {h_kv}")
    ratio = h // h_kv
    sm_scale = 1.0 / float(np.sqrt(dh))
    # head-major for the kernel; s_q == 1 makes this a free reshape
    qt = q.reshape(b, h, 1, dh) if s_q == 1 else q.transpose(0, 2, 1, 3)

    def kv_map(b_i, j, bt, pos):
        # clamp to the chunk's last needed block AND the table's extent
        # (a verify chunk's tail past the mapped window re-walks the last
        # block; its slots are masked in-kernel)
        jc = jnp.minimum(j, (pos[b_i] + s_q - 1) // bs)
        return (bt[b_i, jnp.minimum(jc, mb - 1)], 0, 0, 0)

    q_spec = pl.BlockSpec((None, h, s_q, dh), lambda b_i, j, *_: (b_i, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, h_kv, bs, dh), kv_map)
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, bs=bs, h=h, ratio=ratio, sm_scale=sm_scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, mb),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((h, s_q), jnp.float32),   # running max
                pltpu.VMEM((h, s_q), jnp.float32),   # running denominator
                pltpu.VMEM((h, s_q, dh), jnp.float32),  # running numerator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=backend.interpret(),
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(positions, jnp.int32),
        qt, k_pool, v_pool,
    )
    return out.reshape(b, s_q, h, dh) if s_q == 1 else out.transpose(0, 2, 1, 3)


def paged_decode_attention(q, k_pool, v_pool, block_tables, positions, *,
                           impl: str = "paged", mesh=None):
    """Attention over the PAGED pool from :func:`cached_kv`'s block-table
    mode (``q [B, s, H, dh]`` activation layout, pools head-major
    ``[n_blocks, H_kv, block_size, dh]``). ``s == 1`` is the sampling
    step; ``s > 1`` is the speculative-decoding verify chunk — causal
    within the chunk (query row ``r`` attends logical slots
    ``<= pos + r``), the multi-row twin of the contiguous per-row mask.

    ``impl="paged"`` runs the one-launch-per-layer Pallas kernel
    (:func:`_paged_fused_attention`): unlike the contiguous fused kernel
    it has NO upper batch bound — at serving batch the dense alternative
    must GATHER every row's max_blocks × block_size window into a
    contiguous buffer first (B × max_len bytes through HBM), while the
    kernel walks each row's table and reads only blocks up to the cursor,
    which is what converts the paged layout's saved bytes into tok/s
    (no served cell: not measured on the chip). ``impl="xla"`` is the
    gather-then-dense oracle the kernel is tested against (and the
    correctness path on models pinned to ``attn_impl="xla"``).

    ``mesh``: pass the serving mesh on a multi-chip tensor-sharded engine
    (``tpudist.serve.engine.ServeEngine(mesh=...)``). ``pallas_call`` has
    no GSPMD partitioning rule, so on a >1-device ``tensor`` axis the
    kernel runs per-shard inside ``shard_map``: q splits on its head dim,
    the pools on their KV-head dim (the engine shards the block pool
    ``[n_blocks, H_kv/T, block_size, dh]`` per chip), block tables and
    positions stay replicated. Softmax is complete per head, so the wrap
    is exact with no collective — each chip walks the SAME block tables
    over its own head slice of the pool. The dense oracle path needs no
    wrap (gather + einsums partition under plain GSPMD)."""
    paged_ok = (
        q.shape[2] % k_pool.shape[1] == 0
        # one block's K+V panel stays far under VMEM at any sane
        # block_size; no panel bound needed (the whole point: the DMA
        # unit is a block, not a row's full window)
    )
    if impl == "paged" and paged_ok:
        if mesh is not None:
            from tpudist import mesh as mesh_lib

            tp = int(mesh.shape[mesh_lib.TENSOR_AXIS]) \
                if mesh_lib.TENSOR_AXIS in mesh.axis_names else 1
            h, h_kv = q.shape[2], k_pool.shape[1]
            if tp > 1 and h % tp == 0 and h_kv % tp == 0:
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                fn = shard_map(
                    _paged_fused_attention,
                    mesh=mesh,
                    in_specs=(
                        P(None, None, mesh_lib.TENSOR_AXIS, None),  # q heads
                        P(None, mesh_lib.TENSOR_AXIS, None, None),  # k pool
                        P(None, mesh_lib.TENSOR_AXIS, None, None),  # v pool
                        P(None, None),  # block tables: replicated
                        P(None),        # positions: replicated
                    ),
                    out_specs=P(None, None, mesh_lib.TENSOR_AXIS, None),
                    # pallas_call can't declare varying-manual-axes on its
                    # out_shape (same caveat as ops/attention.py's wrap)
                    check_vma=False,
                )
                return fn(q, k_pool, v_pool,
                          jnp.asarray(block_tables, jnp.int32),
                          jnp.asarray(positions, jnp.int32))
        return _paged_fused_attention(q, k_pool, v_pool, block_tables,
                                      positions)
    # dense oracle: gather each row's table into a contiguous window and
    # reuse the contiguous dense path (per-row causal-within-chunk mask
    # over logical slots <= pos + row)
    b, s_q = q.shape[0], q.shape[1]
    h_kv, bs = k_pool.shape[1], k_pool.shape[2]
    mb = block_tables.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    keys = k_pool[bt].transpose(0, 2, 1, 3, 4).reshape(b, h_kv, mb * bs, -1)
    values = v_pool[bt].transpose(0, 2, 1, 3, 4).reshape(b, h_kv, mb * bs, -1)
    slots = jnp.arange(mb * bs)[None, None, None, :]
    rows = pos[:, None, None, None] + jnp.arange(s_q)[None, None, :, None]
    mask = slots <= rows  # [B, 1, s_q, mb*bs]
    return decode_attention(q, keys, values, mask, pos, impl="xla")
