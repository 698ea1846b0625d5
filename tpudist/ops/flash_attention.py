"""Flash attention as a Pallas TPU kernel.

No reference counterpart (the reference's workload is a CNN, SURVEY.md §5
"long-context: ABSENT") — this is the hot op for the transformer legs of the
BASELINE ladder (ViT, GPT-2) and the building block the ring-attention
context-parallel path reuses blockwise.

Design (FlashAttention-2 style, TPU-first):

- each kernel works sweep by sweep: one resident block (a Q block in the
  forward and dq kernels, a K block in dkv) and a walk over the streamed
  blocks it meets, so the f32 VMEM scratch accumulators (running max
  ``m``, normalizer ``l``, output ``acc``; dq; dk / dv) persist across the
  sweep;
- per tile: one MXU matmul ``q·kᵀ`` (f32 accumulation), online-softmax
  rescale on the VPU, one MXU matmul ``p·v`` into the accumulator — the
  S×S score matrix never exists in HBM;
- masking is two-level, under ONE description of the mask
  (:class:`tpudist.ops.attention.BlockMask`: causal, block-causal, the
  block-diffusion mask over a noised and a clean copy, or a sliding
  window) that the wrapper, the three kernels and the scan backward share:
  a (Q tile, K tile) with no allowed pair is never stepped over where the
  grid can leave it out and predicated off with ``pl.when`` where it
  cannot (``BlockMask.tile_live``: no MXU work runs), a tile whose every
  pair is allowed runs unmasked (``BlockMask.tile_full``), every other
  tile is masked elementwise from ``broadcasted_iota`` (the causal
  instance compares positions, the others ``BlockMask.tile_allowed``);
  ``kv_len`` masks right-padded keys the same two-level way (ragged caller
  shapes are padded to the 128-tile multiple by the wrapper).
  :func:`computed_tile_share` is the static counter of what the first
  level leaves;
- three grids, picked by what the call's mask, ``kv_len`` and blocks
  leave live (:func:`_grid`): the SQUARE ``(b, h, outer, inner)`` where
  every tile is live (no mask, no ``kv_len`` that retires a K block); the
  LIVE STEPS ``(b, h, n_steps)`` where some tile is dead and there is no
  window (causal, block-causal, block diffusion, a ragged ``kv_len``):
  the last axis walks a static list in scalar memory (:func:`_step_list`)
  of the live tiles alone, sweep by sweep in the square's order, each
  entry the outer block, the inner block and whether it opens or closes
  its sweep, so that no grid step is empty and each live tile is fetched
  once (the pipeline copies a block only when its index changes); the
  BAND under a sliding window (``BlockMask(window=W)``), whose live tiles
  are a constant number a sweep: the inner axis spans only the band
  (:func:`_band`: ``first(outer) + j``, clamped at the band's last block
  by the index map, so that a step past it fetches nothing).
  :func:`grid_step_share` counts the steps each grid runs and
  :func:`fetched_tile_share` the fetches its index maps make;
- two backward paths, both O(S·block) memory, recomputing p from the saved
  log-sum-exp: a blockwise ``lax.scan`` in plain JAX and the Pallas FA-2
  dq/dkv kernels. :func:`default_blocks` decides by shape (since PR 28):
  heads of 128 from 2048 tokens on take blocks up to 512 x 1024 and the
  Pallas kernels, every other shape 128 x 128 and the scan (fastest at
  d=64/moderate S on v5e); ``pallas_bwd=`` overrides it.

Numerics: scores/softmax in float32 regardless of input dtype (bf16 in, bf16
out). Matches ``dot_product_attention`` to ~1e-2 in bf16, ~1e-5 in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops import backend
from tpudist.ops.attention import BlockMask
from tpudist.remat import KERNEL_RESIDUALS

NEG_INF = float(np.finfo(np.float32).min)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def default_blocks(seq_q: int, seq_k: int, head_dim: int, window: int = 0):
    """``(block_q, block_k, pallas_bwd)`` for a call that names none, from
    the (128-padded) sequence lengths and the head size (the VALUE width
    where keys are wider: latent attention's keys of 192 on values of 128
    take the blocks of heads of 128). Measured on one
    v5e chip (PERF.md §6, PR 28) at batch 4, 4096 tokens, 8 heads of 128,
    causal, bf16: forward + backward 6.9 ms with blocks 512 x 1024 and the
    Pallas backward against 26.7 ms with 128 x 128 and the scan backward —
    so heads of 128 from 2048 tokens on take the largest of those blocks
    that divide the sequence. Under a sliding ``window`` of at most 512
    the K blocks are as tall as the Q blocks (512 x 512: 31 of 256 tiles
    computed at 8,192 rows and a window of 512, where 512 x 1024 computes
    23 of 128, half again the pairs; measured on a v5e chip, PERF.md §6).
    Every other shape keeps 128 x 128 and the scan backward (at head size
    64 and up to 4096 tokens the scan was the faster one)."""
    if head_dim == 128 and min(seq_q, seq_k) >= 2048:
        fit = lambda most, seq: next(
            b for b in (most, most // 2, most // 4, 128) if seq % b == 0
        )
        widest_k = 512 if 0 < window <= 512 else 1024
        return fit(512, seq_q), fit(widest_k, seq_k), True
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, False


def _lane_pad(width: int) -> int:
    """Zero columns a head of ``width`` gains before the kernels: up to the
    128-lane tile, and past one tile up to the next half tile (a key of
    192 stays 192: the block then spans the array's whole last axis, which
    Mosaic takes, and no pad is written to HBM)."""
    return -width % (128 if width <= 128 else 64)


CAUSAL = BlockMask()


def computed_tile_share(mask: BlockMask | None, seq_len: int, block_q: int,
                        block_k: int) -> float:
    """Share of the ``seq_len²`` score tiles the kernels compute under
    ``mask`` at these blocks: the tiles :func:`_tile_live` leaves on, the
    static counter of the tile skipping (1.0 without a mask). At 8,192
    rows and 512 x 1024: 0.5625 causal, 0.375 for the block-diffusion mask
    ``BlockMask(4, 4096)``, whose allowed pairs are 0.2502 of the
    square."""
    if mask is None:
        return 1.0
    qi = np.arange(seq_len // block_q)[:, None]
    ki = np.arange(seq_len // block_k)[None, :]
    return float(np.mean(np.asarray(
        mask.tile_live(qi, ki, block_q, block_k))))


def _kernel_sweeps(seq_len: int, block_q: int, block_k: int):
    """``(n_outer, n_inner, dkv)`` of the three kernels' grids: forward
    and dq sweep K blocks a Q block, dkv Q blocks a K block."""
    n_q, n_k = seq_len // block_q, seq_len // block_k
    return ((n_q, n_k, False), (n_q, n_k, False), (n_k, n_q, True))


def _committed_names(mask, seq_len, block_q, block_k):
    """For each of the three kernels ``(names, tiles)``: the ``(outer,
    inner)`` blocks its committed index maps name at each step of one
    (batch, head) of its grid (:func:`_grid`), in the order the grid runs
    them, and its ``(S / block_q) (S / block_k)`` tiles."""
    for n_outer, n_inner, dkv in _kernel_sweeps(seq_len, block_q, block_k):
        dims, steps, _, at_outer, at_inner = _grid(
            mask, None, n_outer, n_inner, block_q, block_k, dkv)
        tbl = () if steps is None else (steps.ravel(),)
        names = [(at_outer(0, 0, *pos, *tbl)[2], at_inner(0, 0, *pos, *tbl)[2])
                 for pos in np.ndindex(*dims)]
        yield names, n_outer * n_inner


def fetched_tile_share(mask: BlockMask | None, seq_len: int, block_q: int,
                       block_k: int) -> float:
    """Fetches of the streamed block by the three kernels' grid steps under
    ``mask`` at these blocks, over the ``(S / block_q) (S / block_k)``
    tiles each kernel has: K / V in the forward and dq kernels, Q / dO /
    lse / delta in the dkv kernel. The committed index maps (:func:`_grid`)
    are evaluated over one (batch, head) of each kernel's grid; a step
    fetches where its block differs from the previous step's of the same
    sweep, and a sweep's first step always does (the pipeline may also keep
    a block across two sweeps: not counted). Each live tile is fetched
    once: at 8,192 rows and 512 x 1024, 0.375 under ``BlockMask(4, 4096)``
    and 0.5625 causal (1.0 while the grid ran dead steps that fetched);
    1.0 without a mask."""
    fetched = tiles = 0
    for names, square in _committed_names(mask, seq_len, block_q, block_k):
        fetched += sum(
            prev is None or outer != prev[0] or inner != prev[1]
            for prev, (outer, inner) in zip([None] + names, names))
        tiles += square
    return fetched / tiles


def grid_step_share(mask: BlockMask | None, seq_len: int, block_q: int,
                    block_k: int) -> float:
    """The three kernels' grid steps over their ``(S / block_q) (S /
    block_k)`` tiles each: 1.0 on the square grid (every tile live); on the
    live-step grid the live share, one step more for a sweep with no live
    tile (:func:`_step_list`) — at 8,192 rows and 512 x 1024, 0.375 under
    ``BlockMask(4, 4096)`` and 0.5625 causal, ``computed_tile_share``'s
    values; on a window's band (:func:`_band`) at 8,192 rows and a window
    of 512, 88 / 384 = 0.229 at blocks 512 x 1024, 0.125 at 512 x 512."""
    steps = tiles = 0
    for names, square in _committed_names(mask, seq_len, block_q, block_k):
        steps += len(names)
        tiles += square
    return steps / tiles


def _imin(a, b):
    """``min`` of a traced scalar (an index map's, a kernel's) or of a
    Python int (a counter's, evaluated at trace time)."""
    return min(a, b) if isinstance(a, (int, np.integer)) else jnp.minimum(a, b)


def _imax(a, b):
    return max(a, b) if isinstance(a, (int, np.integer)) else jnp.maximum(a, b)


@functools.lru_cache(maxsize=None)
def _band(mask, n_outer, n_inner, block_q, block_k, dkv):
    """The inner axis of a windowed call's grid, ``(steps, first, last)``,
    or ``None`` for any other mask (the square or the live-step grid). Under ``0 <= i - j <
    W`` the live inner blocks of a sweep are contiguous, from
    ``first(outer)`` to ``last(outer)``: a Q block sees the keys from ``W
    - 1`` before its first row to its last row (forward, dq), a K block is
    seen by the rows from its first key to ``W - 1`` after its last (dkv).
    Grid step ``j`` works on inner block ``first(outer) + j``; ``steps``
    is the widest band, so a narrower sweep (the sequence's first blocks,
    the last ones in dkv) ends on dead steps, which the index map clamps
    to ``last(outer)``: the block already resident, nothing fetched.
    ``first`` and ``last`` take a traced scalar or a Python int."""
    if mask is None or not mask.window:
        return None
    w = mask.window
    if dkv:
        first = lambda o: o * block_k // block_q
        last = lambda o: _imin((o * block_k + block_k + w - 2) // block_q,
                               n_inner - 1)
    else:  # a masked call has as many keys as rows: last < n_inner
        first = lambda o: _imax(o * block_q - (w - 1), 0) // block_k
        last = lambda o: (o * block_q + block_q - 1) // block_k
    steps = max(last(o) - first(o) + 1 for o in range(n_outer))
    return steps, first, last


def _inner(band, outer, j):
    """The inner block grid step ``j`` of a sweep works on, and whether it
    lies in its sweep's band (``True``: the square grid)."""
    if band is None:
        return j, True
    _, first, last = band
    block = first(outer) + j
    return block, block <= last(outer)


def _tile_live(mask, kv_len, qi, ki, block_q, block_k):
    """Has tile ``(qi, ki)`` anything to compute? A traced scalar from the
    grid step's blocks (or ``True``; numpy on numpy grids): the mask's own
    test, and a ``kv_len`` shorter than the padded K retires whole K blocks
    too."""
    live = True
    if mask is not None:
        live = mask.tile_live(qi, ki, block_q, block_k)
    if kv_len is not None:
        live &= ki * block_k < kv_len
    return live


def _steps(take):
    """The steps of a live-step grid over the tiles ``take`` marks, a
    boolean ``[n_outer, n_inner]``: ``int32 [n_steps, 4]``, each row
    ``(outer, inner, first, last)`` — sweep by sweep and, in a sweep, the
    inner blocks in order (the square grid's order, so that each resident
    block accumulates its tiles as it did there), ``first`` / ``last`` 1
    on the sweep's first / last step. A sweep with no tile marked gets one
    step that opens and closes it (init and finalize: a K block that a
    ``kv_len`` retires gets its zero dk / dv), naming the inner block of
    the step before, so that nothing is fetched for it."""
    take = take.copy()
    hollow = ~take.any(axis=1)
    take[hollow, 0] = True
    outer, inner = np.nonzero(take)
    for s in np.flatnonzero(hollow[outer]):
        inner[s] = inner[s - 1] if s else 0
    ends = outer[1:] != outer[:-1]
    steps = np.stack([outer, inner, np.r_[True, ends], np.r_[ends, True]],
                     axis=1).astype(np.int32)
    steps.flags.writeable = False
    return steps


@functools.lru_cache(maxsize=None)
def _step_list(mask, kv_len, n_outer, n_inner, block_q, block_k, dkv):
    """The live-step grid of a kernel (:func:`_steps` over its live tiles),
    or ``None`` where every tile is live (the square grid) or under a
    window (the band's grid, :func:`_band`). A sweep is one outer block's
    walk over the inner ones: K blocks a Q block in the forward and dq
    kernels (one list for both), Q blocks a K block (``dkv``) in the dkv
    kernel. :func:`_tile_live` decides, the test the bodies run, here on
    numpy grids at trace time (numpy throughout: a ``jnp`` op while a step
    is traced would compile on the chip by itself)."""
    if mask is not None and mask.window:
        return None
    outer = np.arange(n_outer)[:, None]
    inner = np.arange(n_inner)[None, :]
    qi, ki = (inner, outer) if dkv else (outer, inner)
    live = np.broadcast_to(_tile_live(mask, kv_len, qi, ki, block_q, block_k),
                           (n_outer, n_inner))
    return None if live.all() else _steps(live)


def _grid(mask, kv_len, n_outer, n_inner, block_q, block_k, dkv):
    """``(dims, steps, band, at_outer, at_inner)`` of a kernel: its grid
    after ``(b, h)``, its step list (scalar prefetch) or ``None``, its band
    or ``None``, and the index maps of the resident block (the outer one)
    and of the streamed block (the inner one). The live-step grid
    ``(n_steps,)`` reads both blocks from its list; the band's ``(n_outer,
    band)`` names ``first(outer) + j``, clamped at the band's last block;
    the square ``(n_outer, n_inner)`` names ``(i, j)``."""
    steps = _step_list(mask, kv_len, n_outer, n_inner, block_q, block_k, dkv)
    if steps is not None:
        return ((len(steps),), steps, None,
                lambda b, h, s, tbl: (b, h, tbl[4 * s], 0),
                lambda b, h, s, tbl: (b, h, tbl[4 * s + 1], 0))
    band = _band(mask, n_outer, n_inner, block_q, block_k, dkv)
    at_outer = lambda b, h, i, j: (b, h, i, 0)
    if band is not None:
        _, first, last = band
        return ((n_outer, band[0]), None, band, at_outer,
                lambda b, h, i, j: (b, h, _imin(first(i) + j, last(i)), 0))
    return ((n_outer, n_inner), None, None, at_outer,
            lambda b, h, i, j: (b, h, j, 0))


def _position(band, steps):
    """``(outer, inner, first, last, in_band)`` of the grid step a kernel
    body runs: its resident and its streamed block, whether the step opens
    / closes its sweep (each a function, called where the body tests it,
    which keeps the square and the band's grids' traces free of the list),
    and whether it lies in its sweep's band (``True`` off the band's
    grid). On the live-step grid all of it is read from the list in scalar
    memory (``steps``, flattened)."""
    if steps is not None:
        s = pl.program_id(2)
        return (steps[4 * s], steps[4 * s + 1],
                lambda: steps[4 * s + 2] != 0, lambda: steps[4 * s + 3] != 0,
                True)
    outer, j = pl.program_id(2), pl.program_id(3)
    inner, in_band = _inner(band, outer, j)
    return (outer, inner, lambda: j == 0,
            lambda: j == pl.num_programs(3) - 1, in_band)


def _pallas(kernel, steps, *, grid, in_specs, out_specs, scratch_shapes,
            out_shape, interpret):
    """``pallas_call`` of ``kernel`` over ``grid``; with a step list the
    list rides, flattened, as the one scalar-prefetch operand, which the
    index maps read and the body gets as ``steps=`` (traced under the
    kernel's own name and source)."""
    if steps is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            interpret=interpret)

    @functools.wraps(kernel.func)
    def body(steps_ref, *refs):
        return kernel(*refs, steps=steps_ref)

    call = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape, interpret=interpret)
    return functools.partial(call, steps.ravel())


def _keep(mask, kv_len, q_pos, k_pos):
    """Which pairs of a tile count, elementwise, or ``None`` where all
    do."""
    if mask is None and kv_len is None:
        return None
    keep = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if mask is not None:
        keep &= mask.allowed(q_pos, k_pos)
    if kv_len is not None:
        keep &= k_pos < kv_len
    return keep


def _tile_keep(mask, kv_len, qi, ki, block_q, block_k):
    """:func:`_keep` for tile ``(qi, ki)`` inside a kernel. The causal
    instance compares positions, as it always did; any other mask answers
    from the tile's own scalars (``BlockMask.tile_allowed``)."""
    if mask is None and kv_len is None:
        return None
    shape = (block_q, block_k)
    if mask is None or mask.causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return _keep(mask, kv_len, q_pos, k_pos)
    keep = mask.tile_allowed(qi, ki, block_q, block_k)
    if kv_len is not None:
        keep &= ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1) < kv_len
    return keep


def _when_live(mask, kv_len, qi, ki, block_q, block_k, compute,
               in_band=True):
    """Run ``compute(mask)`` for tile ``(qi, ki)`` if it has anything to
    compute (and, on a band's grid, lies in its sweep's band: ``in_band``).
    Under a mask other than the causal one the body is traced twice: a
    tile whose EVERY pair is allowed (``BlockMask.tile_full``: half of the
    live tiles under the block-diffusion mask) takes the branch without
    the elementwise mask — a tile's score work is VPU-bound and the mask
    is a good part of it. A window narrower than a tile's diagonal span
    (``W < block_q + block_k - 1``) leaves no tile full: one trace."""
    live = _tile_live(mask, kv_len, qi, ki, block_q, block_k)
    if in_band is not True:
        live &= in_band
    if mask is None or mask.causal or kv_len is not None or (
            mask.window and mask.window < block_q + block_k - 1):
        pl.when(live)(lambda: compute(mask))
        return
    full = mask.tile_full(qi, ki, block_q, block_k)
    pl.when(live & full)(lambda: compute(None))
    pl.when(live & ~full)(lambda: compute(mask))


def _fwd_kernel(
    q_ref, k_ref, v_ref,  # [1,1,bq,d], [1,1,bk,d], [1,1,bk,dv]
    o_ref, lse_ref,       # [1,1,bq,dv], [1,1,bq,128] (lane-padded, see _flash_fwd)
    m_scr, l_scr, acc_scr,  # VMEM f32: [bq,128], [bq,128], [bq,dv]
    *, sm_scale: float, mask: BlockMask | None, block_q: int, block_k: int,
    kv_len: int | None = None, band=None, steps=None,
):
    qi, ki, first, last, in_band = _position(band, steps)

    @pl.when(first())
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Tiles with no allowed pair (causal: K blocks strictly above the
    # diagonal) contribute nothing; a kv_len shorter than the padded K also
    # retires whole blocks. Skip both entirely (predicated off — no MXU
    # work issued).
    def _compute(mask):
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        v = v_ref[0, 0]  # [bk, dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale  # [bq, bk]
        keep = _tile_keep(mask, kv_len, qi, ki, block_q, block_k)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # a row with nothing unmasked yet keeps m = NEG_INF and takes p = 1
        # from this tile; the first tile that holds a key it may see (every
        # row has one: its own) rescales that history by alpha = 0
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1] rescale of history
        p = jnp.exp(s - m_new)                     # [bq, bk]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv

    _when_live(mask, kv_len, qi, ki, block_q, block_k, _compute, in_band)

    @pl.when(last())
    def _finalize():
        l = l_scr[:, :1]
        # guard fully-masked rows (no mask here has one, but it keeps the
        # kernel total-function)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l))  # [bq, 1]
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd(q, k, v, *, mask, sm_scale, block_q, block_k, kv_len=None):
    """q,k: [B, H, S, D], v: [B, H, S, Dv] → (o [B,H,S,Dv], lse [B,H,S]
    f32)."""
    b, h, s_q, d = q.shape
    s_k, d_v = k.shape[2], v.shape[3]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    # TPU tile constraint: last-two dims of every VMEM block must align to
    # (8,128)/(16,128); requiring 128-multiples keeps the MXU fully fed.
    # Unaligned CALLER shapes are padded by flash_attention() (with kv_len
    # masking the padded keys); reaching here misaligned is a bug.
    if s_q % block_q or s_k % block_k or block_q % 128 or block_k % 128:
        raise NotImplementedError(
            f"flash attention needs 128-aligned blocks: seq_q={s_q}, "
            f"seq_k={s_k}, block_q={block_q}, block_k={block_k}"
        )
    dims, steps, band, at_q, at_k = _grid(
        mask, kv_len, s_q // block_q, s_k // block_k, block_q, block_k, False)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, mask=mask,
        block_q=block_q, block_k=block_k, kv_len=kv_len, band=band,
    )
    # lse rides a lane-padded [b,h,s_q,128] buffer: a [*, *, bq] block would
    # put a size-1 dim in the sublane slot, which Mosaic's (8,128) tiling
    # rejects on real TPUs (interpret mode doesn't enforce it)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, s_q, d_v), q.dtype),
        jax.ShapeDtypeStruct((b, h, s_q, 128), jnp.float32),
    ]
    o, lse = _pallas(
        kernel, steps,
        grid=(b, h, *dims),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), at_q),
            pl.BlockSpec((1, 1, block_k, d), at_k),
            pl.BlockSpec((1, 1, block_k, d_v), at_k),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d_v), at_q),
            pl.BlockSpec((1, 1, block_q, 128), at_q),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        interpret=backend.interpret(),
    )(q, k, v)
    return o, lse[..., 0]


def _recompute_p_ds(
    qi, ki, q, k, v, do, lse, delta,
    *, sm_scale: float, mask: BlockMask | None, block_q: int, block_k: int,
    kv_len: int | None = None,
):
    """Shared backward recompute: scores → (p, ds) for one (Q, K) tile.

    Same masking/scaling as the forward kernel; p = exp(s − lse),
    ds = p ∘ (do·vᵀ − δ) · scale. Inlines at trace time — no runtime cost
    to sharing it between the dkv and dq kernels.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [bq, bk]
    keep = _tile_keep(mask, kv_len, qi, ki, block_q, block_k)
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - lse)  # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _bwd_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref,  # [1,1,bq,d], [1,1,bq,dv], [1,1,bq,1]×2
    k_ref, v_ref,                        # [1,1,bk,d], [1,1,bk,dv]
    dk_ref, dv_ref,                      # [1,1,bk,d], [1,1,bk,dv]
    dk_scr, dv_scr,                      # VMEM f32 [bk,d], [bk,dv]
    *, sm_scale: float, mask: BlockMask | None, block_q: int, block_k: int,
    kv_len: int | None = None, band=None, steps=None,
):
    """dk/dv: K/V block resident, sweep over Q blocks."""
    ki, qi, first, last, in_band = _position(band, steps)

    @pl.when(first())
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # a K block gets nothing from a Q tile that sees none of it (causal:
    # only Q rows at or below the diagonal count); fully-padded K blocks
    # produce zero dk/dv (init covers them)
    def _compute(mask):
        q = q_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        p, ds = _recompute_p_ds(
            qi, ki, q, k_ref[0, 0], v_ref[0, 0], do,
            lse_ref[0, 0], delta_ref[0, 0],
            sm_scale=sm_scale, mask=mask, block_q=block_q, block_k=block_k,
            kv_len=kv_len,
        )
        # dv += pᵀ·do ; dk += dsᵀ·q
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_live(mask, kv_len, qi, ki, block_q, block_k, _compute, in_band)

    @pl.when(last())
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    k_ref, v_ref,                        # [1,1,bk,d], [1,1,bk,dv]
    q_ref, do_ref, lse_ref, delta_ref,   # [1,1,bq,d], [1,1,bq,dv], [1,1,bq,1]×2
    dq_ref,                              # [1,1,bq,d]
    dq_scr,                              # VMEM f32 [bq,d]
    *, sm_scale: float, mask: BlockMask | None, block_q: int, block_k: int,
    kv_len: int | None = None, band=None, steps=None,
):
    """dq: Q block resident, sweep over K blocks."""
    qi, ki, first, last, in_band = _position(band, steps)

    @pl.when(first())
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(mask):
        k = k_ref[0, 0]
        _, ds = _recompute_p_ds(
            qi, ki, q_ref[0, 0], k, v_ref[0, 0],
            do_ref[0, 0].astype(jnp.float32),
            lse_ref[0, 0], delta_ref[0, 0],
            sm_scale=sm_scale, mask=mask, block_q=block_q, block_k=block_k,
            kv_len=kv_len,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_live(mask, kv_len, qi, ki, block_q, block_k, _compute, in_band)

    @pl.when(last())
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_pallas(res, g, *, mask, sm_scale, block_q, block_k, kv_len=None,
                interpret=None):
    """Pallas dq/dk/dv (FlashAttention-2 backward): two kernels, each
    recomputing p from the saved log-sum-exp — no S×S tensor in HBM."""
    q, k, v, o, lse = res
    b, h, s_q, d = q.shape
    s_k, d_v = k.shape[2], v.shape[3]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    nq, nk = s_q // block_q, s_k // block_k
    if interpret is None:
        interpret = backend.interpret()

    do = g
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [b,h,sq,1]
    # trailing singleton conforms to Mosaic tiling because a block's last dim
    # may EQUAL the array dim (1==1) instead of being 128-divisible — unlike
    # the forward's lse OUTPUT, whose [*,*,bq] block had bq in the lane slot;
    # validated compiled on a real v5e chip (grads match the scan backward)
    lse_c = lse[..., None]  # [b,h,sq,1]

    # q, k, dq, dk are d wide; v, o, do, dv are d_v wide. Each grid names
    # i, the resident block, and j, the streamed one (_grid: the square's
    # indices, a window's band, or the live-step list's entries; dkv: i = k
    # block, j = q block)
    dims, dkv_steps, dkv_band, at_i, at_j = _grid(
        mask, kv_len, nk, nq, block_q, block_k, True)
    kspec = pl.BlockSpec((1, 1, block_k, d), at_i)
    vspec = pl.BlockSpec((1, 1, block_k, d_v), at_i)
    qspec_j = pl.BlockSpec((1, 1, block_q, d), at_j)
    dospec_j = pl.BlockSpec((1, 1, block_q, d_v), at_j)
    rspec_j = pl.BlockSpec((1, 1, block_q, 1), at_j)

    dk, dv = _pallas(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, mask=mask,
            block_q=block_q, block_k=block_k, kv_len=kv_len, band=dkv_band,
        ),
        dkv_steps,
        grid=(b, h, *dims),
        in_specs=[qspec_j, dospec_j, rspec_j, rspec_j, kspec, vspec],
        out_specs=[kspec, vspec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        interpret=interpret,
    )(q, do, lse_c, delta, k, v)

    # dq grid: i = q block, j = k block, the forward's list or band
    dims, dq_steps, dq_band, at_i, at_j = _grid(
        mask, kv_len, nq, nk, block_q, block_k, False)
    qspec = pl.BlockSpec((1, 1, block_q, d), at_i)
    dospec = pl.BlockSpec((1, 1, block_q, d_v), at_i)
    rspec_i = pl.BlockSpec((1, 1, block_q, 1), at_i)
    kspec_j = pl.BlockSpec((1, 1, block_k, d), at_j)
    vspec_j = pl.BlockSpec((1, 1, block_k, d_v), at_j)
    dq = _pallas(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, mask=mask,
            block_q=block_q, block_k=block_k, kv_len=kv_len, band=dq_band,
        ),
        dq_steps,
        grid=(b, h, *dims),
        in_specs=[kspec_j, vspec_j, qspec, dospec, rspec_i, rspec_i],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(k, v, q, do, lse_c, delta)
    return dq, dk, dv


def _bwd_blockwise(res, g, *, mask, sm_scale, block_k, kv_len=None):
    """Blockwise backward from saved (q,k,v,o,lse): lax.scan over K blocks.

    Standard flash backward identities with the row log-sum-exp:
      p   = exp(q·kᵀ·scale − lse)
      dv  = pᵀ·do
      dp  = do·vᵀ;  δ = rowsum(do ∘ o)
      ds  = p ∘ (dp − δ) · scale
      dq  = Σ_blocks ds·k;   dk = dsᵀ·q
    Never materializes more than [S_q, block_k] of p/ds.
    """
    q, k, v, o, lse = res
    b, h, s_q, d = q.shape
    s_k, d_v = k.shape[2], v.shape[3]
    block_k = min(block_k, s_k)
    nk = s_k // block_k

    qf = q.astype(jnp.float32)
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True)  # [b,h,sq,1]
    lse_e = lse[..., None]  # [b,h,sq,1]
    q_pos = jnp.arange(s_q)[:, None]

    # [nk, b, h, block_k, d] scan layout
    kb = k.astype(jnp.float32).reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.astype(jnp.float32).reshape(b, h, nk, block_k, d_v).transpose(2, 0, 1, 3, 4)

    def one_block(dq_acc, inp):
        ki, kblk, vblk = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk) * sm_scale
        keep = _keep(mask, kv_len, q_pos,
                     ki * block_k + jnp.arange(block_k)[None, :])
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse_e)                     # [b,h,sq,bk]
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, do)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vblk)
        ds = p * (dp - delta) * sm_scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kblk)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros((b, h, s_q, d), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(one_block, dq0, (jnp.arange(nk), kb, vb))
    dk = dk.transpose(1, 2, 0, 3, 4).reshape(b, h, s_k, d)
    dv = dv.transpose(1, 2, 0, 3, 4).reshape(b, h, s_k, d_v)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, mask, sm_scale, block_q, block_k, pallas_bwd, kv_len):
    o, _ = _flash_fwd(
        q, k, v, mask=mask, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    return o


def _flash_vjp_fwd(q, k, v, mask, sm_scale, block_q, block_k, pallas_bwd,
                   kv_len):
    """Forward rule: the output, and ``(q, k, v, o, lse)`` for the backward.

    ``o`` and the [B,H,S] float32 ``lse`` (not the lane-padded buffer the
    kernel writes) carry the names of ``tpudist/remat.py``
    ``KERNEL_RESIDUALS``, given BEFORE the residuals and the output are
    built, so that what a ``dots_saveable`` checkpoint keeps, what the
    backward reads and what flows on to the output projection are one
    value: a block's backward then finds both kept and does not launch
    the forward kernel again. ``q``, ``k``, ``v`` carry no name: layout
    work on kept projection outputs, which the backward kernels need made
    again anyway. Outside a ``jax.checkpoint`` a name lowers to nothing.
    """
    o, lse = _flash_fwd(
        q, k, v, mask=mask, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    o, lse = map(checkpoint_name, (o, lse), KERNEL_RESIDUALS)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(mask, sm_scale, block_q, block_k, pallas_bwd, kv_len,
                   res, g):
    if pallas_bwd and not backend.interpret():
        return _bwd_pallas(
            res, g, mask=mask, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
        )
    return _bwd_blockwise(res, g, mask=mask, sm_scale=sm_scale,
                          block_k=block_k, kv_len=kv_len)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q, k, v, *, causal: bool = False, mask: BlockMask | None = None,
    block_q: int | None = None, block_k: int | None = None,
    pallas_bwd: bool | None = None, kv_len: int | None = None,
):
    """Flash attention on [B, S, H, D] inputs (same layout as
    :func:`tpudist.ops.attention.dot_product_attention`). ``q`` and ``k``
    share one width, ``v`` may have another (latent attention: keys of
    192, values of 128): the output is ``v``'s width, the scale
    ``1/sqrt`` of the keys', and a width that is a multiple of 64 past
    128 reaches the kernels unpadded.

    ``mask`` is a structured mask (:class:`~tpudist.ops.attention.
    BlockMask`); ``causal=True`` is its instance ``BlockMask()``, and the
    two lower to the same kernels. A mask over a noised and a clean copy
    fixes where the second copy starts, so its ``noised_len`` must be a
    multiple of 128 (nothing is padded) and the blocks divide it. A
    sliding window (``BlockMask(window=W)``) runs on the band's grid
    (:func:`_band`).

    Unaligned S is padded to the 128-tile multiple: padded KEYS are masked
    inside the kernels (``kv_len`` — also passable explicitly for
    right-padded batches), padded query rows are sliced off the output.

    ``pallas_bwd`` selects the Pallas FA-2 backward kernels instead of the
    blockwise-scan backward. Both are O(S·block) memory; the one shape
    measured on the chip is :func:`default_blocks`' (heads of 128 at 4096
    tokens, where the kernels win). TPU-only: on
    other backends the flag is ignored and the scan backward runs.
    ``block_q``, ``block_k`` and ``pallas_bwd`` left at ``None`` follow the
    shape (:func:`default_blocks`).
    """
    if q.ndim != 4:
        raise NotImplementedError(f"expected [B,S,H,D], got {q.shape}")
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if kv_len is None:
        kv_len = s_k
    if causal:
        if mask is not None and not mask.causal:
            raise ValueError("pass causal=True or a mask, not both")
        mask = CAUSAL
    if mask is not None and s_q != s_k:
        raise NotImplementedError("a masked call assumes s_q == s_k")
    if mask is not None and mask.noised_len and (
            s_q != 2 * mask.noised_len or mask.noised_len % 128):
        raise NotImplementedError(
            f"a mask over two copies of {mask.noised_len} rows needs "
            f"2 x that many rows (got {s_q}) and a multiple of 128")
    sm_scale = 1.0 / float(np.sqrt(d))
    # Pad ragged sequences to the 128-tile multiple; the kernels mask the
    # padded keys via kv_len and padded query rows are sliced off below.
    pad_q = -s_q % 128
    pad_k = -s_k % 128
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # kv_len == padded length means "nothing masked": drop it so the
    # kernels skip the mask compare entirely
    eff_kv = None if kv_len == k.shape[1] else kv_len
    # Pad each head width to the 128-lane tile (_lane_pad: a width past one
    # tile only to the half tile). Zero-padded q/k leave scores unchanged;
    # padded v columns produce output columns sliced off below.
    d_v = v.shape[3]
    def pad(x):
        extra = _lane_pad(x.shape[3])
        return jnp.pad(x, [(0, 0)] * 3 + [(0, extra)]) if extra else x

    q, k, v = pad(q), pad(k), pad(v)
    # blocks follow the shape; under a mask over two copies they divide
    # one copy, so that no tile straddles the two (BlockMask.tile_live)
    copy = mask.noised_len if mask is not None and mask.noised_len else None
    auto = default_blocks(copy or q.shape[1], copy or k.shape[1], d_v,
                          mask.window if mask is not None else 0)
    block_q, block_k, pallas_bwd = (
        given if given is not None else chosen
        for given, chosen in zip((block_q, block_k, pallas_bwd), auto)
    )
    # [B,S,H,D] → [B,H,S,D] for contiguous per-head tiles
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if copy and (copy % min(block_q, s_q) or copy % min(block_k, s_k)):
        raise NotImplementedError(
            f"blocks {block_q} x {block_k} do not divide a copy of {copy}")
    o = _flash(qt, kt, vt, mask, sm_scale, block_q, block_k, pallas_bwd,
               eff_kv)
    return o.transpose(0, 2, 1, 3)[:, :s_q, :, :d_v]
