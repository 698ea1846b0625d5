"""Whole-sequence-in-VMEM attention: the short/medium-context Pallas kernel.

No reference counterpart (the reference's workload is a CNN,
/root/reference/main.py:40) — this is the framework's hot-op for the
transformer configs at sequence lengths up to 1024 (GPT-2 S=1024, ViT S=197).

Why a third attention path exists
---------------------------------
- XLA einsum attention materializes the [S,S] f32 score tensor in HBM per
  layer per direction.
- The blockwise flash kernel (``tpudist.ops.flash_attention``) eliminates
  that traffic, but pays online-softmax bookkeeping per (128,128) tile and
  a recompute-heavy backward; ``auto`` takes it from S = 2048 on.
- At S ≤ 1024 an ENTIRE head's score matrix fits in VMEM (S=1024 → 4 MB
  f32 of ~16 MB), so this kernel runs one (batch, head) pair per grid
  step with a plain (not online) softmax on the VPU: the whole row is
  resident, scores never touch HBM and no running statistics are carried.
- Bidirectional: ONE q·kᵀ MXU call on the whole S × S tile, one softmax,
  one p·v MXU call.
- Causal (two or more blocks of ``BLOCK_Q`` = 128 query rows): block j
  takes the keys ``[: (j+1)·128]`` only, in ONE q·kᵀ product, and masks
  that whole (128, prefix) score block with one iota compare and one
  ``where`` (only its last 128 columns can fail the compare; masking just
  that diagonal tile, in a product of its own, was measured 0.2 ms a step
  faster for 1.5x the code and dropped, PERF.md §6) — then the same plain
  softmax over the prefix and p·v. The kernel computes ``(n+1)/(2n)`` of
  the square (:func:`computed_tile_share`: 0.5625 at S=1024) and never
  reads the rest. A block's MXU products are
  traced one block ahead of its VPU work (:func:`_one_ahead`).
- The backward is a single kernel per (b, h): recompute p from the saved
  row log-sum-exp, then the FA-2 matmuls (dv, dp, dq, dk) back to back on
  MXU with everything resident in VMEM; causal, tile by tile over the same
  blocks, dk/dv of a block's key prefix accumulating in f32 VMEM scratch.
- Measured in the GPT-2 medium cell (B=8, H=16, S=1024, D=64, bf16, 24
  layers, one v5e; PR 29's traced runs, PERF.md §6): the causal kernel
  takes 7.08 + 14.29 ms a step forward + backward, against 9.63 + 21.69 for
  the whole masked tile. At head size 64 every product half-fills the MXU
  (64 of its 128-deep contraction or of its 128 output columns), which is
  what holds both kernels under a third of the roofline the benchmark counts.

Ragged / padded sequences
-------------------------
TPU tiles want 128-aligned lanes, but callers have S=197 (ViT's 196+cls).
:func:`vmem_attention` pads q/k/v up to the next 128 multiple and masks the
padded KEYS inside the kernel (``kv_len`` — one iota compare per score
tile that holds padding); padded QUERY rows compute garbage that is sliced
off on return. Causal, padded keys lie past every real query's diagonal.
This is what makes the kernel applicable to ViT, where the S² f32 traffic
would otherwise be structural.

Sizing rule: the kernel refuses S_pad > MAX_SEQ (per-(b,h) VMEM footprint
is a handful of [S,S] f32 buffers); longer sequences belong to the
blockwise flash kernel. ``tpudist.ops.attention.multi_head_attention``
routes ``impl="auto"`` accordingly.

Numerics: scores/softmax in f32 regardless of input dtype; p/ds cast to
the input dtype for the backward MXU calls (the FA-2 convention). Matches
``dot_product_attention`` to ~1e-2 in bf16, ~1e-5 in f32 (interpret mode).
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
from tpudist.remat import KERNEL_RESIDUALS

NEG_INF = float(np.finfo(np.float32).min)

# per-(b,h) VMEM budget: bwd keeps ~4 [S,S] f32/bf16 intermediates live;
# S=1024 → ~14 MB of ~16 MB compiles and runs (the cells' shape); S=2048
# would need 4×.
MAX_SEQ = 1024

# rows of one causal query block: the padding granule, so it divides every
# padded length. On the chip 128 beat 256 and 512 at every S from 256 to 1024
# (PERF.md §6, PR 29): shorter blocks compute less of the square, and what
# their shorter MXU passes cost is won back by issuing a block's products ahead
BLOCK_Q = 128


def computed_tile_share(s_pad: int, causal: bool) -> float:
    """Share of a head's S x S score square that the kernel computes: 1.0
    bidirectional; causal, each of the ``n = s_pad / BLOCK_Q`` query blocks
    takes the keys up to its own last row, ``(n + 1) / (2 n)`` of the
    square (one block is the whole square under its mask)."""
    n = s_pad // BLOCK_Q if causal else 1
    return (n + 1) / (2 * n)


def _mm(a, b, ca, cb):
    """MXU product contracting ``a``'s dim ``ca`` with ``b``'s ``cb``, f32 out."""
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=jnp.float32
    )


def _masked_scores(q, k, sm_scale, *, causal, kv_len, row0=0):
    """Scaled q·kᵀ with the causal and padded-key masks; ``row0`` is the
    position of ``q``'s first row among the keys (a query block's offset)."""
    s = _mm(q, k, 1, 1) * sm_scale
    s_q, s_k = s.shape
    need_kv_mask = kv_len is not None and kv_len < s_k
    if causal or need_kv_mask:
        kp = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        keep = jnp.ones(s.shape, bool)
        if causal:
            qp = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
            keep = qp + row0 >= kp if row0 else qp >= kp
        if need_kv_mask:
            keep &= kp < kv_len
        s = jnp.where(keep, s, NEG_INF)
    return s


def _loop_heads(group: int, body):
    """Run ``body(i)`` for the block's ``group`` heads. group==1 stays
    straight-line; grouped blocks use fori_loop (compiles one head's code,
    reuses the per-head VMEM scratch across iterations; far cheaper to
    compile than a full unroll)."""
    if group == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, group, lambda i, _: (body(i), 0)[1], 0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, sm_scale, causal, kv_len, group, kv_shared):
    def one(i):
        q = q_ref[0, i]  # [Sq, D]
        # GQA (kv_shared): the whole q-head block reads ONE resident K/V
        # head — grouped K/V never get repeated in HBM
        k = k_ref[0, 0] if kv_shared else k_ref[0, i]  # [Sk, D]
        v = v_ref[0, 0] if kv_shared else v_ref[0, i]
        s = _masked_scores(q, k, sm_scale, causal=causal, kv_len=kv_len)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = _mm(p.astype(v.dtype), v, 1, 0)
        o_ref[0, i] = (o / l).astype(o_ref.dtype)
        lse_ref[0, i] = m + jnp.log(l)

    _loop_heads(group, one)


def _zero_on_first_visit(dk_ref, dv_ref, group, ratio):
    """GQA: the ratio consecutive grid steps mapping to one K/V head revisit
    the SAME dk/dv output block (Pallas keeps a revisited block resident
    between consecutive steps); zero it on the first visiting step, the
    kernels accumulate on every one."""
    @pl.when((pl.program_id(1) * group) % ratio == 0)
    def _init():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref,
                *, sm_scale, causal, kv_len, group, kv_shared, ratio):
    if kv_shared:
        _zero_on_first_visit(dk_ref, dv_ref, group, ratio)

    def one(i):
        q = q_ref[0, i]
        k = k_ref[0, 0] if kv_shared else k_ref[0, i]
        v = v_ref[0, 0] if kv_shared else v_ref[0, i]
        o = o_ref[0, i].astype(jnp.float32)
        do = do_ref[0, i].astype(jnp.float32)
        lse = lse_ref[0, i]  # [Sq, 1] f32
        s = _masked_scores(q, k, sm_scale, causal=causal, kv_len=kv_len)
        p = jnp.exp(s - lse)  # [Sq, Sk] f32; exact probs (no rescale needed)
        pb = p.astype(v.dtype)
        dob = do.astype(v.dtype)
        dv = _mm(pb, dob, 0, 0)
        dp = _mm(dob, v, 1, 1)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [Sq, 1]
        ds = (p * (dp - delta) * sm_scale).astype(v.dtype)
        dq_ref[0, i] = _mm(ds, k, 1, 0).astype(dq_ref.dtype)
        dk = _mm(ds, q, 0, 0)
        if kv_shared:
            # every q-head in the block feeds the one K/V head's grads
            dv_ref[0, 0] += dv.astype(dv_ref.dtype)
            dk_ref[0, 0] += dk.astype(dk_ref.dtype)
        else:
            dv_ref[0, i] = dv.astype(dv_ref.dtype)
            dk_ref[0, i] = dk.astype(dk_ref.dtype)

    _loop_heads(group, one)


def _one_ahead(stage, s_pad):
    """``(rows, stage(rows))`` for every block of ``BLOCK_Q`` query rows,
    ``stage`` of the NEXT block traced before the current one is handed
    out. Mosaic schedules a straight-line kernel close to program order, so
    a block's MXU products issued one block early run under the previous
    block's VPU passes: compiled for a v5e the S=1024 forward is 3081
    bundles a head against 3666 in plain order, the backward 6088 against
    7247 (the whole tile: 4393 and 9617)."""
    blocks = [slice(lo, lo + BLOCK_Q) for lo in range(0, s_pad, BLOCK_Q)]
    ahead = stage(blocks[0])
    for n, rows in enumerate(blocks):
        now, ahead = ahead, (
            stage(blocks[n + 1]) if n + 1 < len(blocks) else None
        )
        yield rows, now


def _fwd_kernel_blocked(q_ref, k_ref, v_ref, o_ref, lse_ref,
                        *, sm_scale, kv_len, group, kv_shared):
    """Causal forward by query blocks: a block scores its rows against the
    keys up to its own last row only (later keys are never read), then a
    PLAIN softmax over that prefix (the whole row is resident — no online
    rescaling) and p·v."""
    def one(i):
        ik = 0 if kv_shared else i

        def scores(rows):
            return _masked_scores(
                q_ref[0, i, rows], k_ref[0, ik, :rows.stop], sm_scale,
                causal=True, kv_len=kv_len, row0=rows.start,
            )

        for rows, s in _one_ahead(scores, q_ref.shape[2]):
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = _mm(p.astype(v_ref.dtype), v_ref[0, ik, :rows.stop], 1, 0)
            o_ref[0, i, rows] = (o / l).astype(o_ref.dtype)
            lse_ref[0, i, rows] = m + jnp.log(l)

    _loop_heads(group, one)


def _bwd_kernel_blocked(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                        *, sm_scale, kv_len, group, kv_shared, ratio):
    """Causal backward by the forward's query blocks: per block recompute p
    from the saved lse, then the FA-2 products. dq of a block is complete
    in its step; dk/dv of the block's key prefix accumulate in the f32 VMEM
    scratch, written out once a head (GQA: added to the f32 output block
    the group's heads revisit)."""
    if kv_shared:
        _zero_on_first_visit(dk_ref, dv_ref, group, ratio)
    dt = v_ref.dtype

    def one(i):
        ik = 0 if kv_shared else i
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

        def scores_and_dp(rows):
            q = q_ref[0, i, rows]
            do = do_ref[0, i, rows].astype(jnp.float32)
            dob = do.astype(dt)
            delta = jnp.sum(
                do * o_ref[0, i, rows].astype(jnp.float32),
                axis=-1, keepdims=True,
            )
            s = _masked_scores(
                q, k_ref[0, ik, :rows.stop], sm_scale,
                causal=True, kv_len=kv_len, row0=rows.start,
            )
            return q, dob, delta, s, _mm(dob, v_ref[0, ik, :rows.stop], 1, 1)

        for rows, (q, dob, delta, s, dp) in _one_ahead(
                scores_and_dp, q_ref.shape[2]):
            keys = slice(0, rows.stop)
            p = jnp.exp(s - lse_ref[0, i, rows])
            dv_acc[keys] += _mm(p.astype(dt), dob, 0, 0)
            ds = (p * (dp - delta) * sm_scale).astype(dt)
            dq_ref[0, i, rows] = _mm(ds, k_ref[0, ik, keys], 1, 0).astype(
                dq_ref.dtype)
            dk_acc[keys] += _mm(ds, q, 0, 0)
        if kv_shared:
            dk_ref[0, 0] += dk_acc[...]
            dv_ref[0, 0] += dv_acc[...]
        else:
            dk_ref[0, i] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, i] = dv_acc[...].astype(dv_ref.dtype)

    _loop_heads(group, one)


def _head_group(h: int, s_pad: int) -> int:
    """Heads per grid step. Small-S shapes (ViT's 256) are overhead-bound
    at one (b, h) pair per step — 1536 near-empty grid steps for ViT-B —
    so group as many heads as the VMEM budget allows (the per-head score
    scratch is reused across the in-kernel loop; only the IO blocks scale
    with the group; no vision cell: not measured on the chip). Long S
    keeps group=1 — the
    per-step work is already large and the [S,S] scratch leaves no room."""
    if s_pad > 512:
        return 1
    for cand in range(h, 0, -1):
        if h % cand == 0 and cand * s_pad <= 3072:
            return cand
    return 1


def _struct(shape, dtype, like):
    """``ShapeDtypeStruct`` carrying ``like``'s varying-manual-axes type:
    inside a partial-manual ``shard_map`` (e.g. the GPipe schedule's
    pipe-manual region, tpudist.parallel.pp) every pallas output must
    declare how it varies over the manual axes or the shard_map's vma
    check rejects the call."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _spec(g, s, d):
    return pl.BlockSpec((1, g, s, d), lambda b, hg: (b, hg, 0, 0))


def _geometry(q, k):
    """(group, ratio, kv_shared, kv_spec) for the grid. MHA: K/V blocks
    mirror the q-head grouping. GQA (fewer K/V heads): each grid step's
    q-head block reads its ONE K/V head — the group is clamped to divide
    the q-per-kv ratio so a block never spans two K/V heads, and the K/V
    BlockSpec maps grid step hg to kv head (hg·g)/ratio."""
    import math

    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    if h % h_kv:
        raise NotImplementedError(
            f"q heads {h} not a multiple of kv heads {h_kv}"
        )
    ratio = h // h_kv
    g = _head_group(h, max(s_q, s_k))
    if ratio > 1:
        g = math.gcd(g, ratio)
        kv_spec = pl.BlockSpec(
            (1, 1, s_k, d),
            lambda b, hg, _g=g, _r=ratio: (b, (hg * _g) // _r, 0, 0),
        )
        return g, ratio, True, kv_spec
    return g, 1, False, _spec(g, s_k, d)


# Both ``pallas_call``s sit in a ``jax.jit(inline=True)``: a model's layers
# call them with the same shapes, so the second to the last layer inline the
# first one's cached jaxpr instead of tracing the kernel again (unrolled over
# eight blocks it is some hundreds of equations: a 24-layer step traced 20 s
# longer without this, PR 29). Inlined, every layer's ``pallas_call`` equation
# carries the SAME parameters, so JAX also lowers the kernel to Mosaic once a
# step and not once a layer; the equation itself stays directly under the
# caller's scope, where a trace reader looks for it (``h_<n>.<k>``).
_traced_once = functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "kv_len", "interpret"),
    inline=True)


@_traced_once
def _vmem_fwd_raw(q, k, v, *, causal, sm_scale, kv_len, interpret):
    b, h, s_q, d = q.shape
    g, ratio, kv_shared, kv_spec = _geometry(q, k)
    body = (_fwd_kernel_blocked if computed_tile_share(s_q, causal) < 1.0
            else functools.partial(_fwd_kernel, causal=causal))
    kern = functools.partial(
        body, sm_scale=sm_scale, kv_len=kv_len, group=g, kv_shared=kv_shared,
    )
    return pl.pallas_call(
        kern,
        grid=(b, h // g),
        in_specs=[_spec(g, s_q, d), kv_spec, kv_spec],
        out_specs=[_spec(g, s_q, d), _spec(g, s_q, 1)],
        out_shape=[
            _struct(q.shape, q.dtype, q),
            _struct((b, h, s_q, 1), jnp.float32, q),
        ],
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _vmem(q, k, v, causal, sm_scale, kv_len):
    o, _ = _vmem_vjp_fwd(q, k, v, causal, sm_scale, kv_len)
    return o


def _vmem_vjp_fwd(q, k, v, causal, sm_scale, kv_len):
    """Forward rule: the output, and ``(q, k, v, o, lse)`` for the backward.
    ``o`` and ``lse`` carry the names of ``tpudist/remat.py``
    ``KERNEL_RESIDUALS``, given before the residuals and the output are
    built: a ``dots_saveable`` checkpoint keeps both and a block's backward
    does not run the forward kernel again (as ``flash_attention``'s rule).
    Outside a ``jax.checkpoint`` a name lowers to nothing."""
    o, lse = _vmem_fwd_raw(
        q, k, v, causal=causal, sm_scale=sm_scale, kv_len=kv_len,
        interpret=backend.interpret(),
    )
    o, lse = map(checkpoint_name, (o, lse), KERNEL_RESIDUALS)
    return o, (q, k, v, o, lse)


@_traced_once
def _vmem_bwd_raw(q, k, v, o, lse, g, *, causal, sm_scale, kv_len, interpret):
    b, h, s_q, d = q.shape
    grp, ratio, kv_shared, kv_spec = _geometry(q, k)
    blocked = computed_tile_share(s_q, causal) < 1.0
    body = (_bwd_kernel_blocked if blocked
            else functools.partial(_bwd_kernel, causal=causal))
    kern = functools.partial(
        body, sm_scale=sm_scale, kv_len=kv_len, group=grp,
        kv_shared=kv_shared, ratio=ratio,
    )
    # GQA: dk/dv accumulate ratio/grp revisits (plus grp in-block q-heads)
    # into the same output block — accumulate in f32, cast after
    kv_grad_dtype = jnp.float32 if kv_shared else k.dtype
    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(b, h // grp),
        in_specs=[_spec(grp, s_q, d), kv_spec, kv_spec,
                  _spec(grp, s_q, d), _spec(grp, s_q, d), _spec(grp, s_q, 1)],
        out_specs=[_spec(grp, s_q, d), kv_spec, kv_spec],
        out_shape=[
            _struct(q.shape, q.dtype, q),
            _struct(k.shape, kv_grad_dtype, k),
            _struct(v.shape, kv_grad_dtype, v),
        ],
        # the blocked backward sums a head's dk / dv over its blocks in f32
        scratch_shapes=(
            [pltpu.VMEM(k.shape[2:], jnp.float32)] * 2 if blocked else []
        ),
        interpret=interpret,
    )(q, k, v, o, g, lse)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _vmem_vjp_bwd(causal, sm_scale, kv_len, res, g):
    q, k, v, o, lse = res
    return _vmem_bwd_raw(
        q, k, v, o, lse, g, causal=causal, sm_scale=sm_scale, kv_len=kv_len,
        interpret=backend.interpret(),
    )


_vmem.defvjp(_vmem_vjp_fwd, _vmem_vjp_bwd)


def vmem_attention(q, k, v, *, causal: bool = False, kv_len: int | None = None):
    """Attention on [B, S, H, D] inputs (the models' layout, matching
    :func:`tpudist.ops.attention.dot_product_attention`).

    Unaligned S is padded to the next 128 multiple: padded keys are masked
    inside the kernel (``kv_len``), padded query rows are sliced off the
    output. ``kv_len`` may also be passed explicitly for right-padded
    batches whose true key length is shorter than S (every sequence in the
    batch shares it — a static int, not a per-row tensor).

    GQA: ``k``/``v`` may carry fewer heads than ``q`` (heads divisible).
    The kernel reads each K/V head once per query group straight from the
    grouped layout — no ``jnp.repeat`` materializes in HBM — and the
    backward accumulates the group's dk/dv in f32.

    Raises NotImplementedError for S_pad > MAX_SEQ (VMEM budget) — callers
    (``multi_head_attention(impl="auto")``) route long sequences to the
    blockwise flash kernel instead.
    """
    if q.ndim != 4:
        raise NotImplementedError(f"expected [B,S,H,D], got {q.shape}")
    if not q.shape[3] == k.shape[3] == v.shape[3]:
        raise NotImplementedError(
            f"vmem attention takes one head size for q, k and v, got "
            f"{q.shape[3]} / {k.shape[3]} / {v.shape[3]} — the flash kernel "
            "takes a key width and a value width"
        )
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if kv_len is None:
        kv_len = s_k
    pad_q = -s_q % 128
    pad_k = -s_k % 128
    if s_q + pad_q > MAX_SEQ or s_k + pad_k > MAX_SEQ:
        raise NotImplementedError(
            f"vmem attention holds whole [S,S] scores in VMEM; S_pad="
            f"{max(s_q + pad_q, s_k + pad_k)} > {MAX_SEQ} — use the "
            "blockwise flash kernel for long sequences"
        )
    if causal and s_q != s_k:
        raise NotImplementedError("causal path assumes s_q == s_k")
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sm_scale = 1.0 / float(np.sqrt(d))
    # [B,S,H,D] → [B,H,S,D] for contiguous per-(b,h) tiles
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o = _vmem(qt, kt, vt, causal, sm_scale, kv_len)
    return o.transpose(0, 2, 1, 3)[:, :s_q]
