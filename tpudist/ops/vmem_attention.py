"""Whole-sequence-in-VMEM attention: the short/medium-context Pallas kernel.

No reference counterpart (the reference's workload is a CNN,
/root/reference/main.py:40) — this is the framework's hot-op for the
transformer configs at bench sequence lengths (GPT-2 S=1024, ViT S=197).

Why a third attention path exists
---------------------------------
- XLA einsum attention materializes the [S,S] f32 score tensor in HBM per
  layer per direction — the dominant byte term of the GPT-2 step
  (docs/PERF.md §4) and of ViT (§6).
- The blockwise flash kernel (``tpudist.ops.flash_attention``) eliminates
  that traffic, but pays online-softmax bookkeeping per (128,128) tile and
  a recompute-heavy backward; on v5e it only wins from S≈2048.
- At S ≤ 1024 an ENTIRE head's score matrix fits in VMEM (S=1024 → 4 MB
  f32 of ~16 MB), so this kernel runs one (batch, head) pair per grid
  step: ONE q·kᵀ MXU call, one plain (not online) softmax on the VPU, one
  p·v MXU call — scores never touch HBM and there is no per-tile loop
  overhead. Measured fwd+bwd at GPT-2 shapes (B=8, H=12, S=1024, D=64,
  bf16, interleaved repeats on one v5e): **4.2 ms vs 9.5 ms XLA** vs
  10.8/13.4 ms for the blockwise flash variants.
- The backward is a single kernel per (b, h): recompute p from the saved
  row log-sum-exp, then the four FA-2 matmuls (dv, dp, dq, dk) back to
  back on MXU with everything resident in VMEM.

Ragged / padded sequences
-------------------------
TPU tiles want 128-aligned lanes, but callers have S=197 (ViT's 196+cls).
:func:`vmem_attention` pads q/k/v up to the next 128 multiple and masks the
padded KEYS inside the kernel (``kv_len`` — one iota compare per score
tile); padded QUERY rows compute garbage that is sliced off on return.
This is what makes the kernel applicable to ViT, where the S² f32 traffic
was previously "structural" (docs/PERF.md §6).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (VMEM scratch if needed)

from tpudist.ops import backend

NEG_INF = float(np.finfo(np.float32).min)

# per-(b,h) VMEM budget: bwd keeps ~4 [S,S] f32/bf16 intermediates live;
# S=1024 → ~14 MB of ~16 MB works (measured); S=2048 would need 4×.
MAX_SEQ = 1024


def _masked_scores(q, k, sm_scale, *, causal, kv_len):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    s_q, s_k = s.shape
    need_kv_mask = kv_len is not None and kv_len < s_k
    if causal or need_kv_mask:
        kp = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        keep = jnp.ones(s.shape, bool)
        if causal:
            qp = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
            keep = qp >= kp
        if need_kv_mask:
            keep &= kp < kv_len
        s = jnp.where(keep, s, NEG_INF)
    return s


def _loop_heads(group: int, body):
    """Run ``body(i)`` for the block's ``group`` heads. group==1 stays
    straight-line; grouped blocks use fori_loop (compiles one head's code,
    reuses the per-head VMEM scratch across iterations — measured within 2%
    of a full unroll at ViT shapes, far cheaper to compile)."""
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
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, i] = (o / l).astype(o_ref.dtype)
        lse_ref[0, i] = m + jnp.log(l)

    _loop_heads(group, one)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref,
                *, sm_scale, causal, kv_len, group, kv_shared, ratio):
    if kv_shared:
        # GQA: the ratio consecutive grid steps mapping to one K/V head
        # revisit the SAME dk/dv output block (Pallas keeps a revisited
        # block resident between consecutive steps); zero it on the first
        # visiting step, accumulate on the rest
        hg = pl.program_id(1)
        first_visit = (hg * group) % ratio == 0

        @pl.when(first_visit)
        def _init():
            dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
            dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

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
        dv = jax.lax.dot_general(
            pb, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [Sq, 1]
        ds = (p * (dp - delta) * sm_scale).astype(v.dtype)
        dq_ref[0, i] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(dq_ref.dtype)
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        if kv_shared:
            # every q-head in the block feeds the one K/V head's grads
            dv_ref[0, 0] += dv.astype(dv_ref.dtype)
            dk_ref[0, 0] += dk.astype(dk_ref.dtype)
        else:
            dv_ref[0, i] = dv.astype(dv_ref.dtype)
            dk_ref[0, i] = dk.astype(dk_ref.dtype)

    _loop_heads(group, one)


def _head_group(h: int, s_pad: int) -> int:
    """Heads per grid step. Small-S shapes (ViT's 256) are overhead-bound
    at one (b, h) pair per step — 1536 near-empty grid steps for ViT-B —
    so group as many heads as the VMEM budget allows (the per-head score
    scratch is reused across the in-kernel loop; only the IO blocks scale
    with the group). Measured at ViT shapes on v5e: 5.0 ms grouped vs
    5.8 ms ungrouped vs 7.0 ms XLA (fwd+bwd). Long S keeps group=1 — the
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


def _vmem_fwd_raw(q, k, v, *, causal, sm_scale, kv_len):
    b, h, s_q, d = q.shape
    g, ratio, kv_shared, kv_spec = _geometry(q, k)
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, kv_len=kv_len,
        group=g, kv_shared=kv_shared,
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
        interpret=backend.interpret(),
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _vmem(q, k, v, causal, sm_scale, kv_len):
    o, _ = _vmem_fwd_raw(q, k, v, causal=causal, sm_scale=sm_scale, kv_len=kv_len)
    return o


def _vmem_vjp_fwd(q, k, v, causal, sm_scale, kv_len):
    o, lse = _vmem_fwd_raw(q, k, v, causal=causal, sm_scale=sm_scale, kv_len=kv_len)
    return o, (q, k, v, o, lse)


def _vmem_vjp_bwd(causal, sm_scale, kv_len, res, g):
    q, k, v, o, lse = res
    b, h, s_q, d = q.shape
    grp, ratio, kv_shared, kv_spec = _geometry(q, k)
    kern = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, kv_len=kv_len,
        group=grp, kv_shared=kv_shared, ratio=ratio,
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
        interpret=backend.interpret(),
    )(q, k, v, o, g, lse)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


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
