"""Context (sequence) parallelism: ring attention and Ulysses all-to-all.

No reference counterpart (SURVEY.md §5 "long-context: ABSENT" — the
reference's workload is a CNN); built because long-sequence scaling is a
first-class capability of this framework. Two schemes over the ``seq`` mesh
axis, both SPMD via ``shard_map``:

- **Ring attention** (:func:`ring_attention`): Q stays put, K/V chunks rotate
  around the ``seq`` ring with ``lax.ppermute`` (ICI neighbor exchange) while
  each step's partial attention is merged with the online-softmax rescale —
  the S×S score matrix never exists and peak memory is
  O(S_local × S_local) per device. The per-hop transfer overlaps with the
  current chunk's compute under XLA's async collectives.
- **Ulysses** (:func:`ulysses_attention`): ``lax.all_to_all`` re-shards
  [seq-sharded, all heads] → [all seq, head-sharded], runs plain (flash)
  attention per head group over the full sequence, and re-shards back.
  Cheaper collectives for moderate S; requires num_heads % seq_axis == 0.

Both are differentiable (``ppermute``/``all_to_all`` have transpose rules),
so they drop into the compiled train step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.mesh import DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS

NEG_INF = float(np.finfo(np.float32).min)


def _chunk_scores(q, k, *, sm_scale, causal, q_off, k_off):
    """Masked f32 attention scores of a local Q chunk vs one K chunk.

    q: [B, Sq, H, D], k: [B, Sk, H, D] → [B, H, Sq, Sk]; ``q_off``/``k_off``
    are the chunks' global sequence offsets (traced values are fine — the
    mask is data-dependent on positions, not shapes).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        s_q, s_k = s.shape[-2], s.shape[-1]
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    return s


def _online_merge(m, l, acc, s, v):
    """Fold one chunk's scores+values into the online-softmax state.

    m,l: [B,H,Sq,1] f32; acc: [B,Sq,H,D] f32; s: [B,H,Sq,Sk]; v: [B,Sk,H,D].
    Safe when a chunk is fully masked (m stays NEG_INF, contribution 0).
    """
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    # avoid NEG_INF - NEG_INF = nan: fully-masked rows get exp(·)=0 via s=NEG_INF
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    alpha = jnp.exp(jnp.where(m <= NEG_INF, NEG_INF, m - m_safe))  # [B,H,Sq,1]
    p = jnp.exp(s - m_safe)                                        # [B,H,Sq,Sk]
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    alpha_q = alpha.squeeze(-1).transpose(0, 2, 1)[..., None]      # [B,Sq,H,1]
    return m_new, l_new, acc * alpha_q + pv


def ring_attention_local(
    q, k, v, *, axis_name: str = SEQUENCE_AXIS, causal: bool = False
):
    """Per-shard ring attention body — call inside ``shard_map``.

    q, k, v: this device's sequence chunk, [B, S_local, H, D]. The K/V pair
    makes ``axis_size`` hops around the ring; hop ``t`` processes the chunk
    originally owned by device ``(idx - t) mod n``.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    sm_scale = 1.0 / float(np.sqrt(d))
    q_off = idx * s_local

    perm = [(j, (j + 1) % n) for j in range(n)]

    def hop(carry, t):
        k_cur, v_cur, m, l, acc = carry
        src = (idx - t) % n
        s = _chunk_scores(
            q, k_cur, sm_scale=sm_scale, causal=causal,
            q_off=q_off, k_off=src * s_local,
        )
        m, l, acc = _online_merge(m, l, acc, s, v_cur)
        # rotate AFTER compute; skip the final (wasted) hop via cond-free
        # trick: permuting on the last step is harmless and keeps the scan
        # body uniform — XLA overlaps it with the merge.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    m0 = jnp.full((b, h, s_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    # the zero-init carries must carry the same varying-manual-axes type as
    # the per-shard compute results, or scan rejects the carry signature
    vma = tuple(jax.typeof(q).vma)
    if vma:
        m0, l0, acc0 = (jax.lax.pcast(x, vma, to="varying") for x in (m0, l0, acc0))
    (k, v, m, l, acc), _ = jax.lax.scan(
        hop, (k, v, m0, l0, acc0), jnp.arange(n)
    )
    l_q = l.squeeze(-1).transpose(0, 2, 1)[..., None]  # [B,Sq,H,1]
    out = acc / jnp.where(l_q == 0.0, 1.0, l_q)
    return out.astype(q.dtype)


def ring_attention(
    q, k, v, mesh: Mesh, *, causal: bool = False,
    batch_axes=(DATA_AXIS, FSDP_AXIS), seq_axis: str = SEQUENCE_AXIS,
):
    """Ring attention on global [B, S, H, D] arrays: batch over ``data``,
    sequence over ``seq``."""
    spec = P(batch_axes, seq_axis, None, None)
    fn = shard_map(
        functools.partial(ring_attention_local, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return fn(q, k, v)


def ulysses_attention_local(
    q, k, v, *, axis_name: str = SEQUENCE_AXIS, causal: bool = False,
    attn_fn=None,
):
    """Per-shard Ulysses body — call inside ``shard_map``.

    Input [B, S/n, H, D] (sequence-sharded) → all_to_all →
    [B, S, H/n, D] (head-sharded) → full-sequence attention on the local
    head group → all_to_all back. ``attn_fn(q, k, v, causal=...)`` defaults
    to the XLA-oracle attention; pass the flash kernel for long S.
    """
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(f"num_heads {q.shape[2]} not divisible by seq axis {n}")
    if attn_fn is None:
        from tpudist.ops.attention import dot_product_attention
        attn_fn = dot_product_attention

    def to_heads(x):  # [B, S/n, H, D] → [B, S, H/n, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_seq(x):    # [B, S, H/n, D] → [B, S/n, H, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    out = attn_fn(to_heads(q), to_heads(k), to_heads(v), causal=causal)
    return to_seq(out)


def ulysses_attention(
    q, k, v, mesh: Mesh, *, causal: bool = False,
    batch_axes=(DATA_AXIS, FSDP_AXIS), seq_axis: str = SEQUENCE_AXIS,
    attn_fn=None,
):
    """Ulysses (all-to-all) sequence-parallel attention on global
    [B, S, H, D] arrays."""
    spec = P(batch_axes, seq_axis, None, None)
    fn = shard_map(
        functools.partial(
            ulysses_attention_local, axis_name=seq_axis, causal=causal,
            attn_fn=attn_fn,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call (the flash attn_fn) can't declare varying-manual-axes
        # on its out_shape; keep the vma safety net for the default path
        check_vma=(attn_fn is None),
    )
    return fn(q, k, v)
