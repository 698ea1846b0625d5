"""Attention ops for the transformer models (ViT, GPT-2, Llama, BERT).

The reference contains no attention (its workload is a CNN, SURVEY.md §5
"long-context: ABSENT") — these ops serve the BASELINE ladder's transformer
configs (ViT-B/16, GPT-2 124M). Three paths, dispatched by
:func:`multi_head_attention` (``impl="auto"`` picks by sequence length):

- ``dot_product_attention``: plain XLA einsum attention — the correctness
  oracle, and the only path that takes arbitrary masks (a structured
  :class:`BlockMask` goes to the flash kernel too).
- ``tpudist.ops.vmem_attention``: whole-sequence-in-VMEM Pallas kernel for
  S ≤ 1024 — one plain softmax per (batch, head) grid step, no tile loop;
  the path the benchmark's GPT-2 and BERT cells run (PERF.md §4).
- ``tpudist.ops.flash_attention``: blockwise FA-2 Pallas kernel for long
  sequences (≥ 2048) — online softmax so the S×S scores never exist.

Both kernels pad ragged S to the 128-tile multiple and mask padded keys
in-kernel (``kv_len``).
"""

from __future__ import annotations

import dataclasses
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class BlockMask:
    """A structured attention mask, stated by index arithmetic: the one
    description the dense path and the flash kernels share (a caller
    passes it as ``mask=``; it is hashable, so it rides as a static
    argument).

    Rows and keys are the same ``S`` positions. The first ``noised_len``
    of them are the NOISED copy of a sequence and the rest its clean copy
    (``noised_len`` 0: there is one copy); ``p(r)`` is a row's position in
    its copy and ``blk(r) = p(r) // block``. Query ``i`` sees key ``j``
    iff

    - both noised and ``blk(i) == blk(j)`` (a block sees itself whole),
    - ``i`` noised, ``j`` clean and ``blk(j) < blk(i)`` (the clean past),
    - both clean and ``blk(j) <= blk(i)`` (block-causal).

    ``BlockMask()`` is the causal mask (``block`` 1, one copy),
    ``BlockMask(b)`` block-causal, ``BlockMask(b, L)`` over ``2 L`` rows
    the block-diffusion training mask (BD3-LM, arXiv:2503.09573), and
    ``BlockMask(window=W)`` the sliding window: query ``i`` sees key ``j``
    iff ``0 <= i - j < W`` (a window goes with one copy at ``block`` 1
    only). It answers two questions, for traced scalars, index vectors
    and numpy arrays alike: which pairs are allowed (:meth:`allowed`;
    inside a kernel :meth:`tile_allowed`), and whether a (query tile, key
    tile) holds any allowed pair (:meth:`tile_live`) or nothing else
    (:meth:`tile_full`) — for tiles that do not straddle ``noised_len``."""

    block: int = 1
    noised_len: int = 0
    window: int = 0

    def __post_init__(self):
        if self.block < 1 or self.noised_len < 0 \
                or self.noised_len % self.block:
            raise ValueError(
                f"block {self.block} must be >= 1 and divide noised_len "
                f"{self.noised_len}")
        if self.window < 0 or (self.window and (
                self.block != 1 or self.noised_len)):
            raise ValueError(
                f"window {self.window} must be >= 0 and goes with the "
                "causal mask only (block 1, one copy)")

    @property
    def causal(self) -> bool:
        return self.block == 1 and self.noised_len == 0 and not self.window

    def _blk(self, pos):
        """Block index of the in-copy positions ``pos``."""
        if self.block == 1:
            return pos
        if self.block & (self.block - 1) == 0:  # a shift, not a division
            return pos >> (self.block.bit_length() - 1)
        return pos // self.block

    def allowed(self, q_pos, k_pos):
        """Elementwise: may query position ``q_pos`` see key ``k_pos``?"""
        if self.causal:
            return q_pos >= k_pos
        if self.window:
            return (q_pos >= k_pos) & (q_pos - k_pos < self.window)
        if not self.noised_len:
            return self._blk(k_pos) <= self._blk(q_pos)
        qn, kn = q_pos < self.noised_len, k_pos < self.noised_len
        bq = self._blk(q_pos - jnp.where(qn, 0, self.noised_len))
        bk = self._blk(k_pos - jnp.where(kn, 0, self.noised_len))
        # (and / or only: Mosaic has no select between boolean vectors)
        return (qn & kn & (bq == bk)) | (qn & ~kn & (bk < bq)) \
            | (~qn & ~kn & (bk <= bq))

    def _tile(self, qi, ki, block_q: int, block_k: int):
        """What a tile that lies whole in one copy knows as SCALARS: the
        in-copy positions of its first row and first key, and which of the
        three rules holds in it — ``strict`` (1 where a noised row looks at
        clean keys: ``blk(j) < blk(i)``, else 0: ``<=``), ``same`` (both
        noised: ``blk(j) >= blk(i)`` besides) and ``never`` (a clean row
        on noised keys); ``None`` / ``False`` where one copy rules them
        out."""
        q0, k0 = qi * block_q, ki * block_k
        if not self.noised_len:
            return q0, k0, None, False, False
        qn, kn = q0 < self.noised_len, k0 < self.noised_len
        # numpy grids (the flash kernels' static fetch tables, built while
        # a step is traced) stay numpy: a jnp op there would run, and on
        # the chip compile, op by op
        where = np.where if isinstance(qn, np.ndarray) else jnp.where
        q0 = q0 - where(qn, 0, self.noised_len)
        k0 = k0 - where(kn, 0, self.noised_len)
        return q0, k0, (qn & ~kn).astype(jnp.int32), qn & kn, ~qn & kn

    def _tile_rule(self, bq_lo, bq_hi, bk_lo, bk_hi, strict, same, never):
        """``blk(j) <= blk(i) - strict`` and, where ``same``, ``blk(j) >=
        blk(i)`` too — for one pair (``lo`` = ``hi``), for SOME pair of a
        tile (its extreme blocks crossed over) or for EVERY pair (its
        extreme blocks the other way round)."""
        ok = bk_lo <= (bq_hi if strict is None else bq_hi - strict)
        if same is not False:
            ok &= ~same | (bk_hi >= bq_lo)
        if never is not False:
            ok &= ~never
        return ok

    def tile_live(self, qi, ki, block_q: int, block_k: int):
        """Has the tile of query rows ``qi * block_q ..`` and keys
        ``ki * block_k ..`` any allowed pair? The answer for tiles that lie
        whole in one copy (``block_q`` and ``block_k`` divide
        ``noised_len``)."""
        if self.causal:
            return ki * block_k <= qi * block_q + (block_q - 1)
        if self.window:  # some i - j of the tile within [0, W)
            q0, k0 = qi * block_q, ki * block_k
            return (k0 <= q0 + (block_q - 1)) \
                & (q0 - (k0 + block_k - 1) < self.window)
        q0, k0, *rule = self._tile(qi, ki, block_q, block_k)
        return self._tile_rule(
            self._blk(q0), self._blk(q0 + (block_q - 1)),
            self._blk(k0), self._blk(k0 + (block_k - 1)), *rule)

    def tile_full(self, qi, ki, block_q: int, block_k: int):
        """Is EVERY pair of that tile allowed (so that it needs no
        elementwise mask)?"""
        if self.window:  # every i - j of the tile within [0, W)
            q0, k0 = qi * block_q, ki * block_k
            return (k0 + (block_k - 1) <= q0) \
                & (q0 + (block_q - 1) - k0 < self.window)
        q0, k0, *rule = self._tile(qi, ki, block_q, block_k)
        return self._tile_rule(
            self._blk(q0 + (block_q - 1)), self._blk(q0),
            self._blk(k0 + (block_k - 1)), self._blk(k0), *rule)

    def tile_allowed(self, qi, ki, block_q: int, block_k: int):
        """:meth:`allowed` for the ``[block_q, block_k]`` pairs of that
        tile, from its scalars: two iotas, two shifts and two compares an
        element, where the general form pays for finding each element's
        copy."""
        shape = (block_q, block_k)
        if self.window:
            back = qi * block_q - ki * block_k \
                + jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
                - jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return (back >= 0) & (back < self.window)
        q0, k0, strict, same, _ = self._tile(qi, ki, block_q, block_k)
        bq = self._blk(q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        bk = self._blk(k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        return self._tile_rule(bq, bq, bk, bk, strict, same, False)

    def dense(self, seq_len: int):
        """The ``[S, S]`` boolean array of :meth:`allowed`."""
        pos = jnp.arange(seq_len, dtype=jnp.int32)
        return self.allowed(pos[:, None], pos[None, :])


def repeat_kv(q, k, v, *, head_axis: int = 2):
    """Broadcast grouped K/V heads over their query groups ([.., H_kv, D] →
    [.., H, D]) — the GQA normalization for attention paths that need equal
    head counts. XLA fuses the repeat into the attention matmuls. One home
    for the ratio math: callers must not hand-roll the repeat.

    ``head_axis``: where K/V carry their head dim — 2 for the models'
    ``[B, S, H, D]`` activation layout (default), 1 for the decode cache's
    head-major ``[B, H, S, D]`` (q stays ``[B, s, H, D]`` either way)."""
    h, h_kv = q.shape[2], k.shape[head_axis]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    rep = h // h_kv
    if rep == 1:
        return k, v
    return (
        jnp.repeat(k, rep, axis=head_axis),
        jnp.repeat(v, rep, axis=head_axis),
    )


def kernel_attention(q, k, v, *, causal: bool = False):
    """Best fused-kernel attention for the shape — the ``attn_fn`` to hand
    composition sites (e.g. the Ulysses shard_map body, which sees the FULL
    sequence with a local head group after its all-to-all): vmem kernel at
    S ≤ 1024, blockwise flash at ≥ 2048, dense XLA between (crossovers of
    an earlier setup; no cell sits on either side of them)."""
    return multi_head_attention(q, k, v, causal=causal, impl="auto")


def dot_product_attention(q, k, v, *, causal: bool = False, mask=None,
                          bias=None, scale=None):
    """q,k,v: [B, S, H, D] (batch, seq, heads, head_dim) → [B, S, H, D].

    ``bias``: optional additive score bias broadcastable to
    ``[B, H, Sq, Sk]`` (T5's relative position bias). ``scale`` overrides
    the default ``1/sqrt(D)`` (T5 uses 1.0 — the scale is folded into its
    init)."""
    dtype = q.dtype
    depth = q.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(depth).astype(np.float32)
    # compute scores in float32 for stability, cast back at the end
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool))
        logits = jnp.where(causal_mask[None, None], logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def multi_head_attention(q, k, v, *, causal: bool = False, mask=None,
                         impl: str = "xla", kv_len: int | None = None,
                         mesh=None, name: str | None = None):
    """Dispatch over the three attention paths:

    - ``xla``: dense einsum attention (oracle; takes arbitrary masks, and
      a :class:`BlockMask` as the boolean array it describes);
    - ``vmem``: whole-sequence-in-VMEM Pallas kernel for S ≤ 1024: the
      scores never reach HBM; the only kernel
      that handles unaligned S (ViT's 197) by padding + in-kernel key mask;
    - ``flash``: blockwise FA-2 Pallas kernel for long sequences (S ≥ 2048,
      where whole-S scores no longer fit VMEM);
    - ``auto``: vmem when it applies, else xla below 2048 tokens, else
      flash.

    ``mask``: a boolean array broadcastable to ``[B, H, Sq, Sk]`` (dense
    path only), or a :class:`BlockMask` — structured, so the flash kernel
    takes it (skipping tiles with no allowed pair) and ``auto`` sends it
    there from 2048 rows on, to the dense path below.

    ``kv_len``: static true key length for contiguous right-padded K/V —
    the kernels mask padded keys in-kernel; the dense path builds the
    equivalent iota mask. Mutually exclusive with ``mask``.

    ``mesh``: pass the model's mesh on MULTI-CHIP data-parallel runs that
    want a Pallas kernel. ``pallas_call`` has no GSPMD partitioning rule,
    so on a >1-device data axis the kernel must run per-shard inside
    ``shard_map`` (attention is batch-parallel — the wrap is exact); with
    ``mesh=None`` the kernels still partition correctly under pure
    single-chip-per-process DP (one shard per program) and on the CPU
    interpret path (decomposed into partitionable jax ops).

    ``name``: the caller's own scope name (a block's ``self.name``), put
    around the kernel INSIDE that ``shard_map``. XLA names a kernel's call
    after its innermost scope — ``h_3.2`` on one chip, ``shard_map.116``
    under the wrap — and a trace reader finds the attention kernel by the
    block's name (``benchmarks/families``), on a mesh as off it.
    """
    if mask is not None and kv_len is not None:
        raise ValueError("pass mask or kv_len, not both")
    structured = isinstance(mask, BlockMask)
    if structured and causal:
        raise ValueError("pass causal=True or a BlockMask, not both")
    if mesh is not None and impl in ("vmem", "flash", "auto") and (
            mask is None or structured):
        from tpudist import mesh as mesh_lib

        dp = int(np.prod([
            mesh.shape[a] for a in (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)
        ]))
        tp = mesh.shape[mesh_lib.TENSOR_AXIS]
        # indivisible shapes (e.g. the batch-1 init trace) fall through to
        # the unwrapped path — negligible work there, and shard_map would
        # refuse; a REAL training shape falling through on a multi-device
        # mesh is a misconfiguration worth a loud warning
        divisible = (
            q.shape[0] % dp == 0
            and q.shape[2] % tp == 0
            and k.shape[2] % tp == 0
        )
        multi = dp > 1 or tp > 1
        if multi and not divisible and q.shape[0] > 1:
            import warnings

            warnings.warn(
                f"pallas attention on a {dp}x dp / {tp}x tp mesh with "
                f"shapes (batch {q.shape[0]}, q heads {q.shape[2]}, kv "
                f"heads {k.shape[2]}) not divisible by the mesh axes: "
                "running UNWRAPPED (GSPMD cannot partition pallas_call — "
                "expect gathers/replication); adjust batch/head counts"
            )
        if multi and divisible:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            # batch over data/fsdp, heads over tensor (Megatron TP keeps
            # qkv head-sharded) — attention is parallel over both, so the
            # per-shard kernel is exact with no collective
            spec = P((mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS), None,
                     mesh_lib.TENSOR_AXIS, None)

            def per_shard(q, k, v):
                with jax.named_scope(name) if name else nullcontext():
                    return multi_head_attention(
                        q, k, v, causal=causal, mask=mask, impl=impl,
                        kv_len=kv_len
                    )

            fn = shard_map(
                per_shard, mesh=mesh,
                in_specs=(spec, spec, spec), out_specs=spec,
                # pallas_call can't declare varying-manual-axes on its
                # out_shape (same caveat as parallel/cp.py)
                check_vma=False,
            )
            return fn(q, k, v)
    if impl in ("vmem", "auto"):
        if mask is None:
            try:
                from tpudist.ops.vmem_attention import vmem_attention

                return vmem_attention(q, k, v, causal=causal, kv_len=kv_len)
            except NotImplementedError as e:
                if impl == "vmem":
                    import warnings

                    warnings.warn(
                        f"vmem attention unavailable ({e}); trying flash/XLA"
                    )
            # between the vmem ceiling (1024) and 2048 the dense XLA path;
            # from 2048 the S² HBM traffic dominates and flash takes over
            # (an earlier setup's crossover, not re-measured in a cell)
            impl = "flash" if max(q.shape[1], k.shape[1]) >= 2048 else "xla"
        elif impl == "vmem":
            import warnings

            warnings.warn(
                "vmem attention takes no general mask (pass kv_len for "
                "contiguous key padding); using XLA attention"
            )
            impl = "xla"
        elif structured and max(q.shape[1], k.shape[1]) >= 2048:
            impl = "flash"  # auto + structured mask: the kernel takes it
        else:
            impl = "xla"  # auto + general mask → dense path
    if k.shape[2] != q.shape[2]:
        # GQA reaching the dense/flash paths (vmem handles grouped K/V
        # natively)
        k, v = repeat_kv(q, k, v)
    if impl == "flash":
        if mask is not None and not structured:
            # no silent fallback: the caller picked flash to keep the S×S
            # scores out of HBM, and a general mask forces the dense path
            import warnings

            warnings.warn(
                "flash attention takes no general mask; falling back to XLA "
                "attention (S×S scores in HBM) — for contiguous key padding "
                "use kv_len instead"
            )
        else:
            try:
                from tpudist.ops.flash_attention import flash_attention

                return flash_attention(q, k, v, causal=causal, kv_len=kv_len,
                                       mask=mask)
            except (ImportError, NotImplementedError) as e:
                import warnings

                warnings.warn(f"flash attention unavailable ({e}); using XLA attention")
    if structured:
        # dense path: the boolean array the description stands for
        mask = mask.dense(q.shape[1])[None, None]
    if kv_len is not None and kv_len < k.shape[1]:
        # dense path: materialize the contiguous-padding key mask
        mask = (jnp.arange(k.shape[1]) < kv_len)[None, None, None, :]
    return dot_product_attention(q, k, v, causal=causal, mask=mask)
