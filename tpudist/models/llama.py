"""Llama-family decoder in Flax — the modern-LM member of the model zoo.

No reference counterpart (the reference's only model is ResNet-50,
/root/reference/main.py:40); built so the framework covers the
architecture most large-scale TPU training targets today: pre-norm RMSNorm,
rotary position embeddings (RoPE — no learned position table), grouped-query
attention (GQA: fewer K/V heads than Q heads), SwiGLU MLP, no biases,
untied LM head (tying optional).

TPU-first choices mirror :mod:`tpudist.models.gpt2`:

- Megatron tensor-parallel partitioning metadata over the ``tensor`` mesh
  axis (qkv/gate/up column-parallel, out/down row-parallel, embedding and
  head vocab-sharded); GSPMD inserts the two all-reduces per block.
- ``attn_impl`` selects XLA einsum attention, the Pallas flash kernel, or
  the context-parallel paths (ring / Ulysses over the ``seq`` axis) from
  :mod:`tpudist.parallel.cp` — RoPE is applied at the global sequence view,
  so sequence sharding composes without per-shard offset bookkeeping.
- GQA K/V heads are broadcast up to the Q-head count right before the
  attention op: one cheap ``repeat`` that XLA fuses, keeping every attention
  impl (flash kernel included) oblivious to the grouping.
- RoPE angles are computed in fp32 and cast once, keeping bf16 runs stable.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpudist.mesh import TENSOR_AXIS
from tpudist.ops.attention import multi_head_attention
from tpudist.parallel.tp import partitioned as _partitioned


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (arXiv:2309.00071) as ``rope_type: yarn`` states
    it: ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow`` and the ``attention_factor`` that multiplies cos and sin
    (HF's ``attention_scaling``). The frequencies are HF's
    ``_compute_yarn_parameters`` at the rotary width (truncated correction
    range)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def correction_range(self, dim: int, theta: float) -> tuple[int, int]:
        """``(low, high)``: the pairs below ``low`` turn more than
        ``beta_fast`` times over the original context (kept), those past
        ``high`` fewer than ``beta_slow`` times (interpolated)."""
        at = lambda turns: dim * math.log(
            self.original_max_position_embeddings / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))
        return (max(math.floor(at(self.beta_fast)), 0),
                min(math.ceil(at(self.beta_slow)), dim - 1))

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """``float32 [dim / 2]``: ``f_i = theta^(-2i/dim)``, ramp ``r_i =
        clip((i - low) / (high - low), 0, 1)``, ``f_i (1 - r_i) + (f_i /
        factor) r_i``. Numpy: a constant of the step, nothing traced."""
        low, high = self.correction_range(dim, theta)
        f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        return (f * (1 - ramp) + f / self.factor * ramp).astype(np.float32)


def apply_rope(x, *, theta: float = 10000.0, positions=None,
               rotary_dim: int | None = None, interleaved: bool = False,
               yarn: Yarn | None = None):
    """Rotary position embedding over ``x: [B, S, H, D]``: rotate-half
    convention (channel ``i`` pairs with ``i + D/2``), or with
    ``interleaved`` adjacent pairs ``(2i, 2i+1)`` turned in place (a
    DeepSeek-V3-family ``rope_interleave``), pair ``i`` by ``pos ·
    theta^(-2i/D)`` either way. Angles in fp32; output in ``x.dtype``. ``positions`` is
    ``[S]`` (shared across the batch) or ``[B, S]`` (per-row absolute
    positions — slot-pooled decode, where every cache slot sits at its own
    sequence length). ``rotary_dim`` rotates the first ``rotary_dim``
    channels of every head and passes the rest through untouched (a
    ``partial_rotary_factor`` < 1); the default is the whole head.
    ``yarn`` takes the frequencies of :class:`Yarn` at the rotary width
    and multiplies cos and sin by its ``attention_factor`` (the channels
    passed through are not scaled)."""
    b, s, h, d = x.shape
    if rotary_dim is not None and rotary_dim != d:
        if not 0 < rotary_dim < d or rotary_dim % 2:
            raise ValueError(
                f"rotary_dim {rotary_dim} must be even and within the head "
                f"size {d}"
            )
        rotated = apply_rope(x[..., :rotary_dim], theta=theta,
                             positions=positions, interleaved=interleaved,
                             yarn=yarn)
        return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)
    half = d // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn.inv_freq(d, theta))
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    angles = positions[..., :, None] * freqs  # [S, half] or [B, S, half]
    if angles.ndim == 3:
        cos = jnp.cos(angles)[:, :, None, :]              # [B, S, 1, half]
        sin = jnp.sin(angles)[:, :, None, :]
    else:
        cos = jnp.cos(angles)[None, :, None, :]           # [1, S, 1, half]
        sin = jnp.sin(angles)[None, :, None, :]
    if yarn is not None and yarn.attention_factor != 1.0:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(b, s, h, half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    ffn_dim: int
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    rope_theta: float = 10000.0
    mesh: Any = None
    norm_eps: float = 1e-5
    # num_experts > 0 swaps the SwiGLU MLP for a Mixtral-style MoE of
    # SwiGLU experts (tpudist.parallel.ep), expert-sharded over 'expert'
    num_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # expert dispatch impl + router hardening (tpudist.parallel.ep.MoEMlp)
    moe_dispatch: str = "einsum"
    router_z_loss: float = 0.0
    router_jitter: float = 0.0
    # fused_ln=True runs both RMSNorms through the Pallas fused
    # residual-add+norm kernel (tpudist.ops.layernorm, rms=True — same
    # "scale" param as nn.RMSNorm). Decode keeps the reference composition.
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True, decode: bool = False,
                 max_len: int = 0, positions=None, block_tables=None):
        b, s, d = x.shape
        h, kv = self.num_heads, self.num_kv_heads
        if h % kv:
            raise ValueError(f"num_heads {h} not divisible by num_kv_heads {kv}")
        dh = d // h
        dense_init = nn.initializers.lecun_normal()
        fused = self.fused_ln and not decode
        if fused:
            from tpudist.ops.layernorm import FusedLayerNorm

            norm = lambda name: FusedLayerNorm(
                epsilon=self.norm_eps, dtype=self.dtype, rms=True,
                mesh=self.mesh, name=name,
            )
        else:
            norm = lambda name: nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=name
            )

        y = norm("attn_norm")(x)
        # column-parallel projections: head dim sharded over 'tensor'
        q = nn.DenseGeneral((h, dh), use_bias=False, dtype=self.dtype,
                            name="q_proj",
                            kernel_init=_partitioned(dense_init, None, TENSOR_AXIS, None))(y)
        k = nn.DenseGeneral((kv, dh), use_bias=False, dtype=self.dtype,
                            name="k_proj",
                            kernel_init=_partitioned(dense_init, None, TENSOR_AXIS, None))(y)
        v = nn.DenseGeneral((kv, dh), use_bias=False, dtype=self.dtype,
                            name="v_proj",
                            kernel_init=_partitioned(dense_init, None, TENSOR_AXIS, None))(y)
        if decode:
            # KV-cache decode (tpudist.ops.decode): keys are rotated at
            # their absolute positions BEFORE caching, so the cache holds
            # position-encoded keys; q rotates at the same offset
            if self.attn_impl in ("ring", "ulysses", "ulysses_flash"):
                raise ValueError(
                    f"attn_impl={self.attn_impl!r} has no decode path; "
                    "generate with the xla/flash model"
                )
            from tpudist.ops.decode import (
                cached_kv, decode_attention, paged_decode_attention,
            )

            def rope_positions(pos):
                # scalar cursor: the chunk rows sit at pos..pos+s-1; per-row
                # cursors ([B], slot-pooled decode): row b's chunk rows at
                # pos_b..pos_b+s-1 (s > 1 is the speculative verify chunk;
                # RoPE has no table to overrun, so no tail clamp is needed)
                if jnp.ndim(pos) == 0:
                    return (pos + jnp.arange(s)).astype(jnp.float32)
                return (
                    pos[:, None] + jnp.arange(s)[None, :]
                ).astype(jnp.float32)  # [B, s]

            def rotate_k(k, v, pos):
                return apply_rope(k, theta=self.rope_theta,
                                  positions=rope_positions(pos)), v

            keys, values, mask, pos = cached_kv(
                self, k, v, max_len, pre_update=rotate_k,
                positions=positions, block_tables=block_tables,
            )
            q = apply_rope(q, theta=self.rope_theta,
                           positions=rope_positions(pos))
            if block_tables is not None:
                # paged decode: keys/values are the shared block pool and
                # `mask` the per-row block tables (tpudist.serve.blocks);
                # keys were RoPE-rotated at their absolute positions
                # before the paged write, same as the contiguous path
                attn = paged_decode_attention(
                    q, keys, values, mask, pos,
                    impl="xla" if self.attn_impl == "xla" else "paged",
                    mesh=self.mesh,
                )
            else:
                # fused path reads grouped K/V heads natively (no repeat in
                # HBM); the dense oracle repeats inside decode_attention
                attn = decode_attention(
                    q, keys, values, mask, pos,
                    impl="xla" if self.attn_impl == "xla" else "fused",
                )
        else:
            q = apply_rope(q, theta=self.rope_theta)
            k = apply_rope(k, theta=self.rope_theta)
            if kv != h and self.attn_impl in ("ring", "ulysses", "ulysses_flash"):
                # the context-parallel bodies shard/rotate full head sets;
                # broadcast K/V heads up front there. The multi_head_attention
                # dispatch below takes grouped K/V as-is — the vmem kernel
                # reads each K/V head once per query group (no repeat in
                # HBM), and its dense/flash fallbacks repeat internally.
                from tpudist.ops.attention import repeat_kv

                k, v = repeat_kv(q, k, v)
            if self.attn_impl in ("ring", "ulysses", "ulysses_flash"):
                if self.mesh is None:
                    raise ValueError(
                        f"attn_impl={self.attn_impl!r} needs the model's "
                        "mesh= field set (the shard_map runs over its 'seq' "
                        "axis)"
                    )
                from tpudist.parallel.cp import ring_attention, ulysses_attention

                if self.attn_impl == "ring":
                    attn = ring_attention(q, k, v, self.mesh, causal=True)
                else:
                    attn_fn = None
                    if self.attn_impl == "ulysses_flash":
                        from tpudist.ops.attention import kernel_attention

                        attn_fn = kernel_attention
                    attn = ulysses_attention(
                        q, k, v, self.mesh, causal=True, attn_fn=attn_fn
                    )
            else:
                attn = multi_head_attention(
                    q, k, v, causal=True, impl=self.attn_impl,
                    mesh=self.mesh, name=self.name,
                )
        # row-parallel output projection; GSPMD all-reduces over 'tensor'
        o = nn.DenseGeneral(
            d, axis=(-2, -1), use_bias=False, dtype=self.dtype, name="o_proj",
            kernel_init=_partitioned(dense_init, TENSOR_AXIS, None, None),
        )(attn)
        if fused:
            # residual add + RMSNorm in one kernel sweep; the updated
            # residual stream rides back from the same HBM pass
            y, x = norm("mlp_norm")(o, residual=x)
        else:
            x = x + o
            y = norm("mlp_norm")(x)
        if self.num_experts > 0:
            from tpudist.parallel.ep import MoEMlp

            y = MoEMlp(
                num_experts=self.num_experts, top_k=self.moe_top_k,
                capacity_factor=self.capacity_factor,
                ffn_dim=self.ffn_dim, expert_act="swiglu",
                dispatch_impl=self.moe_dispatch,
                router_z_loss=self.router_z_loss,
                router_jitter=self.router_jitter,
                dtype=self.dtype, mesh=self.mesh, name="moe",
            )(y, deterministic=not train)
        else:
            # SwiGLU: silu(gate)·up, both column-parallel; down row-parallel
            gate = nn.Dense(self.ffn_dim, use_bias=False, dtype=self.dtype,
                            name="gate_proj",
                            kernel_init=_partitioned(dense_init, None, TENSOR_AXIS))(y)
            up = nn.Dense(self.ffn_dim, use_bias=False, dtype=self.dtype,
                          name="up_proj",
                          kernel_init=_partitioned(dense_init, None, TENSOR_AXIS))(y)
            y = nn.Dense(d, use_bias=False, dtype=self.dtype, name="down_proj",
                         kernel_init=_partitioned(dense_init, TENSOR_AXIS, None))(
                nn.silu(gate) * up
            )
        return x + y


class _CarryBlock(nn.Module):
    """:class:`LlamaBlock` with the (carry, xs) -> (carry, ys) signature
    ``nn.scan`` maps over; ``train`` rides as a module field because scan
    broadcasts call-time kwargs awkwardly."""

    num_heads: int
    num_kv_heads: int
    ffn_dim: int
    train: bool = True
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    rope_theta: float = 10000.0
    mesh: Any = None
    norm_eps: float = 1e-5
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, _):
        x = LlamaBlock(
            self.num_heads, self.num_kv_heads, self.ffn_dim,
            dtype=self.dtype, attn_impl=self.attn_impl,
            rope_theta=self.rope_theta, mesh=self.mesh,
            norm_eps=self.norm_eps, fused_ln=self.fused_ln, name="block",
        )(x, train=self.train)
        return x, None


def default_ffn_dim(hidden_dim: int) -> int:
    """The SwiGLU sizing ``ffn_dim=None`` resolves to: 8/3·d rounded up to
    a multiple of 256 (Llama convention). One home for the formula — the
    model's forward and the analytic FLOPs dispatcher
    (tpudist.telemetry.flops) must agree on the parameter count."""
    return -(-8 * hidden_dim // 3 // 256) * 256


class Llama(nn.Module):
    vocab_size: int = 32000
    max_seq_len: int = 2048
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    num_kv_heads: int | None = None   # None → MHA (kv == heads)
    ffn_dim: int | None = None        # None → SwiGLU sizing: 8/3·d, /256 ceil
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    tie_embeddings: bool = False
    mesh: Any = None
    norm_eps: float = 1e-5
    # scan_layers=True runs the depth as ONE nn.scan'd block with params
    # stacked [depth, ...] — XLA traces/compiles a single layer regardless
    # of depth (the idiomatic TPU pattern for 32+ layer models; an unrolled
    # llama2-7b traces 32 copies of the block). Param names move from
    # layer_{i}/... to layers/... with a leading depth axis; TP metadata is
    # preserved (the stacked axis stays unsharded). Training/eval only —
    # decode and the interop converters use the unrolled layout.
    scan_layers: bool = False
    # remat_layers=True checkpoints each scanned layer: backward stores only
    # the per-layer boundary activations and recomputes inside the layer —
    # the scan+remat memory pattern that makes depth-32+ long-sequence
    # training fit (requires scan_layers; legacy sugar for
    # remat_policy="full")
    remat_layers: bool = False
    # per-BLOCK rematerialization policy (tpudist.remat names: "full",
    # "dots_saveable", "save_nothing"; None/"none" off), honored in BOTH
    # the scanned and unrolled layouts (unrolled keeps layer_{i} param
    # names — nn.remat is name-transparent). Ignored on the decode path.
    remat_policy: str | None = None
    # num_experts > 0: every moe_every-th block is Mixtral-style MoE (SwiGLU
    # experts over the 'expert' mesh axis, tpudist.parallel.ep); aux
    # load-balance losses are sowed and added by the train step
    num_experts: int = 0
    moe_every: int = 1  # Mixtral: every block is MoE
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # dispatch impl + router hardening, threaded into every MoE block
    moe_dispatch: str = "einsum"
    router_z_loss: float = 0.0
    router_jitter: float = 0.0
    # fused_ln=True: every RMSNorm (attn_norm/mlp_norm/final norm) runs
    # the Pallas fused residual-add+norm kernel (tpudist.ops.layernorm,
    # rms=True) — same param tree, decode path untouched. Usually set via
    # make_train_step(fused="ln"|"all"), which clones the model.
    fused_ln: bool = False

    @property
    def has_aux_loss(self) -> bool:
        return self.num_experts > 0

    @property
    def flops_counter(self) -> str | None:
        """Analytic-FLOPs family tag (tpudist.telemetry.flops) — the MFU
        numerator dispatch. MoE geometries use "llama_moe" (active-param
        accounting: top_k SwiGLU experts + router GEMM per MoE block), so
        MFU rows stay real for sparse models."""
        return "llama_moe" if self.num_experts > 0 else "llama"

    def init_cache(self, batch_size: int):
        """Zeroed decode KV cache for ``batch_size`` rows — the serving
        engine's slot-pool allocation hook (``tpudist.serve.slots``); built
        via ``eval_shape`` so no params materialize."""
        from tpudist.generate import zero_cache

        return zero_cache(self, batch_size)

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False,
                 decode: bool = False, positions=None, block_tables=None):
        b, s = tokens.shape
        if s > self.max_seq_len:
            raise ValueError(f"sequence {s} exceeds max_seq_len {self.max_seq_len}")
        kv = self.num_kv_heads or self.num_heads
        ffn = self.ffn_dim or default_ffn_dim(self.hidden_dim)
        embed = self.param(
            "embed",
            _partitioned(nn.initializers.normal(0.02), TENSOR_AXIS, None),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        x = embed[tokens].astype(self.dtype)  # RoPE: no position table
        block_cfg = dict(
            num_heads=self.num_heads, num_kv_heads=kv, ffn_dim=ffn,
            dtype=self.dtype, attn_impl=self.attn_impl,
            rope_theta=self.rope_theta, mesh=self.mesh,
            norm_eps=self.norm_eps, fused_ln=self.fused_ln,
        )
        from tpudist.remat import remat_module

        block_policy = self.remat_policy or (
            "full" if self.remat_layers else None
        )
        if self.scan_layers:
            if decode:
                raise ValueError(
                    "scan_layers has no decode path (the KV cache needs "
                    "per-layer variables); generate with scan_layers=False"
                )
            if self.num_experts:
                raise ValueError("scan_layers supports dense blocks only")
            body = remat_module(_CarryBlock, block_policy)
            scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=self.depth,
                # stacked depth axis carries no partition name (unsharded);
                # the per-layer TENSOR_AXIS metadata shifts right intact
                metadata_params={nn.PARTITION_NAME: None},
            )(train=train, **block_cfg, name="layers")
            x, _ = scanned(x, None)
        elif self.remat_layers:
            raise ValueError("remat_layers requires scan_layers=True "
                             "(set remat_policy to checkpoint the unrolled "
                             "blocks, or make_train_step(remat=...) for a "
                             "whole-forward checkpoint)")
        else:
            # per-block checkpoint in the unrolled layout: layer_{i} param
            # names unchanged; train/decode/max_len static under the remat
            block_cls = (
                remat_module(LlamaBlock, block_policy, static_argnums=(2, 3, 4))
                if not decode else LlamaBlock
            )
            for i in range(self.depth):
                moe_here = self.num_experts > 0 and (
                    i % self.moe_every == self.moe_every - 1
                )
                x = block_cls(
                    **block_cfg,
                    num_experts=self.num_experts if moe_here else 0,
                    moe_top_k=self.moe_top_k,
                    capacity_factor=self.capacity_factor,
                    moe_dispatch=self.moe_dispatch,
                    router_z_loss=self.router_z_loss,
                    router_jitter=self.router_jitter,
                    name=f"layer_{i}",
                )(x, train, decode, self.max_seq_len,
                  # only the (remat-free) decode path threads per-slot
                  # positions/block tables (same contract as GPT-2)
                  **({"positions": positions,
                      "block_tables": block_tables} if decode else {}))
        if self.fused_ln and not decode:
            from tpudist.ops.layernorm import FusedLayerNorm

            x = FusedLayerNorm(
                epsilon=self.norm_eps, dtype=self.dtype, rms=True,
                mesh=self.mesh, name="norm",
            )(x)
        else:
            x = nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name="norm"
            )(x)
        if return_hidden:
            # the chunked-CE path applies the head per sequence chunk so the
            # [B,S,V] fp32 logits never materialize (gpt2.chunked_lm_forward)
            return x
        if self.tie_embeddings:
            head = embed
        else:
            head = self.param(
                "lm_head",
                _partitioned(nn.initializers.normal(0.02), TENSOR_AXIS, None),
                (self.vocab_size, self.hidden_dim), jnp.float32,
            )
        return jnp.einsum(
            "bsd,vd->bsv", x, head.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )


def stack_llama_layers(params, depth: int) -> dict:
    """Unrolled ``layer_{i}`` params → the ``scan_layers`` layout; lets
    checkpoints move between layouts (e.g. warm-start a scan model from an
    HF import). See :func:`tpudist.models.lm_utils.stack_layers`."""
    from tpudist.models.lm_utils import stack_layers

    return stack_layers(params, depth, prefix="layer_", dest="layers")


def unstack_llama_layers(params) -> dict:
    """``scan_layers`` layout → unrolled ``layer_{i}`` params (the layout
    decode/generation and the HF exporters use)."""
    from tpudist.models.lm_utils import unstack_layers

    return unstack_layers(params, prefix="layer_", dest="layers")


def llama_125m(**kw) -> Llama:
    """GPT-2-124M-comparable Llama: 12 layers, 768 hidden, GQA 12/4."""
    kw.setdefault("num_kv_heads", 4)
    return Llama(**kw)


def llama2_7b(**kw) -> Llama:
    """Llama-2 7B geometry: 32 layers, 4096 hidden, MHA, ffn 11008."""
    kw.setdefault("hidden_dim", 4096)
    kw.setdefault("depth", 32)
    kw.setdefault("num_heads", 32)
    kw.setdefault("ffn_dim", 11008)
    kw.setdefault("max_seq_len", 4096)
    return Llama(**kw)


def mixtral_8x7b(**kw) -> Llama:
    """Mixtral-8x7B geometry: Llama-7B trunk, every block an 8-expert
    top-2 SwiGLU MoE, GQA 32/8, 32k rope theta 1e6."""
    kw.setdefault("hidden_dim", 4096)
    kw.setdefault("depth", 32)
    kw.setdefault("num_heads", 32)
    kw.setdefault("num_kv_heads", 8)
    kw.setdefault("ffn_dim", 14336)
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("rope_theta", 1e6)
    kw.setdefault("max_seq_len", 32768)
    kw.setdefault("num_experts", 8)
    kw.setdefault("moe_top_k", 2)
    return Llama(**kw)


def llama3_8b(**kw) -> Llama:
    """Llama-3 8B geometry: GQA 32/8, ffn 14336, 128k vocab, theta 5e5."""
    kw.setdefault("hidden_dim", 4096)
    kw.setdefault("depth", 32)
    kw.setdefault("num_heads", 32)
    kw.setdefault("num_kv_heads", 8)
    kw.setdefault("ffn_dim", 14336)
    kw.setdefault("vocab_size", 128256)
    kw.setdefault("rope_theta", 500000.0)
    kw.setdefault("max_seq_len", 8192)
    return Llama(**kw)
