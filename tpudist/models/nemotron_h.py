"""Nemotron-H family decoder in Flax (``model_type: nemotron_h``): a hybrid
stack whose layer kinds come from a pattern string — Mamba-2 mixers, grouped-
query attention and expert layers of non-gated squared-ReLU experts — each
layer ONE mixer or feed-forward part in a pre-norm residual block, and an
untied head.

No counterpart in the system this repo was modelled on (its only model
is ResNet-50). Sizes follow NVIDIA's ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
``config.json``; the same equations are written out plainly, the
Mamba-2 layer as its sequential recurrence, in
``benchmarks/reference/nemotron_h.py``.

Every layer is ``x <- x + F_l(RMSNorm(x))`` with ``F_l`` chosen by the
character ``pattern[l]``:

- ``M``, **Mamba-2**: ``[z ; xBC ; dt] = u·W_in`` (``H·P``, ``H·P + 2 G
  N``, ``H``); ``xBC`` through a causal depthwise convolution of
  ``conv_kernel`` taps with a bias, then SiLU, split into ``x`` (``H``
  heads of ``P``), ``B`` and ``C`` (``G`` groups of ``N``: a group serves
  ``H / G`` heads); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  the state-space scan ``y`` (:func:`tpudist.ops.ssd.ssd_scan`: ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``, ``y_t = C_t·h_t + D ⊙ x_t``,
  chunks of ``chunk``); ``y ⊙ silu(z)``, an RMSNorm over groups of ``H·P
  / G`` channels with its scale; ``·W_out``.
- ``*``, **attention**: ``[q ; k ; v] = u·W_qkv`` (``num_heads`` query
  heads on ``num_kv_heads`` of ``head_dim``), no bias, no rotary (the
  published modelling code applies none), causal softmax attention through
  :func:`tpudist.ops.attention.multi_head_attention`, ``·W_o``.
- ``E``, **experts**: :func:`tpudist.parallel.ep.dropless_moe` with
  ``expert_act="relu2"`` (``relu(u·W_up)²·W_down``, experts and shared
  expert alike) under one :class:`~tpudist.parallel.ep.Routing` (sigmoid
  scores, top-k over all experts, the chosen normalised and scaled).

Scope names inside a block are a contract with the device trace
(``tpudist/telemetry/trace.py``): ``h_N/mamba_in_proj``, ``mamba_conv``,
``ssd_scan``, ``mamba_gate_norm``, ``mamba_out_proj``; ``gqa_qkv``,
``gqa_attn`` (the attention call), ``gqa_out``; the expert layer's
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``moe_shared``. A Mamba-2 block sows ``ssd_log_carry`` into
``moe_stats``: the mean over heads and chunks of ``sum_chunk dt·A``, the
log of what a state keeps across one chunk.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.zaya import _rms_norm, shift_right
from tpudist.ops.attention import multi_head_attention
from tpudist.ops.ssd import ssd_scan
from tpudist.parallel.ep import Routing, dropless_moe

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
# the published 52 layers (hybrid_override_pattern)
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A_log = log(A)``, ``A ~ U[1, 16]`` (Mamba-2's initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32, *, dt_min=1e-3, dt_max=0.1,
                  floor=1e-4):
    """The inverse softplus of a time step log-uniform on ``[dt_min,
    dt_max]``, at least ``floor`` (Mamba-2's initialisation)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(dt_min),
                                    math.log(dt_max)))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))


class NemotronHBlock(nn.Module):
    """One layer of kind ``kind`` (a pattern character) in a pre-norm
    residual block."""

    kind: str
    hidden_dim: int
    # Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    state_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    # attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # experts
    ffn_dim: int = 1856
    shared_dim: int = 3712
    routing: Routing | None = None
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-5
    fused_ln: bool = False

    def _dense(self, name: str, width: int):
        return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

    @nn.nowrap  # no ``h_N._mamba`` between the block and its stages' scopes
    def _mamba(self, u):
        b, s, _ = u.shape
        h, p, g, n = (self.mamba_heads, self.mamba_head_dim, self.n_groups,
                      self.state_dim)
        inner, conv_dim = h * p, h * p + 2 * g * n
        with jax.named_scope("mamba_in_proj"):
            z, xbc, dt = jnp.split(
                self._dense("mamba_in_proj", inner + conv_dim + h)(u),
                [inner, inner + conv_dim], axis=-1)
        with jax.named_scope("mamba_conv"):
            taps = self.param("conv_kernel", nn.initializers.lecun_normal(),
                              (self.conv_kernel, conv_dim), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (conv_dim,), jnp.float32)
            xbc = xbc.astype(jnp.float32)
            k = self.conv_kernel
            # y_t = sum_j taps[j] x_{t - (k - 1 - j)} + bias: causal, depthwise
            xbc = sum(taps[j] * shift_right(xbc, k - 1 - j) for j in range(k))
            xbc = nn.silu(xbc + bias).astype(self.dtype)
        with jax.named_scope("ssd_scan"):
            x, B, C = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + self.param(
                "dt_bias", _dt_bias_init, (h,), jnp.float32))
            A = -jnp.exp(self.param("A_log", _a_log_init, (h,), jnp.float32))
            D = self.param("D", nn.initializers.ones, (h,), jnp.float32)
            y = ssd_scan(x.reshape(b, s, h, p), dt, A, B.reshape(b, s, g, n),
                         C.reshape(b, s, g, n), D, chunk=self.chunk)
            # what a state keeps across one chunk, in logs: the mean over
            # heads and chunks of sum_chunk dt·A
            self.sow("moe_stats", "ssd_log_carry", self.chunk
                     * jnp.mean(jax.lax.stop_gradient(dt) * A))
        with jax.named_scope("mamba_gate_norm"):
            y = y.reshape(b, s, inner).astype(jnp.float32) \
                * nn.silu(z.astype(jnp.float32))
            grouped = y.reshape(b, s, g, inner // g)
            grouped = grouped * jax.lax.rsqrt(jnp.mean(
                grouped * grouped, axis=-1, keepdims=True) + self.norm_eps)
            scale = self.param("gate_norm_scale", nn.initializers.ones,
                               (inner,), jnp.float32)
            y = (grouped.reshape(b, s, inner) * scale).astype(self.dtype)
        return self._dense("mamba_out_proj", self.hidden_dim)(y)

    @nn.nowrap
    def _attention(self, u):
        b, s, _ = u.shape
        h, kv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("gqa_qkv"):
            q, k, v = jnp.split(self._dense("gqa_qkv", (h + 2 * kv) * dh)(u),
                                [h * dh, (h + kv) * dh], axis=-1)
            q, k, v = (t.reshape(b, s, -1, dh) for t in (q, k, v))
        with jax.named_scope("gqa_attn"):
            o = multi_head_attention(q, k, v, causal=True,
                                     impl=self.attn_impl, mesh=self.mesh,
                                     name="gqa_attn")
        with jax.named_scope("gqa_out"):
            o = o.reshape(b, s, h * dh)
        return self._dense("gqa_out", self.hidden_dim)(o)

    @nn.compact
    def __call__(self, x):
        if self.kind == EXPERTS:
            # the router scores from a float32 u; the experts compute in dtype
            u = _rms_norm("norm", jnp.float32, eps=self.norm_eps,
                          fused=self.fused_ln, mesh=self.mesh)(x)
            y, _ = dropless_moe(
                self, u, routing=self.routing, ffn_dim=self.ffn_dim,
                shared_dim=self.shared_dim, dtype=self.dtype, mesh=self.mesh,
                norm_eps=self.norm_eps, expert_act="relu2",
            )
            return x + y
        u = _rms_norm("norm", self.dtype, eps=self.norm_eps,
                      fused=self.fused_ln, mesh=self.mesh)(x)
        if self.kind == MAMBA:
            return x + self._mamba(u)
        if self.kind == ATTENTION:
            return x + self._attention(u)
        raise ValueError(f"unknown layer kind {self.kind!r}")


class NemotronH(nn.Module):
    vocab_size: int = 131072
    max_seq_len: int = 262144
    hidden_dim: int = 2688
    depth: int = 52
    pattern: str = PATTERN        # hybrid_override_pattern
    mamba_heads: int = 64         # mamba_num_heads
    mamba_head_dim: int = 64      # mamba_head_dim
    n_groups: int = 8
    state_dim: int = 128          # ssm_state_size
    conv_kernel: int = 4
    chunk: int = 128              # chunk_size
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    ffn_dim: int = 1856           # moe_intermediate_size
    shared_dim: int = 3712        # moe_shared_expert_intermediate_size
    routing: Routing = Routing(128, top_k=6, scoring="sigmoid",
                               routed_scale=2.5)
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-5
    # per-BLOCK rematerialization policy (tpudist.remat names), as Llama's
    remat_policy: str | None = None
    # fused_ln=True runs the blocks' RMSNorms through the Pallas fused norm
    # kernel (same "scale" leaves); set by make_train_step(fused="ln"|"all")
    fused_ln: bool = False

    # the expert layers sow router counters, the Mamba-2 layers the scan's
    # carry, into 'moe_stats' (tpudist.train forwards them to telemetry)
    sows_moe_stats = True
    flops_counter = "nemotron_h"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False):
        del train  # no dropout, no noise: one forward for both
        if tokens.shape[1] > self.max_seq_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if len(self.pattern) < self.depth:
            raise ValueError(f"{self.depth} layers need as many pattern "
                             f"characters (got {len(self.pattern)})")
        table = lambda name: self.param(
            name, nn.initializers.normal(0.02),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        x = table("embed")[tokens].astype(self.dtype)
        from tpudist.remat import remat_module

        block_cls = remat_module(NemotronHBlock, self.remat_policy)
        for i in range(self.depth):
            x = block_cls(
                kind=self.pattern[i], hidden_dim=self.hidden_dim,
                mamba_heads=self.mamba_heads,
                mamba_head_dim=self.mamba_head_dim, n_groups=self.n_groups,
                state_dim=self.state_dim, conv_kernel=self.conv_kernel,
                chunk=self.chunk, num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                ffn_dim=self.ffn_dim, shared_dim=self.shared_dim,
                routing=self.routing, dtype=self.dtype,
                attn_impl=self.attn_impl, mesh=self.mesh,
                norm_eps=self.norm_eps, fused_ln=self.fused_ln,
                name=f"h_{i}",
            )(x)
        x = _rms_norm("norm", self.dtype, eps=self.norm_eps,
                      fused=self.fused_ln, mesh=self.mesh)(x)
        # the head is its own table (tie_word_embeddings false), no bias;
        # ``lm_utils.lm_head_weight`` finds it under this name
        head = table("lm_head")
        if return_hidden:
            return x
        return jnp.einsum(
            "bsd,vd->bsv", x, head.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )


def nemotron_3_nano(**kw) -> NemotronH:
    """NVIDIA-Nemotron-3-Nano-30B-A3B geometry (its ``config.json``): 52
    layers after ``hybrid_override_pattern`` — 23 Mamba-2 mixers (64 heads
    of 64, 8 groups of state 128, a causal convolution of 4 taps, chunks of
    128), 6 grouped-query attention layers (32 query heads on 2 key/value
    heads of 128, no rotary), 23 expert layers (128 non-gated squared-ReLU
    experts of width 1,856 routed top-6 by sigmoid scores scaled 2.5 beside
    one shared expert of 3,712) —, 2688 wide, vocabulary 131,072 with an
    untied head."""
    return NemotronH(**kw)
