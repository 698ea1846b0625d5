"""Laguna family decoder in Flax (``model_type: laguna``): two kinds of
attention layer in one stack — full causal attention and a sliding window —
with a head count, rotary embedding and mask of their own, a gated
attention output, a leading dense SwiGLU layer, then expert layers of
sigmoid-scored top-k routed experts beside a shared expert, and an untied
head.

No counterpart in the system this repo was modelled on (its only model
is ResNet-50). Sizes follow poolside's ``Laguna-XS.2``
``config.json``; the same equations are written out plainly in
``benchmarks/reference/laguna.py``.

Pre-norm blocks without biases, ``x <- x + Attn(RMSNorm(x))``, ``x <- x +
F(RMSNorm(x))``. Layer ``l``'s kind is ``layer_types[l]`` and its query
heads ``heads_per_layer[l]``:

- **Attn**: ``[q ; k ; v] = u·W_qkv`` (``H_l`` query heads on
  ``num_kv_heads`` key/value heads of ``head_dim``), ``g = sigmoid(u·W_g)``
  (``H_l · head_dim``, one gate a channel: the output gate of Gated
  Attention, arXiv:2505.06708); a ``full_attention`` layer turns the first
  ``full_rotary_dim`` channels of each q and k head by YaRN's frequencies
  (:class:`~tpudist.models.llama.Yarn`, cos and sin times its attention
  factor) and attends causally; a ``sliding_attention`` layer turns the
  whole head by plain rotary and attends under
  ``BlockMask(window=window)`` (query ``i`` sees keys ``i - W < j <= i``:
  the flash kernel's grid spans only the band); ``(a ⊙ g)·W_o``.
- **F** is a dense SwiGLU in the first ``dense_layers`` layers and
  :func:`tpudist.parallel.ep.dropless_moe` after them, under one
  :class:`~tpudist.parallel.ep.Routing` (sigmoid scores, top-k over all
  experts, a bias on the selection if the ``Routing`` brings one, the
  chosen scores normalised and scaled) with a shared expert of width
  ``shared_dim`` on every token.

Scope names inside a block are a contract with the device trace
(``tpudist/telemetry/trace.py``): ``h_N/lg_qkv``, ``lg_gate``, ``lg_rope``,
``swa_attn`` / ``full_attn`` (the attention call of either kind),
``lg_out``, and the expert layer's ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``, ``moe_shared``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.llama import Yarn, apply_rope
from tpudist.models.zaya import _rms_norm
from tpudist.ops.attention import BlockMask, multi_head_attention
from tpudist.parallel.ep import Routing, dropless_moe

FULL, SLIDING = "full_attention", "sliding_attention"
# the published stack's period: one full layer, three sliding ones
PERIOD = (FULL, SLIDING, SLIDING, SLIDING)
# the scope of each kind's attention call (its kernel is ``<scope>.<k>``)
ATTENTION_SCOPES = {FULL: "full_attn", SLIDING: "swa_attn"}


class LagunaBlock(nn.Module):
    """One layer of kind ``kind`` with ``num_heads`` query heads: gated
    attention, then the dense SwiGLU (``dense``) or the expert layer."""

    kind: str
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    ffn_dim: int
    shared_dim: int
    routing: Routing
    dense: bool = False
    dense_ffn_dim: int = 0
    full_rope_theta: float = 500000.0
    full_rotary_dim: int = 64
    yarn: Yarn | None = None
    sliding_rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-6
    fused_ln: bool = False

    def _norm(self, name: str, dtype):
        return _rms_norm(name, dtype, eps=self.norm_eps, fused=self.fused_ln,
                         mesh=self.mesh)

    def _dense(self, name: str, width: int):
        return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

    @nn.nowrap  # no ``h_N._attn`` between the block and its stages' scopes
    def _attn(self, u):
        b, s, d = u.shape
        h, kv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("lg_qkv"):
            qkv = self._dense("lg_qkv", (h + 2 * kv) * dh)(u)
            q, k, v = jnp.split(qkv, [h * dh, (h + kv) * dh], axis=-1)
            q, k, v = (x.reshape(b, s, -1, dh) for x in (q, k, v))
        with jax.named_scope("lg_gate"):
            gate = jax.nn.sigmoid(self._dense("lg_gate", h * dh)(u))
        with jax.named_scope("lg_rope"):
            if self.kind == FULL:
                turn = lambda x: apply_rope(
                    x, theta=self.full_rope_theta,
                    rotary_dim=self.full_rotary_dim, yarn=self.yarn)
            else:
                turn = lambda x: apply_rope(x, theta=self.sliding_rope_theta)
            q, k = turn(q), turn(k)
        scope = ATTENTION_SCOPES[self.kind]
        mask = None if self.kind == FULL else BlockMask(window=self.window)
        with jax.named_scope(scope):
            o = multi_head_attention(
                q, k, v, causal=mask is None, mask=mask, impl=self.attn_impl,
                mesh=self.mesh, name=scope,
            )
        with jax.named_scope("lg_out"):
            o = o.reshape(b, s, h * dh) * gate
        return self._dense("lg_out", d)(o)

    @nn.compact
    def __call__(self, x):
        if self.kind not in ATTENTION_SCOPES:
            raise ValueError(f"unknown layer type {self.kind!r}")
        x = x + self._attn(self._norm("attn_norm", self.dtype)(x))
        if self.dense:
            u = self._norm("mlp_norm", self.dtype)(x)
            hid = nn.silu(self._dense("mlp_gate", self.dense_ffn_dim)(u)) \
                * self._dense("mlp_up", self.dense_ffn_dim)(u)
            return x + self._dense("mlp_down", x.shape[-1])(hid)
        # the router scores from a float32 u; the experts compute in dtype
        u = self._norm("moe_norm", jnp.float32)(x)
        y, _ = dropless_moe(
            self, u, routing=self.routing, ffn_dim=self.ffn_dim,
            shared_dim=self.shared_dim, dtype=self.dtype, mesh=self.mesh,
            norm_eps=self.norm_eps,
        )
        return x + y


class Laguna(nn.Module):
    vocab_size: int = 100352
    max_seq_len: int = 262144
    hidden_dim: int = 2048
    depth: int = 40
    layer_types: tuple = PERIOD * 10
    heads_per_layer: tuple = (48, 64, 64, 64) * 10  # num_attention_heads_per_layer
    num_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512             # sliding_window
    dense_layers: int = 1         # mlp_layer_types: the first is dense
    dense_ffn_dim: int = 8192     # intermediate_size
    ffn_dim: int = 512            # moe_intermediate_size
    shared_dim: int = 512         # shared_expert_intermediate_size
    routing: Routing = Routing(256, top_k=8, scoring="sigmoid",
                               routed_scale=2.5)
    full_rope_theta: float = 500000.0
    full_rotary_dim: int = 64     # partial_rotary_factor 0.5 of 128
    yarn: Yarn | None = Yarn(64.0, 4096, beta_fast=64.0, beta_slow=1.0,
                             attention_factor=1.4158883083359672)
    sliding_rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-6
    # per-BLOCK rematerialization policy (tpudist.remat names), as Llama's
    remat_policy: str | None = None
    # fused_ln=True runs every RMSNorm through the Pallas fused norm kernel
    # (same "scale" leaves); set by make_train_step(fused="ln"|"all")
    fused_ln: bool = False

    # the expert layers sow router counters into 'moe_stats' (no aux loss:
    # tpudist.train forwards them to telemetry on this flag)
    sows_moe_stats = True
    flops_counter = "laguna"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False):
        del train  # no dropout, no noise: one forward for both
        if tokens.shape[1] > self.max_seq_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if min(len(self.layer_types), len(self.heads_per_layer)) < self.depth:
            raise ValueError(
                f"{self.depth} layers need as many layer types and head "
                f"counts (got {len(self.layer_types)}, "
                f"{len(self.heads_per_layer)})")
        table = lambda name: self.param(
            name, nn.initializers.normal(0.02),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        x = table("embed")[tokens].astype(self.dtype)
        from tpudist.remat import remat_module

        block_cls = remat_module(LagunaBlock, self.remat_policy)
        for i in range(self.depth):
            x = block_cls(
                kind=self.layer_types[i], num_heads=self.heads_per_layer[i],
                num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                window=self.window, ffn_dim=self.ffn_dim,
                shared_dim=self.shared_dim, routing=self.routing,
                dense=i < self.dense_layers,
                dense_ffn_dim=self.dense_ffn_dim,
                full_rope_theta=self.full_rope_theta,
                full_rotary_dim=self.full_rotary_dim, yarn=self.yarn,
                sliding_rope_theta=self.sliding_rope_theta,
                dtype=self.dtype, attn_impl=self.attn_impl, mesh=self.mesh,
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


def laguna_xs_2(**kw) -> Laguna:
    """Laguna-XS.2 geometry (poolside/Laguna-XS.2 ``config.json``): 40
    layers in periods of one full-attention layer (48 query heads, YaRN on
    half of each head: theta 5e5, factor 64 over 4,096 original positions,
    beta 64 / 1, attention factor 1.4159) and three sliding-window layers
    (64 query heads, window 512, plain rotary theta 1e4), 8 key/value heads
    of 128 and a gated output in all; the first layer a dense SwiGLU of
    width 8192, then 256 experts of width 512 routed top-8 by sigmoid
    scores scaled 2.5 beside one shared expert of width 512; 2048 wide,
    vocabulary 100,352 with an untied head."""
    return Laguna(**kw)
