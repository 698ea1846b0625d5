"""Kanana-2 family decoder in Flax (``model_type: deepseek_v3``): latent
attention (MLA) in every block, a leading dense SwiGLU block, then expert
blocks — sigmoid-scored top-k routed experts beside a shared expert — and
an untied head.

No reference counterpart (the reference's only model is ResNet-50,
/root/reference/main.py:40). Sizes follow kakaocorp's
``kanana-2-30b-a3b-instruct-2601`` ``config.json``; the same equations are
written out plainly in ``benchmarks/reference/kanana.py``.

Pre-norm blocks, ``x <- x + MLA(RMSNorm(x))``, ``x <- x + F(RMSNorm(x))``:

- **MLA** (no query compression): ``q = u·W_q`` in heads of ``nope + rope``
  channels; ``[c ; k_rope] = u·W_kva`` with ``c`` the key/value latent
  (``kv_rank`` wide, RMS-normed) and ``k_rope`` ONE rotary key for all
  heads; ``[k_nope ; v] = c·W_kvb`` per head. Rotary embedding turns
  adjacent channel pairs of ``q_rope`` and ``k_rope``. Softmax attention
  over keys ``[k_nope ; k_rope]`` (``nope + rope`` wide) and values
  ``v_dim`` wide — the flash kernel takes the two widths as they are —,
  scaled by ``1/sqrt(nope + rope)``; one projection back.
- **F** is a dense SwiGLU in the first ``dense_layers`` blocks and
  :func:`tpudist.parallel.ep.dropless_moe` after them, under one
  :class:`~tpudist.parallel.ep.Routing` (sigmoid scores, top-k over all
  experts, a bias on the selection if the ``Routing`` brings one, the
  chosen scores normalised and scaled) with a shared expert of width
  ``shared_dim`` on every token.

Scope names inside a block are a contract with the device trace
(``tpudist/telemetry/trace.py``): ``h_N/mla_q``, ``mla_kv_down``,
``mla_kv_up``, ``mla_rope``, ``mla_attn`` (the attention call),
``mla_out``, and the expert layer's ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``, ``moe_shared``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.llama import apply_rope
from tpudist.models.zaya import _rms_norm
from tpudist.ops.attention import multi_head_attention
from tpudist.parallel.ep import Routing, dropless_moe


class KananaBlock(nn.Module):
    """One layer: MLA, then the dense SwiGLU (``dense``) or the expert
    layer."""

    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    ffn_dim: int
    shared_dim: int
    routing: Routing
    dense: bool = False
    dense_ffn_dim: int = 0
    rope_theta: float = 10000.0
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

    @nn.nowrap  # no ``h_N._mla`` between the block and its stages' scopes
    def _mla(self, u):
        b, s, d = u.shape
        h, nope, rope, dv = (self.num_heads, self.nope_dim, self.rope_dim,
                             self.v_dim)
        q = self._dense("mla_q", h * (nope + rope))(u)
        q = q.reshape(b, s, h, nope + rope)
        down = self._dense("mla_kv_down", self.kv_rank + rope)(u)
        c = self._norm("mla_kv_norm", self.dtype)(down[..., :self.kv_rank])
        kv = self._dense("mla_kv_up", h * (nope + dv))(c)
        kv = kv.reshape(b, s, h, nope + dv)
        with jax.named_scope("mla_rope"):
            turn = lambda x: apply_rope(x, theta=self.rope_theta,
                                        interleaved=True)
            # one rotary key for all heads, stood beside each head's own
            k_rope = jnp.broadcast_to(
                turn(down[..., None, self.kv_rank:]), (b, s, h, rope))
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
            k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
            v = kv[..., nope:]
        with jax.named_scope("mla_attn"):
            o = multi_head_attention(
                q, k, v, causal=True, impl=self.attn_impl, mesh=self.mesh,
                name="mla_attn",
            )
        return self._dense("mla_out", d)(o.reshape(b, s, h * dv))

    @nn.compact
    def __call__(self, x):
        x = x + self._mla(self._norm("attn_norm", self.dtype)(x))
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


class Kanana(nn.Module):
    vocab_size: int = 128256
    max_seq_len: int = 8192
    hidden_dim: int = 2048
    depth: int = 48
    dense_layers: int = 1       # first_k_dense_replace
    num_heads: int = 32
    nope_dim: int = 128         # qk_nope_head_dim
    rope_dim: int = 64          # qk_rope_head_dim
    v_dim: int = 128            # v_head_dim
    kv_rank: int = 512          # kv_lora_rank
    dense_ffn_dim: int = 6144   # intermediate_size
    ffn_dim: int = 768          # moe_intermediate_size
    shared_dim: int = 1536      # n_shared_experts x moe_intermediate_size
    routing: Routing = Routing(128, top_k=6, scoring="sigmoid",
                               routed_scale=2.448)
    rope_theta: float = 1e6
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
    flops_counter = "kanana"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False):
        del train  # no dropout, no noise: one forward for both
        if tokens.shape[1] > self.max_seq_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_seq_len "
                f"{self.max_seq_len}")
        table = lambda name: self.param(
            name, nn.initializers.normal(0.02),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        x = table("embed")[tokens].astype(self.dtype)
        from tpudist.remat import remat_module

        block_cls = remat_module(KananaBlock, self.remat_policy)
        for i in range(self.depth):
            x = block_cls(
                num_heads=self.num_heads, nope_dim=self.nope_dim,
                rope_dim=self.rope_dim, v_dim=self.v_dim,
                kv_rank=self.kv_rank, ffn_dim=self.ffn_dim,
                shared_dim=self.shared_dim, routing=self.routing,
                dense=i < self.dense_layers,
                dense_ffn_dim=self.dense_ffn_dim, rope_theta=self.rope_theta,
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


def kanana2_30b_a3b(**kw) -> Kanana:
    """Kanana-2-30B-A3B geometry (kakaocorp/kanana-2-30b-a3b-instruct-2601
    ``config.json``): 48 layers (1 dense of width 6144, 47 of 128 experts
    of width 768 routed top-6 by sigmoid scores scaled 2.448, beside 2
    shared experts), 2048 wide, 32 MLA heads (keys 128 + 64 rotary, values
    128, key/value latent 512), vocabulary 128,256 with an untied head,
    rotary theta 1e6 on adjacent pairs."""
    kw.setdefault("max_seq_len", 32768)
    return Kanana(**kw)
