"""ZAYA1-family decoder in Flax: compressed convolutional attention (CCA),
a top-1 expert layer routed by a small MLP whose state is carried through
the layer stack, and learned scaling of the residual stream.

No reference counterpart (the reference's only model is ResNet-50,
/root/reference/main.py:40). Sizes follow Zyphra's ``ZAYA1-8B``
``config.json``; what that file does not fix is written out, with the same
equations, in ``benchmarks/reference/zaya.py`` and listed under ``assumed``
in ``benchmarks/configs/zaya1-8b.json``.

Every layer is an attention sublayer then an expert sublayer, each merged
into the stream as ``x <- (a*x + b) + c*f(RMSNorm(x))`` with ``a``, ``b``,
``c`` learned per sublayer.

- **CCA** works inside a latent: q, k, v are projected DOWN (to
  ``heads*head_dim``, ``kv_heads*head_dim`` and two halves of
  ``kv_heads*head_dim/2``), q and k pass two causal convolutions over the
  sequence (depthwise, then grouped by head) and gain a q-k mean, v is half
  this token's and half the token's before (the value shift), q and k are
  L2-normalised per head (k with a learned temperature), rotary embedding
  turns the first ``rotary_dim`` channels of each head, grouped-query
  softmax attention runs in the latent, and one projection goes back up.
- **The expert sublayer** is :func:`tpudist.parallel.ep.dropless_moe` under
  one :class:`~tpudist.parallel.ep.Routing`: no capacity, no dropped token,
  one grouped product over the experts this shard holds; plain top-1
  unless the ``Routing`` brings a ``selection_bias``. Its router hands
  its state ``r`` to the next layer: the block maps ``(x, r) -> (x, r)``,
  and recomputation (``remat_policy``) and the chunked-CE forward carry
  the pair.

Scope names inside a block are a contract with the device trace
(``tpudist/telemetry/trace.py``): ``h_N/cca_proj``, ``h_N/cca_mix``,
``h_N/cca_attn`` (the attention call), ``h_N/cca_out``, ``h_N/moe_router``,
``h_N/moe_dispatch``, ``h_N/moe_experts``, ``h_N/moe_combine``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.models.llama import apply_rope
from tpudist.ops.attention import multi_head_attention
from tpudist.parallel.ep import Routing, dropless_moe

def shift_right(x, steps: int = 1):
    """``y[:, t] = x[:, t - steps]``, nought before the start: the one
    sequence-mixing primitive of the convolutions and the value shift."""
    if steps == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return jnp.pad(x[:, : x.shape[1] - steps], pad)


def _rms_unit(x, eps: float):
    """``sqrt(D) * x / ||x||`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rms_norm(name: str, dtype, *, eps: float, fused: bool, mesh):
    """The model's RMSNorm under ``name``: the Pallas fused norm kernel
    with ``fused`` (same ``scale`` leaf), flax's otherwise."""
    if fused:
        from tpudist.ops.layernorm import FusedLayerNorm

        return FusedLayerNorm(epsilon=eps, dtype=dtype, rms=True, mesh=mesh,
                              name=name)
    return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)


class CcaMix(nn.Module):
    """Everything of CCA between its projections and its attention call:
    q-k mean, the two causal convolutions, the value shift, the norms with
    the key temperature, and the partial rotary embedding. Takes the
    latent ``q [B,S,H,D]``, ``k [B,S,Hkv,D]``, ``va``/``vb [B,S,Hkv,D/2]``;
    returns ``q``, ``k``, ``v`` ready for attention."""

    conv_kernels: tuple[int, int] = (2, 2)
    rotary_dim: int | None = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, q, k, va, vb):
        b, s, h, dh = q.shape
        kv = k.shape[2]
        rep = h // kv
        k0, k1 = self.conv_kernels
        init = nn.initializers.lecun_normal()
        conv0 = self.param("conv0", init, (k0, (h + kv) * dh), jnp.float32)
        conv1 = self.param(
            "conv1", nn.initializers.lecun_normal(in_axis=(0, 2), out_axis=3,
                                                  batch_axis=(1,)),
            (k1, h + kv, dh, dh), jnp.float32,
        )
        temp = self.param(
            "temp_scale", nn.initializers.ones_init(), (kv,), jnp.float32
        )
        # q-k mean, per key/value group
        mean_q = 0.5 * (q + jnp.repeat(k, rep, axis=2))
        mean_k = 0.5 * (
            jnp.mean(q.reshape(b, s, kv, rep, dh), axis=3).astype(k.dtype) + k
        )
        # conv0: depthwise over the sequence; conv1: grouped by head — as
        # shifted multiply-adds / batched products, tap j on token t-(K-1-j)
        qk = jnp.concatenate([q, k], axis=2)  # [B, S, H+Hkv, D]
        w0 = conv0.astype(self.dtype).reshape(k0, h + kv, dh)
        y = sum(w0[j] * shift_right(qk, k0 - 1 - j) for j in range(k0))
        w1 = conv1.astype(self.dtype)
        y = sum(
            jnp.einsum("bshc,hcd->bshd", shift_right(y, k1 - 1 - j), w1[j])
            for j in range(k1)
        )
        q = y[:, :, :h] + mean_q
        k = y[:, :, h:] + mean_k
        # value shift: half of each value head from this token, half from
        # the token before
        v = jnp.concatenate([va, shift_right(vb)], axis=-1)
        # unit-RMS heads (sqrt(D) * x / ||x||), a learned temperature on k
        q = _rms_unit(q, self.norm_eps)
        k = _rms_unit(k, self.norm_eps) * temp[:, None]
        rope = lambda x: apply_rope(
            x.astype(self.dtype), theta=self.rope_theta,
            rotary_dim=self.rotary_dim,
        )
        return rope(q), rope(k), v


class ZayaBlock(nn.Module):
    """One layer: ``(x, r) -> (x, r)`` — CCA sublayer, expert sublayer,
    each with learned residual scaling; ``r`` is the router's carry."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_dim: int
    routing: Routing
    conv_kernels: tuple[int, int] = (2, 2)
    rotary_dim: int | None = None
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-5
    fused_ln: bool = False

    def _norm(self, name: str, dtype):
        return _rms_norm(name, dtype, eps=self.norm_eps, fused=self.fused_ln,
                         mesh=self.mesh)

    def _merge(self, name: str, x, y):
        """``(a*x + b) + c*y`` with ``a``, ``b``, ``c`` learned per
        sublayer (``a``, ``c`` start at one, ``b`` at nought)."""
        d = x.shape[-1]
        one, zero = nn.initializers.ones_init(), nn.initializers.zeros_init()
        a = self.param(f"{name}_in_scale", one, (d,), jnp.float32)
        shift = self.param(f"{name}_shift", zero, (d,), jnp.float32)
        c = self.param(f"{name}_out_scale", one, (d,), jnp.float32)
        cast = lambda p: p.astype(self.dtype)
        return (cast(a) * x + cast(shift)) + cast(c) * y

    @nn.compact
    def __call__(self, x, r=None):
        b, s, d = x.shape
        h, kv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        if h % kv or dh % 2:
            raise ValueError(f"heads {h}/{kv} of {dh}: need kv | heads, even size")
        u = self._norm("attn_norm", self.dtype)(x)
        latent = nn.Dense(
            (h + 2 * kv) * dh, use_bias=False, dtype=self.dtype,
            name="cca_proj",
        )(u)
        q, k, va, vb = jnp.split(
            latent, [h * dh, (h + kv) * dh, (h + kv) * dh + kv * dh // 2],
            axis=-1,
        )
        q, k, v = CcaMix(
            self.conv_kernels, self.rotary_dim, self.rope_theta,
            self.norm_eps, self.dtype, name="cca_mix",
        )(q.reshape(b, s, h, dh), k.reshape(b, s, kv, dh),
          va.reshape(b, s, kv, dh // 2), vb.reshape(b, s, kv, dh // 2))
        with jax.named_scope("cca_attn"):
            o = multi_head_attention(
                q, k, v, causal=True, impl=self.attn_impl, mesh=self.mesh,
                name="cca_attn",
            )
        o = nn.Dense(d, use_bias=False, dtype=self.dtype, name="cca_out")(
            o.reshape(b, s, h * dh)
        )
        x = self._merge("attn", x, o)

        # the router scores from a float32 u; the experts compute in dtype
        u = self._norm("moe_norm", jnp.float32)(x)
        y, r = dropless_moe(
            self, u, r, routing=self.routing, ffn_dim=self.ffn_dim,
            dtype=self.dtype, mesh=self.mesh, norm_eps=self.norm_eps,
        )
        return self._merge("moe", x, y), r


class Zaya(nn.Module):
    vocab_size: int = 262272
    max_seq_len: int = 4096
    hidden_dim: int = 2048
    depth: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    ffn_dim: int = 2048
    routing: Routing = Routing(16, top_k=1, router="mlp", router_width=256)
    conv_kernels: tuple[int, int] = (2, 2)
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-5
    # per-BLOCK rematerialization policy (tpudist.remat names), as Llama's:
    # backward keeps the (x, r) pairs between blocks and recomputes inside
    remat_policy: str | None = None
    # fused_ln=True runs every RMSNorm through the Pallas fused norm kernel
    # (same "scale" leaves); set by make_train_step(fused="ln"|"all")
    fused_ln: bool = False

    # the expert layers sow router counters into 'moe_stats' (no aux loss:
    # tpudist.train forwards them to telemetry on this flag)
    sows_moe_stats = True

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False):
        del train  # no dropout, no noise: one forward for both
        b, s = tokens.shape
        if s > self.max_seq_len:
            raise ValueError(f"sequence {s} exceeds max_seq_len {self.max_seq_len}")
        embed = self.param(
            "embed", nn.initializers.normal(0.02),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        x = embed[tokens].astype(self.dtype)
        from tpudist.remat import remat_module

        block_cls = remat_module(ZayaBlock, self.remat_policy)
        r = None
        for i in range(self.depth):
            x, r = block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, ffn_dim=self.ffn_dim,
                routing=self.routing, conv_kernels=self.conv_kernels,
                rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                rope_theta=self.rope_theta, dtype=self.dtype,
                attn_impl=self.attn_impl, mesh=self.mesh,
                norm_eps=self.norm_eps, fused_ln=self.fused_ln,
                name=f"h_{i}",
            )(x, r)
        x = _rms_norm("norm", self.dtype, eps=self.norm_eps,
                      fused=self.fused_ln, mesh=self.mesh)(x)
        if return_hidden:
            return x
        # the head is tied to the embedding (tie_word_embeddings), no bias
        return jnp.einsum(
            "bsd,vd->bsv", x, embed.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )


def zaya1_8b(**kw) -> Zaya:
    """ZAYA1-8B geometry (Zyphra/ZAYA1-8B ``config.json``): 40 layers, 2048
    wide, CCA with 8 query heads on 2 key/value heads of 128, 16 experts of
    width 2048 routed top-1 by an MLP router of width 256, vocabulary
    262,272 tied to the head, rotary on half of each head at theta 5e6."""
    kw.setdefault("max_seq_len", 131072)
    return Zaya(**kw)
