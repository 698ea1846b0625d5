"""ViT-B/16 in Flax — BASELINE.json config 4 (ViT-B/16, DP + bfloat16).

No reference counterpart exists (the reference is ResNet-only,
/root/reference/main.py:40); this covers the "transformer grads over ICI"
target. TPU-first: bf16 activations with fp32 params, patchify as a single
strided conv (one big MXU matmul), attention via tpudist.ops. Encoder
kernels carry the same Megatron ``tensor``-axis partitioning metadata as
GPT-2 (qkv/mlp-in column-parallel, out/mlp-out row-parallel) — inert on a
``tensor=1`` mesh, GSPMD-sharded otherwise.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from tpudist.mesh import TENSOR_AXIS
from tpudist.ops.attention import multi_head_attention
from tpudist.parallel.tp import partitioned as _partitioned


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        dense_init = nn.initializers.lecun_normal()
        x = nn.Dense(
            self.mlp_dim, dtype=self.dtype,
            kernel_init=_partitioned(dense_init, None, TENSOR_AXIS),
            bias_init=_partitioned(nn.initializers.zeros_init(), TENSOR_AXIS),
        )(x)
        x = nn.gelu(x)
        return nn.Dense(
            d, dtype=self.dtype,
            kernel_init=_partitioned(dense_init, TENSOR_AXIS, None),
        )(x)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    dropout: float = 0.0
    mesh: Any = None  # multi-chip Pallas attention (shard_map wrap)
    # fused_ln=True: both pre-LNs run the Pallas fused residual-add+LN
    # kernel (tpudist.ops.layernorm) under the flax auto-names
    # ("LayerNorm_0"/"LayerNorm_1"), so the param tree is unchanged
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        b, s, d = x.shape
        h = self.num_heads
        drop = lambda y: (
            nn.Dropout(self.dropout, deterministic=not train)(y)
            if self.dropout else y
        )
        dense_init = nn.initializers.lecun_normal()
        if self.fused_ln:
            from tpudist.ops.layernorm import FusedLayerNorm

            # explicit names pin the flax auto-numbering the unfused
            # modules would have received
            ln = lambda name: FusedLayerNorm(
                epsilon=1e-6, dtype=self.dtype, mesh=self.mesh, name=name
            )
        else:
            ln = lambda name: nn.LayerNorm(dtype=self.dtype, name=name)
        y = ln("LayerNorm_0")(x)
        qkv = nn.DenseGeneral(
            (3, h, d // h), dtype=self.dtype, name="qkv",
            kernel_init=_partitioned(dense_init, None, None, TENSOR_AXIS, None),
            bias_init=_partitioned(nn.initializers.zeros_init(), None, TENSOR_AXIS, None),
        )(y)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = multi_head_attention(q, k, v, impl=self.attn_impl,
                                    mesh=self.mesh, name=self.name)
        y = nn.DenseGeneral(
            d, axis=(-2, -1), dtype=self.dtype, name="out",
            kernel_init=_partitioned(dense_init, TENSOR_AXIS, None, None),
        )(attn)
        if self.fused_ln:
            # residual add + LN in one kernel sweep (pre-norm composition)
            y, x = ln("LayerNorm_1")(drop(y), residual=x)
        else:
            x = x + drop(y)
            y = ln("LayerNorm_1")(x)
        return x + drop(MlpBlock(self.mlp_dim, dtype=self.dtype)(y))


class ViT(nn.Module):
    num_classes: int = 1000
    patch_size: int = 16
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    dropout: float = 0.0  # residual dropout; rng plumbed by tpudist.train
    mesh: Any = None  # multi-chip Pallas attention (shard_map wrap)
    # fused_ln=True: every encoder LN + the final LN run the Pallas fused
    # residual-add+LN kernel (tpudist.ops.layernorm); param tree unchanged.
    # Usually set via make_train_step(fused="ln"|"all") / main.py --fused.
    fused_ln: bool = False

    @property
    def flops_counter(self) -> str | None:
        """Analytic-FLOPs family tag (tpudist.telemetry.flops). The vit
        counter assumes the standard 4·H MLP; a custom mlp_dim gets no
        tag (no MFU row) rather than a wrong numerator."""
        return "vit" if self.mlp_dim == 4 * self.hidden_dim else None

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = jnp.asarray(x, self.dtype)
        p = self.patch_size
        x = nn.Conv(
            self.hidden_dim, (p, p), strides=(p, p), padding="VALID",
            dtype=self.dtype, name="embedding",
        )(x)
        b, gh, gw, d = x.shape
        x = x.reshape(b, gh * gw, d)
        cls = self.param("cls", nn.initializers.zeros, (1, 1, d), jnp.float32)
        x = jnp.concatenate([jnp.tile(cls.astype(self.dtype), (b, 1, 1)), x], axis=1)
        pos = self.param(
            "pos_embedding", nn.initializers.normal(0.02), (1, x.shape[1], d), jnp.float32
        )
        x = x + pos.astype(self.dtype)
        for i in range(self.depth):
            x = EncoderBlock(
                self.num_heads, self.mlp_dim, dtype=self.dtype,
                attn_impl=self.attn_impl, dropout=self.dropout,
                mesh=self.mesh, fused_ln=self.fused_ln, name=f"block_{i}",
            )(x, train=train)
        if self.fused_ln:
            from tpudist.ops.layernorm import FusedLayerNorm

            x = FusedLayerNorm(
                epsilon=1e-6, dtype=self.dtype, mesh=self.mesh,
                name="LayerNorm_0",
            )(x)
        else:
            x = nn.LayerNorm(dtype=self.dtype, name="LayerNorm_0")(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x[:, 0])


def vit_b16(**kw) -> ViT:
    return ViT(**kw)
