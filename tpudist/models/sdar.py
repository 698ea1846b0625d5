"""SDAR family decoder in Flax (``model_type: sdar_moe``): a Qwen3-MoE
style stack — grouped-query attention with an RMSNorm on every head's q and
k, softmax top-k routed experts in every layer, an untied head — trained by
DIFFUSION OVER BLOCKS (BD3-LM, arXiv:2503.09573, as the SDAR report
arXiv:2510.06303 adopts it): inside a block of ``block_length`` tokens the
model denoises masked tokens with bidirectional attention, across blocks it
is causal.

No reference counterpart (the reference's only model is ResNet-50,
/root/reference/main.py:40). Sizes follow JetLM's ``SDAR-30B-A3B-Chat``
``config.json``; the same equations are written out plainly in
``benchmarks/reference/sdar.py``.

Pre-norm blocks without biases, ``x <- x + Attn(RMSNorm(x))``, ``x <- x +
F(RMSNorm(x))``:

- **Attn**: ``[q ; k ; v] = u·W_qkv`` (``num_heads`` query heads on
  ``num_kv_heads`` key/value heads of ``head_dim``); q and k pass an RMSNorm
  over the head's channels with a learned scale; rotary embedding turns all
  channels, half-split pairs, at the positions the caller gives; softmax
  attention under the block's ``mask`` (a
  :class:`~tpudist.ops.attention.BlockMask`: the flash kernel skips its
  empty tiles); one projection back.
- **F** is :func:`tpudist.parallel.ep.dropless_moe` under one
  :class:`~tpudist.parallel.ep.Routing` (softmax scores over all experts,
  top-k, the chosen scores normalised to sum 1, a bias on the selection if
  the ``Routing`` brings one); no shared expert.

Training (:func:`block_diffusion_forward`) runs the NOISED copy of each
sequence and its CLEAN copy side by side through the stack once — ``2 L``
rows, both halves at positions ``0..L-1``, under ``BlockMask(block_length,
L)``: a noised block sees itself whole and the clean blocks before it, the
clean half is block-causal — and takes the head over the noised ``L`` rows
only, each masked position predicting its own token under the weight the
loader's :func:`block_diffusion_transform` gives it (``1/t`` of its block).
Called with tokens alone the model runs plain block-causal over its rows:
what a served model prefills with.

Scope names inside a block are a contract with the device trace
(``tpudist/telemetry/trace.py``): ``h_N/attn_qkv``, ``attn_qk_norm``,
``attn_rope``, ``bd_attn`` (the attention call), ``attn_out``, and the
expert layer's ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpudist.models.llama import apply_rope
from tpudist.models.zaya import _rms_norm
from tpudist.ops.attention import BlockMask, multi_head_attention
from tpudist.parallel.ep import Routing, dropless_moe


class SdarBlock(nn.Module):
    """One layer: grouped-query attention under ``mask``, then the expert
    layer. ``positions`` ``[S]`` are the rows' rotary positions."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_dim: int
    routing: Routing
    mask: BlockMask
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-6
    fused_ln: bool = False

    def _norm(self, name: str, dtype):
        return _rms_norm(name, dtype, eps=self.norm_eps, fused=self.fused_ln,
                         mesh=self.mesh)

    @nn.nowrap  # no ``h_N._attn`` between the block and its stages' scopes
    def _attn(self, u, positions):
        b, s, d = u.shape
        h, kv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        qkv = nn.Dense((h + 2 * kv) * dh, use_bias=False, dtype=self.dtype,
                       name="attn_qkv")(u)
        q, k, v = jnp.split(qkv, [h * dh, (h + kv) * dh], axis=-1)
        q, k, v = (x.reshape(b, s, -1, dh) for x in (q, k, v))
        with jax.named_scope("attn_qk_norm"):
            # over each head's channels, one learned scale for all heads
            head_norm = lambda name: nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=name)
            q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)
        with jax.named_scope("attn_rope"):
            turn = lambda x: apply_rope(x, theta=self.rope_theta,
                                        positions=positions)
            q, k = turn(q), turn(k)
        with jax.named_scope("bd_attn"):
            o = multi_head_attention(
                q, k, v, mask=self.mask, impl=self.attn_impl, mesh=self.mesh,
                name="bd_attn",
            )
        return nn.Dense(d, use_bias=False, dtype=self.dtype,
                        name="attn_out")(o.reshape(b, s, h * dh))

    @nn.compact
    def __call__(self, x, positions):
        x = x + self._attn(self._norm("attn_norm", self.dtype)(x), positions)
        # the router scores from a float32 u; the experts compute in dtype
        u = self._norm("moe_norm", jnp.float32)(x)
        y, _ = dropless_moe(
            self, u, routing=self.routing, ffn_dim=self.ffn_dim,
            dtype=self.dtype, mesh=self.mesh, norm_eps=self.norm_eps,
        )
        return x + y


class Sdar(nn.Module):
    vocab_size: int = 151936
    max_seq_len: int = 32768
    hidden_dim: int = 2048
    depth: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    ffn_dim: int = 768          # moe_intermediate_size
    routing: Routing = Routing(128, top_k=8)
    block_length: int = 4
    rope_theta: float = 1e6
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    norm_eps: float = 1e-6
    # per-BLOCK rematerialization policy (tpudist.remat names), as Llama's
    remat_policy: str | None = None
    # fused_ln=True runs the blocks' and the final RMSNorm through the
    # Pallas fused norm kernel (same "scale" leaves); set by
    # make_train_step(fused="ln"|"all")
    fused_ln: bool = False

    # the expert layers sow router counters into 'moe_stats' (no aux loss:
    # tpudist.train forwards them to telemetry on this flag)
    sows_moe_stats = True
    flops_counter = "sdar"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False,
                 *, positions=None, mask: BlockMask | None = None):
        """``tokens [B, S]``. ``positions [S]`` and ``mask`` default to
        ``0..S-1`` under block-causal attention; the training forward
        passes both halves' positions and the two-copy mask."""
        del train  # no dropout, no noise: one forward for both
        s = tokens.shape[1]
        if mask is None:
            mask = BlockMask(self.block_length)
        if positions is None:
            positions = jnp.arange(s, dtype=jnp.float32)
        if positions.shape[0] != s or max(s - mask.noised_len, mask.noised_len) \
                > self.max_seq_len:
            raise ValueError(
                f"{s} rows at {positions.shape[0]} positions under {mask}: "
                f"a copy exceeds max_seq_len {self.max_seq_len}, or the "
                "positions do not cover the rows")
        table = lambda name: self.param(
            name, nn.initializers.normal(0.02),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        x = table("embed")[tokens].astype(self.dtype)
        from tpudist.remat import remat_module

        block_cls = remat_module(SdarBlock, self.remat_policy)
        for i in range(self.depth):
            x = block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, ffn_dim=self.ffn_dim,
                routing=self.routing, mask=mask, rope_theta=self.rope_theta,
                dtype=self.dtype, attn_impl=self.attn_impl, mesh=self.mesh,
                norm_eps=self.norm_eps, fused_ln=self.fused_ln,
                name=f"h_{i}",
            )(x, positions)
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


def sdar_30b_a3b(**kw) -> Sdar:
    """SDAR-30B-A3B-Chat geometry (JetLM/SDAR-30B-A3B-Chat
    ``config.json``): 48 layers, 2048 wide, 32 query heads on 4 key/value
    heads of 128 with q/k norms, 128 experts of width 768 routed top-8 by
    softmax scores in every layer, vocabulary 151,936 with an untied head,
    rotary theta 1e6; block length 4 (the release's default)."""
    return Sdar(**kw)


def block_diffusion_transform(mask_id: int, block_length: int, *,
                              t_min: float = 1e-3, seed: int = 0,
                              key: str = "tokens"):
    """Loader transform applying block-diffusion corruption on the host
    (beside :func:`tpudist.models.bert.mlm_transform`).

    For every block of ``block_length`` tokens of every row a noise level
    ``t ~ U[t_min, 1]`` is drawn; each token of the block becomes
    ``mask_id`` with probability ``t``, independently. Under the linear
    schedule ``alpha_t = 1 - t`` the NELBO weighs a masked position by
    ``1/t``: produces ``{"tokens": noised, "clean": originals,
    "loss_weight": m / t}`` with ``m`` 1 where masked (float32; 0 where the
    token was kept). Randomness is a seeded per-loader stream, like the
    augmentation transforms — deterministic order, not replayed across a
    mid-epoch resume."""
    if not 0.0 < t_min <= 1.0:
        raise ValueError(f"t_min {t_min} must lie in (0, 1]")
    rng = np.random.Generator(np.random.PCG64(seed))

    def run(batch):
        clean = np.asarray(batch[key])
        b, s = clean.shape
        if s % block_length:
            raise ValueError(
                f"sequence {s} is no multiple of block_length {block_length}")
        t = rng.uniform(t_min, 1.0, (b, s // block_length))
        t = np.repeat(t, block_length, axis=1)
        masked = rng.random((b, s)) < t
        out = dict(batch)
        out[key] = np.where(masked, mask_id, clean).astype(clean.dtype)
        out["clean"] = clean
        out["loss_weight"] = (masked / t).astype(np.float32)
        return out

    return run


def block_diffusion_forward(model: Sdar, block_length: int | None = None,
                            chunk: int = 512, *, moe_stats: bool = False):
    """``forward_loss`` for :func:`tpudist.train.make_train_step`: the
    block-diffusion objective in ONE pass. Expects batches from
    :func:`block_diffusion_transform` (``tokens`` the noised copy,
    ``clean``, ``loss_weight``).

    Rows ``[noised ; clean]`` (``2 L``) go through the stack once, both
    halves at positions ``0..L-1``, under ``BlockMask(block_length, L)``;
    the head runs over the first ``L`` rows only, scanned in chunks by
    :func:`~tpudist.models.lm_utils.chunked_head_reduce` (no logits kept),
    each position predicting its own clean token: ``loss = (1 / (B L))
    sum_i w_i CE(logits_i, clean_i)``, the normaliser riding in the
    weights.

    Supports ``.rebuild`` / ``.with_moe_stats`` as ``fit`` asks of a
    ``forward_loss`` (:func:`~tpudist.models.lm_utils.chunked_lm_forward`
    has the same two)."""
    from tpudist.models.lm_utils import chunked_ce_sum, lm_head_weight

    block_length = block_length or model.block_length
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")

    def forward_loss(params, batch_stats, batch):
        noised, clean = batch["tokens"], batch["clean"]
        b, length = noised.shape
        position = jnp.arange(length, dtype=jnp.float32)
        out = model.apply(
            {"params": params}, jnp.concatenate([noised, clean], axis=1),
            train=True, return_hidden=True,
            positions=jnp.concatenate([position, position]),
            mask=BlockMask(block_length, length),
            mutable=["moe_stats"] if moe_stats else False,
        )
        hidden, updates = out if moe_stats else (out, {})
        # the mean's 1 / (b L) rides in the weights (chunked_head_reduce)
        loss = chunked_ce_sum(
            lm_head_weight(params), hidden[:, :length], clean,
            batch["loss_weight"] / (b * length), chunk,
        )
        if moe_stats:
            return loss, (batch_stats, updates.get("moe_stats", {}))
        return loss, batch_stats

    forward_loss.rebuild = lambda m: block_diffusion_forward(
        m, block_length, chunk, moe_stats=moe_stats
    )
    forward_loss.with_moe_stats = lambda: block_diffusion_forward(
        model, block_length, chunk, moe_stats=True
    )
    forward_loss.model = model
    return forward_loss
