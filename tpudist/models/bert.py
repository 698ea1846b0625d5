"""BERT-style bidirectional encoder with a masked-LM objective.

No reference counterpart (the reference is a single ResNet DDP script,
SURVEY.md §2.12); built as a capability extension: the encoder complement
of the GPT-2/Llama decoder families, sharing the framework's contracts —
the same Megatron TP metadata scheme over the ``tensor`` axis
(``tpudist.parallel.tp``), the same attention ops (``tpudist.ops``), the
``return_hidden`` hook, and the ``forward_loss`` train-step interface
(:func:`mlm_forward` plugs into ``make_train_step`` exactly like
``chunked_lm_forward``).

Architecture follows BERT-base conventions: learned token+position (+
segment) embeddings with post-embedding LayerNorm, post-LN transformer
blocks with bidirectional attention and GELU MLPs, and a weight-tied MLM
head behind BERT's dense+LN "transform".

The MLM corruption runs host-side as a loader ``transform``
(:func:`mlm_transform`) with the standard 80/10/10 recipe — integer ops on
the host keep the device step static-shaped, and the transform slots into
the existing DataLoader/TokenWindowLoader pipeline like any augmentation.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpudist.mesh import TENSOR_AXIS
from tpudist.ops.attention import multi_head_attention
from tpudist.parallel.tp import partitioned as _partitioned


class EncoderBlock(nn.Module):
    """Post-LN bidirectional transformer block (BERT convention: the
    residual sum is normalized, rather than the branch input)."""

    num_heads: int
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    dropout: float = 0.0
    mesh: Any = None
    # fused_ln=True: both post-LNs run the Pallas fused residual-add+LN
    # kernel (tpudist.ops.layernorm) — the post-norm composition is the
    # ideal fusion target (the sum never needs a separate HBM round trip;
    # only the normed value is written). Same param names as nn.LayerNorm.
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True, attention_mask=None):
        b, s, d = x.shape
        h = self.num_heads
        drop = lambda y: (
            nn.Dropout(self.dropout, deterministic=not train)(y)
            if self.dropout else y
        )
        if self.fused_ln:
            from tpudist.ops.layernorm import FusedLayerNorm

            post_ln = lambda name, res, y: FusedLayerNorm(
                epsilon=1e-12, dtype=self.dtype, mesh=self.mesh, name=name
            )(y, residual=res, return_residual=False)
        else:
            post_ln = lambda name, res, y: nn.LayerNorm(
                epsilon=1e-12, dtype=self.dtype, name=name
            )(res + y)
        dense_init = nn.initializers.lecun_normal()
        # column-parallel qkv / row-parallel out — same TP scheme as the
        # decoder Block (tpudist/models/gpt2.py), no causal mask
        qkv = nn.DenseGeneral(
            (3, h, d // h), dtype=self.dtype, name="qkv",
            kernel_init=_partitioned(dense_init, None, None, TENSOR_AXIS, None),
            bias_init=_partitioned(
                nn.initializers.zeros_init(), None, TENSOR_AXIS, None
            ),
        )(x)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.attn_impl in ("ring", "ulysses", "ulysses_flash"):
            # context-parallel bidirectional attention over the 'seq' mesh
            # axis (tpudist.parallel.cp, causal=False) — long-document
            # encoder training with sequence-sharded activations
            if attention_mask is not None:
                raise ValueError(
                    f"attention_mask is not supported with attn_impl="
                    f"{self.attn_impl!r} (the context-parallel paths assume "
                    "dense fixed-length windows); pad-free batches or the "
                    "xla/flash impls"
                )
            if self.mesh is None:
                raise ValueError(
                    f"attn_impl={self.attn_impl!r} needs the model's mesh= "
                    "field set (the shard_map runs over its 'seq' axis)"
                )
            from tpudist.parallel.cp import ring_attention, ulysses_attention

            if self.attn_impl == "ring":
                attn = ring_attention(q, k, v, self.mesh, causal=False)
            else:
                attn_fn = None
                if self.attn_impl == "ulysses_flash":
                    from tpudist.ops.attention import kernel_attention

                    attn_fn = kernel_attention
                attn = ulysses_attention(
                    q, k, v, self.mesh, causal=False, attn_fn=attn_fn
                )
        else:
            # [b, s] key-padding mask (1 = real token) → broadcast over
            # heads and query positions: padded KEYS are excluded from every
            # softmax; padded query rows produce garbage that downstream
            # consumers never read (BERT reads [CLS] / masked positions only)
            key_mask = (
                None if attention_mask is None
                else attention_mask[:, None, None, :].astype(bool)
            )
            attn = multi_head_attention(
                q, k, v, causal=False, mask=key_mask, impl=self.attn_impl,
                mesh=self.mesh, name=self.name,
            )
        y = nn.DenseGeneral(
            d, axis=(-2, -1), dtype=self.dtype, name="out",
            kernel_init=_partitioned(dense_init, TENSOR_AXIS, None, None),
        )(attn)
        x = post_ln("ln_attn", x, drop(y))
        y = nn.Dense(
            4 * d, dtype=self.dtype, name="mlp_fc",
            kernel_init=_partitioned(dense_init, None, TENSOR_AXIS),
            bias_init=_partitioned(nn.initializers.zeros_init(), TENSOR_AXIS),
        )(x)
        # exact (erf) GELU — BERT's convention, and what HF BertForMaskedLM
        # computes; the tanh approximation is GPT-2's flavor
        y = nn.gelu(y, approximate=False)
        y = nn.Dense(
            d, dtype=self.dtype, name="mlp_proj",
            kernel_init=_partitioned(dense_init, TENSOR_AXIS, None),
        )(y)
        return post_ln("ln_mlp", x, drop(y))


class MlmHead(nn.Module):
    """BERT's MLM head: transform (dense + gelu + LN) then the weight-tied
    decode against the embedding table with a free output bias. A submodule
    (its own param scope) so :func:`mlm_forward`'s chunked path can apply it
    per sequence chunk without duplicating the math."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, wte):
        d = wte.shape[1]
        y = nn.Dense(d, dtype=self.dtype, name="transform")(x)
        y = nn.gelu(y, approximate=False)  # erf GELU, the BERT convention
        y = nn.LayerNorm(epsilon=1e-12, dtype=self.dtype, name="ln")(y)
        logits = jnp.einsum(
            "...d,vd->...v", y, wte.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (wte.shape[0],), jnp.float32
        )
        return logits + bias


class _CarryEncoderBlock(nn.Module):
    """:class:`EncoderBlock` with the (carry, xs) → (carry, ys) signature
    ``nn.scan`` maps over (``train`` rides as a field)."""

    num_heads: int
    train: bool = True
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    dropout: float = 0.0
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, attention_mask):
        x = EncoderBlock(
            self.num_heads, dtype=self.dtype, attn_impl=self.attn_impl,
            mesh=self.mesh, dropout=self.dropout, fused_ln=self.fused_ln,
            name="block",
        )(x, train=self.train, attention_mask=attention_mask)
        return x, None


class Bert(nn.Module):
    vocab_size: int = 30522
    max_seq_len: int = 512
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    type_vocab: int = 2
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    dropout: float = 0.0
    mesh: Any = None
    # scan_layers/remat_layers: nn.scan'd depth with optional per-layer
    # checkpointing — same fields and semantics as the decoder families
    # (one traced layer at any depth; params stack [depth, ...])
    scan_layers: bool = False
    remat_layers: bool = False
    # fused_ln=True: the embedding LN and every block's post-LNs run the
    # Pallas fused residual-add+LN kernel (tpudist.ops.layernorm). Same
    # param tree; usually set via make_train_step(fused="ln"|"all").
    fused_ln: bool = False

    @property
    def flops_counter(self) -> str:
        """Analytic-FLOPs family tag (tpudist.telemetry.flops): encoder
        blocks + the MLM head's transform and tied projection."""
        return "bert"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False,
                 token_types=None, attention_mask=None):
        b, s = tokens.shape
        if s > self.max_seq_len:
            raise ValueError(
                f"sequence {s} exceeds max_seq_len {self.max_seq_len}"
            )
        wte = self.param(
            "wte",
            _partitioned(nn.initializers.normal(0.02), TENSOR_AXIS, None),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        wpe = self.param(
            "wpe", nn.initializers.normal(0.02),
            (self.max_seq_len, self.hidden_dim), jnp.float32,
        )
        x = wte[tokens] + wpe[:s]
        if self.type_vocab:
            wty = self.param(
                "wty", nn.initializers.normal(0.02),
                (self.type_vocab, self.hidden_dim), jnp.float32,
            )
            types = (
                jnp.zeros_like(tokens) if token_types is None else token_types
            )
            x = x + wty[types]
        if self.fused_ln:
            from tpudist.ops.layernorm import FusedLayerNorm

            x = FusedLayerNorm(
                epsilon=1e-12, dtype=self.dtype, mesh=self.mesh,
                name="ln_embed",
            )(x.astype(self.dtype))
        else:
            x = nn.LayerNorm(
                epsilon=1e-12, dtype=self.dtype, name="ln_embed"
            )(x.astype(self.dtype))
        if self.dropout:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        if self.scan_layers:
            body = (
                nn.remat(_CarryEncoderBlock)
                if self.remat_layers else _CarryEncoderBlock
            )
            scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=self.depth,
                # the padding mask is layer-invariant: broadcast, not mapped
                in_axes=nn.broadcast,
                # stacked depth axis carries no partition name (unsharded);
                # per-layer TENSOR_AXIS metadata shifts right intact
                metadata_params={nn.PARTITION_NAME: None},
            )(
                num_heads=self.num_heads, train=train, dtype=self.dtype,
                attn_impl=self.attn_impl, mesh=self.mesh,
                dropout=self.dropout, fused_ln=self.fused_ln, name="hs",
            )
            x, _ = scanned(x, attention_mask)
        elif self.remat_layers:
            raise ValueError("remat_layers requires scan_layers=True "
                             "(use make_train_step(remat=True) to checkpoint "
                             "an unrolled forward)")
        else:
            for i in range(self.depth):
                x = EncoderBlock(
                    self.num_heads, dtype=self.dtype,
                    attn_impl=self.attn_impl, mesh=self.mesh,
                    dropout=self.dropout, fused_ln=self.fused_ln,
                    name=f"h_{i}",
                )(x, train=train, attention_mask=attention_mask)
        if return_hidden:
            return x
        return MlmHead(dtype=self.dtype, name="mlm_head")(x, wte)


class BertClassifier(nn.Module):
    """Sequence classification on the encoder — the fine-tuning surface.

    BERT's recipe: the first token's hidden state through the tanh pooler,
    then a ``num_labels`` head. The encoder lives under the ``bert`` param
    scope so :func:`classifier_params_from_mlm` can graft pretrained
    weights (from :class:`Bert` MLM pretraining or an HF import) leaf-for-
    leaf into a fresh classifier tree.
    """

    num_labels: int
    vocab_size: int = 30522
    max_seq_len: int = 512
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    type_vocab: int = 2
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    dropout: float = 0.0

    @nn.compact
    def __call__(self, tokens, train: bool = True, token_types=None,
                 attention_mask=None):
        # attention_mask ([b, s], 1 = real token): padded variable-length
        # classification batches must pass it, or pad tokens join every
        # softmax (HF BERT semantics require the mask)
        hidden = Bert(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            hidden_dim=self.hidden_dim, depth=self.depth,
            num_heads=self.num_heads, type_vocab=self.type_vocab,
            dtype=self.dtype, attn_impl=self.attn_impl,
            dropout=self.dropout, name="bert",
        )(tokens, train=train, return_hidden=True, token_types=token_types,
          attention_mask=attention_mask)
        pooled = jnp.tanh(
            nn.Dense(self.hidden_dim, dtype=self.dtype, name="pooler")(
                hidden[:, 0]
            )
        )
        if self.dropout:
            pooled = nn.Dropout(self.dropout, deterministic=not train)(pooled)
        # fp32 head: classification logits are cheap and the loss is
        # precision-sensitive
        return nn.Dense(self.num_labels, dtype=jnp.float32, name="classifier")(
            pooled
        )


def classifier_params_from_mlm(classifier_params, pretrained):
    """Graft a pretrained encoder (MLM params, tpudist or HF-imported) into
    a freshly-initialized :class:`BertClassifier` tree: every encoder leaf
    is replaced, the pooler/classifier head keeps its fresh init (HF's
    fine-tuning convention). ``mlm_head`` is dropped."""
    import jax

    encoder = {k: v for k, v in pretrained.items() if k != "mlm_head"}
    out = dict(classifier_params)
    # leaf-for-leaf replacement with a structure check: a geometry mismatch
    # fails loudly instead of training a half-grafted model
    out["bert"] = jax.tree_util.tree_map(
        lambda fresh, pre: pre.astype(fresh.dtype)
        if hasattr(pre, "astype") else pre,
        dict(classifier_params["bert"]), encoder,
    )
    return out


def bert_base(**kw) -> Bert:
    return Bert(**kw)


def bert_large(**kw) -> Bert:
    kw.setdefault("hidden_dim", 1024)
    kw.setdefault("depth", 24)
    kw.setdefault("num_heads", 16)
    return Bert(**kw)


def mlm_transform(
    vocab_size: int, mask_id: int, *, mask_rate: float = 0.15,
    random_rate: float = 0.1, keep_rate: float = 0.1, seed: int = 0,
    key: str = "tokens",
):
    """Loader transform applying BERT's MLM corruption on the host.

    Each position is selected with probability ``mask_rate``; of the
    selected, 80% become ``mask_id``, 10% a uniformly random id, 10% stay
    unchanged (the 80/10/10 recipe — ``random_rate``/``keep_rate`` are
    fractions OF the selected positions). Produces
    ``{"tokens": corrupted, "targets": originals, "mlm_mask": bool}``.
    Randomness is a seeded per-loader stream, like the augmentation
    transforms (tpudist/data/transforms.py) — deterministic order, not
    replayed across a mid-epoch resume.
    """
    rng = np.random.Generator(np.random.PCG64(seed))

    def run(batch):
        tokens = np.asarray(batch[key])
        u = rng.random(tokens.shape)
        selected = u < mask_rate
        # carve the selected mass into mask/random/keep sub-ranges of u
        to_random = selected & (u < mask_rate * random_rate)
        to_keep = selected & (u >= mask_rate * (1.0 - keep_rate))
        to_mask = selected & ~to_random & ~to_keep
        corrupted = tokens.copy()
        corrupted[to_mask] = mask_id
        # draw "random token" from the vocab EXCLUDING mask_id: draw over
        # vocab_size-1 ids and shift the ones at/above mask_id up by one, so
        # [MASK] can never appear as a target-bearing random id
        draw = rng.integers(0, vocab_size - 1, int(to_random.sum()))
        corrupted[to_random] = draw + (draw >= mask_id)
        out = dict(batch)
        out[key] = corrupted
        out["targets"] = tokens
        out["mlm_mask"] = selected
        return out

    return run


def mlm_forward(model: Bert, chunk: int | None = None):
    """``forward_loss`` for :func:`tpudist.train.make_train_step`: mean CE
    over the corrupted positions only — the MLM objective. Expects batches
    from :func:`mlm_transform` (``tokens``/``targets``/``mlm_mask``).

    ``chunk`` scans the MLM head over sequence chunks, bounding live
    logits to [B, chunk, V] — the same HBM discipline as
    ``chunked_lm_forward`` (at bert-base shapes, batch 32 × seq 512 ×
    V=30522 fp32 logits are ~2 GB otherwise). The chunk path rides the
    shared :func:`~tpudist.models.lm_utils.chunked_head_reduce` skeleton
    with :func:`mlm_head_logits_fn`: under differentiation the sweep takes
    each chunk's gradient while its logits are there, so none are kept and
    none are made a second time.
    """
    import optax

    from tpudist.models.lm_utils import chunked_head_reduce

    if getattr(model, "dropout", 0.0):
        raise ValueError(
            "mlm_forward has no rng stream; use dropout=0 (match "
            "chunked_lm_forward's contract) or extend the default forward"
        )

    head = MlmHead(dtype=model.dtype)

    def masked_ce_sum(logits, targets, mask):
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return jnp.sum(ce * mask)

    def forward_loss(params, batch_stats, batch):
        mask = batch["mlm_mask"].astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        if chunk is None:
            logits = model.apply(
                {"params": params}, batch["tokens"], train=True
            )
            with jax.named_scope("loss_head"):
                loss = masked_ce_sum(logits, batch["targets"], mask) / denom
            return loss, batch_stats

        hidden = model.apply(
            {"params": params}, batch["tokens"], train=True,
            return_hidden=True,
        )
        # the mean's 1 / denom rides in the weights (chunked_head_reduce)
        loss = chunked_head_reduce(
            mlm_head_logits_fn(head), mlm_head_params(params), hidden,
            batch["targets"], mask / denom, chunk,
        )
        return loss, batch_stats

    return forward_loss


def mlm_head_params(params) -> dict:
    """The ``head_params`` of :func:`mlm_head_logits_fn`: the tied table and
    the head's own leaves, out of a Bert's parameters."""
    return {
        "wte": nn.meta.unbox(params["wte"]),
        "mlm_head": nn.meta.unbox(params["mlm_head"]),
    }


def mlm_head_logits_fn(head: MlmHead):
    """``logits_fn`` for ``chunked_head_reduce``: BERT's transform + tied
    decode, applied per hidden chunk through the :class:`MlmHead` module
    (no duplicated head math) on :func:`mlm_head_params`."""
    return lambda p, hc: head.apply({"params": p["mlm_head"]}, hc, p["wte"])
