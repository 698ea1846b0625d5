"""SDAR (JetLM; ``model_type: sdar_moe``) trained by diffusion over blocks,
in plain ``jax.numpy``, float32. Written from the equations of the issue
that brought it, which follow the published ``config.json`` and, for the
objective, BD3-LM (arXiv:2503.09573) as the SDAR report (arXiv:2510.06303)
adopts it.

**Corruption** (on the host, not here: the batch arrives corrupted). Clean
tokens ``x[0..L)``, block length ``b`` (``L`` a multiple of ``b``),
``blk(i) = i // b``. For every block ``k`` a level ``t_k ~ U[t_min, 1]``;
every token of block ``k`` becomes the mask id with probability ``t_k``,
independently; ``m_i`` = 1 where masked. Linear schedule ``alpha_t = 1 -
t``, so the NELBO weight is ``1/t``: ``w_i = m_i / t_blk(i)``. The batch
holds ``tokens`` (the noised ``x~``), ``clean`` (``x``) and
``loss_weight`` (``w``), and the reference takes them as given.

**Input**: rows ``[x~ ; x]`` (``2 L``), positions ``[0..L) ; [0..L)``.
**Mask**, query row ``i``, key row ``j``, ``n(r) = r < L`` (the noised
half), ``p(r) = r mod L``: allowed iff (``n(i)`` and ``n(j)`` and
``blk(p(i)) == blk(p(j))``) or (``n(i)`` and not ``n(j)`` and ``blk(p(j))
< blk(p(i))``) or (not ``n(i)`` and not ``n(j)`` and ``blk(p(j)) <=
blk(p(i))``).

**Layer** (pre-norm, no biases): ``u = RMSNorm(h)``; ``q = u W_q`` (heads x
128), ``k = u W_k``, ``v = u W_v`` (key/value heads x 128); ``q, k <-
RMSNorm_128(q), RMSNorm_128(k)`` per head with a learned scale; rotary on
all 128 channels, half-split pairs ``(i, i + 64)``, at ``p(r)``; each
key/value head serves ``heads / kv_heads`` query heads; ``softmax(q k^T /
sqrt(128) + mask)`` in float32; ``h <- h + attn W_o``. ``u = RMSNorm(h)``;
``z = u W_g`` (float32); ``s = softmax(z)`` over all experts; chosen =
top-k; ``g_e = s_e / sum over the chosen of s``; ``h <- h + sum over the
chosen AND HELD e of g_e W_down,e (silu(u W_gate,e) * u W_up,e)``.

**Loss**: final RMSNorm, the untied head on the noised rows only, a masked
position predicts its own token: ``loss = (1 / (B L)) sum_{i<L} w_i
CE(logits_i, x_i)``, in float32.

Departures from the published model, all stated under ``assumed`` in
``benchmarks/configs/sdar-30b-a3b.json``:

- the share: the held experts' part only — what the absent experts would
  add for the (row, choice) pairs routed to them is left out, here as in
  the program — and a slice of the vocabulary;
- the selection is biased by the benchmark's per-sequence rule
  (``recipe.selection_bias`` ``sequence_quantile``, over the ``2 L`` rows
  of a batch row; own copy below); the model has no such bias;
- block length, schedule, ``t_min`` and the mask id are the family's
  convention or ours (``config.json`` has no key for them), and q/k norms
  follow Qwen3-MoE, which ``sdar_moe`` extends.

Dense over the held experts with a mask — no sort, no grouped product, no
kernel; the attention mask is a boolean array built from the index
arithmetic above, attention runs a head at a time so that ``2 L x 2 L``
scores of one head fit.

Weights arrive as a flat ``{"embed": ..., "h_0/attn_qkv/kernel": ...}``
dict in the layout the harness generates them in:

- ``attn_qkv/kernel [d, (H + 2 Hkv) * 128]``: all of q (head-major), then
  k, then v; ``q_norm/scale``, ``k_norm/scale [128]``;
  ``attn_out/kernel [H * 128, d]``;
- ``moe_norm/scale``, ``moe_router/kernel [d, E]``,
  ``moe_experts/{w_gate,w_up} [held, d, ff]``, ``w_down [held, ff, d]``;
- ``embed``, ``lm_head [V, d]``, ``norm/scale``, ``attn_norm/scale``.

Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.precision import OPERAND

HEAD_STRETCH = 1024  # positions of the head and loss computed at a time

_KEYS = ("attn_norm/scale", "attn_qkv/kernel", "q_norm/scale",
         "k_norm/scale", "attn_out/kernel", "moe_norm/scale",
         "moe_router/kernel", "moe_experts/w_gate", "moe_experts/w_up",
         "moe_experts/w_down")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def block_diffusion_mask(length: int, block: int):
    """``[2 L, 2 L]`` booleans: may row ``i`` see row ``j``? The first
    ``length`` rows are the noised copy, the rest the clean one."""
    row = jnp.arange(2 * length)
    noised, blk = row < length, (row % length) // block
    ni, nj = noised[:, None], noised[None, :]
    bi, bj = blk[:, None], blk[None, :]
    return (ni & nj & (bi == bj)) | (ni & ~nj & (bj < bi)) \
        | (~ni & ~nj & (bj <= bi))


def block_causal_mask(length: int, block: int):
    """``[L, L]`` booleans: row ``i`` sees the blocks up to its own."""
    blk = jnp.arange(length) // block
    return blk[None, :] <= blk[:, None]


def rope_halves(x, positions, theta):
    """Channels ``(i, i + D/2)`` of ``x [B, S, H, D]`` turned by
    ``positions[s] * theta^(-2i/D)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def sequence_quantile_bias(logits, top_k: int):
    """The benchmark's balancing rule: minus each expert's
    ``(top_k * S / E)``-th largest logit of the sequence. ``logits``:
    ``[B, S, E]``."""
    s, e = logits.shape[-2:]
    kth = jnp.sort(logits, axis=-2)[..., s - max(top_k * s // e, 1), :]
    return -kth[..., None, :]


def expert_layer(u, p, *, num_experts: int, top_k: int, first: int,
                 count: int, q_=lambda x: x,
                 selection_bias: str | None = None):
    """The expert layer's ``F`` over the share ``first .. first+count`` of
    ``num_experts``, from the normed input ``u [B, S, d]`` and the layer's
    leaves ``p``: softmax scores over all experts, top-k of the (biased)
    logits, weights the unbiased scores normalised over the chosen; the
    held experts' part."""
    # the router: float32 whatever the step's precision
    logits = u @ p["moe_router/kernel"]
    scores = jax.nn.softmax(logits, axis=-1)
    ranked = jax.lax.stop_gradient(logits)
    if selection_bias == "sequence_quantile":
        ranked = ranked + sequence_quantile_bias(ranked, top_k)
    elif selection_bias is not None:
        raise ValueError(f"unknown selection_bias {selection_bias!r}")
    # its own top-k: the k largest, one argmax at a time
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(top_k):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, ranked), axis=-1)
        chosen = chosen | (best[..., None] == jnp.arange(num_experts))
    picked = jnp.where(chosen, scores, 0.0)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-9)

    # every held expert over every row, weighted (nought where not chosen)
    def one(y, xs):
        e, wg, wu, wd = xs
        w = jax.lax.dynamic_index_in_dim(weights, first + e, axis=-1)
        hid = jax.nn.silu(q_(u) @ q_(wg)) * (q_(u) @ q_(wu))
        return y + w * (q_(hid) @ q_(wd)), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(u),
        (jnp.arange(count), p["moe_experts/w_gate"], p["moe_experts/w_up"],
         p["moe_experts/w_down"]),
    )
    return y


def make_stack(config: dict, precision: str = "float32"):
    """``stack(params, tokens [B, S], positions [S], mask [S, S]) -> hidden
    [B, S, d]`` after the final norm."""
    depth = config["num_hidden_layers"]
    h, kv, dh = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    first, count = config["deployment"]["experts_held_first"], \
        config["num_experts_held"]
    if not config["norm_topk_prob"] or config["rope_scaling"] is not None \
            or config["mlp_only_layers"] or config["decoder_sparse_step"] != 1 \
            or config["attention_bias"] or config["use_sliding_window"]:
        raise ValueError("the reference knows softmax scores normalised "
                         "over the chosen, experts in every layer, no "
                         "rotary scaling, no bias and no window")
    q_ = OPERAND[precision]
    selection_bias = config.get("recipe", {}).get("selection_bias")

    def attention(u, p, positions, mask):
        b, s, _ = u.shape
        qkv = q_(u) @ q_(p["attn_qkv/kernel"])
        q = qkv[..., :h * dh].reshape(b, s, h, dh)
        k = qkv[..., h * dh:(h + kv) * dh].reshape(b, s, kv, dh)
        v = qkv[..., (h + kv) * dh:].reshape(b, s, kv, dh)
        q = rope_halves(_rms_norm(q, p["q_norm/scale"], eps), positions, theta)
        k = rope_halves(_rms_norm(k, p["k_norm/scale"], eps), positions, theta)

        # a head at a time, rematerialised: S x S scores of one head fit;
        # query head i reads key/value head i // (h / kv)
        def one_head(_, xs):
            qh, kh, vh = xs  # [B, S, dh]
            scores = jnp.einsum("bqe,bke->bqk", q_(qh), q_(kh)) \
                / jnp.sqrt(jnp.float32(dh))
            scores = jnp.where(mask, scores, -jnp.inf)
            return None, jnp.einsum(
                "bqk,bke->bqe", q_(jax.nn.softmax(scores, axis=-1)), q_(vh))

        heads_first = lambda x: jnp.moveaxis(x, 2, 0)
        group = lambda x: jnp.repeat(heads_first(x), h // kv, axis=0)
        _, o = jax.lax.scan(jax.checkpoint(one_head), None,
                            (heads_first(q), group(k), group(v)))
        o = jnp.moveaxis(o, 0, 2)
        return q_(o.reshape(b, s, h * dh)) @ q_(p["attn_out/kernel"])

    def block(x, p, positions, mask):
        x = x + attention(_rms_norm(x, p["attn_norm/scale"], eps), p,
                          positions, mask)
        u = _rms_norm(x, p["moe_norm/scale"], eps)
        return x + expert_layer(
            u, p, num_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"], first=first, count=count,
            q_=q_, selection_bias=selection_bias,
        )

    def stack(params, tokens, positions, mask):
        x = params["embed"][tokens]
        # layer by layer, rematerialised, so that a block of rows fits
        for i in range(depth):
            x = jax.checkpoint(block)(
                x, {k: params[f"h_{i}/{k}"] for k in _KEYS}, positions, mask)
        return _rms_norm(x, params["norm/scale"], eps)

    return stack


def make_loss_sum(config: dict, precision: str = "float32"):
    """``loss_sum(params, rows) -> (sum of w_i CE_i over the noised rows,
    B L)``; ``rows`` holds ``tokens``, ``clean`` and ``loss_weight``."""
    stack = make_stack(config, precision)
    block = config["block_length"]
    q_ = OPERAND[precision]

    def loss_sum(params, rows):
        with jax.default_matmul_precision("highest"):
            noised, clean = rows["tokens"], rows["clean"]
            b, length = noised.shape
            position = jnp.arange(length)
            x = stack(params, jnp.concatenate([noised, clean], axis=1),
                      jnp.concatenate([position, position]),
                      block_diffusion_mask(length, block))[:, :length]
            head = params["lm_head"]

            # a stretch of positions at a time, rematerialised: the logits
            # of one stretch fit beside the state of the first steps
            def stretch(h, targets, weight):
                logits = jnp.einsum("bsd,vd->bsv", q_(h), q_(head))
                logp = jax.nn.log_softmax(logits, axis=-1)
                return -jnp.sum(weight * jnp.take_along_axis(
                    logp, targets[..., None], axis=-1)[..., 0])

            total = 0.0
            for lo in range(0, length, HEAD_STRETCH):
                hi = min(lo + HEAD_STRETCH, length)
                total = total + jax.checkpoint(stretch)(
                    x[:, lo:hi], clean[:, lo:hi],
                    rows["loss_weight"][:, lo:hi])
            return total, jnp.float32(b * length)

    return loss_sum
