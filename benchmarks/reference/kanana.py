"""Kanana-2 (kakaocorp; ``model_type: deepseek_v3``) in plain ``jax.numpy``,
float32: pre-norm blocks ``x <- x + MLA(RMSNorm(x))``, ``x <- x +
F(RMSNorm(x))`` with ``F`` a dense SwiGLU in the first
``first_k_dense_replace`` layers and the expert layer after them; final
RMSNorm; an untied head; mean next-token cross-entropy.

Written from the equations of the issue that brought it, which follow the
published ``config.json`` (``q_lora_rank`` null: no query compression;
``rope_scaling`` null: no YaRN, no mscale; ``n_group`` 1: no group limit).
Departures from the published model, all stated under ``assumed`` in
``benchmarks/configs/kanana-2-30b-a3b.json``:

- the bias on the selection is not the model's carried one
  (``topk_method: noaux_tc``) but the benchmark's per-sequence rule
  (``recipe.selection_bias`` ``sequence_quantile``; own copy below);
- rotary embedding turns adjacent channel pairs IN PLACE; the published
  code also moves pair ``i`` to channels ``i`` and ``i + 32`` of q and k
  alike, which no score can see.

Given the SAME share as the program: the experts ``held`` (first, count) of
``n_routed_experts`` and the slice of the vocabulary. Dense over the held
experts with a mask — no sort, no grouped product, no kernel. A (token,
choice) whose expert is not held adds nothing; the shared expert is whole
on every share; that partial result goes on, here as in the program.

Weights arrive as a flat ``{"embed": ..., "h_0/mla_q/kernel": ...}`` dict
in the layout the harness generates them in:

- ``mla_q/kernel [d, H*(nope+rope)]``: per head ``nope`` then ``rope``;
- ``mla_kv_down/kernel [d, rank+rope]``: the latent ``c``, then ``k_rope``;
- ``mla_kv_norm/scale [rank]``; ``mla_kv_up/kernel [rank, H*(nope+v)]``:
  per head ``k_nope`` then ``v``; ``mla_out/kernel [H*v, d]``;
- dense layers: ``mlp_norm/scale``, ``mlp_{gate,up}/kernel [d, ff]``,
  ``mlp_down/kernel [ff, d]``;
- expert layers: ``moe_norm/scale``, ``moe_router/kernel [d, E]``,
  ``moe_experts/{w_gate,w_up} [held, d, ff]``, ``w_down [held, ff, d]``,
  ``moe_shared/{w_gate,w_up}/kernel [d, shared]``, ``w_down/kernel``;
- ``embed``, ``lm_head [V, d]``, ``norm/scale``, ``attn_norm/scale``.

Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.precision import OPERAND

HEAD_STRETCH = 1024  # positions of the head and loss computed at a time

_MLA_KEYS = ("attn_norm/scale", "mla_q/kernel", "mla_kv_down/kernel",
             "mla_kv_norm/scale", "mla_kv_up/kernel", "mla_out/kernel")
_DENSE_KEYS = _MLA_KEYS + ("mlp_norm/scale", "mlp_gate/kernel",
                           "mlp_up/kernel", "mlp_down/kernel")
_EXPERT_KEYS = _MLA_KEYS + (
    "moe_norm/scale", "moe_router/kernel",
    "moe_experts/w_gate", "moe_experts/w_up", "moe_experts/w_down",
    "moe_shared/w_gate/kernel", "moe_shared/w_up/kernel",
    "moe_shared/w_down/kernel",
)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope_pairs(x, theta):
    """Adjacent channel pairs ``(2i, 2i+1)`` of ``x [B, S, H, D]`` turned
    by ``pos * theta^(-2i/D)``, in place."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def sequence_quantile_bias(scores, top_k: int):
    """The benchmark's balancing rule: minus each expert's
    ``(top_k * S / E)``-th largest score of the sequence. ``scores``:
    ``[B, S, E]``."""
    s, e = scores.shape[-2:]
    kth = jnp.sort(scores, axis=-2)[..., s - max(top_k * s // e, 1), :]
    return -kth[..., None, :]


def expert_layer(u, p, *, num_experts: int, top_k: int, first: int,
                 count: int, routed_scale: float, q_=lambda x: x,
                 selection_bias: str | None = None, shared: bool = True):
    """The expert layer's ``F`` over the share ``first .. first+count`` of
    ``num_experts``, from the normed input ``u [B, S, d]`` and the layer's
    leaves ``p``: sigmoid scores, top-k of the (biased) scores over all
    experts, weights from the unbiased scores normalised over the chosen
    and scaled; the held experts' part, plus the shared expert's where
    ``shared``."""
    # the router: float32 whatever the step's precision
    scores = jax.nn.sigmoid(u @ p["moe_router/kernel"])
    ranked = jax.lax.stop_gradient(scores)
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
    weights = routed_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    def swiglu(x, wg, wu, wd):
        hid = jax.nn.silu(q_(x) @ q_(wg)) * (q_(x) @ q_(wu))
        return q_(hid) @ q_(wd)

    # every held expert over every token, weighted (nought where not chosen)
    def one(y, xs):
        e, wg, wu, wd = xs
        w = jax.lax.dynamic_index_in_dim(weights, first + e, axis=-1)
        return y + w * swiglu(u, wg, wu, wd), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(u),
        (jnp.arange(count), p["moe_experts/w_gate"], p["moe_experts/w_up"],
         p["moe_experts/w_down"]),
    )
    if shared:
        y = y + swiglu(u, p["moe_shared/w_gate/kernel"],
                       p["moe_shared/w_up/kernel"],
                       p["moe_shared/w_down/kernel"])
    return y


def make_loss_sum(config: dict, precision: str = "float32"):
    """``loss_sum(params, rows) -> (sum of next-token CE, positions)``."""
    depth = config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    h = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    first, count = config["deployment"]["experts_held_first"], \
        config["num_experts_held"]
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"] \
            or config["n_group"] != 1 or config["q_lora_rank"] is not None \
            or config["rope_scaling"] is not None:
        raise ValueError("the reference knows sigmoid scores normalised "
                         "over the chosen, one group, no query compression "
                         "and no rotary scaling")
    q_ = OPERAND[precision]
    selection_bias = config.get("recipe", {}).get("selection_bias")

    def mla(u, p):
        b, s, _ = u.shape
        q = (q_(u) @ q_(p["mla_q/kernel"])).reshape(b, s, h, nope + rope)
        down = q_(u) @ q_(p["mla_kv_down/kernel"])
        c = _rms_norm(down[..., :rank], p["mla_kv_norm/scale"], eps)
        kv = (q_(c) @ q_(p["mla_kv_up/kernel"])).reshape(b, s, h, nope + dv)
        q_rope = rope_pairs(q[..., nope:], theta)
        k_rope = rope_pairs(down[..., None, rank:], theta)[:, :, 0]  # one head
        causal = jnp.tril(jnp.ones((s, s), bool))

        # a head at a time, rematerialised: S x S scores of one head fit
        def one_head(_, xs):
            qn, qr, kn, v = xs  # [B, S, nope | rope | nope | dv]
            scores = jnp.einsum("bqe,bke->bqk", q_(qn), q_(kn)) \
                + jnp.einsum("bqe,bke->bqk", q_(qr), q_(k_rope))
            scores = scores / jnp.sqrt(jnp.float32(nope + rope))
            scores = jnp.where(causal, scores, -jnp.inf)
            return None, jnp.einsum(
                "bqk,bke->bqe", q_(jax.nn.softmax(scores, axis=-1)), q_(v))

        heads_first = lambda x: jnp.moveaxis(x, 2, 0)
        _, o = jax.lax.scan(jax.checkpoint(one_head), None, (
            heads_first(q[..., :nope]), heads_first(q_rope),
            heads_first(kv[..., :nope]), heads_first(kv[..., nope:])))
        o = jnp.moveaxis(o, 0, 2)
        return q_(o.reshape(b, s, h * dv)) @ q_(p["mla_out/kernel"])

    def dense_block(x, p):
        x = x + mla(_rms_norm(x, p["attn_norm/scale"], eps), p)
        u = _rms_norm(x, p["mlp_norm/scale"], eps)
        hid = jax.nn.silu(q_(u) @ q_(p["mlp_gate/kernel"])) \
            * (q_(u) @ q_(p["mlp_up/kernel"]))
        return x + q_(hid) @ q_(p["mlp_down/kernel"])

    def expert_block(x, p):
        x = x + mla(_rms_norm(x, p["attn_norm/scale"], eps), p)
        u = _rms_norm(x, p["moe_norm/scale"], eps)
        return x + expert_layer(
            u, p, num_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"], first=first, count=count,
            routed_scale=config["routed_scaling_factor"], q_=q_,
            selection_bias=selection_bias,
        )

    def loss_sum(params, rows):
        with jax.default_matmul_precision("highest"):
            tokens = rows["tokens"]
            x = params["embed"][tokens]
            # layer by layer, rematerialised, so that a block of rows fits
            for i in range(depth):
                keys, block = (_DENSE_KEYS, dense_block) \
                    if i < dense_layers else (_EXPERT_KEYS, expert_block)
                x = jax.checkpoint(block)(
                    x, {k: params[f"h_{i}/{k}"] for k in keys})
            x = _rms_norm(x, params["norm/scale"], eps)
            head = params["lm_head"]

            # a stretch of positions at a time, rematerialised: the logits
            # of one stretch fit beside the state of the first steps
            def stretch(h, targets):
                logits = jnp.einsum("bsd,vd->bsv", q_(h), q_(head))
                logp = jax.nn.log_softmax(logits, axis=-1)
                return -jnp.sum(jnp.take_along_axis(
                    logp, targets[..., None], axis=-1))

            total, last = 0.0, tokens.shape[1] - 1
            for lo in range(0, last, HEAD_STRETCH):
                hi = min(lo + HEAD_STRETCH, last)
                total = total + jax.checkpoint(stretch)(
                    x[:, lo:hi], tokens[:, lo + 1:hi + 1])
            return total, jnp.float32(tokens.shape[0] * last)

    return loss_sum
