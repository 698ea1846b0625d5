"""Laguna (poolside; ``model_type: laguna``) in plain ``jax.numpy``,
float32: pre-norm blocks ``x <- x + Attn_l(RMSNorm(x))``, ``x <- x +
F_l(RMSNorm(x))``; final RMSNorm; an untied head; mean next-token
cross-entropy. Written from the equations of the issue that brought it,
which follow the published ``config.json``.

**Layer** ``l`` of kind ``layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads on ``num_key_value_heads``
key/value heads of ``head_dim`` (128); ``u = RMSNorm(x)``, no biases:

- ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; ``g = sigmoid(u W_g)``
  (``H_l x 128``: one gate a channel);
- ``full_attention``: rotary on channels ``0 .. 63`` of each q and k head,
  pairs ``(i, i + 32)``, frequencies YaRN's: ``f_i = theta^(-2i/64)``,
  ``low = floor(64 ln(L0 / (64 · 2 pi)) / (2 ln theta))``, ``high =
  ceil(64 ln(L0 / (2 pi)) / (2 ln theta))`` (``L0`` the original 4,096
  positions, 64 and 1 the two betas), ``r_i = clip((i - low) / (high -
  low), 0, 1)``, ``f_i (1 - r_i) + (f_i / factor) r_i``; cos and sin times
  the attention factor; channels ``64 .. 127`` untouched; query ``i``
  sees key ``j <= i``;
- ``sliding_attention``: rotary on all 128 channels, pairs ``(i, i +
  64)``, ``f_i = theta^(-2i/128)`` at theta 1e4; query ``i`` sees key
  ``j`` iff ``0 <= i - j < W`` (``sliding_window``);
- ``softmax(q k^T / sqrt(128))`` over the allowed keys, query head ``n``
  reading key/value head ``n // (H_l / 8)``; ``x <- x + (a ⊙ g) W_o``.

``F_l`` is a SwiGLU of ``intermediate_size`` where ``mlp_layer_types[l]``
is ``dense``, else the expert layer: sigmoid scores over all experts,
top-k of the (biased) scores, weights ``routed_scale x`` the unbiased
scores normalised over the chosen; the held experts' part plus the shared
expert (``benchmarks/reference/kanana.py`` ``expert_layer``, the same
equations).

Departures from the published model, all stated under ``assumed`` in
``benchmarks/configs/laguna-xs-2.json``: what ``gating: true`` gates (the
output gate above); the router's scoring and normalisation; the share — the
held experts' part only and a slice of the vocabulary; the selection
biased by the benchmark's per-sequence rule (``recipe.selection_bias``
``sequence_quantile``).

Dense over the held experts with a mask — no sort, no grouped product, no
kernel. Full attention runs a head at a time (``S x S`` scores of one head
fit); a sliding layer runs all heads over blocks of ``ROW_BLOCK`` query
rows against the ``ROW_BLOCK + W`` keys before them, under a dense mask
of the block.

Weights arrive as a flat ``{"embed": ..., "h_0/lg_qkv/kernel": ...}`` dict
in the layout the harness generates them in:

- ``lg_qkv/kernel [d, (H_l + 2 Hkv) * 128]``: all of q (head-major), then
  k, then v; ``lg_gate/kernel [d, H_l * 128]``; ``lg_out/kernel [H_l *
  128, d]``; ``attn_norm/scale``;
- dense layers: ``mlp_norm/scale``, ``mlp_{gate,up}/kernel [d, ff]``,
  ``mlp_down/kernel [ff, d]``;
- expert layers: ``moe_norm/scale``, ``moe_router/kernel [d, E]``,
  ``moe_experts/{w_gate,w_up} [held, d, ff]``, ``w_down [held, ff, d]``,
  ``moe_shared/{w_gate,w_up}/kernel [d, shared]``, ``w_down/kernel``;
- ``embed``, ``lm_head [V, d]``, ``norm/scale``.

Imports nothing of the program."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.kanana import _rms_norm, expert_layer
from benchmarks.reference.precision import OPERAND

HEAD_STRETCH = 1024  # positions of the head and loss computed at a time
ROW_BLOCK = 512      # query rows of a sliding layer computed at a time

FULL, SLIDING = "full_attention", "sliding_attention"

_ATTN_KEYS = ("attn_norm/scale", "lg_qkv/kernel", "lg_gate/kernel",
              "lg_out/kernel")
_DENSE_KEYS = _ATTN_KEYS + ("mlp_norm/scale", "mlp_gate/kernel",
                            "mlp_up/kernel", "mlp_down/kernel")
_EXPERT_KEYS = _ATTN_KEYS + (
    "moe_norm/scale", "moe_router/kernel",
    "moe_experts/w_gate", "moe_experts/w_up", "moe_experts/w_down",
    "moe_shared/w_gate/kernel", "moe_shared/w_up/kernel",
    "moe_shared/w_down/kernel",
)


def yarn_inv_freq(dim: int, theta: float, *, factor: float,
                  original: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's frequencies at the rotary width ``dim`` (the equations in
    this module's docstring), float32."""
    at = lambda turns: dim * math.log(original / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1 - ramp) + f / factor * ramp).astype(np.float32)


def rope_halves(x, inv_freq, scale: float = 1.0):
    """Channels ``(i, i + D/2)`` of ``x [B, S, H, D]`` turned by ``pos ·
    inv_freq[i]``, cos and sin times ``scale``."""
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = scale * jnp.cos(angles)[None, :, None, :]
    sin = scale * jnp.sin(angles)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def sliding_mask(rows: int, keys_before: int, window: int, start):
    """``[rows, rows + keys_before]`` booleans of a block of query rows
    ``start ..`` against the keys ``start - keys_before ..``: ``0 <= i - j
    < W`` and ``j >= 0``."""
    i = start + jnp.arange(rows)[:, None]
    j = start - keys_before + jnp.arange(rows + keys_before)[None, :]
    return (i - j >= 0) & (i - j < window) & (j >= 0)


def make_stack(config: dict, precision: str = "float32"):
    """``stack(params, tokens [B, S]) -> hidden [B, S, d]`` after the final
    norm."""
    depth = config["num_hidden_layers"]
    kinds = config["layer_types"][:depth]
    heads = config["num_attention_heads_per_layer"][:depth]
    mlp = config["mlp_layer_types"][:depth]
    kv, dh = config["num_key_value_heads"], config["head_dim"]
    eps, window = config["rms_norm_eps"], config["sliding_window"]
    rope = config["rope_parameters"]
    full, sliding = rope[FULL], rope[SLIDING]
    first, count = config["deployment"]["experts_held_first"], \
        config["num_experts_held"]
    if config["gating"] is not True or config["tie_word_embeddings"] \
            or config["attention_bias"] or full["rope_type"] != "yarn" \
            or sliding["rope_type"] != "default" \
            or sliding["partial_rotary_factor"] != 1:
        raise ValueError("the reference knows a gated output, an untied "
                         "head, no bias, YaRN on the full layers and plain "
                         "rotary on the whole head of the sliding ones")
    rot = int(dh * full["partial_rotary_factor"])
    full_freq = yarn_inv_freq(
        rot, float(full["rope_theta"]), factor=full["factor"],
        original=full["original_max_position_embeddings"],
        beta_fast=full["beta_fast"], beta_slow=full["beta_slow"])
    sliding_freq = float(sliding["rope_theta"]) ** (
        -np.arange(0, dh, 2, dtype=np.float64) / dh)
    q_ = OPERAND[precision]
    selection_bias = config.get("recipe", {}).get("selection_bias")

    def full_attention(q, k, v):
        """Causal, a head at a time; ``q [B, S, H, dh]``, ``k, v [B, S,
        Hkv, dh]``."""
        s = q.shape[1]
        causal = jnp.tril(jnp.ones((s, s), bool))

        def one_head(_, xs):
            qh, kh, vh = xs  # [B, S, dh]
            scores = jnp.einsum("bqe,bke->bqk", q_(qh), q_(kh)) \
                / jnp.sqrt(jnp.float32(dh))
            scores = jnp.where(causal, scores, -jnp.inf)
            return None, jnp.einsum(
                "bqk,bke->bqe", q_(jax.nn.softmax(scores, axis=-1)), q_(vh))

        heads_first = lambda x: jnp.moveaxis(x, 2, 0)
        group = lambda x: jnp.repeat(heads_first(x), q.shape[2] // kv, axis=0)
        _, o = jax.lax.scan(jax.checkpoint(one_head), None,
                            (heads_first(q), group(k), group(v)))
        return jnp.moveaxis(o, 0, 2)

    def sliding_attention(q, k, v):
        """All heads over blocks of ``ROW_BLOCK`` query rows, each against
        the ``ROW_BLOCK + W`` keys that end with its own last row."""
        b, s, h, _ = q.shape
        rows = min(ROW_BLOCK, s)
        group = h // kv
        pad = lambda x: jnp.pad(x, ((0, 0), (window, 0), (0, 0), (0, 0)))
        kp, vp = pad(k), pad(v)

        def one_block(_, start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
            kb = jax.lax.dynamic_slice_in_dim(kp, start, rows + window, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vp, start, rows + window, axis=1)
            qb = qb.reshape(b, rows, kv, group, dh)
            scores = jnp.einsum("bqngd,bknd->bngqk", q_(qb), q_(kb)) \
                / jnp.sqrt(jnp.float32(dh))
            scores = jnp.where(sliding_mask(rows, window, window, start),
                               scores, -jnp.inf)
            o = jnp.einsum("bngqk,bknd->bqngd",
                           q_(jax.nn.softmax(scores, axis=-1)), q_(vb))
            return None, o.reshape(b, rows, h, dh)

        _, o = jax.lax.scan(jax.checkpoint(one_block), None,
                            jnp.arange(0, s, rows))
        return jnp.moveaxis(o, 0, 1).reshape(b, s, h, dh)

    def attention(u, p, kind, h):
        b, s, _ = u.shape
        qkv = q_(u) @ q_(p["lg_qkv/kernel"])
        q = qkv[..., :h * dh].reshape(b, s, h, dh)
        k = qkv[..., h * dh:(h + kv) * dh].reshape(b, s, kv, dh)
        v = qkv[..., (h + kv) * dh:].reshape(b, s, kv, dh)
        gate = jax.nn.sigmoid(q_(u) @ q_(p["lg_gate/kernel"]))
        if kind == FULL:
            turn = lambda x: jnp.concatenate([
                rope_halves(x[..., :rot], full_freq,
                            full["attention_factor"]), x[..., rot:]], -1)
            o = full_attention(turn(q), turn(k), v)
        elif kind == SLIDING:
            o = sliding_attention(rope_halves(q, sliding_freq),
                                  rope_halves(k, sliding_freq), v)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        return q_(o.reshape(b, s, h * dh) * gate) @ q_(p["lg_out/kernel"])

    def dense_block(x, p, kind, h):
        x = x + attention(_rms_norm(x, p["attn_norm/scale"], eps), p, kind, h)
        u = _rms_norm(x, p["mlp_norm/scale"], eps)
        hid = jax.nn.silu(q_(u) @ q_(p["mlp_gate/kernel"])) \
            * (q_(u) @ q_(p["mlp_up/kernel"]))
        return x + q_(hid) @ q_(p["mlp_down/kernel"])

    def expert_block(x, p, kind, h):
        x = x + attention(_rms_norm(x, p["attn_norm/scale"], eps), p, kind, h)
        u = _rms_norm(x, p["moe_norm/scale"], eps)
        return x + expert_layer(
            u, p, num_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"], first=first, count=count,
            routed_scale=config["moe_routed_scaling_factor"], q_=q_,
            selection_bias=selection_bias,
        )

    def stack(params, tokens):
        x = params["embed"][tokens]
        # layer by layer, rematerialised, so that a block of rows fits
        for i in range(depth):
            keys, block = (_DENSE_KEYS, dense_block) if mlp[i] == "dense" \
                else (_EXPERT_KEYS, expert_block)
            x = jax.checkpoint(block, static_argnums=(2, 3))(
                x, {k: params[f"h_{i}/{k}"] for k in keys}, kinds[i],
                heads[i])
        return _rms_norm(x, params["norm/scale"], eps)

    return stack


def make_loss_sum(config: dict, precision: str = "float32"):
    """``loss_sum(params, rows) -> (sum of next-token CE, positions)``."""
    stack = make_stack(config, precision)
    q_ = OPERAND[precision]

    def loss_sum(params, rows):
        with jax.default_matmul_precision("highest"):
            tokens = rows["tokens"]
            x = stack(params, tokens)
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
