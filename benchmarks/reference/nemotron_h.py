"""Nemotron-H (NVIDIA; ``model_type: nemotron_h``) in plain ``jax.numpy``,
float32: pre-norm blocks ``x <- x + F_l(RMSNorm(x))``, ``F_l`` chosen by
the character ``hybrid_override_pattern[l]``; final RMSNorm; an untied
head; mean next-token cross-entropy. Written from the published
``config.json`` and the Mamba-2 paper (arXiv:2405.21060).

``u = RMSNorm(x)`` (``norm_eps``), no biases in the projections:

- ``M``, Mamba-2, ``H`` heads of ``P``, ``G`` groups of state ``N``:
  ``[z ; xBC ; dt] = u W_in``; ``xBC_t <- silu(sum_j w_j xBC_{t-3+j} + b)``
  (four taps, nought before the start); ``xBC = [x ; B ; C]``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; head ``h`` reads
  group ``h // (H / G)`` and carries ``S_h [N, P]`` from nought::

      S_t = exp(dt_t A) S_{t-1} + dt_t B_t^T x_t,   y_t = C_t S_t + D x_t

  — the sequential recurrence itself, a position at a time (a scan, a
  group of heads at a time, rematerialised every ``CARRY_BLOCK`` positions
  so that its backward fits); then ``y ⊙ silu(z)``, RMS-normed over groups of ``H P / G``
  channels (eps ``layer_norm_epsilon``) times its scale, ``W_out``.
- ``*``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; causal ``softmax(q
  k^T / sqrt(head_dim))``, query head ``n`` reading key/value head ``n //
  (H / Hkv)``; no rotary; ``W_o``.
- ``E``: sigmoid scores over all experts, top-k of the (biased) scores,
  weights ``routed_scale x`` the unbiased scores normalised over the
  chosen; ``relu(u W_up)² W_down`` for each held expert (dense over the
  held experts with a mask) plus the shared expert of the same form.

Departures from the published model, all stated under ``assumed`` in
``benchmarks/configs/nemotron-3-nano-30b-a3b.json``: the selection biased
by the benchmark's per-sequence rule (``recipe.selection_bias``
``sequence_quantile``) in place of the carried ``e_score_correction_bias``;
the share — the held experts' part only and a slice of the vocabulary; the
time step unclamped (``time_step_limit`` (0, inf)).

Weights arrive as a flat ``{"embed": ..., "h_0/mamba_in_proj/kernel":
...}`` dict in the layout the harness generates them in:

- every layer: ``norm/scale``;
- ``M``: ``mamba_in_proj/kernel [d, H P + (H P + 2 G N) + H]`` (z, then
  x, B, C, then dt), ``conv_kernel [4, H P + 2 G N]``, ``conv_bias``,
  ``dt_bias``, ``A_log``, ``D`` ``[H]``, ``gate_norm_scale [H P]``,
  ``mamba_out_proj/kernel [H P, d]``;
- ``*``: ``gqa_qkv/kernel [d, (H + 2 Hkv) head_dim]`` (all of q, then k,
  then v), ``gqa_out/kernel``;
- ``E``: ``moe_router/kernel [d, E]``, ``moe_experts/w_up [held, d, ff]``,
  ``w_down [held, ff, d]``, ``moe_shared/{w_up,w_down}/kernel``;
- ``embed``, ``lm_head [V, d]``, ``norm/scale``.

Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.kanana import _rms_norm, sequence_quantile_bias
from benchmarks.reference.precision import OPERAND

HEAD_STRETCH = 1024  # positions of the head and loss computed at a time
CARRY_BLOCK = 128    # positions of the recurrence between kept states

_NORM = ("norm/scale",)
_KEYS = {
    "M": _NORM + ("mamba_in_proj/kernel", "conv_kernel", "conv_bias",
                  "dt_bias", "A_log", "D", "gate_norm_scale",
                  "mamba_out_proj/kernel"),
    "*": _NORM + ("gqa_qkv/kernel", "gqa_out/kernel"),
    "E": _NORM + ("moe_router/kernel", "moe_experts/w_up",
                  "moe_experts/w_down", "moe_shared/w_up/kernel",
                  "moe_shared/w_down/kernel"),
}


def recurrence(x, dt, A, B, C, D, q_=lambda v: v):
    """The state-space recurrence a position at a time: ``x [b, S, H, P]``,
    ``dt [b, S, H]``, ``A``, ``D`` ``[H]``, ``B``, ``C`` ``[b, S, G, N]``.
    Returns ``y [b, S, H, P]``, float32. A group of heads at a time (the
    group's ``B`` and ``C`` serve its heads by broadcasting), each
    rematerialised, so that one group's backward is live at once."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    per = h // g

    def one_group(args):
        xg, dtg, Ag, Bg, Cg, Dg = args  # [b, S, per, P], [b, S, per], [per], [b, S, N]

        def step(state, inp):
            xt, dtt, bt, ct = inp
            state = jnp.exp(dtt * Ag)[..., None, None] * state \
                + dtt[..., None, None] * q_(bt)[:, None, :, None] \
                * q_(xt)[..., None, :]
            y = jnp.einsum("bn,bhnp->bhp", q_(ct), q_(state)) \
                + Dg[:, None] * xt
            return state, y

        def block(state, inp):
            return jax.lax.scan(step, state, inp)

        blocks = s // CARRY_BLOCK if s % CARRY_BLOCK == 0 else 1
        cut = lambda v: jnp.moveaxis(v, 1, 0).reshape(
            (blocks, s // blocks) + (v.shape[0],) + v.shape[2:])
        _, y = jax.lax.scan(jax.checkpoint(block),
                            jnp.zeros((b, per, n, p)),
                            (cut(xg), cut(dtg), cut(Bg), cut(Cg)))
        return jnp.moveaxis(y.reshape((s, b, per, p)), 0, 1)

    group_first = lambda v, shape: jnp.moveaxis(v.reshape(shape), 2, 0)
    y = jax.lax.map(jax.checkpoint(one_group), (
        group_first(x, (b, s, g, per, p)), group_first(dt, (b, s, g, per)),
        A.reshape(g, per), jnp.moveaxis(B, 2, 0), jnp.moveaxis(C, 2, 0),
        D.reshape(g, per)))
    return jnp.moveaxis(y, 0, 2).reshape(b, s, h, p)


def expert_layer(u, w, *, num_experts: int, top_k: int, first: int,
                 count: int, routed_scale: float, q_=lambda v: v,
                 selection_bias: str | None = None, shared: bool = True):
    """The expert layer's ``F`` over the share ``first .. first+count`` of
    ``num_experts`` from the normed input ``u [B, S, d]``: sigmoid scores,
    top-k of the (biased) scores over all experts, weights from the
    unbiased scores normalised over the chosen and scaled; the held
    experts' part, plus the shared expert's where ``shared``."""
    scores = jax.nn.sigmoid(u @ w["moe_router/kernel"])      # float32
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

    def relu2(t, up, down):
        return q_(jnp.square(jax.nn.relu(q_(t) @ q_(up)))) @ q_(down)

    # every held expert over every token, weighted (nought where not chosen)
    def one(y, xs):
        e, up, down = xs
        gate = jax.lax.dynamic_index_in_dim(weights, first + e, axis=-1)
        return y + gate * relu2(u, up, down), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(u),
        (jnp.arange(count), w["moe_experts/w_up"], w["moe_experts/w_down"]))
    if shared:
        y = y + relu2(u, w["moe_shared/w_up/kernel"],
                      w["moe_shared/w_down/kernel"])
    return y


def make_stack(config: dict, precision: str = "float32"):
    """``stack(params, tokens [B, S]) -> hidden [B, S, d]`` after the final
    norm."""
    depth = config["num_hidden_layers"]
    kinds = config["hybrid_override_pattern"][:depth]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    eps, gate_eps = config["norm_eps"], config["layer_norm_epsilon"]
    first, count = config["deployment"]["experts_held_first"], \
        config["num_experts_held"]
    if config["mamba_hidden_act"] != "silu" \
            or config["mlp_hidden_act"] != "relu2" \
            or config["tie_word_embeddings"] or config["attention_bias"] \
            or config["use_bias"] or not config["use_conv_bias"] \
            or not config["norm_topk_prob"] or config["n_group"] != 1:
        raise ValueError("the reference knows SiLU in the mixer, squared-"
                         "ReLU experts, an untied head, no projection bias, "
                         "a convolution bias and one router group")
    q_ = OPERAND[precision]
    selection_bias = config.get("recipe", {}).get("selection_bias")
    inner = h * p

    def conv(xbc, taps, bias):
        k, s = taps.shape[0], xbc.shape[1]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        return jax.nn.silu(sum(taps[j] * padded[:, j:j + s] for j in range(k))
                           + bias)

    def gate_norm(y, z, scale):
        b, s = z.shape[:2]
        y = y.reshape(b, s, inner) * jax.nn.silu(z)
        y = y.reshape(b, s, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + gate_eps)
        return y.reshape(b, s, inner) * scale

    def mamba(u, w):
        b, s, _ = u.shape
        zxbcdt = q_(u) @ q_(w["mamba_in_proj/kernel"])
        z = zxbcdt[..., :inner]
        dt = zxbcdt[..., -h:]
        # each stage rematerialised: only its output is kept for the
        # backward, so that one layer's intermediates fit
        xbc = jax.checkpoint(conv)(zxbcdt[..., inner:-h], w["conv_kernel"],
                                   w["conv_bias"])
        x = xbc[..., :inner].reshape(b, s, h, p)
        B = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
        C = xbc[..., inner + g * n:].reshape(b, s, g, n)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        y = recurrence(x, dt, -jnp.exp(w["A_log"]), B, C, w["D"], q_)
        y = jax.checkpoint(gate_norm)(y, z, w["gate_norm_scale"])
        return q_(y) @ q_(w["mamba_out_proj/kernel"])

    def attention(u, w):
        b, s, _ = u.shape
        qkv = q_(u) @ q_(w["gqa_qkv/kernel"])
        q = qkv[..., :heads * dh].reshape(b, s, heads, dh)
        k = qkv[..., heads * dh:(heads + kv) * dh].reshape(b, s, kv, dh)
        v = qkv[..., (heads + kv) * dh:].reshape(b, s, kv, dh)
        causal = jnp.tril(jnp.ones((s, s), bool))

        # a head at a time, rematerialised: S x S scores of one head fit
        def one_head(_, xs):
            qh, kh, vh = xs  # [B, S, dh]
            scores = jnp.einsum("bqe,bke->bqk", q_(qh), q_(kh)) \
                / jnp.sqrt(jnp.float32(dh))
            scores = jnp.where(causal, scores, -jnp.inf)
            return None, jnp.einsum(
                "bqk,bke->bqe", q_(jax.nn.softmax(scores, axis=-1)), q_(vh))

        heads_first = lambda t: jnp.moveaxis(t, 2, 0)
        group = lambda t: jnp.repeat(heads_first(t), heads // kv, axis=0)
        _, o = jax.lax.scan(jax.checkpoint(one_head), None,
                            (heads_first(q), group(k), group(v)))
        o = jnp.moveaxis(o, 0, 2).reshape(b, s, heads * dh)
        return q_(o) @ q_(w["gqa_out/kernel"])

    def experts(u, w):
        return expert_layer(
            u, w, num_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"], first=first, count=count,
            routed_scale=config["routed_scaling_factor"], q_=q_,
            selection_bias=selection_bias)

    mixers = {"M": mamba, "*": attention, "E": experts}

    def block(x, w, kind):
        return x + mixers[kind](_rms_norm(x, w["norm/scale"], eps), w)

    def stack(params, tokens):
        x = params["embed"][tokens]
        # layer by layer, rematerialised, so that a block of rows fits
        for i, kind in enumerate(kinds):
            x = jax.checkpoint(block, static_argnums=(2,))(
                x, {k: params[f"h_{i}/{k}"] for k in _KEYS[kind]}, kind)
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
            def stretch(hid, targets):
                logits = jnp.einsum("bsd,vd->bsv", q_(hid), q_(head))
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
