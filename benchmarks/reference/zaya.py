"""ZAYA1 (Zyphra) in plain ``jax.numpy``, float32: every layer a compressed
convolutional attention (CCA) sublayer then a top-1 expert sublayer, each
merged into the stream as ``x <- (a*x + b) + c*f(RMSNorm(x))``; final
RMSNorm; head tied to the embedding; mean next-token cross-entropy.

Written from the equations of the issue that brought it (what
``config.json`` does not fix is listed under ``assumed`` in
``benchmarks/configs/zaya1-8b.json``). Given the SAME share as the program:
the experts ``held`` (first, count) of ``num_experts`` and the slice of the
vocabulary. Dense over the held experts with a mask — no sort, no grouped
product, no kernel. A token whose expert is not held adds nothing in the
expert sublayer; that partial result goes on, here as in the program.

Weights arrive as a flat ``{"embed": ..., "h_0/cca_proj/kernel": ...}``
dict in the layout the harness generates them in:

- ``cca_proj/kernel [d, (H + 2*Hkv) * D]``: columns are q (H*D), k
  (Hkv*D), v_a (Hkv*D/2), v_b (Hkv*D/2);
- ``cca_mix/conv0 [K0, (H+Hkv)*D]`` (depthwise; tap j multiplies token
  ``t-(K0-1-j)``), ``cca_mix/conv1 [K1, H+Hkv, D, D]`` (per head, in x
  out), ``cca_mix/temp_scale [Hkv]``;
- ``moe_router/{down,fc1,fc2,out}/{kernel,bias}``, ``moe_router/norm/scale``,
  ``moe_router/depth_scale [R]``;
- ``moe_experts/{w_gate,w_up} [held, d, ff]``, ``w_down [held, ff, d]``;
- ``{attn,moe}_{in_scale,shift,out_scale} [d]``, ``{attn,moe}_norm/scale``.

Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.precision import OPERAND

_LAYER_KEYS = (
    "attn_norm/scale", "attn_in_scale", "attn_shift", "attn_out_scale",
    "cca_proj/kernel", "cca_mix/conv0", "cca_mix/conv1",
    "cca_mix/temp_scale", "cca_out/kernel",
    "moe_norm/scale", "moe_in_scale", "moe_shift", "moe_out_scale",
    "moe_router/down/kernel", "moe_router/down/bias",
    "moe_router/depth_scale", "moe_router/norm/scale",
    "moe_router/fc1/kernel", "moe_router/fc1/bias",
    "moe_router/fc2/kernel", "moe_router/fc2/bias",
    "moe_router/out/kernel", "moe_router/out/bias",
    "moe_experts/w_gate", "moe_experts/w_up", "moe_experts/w_down",
)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)
    ))


def _shift(x, steps=1):
    """``y[:, t] = x[:, t - steps]``, nought before the start."""
    if steps == 0:
        return x
    return jnp.concatenate(
        [jnp.zeros_like(x[:, :steps]), x[:, :-steps]], axis=1
    )


def _rope(x, theta, rotary_dim):
    """Rotate-half rotary embedding on the first ``rotary_dim`` channels of
    each head of ``x [B, S, H, D]``; the rest pass through."""
    s = x.shape[1]
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rotary_dim:]],
        axis=-1,
    )


def expert_sublayer(u, r_prev, p, *, num_experts: int, first: int,
                    count: int, eps: float, q_=lambda x: x,
                    selection_bias: str | None = None):
    """The expert sublayer's ``f`` over the share ``first .. first+count``
    of ``num_experts``: ``(y, r)`` from the normed input ``u [B, S, d]``,
    the router's carry ``r_prev [B, S, R]`` and the layer's leaves ``p``
    (``moe_experts/*`` hold the ``count`` held experts).
    ``selection_bias`` ``"sequence_quantile"``: the expert is chosen by the
    logits less each expert's (S / E)-th largest of the sequence; the gate
    stays the chosen expert's unbiased probability."""
    # the router: float32 whatever the step's precision
    r = u @ p["moe_router/down/kernel"] + p["moe_router/down/bias"]
    r = r + p["moe_router/depth_scale"] * r_prev
    z = _rms_norm(r, p["moe_router/norm/scale"], eps)
    z = _gelu_tanh(z @ p["moe_router/fc1/kernel"] + p["moe_router/fc1/bias"])
    z = _gelu_tanh(z @ p["moe_router/fc2/kernel"] + p["moe_router/fc2/bias"])
    z = z @ p["moe_router/out/kernel"] + p["moe_router/out/bias"]
    probs = jax.nn.softmax(z, axis=-1)
    # top-1 of all num_experts; the gate is the chosen probability, raw
    if selection_bias is None:
        chosen = jnp.argmax(probs, axis=-1)
    elif selection_bias == "sequence_quantile":
        s = z.shape[1]
        kth = jnp.sort(z, axis=1)[:, s - s // num_experts]
        chosen = jnp.argmax(z - kth[:, None, :], axis=-1)
    else:
        raise ValueError(f"unknown selection_bias {selection_bias!r}")
    gate = jnp.take_along_axis(probs, chosen[..., None], axis=-1)[..., 0]

    # g * (silu(u Wg) * (u Wu)) Wd of the chosen expert where it is held,
    # nought elsewhere: every held expert over every token, masked
    def one(y, xs):
        e, wg, wu, wd = xs
        hid = jax.nn.silu(q_(u) @ q_(wg)) * (q_(u) @ q_(wu))
        mine = (chosen == first + e)[..., None]
        return y + jnp.where(mine, q_(hid) @ q_(wd), 0.0), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(u),
        (jnp.arange(count), p["moe_experts/w_gate"], p["moe_experts/w_up"],
         p["moe_experts/w_down"]),
    )
    return gate[..., None] * y, r


def make_loss_sum(config: dict, precision: str = "float32"):
    """``loss_sum(params, rows) -> (sum of next-token CE, positions)``."""
    depth = config["num_hidden_layers"]
    h, kv, dh = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    rep = h // kv
    eps = config["rms_norm_eps"]
    experts = config["num_experts"]
    first, count = config["deployment"]["experts_held_first"], \
        config["num_experts_held"]
    k0, k1 = config["cca_time0"], config["cca_time1"]
    rope = config["rope_parameters"]["hybrid"]
    rotary_dim = int(dh * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    if config["num_experts_per_tok"] != 1:
        raise ValueError("the reference routes top-1")
    q_ = OPERAND[precision]
    selection_bias = config.get("recipe", {}).get("selection_bias")

    def cca(u, p):
        b, s, _ = u.shape
        latent = q_(u) @ q_(p["cca_proj/kernel"])
        q, k, va, vb = jnp.split(
            latent, [h * dh, (h + kv) * dh, (h + kv) * dh + kv * dh // 2],
            axis=-1,
        )
        q = q.reshape(b, s, h, dh)
        k = k.reshape(b, s, kv, dh)
        # value shift: half of each value head from this token, half from
        # the token before
        v = jnp.concatenate([
            va.reshape(b, s, kv, dh // 2),
            _shift(vb.reshape(b, s, kv, dh // 2)),
        ], axis=-1)
        # q-k mean within a key/value group
        mean_q = 0.5 * (q + jnp.repeat(k, rep, axis=2))
        mean_k = 0.5 * (jnp.mean(q.reshape(b, s, kv, rep, dh), axis=3) + k)
        # two causal convolutions on [q ; k]: depthwise, then per head
        qk = jnp.concatenate([q, k], axis=2)
        w0 = p["cca_mix/conv0"].reshape(k0, h + kv, dh)
        y = sum(w0[j] * _shift(qk, k0 - 1 - j) for j in range(k0))
        y = sum(
            jnp.einsum("bshc,hcd->bshd", q_(_shift(y, k1 - 1 - j)),
                       q_(p["cca_mix/conv1"][j]))
            for j in range(k1)
        )
        q = y[:, :, :h] + mean_q
        k = y[:, :, h:] + mean_k
        # sqrt(D) * x / ||x|| per head; a learned temperature on the keys
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        q = _rope(unit(q), theta, rotary_dim)
        k = _rope(unit(k) * p["cca_mix/temp_scale"][:, None], theta,
                  rotary_dim)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_(q), q_(k))
        scores = scores / jnp.sqrt(jnp.float32(dh))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        o = jnp.einsum(
            "bhqk,bkhe->bqhe", q_(jax.nn.softmax(scores, axis=-1)), q_(v)
        )
        return q_(o.reshape(b, s, h * dh)) @ q_(p["cca_out/kernel"])

    def block(x, r, p):
        u = _rms_norm(x, p["attn_norm/scale"], eps)
        x = (p["attn_in_scale"] * x + p["attn_shift"]) \
            + p["attn_out_scale"] * cca(u, p)
        u = _rms_norm(x, p["moe_norm/scale"], eps)
        y, r = expert_sublayer(
            u, r, p, num_experts=experts, first=first, count=count, eps=eps,
            q_=q_, selection_bias=selection_bias,
        )
        x = (p["moe_in_scale"] * x + p["moe_shift"]) + p["moe_out_scale"] * y
        return x, r

    def loss_sum(params, rows):
        with jax.default_matmul_precision("highest"):
            tokens = rows["tokens"]
            b, s = tokens.shape
            x = params["embed"][tokens]
            r = jnp.zeros((b, s, config["router_hidden_size"]), jnp.float32)
            # layer by layer, rematerialised, so that a block of rows fits
            for i in range(depth):
                layer = {k: params[f"h_{i}/{k}"] for k in _LAYER_KEYS}
                x, r = jax.checkpoint(block)(x, r, layer)
            x = _rms_norm(x, params["norm/scale"], eps)
            logits = jnp.einsum(
                "bsd,vd->bsv", q_(x[:, :-1]), q_(params["embed"])
            )
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, tokens[:, 1:, None], axis=-1
            )[..., 0]
            return -jnp.sum(picked), jnp.float32(picked.size)

    return loss_sum
