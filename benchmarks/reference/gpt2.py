"""GPT-2 (Radford et al. 2019) in plain ``jax.numpy``, float32: learned
positions, pre-norm blocks, causal softmax attention, tanh-GELU MLP of four
times the width, final norm, output head tied to the token embedding, mean
next-token cross-entropy. No kernels, no cache, no batching tricks: the
layers are scanned and rematerialised only so that a block of rows fits.

Weights arrive as a flat ``{"wte": ..., "h_0/qkv/kernel": ...}`` dict in
the layout the harness generates them in: ``qkv/kernel`` is
``[width, 3, heads, head]``, ``out/kernel`` is ``[heads, head, width]``.
Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.precision import OPERAND

EPS = 1e-5
_LAYER_KEYS = (
    "ln_1/scale", "ln_1/bias", "qkv/kernel", "qkv/bias", "out/kernel",
    "out/bias", "ln_2/scale", "ln_2/bias", "mlp_fc/kernel", "mlp_fc/bias",
    "mlp_proj/kernel", "mlp_proj/bias",
)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)
    ))


def make_loss_sum(config: dict, precision: str = "float32"):
    """``loss_sum(params, rows) -> (sum of next-token CE, positions)``."""
    depth = config["n_layer"]
    q_ = OPERAND[precision]

    def block(x, layer):
        b, s, d = x.shape
        y = _layer_norm(x, layer["ln_1/scale"], layer["ln_1/bias"])
        qkv = jnp.einsum(
            "bsd,dthe->bsthe", q_(y), q_(layer["qkv/kernel"])
        ) + layer["qkv/bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_(q), q_(k))
        scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jnp.einsum(
            "bhqk,bkhe->bqhe", q_(jax.nn.softmax(scores, axis=-1)), q_(v)
        )
        x = x + jnp.einsum(
            "bshe,hed->bsd", q_(attn), q_(layer["out/kernel"])
        ) + layer["out/bias"]
        y = _layer_norm(x, layer["ln_2/scale"], layer["ln_2/bias"])
        y = _gelu_tanh(
            q_(y) @ q_(layer["mlp_fc/kernel"]) + layer["mlp_fc/bias"]
        )
        return x + q_(y) @ q_(layer["mlp_proj/kernel"]) \
            + layer["mlp_proj/bias"]

    def loss_sum(params, rows):
        with jax.default_matmul_precision("highest"):
            tokens = rows["tokens"]
            s = tokens.shape[1]
            stacked = {
                k: jnp.stack([params[f"h_{i}/{k}"] for i in range(depth)])
                for k in _LAYER_KEYS
            }
            x = params["wte"][tokens] + params["wpe"][:s]
            x, _ = jax.lax.scan(
                jax.checkpoint(lambda x, layer: (block(x, layer), None)),
                x, stacked,
            )
            x = _layer_norm(x, params["ln_f/scale"], params["ln_f/bias"])
            logits = jnp.einsum(
                "bsd,vd->bsv", q_(x[:, :-1]), q_(params["wte"])
            )
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, tokens[:, 1:, None], axis=-1
            )[..., 0]
            return -jnp.sum(picked), jnp.float32(picked.size)

    return loss_sum
