"""BERT (Devlin et al. 2019) masked-language-model pre-training in plain
``jax.numpy``, float32: token + position + segment embeddings under a
LayerNorm, post-norm blocks (the residual SUM is normalised), bidirectional
softmax attention, exact (erf) GELU MLP of four times the width, MLM head
(dense, GELU, LayerNorm, decode against the tied token embedding plus a
bias), mean cross-entropy over the corrupted positions of the whole batch.
No next-sentence head (listed under ``assumed``).

Weights arrive as a flat dict in the layout the harness generates them in
(``qkv/kernel`` ``[width, 3, heads, head]``, ``out/kernel``
``[heads, head, width]``). Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.precision import OPERAND

EPS = 1e-12
_LAYER_KEYS = (
    "qkv/kernel", "qkv/bias", "out/kernel", "out/bias", "ln_attn/scale",
    "ln_attn/bias", "mlp_fc/kernel", "mlp_fc/bias", "mlp_proj/kernel",
    "mlp_proj/bias", "ln_mlp/scale", "ln_mlp/bias",
)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def make_loss_sum(config: dict, precision: str = "float32"):
    """``loss_sum(params, rows) -> (sum of masked CE, masked positions)``."""
    depth = config["num_hidden_layers"]
    q_ = OPERAND[precision]

    def block(x, layer):
        qkv = jnp.einsum(
            "bsd,dthe->bsthe", q_(x), q_(layer["qkv/kernel"])
        ) + layer["qkv/bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_(q), q_(k))
        scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
        attn = jnp.einsum(
            "bhqk,bkhe->bqhe", q_(jax.nn.softmax(scores, axis=-1)), q_(v)
        )
        y = jnp.einsum(
            "bshe,hed->bsd", q_(attn), q_(layer["out/kernel"])
        ) + layer["out/bias"]
        x = _layer_norm(x + y, layer["ln_attn/scale"], layer["ln_attn/bias"])
        y = _gelu(q_(x) @ q_(layer["mlp_fc/kernel"]) + layer["mlp_fc/bias"])
        y = q_(y) @ q_(layer["mlp_proj/kernel"]) + layer["mlp_proj/bias"]
        return _layer_norm(x + y, layer["ln_mlp/scale"], layer["ln_mlp/bias"])

    def loss_sum(params, rows):
        with jax.default_matmul_precision("highest"):
            tokens = rows["tokens"]
            s = tokens.shape[1]
            stacked = {
                k: jnp.stack([params[f"h_{i}/{k}"] for i in range(depth)])
                for k in _LAYER_KEYS
            }
            x = params["wte"][tokens] + params["wpe"][:s] + params["wty"][0]
            x = _layer_norm(
                x, params["ln_embed/scale"], params["ln_embed/bias"]
            )
            x, _ = jax.lax.scan(
                jax.checkpoint(lambda x, layer: (block(x, layer), None)),
                x, stacked,
            )
            y = _gelu(
                q_(x) @ q_(params["mlm_head/transform/kernel"])
                + params["mlm_head/transform/bias"]
            )
            y = _layer_norm(
                y, params["mlm_head/ln/scale"], params["mlm_head/ln/bias"]
            )
            logits = jnp.einsum(
                "bsd,vd->bsv", q_(y), q_(params["wte"])
            ) + params["mlm_head/bias"]
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, rows["targets"][..., None], axis=-1
            )[..., 0]
            mask = rows["mlm_mask"].astype(jnp.float32)
            return -jnp.sum(picked * mask), jnp.sum(mask)

    return loss_sum
