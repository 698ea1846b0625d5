"""BERT family (MLM pre-training): built and fed as ``examples/
train_bert.py`` does — ``mlm_transform`` corrupting each batch on the host,
``mlm_forward`` as the loss — under the recipe the configuration states."""

from __future__ import annotations

import numpy as np

from benchmarks.families import common


def build(config: dict, traffic: dict, mesh) -> dict:
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    from tpudist.models.bert import Bert, mlm_forward

    recipe = config["recipe"]
    seq = traffic["seq_len"]
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} > max_position_embeddings")
    attn = common.resolve_attn(recipe["attn"], seq)
    fused = None if recipe["fused"] == "none" else recipe["fused"]
    model = Bert(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_dim=config["hidden_size"], depth=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        type_vocab=config["type_vocab_size"],
        dtype=common.compute_dtype(recipe), attn_impl=attn, mesh=mesh,
        # mlm_forward closes over the model and has no rebuild hook, so the
        # fused-LN model is built here rather than cloned by the step
        fused_ln=fused in ("ln", "all"),
    )
    sample = jnp.zeros((mesh_lib.data_parallel_size(mesh), seq), jnp.int32)
    return {
        "model": model,
        "tx": common.optimizer(recipe),
        "attn": attn,
        "param_shapes": common.param_shapes(model, sample),
        "fit": dict(
            input_key="tokens", label_key="targets",
            forward_loss=mlm_forward(model, chunk=recipe.get("chunked_ce")),
            grad_accum=traffic.get("grad_accum", 1), fused=fused,
            batch_size=traffic["per_chip_batch"],
            world_size=mesh_lib.data_parallel_size(mesh),
        ),
    }


def make_stream(config: dict, traffic: dict, chips: int):
    """``rng -> (() -> batch)``: uniform random ids (special ids left
    out), then the program's own host-side corruption, as its loader
    applies it, on a stream seeded from ``rng``."""
    from tpudist.models.bert import mlm_transform

    shape = (traffic["per_chip_batch"] * chips, traffic["seq_len"])
    first, vocab = traffic.get("first_plain_id", 1000), config["vocab_size"]

    def stream(rng):
        corrupt = mlm_transform(
            vocab, traffic["mask_id"], mask_rate=traffic["mask_rate"],
            seed=int(rng.integers(0, 2**31)),
        )

        def next_batch():
            tokens = rng.integers(first, vocab, shape, dtype=np.int32)
            out = corrupt({"tokens": tokens})
            return {"tokens": out["tokens"].astype(np.int32),
                    "targets": out["targets"].astype(np.int32),
                    "mlm_mask": out["mlm_mask"]}

        return next_batch

    return stream


tokens_per_step = common.tokens_per_step


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Copied from ``tpudist/telemetry/flops.py`` ``bert_train_flops``:
    6 x (12 H^2 a block + the MLM head's H^2 transform + the tied V H
    decode, over every position as the program computes it) + attention
    12 S H a layer. Recomputation does not count."""
    h, depth = config["hidden_size"], config["num_hidden_layers"]
    weights = depth * 12 * h * h + h * h + config["vocab_size"] * h
    return 6.0 * weights + depth * 12.0 * traffic["seq_len"] * h


def attention_cost(config: dict, traffic: dict) -> dict:
    return common.attention_cost(
        traffic, width=config["hidden_size"], layers=config["num_hidden_layers"],
        compute_dtype=config["recipe"]["compute_dtype"], causal=False,
    )


# the attention kernel's events in a device trace: the trace names a Pallas
# call after the flax scope that encloses it, and in a block that scope
# holds no other kernel (the norms have scopes of their own). A stable
# named_scope on the kernel would replace this (PERF.md, tracing issue).
ATTENTION_OPS = r"^h_\d+(\.\d+)?$"


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import bert

    return bert.make_loss_sum(config, precision)
