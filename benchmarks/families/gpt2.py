"""GPT-2 family: how a configuration of it is built and fed through the
repo's normal constructors (as ``examples/train_gpt2.py`` does), what a
step of it costs in operations, and where its plain reference is."""

from __future__ import annotations

import numpy as np

from benchmarks.families import common


def build(config: dict, traffic: dict, mesh) -> dict:
    """Model, optimizer and the arguments ``fit`` gets from the example
    entry point under this configuration's recipe."""
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    from tpudist.models.gpt2 import GPT2, chunked_lm_forward
    from tpudist.train import lm_loss

    recipe = config["recipe"]
    seq = traffic["seq_len"]
    if seq > config["n_positions"]:
        raise ValueError(f"seq_len {seq} > n_positions")
    attn = common.resolve_attn(recipe["attn"], seq)
    model = GPT2(
        vocab_size=config["vocab_size"], max_seq_len=config["n_positions"],
        hidden_dim=config["n_embd"], depth=config["n_layer"],
        num_heads=config["n_head"], dtype=common.compute_dtype(recipe),
        attn_impl=attn, mesh=mesh, dropout=0.0,
    )
    forward_loss = None
    if recipe.get("chunked_ce"):
        forward_loss = chunked_lm_forward(model, chunk=recipe["chunked_ce"])
    sample = jnp.zeros((mesh_lib.data_parallel_size(mesh), seq), jnp.int32)
    return {
        "model": model,
        "tx": common.optimizer(recipe),
        "attn": attn,
        "param_shapes": common.param_shapes(model, sample),
        "fit": dict(
            loss_fn=lm_loss, input_key="tokens", label_key="tokens",
            grad_accum=traffic.get("grad_accum", 1),
            fused=None if recipe["fused"] == "none" else recipe["fused"],
            forward_loss=forward_loss,
            batch_size=traffic["per_chip_batch"],
            world_size=mesh_lib.data_parallel_size(mesh),
        ),
    }


def make_stream(config: dict, traffic: dict, chips: int):
    """``rng -> (() -> batch)`` of uniform random ids, every row
    different."""
    shape = (traffic["per_chip_batch"] * chips, traffic["seq_len"])
    vocab = config["vocab_size"]
    return lambda rng: lambda: {
        "tokens": rng.integers(0, vocab, shape, dtype=np.int32)
    }


tokens_per_step = common.tokens_per_step


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Model operations per trained token (copied from
    ``tpudist/telemetry/flops.py`` ``gpt2_train_flops``): weight GEMMs
    forward + two backward (6 x matmul parameters, 12 H^2 a block plus the
    tied head V H), attention 12 S H a layer (QK^T and AV, three passes, the
    causal half NOT taken off — the repo's and PaLM's convention).
    Recomputation does not count."""
    h, depth = config["n_embd"], config["n_layer"]
    weights = depth * 12 * h * h + config["vocab_size"] * h
    return 6.0 * weights + depth * 12.0 * traffic["seq_len"] * h


def attention_cost(config: dict, traffic: dict) -> dict:
    return common.attention_cost(
        traffic, width=config["n_embd"], layers=config["n_layer"],
        compute_dtype=config["recipe"]["compute_dtype"], causal=True,
    )


# the attention kernel's events in a device trace: the trace names a Pallas
# call after the flax scope that encloses it, and in a block that scope
# holds no other kernel (the norms have scopes of their own). A stable
# named_scope on the kernel would replace this (PERF.md, tracing issue).
ATTENTION_OPS = r"^h_\d+(\.\d+)?$"


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import gpt2

    return gpt2.make_loss_sum(config, precision)
