"""SDAR family (``model_type: sdar_moe``), trained by diffusion over
blocks: how a configuration of it is built and fed through the repo's
normal constructors (as ``examples/train_gpt2.py --arch sdar`` does), what
a step of it costs in operations, and where its plain reference is.

A configuration states a SHARE of a deployment (``deployment`` in its
file): the experts held here (``num_experts_held`` from
``experts_held_first``) of the ``num_experts`` the router scores, and the
slice of the vocabulary (``vocab_size``). A step runs ``2 L`` ROWS a
sequence — the noised and the clean copy — and trains ``L`` tokens:
``tokens_per_step`` counts the trained tokens, the expert layer's counters
count rows."""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.families import common, kanana, zaya

held = zaya.held


def selection_bias(config: dict):
    """``recipe.selection_bias`` ``sequence_quantile``: the Kanana-2
    family's rule (minus each expert's (k S / E)-th largest entry of the
    sequence) on what a softmax router ranks, its LOGITS, over the ``2 L``
    rows of a batch row."""
    rule = config["recipe"].get("selection_bias")
    if rule is None:
        return None
    if rule != "sequence_quantile":
        raise ValueError(f"unknown selection_bias {rule!r}")
    return functools.partial(kanana.sequence_quantile_bias,
                             top_k=config["num_experts_per_tok"])


def build(config: dict, traffic: dict, mesh) -> dict:
    """Model, optimizer and the arguments ``fit`` gets from the example
    entry point under this configuration's recipe."""
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    try:
        from tpudist.models.sdar import Sdar, block_diffusion_forward
    except ImportError as e:
        # a checkout from before the model (the parent of the PR that
        # brought this cell): no run, exit 3, at once
        from benchmarks.cell import Refused

        raise Refused(f"this checkout's program cannot run the cell: {e}")
    from tpudist.parallel.ep import Routing

    recipe = config["recipe"]
    seq, block = traffic["seq_len"], config["block_length"]
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} > max_position_embeddings")
    if traffic["block_length"] != block or traffic["mask_id"] \
            != config["vocab_size"] - 1:
        raise ValueError("the traffic's block_length and mask_id are the "
                         "configuration's block_length and last id")
    # both copies of a sequence run side by side: attention sees 2 L rows
    attn = common.resolve_attn(recipe["attn"], 2 * seq)
    model = Sdar(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_dim=config["hidden_size"], depth=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn_dim=config["moe_intermediate_size"],
        routing=Routing(
            config["num_experts"], top_k=config["num_experts_per_tok"],
            held=held(config), scoring="softmax",
            selection_bias=selection_bias(config),
        ),
        block_length=block, rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        remat_policy=recipe.get("remat_policy"),
        dtype=common.compute_dtype(recipe), attn_impl=attn, mesh=mesh,
    )
    sample = jnp.zeros((mesh_lib.data_parallel_size(mesh), seq), jnp.int32)
    return {
        "model": model,
        "tx": common.optimizer(recipe),
        "attn": attn,
        "param_shapes": common.param_shapes(model, sample),
        "fit": dict(
            input_key="tokens", label_key="clean",
            forward_loss=block_diffusion_forward(
                model, block, chunk=recipe["chunked_ce"]),
            grad_accum=traffic.get("grad_accum", 1),
            fused=None if recipe["fused"] == "none" else recipe["fused"],
            batch_size=traffic["per_chip_batch"],
            world_size=mesh_lib.data_parallel_size(mesh),
        ),
    }


def make_stream(config: dict, traffic: dict, chips: int):
    """``rng -> (() -> batch)``: uniform random clean ids under the mask
    id, then the program's own host-side corruption, as its loader applies
    it, on a stream seeded from ``rng``."""
    from tpudist.models.sdar import block_diffusion_transform

    shape = (traffic["per_chip_batch"] * chips, traffic["seq_len"])

    def stream(rng):
        corrupt = block_diffusion_transform(
            traffic["mask_id"], traffic["block_length"],
            t_min=traffic["t_min"], seed=int(rng.integers(0, 2**31)),
        )

        def next_batch():
            clean = rng.integers(0, traffic["mask_id"], shape, dtype=np.int32)
            out = corrupt({"tokens": clean})
            return {"tokens": out["tokens"].astype(np.int32),
                    "clean": out["clean"].astype(np.int32),
                    "loss_weight": out["loss_weight"]}

        return next_batch

    return stream


# the TRAINED tokens of a step: L a sequence, not the 2 L rows of the stack
tokens_per_step = common.tokens_per_step


def rows_per_step(traffic: dict, chips: int) -> int:
    """Rows through the stack a step: the noised and the clean copy."""
    return 2 * common.tokens_per_step(traffic, chips)


def expected_held_share(config: dict) -> float:
    return config["num_experts_held"] / config["num_experts"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Model operations per TRAINED token, by the repo's convention (copied
    from ``tpudist/telemetry/flops.py`` ``sdar_train_flops``): 6 x matmul
    weights (forward + two backward) x 2 rows a token for the layers — the
    fused q/k/v and the output projection, the router and ``k`` routed
    experts at the EXPECTED held share —, 6 x the untied head once (it
    sees the noised rows only), attention at the pairs the mask NEEDS,
    ``L² + L b`` a head a sequence: 12 (L + b) heads x head size a layer a
    token (QK^T and PV, three passes). Recomputation does not count."""
    d, depth = config["hidden_size"], config["num_hidden_layers"]
    h, kv, dh = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    attn = d * (h + 2 * kv) * dh + h * dh * d
    layer = attn + d * config["num_experts"] \
        + config["num_experts_per_tok"] * expected_held_share(config) \
        * 3 * d * config["moe_intermediate_size"]
    return 6.0 * (2 * depth * layer + config["vocab_size"] * d) \
        + depth * 12.0 * (traffic["seq_len"] + config["block_length"]) * h * dh


def needed_pairs(config: dict, traffic: dict) -> float:
    """(query, key) pairs the mask allows, a head a step: ``B (L² + L
    b)``."""
    length = traffic["seq_len"]
    return float(traffic["per_chip_batch"]) * (
        length * length + length * config["block_length"])


def attention_cost(config: dict, traffic: dict) -> dict:
    """Operations and HBM bytes one masked attention call NEEDS on one
    chip, at the allowed pairs ``B (L² + L b)`` a head: forward QK^T and
    PV; backward dV, dP, dQ, dK (the scores a flash-style backward computes
    again are recomputation and do not count). Bytes over the ``2 L`` rows
    at the widths the mathematics has — q, o, do, dq at the query heads,
    k, v, dk, dv at the key/value heads: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    h, kv, dh = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    matmul = 2.0 * needed_pairs(config, traffic) * h * dh
    rows = rows_per_step(traffic, 1) * kanana._itemsize(config)
    q, k = h * dh, kv * dh
    return {
        "fwd": {"flops": 2 * matmul, "bytes": rows * (2 * q + 2 * k)},
        "bwd": {"flops": 4 * matmul, "bytes": rows * (4 * q + 4 * k)},
        "calls_per_step": config["num_hidden_layers"],
    }


def tile_shares(config: dict, traffic: dict) -> dict | None:
    """The program's static counter beside the need: the share of the
    ``(2 L)²`` score tiles its kernel computes for the cell's mask at the
    blocks its shape takes, and the share the mask allows. Nothing on a
    program without the counter."""
    try:
        from tpudist.ops.attention import BlockMask
        from tpudist.ops.flash_attention import (
            computed_tile_share, default_blocks,
        )
    except ImportError:
        return None
    length = traffic["seq_len"]
    block_q, block_k, _ = default_blocks(length, length, config["head_dim"])
    return {
        "computed_tile_share": computed_tile_share(
            BlockMask(config["block_length"], length), 2 * length,
            block_q, block_k),
        "blocks": [block_q, block_k],
        "needed_share": needed_pairs(config, traffic)
        / traffic["per_chip_batch"] / (2 * length) ** 2,
    }


# the trace names a Pallas call after its innermost scope: the block puts
# ``bd_attn`` around its attention call and nothing else
ATTENTION_OPS = r"^bd_attn(\.\d+)?$"


def expert_gemm_cost(config: dict, traffic: dict, held_tokens: float) -> dict:
    """Operations and HBM bytes the grouped products of ONE step need, all
    layers, for ``held_tokens`` (row, choice) pairs a layer routed to held
    experts: the Kanana-2 family's count, every layer an expert layer."""
    return kanana.expert_gemm_cost(
        dict(config, first_k_dense_replace=0), traffic, held_tokens)


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import sdar

    return sdar.make_loss_sum(config, precision)


# the dropless layer's counters, as the ZAYA1 family reads them (rows are
# (row, choice) pairs of the 2 L rows: ``held_share`` is of the 2 L k)
moe_counters = zaya.moe_counters
