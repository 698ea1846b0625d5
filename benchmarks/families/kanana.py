"""Kanana-2 family (``model_type: deepseek_v3``): how a configuration of it
is built and fed through the repo's normal constructors (as
``examples/train_gpt2.py --arch kanana`` does), what a step of it costs in
operations, and where its plain reference is.

A configuration states a SHARE of a deployment (``deployment`` in its
file): the experts held here (``num_experts_held`` from
``experts_held_first``) of the ``n_routed_experts`` the router scores, and
the slice of the vocabulary (``vocab_size``)."""

from __future__ import annotations

import functools

from benchmarks.families import common, gpt2, zaya

held = zaya.held


def sequence_quantile_bias(scores, *, top_k: int):
    """The cell's bias on the selection (``recipe.selection_bias``
    ``sequence_quantile``; ``Routing.selection_bias`` of the program), the
    ZAYA1 family's rule made for top-k on scores: minus each expert's
    (k S / E)-th largest score of the sequence, so that every expert is
    among the chosen of about k S / E of a sequence's tokens whatever the
    seed drew. The benchmark's stand-in for the bias the model carries
    between steps (``topk_method`` ``noaux_tc``): it looks at later tokens
    and costs a sort a layer — fit for a training cell on random weights,
    not for a served model (the configuration file's ``assumed.balancing``).
    ``scores``: ``[B, S, E]``."""
    import jax.numpy as jnp

    s, e = scores.shape[-2:]
    kth = jnp.sort(scores, axis=-2)[..., s - max(top_k * s // e, 1), :]
    return -kth[..., None, :]


def selection_bias(config: dict):
    rule = config["recipe"].get("selection_bias")
    if rule is None:
        return None
    if rule != "sequence_quantile":
        raise ValueError(f"unknown selection_bias {rule!r}")
    return functools.partial(sequence_quantile_bias,
                             top_k=config["num_experts_per_tok"])


def build(config: dict, traffic: dict, mesh) -> dict:
    """Model, optimizer and the arguments ``fit`` gets from the example
    entry point under this configuration's recipe."""
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    from tpudist.models.lm_utils import chunked_lm_forward
    try:
        from tpudist.models.kanana import Kanana
    except ImportError as e:
        # a checkout from before the model (the parent of the PR that
        # brought this cell): no run, exit 3, at once
        from benchmarks.cell import Refused

        raise Refused(f"this checkout's program cannot run the cell: {e}")
    from tpudist.parallel.ep import Routing
    from tpudist.train import lm_loss

    recipe = config["recipe"]
    seq = traffic["seq_len"]
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} > max_position_embeddings")
    attn = common.resolve_attn(recipe["attn"], seq)
    model = Kanana(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_dim=config["hidden_size"], depth=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"],
        dense_ffn_dim=config["intermediate_size"],
        ffn_dim=config["moe_intermediate_size"],
        shared_dim=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        routing=Routing(
            config["n_routed_experts"], top_k=config["num_experts_per_tok"],
            held=held(config), scoring=config["scoring_func"],
            routed_scale=config["routed_scaling_factor"],
            selection_bias=selection_bias(config),
        ),
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        remat_policy=recipe.get("remat_policy"),
        dtype=common.compute_dtype(recipe), attn_impl=attn, mesh=mesh,
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


# uniform random ids over the configuration's vocabulary (its slice)
make_stream = gpt2.make_stream
tokens_per_step = common.tokens_per_step


def expected_held_share(config: dict) -> float:
    return config["num_experts_held"] / config["n_routed_experts"]


def _heads(config: dict) -> tuple[int, int, int]:
    """Heads, key width (nope + rope) and value width."""
    return (config["num_attention_heads"],
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Model operations per trained token, by the repo's convention (copied
    from ``tpudist/telemetry/flops.py`` ``kanana_train_flops``): 6 x matmul
    weights (forward + two backward) — MLA's four projections in every
    layer, the dense SwiGLU in the leading layers, router, shared expert
    and ``k`` routed experts at the EXPECTED held share (held / all: a
    choice whose expert is not held computes none here) in the others, the
    untied head —, attention 6 S H (key width + value width) a layer (QK^T
    and PV, three passes, the causal half NOT taken off). Recomputation
    does not count."""
    d, depth = config["hidden_size"], config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    h, dk, dv = _heads(config)
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    ff = config["moe_intermediate_size"]
    mla = d * h * dk + d * (rank + rope) \
        + rank * h * (config["qk_nope_head_dim"] + dv) + h * dv * d
    expert_layer = d * config["n_routed_experts"] \
        + 3 * d * ff * config["n_shared_experts"] \
        + config["num_experts_per_tok"] * expected_held_share(config) \
        * 3 * d * ff
    weights = depth * mla + dense * 3 * d * config["intermediate_size"] \
        + (depth - dense) * expert_layer + config["vocab_size"] * d
    return 6.0 * weights + depth * 6.0 * traffic["seq_len"] * h * (dk + dv)


def _itemsize(config: dict) -> int:
    return 2 if config["recipe"]["compute_dtype"] == "bfloat16" else 4


def attention_cost(config: dict, traffic: dict) -> dict:
    """Operations and HBM bytes one MLA attention call NEEDS on one chip,
    causal half: forward QK^T at the key width (192) and PV at the value
    width (128); backward dV and dP at the value width, dQ and dK at the
    key width (the scores a flash-style backward computes again are
    recomputation and do not count). Bytes at the widths the mathematics
    has — q, dq at heads x key width; k, dk at heads x nope plus the ONE
    rotary key; v, o, do, dv at heads x value width: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    h, dk, dv = _heads(config)
    matmul = lambda width: 2.0 * b * s * s * h * width * 0.5
    rows = b * s * _itemsize(config)
    q, v = h * dk, h * dv
    k = h * config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return {
        "fwd": {"flops": matmul(dk) + matmul(dv),
                "bytes": rows * (q + k + 2 * v)},
        "bwd": {"flops": 2 * matmul(dv) + 2 * matmul(dk),
                "bytes": rows * (2 * q + 2 * k + 4 * v)},
        "calls_per_step": config["num_hidden_layers"],
    }


# the trace names a Pallas call after its innermost scope: the block puts
# ``mla_attn`` around its attention call and nothing else
ATTENTION_OPS = r"^mla_attn(\.\d+)?$"


def expert_gemm_cost(config: dict, traffic: dict, held_tokens: float) -> dict:
    """Operations and HBM bytes the grouped products of ONE step need, all
    expert layers, for ``held_tokens`` (token, choice) rows a layer routed
    to held experts: three products forward (gate, up, down) and six
    backward, 2 x rows x d x ff each; recomputation does not count. Bytes:
    every product reads or writes one held weight stack and its rows'
    operand and result, in the compute type."""
    d, ff = config["hidden_size"], config["moe_intermediate_size"]
    itemsize = _itemsize(config)
    products = 3 + 6
    stack = config["num_experts_held"] * d * ff * itemsize
    rows = held_tokens * (d + ff) * itemsize
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return {
        "flops": layers * products * 2.0 * held_tokens * d * ff,
        "bytes": layers * products * (stack + rows),
    }


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import kanana

    return kanana.make_loss_sum(config, precision)


# the dropless layer's counters, as the ZAYA1 family reads them (rows are
# (token, choice) pairs here: ``held_share`` is of the T k rows)
moe_counters = zaya.moe_counters
