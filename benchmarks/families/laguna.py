"""Laguna family (``model_type: laguna``): how a configuration of it is
built and fed through the repo's normal constructors (as
``examples/train_gpt2.py --arch laguna`` does), what a step of it costs in
operations, and where its plain reference is.

A configuration states a SHARE of a deployment (``deployment`` in its
file): the experts held here (``num_experts_held`` from
``experts_held_first``) of the ``num_experts`` the router scores, and the
slice of the vocabulary (``vocab_size``). Its layers are of two kinds,
``full_attention`` and ``sliding_attention``, each with a head count of its
own: the attention kernel's costs and its trace name are per kind
(:data:`KINDS`)."""

from __future__ import annotations

import types

from benchmarks.families import common, gpt2, kanana, zaya

held = zaya.held
selection_bias = kanana.selection_bias

FULL, SLIDING = "full_attention", "sliding_attention"


def _layers(config: dict) -> list[tuple[str, int]]:
    """``(kind, query heads)`` of each layer held here."""
    depth = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:depth],
                    config["num_attention_heads_per_layer"][:depth]))


def _dense_layers(config: dict) -> int:
    """The leading dense layers (``mlp_layer_types``: ``dense`` first)."""
    types_ = config["mlp_layer_types"][:config["num_hidden_layers"]]
    dense = next((i for i, t in enumerate(types_) if t != "dense"),
                 len(types_))
    if any(t == "dense" for t in types_[dense:]):
        raise ValueError("dense layers lead the stack, or there are none")
    return dense


def _yarn(config: dict):
    from tpudist.models.llama import Yarn

    full = config["rope_parameters"][FULL]
    if full["rope_type"] != "yarn":
        raise ValueError(f"full layers' rope_type {full['rope_type']!r}")
    return Yarn(float(full["factor"]),
                full["original_max_position_embeddings"],
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=float(full["attention_factor"]))


def build(config: dict, traffic: dict, mesh) -> dict:
    """Model, optimizer and the arguments ``fit`` gets from the example
    entry point under this configuration's recipe."""
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    from tpudist.models.lm_utils import chunked_lm_forward
    try:
        from tpudist.models.laguna import Laguna
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
    rope = config["rope_parameters"]
    if rope[SLIDING]["rope_type"] != "default" \
            or rope[SLIDING]["partial_rotary_factor"] != 1:
        raise ValueError("sliding layers turn the whole head, plain rotary")
    attn = common.resolve_attn(recipe["attn"], seq)
    kinds, heads = zip(*_layers(config))
    dh = config["head_dim"]
    model = Laguna(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_dim=config["hidden_size"], depth=config["num_hidden_layers"],
        layer_types=kinds, heads_per_layer=heads,
        num_kv_heads=config["num_key_value_heads"], head_dim=dh,
        window=config["sliding_window"], dense_layers=_dense_layers(config),
        dense_ffn_dim=config["intermediate_size"],
        ffn_dim=config["moe_intermediate_size"],
        shared_dim=config["shared_expert_intermediate_size"],
        routing=Routing(
            config["num_experts"], top_k=config["num_experts_per_tok"],
            held=held(config), scoring="sigmoid",
            routed_scale=config["moe_routed_scaling_factor"],
            selection_bias=selection_bias(config),
        ),
        full_rope_theta=float(rope[FULL]["rope_theta"]),
        full_rotary_dim=int(dh * rope[FULL]["partial_rotary_factor"]),
        yarn=_yarn(config),
        sliding_rope_theta=float(rope[SLIDING]["rope_theta"]),
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
    return config["num_experts_held"] / config["num_experts"]


def window_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs a head of one sequence needs: the causal
    triangle, or the sliding window's band (copied from
    ``tpudist/telemetry/flops.py`` ``window_pairs``)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def _window(config: dict, kind: str) -> int | None:
    return config["sliding_window"] if kind == SLIDING else None


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Model operations per trained token, by the repo's convention (copied
    from ``tpudist/telemetry/flops.py`` ``laguna_train_flops``): 6 x matmul
    weights (forward + two backward) — q/k/v, the output gate and the
    output projection at each layer's head count, the dense SwiGLU in the
    leading layers, router, shared expert and ``k`` routed experts at the
    EXPECTED held share in the others, the untied head —, attention at the
    pairs each layer NEEDS (the causal triangle, or the window's band), 12
    x head size a pair a head (QK^T and PV, three passes). Recomputation
    does not count."""
    d, dh, kv = (config["hidden_size"], config["head_dim"],
                 config["num_key_value_heads"])
    seq, dense = traffic["seq_len"], _dense_layers(config)
    ff = config["moe_intermediate_size"]
    weights, pairs = config["vocab_size"] * d, 0.0
    for layer, (kind, h) in enumerate(_layers(config)):
        weights += d * (h + 2 * kv) * dh + 2 * h * dh * d
        if layer < dense:
            weights += 3 * d * config["intermediate_size"]
        else:
            weights += d * config["num_experts"] \
                + 3 * d * config["shared_expert_intermediate_size"] \
                + config["num_experts_per_tok"] \
                * expected_held_share(config) * 3 * d * ff
        pairs += h * window_pairs(seq, _window(config, kind))
    return 6.0 * weights + 12.0 * dh * pairs / seq


def attention_cost_of(kind: str):
    """``attention_cost(config, traffic)`` of one kind's calls: operations
    and HBM bytes one call NEEDS on one chip, at the pairs its mask allows
    a head (:func:`window_pairs`) — forward QK^T and PV; backward dV, dP,
    dQ, dK (the scores a flash-style backward computes again are
    recomputation and do not count). Bytes at the widths the mathematics
    has — q, o, do, dq at the kind's query heads, k, v, dk, dv at the
    key/value heads: forward reads q, k, v and writes o; backward reads q,
    k, v, o, do and writes dq, dk, dv. ``calls_per_step``: the layers of
    that kind held here."""

    def attention_cost(config: dict, traffic: dict) -> dict:
        mine = [h for k, h in _layers(config) if k == kind]
        h = mine[0] if mine else 0
        if any(x != h for x in mine):
            raise ValueError(f"{kind} layers with different head counts")
        b, s = traffic["per_chip_batch"], traffic["seq_len"]
        dh, kv = config["head_dim"], config["num_key_value_heads"]
        matmul = 2.0 * b * window_pairs(s, _window(config, kind)) * h * dh
        rows = b * s * kanana._itemsize(config)
        q, k = h * dh, kv * dh
        return {
            "fwd": {"flops": 2 * matmul, "bytes": rows * (2 * q + 2 * k)},
            "bwd": {"flops": 4 * matmul, "bytes": rows * (4 * q + 4 * k)},
            "calls_per_step": len(mine),
        }

    return attention_cost


# each kind as the roofline reader sees a family: the trace names a Pallas
# call after its innermost scope, and the block puts ``swa_attn`` /
# ``full_attn`` around its attention call and nothing else
KINDS = {
    SLIDING: types.SimpleNamespace(ATTENTION_OPS=r"^swa_attn(\.\d+)?$",
                                   attention_cost=attention_cost_of(SLIDING)),
    FULL: types.SimpleNamespace(ATTENTION_OPS=r"^full_attn(\.\d+)?$",
                                attention_cost=attention_cost_of(FULL)),
}


def tile_shares(config: dict, traffic: dict) -> dict | None:
    """The program's static counters of the windowed kernel beside the
    need, at the blocks its shape takes: the share of the ``S²`` score
    tiles it computes, of the tiles whose block it fetches and of the grid
    steps it runs (three kernels), and the share of the pairs the window
    allows. Nothing on a program without the counters."""
    try:
        from tpudist.ops.attention import BlockMask
        from tpudist.ops.flash_attention import (
            computed_tile_share, default_blocks, fetched_tile_share,
            grid_step_share,
        )
    except ImportError:
        return None
    s, window = traffic["seq_len"], config["sliding_window"]
    mask = BlockMask(window=window)
    blocks = default_blocks(s, s, config["head_dim"], window)[:2]
    return {
        "computed_tile_share": computed_tile_share(mask, s, *blocks),
        "fetched_tile_share": fetched_tile_share(mask, s, *blocks),
        "grid_step_share": grid_step_share(mask, s, *blocks),
        "blocks": list(blocks),
        "needed_share": window_pairs(s, window) / s ** 2,
    }


def expert_gemm_cost(config: dict, traffic: dict, held_tokens: float) -> dict:
    """Operations and HBM bytes the grouped products of ONE step need, all
    expert layers, for ``held_tokens`` (token, choice) rows a layer routed
    to held experts: the Kanana-2 family's count."""
    return kanana.expert_gemm_cost(
        dict(config, first_k_dense_replace=_dense_layers(config)), traffic,
        held_tokens)


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import laguna

    return laguna.make_loss_sum(config, precision)


# the dropless layer's counters, as the ZAYA1 family reads them (rows are
# (token, choice) pairs here: ``held_share`` is of the T k rows)
moe_counters = zaya.moe_counters
