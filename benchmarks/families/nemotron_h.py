"""Nemotron-H family (``model_type: nemotron_h``): how a configuration of it
is built and fed through the repo's normal constructors (as
``examples/train_gpt2.py --arch nemotron_h`` does), what a step of it costs
in operations, and where its plain reference is.

A configuration states a SHARE of a deployment (``deployment`` in its
file): the experts held here (``num_experts_held`` from
``experts_held_first``) of the ``n_routed_experts`` the router scores, and
the slice of the vocabulary (``vocab_size``). Its layers are the first
``num_hidden_layers`` characters of ``hybrid_override_pattern``: ``M`` a
Mamba-2 mixer (the chunked state-space kernel: :func:`ssd_cost`), ``*``
grouped-query attention, ``E`` an expert layer of squared-ReLU experts."""

from __future__ import annotations

from benchmarks.families import common, gpt2, kanana, laguna, zaya

held = zaya.held
selection_bias = kanana.selection_bias


def kinds(config: dict) -> str:
    """The pattern characters of the layers held here."""
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def build(config: dict, traffic: dict, mesh) -> dict:
    """Model, optimizer and the arguments ``fit`` gets from the example
    entry point under this configuration's recipe."""
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    from tpudist.models.lm_utils import chunked_lm_forward
    try:
        from tpudist.models.nemotron_h import NemotronH
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
    if config["norm_eps"] != config["layer_norm_epsilon"] \
            or config["mlp_hidden_act"] != "relu2":
        raise ValueError("one norm epsilon, and squared-ReLU experts")
    attn = common.resolve_attn(recipe["attn"], seq)
    model = NemotronH(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_dim=config["hidden_size"], depth=config["num_hidden_layers"],
        pattern=config["hybrid_override_pattern"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"], state_dim=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk=config["chunk_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        ffn_dim=config["moe_intermediate_size"],
        shared_dim=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        routing=Routing(
            config["n_routed_experts"], top_k=config["num_experts_per_tok"],
            held=held(config), scoring="sigmoid",
            routed_scale=config["routed_scaling_factor"],
            selection_bias=selection_bias(config),
        ),
        norm_eps=config["norm_eps"],
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


def ssd_cost(config: dict, traffic: dict) -> dict:
    """Operations and HBM bytes one chunked state-space scan call NEEDS on
    one chip, forward and backward apart (copied from
    ``tpudist/ops/ssd.py`` ``ssd_cost``). Forward, a chunk of ``L``:
    ``C B^T`` once a group (``2 L² N``); a head's in-chunk product (``2 L²
    P``), carried-state read-out and state update (``2 L N P`` each).
    Backward: two products for each. Bytes: forward reads ``x``, ``B``,
    ``C`` in the compute type and writes ``y`` and every chunk's float32
    state; backward reads ``x``, ``B``, ``C``, the states and ``dy`` and
    writes ``dx``, ``dB``, ``dC``. ``calls_per_step``: the Mamba-2 layers
    held here; ``chunks``: a call's chunks a row."""
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    L, n = config["chunk_size"], config["ssm_state_size"]
    h, p, g = (config["mamba_num_heads"], config["mamba_head_dim"],
               config["n_groups"])
    if s % L:
        raise ValueError(f"seq_len {s} is not a multiple of chunk {L}")
    nc = s // L
    fwd = b * nc * (g * 2 * L * L * n + h * (2 * L * L * p + 4 * L * n * p))
    item = kanana._itemsize(config)
    x_bytes = b * s * h * p * item
    bc_bytes = 2 * b * s * g * n * item
    st_bytes = b * nc * h * n * p * 4
    return {
        "fwd": {"flops": float(fwd),
                "bytes": float(2 * x_bytes + bc_bytes + st_bytes)},
        "bwd": {"flops": float(2 * fwd),
                "bytes": float(3 * x_bytes + 2 * bc_bytes + st_bytes)},
        "calls_per_step": kinds(config).count("M"),
        "chunks": nc,
    }


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Model operations per trained token, by the repo's convention (copied
    from ``tpudist/telemetry/flops.py`` ``nemotron_h_train_flops``): 6 x
    matmul weights (forward + two backward) — a Mamba-2 layer's in and
    out projections, an attention layer's q/k/v and output, an expert
    layer's router, shared expert and ``k`` routed squared-ReLU experts
    (two matrices) at the EXPECTED held share, the untied head —; three
    passes of a Mamba-2 layer's chunked scan (:func:`ssd_cost`'s forward)
    and of its four-tap convolution; attention at the causal triangle's
    pairs, 12 x head size a pair a head. Recomputation does not count."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    inner = h * p
    conv_dim = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    shared = config["n_shared_experts"] \
        * config["moe_shared_expert_intermediate_size"]
    per_layer = {
        "M": d * (inner + conv_dim + h) + inner * d,
        "*": d * (heads + 2 * kv) * dh + heads * dh * d,
        "E": d * config["n_routed_experts"] + 2 * d * shared
        + config["num_experts_per_tok"] * expected_held_share(config)
        * 2 * d * config["moe_intermediate_size"],
    }
    layers = kinds(config)
    weights = config["vocab_size"] * d \
        + sum(per_layer[kind] for kind in layers)
    scan = ssd_cost(config, dict(traffic, per_chip_batch=1))["fwd"]["flops"] \
        / seq + 2 * config["conv_kernel"] * conv_dim
    attention = 12.0 * dh * heads * laguna.window_pairs(seq, None) / seq
    return 6.0 * weights + layers.count("M") * 3.0 * scan \
        + layers.count("*") * attention


def expert_gemm_cost(config: dict, traffic: dict, held_tokens: float) -> dict:
    """Operations and HBM bytes the grouped products of ONE step need, all
    expert layers, for ``held_tokens`` (token, choice) rows a layer routed
    to held experts: two products forward (up, down) and four backward, 2
    x rows x d x ff each — a squared-ReLU expert has no gate; recomputation
    does not count. Bytes: every product reads or writes one held weight
    stack and its rows' operand and result, in the compute type."""
    d, ff = config["hidden_size"], config["moe_intermediate_size"]
    itemsize = kanana._itemsize(config)
    products = 2 + 4
    stack = config["num_experts_held"] * d * ff * itemsize
    rows = held_tokens * (d + ff) * itemsize
    layers = kinds(config).count("E")
    return {
        "flops": layers * products * 2.0 * held_tokens * d * ff,
        "bytes": layers * products * (stack + rows),
    }


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import nemotron_h

    return nemotron_h.make_loss_sum(config, precision)


# the dropless layer's counters, as the ZAYA1 family reads them (rows are
# (token, choice) pairs here: ``held_share`` is of the T k rows)
moe_counters = zaya.moe_counters


def ssd_counters(ctx: dict) -> dict | None:
    """The program's ``ssd_log_carry`` over the window's telemetry rows
    (``moe`` rows, field ``h_<n>/ssd_log_carry`` of each Mamba-2 layer):
    the mean over layers and logged steps. Nothing where the program
    writes no such field."""
    first = ctx["window"].warmup_steps
    values = [v for r in ctx["telemetry_rows"]
              if r.get("kind") == "moe" and r.get("step", 0) > first
              for k, v in r.items() if k.endswith("/ssd_log_carry")]
    if not values:
        return None
    return {"ssd_log_carry": sum(values) / len(values),
            "layers_steps": len(values)}
