"""ZAYA1 family: how a configuration of it is built and fed through the
repo's normal constructors (as ``examples/train_gpt2.py --arch zaya`` does),
what a step of it costs in operations, and where its plain reference is.

A configuration states a SHARE of a deployment (``deployment`` in its
file): the experts held here (``num_experts_held`` from
``experts_held_first``) of the ``num_experts`` the router scores, and the
slice of the vocabulary (``vocab_size``)."""

from __future__ import annotations

from benchmarks.families import common, gpt2


def held(config: dict) -> tuple[int, int]:
    return (config["deployment"]["experts_held_first"],
            config["num_experts_held"])


def sequence_quantile_bias(logits):
    """The cell's bias on the selection (``recipe.selection_bias``
    ``sequence_quantile``; ``Routing.selection_bias`` of the program):
    minus each expert's (S / E)-th largest logit of the sequence, so that
    every expert is the first choice of about S / E of a sequence's
    tokens whatever biases the harness has drawn for the router. The
    benchmark's stand-in for the bias ZAYA1 carries between steps: a
    function of the step's own logits, because the harness's reference
    follows parameters only (the configuration file's ``assumed.balancing``
    says what that costs). ``logits``: ``[B, S, E]``."""
    import jax.numpy as jnp

    s, e = logits.shape[-2:]
    kth = jnp.sort(logits, axis=-2)[..., s - s // e, :]
    return -kth[..., None, :]


SELECTION_BIAS = {None: None, "sequence_quantile": sequence_quantile_bias}


def build(config: dict, traffic: dict, mesh) -> dict:
    """Model, optimizer and the arguments ``fit`` gets from the example
    entry point under this configuration's recipe."""
    import jax.numpy as jnp

    from tpudist import mesh as mesh_lib
    from tpudist.models.lm_utils import chunked_lm_forward
    from tpudist.models.zaya import Zaya
    from tpudist.parallel.ep import Routing
    from tpudist.train import lm_loss

    recipe = config["recipe"]
    seq = traffic["seq_len"]
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} > max_position_embeddings")
    attn = common.resolve_attn(recipe["attn"], seq)
    rope = config["rope_parameters"]["hybrid"]
    model = Zaya(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_dim=config["hidden_size"], depth=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn_dim=config["moe_intermediate_size"],
        routing=Routing(
            config["num_experts"], top_k=config["num_experts_per_tok"],
            held=held(config), router="mlp",
            router_width=config["router_hidden_size"],
            selection_bias=SELECTION_BIAS[recipe.get("selection_bias")],
        ),
        conv_kernels=(config["cca_time0"], config["cca_time1"]),
        partial_rotary_factor=rope["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]),
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


def layer_matmul_params(config: dict, held_share: float) -> float:
    """Matmul weights one token passes in one layer: CCA's down- and
    up-projection and its per-head convolution, the router MLP, and the
    one expert of a token whose expert is held (a share of the tokens)."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    r, ff = config["router_hidden_size"], config["moe_intermediate_size"]
    cca = d * (h + 2 * kv) * dh + h * dh * d \
        + config["cca_time1"] * (h + kv) * dh * dh
    router = d * r + 2 * r * r + r * config["num_experts"]
    return cca + router + held_share * 3 * d * ff


def expected_held_share(config: dict) -> float:
    return config["num_experts_held"] / config["num_experts"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Model operations per trained token, by the repo's convention
    (``gpt2.train_flops_per_token``): 6 x matmul weights (forward + two
    backward), attention 12 S E_q a layer in the latent (E_q = heads x
    head size; QK^T and PV, three passes, the causal half NOT taken off).
    The experts count for the EXPECTED held share (held / all: a token
    whose expert is not held computes none here); the traced run prints
    the measured share beside (``expert_gemm_roofline``'s line).
    Recomputation does not count."""
    depth = config["num_hidden_layers"]
    latent = config["num_attention_heads"] * config["head_dim"]
    weights = depth * layer_matmul_params(config, expected_held_share(config)) \
        + config["vocab_size"] * config["hidden_size"]
    return 6.0 * weights + depth * 12.0 * traffic["seq_len"] * latent


def _itemsize(config: dict) -> int:
    return 2 if config["recipe"]["compute_dtype"] == "bfloat16" else 4


def attention_cost(config: dict, traffic: dict) -> dict:
    """Operations and HBM bytes one CCA attention call NEEDS on one chip:
    8 query heads on 2 key/value heads inside the latent, causal half.
    Forward QK^T and PV, backward dP, dV, dQ, dK at the query width; bytes:
    q and o at the query width, k and v at the key/value width (forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv)."""
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    dh = config["head_dim"]
    wide = config["num_attention_heads"] * dh
    narrow = config["num_key_value_heads"] * dh
    itemsize = _itemsize(config)
    matmul = 2.0 * b * s * s * wide * 0.5
    rows = b * s * itemsize
    return {
        "fwd": {"flops": 2 * matmul, "bytes": rows * (2 * wide + 2 * narrow)},
        "bwd": {"flops": 4 * matmul, "bytes": rows * (4 * wide + 4 * narrow)},
        "calls_per_step": config["num_hidden_layers"],
    }


# the trace names a Pallas call after its innermost scope: the block puts
# ``cca_attn`` around its attention call and nothing else
ATTENTION_OPS = r"^cca_attn(\.\d+)?$"


def expert_gemm_cost(config: dict, traffic: dict, held_tokens: float) -> dict:
    """Operations and HBM bytes the grouped products of ONE step need, all
    layers, for ``held_tokens`` rows a layer routed to held experts: three
    products forward (gate, up, down) and six backward (each product's two
    gradients), 2 x rows x d x ff each; recomputation does not count.
    Bytes: every product reads or writes one held weight stack and its
    rows' operand and result, in the compute type."""
    d, ff = config["hidden_size"], config["moe_intermediate_size"]
    itemsize = _itemsize(config)
    products = 3 + 6
    stack = config["num_experts_held"] * d * ff * itemsize
    rows = held_tokens * (d + ff) * itemsize
    layers = config["num_hidden_layers"]
    return {
        "flops": layers * products * 2.0 * held_tokens * d * ff,
        "bytes": layers * products * (stack + rows),
    }


def reference_loss_sum(config: dict, precision: str = "float32"):
    from benchmarks.reference import zaya

    return zaya.make_loss_sum(config, precision)


def moe_counters(ctx: dict) -> dict | None:
    """The program's router counters over the window's telemetry rows
    (``moe`` rows, one a logged step; fields ``h_<n>/tokens`` — rows routed
    to each held expert —, ``h_<n>/held_share``, ``h_<n>/load_max_over_mean``):
    per layer and logged step, the mean rows routed to held experts, the
    mean held share and the mean load ratio. Nothing where the program
    writes no such rows."""
    first = ctx["window"].warmup_steps
    rows = [r for r in ctx["telemetry_rows"]
            if r.get("kind") == "moe" and r.get("step", 0) > first]
    pick = lambda suffix: [v for r in rows for k, v in r.items()
                           if k.endswith("/" + suffix)]
    tokens, share, ratio = (pick("tokens"), pick("held_share"),
                            pick("load_max_over_mean"))
    if not (tokens and share and ratio):
        return None
    mean = lambda xs: sum(xs) / len(xs)
    return {"held_tokens": mean([sum(t) for t in tokens]),
            "held_share": mean(share), "load_max_over_mean": mean(ratio),
            "rows": len(rows)}
