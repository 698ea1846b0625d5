"""What the family files share: the optimizer the examples build, the
resolution of ``attn auto`` as the example entry points echo it, and the
shapes of a model's parameters."""

from __future__ import annotations


def resolve_attn(requested: str, seq_len: int) -> str:
    """``examples/train_gpt2.py``'s resolution of ``--attn auto`` (vmem up
    to 1024, dense XLA to 2047, flash from 2048; XLA off the TPU)."""
    import jax

    if requested != "auto":
        return requested
    if jax.default_backend() != "tpu":
        return "xla"
    if seq_len <= 1024:
        return "vmem"
    return "xla" if seq_len < 2048 else "flash"


def compute_dtype(recipe: dict):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        recipe["compute_dtype"]
    ]


def optimizer(recipe: dict):
    """The optimizer as ``examples/train_gpt2.py`` builds it."""
    import jax.numpy as jnp

    from tpudist.optim import make_optimizer, run_schedule

    opt = recipe["optimizer"]
    dtype = compute_dtype(recipe)
    return make_optimizer(
        run_schedule(opt["lr"], total_steps=opt["total_steps"],
                     warmup_steps=opt["warmup_steps"]),
        optimizer="adam", b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"],
        fused=recipe["fused"] in ("optimizer", "all"),
        compute_dtype=dtype if dtype != jnp.float32 else None,
    )


def param_shapes(model, sample):
    """Shapes of ``model``'s parameters, unboxed, without running it."""
    import jax
    from flax import linen as nn

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample, train=False)
    )["params"]
    return nn.meta.unbox(shapes)


def tokens_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * chips * traffic["seq_len"]


def attention_cost(traffic: dict, *, width: int, layers: int,
                   compute_dtype: str, causal: bool) -> dict:
    """Operations and HBM bytes one attention call NEEDS on one chip
    (``per_chip_batch`` rows), forward and backward apart. Causal: half of
    the S x S tiles. Forward: QK^T and PV (2 matmuls); backward: dP, dV,
    dQ, dK (4 — the scores a flash-style backward computes again are
    recomputation and do not count). Bytes: q, k, v read and o written
    (forward); q, k, v, o, do read and dq, dk, dv written (backward), in
    the compute type."""
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    itemsize = 2 if compute_dtype == "bfloat16" else 4
    matmul = 2.0 * b * s * s * width * (0.5 if causal else 1.0)
    tensor = b * s * width * itemsize
    return {
        "fwd": {"flops": 2 * matmul, "bytes": 4 * tensor},
        "bwd": {"flops": 4 * matmul, "bytes": 8 * tensor},
        "calls_per_step": layers,
    }
