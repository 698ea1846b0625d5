"""The precisions a reference is computed in: float32 as the reference
proper, and the lower ones a control uses to show that the comparison would
catch a step computed below what the configuration states."""

from __future__ import annotations

import jax
import jax.numpy as jnp

def _round_to(x, dtype, largest: float):
    """Round to an 8-bit float with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def _fp8(x):
    """A matmul operand as an fp8 training recipe holds it: e4m3 forward,
    and the gradient that comes back through it in e5m2."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(
    lambda x: (_fp8(x), None),
    lambda _, g: (_round_to(g, jnp.float8_e5m2, 57344.0),),
)


def _bf16(x):
    q = x.astype(jnp.bfloat16).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


OPERAND = {"float32": lambda x: x, "bfloat16": _bf16, "fp8": _fp8}
