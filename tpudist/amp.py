"""Mixed precision policy for TPU training.

The reference trains in fp32 end-to-end (no autocast/AMP anywhere in
/root/reference/main.py — SURVEY.md §2.12 lists "AMP/bf16 autocast" as
explicitly absent); BASELINE.json config 4 (ViT-B/16) demands a bf16 path.
The TPU-native story is simpler than CUDA AMP: MXU matmuls take bf16 inputs
natively and accumulate in fp32, so there is no fp16 loss-scaling dance —
the policy is "fp32 master params, bf16 compute, fp32 logits/loss", which
the flax modules implement via their ``dtype`` field (params are created in
fp32 and cast per-op). This module gives that convention a name, plus
guards for the rare bf16 overflow spike.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax


@dataclasses.dataclass(frozen=True)
class Policy:
    """Dtype roles for a training step.

    ``param_dtype``: master copy precision (optimizer state math);
    ``compute_dtype``: forward/backward matmul inputs;
    ``output_dtype``: logits/loss precision.
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32

    def cast_to_compute(self, tree):
        return _cast_floats(tree, self.compute_dtype)

    def cast_to_param(self, tree):
        return _cast_floats(tree, self.param_dtype)

    def cast_to_output(self, tree):
        return _cast_floats(tree, self.output_dtype)


FP32 = Policy()
BF16_COMPUTE = Policy(compute_dtype=jnp.bfloat16)


def policy_for(bf16: bool) -> Policy:
    return BF16_COMPUTE if bf16 else FP32


def _cast_floats(tree, dtype):
    def cast(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return jnp.asarray(x, dtype)
        return x

    with jax.named_scope("cast"):
        return jax.tree_util.tree_map(cast, tree)


def all_finite(tree) -> jax.Array:
    """Scalar bool: every float leaf of ``tree`` is finite."""
    leaves = [
        jnp.all(jnp.isfinite(x))
        for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
    ]
    if not leaves:
        return jnp.asarray(True)
    return jnp.stack(leaves).all()


def nonfinite_count(tree) -> jax.Array:
    """int32 scalar: how many elements across the float leaves of ``tree``
    are non-finite. The telemetry health metric's counter — one home, so
    the compiled step and any future consumer (e.g. the explicit-reduction
    path's detection on dequantized grads) count the same way. Non-float
    leaves don't count (they cannot hold NaN/inf)."""
    counts = [
        jnp.sum(~jnp.isfinite(x))
        for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
    ]
    if not counts:
        return jnp.zeros((), jnp.int32)
    return jnp.asarray(sum(counts), jnp.int32)


class SkipNonfinite(NamedTuple):
    """:func:`skip_nonfinite`'s return type: the optax ``(init, update)``
    surface plus ``inner`` — the wrapped transformation, kept visible so
    capability probes (``tpudist.optim``'s fused-optimizer detection) can
    walk through the wrapper the same way they walk through
    ``ShardedStateOptimizer.inner``. Every existing consumer duck-types
    ``init``/``update`` and is unaffected."""

    init: Callable
    update: Callable
    inner: Any


def skip_nonfinite(tx: optax.GradientTransformation) -> SkipNonfinite:
    """Wrap an optimizer so steps with non-finite gradients become no-ops.

    A bf16 overflow spike (or a data glitch) then skips one update instead
    of poisoning params and Adam moments with NaNs forever. The skip count
    is kept in the wrapper's state for observability.
    """

    def init(params):
        return (tx.init(params), jnp.zeros((), jnp.int32))

    def update(grads, state, params=None):
        inner_state, skipped = state
        ok = all_finite(grads)
        safe = jax.tree_util.tree_map(
            lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads
        )
        new_updates, new_inner = tx.update(safe, inner_state, params)
        # non-finite step: zero updates, optimizer state unchanged
        updates = jax.tree_util.tree_map(
            lambda u: jnp.where(ok, u, jnp.zeros_like(u)), new_updates
        )
        inner = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ok, new, old)
            if jnp.issubdtype(jnp.asarray(new).dtype, jnp.inexact)
            or jnp.issubdtype(jnp.asarray(new).dtype, jnp.integer)
            else new,
            new_inner, inner_state,
        )
        return updates, (inner, skipped + jnp.where(ok, 0, 1))

    return SkipNonfinite(init, update, tx)


def skipped_steps(opt_state) -> int:
    """Read the skip counter out of a :func:`skip_nonfinite` state."""
    return int(opt_state[1])


def is_skip_state(opt_state) -> bool:
    """True when ``opt_state`` is structurally a :func:`skip_nonfinite`
    state — ``(inner_state, int32 scalar counter)``, the wrapper applied
    outermost by convention (including under
    :func:`tpudist.optim.shard_state`, whose counter leaf is replicated).
    Works on tracers too (shape/dtype are static), which is how
    ``make_train_step``'s non-finite guard finds the counter leaf to
    exempt from its opt-state freeze. The ONE structural definition: a
    future change to the wrapper's state shape is updated here, next to
    the wrapper, and every reader follows."""
    if not (isinstance(opt_state, tuple) and len(opt_state) == 2):
        return False
    counter = opt_state[1]
    return (
        hasattr(counter, "dtype")
        and getattr(counter, "ndim", None) == 0
        and jnp.issubdtype(counter.dtype, jnp.integer)
    )


def maybe_skipped_steps(opt_state) -> int | None:
    """Best-effort :func:`skipped_steps` for chains that may not carry the
    wrapper: the count, or ``None`` when the chain carries no skip wrapper
    — the telemetry run-summary row then reports ``null`` instead of
    fabricating a zero (tpudist.telemetry)."""
    return skipped_steps(opt_state) if is_skip_state(opt_state) else None
