"""One-formula AdamW update, one HBM sweep per leaf in the leaf's own layout.

The optax ``adamw`` chain is a sequence of tree transforms (moment update,
bias correction, decayed weights, learning-rate scale), plus — in a
bf16-compute run — a separate whole-model fp32→bf16 cast of every
parameter each step. :func:`fused_leaf_update` writes the whole update of
one leaf as ONE elementwise expression over ``(grad, m, v, fp32 master
param)`` giving ``(update, m', v', compute copy)``. All of its arrays have
the leaf's shape, so XLA emits one multi-output loop fusion per leaf in the
tiling the leaf already has, and folds what surrounds the call into it: the
clip's scale on ``g`` before it, ``optax.apply_updates``' ``p + u`` after
it (the update itself then never reaches HBM). Nothing params-sized is
ravelled, padded, sliced or reshaped.

Why no kernel: AdamW is elementwise, so any layout will do as long as the
arrays share it, and the compiler's own fusion has that for free. A Pallas
kernel needs a 2-D view of every leaf. On a TPU an array lives in (8, 128)
tiles of its two minor dimensions, so a ``(rows, 128)`` view is a read and a
write of the whole leaf on either side of the call; a view that only
collapses the leading dimensions is free, but the call still stands outside
XLA's fusion (``p + u`` runs apart) and its placement of operands. All three
were measured on the chip (PERF.md §6, PR 26; ``opt_ms`` of a GPT-2 medium
step): this path 7.8 ms, a kernel on the trailing view 12.0, the kernel on
``(rows, 128)`` that this module held before 27.9 (with the prefetch waits
that the trace leaves unnamed: 16.9 / 19.8 / 36.7). Being plain XLA, the
update also needs no ``shard_map`` on a mesh, and GSPMD partitions it like
any elementwise op. Under ZeRO-1 (``optim.shard_state``) a leaf stored
sharded on a dimension the axis divides compiles, for a described 2x2 mesh,
to one fusion at the shard's shape with one all-gather after it, of the new
master (tests/test_tpu_compile.py; compiled, not measured on the chip); a
leaf in the pad-and-reshape layout is still gathered and laid out anew
around its update, as under any optimizer.

The ARITHMETIC mirrors ``optax.adamw`` exactly (division-form bias
correction, ``sqrt(v̂)+eps`` denominator, decay-then-scale order), so this
path and the reference chain agree bit-for-bit on the CPU — the parity bar
tests/test_fused_update.py pins. The update is returned (rather than the
new param) so the surface stays optax-compatible; the compute copy is
``compute_dtype(p + u)``, bit-identical to casting the post-update master.
The optimizer-facing wrapper (``tpudist.optim.fused_adamw``) owns the tree
walk, hyperparameters, and optax ``(init, update)`` surface.
"""

from __future__ import annotations

import jax.numpy as jnp


def adamw_math(g, m, v, p, lr, b1c, b2c, *, b1, b2, eps, wd):
    """The ONE AdamW formula, optax-order arithmetic:

    ``m' = b1·m + (1−b1)·g``; ``v' = b2·v + (1−b2)·g²``;
    ``u = −lr · ( (m'/b1c) / (√(v'/b2c) + eps) + wd·p )``.

    ``b1c``/``b2c`` are the bias-correction denominators ``1 − βᵗ`` (traced
    scalars, computed once per step by the caller). Returns
    ``(m', v', u)`` in fp32.
    """
    g = g.astype(jnp.float32)
    m = m.astype(jnp.float32)
    v = v.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    m2 = b1 * m + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * jnp.square(g)
    mhat = m2 / b1c
    vhat = v2 / b2c
    direction = mhat / (jnp.sqrt(vhat) + eps)
    if wd:
        direction = direction + wd * p32
    return m2, v2, direction * (-lr)


def fused_leaf_update(g, m, v, p, lr, b1c, b2c, *, b1, b2, eps, wd=0.0,
                      compute_dtype=None):
    """AdamW for one parameter leaf, in the leaf's own shape.

    ``g``/``m``/``v``/``p``: same shape, any rank. ``lr``/``b1c``/``b2c``:
    traced fp32 scalars. ``wd`` is this leaf's static decay coefficient
    (0.0 for masked-off leaves — bias/norm params under ``decay_mask``).
    ``compute_dtype`` adds the cast compute copy as a fourth output of the
    same expression.

    Returns ``(u, m', v', copy|None)`` with ``u`` in ``p.dtype`` and the
    moments in their input dtypes.
    """
    m2, v2, u = adamw_math(g, m, v, p, lr, b1c, b2c,
                           b1=b1, b2=b2, eps=eps, wd=wd)
    copy = None
    if compute_dtype is not None:
        copy = (p.astype(jnp.float32) + u).astype(compute_dtype)
    return u.astype(p.dtype), m2.astype(m.dtype), v2.astype(v.dtype), copy
