"""One-pass fused AdamW update as a Pallas TPU kernel.

The optax ``adamw`` chain is a sequence of tree transforms (moment update,
bias correction, decayed weights, learning-rate scale) each of which is its
own pass over params-shaped trees, plus — in a bf16-compute run — a
separate whole-model fp32→bf16 cast of every parameter each step. On the
124M GPT-2 step those passes are part of the measured ~100 ms serial
elementwise tail (docs/PERF.md §4b): bandwidth-bound work XLA fuses only
partially.

This kernel reads ``(grad, m, v, fp32 master param)`` and writes
``(m', v', update, bf16 compute copy)`` in a single HBM sweep per leaf:
every intermediate (biased-corrected moments, the Adam direction, the
decayed-weight term, the new parameter value the copy is cast from) lives
only in VMEM. The update is returned (rather than the new param written
in place) so the surface stays optax-compatible — ``optax.apply_updates``
adds it to the master, one fusion XLA folds — and the compute copy is
``compute_dtype(p + u)``, bit-identical to casting the post-update master.

The ARITHMETIC mirrors ``optax.adamw`` exactly (division-form bias
correction, ``sqrt(v̂)+eps`` denominator, decay-then-scale order), so the
kernel path and the reference chain agree bit-for-bit in interpret mode —
the parity bar tests/test_fused_update.py pins.

Leaves below :data:`MIN_KERNEL_ELEMS` take the identical-formula XLA path
(:func:`reference_leaf_update`): a kernel launch per 4-element bias is all
overhead, and the two paths share one formula function so they cannot
drift. The optimizer-facing wrapper (``tpudist.optim.fused_adamw``) owns
the tree walk, hyperparameters, and optax ``(init, update)`` surface.

GSPMD note: ``pallas_call`` has no partitioning rule, and the TPU compiler
refuses a bare Mosaic call in a program that spans several chips. So where
a mesh is in context — the train step puts its own there around
``tx.update`` — the call runs inside a ``shard_map`` with every operand
replicated: on replicated state (pure DP — the regime §4b measures) every
chip runs the sweep on its own copy, exactly like the optax chain. A
SHARDED leaf (ZeRO-1 ``shard_state``, tensor/fsdp axes) is all-gathered
around the call — combine fused LN with those freely, but measure before
combining the fused *optimizer* with them on hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpudist.ops import backend

# below this many elements the per-launch overhead dwarfs the sweep; the
# XLA path runs the same formula (tests pin the two paths to agreement)
MIN_KERNEL_ELEMS = 8 * 128

_LANES = 128


def adamw_math(g, m, v, p, lr, b1c, b2c, *, b1, b2, eps, wd):
    """The ONE AdamW formula both paths share, optax-order arithmetic:

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


def reference_leaf_update(g, m, v, p, lr, b1c, b2c, *, b1, b2, eps, wd,
                          compute_dtype=None):
    """Plain-XLA AdamW for one leaf — the small-leaf path and the oracle
    the kernel is pinned against. Returns ``(u, m', v', copy|None)``."""
    m2, v2, u = adamw_math(g, m, v, p, lr, b1c, b2c,
                           b1=b1, b2=b2, eps=eps, wd=wd)
    copy = None
    if compute_dtype is not None:
        copy = (p.astype(jnp.float32) + u).astype(compute_dtype)
    return u.astype(p.dtype), m2.astype(m.dtype), v2.astype(v.dtype), copy


def _update_kernel(s_ref, g_ref, m_ref, v_ref, p_ref,
                   u_ref, m_out, v_out, *maybe_c,
                   b1: float, b2: float, eps: float, wd: float,
                   has_copy: bool):
    lr, b1c, b2c = s_ref[0], s_ref[1], s_ref[2]
    p = p_ref[...]
    m2, v2, u = adamw_math(
        g_ref[...], m_ref[...], v_ref[...], p, lr, b1c, b2c,
        b1=b1, b2=b2, eps=eps, wd=wd,
    )
    u_ref[...] = u.astype(u_ref.dtype)
    m_out[...] = m2.astype(m_out.dtype)
    v_out[...] = v2.astype(v_out.dtype)
    if has_copy:
        c_ref = maybe_c[0]
        c_ref[...] = (p.astype(jnp.float32) + u).astype(c_ref.dtype)


def fused_leaf_update(g, m, v, p, lr, b1c, b2c, *, b1, b2, eps, wd=0.0,
                      compute_dtype=None, block_rows: int = 512,
                      min_kernel_elems: int | None = None):
    """One-HBM-sweep AdamW for one parameter leaf.

    ``g``/``m``/``v``/``p``: same shape, any rank. ``lr``/``b1c``/``b2c``:
    traced fp32 scalars (the per-step hyperparameter vector rides SMEM).
    ``wd`` is this leaf's static decay coefficient (0.0 for masked-off
    leaves — bias/norm params under ``decay_mask``). ``compute_dtype``
    adds the cast compute copy as a fourth output written in the same
    sweep.

    Returns ``(u, m', v', copy|None)`` with ``u`` in ``p.dtype`` and the
    moments in their input dtypes. Leaves smaller than
    :data:`MIN_KERNEL_ELEMS` (override via ``min_kernel_elems``) run
    :func:`reference_leaf_update` — same formula, no launch.
    """
    limit = MIN_KERNEL_ELEMS if min_kernel_elems is None else min_kernel_elems
    if p.size < limit:
        return reference_leaf_update(
            g, m, v, p, lr, b1c, b2c, b1=b1, b2=b2, eps=eps, wd=wd,
            compute_dtype=compute_dtype,
        )

    shape = p.shape
    n = p.size
    rows = -(-n // _LANES)
    bn = max(8, min(block_rows, rows) // 8 * 8)
    rows_pad = rows + (-rows % bn)

    def prep(a):
        flat = jnp.ravel(a)
        return jnp.pad(flat, (0, rows_pad * _LANES - n)).reshape(
            rows_pad, _LANES
        )

    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(b1c, jnp.float32),
        jnp.asarray(b2c, jnp.float32),
    ])
    row_spec = pl.BlockSpec((bn, _LANES), lambda i: (i, 0))
    has_copy = compute_dtype is not None
    out_specs = [row_spec, row_spec, row_spec]
    out_shape = [
        jax.ShapeDtypeStruct((rows_pad, _LANES), p.dtype),
        jax.ShapeDtypeStruct((rows_pad, _LANES), m.dtype),
        jax.ShapeDtypeStruct((rows_pad, _LANES), v.dtype),
    ]
    if has_copy:
        out_specs.append(row_spec)
        out_shape.append(
            jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.dtype(compute_dtype))
        )
    sweep = pl.pallas_call(
        functools.partial(
            _update_kernel, b1=float(b1), b2=float(b2), eps=float(eps),
            wd=float(wd), has_copy=has_copy,
        ),
        grid=(rows_pad // bn,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  row_spec, row_spec, row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=backend.interpret(),
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size > 1:
        # no GSPMD rule (module docstring): per chip, on replicated operands
        sweep = jax.shard_map(
            sweep, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
        )
    out = sweep(scalars, prep(g), prep(m), prep(v), prep(p))

    def unprep(a):
        return jnp.ravel(a)[:n].reshape(shape)

    u, m2, v2 = unprep(out[0]), unprep(out[1]), unprep(out[2])
    copy = unprep(out[3]) if has_copy else None
    return u, m2, v2, copy
