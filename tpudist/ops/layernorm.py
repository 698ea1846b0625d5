"""Fused residual-add + LayerNorm/RMSNorm as a Pallas TPU kernel.

Between a transformer step's GEMMs sits a serial elementwise tail —
layernorms, residual adds, casts. XLA fuses those chains, but each ``x + y`` → ``LayerNorm`` pair still costs separate HBM
round trips for the add's result and the norm's two reduction passes. This
kernel collapses one pair into a single sweep:

- **forward**: one grid pass over row blocks computes ``r = x + y`` (the
  residual-stream update), the masked mean/variance of ``r`` over the true
  feature width, and the normalized/affine output — all while the block is
  VMEM-resident, with one HBM read of (x, y) and one write of (out, r).
  The optional output cast (bf16 models) happens in the same write instead
  of a separate cast pass.
- **backward** (``custom_vjp``): one grid pass over the SAME saved ``r``
  recomputes the row statistics in-block (cheaper than storing them:
  lane-padded stats would cost ~1/6 of the activation bytes at width 768)
  and emits ``dr`` plus ``dscale``/``dbias`` accumulated across the row
  sweep in VMEM scratch — the classic LN backward identities, one HBM read
  of (r, g), one write of dr. Because ``r = x + y`` is a plain add,
  ``dx = dy = dr (+ the residual-stream cotangent)`` and no second pass
  exists.

Numerics: statistics and the normalize are computed in float32 regardless
of input dtype (the flax modules cast the *normalize* to the compute dtype;
this kernel is the strictly-better-precision side of the fp32 tolerance the
parity tests pin). Variance is the direct ``E[(x-µ)²]`` form.

Three public compositions (all interpret-mode on CPU, like the flash/vmem
kernels, so the whole test suite exercises the real kernel code paths):

- ``fused_layernorm(x, scale, bias)`` — plain one-pass norm (a model's
  first/final LN, which has no pending residual add);
- ``fused_layernorm(x, scale, bias, residual=r)`` — pre-norm blocks:
  returns ``(normed, r + x)`` so the residual stream continues;
- ``... return_residual=False`` — post-norm blocks (BERT): the sum is
  normalized and only the normed value returns (the sum is still saved
  for backward, exactly what autodiff would have stored).

``rms=True`` selects scale-only RMS normalization (Llama/T5 convention,
flax ``nn.RMSNorm`` parity). The :class:`FusedLayerNorm` flax module
declares params under the SAME names/shapes as ``nn.LayerNorm`` /
``nn.RMSNorm`` ("scale", "bias"), so a model can flip its ``fused_ln``
knob without changing its checkpoint format.

GSPMD: like every Pallas op here, ``pallas_call`` has no partitioning
rule, so on a >1-device mesh the kernel must run per-shard inside
``shard_map`` — pass ``mesh=`` (the models thread their own ``mesh``
field); rows are batch-parallel so the wrap is exact. With ``mesh=None``
the op still partitions correctly under single-chip-per-process DP and on
the CPU interpret path (tpudist.ops.attention documents the same rule).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops import backend


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static kernel configuration — hashable, rides custom_vjp's
    nondiff_argnums. ``d_true`` is the unpadded feature width (the mask +
    mean denominator); ``out_dtype``/``res_dtype`` are canonical dtype
    names (dtypes themselves are unhashable pre-numpy-2)."""

    eps: float
    d_true: int
    rms: bool
    out_dtype: str
    res_dtype: str
    block_rows: int


def _pick_block_rows(d_pad: int) -> int:
    # ~2 MB of f32 per VMEM buffer; sublane multiple of 8
    bn = (1 << 21) // (d_pad * 4)
    return int(max(8, min(256, bn // 8 * 8)))


def _row_stats(r, cfg: _Cfg, d_pad: int):
    """Masked per-row (mean, rstd) over the true feature width — shared
    verbatim by the forward and the recomputing backward so they cannot
    disagree bitwise."""
    if d_pad != cfg.d_true:
        mask = jax.lax.broadcasted_iota(jnp.int32, r.shape, 1) < cfg.d_true
        rm = jnp.where(mask, r, 0.0)
    else:
        mask = None
        rm = r
    inv_d = 1.0 / cfg.d_true
    if cfg.rms:
        mean = jnp.zeros((r.shape[0], 1), jnp.float32)
        var = jnp.sum(rm * rm, axis=1, keepdims=True) * inv_d
    else:
        mean = jnp.sum(rm, axis=1, keepdims=True) * inv_d
        diff = r - mean
        if mask is not None:
            diff = jnp.where(mask, diff, 0.0)
        var = jnp.sum(diff * diff, axis=1, keepdims=True) * inv_d
    rstd = jax.lax.rsqrt(var + cfg.eps)
    return mean, rstd, mask


def _fwd_kernel(x_ref, *rest, cfg: _Cfg, has_residual: bool):
    if has_residual:
        y_ref, scale_ref, bias_ref, out_ref, res_ref = rest
    else:
        y_ref, res_ref = None, None
        scale_ref, bias_ref, out_ref = rest
    r = x_ref[...].astype(jnp.float32)
    if has_residual:
        r = r + y_ref[...].astype(jnp.float32)
    mean, rstd, _ = _row_stats(r, cfg, x_ref.shape[1])
    n = (r - mean) * rstd * scale_ref[...].astype(jnp.float32)
    if not cfg.rms:
        n = n + bias_ref[...].astype(jnp.float32)
    out_ref[...] = n.astype(out_ref.dtype)
    if has_residual:
        res_ref[...] = r.astype(res_ref.dtype)


def _bwd_kernel(r_ref, g_ref, *rest, cfg: _Cfg, has_gr: bool):
    if has_gr:
        gr_ref, scale_ref, dr_ref, ds_ref, db_ref, ds_scr, db_scr = rest
    else:
        gr_ref = None
        scale_ref, dr_ref, ds_ref, db_ref, ds_scr, db_scr = rest
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    r = r_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mean, rstd, mask = _row_stats(r, cfg, r_ref.shape[1])
    xhat = (r - mean) * rstd
    dxhat = g * scale_ref[...].astype(jnp.float32)
    if mask is not None:
        # padded feature columns carry zero cotangent by construction (the
        # wrapper's slice pads g with zeros), but xhat is garbage there —
        # keep it out of the row means and the dscale accumulator
        xhat = jnp.where(mask, xhat, 0.0)
        dxhat = jnp.where(mask, dxhat, 0.0)
    inv_d = 1.0 / cfg.d_true
    c2 = jnp.sum(dxhat * xhat, axis=1, keepdims=True) * inv_d
    dr = dxhat - xhat * c2
    if not cfg.rms:
        c1 = jnp.sum(dxhat, axis=1, keepdims=True) * inv_d
        dr = dr - c1
    dr = dr * rstd
    if has_gr:
        dr = dr + gr_ref[...].astype(jnp.float32)
    dr_ref[...] = dr.astype(dr_ref.dtype)
    # every scratch row accumulates the SAME block row-sum (the 8-row shape
    # keeps the sublane dim tile-conformant on real TPUs — a (1, D) block
    # would put 1 in the sublane slot; interpret mode doesn't enforce it,
    # the flash kernel's lse buffer documents the same dance)
    ds_scr[...] += jnp.broadcast_to(
        jnp.sum(g * xhat, axis=0, keepdims=True), ds_scr.shape
    )
    db_scr[...] += jnp.broadcast_to(
        jnp.sum(g, axis=0, keepdims=True), db_scr.shape
    )

    @pl.when(i == nb - 1)
    def _fin():
        ds_ref[...] = ds_scr[...]
        db_ref[...] = db_scr[...]


def _fwd_call(x, y, scale, bias, cfg: _Cfg):
    """x[, y]: [N, Dp] padded; scale/bias: [1, Dp]. → (n, r|None)."""
    n_rows, d_pad = x.shape
    bn = cfg.block_rows
    grid = (n_rows // bn,)
    row_spec = pl.BlockSpec((bn, d_pad), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d_pad), lambda i: (0, 0))
    has_residual = y is not None
    in_specs = [row_spec] + ([row_spec] if has_residual else []) + [vec_spec, vec_spec]
    out_specs = [row_spec] + ([row_spec] if has_residual else [])
    out_shape = [jax.ShapeDtypeStruct(x.shape, jnp.dtype(cfg.out_dtype))] + (
        [jax.ShapeDtypeStruct(x.shape, jnp.dtype(cfg.res_dtype))]
        if has_residual else []
    )
    args = (x, y, scale, bias) if has_residual else (x, scale, bias)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, has_residual=has_residual),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=backend.interpret(),
    )(*args)
    return (out[0], out[1]) if has_residual else (out[0], None)


def _bwd_call(r, g, gr, scale, cfg: _Cfg):
    """→ (dr [N, Dp] in res dtype, dscale [1, Dp] f32, dbias [1, Dp] f32)."""
    n_rows, d_pad = r.shape
    bn = cfg.block_rows
    grid = (n_rows // bn,)
    row_spec = pl.BlockSpec((bn, d_pad), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d_pad), lambda i: (0, 0))
    has_gr = gr is not None
    in_specs = [row_spec, row_spec] + ([row_spec] if has_gr else []) + [vec_spec]
    args = (r, g, gr, scale) if has_gr else (r, g, scale)
    red_spec = pl.BlockSpec((8, d_pad), lambda i: (0, 0))
    dr, ds, db = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg, has_gr=has_gr),
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec, red_spec, red_spec],
        out_shape=[
            jax.ShapeDtypeStruct(r.shape, jnp.dtype(cfg.res_dtype)),
            jax.ShapeDtypeStruct((8, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((8, d_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, d_pad), jnp.float32),
            pltpu.VMEM((8, d_pad), jnp.float32),
        ],
        interpret=backend.interpret(),
    )(*args)
    # all 8 accumulator rows hold the same total; row 0 is the reduction
    return dr, ds[:1], db[:1]


# --- three custom_vjp compositions over the padded [N, Dp] core ----------
#
# The pad/slice to tile-aligned shapes lives OUTSIDE these functions (in
# fused_layernorm), so autodiff of the slice delivers zero cotangents for
# padded rows/columns automatically and the kernels never special-case them.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_plain(x, scale, bias, cfg):
    n, _ = _fwd_call(x, None, scale, bias, cfg)
    return n


def _ln_plain_fwd(x, scale, bias, cfg):
    n, _ = _fwd_call(x, None, scale, bias, cfg)
    return n, (x, scale)


def _ln_plain_bwd(cfg, res, g):
    x, scale = res
    dr, ds, db = _bwd_call(x, g, None, scale, cfg)
    return dr, ds.astype(scale.dtype), db.astype(scale.dtype)


_ln_plain.defvjp(_ln_plain_fwd, _ln_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ln_post(x, y, scale, bias, cfg):
    n, _ = _fwd_call(x, y, scale, bias, cfg)
    return n


def _ln_post_fwd(x, y, scale, bias, cfg):
    n, r = _fwd_call(x, y, scale, bias, cfg)
    return n, (r, scale)


def _ln_post_bwd(cfg, res, g):
    r, scale = res
    dr, ds, db = _bwd_call(r, g, None, scale, cfg)
    return dr, dr, ds.astype(scale.dtype), db.astype(scale.dtype)


_ln_post.defvjp(_ln_post_fwd, _ln_post_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ln_pre(x, y, scale, bias, cfg):
    return _fwd_call(x, y, scale, bias, cfg)


def _ln_pre_fwd(x, y, scale, bias, cfg):
    n, r = _fwd_call(x, y, scale, bias, cfg)
    return (n, r), (r, scale)


def _ln_pre_bwd(cfg, res, gs):
    r, scale = res
    g, gr = gs
    dr, ds, db = _bwd_call(r, g, gr, scale, cfg)
    return dr, dr, ds.astype(scale.dtype), db.astype(scale.dtype)


_ln_pre.defvjp(_ln_pre_fwd, _ln_pre_bwd)


def fused_layernorm(
    x,
    scale,
    bias=None,
    *,
    residual=None,
    eps: float = 1e-6,
    rms: bool = False,
    out_dtype=None,
    return_residual: bool | None = None,
    mesh=None,
    block_rows: int | None = None,
):
    """Fused (residual-add +) LayerNorm/RMSNorm over the last axis of ``x``.

    ``x``: ``[..., D]``; ``scale``/``bias``: ``[D]`` (``bias`` ignored when
    ``rms``). ``residual``: optional same-shape tensor; the kernel computes
    ``r = x + residual`` and normalizes ``r``. ``return_residual`` (default:
    ``residual is not None``) controls whether ``r`` is returned alongside
    the normed value — pre-norm blocks need it (the residual stream
    continues), post-norm blocks don't (one fewer HBM write).

    Returns ``normed`` or ``(normed, r)``. ``out_dtype`` defaults to
    ``x.dtype`` (pass the model's compute dtype to fold the bf16 cast into
    the kernel's write). Unaligned shapes are padded to the (8, 128) tile
    outside the kernel and masked/sliced — the mean/variance denominators
    always use the true ``D``.
    """
    if return_residual is None:
        return_residual = residual is not None
    if return_residual and residual is None:
        raise ValueError("return_residual=True needs a residual operand")
    d = x.shape[-1]
    if scale.shape != (d,):
        raise ValueError(f"scale shape {scale.shape} != ({d},)")
    if residual is not None and residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != x shape {x.shape}"
        )
    out_dtype = jnp.dtype(out_dtype or x.dtype)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from tpudist import mesh as mesh_lib

        dp = int(np.prod([
            mesh.shape[a] for a in (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)
        ]))
        # rows are batch-parallel: per-shard execution is exact. Indivisible
        # shapes (the batch-1 init trace) fall through unwrapped — same
        # rule as tpudist.ops.attention.
        if dp > 1 and x.shape[0] % dp == 0:
            spec = P((mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS),
                     *([None] * (x.ndim - 1)))
            rep = P(None)
            has_res = residual is not None
            fn = shard_map(
                lambda xs, rs, sc, bi: fused_layernorm(
                    xs, sc, bi, residual=rs if has_res else None, eps=eps,
                    rms=rms, out_dtype=out_dtype,
                    return_residual=return_residual, block_rows=block_rows,
                ),
                mesh=mesh,
                in_specs=(spec, spec if residual is not None else rep,
                          rep, rep),
                out_specs=(spec, spec) if return_residual else spec,
                check_vma=False,
            )
            return fn(
                x,
                residual if residual is not None else jnp.zeros((1,), x.dtype),
                scale,
                bias if bias is not None else jnp.zeros((d,), scale.dtype),
            )

    # flatten rows, pad to the (block_rows, 128) tile
    lead = x.shape[:-1]
    n = int(np.prod(lead)) if lead else 1
    d_pad = d + (-d % 128)
    bn = min(block_rows or _pick_block_rows(d_pad), 256)
    bn = max(8, bn - bn % 8)
    n_pad = n + (-n % bn)

    def prep(a):
        a2 = a.reshape(n, d)
        return jnp.pad(a2, ((0, n_pad - n), (0, d_pad - d)))

    x2 = prep(x)
    y2 = prep(residual) if residual is not None else None
    scale2 = jnp.pad(scale, (0, d_pad - d)).reshape(1, d_pad)
    bias_arr = bias if (bias is not None and not rms) else jnp.zeros(
        (d,), scale.dtype
    )
    bias2 = jnp.pad(bias_arr, (0, d_pad - d)).reshape(1, d_pad)

    cfg = _Cfg(
        eps=float(eps), d_true=d, rms=bool(rms),
        out_dtype=out_dtype.name, res_dtype=jnp.dtype(x.dtype).name,
        block_rows=bn,
    )
    if residual is None:
        n_out = _ln_plain(x2, scale2, bias2, cfg)
        r_out = None
    elif return_residual:
        n_out, r_out = _ln_pre(x2, y2, scale2, bias2, cfg)
    else:
        n_out = _ln_post(x2, y2, scale2, bias2, cfg)
        r_out = None

    def unprep(a):
        return a[:n, :d].reshape(*lead, d)

    if return_residual:
        return unprep(n_out), unprep(r_out)
    return unprep(n_out)


class FusedLayerNorm(nn.Module):
    """Drop-in fused counterpart of ``nn.LayerNorm`` / ``nn.RMSNorm``
    (``rms=True``) with an optional fused residual add.

    Declares the SAME params ("scale" [D]; "bias" [D] unless ``rms``) under
    whatever ``name=`` the caller gives it, so a model toggling between the
    flax modules and this one keeps an identical parameter tree — the
    property the ``fused_ln`` model knob (and every existing checkpoint)
    relies on.

    ``__call__(x, residual=None, return_residual=None)`` mirrors
    :func:`fused_layernorm`: plain norm, post-norm (``residual=`` with the
    default ``return_residual=False`` semantics when only the normed value
    is consumed), or pre-norm (``(normed, new_residual_stream)``).
    """

    epsilon: float = 1e-6
    dtype: Any = jnp.float32
    rms: bool = False
    mesh: Any = None

    @nn.compact
    def __call__(self, x, residual=None, return_residual: bool | None = None):
        d = x.shape[-1]
        scale = self.param(
            "scale", nn.initializers.ones_init(), (d,), jnp.float32
        )
        bias = None if self.rms else self.param(
            "bias", nn.initializers.zeros_init(), (d,), jnp.float32
        )
        return fused_layernorm(
            x, scale, bias, residual=residual, eps=self.epsilon,
            rms=self.rms, out_dtype=self.dtype,
            return_residual=return_residual, mesh=self.mesh,
        )
