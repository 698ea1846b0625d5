"""Pipeline parallelism — GPipe-style microbatch pipelining over the
``pipe`` mesh axis.

No reference counterpart (SURVEY.md §2.12: the reference's only strategy is
DDP, /root/reference/main.py:83); built so the framework scales depth past
one chip. TPU-native design (the "How to Scale Your Model" pipelining
recipe, not a torch-style stage-process scheduler):

- The model's repeated blocks are *stacked*: every param leaf carries a
  leading ``[n_layers, ...]`` dimension, sharded ``P('pipe')`` — stage ``i``
  of the mesh holds layers ``[i·L/S, (i+1)·L/S)`` in its HBM. There is no
  per-stage process or RPC; the whole pipeline is ONE jitted SPMD program.
- Inside :func:`pipeline_apply`, a ``shard_map`` over ``pipe`` runs the
  classic GPipe schedule as a ``lax.scan`` over ``num_micro + n_stages - 1``
  ticks: each tick every stage applies its local layers to the activation it
  holds, then ``lax.ppermute`` shifts activations one hop down the ring
  (stage 0 feeds in the next microbatch, the last stage banks its result).
  The hop is a neighbor exchange on ICI that XLA overlaps with the next
  tick's compute.
- Ramp-up/ramp-down ticks compute on garbage (the pipeline bubble,
  ``(S-1)/(M+S-1)`` of the schedule) — outputs are gated so garbage never
  escapes; choose ``num_micro >= 4·n_stages`` to amortize.
- Everything (``scan``, ``ppermute``, the gating ``where``) is
  differentiable, so ``jax.grad`` of a loss through :func:`pipeline_apply`
  yields the full backward pipeline, with XLA scheduling the reverse-order
  hops.

Two schedules share the forward ring (``pipeline_apply(schedule=...)``):

- ``"gpipe"`` (default, the original): plain reverse-mode through the
  forward scan. XLA's scan-backward saves EVERY tick's stage internals —
  all ``M`` microbatches' block activations are live when the backward
  begins, the classic GPipe memory profile.
- ``"1f1b"``: an explicit one-forward-one-backward backward schedule via
  ``jax.custom_vjp`` (the "Scaling Deep Learning Training with MPMD
  Pipeline Parallelism" recipe, PAPERS.md, expressed SPMD). The forward
  banks ONE tensor per (stage, microbatch) — the stage input, the remat
  floor — instead of the per-tick internals; the backward runs its own
  ``nm + S - 1``-tick scan flowing cotangents UP the ring
  (``ppermute`` with the reversed permutation), recomputing each stage's
  forward tick-by-tick via ``jax.vjp`` exactly when its cotangent
  arrives. Saved-activation memory per stage drops from ``M`` microbatches
  of full block internals (≈ ``(8+2·ffn_mult)·H`` per token,
  ``tpudist.memory``) to ``M`` stage INPUTS (``1·H`` per token) — the
  in-flight-internals profile of 1F1B — at the standard remat price of
  one extra forward inside the backward. The bubble fraction matches
  GPipe's (non-interleaved 1F1B's bubble is GPipe's; the interleave hook
  — splitting each stage's layer slice into virtual stages — is the
  schedule's natural extension and is left explicitly named here). From
  the outside the function is an ordinary differentiable apply:
  ``jax.grad`` composes, and per-block remat inside ``block_fn`` stacks
  as usual.

Composition with the other axes falls out of the mesh: the ``shard_map`` is
manual over ``pipe`` ONLY (``axis_names={'pipe'}``) — every other mesh axis
stays under GSPMD control inside the schedule. The microbatch dim rides its
``data``/``fsdp`` sharding (each stage computes on its data shard), and
stacked block params may additionally carry ``tensor`` shardings on their
trailing dims for Megatron TP-within-stage: GSPMD inserts the per-block
all-reduces from the param shardings exactly as it does for the unrolled
model, while ``ppermute`` hops activations down the ``pipe`` ring. The
``data x pipe x tensor`` composition is certified against the same-function
DP reference in ``__graft_entry__.dryrun_multichip`` and
``tests/test_pipeline.py``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.mesh import DATA_AXIS, FSDP_AXIS, PIPELINE_AXIS


def stacked_param_specs(stacked_params, *, axis: str = PIPELINE_AXIS):
    """PartitionSpec tree for stacked block params: leading (layer) dim
    sharded over ``pipe``, trailing dims replicated."""
    return jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params
    )


def stacked_param_shardings(stacked_params, mesh: Mesh, *, axis: str = PIPELINE_AXIS):
    """NamedSharding tree placing stacked block params layer-wise over the
    pipeline stages."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        stacked_param_specs(stacked_params, axis=axis),
        is_leaf=lambda s: isinstance(s, P),
    )


def _pipeline_local(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    params_local,
    x_local: jax.Array,
    *,
    axis_name: str,
):
    """Per-stage GPipe schedule — runs inside the pipe-manual ``shard_map``.

    ``params_local``: this stage's layer slice, leaves ``[L/S, ...]``
    (still sharded over auto axes, e.g. ``tensor``, which GSPMD handles).
    ``x_local``: all microbatches, ``[num_micro, micro_batch, ...]``
    (replicated over ``pipe``; ``data``-sharded on the microbatch dim under
    GSPMD). Returns the pipeline output for every microbatch, same shape as
    ``x_local`` (valid on every stage — the last stage's results are
    ``psum``-broadcast over the ``pipe`` axis).
    """
    n = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    nm = x_local.shape[0]
    is_first = stage == 0
    is_last = stage == n - 1
    perm = [(i, i + 1) for i in range(n - 1)]  # one hop down; stage 0 gets zeros
    stage_fn = _stage_fn(block_fn)

    def tick(carry, t):
        buf, outs = carry
        # stage 0 injects microbatch t (clamped past the end — garbage ticks
        # are gated below); later stages consume what the ring delivered
        mb = jax.lax.dynamic_index_in_dim(
            x_local, jnp.clip(t, 0, nm - 1), keepdims=False
        )
        inp = jnp.where(is_first, mb, buf)
        y = stage_fn(params_local, inp)
        # the last stage banks microbatch t-(n-1) once it's real
        out_idx = t - (n - 1)
        slot = jnp.clip(out_idx, 0, nm - 1)
        prev = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(is_last & (out_idx >= 0), y, prev), slot, 0
        )
        buf = jax.lax.ppermute(y, axis_name, perm)
        return (buf, outs), None

    # zero carries must match the per-shard compute's varying-manual-axes
    # type or scan rejects the carry signature (same trick as parallel/cp.py):
    # y varies over 'pipe' (axis_index feeds the gating), the zeros don't yet
    buf0 = _pcast_varying(jnp.zeros_like(x_local[0]), axis_name)
    outs0 = _pcast_varying(jnp.zeros_like(x_local), axis_name)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(nm + n - 1))
    # only the last stage holds real outputs; psum broadcasts them so the
    # loss/head can run stage-replicated (zeros elsewhere contribute nothing)
    return jax.lax.psum(outs, axis_name)


def _pcast_varying(tree, axis_name: str):
    """Promote carries to the varying-manual-axes type over ``axis_name``
    — scan rejects a carry whose type changes between the zeros and the
    per-shard compute. Leaves that already vary (``zeros_like`` of a
    varying value) are left alone: pcast refuses varying -> varying."""
    def cast(x):
        if axis_name in jax.typeof(x).vma:
            return x
        return jax.lax.pcast(x, (axis_name,), to="varying")

    return jax.tree_util.tree_map(cast, tree)


def _stage_fn(block_fn):
    """One stage's forward: its local layer slice as a lax.scan."""

    def stage(params_local, h):
        def layer(h, p):
            return block_fn(p, h), None

        h, _ = jax.lax.scan(layer, h, params_local)
        return h

    return stage


def _1f1b_fwd_local(
    block_fn, params_local, x_local, *, axis_name: str
):
    """1F1B forward — the same ring as the GPipe schedule, plus a bank of
    each (stage, microbatch) INPUT: the only residual the explicit
    backward needs (stage internals are recomputed tick-by-tick there).
    Returns ``(outs, banked)``; ``banked`` grows a leading stage dim so
    its out_spec can be ``P(pipe, ...)``."""
    n = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    nm = x_local.shape[0]
    is_first = stage == 0
    is_last = stage == n - 1
    perm = [(i, i + 1) for i in range(n - 1)]
    stage_fn = _stage_fn(block_fn)

    def tick(carry, t):
        buf, outs, banked = carry
        mb = jax.lax.dynamic_index_in_dim(
            x_local, jnp.clip(t, 0, nm - 1), keepdims=False
        )
        inp = jnp.where(is_first, mb, buf)
        # this stage consumes microbatch t - stage this tick; bank its
        # input at that slot (garbage ticks gated — the slot keeps its
        # previous value)
        in_idx = t - stage
        in_valid = (in_idx >= 0) & (in_idx < nm)
        in_slot = jnp.clip(in_idx, 0, nm - 1)
        prev_in = jax.lax.dynamic_index_in_dim(banked, in_slot, keepdims=False)
        banked = jax.lax.dynamic_update_index_in_dim(
            banked, jnp.where(in_valid, inp, prev_in), in_slot, 0
        )
        y = stage_fn(params_local, inp)
        out_idx = t - (n - 1)
        slot = jnp.clip(out_idx, 0, nm - 1)
        prev = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(is_last & (out_idx >= 0), y, prev), slot, 0
        )
        buf = jax.lax.ppermute(y, axis_name, perm)
        return (buf, outs, banked), None

    buf0 = _pcast_varying(jnp.zeros_like(x_local[0]), axis_name)
    outs0 = _pcast_varying(jnp.zeros_like(x_local), axis_name)
    banked0 = _pcast_varying(jnp.zeros_like(x_local), axis_name)
    (_, outs, banked), _ = jax.lax.scan(
        tick, (buf0, outs0, banked0), jnp.arange(nm + n - 1)
    )
    return jax.lax.psum(outs, axis_name), banked[None]


def _1f1b_bwd_local(
    block_fn, params_local, banked, g, *, axis_name: str
):
    """1F1B backward — cotangents enter at the LAST stage and hop UP the
    ring (the reversed permutation), one microbatch per tick per stage.
    Each tick recomputes the stage's forward from its banked input
    (``jax.vjp``) exactly when the cotangent arrives — the
    one-forward-one-backward interleave, ``nm + S - 1`` ticks total —
    accumulating the stage's param grads; stage 0 banks the input
    cotangents."""
    n = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    banked = banked[0]  # drop the stage dim the fwd out_spec added
    nm = g.shape[0]
    is_first = stage == 0
    is_last = stage == n - 1
    perm_up = [(i + 1, i) for i in range(n - 1)]
    stage_fn = _stage_fn(block_fn)

    def tick(carry, u):
        buf, dparams, dxs = carry
        # the cotangent for microbatch u enters the last stage at tick u
        # and reaches stage s after (n-1-s) hops
        mb = u - (n - 1 - stage)
        valid = (mb >= 0) & (mb < nm)
        slot = jnp.clip(mb, 0, nm - 1)
        g_mb = jax.lax.dynamic_index_in_dim(
            g, jnp.clip(u, 0, nm - 1), keepdims=False
        )
        ct = jnp.where(is_last, g_mb, buf)
        inp = jax.lax.dynamic_index_in_dim(banked, slot, keepdims=False)
        _, f_vjp = jax.vjp(stage_fn, params_local, inp)
        dp, dinp = f_vjp(ct)
        dparams = jax.tree_util.tree_map(
            lambda a, b: a + jnp.where(valid, b, jnp.zeros_like(b)),
            dparams, dp,
        )
        prev = jax.lax.dynamic_index_in_dim(dxs, slot, keepdims=False)
        dxs = jax.lax.dynamic_update_index_in_dim(
            dxs, jnp.where(is_first & valid, dinp, prev), slot, 0
        )
        buf = jax.lax.ppermute(dinp, axis_name, perm_up)
        return (buf, dparams, dxs), None

    buf0 = _pcast_varying(jnp.zeros_like(g[0]), axis_name)
    dparams0 = _pcast_varying(
        jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), params_local
        ),
        axis_name,
    )
    dxs0 = _pcast_varying(jnp.zeros_like(banked), axis_name)
    (_, dparams, dxs), _ = jax.lax.scan(
        tick, (buf0, dparams0, dxs0), jnp.arange(nm + n - 1)
    )
    # only stage 0 banked real input cotangents; psum broadcasts them so
    # dx comes back stage-replicated (zeros elsewhere contribute nothing)
    return dparams, jax.lax.psum(dxs, axis_name)


def _apply_1f1b(block_fn, stacked_params, xm, mesh, *, axis: str):
    """The custom_vjp wrapper pairing the two local schedules. Looks like
    an ordinary differentiable ``(params, x) -> out`` from the outside."""
    p_specs = stacked_param_specs(stacked_params, axis=axis)
    x_spec = P(*([None] * xm.ndim))
    banked_spec = P(axis, *([None] * xm.ndim))
    fwd_sm = shard_map(
        functools.partial(_1f1b_fwd_local, block_fn, axis_name=axis),
        mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, banked_spec),
        axis_names={axis},
    )
    bwd_sm = shard_map(
        functools.partial(_1f1b_bwd_local, block_fn, axis_name=axis),
        mesh=mesh,
        in_specs=(p_specs, banked_spec, x_spec),
        out_specs=(p_specs, x_spec),
        axis_names={axis},
    )

    @jax.custom_vjp
    def run(params, x):
        out, _ = fwd_sm(params, x)
        return out

    def run_fwd(params, x):
        out, banked = fwd_sm(params, x)
        return out, (params, banked)

    def run_bwd(res, ct):
        params, banked = res
        return bwd_sm(params, banked, ct)

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, xm)


SCHEDULES = ("gpipe", "1f1b")


def pipeline_apply(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    *,
    num_micro: int,
    axis: str = PIPELINE_AXIS,
    batch_axes=(DATA_AXIS, FSDP_AXIS),
    schedule: str = "gpipe",
):
    """Run ``x`` through the stacked blocks with GPipe pipelining.

    ``block_fn(layer_params, h) -> h`` applies ONE block (same input/output
    shape — residual blocks). ``stacked_params``: leaves ``[n_layers, ...]``;
    ``n_layers`` must divide by the mesh's ``pipe`` size. ``x``:
    ``[batch, ...]`` with ``batch`` divisible by ``num_micro`` (and the
    microbatch by the ``data`` sharding).

    The ``shard_map`` is manual over ``pipe`` only: the batch keeps its
    ``data`` sharding and the params their ``tensor`` sharding under GSPMD
    inside the schedule, so DP and Megatron-TP compose with the pipeline
    without hand-written collectives. ``batch_axes`` names the mesh axes
    the microbatch dim is constrained to (the ``with_sharding_constraint``
    below) — override it for a custom batch layout.

    ``schedule``: ``"gpipe"`` (default — reverse-mode through the forward
    scan, all ``num_micro`` microbatches' stage internals saved) or
    ``"1f1b"`` (explicit one-forward-one-backward backward ring via
    custom_vjp: forward banks only each stage's microbatch INPUTS,
    backward recomputes stage internals tick-by-tick — the module
    docstring carries the memory math). Both compute the identical
    function and gradients (an execution schedule, not a numerical
    change; ``tests/test_pipeline.py`` pins fwd+grad agreement).
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"schedule must be one of {SCHEDULES}, got {schedule!r}"
        )
    n_stages = mesh.shape[axis]
    layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if layers % n_stages:
        raise ValueError(f"{layers} layers not divisible by {n_stages} stages")
    b = x.shape[0]
    if b % num_micro:
        raise ValueError(f"batch {b} not divisible by num_micro {num_micro}")
    xm = x.reshape(num_micro, b // num_micro, *x.shape[1:])
    # pin the microbatch dim's data sharding (GSPMD would usually propagate
    # it from the embedding output, but the constraint makes the layout
    # deterministic: microbatch rows stay on the device that computes them)
    xm = jax.lax.with_sharding_constraint(
        xm, NamedSharding(mesh, P(None, batch_axes, *([None] * (x.ndim - 1))))
    )

    if schedule == "1f1b":
        out = _apply_1f1b(block_fn, stacked_params, xm, mesh, axis=axis)
        return out.reshape(b, *out.shape[2:])

    x_spec = P(*([None] * (x.ndim + 1)))
    fn = shard_map(
        functools.partial(_pipeline_local, block_fn, axis_name=axis),
        mesh=mesh,
        in_specs=(stacked_param_specs(stacked_params, axis=axis), x_spec),
        out_specs=x_spec,
        axis_names={axis},
    )
    out = fn(stacked_params, xm)
    return out.reshape(b, *out.shape[2:])
