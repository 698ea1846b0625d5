"""Optimizer construction: schedules, clipping, decay masks.

The reference's optimization surface is exactly ``Adam(lr=1e-3)``
(/root/reference/main.py:80) with no schedule, clipping, or weight decay.
:func:`make_optimizer` reproduces that as its default and adds the standard
knobs the BASELINE ladder's transformer configs want (warmup+cosine, global
-norm clipping, AdamW with norm/bias exclusion), all as one ``optax.chain``
that runs in-graph inside the compiled train step.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.mesh import DATA_AXIS, largest_divisible_spec


def warmup_cosine(
    peak_lr: float,
    *,
    warmup_steps: int,
    total_steps: int,
    end_lr_ratio: float = 0.0,
) -> optax.Schedule:
    """Linear warmup from 0 to ``peak_lr`` then cosine decay to
    ``peak_lr·end_lr_ratio`` at ``total_steps``."""
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=peak_lr,
        warmup_steps=warmup_steps,
        decay_steps=total_steps,
        end_value=peak_lr * end_lr_ratio,
    )


def run_schedule(
    peak_lr: float, *, total_steps: int, warmup_steps: int = 0
) -> optax.Schedule:
    """:func:`warmup_cosine` sized to a training run: one optimizer step
    per loader batch (grad accumulation does not reduce the count), warmup
    clamped to half the horizon so short runs still decay. The one home
    for this recipe — both CLI entry points use it."""
    total = max(total_steps, 1)
    return warmup_cosine(
        peak_lr, warmup_steps=min(warmup_steps, total // 2), total_steps=total
    )


def decay_mask(params) -> Any:
    """True for leaves that SHOULD receive weight decay: everything except
    1-D params (biases, LayerNorm/BatchNorm scales and offsets)."""
    return jax.tree_util.tree_map(lambda p: p.ndim > 1, params)


def _scoped(name: str, tx: optax.GradientTransformation):
    """``tx`` with its update under ``jax.named_scope(name)``: the device
    trace then says which link of the chain an op belongs to."""

    def update(updates, state, params=None):
        with jax.named_scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def make_optimizer(
    lr: float | optax.Schedule = 1e-3,
    *,
    optimizer: str = "adam",
    b1: float = 0.9,
    b2: float | None = None,  # None → 0.999 (adam/lamb), 0.99 (lion)
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    clip_norm: float | None = None,
    skip_nonfinite_updates: bool = False,
    fused: bool = False,
    compute_dtype: Any = None,
) -> optax.GradientTransformation:
    """One-stop optimizer factory.

    Defaults reproduce the reference exactly: ``make_optimizer()`` ≡
    ``Adam(lr=1e-3)`` (/root/reference/main.py:80). ``weight_decay > 0``
    switches to decoupled decay (AdamW) masked off 1-D params;
    ``clip_norm`` prepends global-norm clipping;
    ``skip_nonfinite_updates`` wraps the chain in
    :func:`tpudist.amp.skip_nonfinite`.

    ``fused=True`` builds :func:`fused_adamw` instead — the one-formula
    update, one sweep per leaf, with bit-compatible math
    (``optimizer="adam"`` only; clipping/decay/mask/skip all compose).
    ``compute_dtype`` (with ``fused``) keeps the in-state compute-precision
    param copy the fused train step's forward reads
    (``make_train_step(fused=...)``).
    """
    if b2 is None:
        b2 = 0.99 if optimizer == "lion" else 0.999
    if fused:
        if optimizer != "adam":
            raise ValueError(
                f"fused=True implements the adam/adamw update only, got "
                f"optimizer={optimizer!r}"
            )
        tx = fused_adamw(
            lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
            mask=decay_mask if weight_decay > 0.0 else None,
            clip_norm=clip_norm, compute_dtype=compute_dtype,
        )
        if skip_nonfinite_updates:
            from tpudist.amp import skip_nonfinite

            tx = skip_nonfinite(tx)
        return tx
    parts = []
    if clip_norm is not None:
        parts.append(_scoped("grad_clip", optax.clip_by_global_norm(clip_norm)))
    if optimizer == "adam":
        if weight_decay > 0.0:
            parts.append(
                optax.adamw(
                    lr, b1=b1, b2=b2, eps=eps,
                    weight_decay=weight_decay, mask=decay_mask,
                )
            )
        else:
            parts.append(optax.adam(lr, b1=b1, b2=b2, eps=eps))
    elif optimizer == "sgd":
        parts.append(optax.sgd(lr, momentum=b1))
        if weight_decay > 0.0:
            parts.insert(-1, optax.add_decayed_weights(weight_decay, decay_mask))
    elif optimizer == "lamb":
        # layerwise-adaptive Adam — the large-batch (32k+) training optimizer
        parts.append(
            optax.lamb(lr, b1=b1, b2=b2, eps=eps,
                       weight_decay=weight_decay, mask=decay_mask)
        )
    elif optimizer == "muon":
        # Newton-Schulz-orthogonalized momentum on hidden weight matrices
        # (the modded-nanogpt optimizer), Adam on everything else — all
        # in-graph, so the 5 NS iterations fuse into the compiled step.
        # Following the speedrun recipe, embeddings and classifier/LM heads
        # stay on Adam even when 2-D (orthogonalizing their updates hurts);
        # biases/norm scales (1-D) ride Adam too. Weight decay applies to
        # the Muon-routed matrices (decay_mask is all-true there); the
        # Adam-routed remainder is exactly the set the recipe leaves
        # undecayed. Multi-axis kernels are orthogonalized through their
        # matrix view via MuonDimensionNumbers — qkv [D,3,H,dh] as
        # D×(3·H·dh), out/o_proj [H,dh,D] as (H·dh)×D, convs [kh,kw,I,O] as
        # (kh·kw·I)×O — so attention and conv weights get real Muon, not a
        # silent Adam fallback.
        from optax.contrib import MuonDimensionNumbers

        # top-level param names that are embeddings or heads (wte/wpe/embed/
        # lm_head/embedding: GPT-2+Llama+ViT embeddings; head: ViT head;
        # Dense_0: ResNet's anonymous final classifier)
        _ADAM_TOP = ("wte", "wpe", "embed", "lm_head", "embedding",
                     "head", "Dense_0")

        def muon_dims(params):
            def label(path, p):
                # train-state bring-up runs tx.init on flax-BOXED params
                # (nn.Partitioned); updates run on raw arrays — unbox so the
                # routing (and optax's partition structure) agree between
                # the two, or the moment trees mismatch at the first step
                if hasattr(p, "unbox"):
                    p = p.unbox()
                top = getattr(path[0], "key", str(path[0]))
                leaf = getattr(path[-1], "key", str(path[-1]))
                # only weight kernels orthogonalize: a reshaped multi-dim
                # BIAS (e.g. qkv's [3,H,dh]) is still a vector per output
                if p.ndim < 2 or top in _ADAM_TOP or leaf != "kernel":
                    return None  # Adam
                names = {getattr(k, "key", str(k)) for k in path}
                if names & {"out", "o_proj"}:
                    # DenseGeneral contracting all leading axes → last
                    return MuonDimensionNumbers(
                        tuple(range(p.ndim - 1)), (p.ndim - 1,)
                    )
                if any("conv" in n.lower() for n in names):
                    # HWIO conv kernel: spatial+input reduce into output
                    return MuonDimensionNumbers(
                        tuple(range(p.ndim - 1)), (p.ndim - 1,)
                    )
                # Dense/DenseGeneral splitting the output (qkv [D,3,H,dh],
                # llama qkv [D,H,dh], plain 2-D): input first, rest output
                return MuonDimensionNumbers((0,), tuple(range(1, p.ndim)))

            return jax.tree_util.tree_map_with_path(
                label, params, is_leaf=lambda x: hasattr(x, "unbox")
            )

        parts.append(
            optax.contrib.muon(
                lr, eps=eps, weight_decay=weight_decay,
                weight_decay_mask=decay_mask,
                adam_b1=b1, adam_b2=b2,
                muon_weight_dimension_numbers=muon_dims,
            )
        )
    elif optimizer == "lion":
        # sign-momentum; half the optimizer HBM of Adam (one moment, and it
        # tolerates bf16) — useful when the Adam mirrors dominate memory
        parts.append(
            optax.lion(lr, b1=b1, b2=b2,
                       weight_decay=weight_decay, mask=decay_mask)
        )
    else:
        raise ValueError(f"unknown optimizer {optimizer!r}")
    tx = optax.chain(*parts) if len(parts) > 1 else parts[0]
    if skip_nonfinite_updates:
        from tpudist.amp import skip_nonfinite

        tx = skip_nonfinite(tx)
    return tx


# --------------------------------------------------------------------------
# ZeRO-1 / cross-replica weight-update sharding (arXiv:2004.13336)
# --------------------------------------------------------------------------
#
# Replicated Adam keeps TWO fp32 params-shaped mirrors (mu, nu) on every
# chip: at ~1B params that is ~8 GB of a 16 GB HBM before a single
# activation exists. But the update is elementwise — nothing about it needs
# the whole tree on one chip. shard_state() places each moment leaf sharded
# over the ``data`` axis; because the compiled train step's out_shardings
# then pin the moments sharded while the loss is still a global-batch mean,
# XLA lowers the gradient all-reduce into reduce-scatter → per-shard update
# → params all-gather (the automatic weight-update sharding of
# arXiv:2004.13336) inside the SAME single jit-compiled step, donated
# buffers and all. Per-chip optimizer state drops ~world_size×; step cost is
# the same collective bytes re-ordered (rs+ag ≡ all-reduce).


def _zero1_layout(shape, world: int, min_size: int):
    """How one state leaf is stored under ZeRO-1.

    Returns ``("replicate", None)`` (scalars / below ``min_size``),
    ``("shard", dim)`` (largest ``world``-divisible dim — the leaf keeps
    its natural shape and a ``PartitionSpec`` does the work), or
    ``("pad", cols)`` (no divisible dim: the leaf is stored flattened,
    zero-padded to ``world·cols`` and reshaped ``[world, cols]`` so the
    ``data`` axis shards its leading dim evenly — the paper's pad-and-
    reshape fallback, required because uneven shardings are rejected)."""
    if world <= 1 or len(shape) == 0 or math.prod(shape) < min_size:
        return ("replicate", None)
    spec = largest_divisible_spec(shape, DATA_AXIS, world, min_size=min_size)
    if any(s is not None for s in spec):
        return ("shard", next(i for i, s in enumerate(spec) if s is not None))
    return ("pad", -(-math.prod(shape) // world))


@dataclasses.dataclass(frozen=True)
class ShardedStateOptimizer:
    """ZeRO-1 wrapper around a ``GradientTransformation``.

    Duck-types the ``init``/``update`` surface every consumer in this repo
    uses (``create_train_state``, ``make_train_step``), and additionally
    exposes :meth:`state_shardings` so the state can be *born* sharded —
    ``create_train_state`` consults it instead of the (replicated)
    partitioning-metadata path, and the moments never materialize
    replicated even transiently.
    """

    init: Callable
    update: Callable
    state_shardings: Callable
    inner: optax.GradientTransformation
    mesh: Mesh
    axis: str


def shard_state(
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    axis: str = DATA_AXIS,
    min_size: int = 1024,
    skip_spec: "Callable[[tuple], bool] | None" = None,
) -> ShardedStateOptimizer:
    """Shard ``tx``'s state across the ``axis`` (default ``data``) replicas.

    The wrapped transformation stores every state leaf per
    :func:`_zero1_layout`; ``update`` restores the natural layout in-graph
    (nothing to do for a ``shard`` leaf; a gather, slice and reshape of the
    leaf for a ``pad`` one), runs the inner update, and re-stores
    — so the inner optimizer's math is untouched and the wrapped step is
    numerically the replicated step (``tests/test_sharded_optim.py`` holds
    it to that on an emulated mesh, non-divisible shapes included).

    Composition notes: apply OUTERMOST (around ``make_optimizer``'s whole
    chain, including ``skip_nonfinite``) so every params-shaped mirror in
    the chain shards. Params themselves stay wherever their own shardings
    put them (replicated for DP, ``fsdp``-sharded under ZeRO-3, Megatron
    specs under TP) — this wrapper touches optimizer STATE only, which is
    what makes it ZeRO-1. Leaves below ``min_size`` elements stay
    replicated (same threshold rule as ``fsdp_spec``).

    Explicit gradient reduction (``make_train_step(reduce=...)``,
    ``tpudist.parallel.dp``) composes from the OUTSIDE: the reducer hands
    this wrapper replicated, already-dequantized mean gradients, so XLA's
    weight-update-sharding decomposition inserts no second gradient
    collective — the update math runs on the sharded moments (the grads
    slice for free) and only the params-shaped update all-gather that
    ZeRO-1 always pays remains. Net wire bytes: ~0.5× fp32-AR for the int8
    grad reduction + 1× for the update all-gather, vs 2× for the implicit
    fp32 rs+ag (byte counts from the layouts; not measured on the chip).

    Checkpoints hold the stored (sharded/padded) layout; resuming needs the
    same world size, which the geometry guard in ``fit()`` already
    enforces.

    ``skip_spec(shape) -> bool`` exempts leaves from the ZeRO layout
    entirely (stored natural, classified ``replicate`` here) — the
    composition hook ``tpudist.parallel.plan.ParallelPlan.wrap_zero1``
    uses so leaves the plan scatters over ``fsdp`` are never flattened
    into the pad-and-reshape layout out from under their fsdp spec
    (sharded state either way, no double-sharding).
    """
    world = int(mesh.shape[axis])

    def _layout(shape):
        if skip_spec is not None and skip_spec(tuple(shape)):
            return ("replicate", None)
        return _zero1_layout(shape, world, min_size)

    def _unbox(tree):
        # create_train_state runs init on flax-BOXED params; the ZeRO
        # layout is pure shape math, so strip the metadata boxes (the
        # moments' placement comes from state_shardings, not nn.Partitioned)
        return jax.tree_util.tree_map(
            lambda p: p.unbox() if hasattr(p, "unbox") else p,
            tree,
            is_leaf=lambda x: hasattr(x, "unbox"),
        )

    def _inner_shapes(params):
        # the natural (unpadded) state layout, recomputed per call from
        # params — trace-time only under jit, so it costs nothing at run
        # time and needs no mutable closure state to survive restore
        return jax.eval_shape(tx.init, _unbox(params))

    def _store(leaf, ref):
        mode, cols = _layout(ref.shape)
        if mode != "pad":
            return leaf
        flat = jnp.ravel(leaf)
        return jnp.pad(flat, (0, world * cols - flat.size)).reshape(world, cols)

    def _restore(leaf, ref):
        mode, _ = _layout(ref.shape)
        if mode != "pad":
            return leaf
        return jnp.ravel(leaf)[: math.prod(ref.shape)].reshape(ref.shape)

    def init(params):
        params = _unbox(params)
        state = tx.init(params)
        return jax.tree_util.tree_map(
            _store, state, jax.eval_shape(tx.init, params)
        )

    def update(updates, state, params=None):
        if params is None:
            raise ValueError(
                "shard_state requires params at update time (the natural "
                "state layout is derived from them); tpudist's train step "
                "always passes them"
            )
        refs = _inner_shapes(params)
        natural = jax.tree_util.tree_map(_restore, state, refs)
        out, new_state = tx.update(updates, natural, params)
        return out, jax.tree_util.tree_map(_store, new_state, refs)

    def state_shardings(params):
        """Opt-state-shaped tree of NamedShardings for the STORED layout —
        feed to ``create_train_state``/``make_train_step`` (the former does
        so automatically when it sees this attribute)."""

        def sharding(ref):
            mode, _ = _layout(ref.shape)
            if mode == "replicate":
                return NamedSharding(mesh, P())
            if mode == "pad":
                return NamedSharding(mesh, P(axis, None))
            spec = largest_divisible_spec(
                ref.shape, axis, world, min_size=min_size
            )
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map(sharding, _inner_shapes(params))

    return ShardedStateOptimizer(
        init=init, update=update, state_shardings=state_shardings,
        inner=tx, mesh=mesh, axis=axis,
    )


# --------------------------------------------------------------------------
# Fused one-pass AdamW (tpudist.ops.fused_update)
# --------------------------------------------------------------------------
#
# The optax Adam chain (moment pass, bias correction, decayed weights, lr
# scale) plus the per-step fp32→bf16 param casts are params-sized passes of
# their own. fused_adamw writes the whole update as ONE elementwise
# expression per leaf — read (g, m, v, p), write (m', v', update, bf16
# compute copy) in the leaf's own layout, one loop fusion XLA also folds the
# clip's scale and apply_updates' add into — behind the standard optax
# (init, update) surface, so everything that composes with an optimizer here
# (ZeRO-1 shard_state, amp.skip_nonfinite, make_train_step's
# guard_nonfinite, telemetry's norms) composes with it unchanged.


class FusedAdamWState(NamedTuple):
    """State of :func:`fused_adamw`. ``compute`` is the params-shaped
    compute-dtype copy (written in the same sweep as the moments) or the
    EMPTY tuple when ``compute_dtype`` is off — zero
    leaves, so checkpoints/shardings of copy-less states carry nothing
    extra (the ``TrainState.comm_residual`` convention)."""

    count: Any
    mu: Any
    nu: Any
    compute: Any


@dataclasses.dataclass(frozen=True)
class FusedAdamW:
    """Duck-typed ``(init, update)`` optimizer running the one-pass fused
    AdamW update (:mod:`tpudist.ops.fused_update`). Built by
    :func:`fused_adamw`; detected through wrappers (``shard_state``,
    ``amp.skip_nonfinite`` — both expose ``inner``) by
    :func:`find_fused`."""

    init: Callable
    update: Callable
    compute_dtype: Any
    learning_rate: Any
    weight_decay: float


def fused_adamw(
    learning_rate: float | optax.Schedule = 1e-3,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    mask: Callable | None = None,
    clip_norm: float | None = None,
    compute_dtype: Any = None,
) -> FusedAdamW:
    """One-pass fused AdamW with an optax-compatible surface.

    Matches ``optax.adamw(lr, b1, b2, eps, weight_decay, mask=mask)``
    (and plain ``optax.adam`` at ``weight_decay=0``) BIT-FOR-BIT on the
    CPU — same division-form bias correction, same
    ``√v̂ + eps`` denominator, same decay-then-scale order
    (tests/test_fused_update.py pins it) — while collapsing the chain's
    per-transform tree passes into one HBM sweep per leaf.

    ``mask``: callable ``params → tree of static bools`` selecting decayed
    leaves (:func:`decay_mask`); ``None`` decays everything (optax's
    convention). ``clip_norm`` prepends ``clip_by_global_norm`` with
    optax's exact arithmetic (the global norm is one tree reduction; the
    scale rides into the sweep's read of ``g``). ``learning_rate`` may be
    a schedule (called on the pre-increment step count, optax's
    convention).

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) adds a params-shaped compute
    copy to the state, refreshed in the same sweep as the moments:
    ``compute = compute_dtype(p + update)``, bit-identical to
    casting the post-update master. ``make_train_step(fused=...)`` routes
    the next step's forward through it, which deletes the per-step
    fp32→bf16 cast of every parameter AND halves the forward's param-read
    bytes. Float leaves cast; non-float leaves ride along unchanged.

    ZeRO-1: apply ``tpudist.optim.shard_state`` AROUND this (the usual
    order) — the update math runs on the restored layout, and being plain
    elementwise XLA it partitions like any other op. Compiled for a
    described 2x2 mesh (tests/test_tpu_compile.py; not measured on the
    chip), a leaf stored sharded on a divisible dimension is one fusion at
    the shard's shape, followed by the all-gather of the new master that
    ZeRO-1 always pays; a leaf in the pad-and-reshape layout (no divisible
    dimension) is gathered, reshaped and re-stored around its update.
    """
    from tpudist.ops.fused_update import fused_leaf_update

    def _cast_copy(p):
        if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating):
            return jnp.asarray(p, compute_dtype)
        return p

    def init(params):
        # zeros_like/astype map over the INNER arrays of nn.Partitioned
        # boxes (they are pytree nodes), so a boxed init — what
        # create_train_state runs — yields moments/copy carrying the same
        # partitioning metadata as the params, like optax.adam's would
        zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
        compute = (
            jax.tree_util.tree_map(_cast_copy, params)
            if compute_dtype is not None else ()
        )
        return FusedAdamWState(
            count=jnp.zeros((), jnp.int32),
            mu=zeros(params), nu=zeros(params), compute=compute,
        )

    def update(grads, state, params=None):
        if params is None:
            raise ValueError(
                "fused_adamw requires params at update time (weight decay "
                "and the compute copy read them); tpudist's train step "
                "always passes them"
            )
        if clip_norm is not None:
            # optax.clip_by_global_norm's exact arithmetic (divide by the
            # norm, then scale by the max) so the fused chain stays
            # bit-compatible with the unfused one
            with jax.named_scope("grad_clip"):
                g_norm = optax.global_norm(grads)
                grads = jax.tree_util.tree_map(
                    lambda t: jnp.where(
                        g_norm < clip_norm, t,
                        (t / g_norm.astype(t.dtype)) * clip_norm,
                    ),
                    grads,
                )
        count_inc = optax.safe_int32_increment(state.count)
        b1c = 1.0 - b1 ** count_inc.astype(jnp.float32)
        b2c = 1.0 - b2 ** count_inc.astype(jnp.float32)
        lr_t = (
            learning_rate(state.count) if callable(learning_rate)
            else learning_rate
        )
        lr_t = jnp.asarray(lr_t, jnp.float32)

        mask_tree = mask(params) if mask is not None else None
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)
        wd_leaves = (
            treedef.flatten_up_to(mask_tree) if mask_tree is not None
            else [True] * len(p_leaves)
        )
        results = [
            fused_leaf_update(
                g, m, v, p, lr_t, b1c, b2c, b1=b1, b2=b2, eps=eps,
                wd=weight_decay if decayed else 0.0,
                compute_dtype=(
                    compute_dtype if compute_dtype is not None
                    and jnp.issubdtype(p.dtype, jnp.floating) else None
                ),
            )
            for g, m, v, p, decayed in zip(
                g_leaves, m_leaves, v_leaves, p_leaves, wd_leaves
            )
        ]
        updates = treedef.unflatten([r[0] for r in results])
        new_state = FusedAdamWState(
            count=count_inc,
            mu=treedef.unflatten([r[1] for r in results]),
            nu=treedef.unflatten([r[2] for r in results]),
            compute=(
                treedef.unflatten([
                    r[3] if r[3] is not None else p
                    for r, p in zip(results, p_leaves)
                ])
                if compute_dtype is not None else ()
            ),
        )
        return updates, new_state

    return FusedAdamW(
        init=init, update=update, compute_dtype=compute_dtype,
        learning_rate=learning_rate, weight_decay=weight_decay,
    )


def find_fused(tx) -> FusedAdamW | None:
    """The :class:`FusedAdamW` inside ``tx``, walking the wrappers that
    expose ``inner`` (:class:`ShardedStateOptimizer`,
    ``amp.SkipNonfinite``) — or ``None``. An ``optax.chain`` hides its
    members, so a chained fused optimizer keeps the one-pass update but is
    invisible to the compute-copy wiring; build clipping into
    :func:`fused_adamw` (``clip_norm=``) instead of chaining."""
    seen = 0
    while tx is not None and seen < 8:
        if isinstance(tx, FusedAdamW):
            return tx
        tx = getattr(tx, "inner", None)
        seen += 1
    return None


def _fused_state_in(opt_state):
    from tpudist.amp import is_skip_state

    if isinstance(opt_state, FusedAdamWState):
        return opt_state
    if is_skip_state(opt_state):
        return _fused_state_in(opt_state[0])
    if isinstance(opt_state, (tuple, list)) and not hasattr(
        opt_state, "_fields"
    ):
        for el in opt_state:
            found = _fused_state_in(el)
            if found is not None:
                return found
    return None


def _copy_matches(compute, params) -> bool:
    c_leaves = jax.tree_util.tree_leaves(compute)
    p_leaves = jax.tree_util.tree_leaves(params)
    if not c_leaves or len(c_leaves) != len(p_leaves):
        return False
    if jax.tree_util.tree_structure(compute) != jax.tree_util.tree_structure(
        params
    ):
        return False
    return all(
        getattr(c, "shape", None) == getattr(p, "shape", None)
        for c, p in zip(c_leaves, p_leaves)
    )


def fused_compute_params(opt_state, params):
    """The compute-dtype param copy carried by a :func:`fused_adamw` state,
    or ``None`` when absent/unusable. Usable means: reachable through the
    known wrappers AND params-shaped leaf-for-leaf — under ZeRO-1 a
    pad-and-reshape-stored leaf breaks the shape match and the whole copy
    is declined (the forward then reads the masters; a stale or re-laid-out
    copy can never be silently used). Static structure/shape checks only —
    free at trace time."""
    st = _fused_state_in(opt_state)
    if st is None:
        return None
    if not _copy_matches(st.compute, params):
        return None
    return st.compute


def refresh_fused_compute(opt_state, params):
    """Re-cast the fused compute copy from ``params`` wherever it is
    reachable and params-shaped — fit()'s warm-start hook (``init_params``
    replaces the masters AFTER ``tx.init`` built the copy; without the
    refresh the copy would describe the discarded random init). States
    without a usable copy pass through unchanged, which is safe: the same
    shape predicate gates :func:`fused_compute_params`, so an unrefreshed
    copy is also an unused one."""
    if isinstance(opt_state, FusedAdamWState):
        if not _copy_matches(opt_state.compute, params):
            return opt_state
        fresh = jax.tree_util.tree_map(
            lambda p, c: jnp.asarray(p, c.dtype), params, opt_state.compute
        )
        return opt_state._replace(compute=fresh)
    from tpudist.amp import is_skip_state

    if is_skip_state(opt_state):
        inner = refresh_fused_compute(opt_state[0], params)
        return opt_state if inner is opt_state[0] else (inner, opt_state[1])
    if isinstance(opt_state, tuple) and not hasattr(opt_state, "_fields"):
        refreshed = tuple(refresh_fused_compute(el, params) for el in opt_state)
        if all(a is b for a, b in zip(refreshed, opt_state)):
            return opt_state  # nothing fused inside: identity, not a rebuild
        return refreshed
    return opt_state
