"""The compiled training step and epoch driver.

This is where the reference's layers L2–L5 collapse (SURVEY.md §3.4): the
per-step sequence ``.cuda() → forward (SyncBN all-gathers) → loss →
zero_grad → backward (DDP bucketed async all-reduce) → opt.step() →
reduce_loss`` (/root/reference/main.py:98-105) becomes ONE jit-compiled SPMD
program over the device mesh:

- params are replicated, the batch is sharded over the ``data`` axis;
- the loss is the mean over the *global* logical batch, so ``jax.grad``
  produces already-all-reduced gradients — XLA inserts the ICI/DCN psum and
  overlaps it with backward compute, which *is* the TPU-native equivalent of
  DDP's C++ Reducer bucketing (SURVEY.md §2.5);
- batch-norm statistics are computed over the global batch inside the same
  program (the SyncBatchNorm equivalent, §2.8);
- the Adam update (optax) runs in-graph (§2.9);
- the only host↔device traffic is the batch in and the scalar loss out.

Init-sync: DDP broadcasts rank-0 params at wrap time (main.py:83);
:func:`create_train_state` instead initializes from an explicit PRNG seed
inside a compiled program with replicated output sharding, so every process
holds bit-identical params by construction.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax import struct
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist import mesh as mesh_lib
from tpudist.metrics import MetricsLogger
from tpudist.profiling import WindowedProfiler


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any  # empty FrozenDict for models without BN
    opt_state: Any
    # error-feedback residual of the explicit quantized gradient reduction
    # (tpudist.parallel.dp) — [world, n_buckets, bucket_size] fp32 sharded
    # over `data`, attached by GradReducer.attach_residual. None (the empty
    # pytree: zero leaves, so checkpoints and shardings of residual-free
    # states are untouched) everywhere else.
    comm_residual: Any = None


def cross_entropy_loss(logits, labels):
    """Softmax CE on logits vs int labels — the reference's
    ``CrossEntropyLoss`` (/root/reference/main.py:79,101)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def smoothed_cross_entropy(smoothing: float):
    """CE against smoothed targets — the standard ImageNet recipe knob
    (ε=0.1 for the 76%-top-1 ResNet-50 schedule); ε=0 reduces exactly to
    :func:`cross_entropy_loss`."""

    def loss_fn(logits, labels):
        n = logits.shape[-1]
        targets = optax.smooth_labels(jax.nn.one_hot(labels, n), smoothing)
        return optax.softmax_cross_entropy(logits, targets).mean()

    return loss_fn


def lm_loss(logits, tokens):
    """Next-token CE for the GPT-2 config: predict tokens[1:] from tokens[:-1]."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]
    ).mean()


def create_train_state(
    model,
    rng: jax.Array | int,
    sample_input,
    tx: optax.GradientTransformation,
    mesh: Mesh | None = None,
    plan=None,
) -> TrainState:
    """Initialize params/opt state on the mesh.

    Placement follows the model's ``nn.with_partitioning`` metadata:
    metadata-free models (ResNet — the DDP model) come out fully
    replicated; annotated models (GPT-2's and ViT's Megatron specs, inert
    on a size-1 ``tensor`` axis) come out sharded, with the optimizer's
    params-shaped mirrors sharded to match.

    Same seed on every process ⇒ bit-identical params — the TPU-native
    init-sync replacing DDP's rank-0 broadcast (SURVEY.md §2.5).

    A ZeRO-1 optimizer (``tpudist.optim.shard_state`` — it advertises
    ``state_shardings``) overrides the metadata-derived (replicated)
    opt-state placement with its own data-axis shardings, so the Adam
    mirrors are BORN sharded inside this one compiled init — they never
    materialize replicated, not even transiently, which is what lets a
    ~1B-param state fit 16 GB HBM at bring-up.

    A ``plan`` (:class:`tpudist.parallel.plan.ParallelPlan`) resolves the
    whole composed placement instead: Megatron/pipe metadata kept, every
    still-replicated leaf (optimizer mirrors included) scattered over
    ``fsdp``, ZeRO-1's data-axis layout overlaid where the plan skipped —
    the state is born 3-D/4-D sharded in the same one compiled init.
    """
    if isinstance(rng, int):
        rng = jax.random.key(rng)

    def _boxed():
        # params stay in their nn.Partitioned boxes through tx.init, so the
        # optimizer's params-shaped mirrors (adam mu/nu) carry the same
        # partitioning metadata — the sharding tree below covers them too
        variables = model.init(rng, sample_input, train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", FrozenDict())
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=tx.init(params),
        )

    def _init():
        return nn.meta.unbox(_boxed())

    if plan is not None:
        if mesh is not None and mesh != plan.mesh:
            raise ValueError(
                "create_train_state got both a mesh and a plan with a "
                "DIFFERENT mesh — build the plan over the run's mesh "
                "(ParallelPlan(mesh)) or drop the mesh argument"
            )
        shardings = plan.state_shardings(_boxed, tx)
        return jax.jit(_init, out_shardings=shardings)()
    if mesh is None:
        return jax.jit(_init)()
    shardings = state_shardings_from_meta(_boxed, mesh)
    if hasattr(tx, "state_shardings"):
        # ZeRO-1: the optimizer owns its state's placement
        params_shapes = jax.eval_shape(_boxed).params
        shardings = shardings.replace(
            opt_state=tx.state_shardings(params_shapes)
        )
    return jax.jit(_init, out_shardings=shardings)()


def state_shardings_from_meta(boxed_init_fn, mesh: Mesh):
    """TrainState-shaped tree of NamedShardings from ``nn.with_partitioning``
    metadata (unannotated leaves → replicated). The tree matches the
    *unboxed* state, which is what ``nn.get_partition_spec`` returns."""
    specs = nn.get_partition_spec(jax.eval_shape(boxed_init_fn))
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def state_shardings_of(state: TrainState):
    """The concrete sharding of every leaf of a placed TrainState — pass to
    :func:`make_train_step` as ``state_sharding`` for TP/FSDP runs."""
    return jax.tree_util.tree_map(lambda x: x.sharding, state)



def _apply_input_transform(transform, inputs, batch, step=None):
    """The one home for the input_transform calling convention: plain
    transforms receive the inputs; transforms declaring ``wants_batch``
    also receive the whole batch dict — the hook for device-resident
    operands (e.g. DeviceCachedLoader's "_cache") that must arrive as REAL
    jit arguments. A closure-captured jax.Array would be lowered as an HLO
    literal: a literal the size of a dataset bloats every compile and keeps
    a second copy in device memory.

    Transforms declaring ``wants_step`` additionally receive the step
    counter (last positional arg) — the randomness key for in-graph
    augmentation (``tpudist.data.transforms.device_random_crop_flip``).
    Eval paths pass ``step=None`` and refuse such transforms: augmentation
    has no business in an eval pass, and scoring through one silently
    would corrupt the measurement."""
    if transform is None:
        return inputs
    wants_step = getattr(transform, "wants_step", False)
    if wants_step and step is None:
        raise ValueError(
            "input_transform declares wants_step (an augmenting transform) "
            "but this is an eval path — evaluate with the normalization "
            "transform only"
        )
    args = [inputs]
    if getattr(transform, "wants_batch", False):
        args.append(batch)
    if wants_step:
        args.append(step)
    return transform(*args)


def resolve_fused(fused, model, tx) -> frozenset:
    """Resolve a ``fused=`` request against what the model/optimizer
    support — the ONE mapping both :func:`make_train_step` and ``fit``
    (via the step's ``fused_info``) rely on.

    ``None``/``False``/``"none"`` → nothing (programs bit-identical to the
    pre-fusion rounds). ``"auto"`` → every fusion that is AVAILABLE: the
    Pallas LN path when the model exposes a ``fused_ln`` knob (the
    GPT-2/Llama/BERT/ViT families), the fused-optimizer forward wiring
    when ``tx`` carries a :func:`tpudist.optim.fused_adamw` (directly or
    under ``shard_state``/``skip_nonfinite``). ``"ln"``/``"optimizer"``
    demand exactly one side and raise when unsupported — a request that
    silently did nothing would be a benchmark lying about its
    configuration. ``"all"`` demands both.
    """
    if not fused or fused == "none":
        return frozenset()
    if fused is True:
        fused = "auto"
    if fused not in ("auto", "ln", "optimizer", "all"):
        raise ValueError(
            f"fused={fused!r}: expected None/'none'/'auto'/'ln'/"
            "'optimizer'/'all'"
        )
    from tpudist.optim import find_fused

    ln_ok = hasattr(model, "fused_ln")
    opt_ok = find_fused(tx) is not None
    out = set()
    if fused in ("ln", "all") or (fused == "auto" and ln_ok):
        if not ln_ok:
            raise ValueError(
                f"fused={fused!r} requests the fused LN path but "
                f"{type(model).__name__} has no fused_ln knob (the "
                "GPT-2/Llama/BERT/ViT families carry it)"
            )
        out.add("ln")
    if fused in ("optimizer", "all") or (fused == "auto" and opt_ok):
        if not opt_ok:
            raise ValueError(
                f"fused={fused!r} requests the fused-optimizer path but "
                "the optimizer chain carries no tpudist.optim.fused_adamw "
                "(build one via make_optimizer(fused=True) or "
                "optim.fused_adamw; shard_state/skip_nonfinite wrappers "
                "are looked through)"
            )
        out.add("optimizer")
    return frozenset(out)


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    loss_fn: Callable = cross_entropy_loss,
    input_key: str = "image",
    label_key: str = "label",
    grad_accum: int = 1,
    remat: bool | str = False,
    state_sharding=None,
    batch_spec: Mapping[str, P] | None = None,
    forward_loss: Callable | None = None,
    dropout_seed: int = 0,
    input_transform: Callable | None = None,
    telemetry: bool = False,
    guard_nonfinite: bool = False,
    reduce: Any = "none",
    reduce_bucket_size: int | None = None,
    error_feedback: bool = True,
    fused: str | bool | None = None,
    plan=None,
):
    """Build the jit-compiled (state, batch) → (state, metrics) step.

    ``reduce`` selects the gradient-reduction path (``tpudist.parallel.dp``):
    ``"none"`` (default) keeps the implicit XLA psum — optimal on ICI;
    ``"bucketed"`` computes per-replica gradients inside a ``shard_map`` and
    all-reduces them explicitly as fixed-size fp32 buckets (the DDP-Reducer
    structure, exact); ``"quantized"`` additionally ships int8 on the wire —
    per-bucket scales, stochastic rounding, fp32 master accumulation, and an
    error-feedback residual carried in ``state.comm_residual`` (attach once
    via ``step.grad_reducer.attach_residual(state)``; ``fit()`` does it) so
    convergence tracks fp32 within tolerance; ``"auto"`` picks quantized on
    a multi-slice (DCN-crossing) attach and none otherwise. A prebuilt
    ``dp.GradReducer`` is accepted verbatim. With ``grad_accum > 1`` the
    quantized+error-feedback reduction is double-buffered inside the
    accumulation scan: microbatch ``i-1``'s buckets reduce while microbatch
    ``i``'s forward/backward runs (residual-free configs accumulate locally
    and reduce once after the scan).
    The explicit path is pure-DP (replicated params, no ``batch_spec``, no
    device-resident ``"_"`` operands — enforced loudly) and composes with
    ZeRO-1 ``shard_opt_state``, ``amp.skip_nonfinite`` and
    ``guard_nonfinite`` (both see the already-dequantized gradients; a
    skipped step never poisons the residual). ``reduce_bucket_size``
    overrides the bucket size in ELEMENTS (default
    ``tpudist.comm.DEFAULT_BUCKET_ELEMS``); ``error_feedback=False`` drops
    the residual (pure unbiased quantization noise — the A/B knob the
    convergence tests pin down). The reducer is exposed as
    ``step.grad_reducer`` (``None`` on the implicit path) and the wire
    accounting as ``step.comm_stats(params)``.

    ``telemetry=True`` folds the in-step health metrics into the compiled
    program (tpudist.telemetry): global grad-norm, param-norm (pre-update),
    update-norm, and the non-finite gradient element count ride the metrics
    pytree out — a handful of reductions XLA fuses into the existing
    backward/psum path (their share of a step: not measured on the chip).
    ``guard_nonfinite=True`` additionally
    SKIPS a poisoned update inside the same program: when the loss or any
    gradient is non-finite, params/opt-state/batch-stats keep their
    pre-step values (the step counter still advances, so data position and
    resume math stay exact) and ``metrics["update_skipped"]`` reports 1.
    The in-graph skip is what makes the host-side NaN sentry's event
    "after the fact" harmless — by the time the host sees the anomaly the
    state was never corrupted. Both default off: the step's programs (and
    HLO) are bit-identical to previous rounds when unused.

    ``input_transform``: optional in-graph function applied to
    ``batch[input_key]`` before the model — e.g.
    :func:`tpudist.data.transforms.device_normalize`, which lets the loader
    ship uint8 pixels (4× less host→device traffic and host float work than
    staging float32) and runs the ToTensor+normalize affine on device, where
    XLA fuses it into the first conv's input read.

    ``forward_loss``: optional fused ``(params, batch_stats, batch) →
    (loss, new_stats)`` replacing the default logits+loss_fn composition —
    e.g. :func:`tpudist.models.gpt2.chunked_lm_forward`, which keeps the LM
    head's logits from ever materializing (live logits [B, chunk, V]) and
    takes the head's gradient in the same sweep that makes them: a
    ``jax.custom_vjp``, so such a loss is for reverse mode only, and under
    a whole-forward ``remat`` its sweep is what the backward runs again.

    ``dropout_seed`` keys the per-step dropout stream for models whose
    ``dropout`` field is > 0 (the key is folded with the step counter, so
    masks differ every step but agree across replicas/processes).

    ``state_sharding``: a TrainState-shaped pytree of NamedShardings (see
    :func:`state_shardings_of`) for TP/FSDP runs where params are NOT fully
    replicated; defaults to the replicated DDP model.

    ``plan`` (:class:`tpudist.parallel.plan.ParallelPlan`): the composed
    3-D/4-D configuration this step runs under. The plan does not replace
    ``state_sharding`` (build the state with ``create_train_state(...,
    plan=plan)`` and pass ``state_shardings_of(state)`` — ``fit(plan=...)``
    does both); it validates the composition loudly instead: the mesh must
    match, the state must arrive plan-sharded, and an explicit ``reduce``
    request on a model-sharded plan raises naming the fix (the explicit
    reducer reduces over ``data`` only; composed plans keep the implicit
    GSPMD reduction). Carried as ``step.plan`` for telemetry's
    attribution.

    ``batch_spec``: per-key PartitionSpec overrides for the staged batch —
    e.g. ``{"tokens": P(('data','fsdp'), 'seq')}`` shards the sequence dim
    over the ``seq`` axis for context-parallel (ring/Ulysses) models. Keys
    not listed keep the default batch-dim-over-data sharding. With
    ``grad_accum > 1`` the spec must include the leading microbatch dim.

    ``grad_accum > 1`` scans over ``grad_accum`` microbatches
    (batch leading dims ``[grad_accum, micro_batch, ...]``, microbatch dim
    sharded over ``data``) accumulating gradients in fp32 — the
    BASELINE.json config-5 extension; XLA still emits a single fused program
    with one logical all-reduce per step.

    ``remat`` selects an activation-rematerialization policy by name
    (:mod:`tpudist.remat`): ``"none"``, ``"full"``, ``"dots_saveable"``
    (save MXU outputs, recompute the elementwise tail — usually the best
    TPU trade), ``"save_nothing"``; the legacy bool still works
    (``True`` ≡ ``"full"``). This wraps the WHOLE forward; per-block
    checkpointing — the stronger memory lever for deep models — is the
    model zoo's ``remat_policy`` field, same policy names.

    ``fused`` selects the step-fusion layer for the elementwise tail
    between the GEMMs (the cells run ``"all"``, PERF.md §4): ``"ln"``
    clones the model with
    ``fused_ln=True`` (the Pallas fused residual-add+LayerNorm kernel in
    every block, ``tpudist.ops.layernorm``), ``"optimizer"`` routes the
    forward through the compute-dtype param copy a
    ``tpudist.optim.fused_adamw`` keeps in its state (deleting the
    per-step fp32→bf16 param casts; gradients then arrive in the compute
    dtype — the standard mixed-precision trade, exact when the compute
    dtype IS fp32), ``"all"`` both, ``"auto"`` whatever the model/tx
    support, ``None`` (default) nothing — programs bit-identical to
    before. The resolved set rides ``step.fused`` / ``step.fused_info``
    (fit's telemetry ``fusion`` row). With a custom ``forward_loss``,
    ``"ln"`` needs the loss builder's ``rebuild`` hook
    (``chunked_lm_forward`` carries one) so the fused clone actually
    reaches the forward.
    """
    batch_axes = (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)

    if plan is not None:
        # composed-parallelism validation (tpudist.parallel.plan): the
        # plan must describe THIS mesh, the state must arrive with the
        # plan's shardings (never the replicated default), and an explicit
        # reduce request routes — data-axis-only, with the fix named —
        # before the reducer's own narrower refusals fire
        if plan.mesh != mesh:
            raise ValueError(
                f"make_train_step got plan {plan.describe()} over a "
                "different mesh than the step's — build the plan over the "
                "run's mesh (ParallelPlan(mesh))"
            )
        plan.validate_state_sharding(state_sharding)
        plan.validate_reduce(
            reduce if isinstance(reduce, str) or reduce is None
            else getattr(reduce, "method", None)
        )

    from tpudist.parallel import dp as dp_mod

    reducer = dp_mod.make_reducer(
        reduce, mesh,
        **({} if reduce_bucket_size is None
           else {"bucket_size": reduce_bucket_size}),
        error_feedback=error_feedback, seed=dropout_seed,
    )
    if reducer is not None:
        if batch_spec is not None:
            raise ValueError(
                "reduce=... is pure-DP and incompatible with batch_spec "
                "overrides (context/sequence-parallel models keep the "
                "implicit XLA reduction)"
            )
        if state_sharding is not None:
            def _sharded_for_real(s):
                # Megatron annotations on size-1 axes (the model zoo's
                # inert TP specs) are replication in fact — only a spec
                # naming an axis with >1 devices actually splits params
                spec = getattr(s, "spec", P())
                for part in spec:
                    names = part if isinstance(part, tuple) else (part,)
                    for name in names:
                        if name is not None and mesh.shape[name] > 1:
                            return True
                return False

            bad = [
                s.spec for s in jax.tree_util.tree_leaves(
                    getattr(state_sharding, "params", state_sharding)
                )
                if _sharded_for_real(s)
            ]
            if bad:
                raise ValueError(
                    "reduce=... requires fully-replicated params (the "
                    "explicit bucketed/quantized reducer reduces over the "
                    f"'data' axis only); got param shardings {bad[:3]} — "
                    "keep reduce='none' (GSPMD reduce-scatters over "
                    "fsdp/tensor in-graph), or move those devices to the "
                    "data axis (make_train_step(plan=ParallelPlan.build("
                    "data=-1)) / MeshConfig(data=-1)) before asking for "
                    "the explicit wire format"
                )

    fused_set = resolve_fused(fused, model, tx)
    if ("ln" in fused_set and not getattr(model, "fused_ln", False)
            and forward_loss is not None
            and getattr(forward_loss, "rebuild", None) is None):
        # a custom forward_loss closure captured the UNFUSED model and
        # exposes no way to re-close over the fused clone. Under "auto"
        # (best-effort by contract) the LN side simply isn't available —
        # decline it with a warning; an explicit request must not
        # silently run unfused, so it raises.
        if fused in ("auto", True):
            import warnings

            warnings.warn(
                "fused='auto': declining LN fusion — forward_loss has no "
                ".rebuild(model) hook, so the fused model clone cannot "
                "reach the forward (chunked_lm_forward carries the hook; "
                "or build forward_loss from a fused_ln=True model)"
            )
            fused_set = fused_set - {"ln"}
        else:
            raise ValueError(
                "fused LN needs the forward to run the CLONED model, "
                "but this forward_loss closure captured the unfused "
                "one and exposes no .rebuild(model) hook — build it "
                "from a fused_ln=True model yourself, or use "
                "chunked_lm_forward (which carries the hook)"
            )
    if "ln" in fused_set and not getattr(model, "fused_ln", False):
        # same params, same names — fused_ln only swaps the LN modules for
        # their kernel twins, so the state built from the unfused model
        # drives this clone unchanged
        model = model.clone(fused_ln=True)
        if forward_loss is not None:
            forward_loss = forward_loss.rebuild(model)
    if "optimizer" in fused_set:
        from tpudist.optim import find_fused as _find_fused

        _fused_tx = _find_fused(tx)
        fused_info = {
            "ln": "ln" in fused_set,
            "optimizer": True,
            "compute_dtype": (
                None if _fused_tx.compute_dtype is None
                else jnp.dtype(_fused_tx.compute_dtype).name
            ),
        }
    else:
        fused_info = {
            "ln": "ln" in fused_set, "optimizer": False,
            "compute_dtype": None,
        }

    # models that sow auxiliary losses (e.g. MoE load-balance,
    # parallel/ep.py) declare it via ``has_aux_loss``; duck-typed models
    # without the attribute keep the plain (non-mutable) apply path
    wants_aux = bool(getattr(model, "has_aux_loss", False))
    # MoE router observability (docs/OBSERVABILITY.md §1): when the model
    # sows router stats (tpudist.parallel.ep's 'moe_stats' collection — a
    # model with an aux loss does under telemetry, one that says so with
    # ``sows_moe_stats`` always: a few scalars a layer), the forward also
    # returns them and they ride the step metrics into the telemetry "moe"
    # rows. On the plain single-pass path, and under a ``forward_loss``
    # that can return them (``with_moe_stats``: the chunked-CE forward):
    # the explicit reducer's grad_fn contract and the micro-scan's carry
    # both fix the forward's return shape to (loss, stats), and router
    # stats are a health signal, not gradient math — the restricted paths
    # simply don't emit the rows.
    moe_telemetry = bool(
        ((telemetry and wants_aux)
         or getattr(model, "sows_moe_stats", False))
        and reducer is None and grad_accum == 1
        and (forward_loss is None
             or hasattr(forward_loss, "with_moe_stats"))
    )
    if moe_telemetry and forward_loss is not None:
        forward_loss = forward_loss.with_moe_stats()
    # models with a dropout field > 0 need a 'dropout' rng each step; the
    # key is derived from the step counter so every step (and every process,
    # identically — the mask must agree across replicas) draws fresh noise.
    # router_jitter (MoE router-input noise, parallel/ep.py) rides the same
    # stream under the same derivation.
    dropout_rate = float(getattr(model, "dropout", 0.0) or 0.0)
    jitter_rate = float(getattr(model, "router_jitter", 0.0) or 0.0)
    dropout_base = jax.random.key(dropout_seed)

    def _moe_metrics(sown) -> dict:
        """Sown 'moe_stats' tree → flat metric keys: the dict path joined
        with '/', the MoEMlp module's own 'moe' segment elided, prefixed
        'moe/' — e.g. ``{'h_1': {'moe': {'load': (arr,)}}}`` →
        ``{'moe/h_1/load': arr}``."""
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(sown)[0]:
            segs = [
                p.key for p in path if hasattr(p, "key") and p.key != "moe"
            ]
            out["moe/" + "/".join(segs)] = leaf
        return out

    def forward(params, batch_stats, batch, step):
        variables = {"params": params, "batch_stats": batch_stats}
        has_stats = len(batch_stats) > 0
        inputs = _apply_input_transform(
            input_transform, batch[input_key], batch, step
        )
        mutable = (["batch_stats"] if has_stats else []) + (
            ["losses"] if wants_aux else []
        ) + (["moe_stats"] if moe_telemetry else [])
        kwargs = {}
        if dropout_rate > 0 or jitter_rate > 0:
            key = jax.random.fold_in(dropout_base, step)
            if reducer is not None:
                # inside the explicit path's shard_map each replica sees
                # only its local batch rows; the step-derived key alone
                # would draw the SAME mask on every replica (row i of every
                # shard sharing noise — W-fold less mask diversity than the
                # implicit path's one global-batch draw). Folding in the
                # replica index restores independent per-rank masks — DDP's
                # exact dropout semantics.
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(mesh_lib.DATA_AXIS)
                )
            kwargs["rngs"] = {"dropout": key}
        if mutable:
            logits, updates = model.apply(
                variables, inputs, train=True, mutable=mutable, **kwargs
            )
            new_stats = updates.get("batch_stats", batch_stats)
            aux = sum(jax.tree_util.tree_leaves(updates.get("losses", {})), 0.0)
        else:
            logits = model.apply(variables, inputs, train=True, **kwargs)
            new_stats = batch_stats
            aux = 0.0
        with jax.named_scope("loss_head"):
            loss = loss_fn(logits, batch[label_key]) + aux
        if moe_telemetry:
            return loss, (new_stats, _moe_metrics(
                updates.get("moe_stats", {})
            ))
        return loss, new_stats

    if forward_loss is not None:
        # fused losses don't take the step arg (no dropout on that path) —
        # refuse rather than silently train without the configured dropout
        if dropout_rate > 0:
            raise ValueError(
                f"model.dropout={dropout_rate} but forward_loss has no rng "
                "stream; use the default forward or a dropout-free model"
            )
        if jitter_rate > 0:
            raise ValueError(
                f"model.router_jitter={jitter_rate} but forward_loss has "
                "no rng stream; use the default forward or router_jitter=0"
            )
        if moe_telemetry:
            def forward(params, stats, batch, step):
                loss, (new_stats, sown) = forward_loss(params, stats, batch)
                return loss, (new_stats, _moe_metrics(sown))
        else:
            forward = lambda params, stats, batch, step: forward_loss(params, stats, batch)
    from tpudist.remat import checkpoint as _remat_checkpoint

    forward = _remat_checkpoint(forward, remat)

    grad_fn = jax.value_and_grad(forward, has_aux=True)

    def step_fn(state: TrainState, batch):
        new_residual = state.comm_residual
        # fused-optimizer forward wiring: the forward reads the compute-
        # dtype copy fused_adamw wrote in LAST step's update sweep (==
        # compute_dtype(current params), never stale), deleting the
        # per-op fp32→compute casts and halving the forward's param-read
        # bytes. Declined (masters used) whenever the copy is absent or
        # not params-shaped — e.g. ZeRO-1 pad-stored leaves.
        fwd_params = state.params
        if "optimizer" in fused_set:
            from tpudist.optim import fused_compute_params

            copy = fused_compute_params(state.opt_state, state.params)
            if copy is not None:
                fwd_params = copy
        if reducer is not None:
            bad_keys = sorted(k for k in batch if k.startswith("_"))
            if bad_keys:
                raise ValueError(
                    f"batch carries device-resident operands {bad_keys}, "
                    "which the explicit-reduction path does not stage into "
                    "its shard_map — use the implicit path (reduce='none') "
                    "with DeviceCachedLoader"
                )
            loss, grads, new_stats, ef_res = reducer.compute(
                grad_fn, fwd_params, state.batch_stats, batch, state.step,
                state.comm_residual, grad_accum,
            )
            if ef_res is not None:
                new_residual = ef_res
        elif grad_accum == 1:
            (loss, fwd_aux), grads = grad_fn(
                fwd_params, state.batch_stats, batch, state.step
            )
            if moe_telemetry:
                new_stats, moe_metrics = fwd_aux
            else:
                new_stats = fwd_aux
        else:
            # "_"-prefixed keys are per-step operands (e.g. the
            # DeviceCachedLoader's "_cache"), not row data: they have no
            # microbatch dim, so they ride into every microbatch unscanned
            # instead of being scanned over (whose leading-axis check they
            # would fail)
            operands = {k: v for k, v in batch.items() if k.startswith("_")}
            rows = {k: v for k, v in batch.items() if not k.startswith("_")}

            def micro(carry, xs):
                mb, i = xs
                gsum, stats, lsum = carry
                # distinct dropout stream per microbatch
                (l, stats), g = grad_fn(
                    fwd_params, stats, {**mb, **operands},
                    state.step * grad_accum + i
                )
                gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                return (gsum, stats, lsum + l), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (gsum, new_stats, lsum), _ = jax.lax.scan(
                micro,
                (zeros, state.batch_stats, jnp.zeros((), jnp.float32)),
                (rows, jnp.arange(grad_accum)),
            )
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum

        # named for the device trace (metadata only): without a scope the
        # update's ops sit at jit(step_fn)/<primitive>
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        if reducer is not None and reducer.error_feedback:
            # a non-finite step (bf16 spike, data glitch) must not bank its
            # garbage into the error-feedback residual: whether the update
            # itself is rejected by guard_nonfinite, amp.skip_nonfinite, or
            # nothing at all, the residual reverts — detection on the
            # DEQUANTIZED grads, the same values every other consumer sees
            from tpudist.amp import all_finite as _all_finite

            res_ok = jnp.isfinite(loss) & _all_finite(grads)
            new_residual = jnp.where(
                res_ok, new_residual, state.comm_residual
            )
        # loss is the global-batch mean — the in-graph equivalent of the
        # reference's post-step reduce_loss (main.py:105)
        metrics = {"loss": loss}
        if moe_telemetry:
            metrics.update(moe_metrics)
        if reducer is not None:
            # wire bytes this step's reductions move per replica — a static
            # constant, but carried as a metric so it rides the existing
            # one-step-delayed fetch with the other step scalars. fp32's
            # 24-bit mantissa rounds GB-scale counts; exact-integer
            # consumers (the telemetry rows) read comm_stats() instead
            metrics["comm_bytes"] = jnp.asarray(
                reducer.layout_for(state.params).wire_bytes(
                    reducer.method,
                    reductions=reducer.reductions_per_step(grad_accum),
                ),
                jnp.float32,
            )
        if telemetry:
            # health metrics inside the same compiled program: these are
            # full-tree reductions over values the step already holds, so
            # XLA schedules them alongside the backward pass and the only
            # addition to the metrics fetch is four more scalars on the
            # existing one-step-delayed async path. On the explicit-
            # reduction path `grads` is the dequantized cross-replica mean,
            # so the count sees exactly what the optimizer sees.
            from tpudist.amp import nonfinite_count

            nonfinite = nonfinite_count(grads)
            metrics.update(
                grad_norm=optax.global_norm(grads),
                param_norm=optax.global_norm(state.params),
                update_norm=optax.global_norm(updates),
                nonfinite_grad_count=nonfinite,
            )
        if guard_nonfinite:
            if telemetry:
                ok = jnp.isfinite(loss) & (metrics["nonfinite_grad_count"] == 0)
            else:
                from tpudist.amp import all_finite

                ok = jnp.isfinite(loss) & all_finite(grads)
            keep = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new, old
            )
            from tpudist.amp import is_skip_state

            new_params = keep(new_params, state.params)
            new_opt = keep(new_opt, state.opt_state)
            if is_skip_state(new_opt):
                # amp.skip_nonfinite's (inner_state, int32 counter) shape,
                # static at trace time: the counter is run metadata (how
                # many updates were rejected), not optimizer state — the
                # freeze must not revert its increment, or
                # amp.skipped_steps / the telemetry run-summary read 0
                # whenever the guard is on. Under the guard "rejected"
                # means exactly ~ok, whichever check (the wrapper's own
                # updates scan or the guard's loss/grad one) caught it.
                new_opt = (new_opt[0], jnp.where(
                    ok, new_opt[1], state.opt_state[1] + 1
                ))
            new_stats = keep(new_stats, state.batch_stats)
            metrics["update_skipped"] = (~ok).astype(jnp.int32)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt,
            comm_residual=new_residual,
        )
        return new_state, metrics

    repl = mesh_lib.replicated_sharding(mesh)
    out_state_sharding = state_sharding if state_sharding is not None else repl
    if reducer is not None and reducer.error_feedback:
        # the residual is PER-REPLICA state — forcing it under the default
        # replicated sharding would all-gather world× copies onto every
        # chip; pin its leaf to the data-sharded layout it was born with
        res_sh = reducer.residual_sharding()
        if state_sharding is None:
            out_state_sharding = TrainState(
                step=repl, params=repl, batch_stats=repl, opt_state=repl,
                comm_residual=res_sh,
            )
        else:
            out_state_sharding = state_sharding.replace(comm_residual=res_sh)

    def batch_sh(key, x):
        if batch_spec is not None and key in batch_spec:
            return NamedSharding(mesh, batch_spec[key])
        if grad_accum == 1:
            return mesh_lib.batch_sharding(mesh, extra_dims=x.ndim - 1)
        # leading microbatch dim replicated (scanned over), second dim sharded
        return NamedSharding(mesh, P(None, batch_axes, *([None] * (x.ndim - 2))))

    def stage(batch):
        """Host batch (flat leading dim [global_batch, ...]) → device batch.

        With grad accumulation the flat dim is folded to
        ``[grad_accum, micro, ...]`` *before* staging, so each device keeps
        contiguous rows of every microbatch and no resharding is needed.
        """
        mesh_lib.check_reserved_device_keys(batch)
        out = {}
        for k, v in batch.items():
            if isinstance(v, jax.Array):
                out[k] = v
                continue
            v = np.asarray(v)
            if grad_accum > 1:
                v = v.reshape(grad_accum, -1, *v.shape[1:])
            out[k] = mesh_lib.put_sharded(v, batch_sh(k, v))
        return out

    def compiled(state, batch):
        return _jitted(state, stage(batch))

    _jitted = jax.jit(
        step_fn, out_shardings=(out_state_sharding, repl), donate_argnums=(0,)
    )
    compiled.jitted = _jitted
    compiled.stage = stage
    compiled.grad_reducer = reducer
    compiled.comm_stats = (
        None if reducer is None
        else lambda params: reducer.comm_stats(params, grad_accum)
    )
    compiled.fused = fused_set
    compiled.fused_info = fused_info
    compiled.plan = plan
    return compiled


def fit(
    model,
    tx: optax.GradientTransformation,
    train_loader,
    *,
    epochs: int,
    mesh: Mesh | None = None,
    plan=None,
    seed: int = 0,
    job_id: str = "Job0",
    batch_size: int | None = None,
    world_size: int | None = None,
    global_rank: int | None = None,
    loss_fn: Callable = cross_entropy_loss,
    input_key: str = "image",
    label_key: str = "label",
    grad_accum: int = 1,
    remat: bool | str = False,
    shard_opt_state: bool = False,
    reduce: str = "none",
    fused: str | None = None,
    batch_spec: Mapping[str, P] | None = None,
    forward_loss: Callable | None = None,
    input_transform: Callable | None = None,
    profile: bool = True,
    prefetch_depth: int = 2,
    log_dir: str = ".",
    telemetry: bool | Any = False,
    memory_log_every: int | None = None,
    metrics_logger: MetricsLogger | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    checkpoint_every_s: float | None = None,
    keep_last: int | None = None,
    resume: bool = True,
    elastic: bool = False,
    compile_cache: str | None = None,
    preempt: bool | str = "auto",
    repair=None,
    chaos=None,
    init_params=None,
    init_input=None,
    metrics_port: int | None = None,
) -> tuple[TrainState, list[float]]:
    """The reference's whole training program (/root/reference/main.py:86-117)
    as a function: epochs × batches, per-epoch sampler re-shuffle, windowed
    profiler, TSV metrics, TrainTime footer. Returns final state and the
    per-step loss history.

    ``checkpoint_dir`` enables periodic async checkpointing (every
    ``checkpoint_every`` steps plus once at the end); with ``resume`` the
    latest checkpoint is restored and training continues from the exact
    step it stopped at (same epoch, same position in the sampler's
    deterministic order) — a capability the reference lacks entirely
    (SURVEY.md §5: no save/load; crash = start over).
    ``checkpoint_every_s`` adds a WALL-CLOCK cadence alongside the
    step-based one: a save triggers when either knob is due. The time
    knob is what bounds preemption loss on runs with variable step times
    — "at most N steps of work lost" is meaningless when steps range
    from 0.3 s to 30 s, "at most M minutes" is the contract operators
    actually want; the step knob keeps saves aligned to deterministic
    step numbers for A/B debugging. Interaction when both are set: every
    save (whichever knob triggered it) resets the time knob's clock, but
    the step knob stays pinned to absolute multiples of
    ``checkpoint_every`` — a time-triggered save between multiples does
    NOT postpone the next step-aligned save (alignment is the step
    knob's whole point), so the worst-case save frequency is the SUM of
    the two cadences, not the denser one.

    ``elastic=True`` lets a resume proceed when the checkpoint's recorded
    geometry differs from the live run by a WORLD RESIZE only
    (``tpudist.resilience.elastic``, docs/MULTIHOST.md "Resuming on a
    different world size"): ZeRO-1's pad-and-reshape optimizer leaves are
    re-laid onto the new mesh, the quantized reducer's error-feedback
    residual restarts zeroed (one step of uncompensated quantization
    noise, recorded by a one-shot telemetry ``reshard`` row), and
    ``state.step`` is remapped into the new world's step units so the
    sampler cursor lands on the same data position. The resharded state
    is committed immediately — a synchronous save in new-step units plus
    an atomic meta flip, with the old-geometry steps quarantined until
    both are durable — so a crash mid-commit always leaves a restorable
    directory. Mismatches that are NOT a pure resize (reduction method,
    shard_opt_state) still refuse loudly. The newest-checkpoint
    deserialization failure fallback (walk back one saved step, tagged
    ``checkpoint_fallback`` warning row) is active on every resume,
    elastic or not.

    ``compile_cache`` names a directory of serialized AOT step
    executables (``tpudist.compile_cache``): bring-up starts
    deserializing the matching executable WHILE the checkpoint restore
    streams, so a relaunched generation skips tracing entirely on a hit
    (the dominant term in ``restart_overhead_s``); on a miss the step is
    AOT-compiled at bring-up and stored for the next life. Keyed by
    (device topology, state/batch geometry, step config, jax versions);
    any mismatch or deserialization failure falls through to ordinary
    tracing with a ``warning`` row — the cache can cost a recompile,
    never a wrong program. Goodput attributes a warm first iteration to
    ``cache_load_s``, not ``compile_s``.

    ``preempt`` (default ``"auto"``) traps SIGTERM/SIGINT as a
    signal-safe flag checked at step boundaries (``tpudist.resilience``):
    on trip the in-flight step finishes, a *synchronous* emergency
    checkpoint is written (when ``checkpoint_dir`` is set), telemetry and
    the run report flush with ``exit_reason="preempted"``, and
    :class:`tpudist.resilience.Preempted` is raised — a ``SystemExit``
    carrying exit code 75, the code ``tpudist.launch`` restarts on.
    ``"auto"`` installs only where possible (main thread); ``False``
    keeps the default signal dispositions (the pre-resilience behavior).

    ``chaos`` injects a deterministic fault at a step boundary for
    recovery testing (``tpudist.resilience.chaos``): a spec string like
    ``"sigterm@12"`` / ``"crash@5@*"`` / ``"hang:600@8"`` /
    ``"corrupt@12"`` (truncate the newest checkpoint, then crash — the
    die-mid-write drill the fallback restore absorbs) /
    ``"bitflip@12"`` (flip one mantissa bit in one data-replica's param
    copy — the SDC drill the divergence probe + repair loop absorb) /
    ``"nanburst:3@12"`` (poison three consecutive steps' batches with
    NaNs, defeating the single-step guard), a comma-separated
    composition of several specs, a ``ChaosSpec`` (or list), or a
    prebuilt ``ChaosInjector``. ``None`` (default) injects nothing.

    ``repair`` (``None``/``False`` off; ``True`` = default
    :class:`tpudist.resilience.repair.RepairPolicy`; a policy or a dict
    of overrides to tune) turns detector verdicts into the self-healing
    escalation ladder (docs/MULTIHOST.md "Recovering from loss spikes
    and SDCs"): on a replica-divergence verdict, a ``skip_streak`` of
    consecutive guard-skipped steps, or a sustained NanSentry spike, fit
    rolls state back to the last-known-good ANCHORED checkpoint (a save
    promoted only after ``anchor_clean_steps`` clean health steps),
    advances the data cursor ``skip_window`` batches past the trigger,
    folds a repair-generation salt into the step RNG so dropout/
    stochastic-rounding redraw, and continues — in-process, no
    supervisor involved. A repeat trigger inside the just-repaired
    window persists a rollback-and-skip directive and raises
    :class:`tpudist.resilience.RepairRestart` (SystemExit 77, the
    restartable code the supervisor relaunches; bring-up consumes the
    directive); a rolling ``max_repairs``/``budget_window_s`` budget
    circuit-breaks a deterministic poison with
    :class:`tpudist.resilience.RepairExhausted` instead of spinning.
    Requires ``checkpoint_dir`` plus a save cadence; implies
    ``telemetry=True`` when telemetry is off (the detectors live
    there), and an SDC trigger additionally needs
    ``divergence_every``. Every action books honestly: a ``repair``
    JSONL row, the report's ``repairs`` history, and the goodput
    ``repair_s``/``repair_replay_s`` components.

    ``keep_last`` bounds checkpoint retention to the newest N step dirs
    (``Checkpointer.keep_last``) so long runs with a tight save cadence
    stop accumulating unbounded step dirs — the health-ANCHORED step is
    exempt from pruning (it is the repair loop's rollback target).
    ``None`` keeps the legacy orbax ``max_to_keep=3`` behavior, except
    under ``repair`` where anchor-protecting retention is forced
    (``keep_last=3``).

    ``telemetry`` (False | True | ``tpudist.telemetry.TelemetryConfig``)
    turns on the observability subsystem (docs/OBSERVABILITY.md): in-step
    health metrics and the non-finite update guard inside the compiled
    step, the NaN/divergence sentry + on-demand profiler flight recorder,
    per-step data-wait/dispatch/device time attribution, MFU rows for
    models that advertise a ``flops_counter``, and per-process heartbeat
    rows — all into a ``{job_id}_telemetry_{rank}.jsonl`` stream next to
    the TSV, which stays byte-identical to the reference contract when
    telemetry is off. The run-health layer rides the same config
    (``tpudist.telemetry.health``, docs/OBSERVABILITY.md §7): cross-process
    straggler aggregation, a replica-divergence probe, a hang watchdog
    with crash forensics, and a ``{job_id}_report.json`` end-of-run report
    written on normal exit AND from the crash/watchdog paths — the health
    detectors are off unless their config fields are set
    (``tpudist.telemetry.health.health_config`` is the production preset).

    ``memory_log_every`` cadences ``MetricsLogger.log_memory`` (live HBM
    rows) during training: ``None`` (default) auto-selects ``log_every·10``
    steps on backends that report allocator stats and off on those that
    don't (CPU); ``0`` disables; ``N`` forces a cadence.

    ``reduce`` selects the gradient-reduction path (see
    :func:`make_train_step`): ``"none"`` (default, implicit XLA psum),
    ``"bucketed"`` / ``"quantized"`` (explicit bucketed all-reduce, fp32 or
    int8-on-the-wire with error feedback — the DCN-bound data-parallel
    lever; no cell runs it), ``"auto"`` (quantized on a multi-slice
    attach). fit() attaches the error-feedback residual to the train state,
    records the method in the checkpoint geometry meta, and — with
    telemetry on — streams per-step comm bytes plus a one-time measured
    comm-time probe into the JSONL sink (a ``comm`` column on the step-time
    breakdown rows; rows are unchanged when the feature is off).

    ``fused`` selects the step-fusion layer (see :func:`make_train_step`):
    ``"ln"`` / ``"optimizer"`` / ``"all"`` /
    ``"auto"``; ``None`` (default) keeps the compiled programs
    bit-identical to previous rounds. With telemetry on, the resolved
    configuration is recorded as a one-time ``fusion`` JSONL row so run
    reports stay attributable to the kernels that
    actually ran.

    ``shard_opt_state=True`` wraps ``tx`` in ZeRO-1 cross-replica
    optimizer-state sharding (``tpudist.optim.shard_state``): the Adam
    mirrors live sharded over the ``data`` replicas (~1/world_size per
    chip, born sharded at init) and XLA decomposes the gradient all-reduce
    into reduce-scatter → sharded update → params all-gather inside the
    same compiled step. Combine with ``remat`` (named policy or the
    models' per-block ``remat_policy``) for the full memory-discipline
    recipe — the pair is what moves the trainable-size frontier on a
    16 GB chip (docs/LM_TRAINING.md "Fitting ~1B parameters").

    ``plan`` (:class:`tpudist.parallel.plan.ParallelPlan`) runs the whole
    loop under one composed ``(data, fsdp, pipe, tensor)`` configuration
    (docs/MULTIHOST.md "Choosing a parallelism plan"): the state is born with
    the plan's placements (Megatron/pipe metadata kept, replicated leaves
    fsdp-scattered, ZeRO-1 overlaid when ``shard_opt_state=True`` — via
    ``plan.wrap_zero1``, which never double-shards an fsdp leaf), the
    step validates the composition loudly (explicit ``reduce`` routes to
    the data axis only, with the fix named), checkpoint geometry meta
    records the model-axis worlds (``fsdp_world``/``tensor_world``/
    ``pipe_world`` — a non-data-axis resize is default-denied with a
    precise hint, ``tpudist.resilience.elastic``), and telemetry's MFU
    rows divide model FLOPs by the plan's FULL chip count. ``mesh`` may
    be omitted (the plan carries it) or must match the plan's.
    """
    import itertools

    from tpudist.data.loader import prefetch_to_mesh
    from tpudist.resilience import (
        GoodputTracker,
        Preempted,
        PreemptionGuard,
        make_injector,
        restart_generation,
    )
    from tpudist.resilience import repair as repair_mod
    from tpudist.telemetry.trace import TRAIN_STEP, Bringup, span

    generation = restart_generation()
    repair_policy = repair_mod.resolve_policy(repair)
    if repair_policy is not None and not telemetry:
        # the triggers ARE telemetry verdicts; a repair request with
        # telemetry off would watch nothing
        telemetry = True
    tel_cfg = None
    if telemetry:
        from tpudist.telemetry import TelemetryConfig

        tel_cfg = (
            telemetry if isinstance(telemetry, TelemetryConfig)
            else TelemetryConfig()
        )
    # goodput spans only surface through the run report, so the tracker
    # rides the telemetry switch; its per-boundary cost is two clock reads.
    # Built here so that its clock starts at fit's entry, as the bring-up
    # account's does
    gp = GoodputTracker(generation=generation) if tel_cfg is not None else None
    guard = ckpt = tel = None  # what the finally below tears down

    def on_recompile(fun, **seconds):
        # from the thread that compiled; the step being dispatched
        if tel is not None:
            tel.recompiled(global_step, fun, **seconds)

    # the bring-up's phases (docs/OBSERVABILITY.md §8): profiler
    # annotations always; with telemetry also the `bringup` row's phases
    # and compile table, and a `recompile` warning for a compile after it
    bringup = Bringup(observe=tel_cfg is not None, on_recompile=on_recompile)
    try:
        bringup.enter("bringup/probe")
        if plan is not None:
            if mesh is not None and mesh != plan.mesh:
                raise ValueError(
                    f"fit got both a mesh and a plan ({plan.describe()}) over "
                    "a different mesh — build the plan over the run's mesh "
                    "(ParallelPlan(mesh)) or drop the mesh argument"
                )
            mesh = plan.mesh
        mesh = mesh or mesh_lib.create_mesh()
        if world_size is None:
            world_size = jax.device_count()
        global_rank = (
            global_rank if global_rank is not None else jax.process_index()
        )
        if batch_size is None:
            # loader batch is per-process; the logged batch_size is per-replica
            # (the reference's per-GPU --batch_size, main.py:25)
            batch_size = train_loader.batch_size // jax.local_device_count()

        # init sample batch = the mesh's replica count, not 1: models with
        # manual (shard_map) axes — ring/Ulysses attention — refuse traces
        # whose batch doesn't divide the mesh; zeros keep init cheap and
        # content-independent. ``init_input`` overrides the probe-derived
        # shape for models whose init takes more than batch[input_key] (e.g.
        # T5's (enc, dec) tuple) — and skips the probe entirely (its only
        # consumer).
        if init_input is None:
            # shape/dtype probe: one gathered sample where the loader supports
            # it (a full first batch would e.g. JPEG-decode the whole thing
            # twice)
            sample = (
                train_loader.probe()
                if hasattr(train_loader, "probe")
                else next(iter(train_loader))
            )
            sample_in = np.asarray(sample[input_key])
            init_input = jnp.zeros(
                (mesh_lib.data_parallel_size(mesh), *sample_in.shape[1:]),
                sample_in.dtype,
            )
        bringup.enter("bringup/init_state")
        if shard_opt_state:
            if plan is not None:
                # ZeRO-1 composed with the plan: skip the leaves the plan
                # scatters over fsdp (no double-sharding — parallel/plan.py).
                # On an expert plan the skip rule also needs the expert-sharded
                # leaf SHAPES (the rule is shape-only), identified from an
                # abstract trace of the init's partitioning metadata.
                boxed = None
                if plan.expert > 1:
                    boxed = jax.eval_shape(
                        lambda: model.init(
                            jax.random.PRNGKey(0), init_input, train=False
                        )
                    )["params"]
                tx = plan.wrap_zero1(tx, params=boxed)
            else:
                from tpudist.optim import shard_state as _zero1

                tx = _zero1(tx, mesh)
        state = create_train_state(
            model, seed, init_input, tx, mesh, plan=plan
        )
        bringup.enter("bringup/place_params")
        if init_params is not None:
            # warm-start (e.g. an HF checkpoint through tpudist.interop):
            # replace the random init leaf-for-leaf, keeping each leaf's mesh
            # placement and dtype; optimizer state stays fresh
            placed = jax.tree_util.tree_map(
                lambda ref, new: jax.device_put(
                    jnp.asarray(new, ref.dtype), ref.sharding
                ),
                state.params, init_params,
            )
            from tpudist.optim import refresh_fused_compute

            # a fused_adamw compute copy was cast from the DISCARDED random
            # init — re-cast it from the warm-start weights (no-op for states
            # without a usable copy, which the forward also never reads)
            state = state.replace(
                params=placed,
                opt_state=refresh_fused_compute(state.opt_state, placed),
            )
        # DDP verifies rank param consistency at wrap time (main.py:83); same
        # check here — same seed must have produced identical params (no-op
        # single-process)
        from tpudist.distributed import verify_replicas

        bringup.enter("bringup/verify_replicas")
        verify_replicas(state.params)

        bringup.enter("bringup/build_step")
        repair_ctl = None
        if repair_policy is not None:
            if checkpoint_dir is None:
                raise ValueError(
                    "fit(repair=...) needs checkpoint_dir: the escalation "
                    "ladder's first rung is a rollback to the last-known-good "
                    "checkpoint (docs/MULTIHOST.md)"
                )
            if not checkpoint_every and not checkpoint_every_s:
                raise ValueError(
                    "fit(repair=...) needs a save cadence (checkpoint_every "
                    "and/or checkpoint_every_s): without periodic saves the "
                    "rollback target never advances past bring-up"
                )
            if keep_last is None:
                # anchor-protecting retention: orbax's newest-N policy would
                # prune the rollback target out from under the repair loop
                keep_last = 3
            # built BEFORE the step so the directive's RNG salt (and the
            # repair-generation salt of a resumed post-repair trajectory)
            # reaches the compiled program's dropout/SR streams
            repair_ctl = repair_mod.RepairController(
                repair_policy, checkpoint_dir, generation=generation
            )

        def build_step(step_seed):
            return make_train_step(
                model, tx, mesh,
                loss_fn=loss_fn, input_key=input_key, label_key=label_key,
                grad_accum=grad_accum, remat=remat, batch_spec=batch_spec,
                forward_loss=forward_loss, dropout_seed=step_seed,
                input_transform=input_transform, reduce=reduce, fused=fused,
                **(tel_cfg.step_kwargs() if tel_cfg else {}),
                # keep whatever sharding create_train_state produced
                # (replicated for plain DP, sharded for TP-annotated models
                # and plan-composed runs) — forcing replicated here would
                # all-gather a TP model's params on the first step
                state_sharding=state_shardings_of(state),
                plan=plan,
            )

        eff_seed = (
            repair_policy.salted_seed(seed, repair_ctl.salt)
            if repair_ctl is not None else seed
        )
        step = build_step(eff_seed)
        if step.grad_reducer is not None:
            # error-feedback residual born sharded over the data replicas
            # (no-op for methods that carry none)
            state = step.grad_reducer.attach_residual(state)

        # sized loaders only matter for resume math; a re-iterable loader
        # without __len__ still trains as long as checkpointing is off
        steps_per_epoch = (
            len(train_loader) if hasattr(train_loader, "__len__") else None
        )
        if checkpoint_dir is not None and steps_per_epoch is None:
            raise ValueError(
                "checkpointing needs a sized train_loader (len() maps "
                "state.step to an epoch/batch position for exact resume)"
            )
        run_meta = {
            "steps_per_epoch": steps_per_epoch,
            "batch_size": batch_size,
            "world_size": world_size,
            "grad_accum": grad_accum,
            # the model-axis worlds the state's placements are bound to
            # (composable-parallelism geometry): appended keys — metas
            # written before this layer carried none and default to 1, and
            # a NON-data-axis resize is default-denied with a precise hint
            # (tpudist.resilience.elastic.refusal_reason)
            "fsdp_world": int(mesh.shape[mesh_lib.FSDP_AXIS]),
            "tensor_world": int(mesh.shape[mesh_lib.TENSOR_AXIS]),
            "pipe_world": int(mesh.shape[mesh_lib.PIPELINE_AXIS]),
        }
        if shard_opt_state:
            # ZeRO-1 changes the opt-state LAYOUT on disk (padded [world, cols]
            # leaves): resuming it replicated (or at another world size) would
            # die in orbax with a shape mismatch — make the geometry guard say
            # so instead. Only recorded when on, so replicated runs' meta (and
            # their resumability) is unchanged.
            run_meta["shard_opt_state"] = True
        if step.grad_reducer is not None:
            # same geometry rule for the explicit-reduction path: the
            # error-feedback residual's [world, ...] layout (and the stochastic
            # rounding stream) is world-size-bound — resuming a quantized run
            # replicated (or vice versa) must refuse, not silently diverge
            run_meta["reduce"] = step.grad_reducer.method
        if shard_opt_state or step.grad_reducer is not None:
            # the world the stored layouts are actually bound to is the MESH's
            # data-axis size, not the (process-count-shaped) world_size above:
            # a device-count resize with an unchanged process count would
            # otherwise slip past the geometry guard and die in orbax with a
            # bare shape mismatch instead of a validated reshard/refusal
            run_meta["data_world"] = int(mesh.shape[mesh_lib.DATA_AXIS])
        chaos_inj = make_injector(chaos)
        # SIGTERM/SIGINT → a signal-safe flag checked at step boundaries —
        # the graceful-preemption path (docs/MULTIHOST.md "Surviving
        # preemption"). Installed here (post state-init, before checkpoint
        # bring-up and the whole loop — the step compile included): a
        # preemption anywhere past this line exits 75 after persisting
        # whatever had become restorable.
        guard = PreemptionGuard(enabled=bool(preempt)).__enter__()
        preempt_signum = None
        repair_exit = None  # the ladder's rung-3 action, raised as exit 77
        start_step = 0
        losses: list[float] = []
        logger = None
        # bring-up diagnoses that happen BEFORE the telemetry sink exists
        # (reshard record, checkpoint-fallback warnings, compile-cache
        # outcome) — replayed into the sink once it is up
        bringup_events: list[dict] = []
        # AOT executable cache (tpudist.compile_cache): start deserializing
        # the cached step executable NOW, on a side thread, so the load
        # overlaps the checkpoint restore below instead of serializing with it
        bringup.enter("bringup/restore")
        cc = cc_key = cc_handle = cc_staged = None
        cc_info: dict | None = None
        tel_box: list = []  # late-bound telemetry ref for the AOT fallback
        if compile_cache is not None:
            try:
                from tpudist import compile_cache as cc_mod

                cc = cc_mod.CompileCache(compile_cache)
                cc_staged = cc_mod.staged_example(step, train_loader)
                if cc_staged is None:
                    bringup_events.append({
                        "tag": "compile_cache_unsupported",
                        "reason": "loader cannot be probed into a shaped "
                        "batch (device-resident operands or unsized stream) "
                        "— falling through to ordinary tracing",
                    })
                    cc = None
                else:
                    tel_knobs = tel_cfg.step_kwargs() if tel_cfg else {}
                    model_id = cc_mod.model_identity(model)
                    if ":" not in model_id:
                        # type-only identity (address-bearing default repr):
                        # the key cannot see model-code edits — say so once
                        bringup_events.append({
                            "tag": "compile_cache_weak_key",
                            "reason": "model repr is the default "
                            "address-bearing one, so the cache key sees only "
                            "the model TYPE — code edits with identical "
                            "geometry would reuse a stale executable; bump "
                            "the compile_cache dir after changing model code",
                        })
                    cc_key = cc_mod.step_key(
                        mesh=mesh, state=state, batch=cc_staged,
                        config={
                            "reduce": getattr(
                                step.grad_reducer, "method", "none"
                            ),
                            "fused": sorted(step.fused),
                            "grad_accum": grad_accum,
                            "remat": str(remat),
                            "telemetry": bool(tel_knobs.get("telemetry")),
                            "guard_nonfinite": bool(
                                tel_knobs.get("guard_nonfinite")
                            ),
                            "shard_opt_state": bool(shard_opt_state),
                            "loss_fn": getattr(
                                loss_fn, "__qualname__", str(loss_fn)
                            ),
                            "forward_loss": (
                                getattr(forward_loss, "__qualname__",
                                        str(forward_loss))
                                if forward_loss is not None else None
                            ),
                            "input_key": input_key,
                            "label_key": label_key,
                            # the SALTED seed: a post-repair trajectory's
                            # program differs exactly when its RNG streams do
                            "dropout_seed": eff_seed,
                            "model": model_id,
                        },
                    )
                    cc_handle = cc.begin_load(cc_key)
            except Exception as exc:
                bringup_events.append({
                    "tag": "compile_cache_unsupported",
                    "reason": f"{type(exc).__name__}: {exc}",
                })
                cc = None
        if checkpoint_dir is not None:
            from tpudist.checkpoint import Checkpointer

            # inside try/finally so the manager's async-checkpointing threads
            # are torn down even when bring-up below raises
            ckpt = Checkpointer(checkpoint_dir, keep_last=keep_last)
            if chaos_inj is not None:
                # the corrupt@step drill truncates the newest checkpoint:
                # bind the target and the settle hook so it corrupts a
                # deterministic, already-committed step
                chaos_inj.bind(checkpoint_dir, wait=ckpt.wait)
            if repair_ctl is not None:
                # anchor persistence + rollback-target enumeration +
                # the retention protect hook (candidates must outlive
                # keep_last pruning until they promote or demote)
                repair_ctl.bind(ckpt)

                def apply_rollback(state, rollback_step, skip_to, *,
                                   on_event=None):
                    """The ONE rollback-apply — the exit-77 bring-up
                    directive and the in-process ladder share it: settle
                    async saves, restore the target step, flush the
                    reducer's error-feedback banks (trajectory state —
                    the same reset elastic.py performs), set aside newer
                    (suspect) saves so a crash right after resumes from
                    the anchor, and jump the data cursor past the
                    skipped window (state.step IS the cursor, so resume
                    math and later checkpoints stay consistent)."""
                    rollback_step = int(rollback_step)
                    ckpt.wait()
                    state = ckpt.restore(
                        like=state, step=rollback_step, on_event=on_event
                    )
                    if step.grad_reducer is not None:
                        state = step.grad_reducer.attach_residual(state)
                    for s in ckpt.all_steps():
                        if s > rollback_step:
                            ckpt.quarantine_failed_step(s)
                    return state.replace(
                        step=jax.device_put(
                            jnp.asarray(int(skip_to), state.step.dtype),
                            state.step.sharding,
                        )
                    )
            # finish or roll back an elastic commit a previous life
            # crashed mid-way: adopt the committed new-world save (its
            # marker meta becomes THE meta — without this, a crash
            # between the barrier-save and the meta flip would re-reshard
            # an already-resharded checkpoint, double-remapping the
            # cursor) or rename the quarantined old steps back
            ckpt.recover_interrupted_reshard()
            resharded = False
            repair_directive = (
                repair_ctl.pending if repair_ctl is not None else None
            )
            if ckpt.latest_step() is not None:
                if not resume:
                    raise ValueError(
                        f"checkpoint_dir {checkpoint_dir} already holds "
                        "checkpoints but resume=False; refusing to mix runs "
                        "(the old steps + overwritten meta would corrupt a "
                        "later resume) — use a fresh checkpoint_dir"
                    )
                saved_meta = ckpt.read_meta()
                from tpudist.resilience import elastic as elastic_mod

                if saved_meta is not None and not elastic_mod.meta_matches(
                    saved_meta, run_meta
                ):
                    reason = elastic_mod.refusal_reason(
                        saved_meta, run_meta
                    )
                    if not elastic or reason is not None:
                        hint = (
                            " — this is a pure world resize; pass "
                            "fit(elastic=True) to reshard onto the live "
                            "mesh (docs/MULTIHOST.md)"
                            if reason is None else f" — {reason}"
                        )
                        raise ValueError(
                            f"checkpoint at {checkpoint_dir} was written by "
                            f"a run with different geometry ({saved_meta} "
                            f"!= {run_meta}); state.step would map to the "
                            "wrong data position — resume with the "
                            "original settings or start a fresh "
                            f"checkpoint_dir{hint}"
                        )
                    resharded = True
                if repair_directive is not None and resharded:
                    raise ValueError(
                        "a pending repair directive (exit-77 rollback-and-"
                        "skip) cannot compose with an elastic world resize "
                        "in the same bring-up — resume on the original "
                        "world first, or clear tpudist_repair.json"
                    )
                t_restore = time.perf_counter()
                if repair_directive is not None:
                    # exit-77 relaunch: rung 3 of the repair ladder left a
                    # rollback-and-skip directive — restore the ANCHORED
                    # step, not the (suspect) newest, and apply the skip
                    state = apply_rollback(
                        state, repair_directive["rollback_step"],
                        repair_directive["skip_to"],
                        on_event=bringup_events.append,
                    )
                else:
                    state = ckpt.restore(
                        like=state, reshard=resharded, run_meta=run_meta,
                        mesh=mesh, fallback=True,
                        on_event=bringup_events.append,
                    )
                if gp is not None:
                    gp.add("restore_s", time.perf_counter() - t_restore)
                if repair_directive is not None:
                    start_step = int(repair_directive["skip_to"])
                    repair_ctl.consume_pending()
                    resume_row = dict(repair_directive)
                    resume_row["action"] = "resume"
                    resume_row["resumed_generation"] = generation
                    bringup_events.append({"tag": "repair", **resume_row})
                else:
                    start_step = int(state.step)
                for ev in bringup_events:
                    # a step the fallback walked past failed to
                    # deserialize: set it aside (never delete — the
                    # failure may be transient I/O and the dir may still
                    # hold the healthy newest state), or it keeps
                    # shadowing latest_step AND blocks orbax's monotonic
                    # save order for every cadence save below its number
                    if ev.get("tag") == "checkpoint_fallback":
                        ckpt.quarantine_failed_step(ev["failed_step"])
                if resharded:
                    # commit the resharded world: the old-geometry step
                    # dirs are uninterpretable under the remapped counter
                    # (and may collide with its numbering), so quarantine
                    # them, barrier-save the new-world state, flip the
                    # meta atomically, and only then purge — a crash at
                    # any point leaves a restorable directory (see
                    # Checkpointer's reshard-commit protocol)
                    t_save = time.perf_counter()
                    ckpt.quarantine_steps(commit_meta=run_meta)
                    ckpt.save(state, wait=True)
                    if gp is not None:
                        gp.add(
                            "checkpoint_s", time.perf_counter() - t_save
                        )
            ckpt.write_meta(run_meta)
            ckpt.purge_quarantined()
            if repair_ctl is not None and ckpt.latest_step() is None:
                # a rollback target must exist from step one: a trigger
                # before the first cadence save would otherwise have
                # nothing to roll back to. Synchronous — a repairable run
                # is durable before it trains.
                t_save = time.perf_counter()
                ckpt.save(state, wait=True)
                if gp is not None:
                    gp.add("checkpoint_s", time.perf_counter() - t_save)
                repair_ctl.on_save(int(state.step))

        if cc is not None:
            from tpudist import compile_cache as cc_mod

            # join the background deserialization (it overlapped the
            # restore above); a miss AOT-compiles HERE — bring-up, where
            # goodput attributes it as compile_s — and stores the
            # executable for the next generation. Either way iteration 1
            # becomes an ordinary step.
            exe, cc_info = cc.finish(
                cc_handle, step, state, cc_staged, cc_key,
                meta={"job_id": job_id},
            )
            if exe is not None:
                def _aot_fallback(exc):
                    # first-call validation failed (a geometry the key
                    # could not see): permanent fall-through to tracing,
                    # surfaced in the stream — never a silent wrong
                    # guess. Iteration 1 now pays a REAL trace+compile,
                    # so goodput reverts to the cold attribution too.
                    if gp is not None:
                        gp.clear_precompiled()
                    if tel_box:
                        tel_box[0].warn(
                            "compile_cache_fallback",
                            error=f"{type(exc).__name__}: {exc}",
                        )

                step = cc_mod.wrap_step(
                    step, exe, on_fallback=_aot_fallback,
                    expected_batch=cc_staged,
                )
                if gp is not None:
                    gp.set_precompiled(warm=bool(cc_info.get("hit")))
                    if cc_info.get("hit"):
                        # only the NON-overlapped wait: the load ran
                        # concurrently with the restore, and the goodput
                        # partition is disjoint by contract
                        gp.add(
                            "cache_load_s",
                            cc_info.get("load_wait_s", 0.0),
                        )
                    else:
                        gp.add("compile_s", cc_info.get("compile_s", 0.0))

        # the logger truncates ("w") its TSV on construction, so it must not
        # exist until checkpoint bring-up has succeeded — a refused resume
        # above would otherwise clobber the previous run's metrics
        bringup.enter("bringup/telemetry")
        logger = metrics_logger or MetricsLogger(
            job_id, batch_size, global_rank, world_size, log_dir=log_dir
        )
        # logger as context manager: the TrainTime footer is written even if a
        # step raises mid-training
        with logger, WindowedProfiler(
            job_id, enabled=profile, log_dir=f"{log_dir}/log_{job_id}"
        ) as p:
            print("Start")
            from tpudist.telemetry import TimedIterator, build_telemetry
            from tpudist.telemetry.flops import mesh_chips as flops_chips

            # sink attached BEFORE the first log_memory: the dual-sink
            # contract mirrors every logger row, including the bring-up
            # HBM baseline the live cadence rows are compared against
            tel = build_telemetry(
                tel_cfg or False,
                job_id=job_id, log_dir=log_dir, rank=global_rank,
                world_size=world_size, log_every=logger.log_every,
                # the MESH's chip count, not jax.device_count(): the MFU
                # denominator must count every chip the model program
                # actually spans (tensor/pipe splits included) and ONLY
                # those — a sub-mesh run on a larger host would
                # otherwise divide by chips it never used
                n_chips=flops_chips(mesh),
                profiler=p, model=model,
                input_key=input_key, mesh=mesh,
            )
            if tel is not None:
                tel.goodput = gp
                if metrics_port is not None and global_rank == 0:
                    # opt-in live scrape endpoint (rank 0 only — the rank
                    # that owns the report): host-side counters the loop
                    # already computes, no extra device syncs. Closed by
                    # tel.shutdown() in the finally below.
                    from tpudist.telemetry.trace import MetricsExporter

                    tel.exporter = MetricsExporter(metrics_port)
                if repair_ctl is not None:
                    # detector → event-bus → repair controller: sentry and
                    # divergence verdicts become triggers; the report's
                    # `repairs` section reads the controller's live
                    # cross-generation history
                    tel.add_listener(repair_ctl.on_detection)
                    tel.repair_history = repair_ctl.history
                if tel.health is not None and ckpt is not None:
                    # hang_action="exit" tears the process down from the
                    # watchdog thread: give an in-flight async checkpoint
                    # commit a bounded chance to finalize first, or the
                    # relaunch restores an older step than exit-76 promises
                    tel.health.set_exit_drain(ckpt.wait)
                if gp is not None and generation and tel.health is not None:
                    # aggregate goodput across the lives of this job: the
                    # previous generation left its entries in the report
                    # this generation will overwrite
                    gp.load_previous(tel.health.report_path)
                logger.attach_sink(tel.sink)
                tel_box.append(tel)
                # replay what predates the sink: the bring-up phases closed
                # so far (`span` rows, with trace=True), then the diagnoses —
                # the elastic reshard record, checkpoint-fallback warnings,
                # and the AOT-cache outcome
                bringup.attach(tel.tracer)
                for ev in bringup_events:
                    ev = dict(ev)
                    tag = ev.pop("tag")
                    if tag == "reshard":
                        tel.set_reshard(ev)
                    elif tag == "repair":
                        tel.set_repair(ev)
                    else:
                        tel.warn(tag, **ev)
                if cc_info is not None:
                    tel.set_compile_cache(cc_info)
                if fused is not None:
                    # one-time fusion config row: which kernels this run's
                    # compiled step actually engaged — the attribution a
                    # run report needs next to its numbers
                    tel.set_fusion(step.fused_info)
                if step.grad_reducer is not None:
                    # one-time comm accounting + a measured standalone
                    # probe of the reduce-only program: the `comm` column
                    # the step-time breakdown rows carry (an unoverlapped
                    # upper bound; per-step comm BYTES additionally ride
                    # the compiled step's metrics through the delayed
                    # fetch)
                    tel.set_comm(
                        step.comm_stats(state.params),
                        probe_s=step.grad_reducer.time_probe(
                            state.params, grad_accum
                        ),
                    )
                if jax.default_backend() != "cpu":
                    # H2D probe: one 8 MB staged buffer measures what the
                    # host→device path sustains, so a staging-bound run gets
                    # a tagged warning row pointing at DeviceCachedLoader
                    # instead of failing silently slow. Skipped on the CPU,
                    # where "device" memory is host memory
                    from tpudist.comm import measure_h2d_mbps

                    tel.h2d_mbps = measure_h2d_mbps()
                if tel.config.anatomy:
                    # program anatomy at bring-up (docs/OBSERVABILITY.md
                    # §9): ask XLA what it actually compiled — FLOPs,
                    # bytes, static HBM — and cross-check the analytic
                    # MFU counter against it. The AOT path reuses the
                    # compile-cache executable for free; the jit path
                    # pays one lowering (no compile). Entirely fail-soft:
                    # introspection must never take a training run down.
                    try:
                        from tpudist import compile_cache as cc_mod
                        from tpudist.telemetry import anatomy as anat_mod

                        anat_staged = cc_staged
                        if anat_staged is None:
                            anat_staged = cc_mod.staged_example(
                                step, train_loader
                            )
                        if anat_staged is None:
                            tel.warn(
                                "anatomy_unavailable",
                                reason="loader cannot be probed into a "
                                "shaped batch — no program to lower",
                            )
                        else:
                            tel.set_anatomy(anat_mod.analyze_train_step(
                                step, state, anat_staged, model=model,
                                input_key=input_key,
                                grad_accum=grad_accum,
                            ))
                    except Exception as exc:
                        tel.warn(
                            "anatomy_failed",
                            error=f"{type(exc).__name__}: {exc}"[:300],
                        )
            breakdown = tel is not None and tel.config.breakdown
            # the loop's spans (docs/OBSERVABILITY.md §8) are profiler
            # annotations always and `span` rows too when the run traces
            tracer = tel.tracer if tel is not None else None

            # live HBM snapshot post-bring-up (params+opt state placed,
            # no activations yet): the measured side of the pre-compile
            # budget tpudist.memory reports; silent no-op on backends
            # without memory_stats (CPU)
            from tpudist.memory import device_memory_stats

            mem_stats = device_memory_stats()
            logger.log_memory(mem_stats)
            # automatic HBM-row cadence (None = auto: on only where the
            # allocator reports stats — the probe above doubles as the
            # capability check; 0 = off; N = every N steps)
            mem_every = memory_log_every
            if mem_every is None:
                mem_every = logger.log_every * 10 if mem_stats else 0
            # per-interval peak tracking for the cadence rows: the
            # allocator's peak_bytes_in_use is a LIFETIME high-water mark
            # — it plateaus after the first big step and hides later
            # spikes. Watching whether it ADVANCED since the previous
            # sample recovers the interval's peak (the spike value when
            # it moved, the current bytes otherwise), appended to the
            # memory row after the existing fields.
            mem_peak_seen = (mem_stats or {}).get("peak_bytes_in_use")

            global_step = start_step
            logger.start_timer()
            if gp is not None:
                gp.loop_started()
            bringup.enter("bringup/first_batch")
            bringing_up = True
            last_save_t = time.monotonic()

            # one-step-delayed metric resolution: step k's scalars (loss +
            # the in-step health metrics) are FETCHED while step k+1
            # executes (copy_to_host_async starts the D2H as soon as the
            # values exist). A synchronous per-step fetch would insert one
            # host↔device round trip into every step and leave the device
            # idle for it. One step stays in flight, which also
            # throttles dispatch to the device rate. Rows land in the TSV
            # (and JSONL) in step order, one iteration later; the logged
            # duration is the inter-step interval (the sustained rate the
            # reference's clock measures, /root/reference/main.py:95-111).
            pending = None  # (step, epoch, idx, start, metrics, breakdown)

            def resolve(now):
                g, pe, pidx, pstart, dev_metrics, waits = pending
                # the one place a device-bound loop sleeps: the conversions
                # block until step g's scalars exist
                with span("fit/resolve_wait", step=g, tracer=tracer):
                    # integer metrics (nonfinite_grad_count, update_skipped)
                    # stay ints — float() here would defeat the sink's
                    # Integral-preserving serialization and land 3.0 in rows
                    # documented as integer counts
                    host = {
                        k: (v.tolist() if jnp.ndim(v) > 0
                            else int(v)
                            if jnp.issubdtype(v.dtype, jnp.integer)
                            else float(v))
                        for k, v in dev_metrics.items()
                    }
                loss_value = host["loss"]
                losses.append(loss_value)
                with span("fit/log", step=g, tracer=tracer):
                    logger.log_step(g, loss_value, now - pstart)
                    logger.print_progress(pe, pidx, loss_value)
                    if tel is not None:
                        data_wait_s, dispatch_s = waits
                        tel.on_step(
                            g, host, epoch=pe, interval_s=now - pstart,
                            data_wait_s=data_wait_s, dispatch_s=dispatch_s,
                        )
                    if repair_ctl is not None:
                        # skip-streak arithmetic, anchor promotion clock,
                        # and replay pricing — after tel.on_step, whose
                        # sentry/divergence publications may already have
                        # set a trigger this same resolve
                        repair_ctl.observe_step(
                            g, host, interval_s=now - pstart
                        )

            # a SIGTERM that lands while the consumer is BLOCKED on a
            # stalled input pipeline must still reach the graceful path:
            # the prefetch wait polls this flag and ends the stream early
            # (staged batches drain first), and the epoch loop's own check
            # below then takes the preemption branch
            stop_check = (
                (lambda: guard.tripped is not None) if guard.active else None
            )
            try:
              # the repair loop: one pass per trajectory segment. A
              # repair trigger breaks out of the epoch loop, the handler
              # below rolls back / skips / escalates, and the while
              # re-enters the epoch loop at the repaired cursor. A
              # repair-less run takes exactly one pass.
              while True:
                repair_request = None
                start_epoch = (
                    global_step // steps_per_epoch if steps_per_epoch else 0
                )
                skip_batches = (
                    global_step % steps_per_epoch if steps_per_epoch else 0
                )
                for e in range(start_epoch, epochs):
                    if guard.tripped is not None:
                        preempt_signum = guard.tripped
                        break
                    if hasattr(train_loader, "sampler"):
                        train_loader.sampler.set_epoch(e)
                    first_idx = skip_batches if e == start_epoch else 0
                    # the sampler order is deterministic per epoch, so starting
                    # at the first unconsumed batch resumes mid-epoch at the
                    # exact position the checkpoint was taken; iter_from skips
                    # at the index level (no discarded gather/transform work),
                    # islice is the fallback for foreign loaders
                    if first_idx and hasattr(train_loader, "iter_from"):
                        batches = train_loader.iter_from(first_idx)
                    elif first_idx:
                        batches = itertools.islice(iter(train_loader), first_idx, None)
                    else:
                        batches = iter(train_loader)
                    if chaos_inj is not None:
                        # the nanburst drill poisons batches by STEP
                        # position — the wrapper maps this epoch's stream
                        # onto the steps it will train
                        batches = chaos_inj.wrap_batches(
                            batches, global_step + 1
                        )
                    staged = prefetch_to_mesh(
                        batches, mesh,
                        depth=prefetch_depth, stage_fn=step.stage,
                        stop_check=stop_check, tracer=tracer,
                    )
                    # data-wait attribution: seconds this loop blocked on
                    # the prefetch queue (≈0 while the pipeline keeps up; →
                    # step time when the run is input-bound), as the
                    # `fit/next_batch` span and `last_wait_s` (breakdown
                    # rows, goodput)
                    staged = TimedIterator(
                        staged, step=global_step, tracer=tracer
                    )
                    for idx, batch in enumerate(staged, start=first_idx):
                        # step-boundary resilience hooks, BEFORE the next
                        # dispatch: chaos first (an injected SIGTERM must
                        # be visible to the guard check in this same
                        # iteration), then the graceful-preemption flag —
                        # so the last dispatched step is the one the
                        # emergency checkpoint persists
                        if chaos_inj is not None:
                            chaos_inj.maybe_fire(global_step)
                            state = chaos_inj.maybe_flip(
                                global_step, state, mesh
                            )
                        if guard.tripped is not None:
                            preempt_signum = guard.tripped
                            break
                        start = time.time()
                        global_step += 1
                        if bringing_up:
                            bringup.enter("bringup/first_dispatch")
                        dispatch_t0 = time.perf_counter()
                        with span(TRAIN_STEP, step=global_step,
                                  tracer=tracer, marks_step=True):
                            state, metrics = step(state, batch)
                        dispatch_s = time.perf_counter() - dispatch_t0
                        if bringing_up:
                            # the first dispatch has returned: the bring-up
                            # account closes (one `bringup` row); a compile
                            # from here on is a `recompile` warning
                            bringing_up = False
                            row = bringup.finish()
                            if tel is not None:
                                tel.set_bringup(global_step, row)
                        for v in metrics.values():
                            v.copy_to_host_async()
                        if tel is not None:
                            # run-health hooks (no-ops unless configured),
                            # AFTER dispatch: the batch sizes the MFU
                            # numerator once; the watchdog beat marks "the
                            # loop is alive" once per iteration — bring-up's
                            # first compile sits before the first beat and
                            # can't false-trip the deadline — and the
                            # divergence probe dispatches on the fresh state
                            # at its cadence (async; resolved one cadence
                            # later on the delayed pipeline)
                            with span("fit/health", step=global_step,
                                      tracer=tracer):
                                tel.observe_batch(batch)
                                tel.beat(global_step)
                                tel.observe_state(global_step, state)
                        # profiler schedule advances BEFORE resolve: resolve
                        # may arm the anomaly window, and arming after this
                        # iteration's step() means the window's countdown
                        # only starts at the NEXT annotated step — the full
                        # capture_steps budget lands on annotated steps
                        # (arming before it would burn one tick on the
                        # already-dispatched current iteration)
                        p.step()
                        if pending is not None:
                            resolve(start)
                        pending = (
                            global_step, e, idx, start, metrics,
                            (
                                staged.last_wait_s if breakdown else None,
                                dispatch_s,
                            ),
                        )
                        if (repair_ctl is not None
                                and repair_ctl.triggered is not None):
                            # a detector verdict became a trigger (set by
                            # the resolve above or by a probe verdict
                            # resolved during observe_state): break to the
                            # repair handler BEFORE the cadence save — the
                            # current state is suspect and must not become
                            # a checkpoint
                            repair_request = repair_ctl.take_trigger()
                            break
                        if mem_every and global_step % mem_every == 0:
                            with span("fit/memory_stats", step=global_step,
                                      tracer=tracer):
                                m = device_memory_stats()
                                interval_peak = None
                                if m:
                                    lp = m.get("peak_bytes_in_use")
                                    if lp is not None and (
                                            mem_peak_seen is None
                                            or lp > mem_peak_seen):
                                        interval_peak = lp
                                        mem_peak_seen = lp
                                    else:
                                        interval_peak = m.get("bytes_in_use")
                                logger.log_memory(
                                    m, peak_bytes_in_use=interval_peak
                                )
                        if ckpt is not None and (
                            (checkpoint_every
                             and global_step % checkpoint_every == 0)
                            or (checkpoint_every_s
                                and time.monotonic() - last_save_t
                                >= checkpoint_every_s)
                        ):
                            t_save = time.perf_counter()
                            # the save stall; the stream keeps the row's
                            # name from before the span had a prefix
                            with span("fit/checkpoint", step=global_step,
                                      tracer=tracer, row="checkpoint"):
                                saved = ckpt.save(state)
                            if saved and repair_ctl is not None:
                                # a new anchor CANDIDATE — promoted only
                                # after anchor_clean_steps clean steps
                                # (tpudist.resilience.repair)
                                repair_ctl.on_save(global_step)
                            if gp is not None:
                                gp.add(
                                    "checkpoint_s",
                                    time.perf_counter() - t_save,
                                )
                            last_save_t = time.monotonic()
                        if gp is not None:
                            gp.step_boundary(staged.last_wait_s)
                    # a trip during a stalled prefetch wait ends the batch
                    # stream early WITHOUT running the in-loop check —
                    # re-check here so a last-epoch stall still takes the
                    # preemption branch instead of reporting "completed"
                    if preempt_signum is None and guard.tripped is not None:
                        preempt_signum = guard.tripped
                    if preempt_signum is not None or repair_request is not None:
                        break
                if (repair_request is None and preempt_signum is None
                        and repair_ctl is not None
                        and repair_ctl.triggered is not None):
                    # a verdict resolved on the run's very last iteration:
                    # still repair (the rollback discards the poisoned
                    # tail; the clamped skip_to ends the run at the clean
                    # cursor) rather than report a poisoned "completed"
                    repair_request = repair_ctl.take_trigger()
                if repair_request is None or preempt_signum is not None:
                    break
                # ---- the repair ladder (tpudist.resilience.repair) ----
                # the in-flight delayed-fetch step belongs to the
                # discarded trajectory: drop it before anything else
                pending = None
                t_rep = time.perf_counter()
                total_steps = epochs * steps_per_epoch
                action = repair_ctl.plan(
                    repair_request, global_step, max_step=total_steps
                )  # raises RepairExhausted when the budget is spent
                if action.kind == "restart":
                    # rung 3: repeat trigger inside the window just
                    # repaired — persist the directive and ask the
                    # supervisor for a fresh process (exit 77). No save
                    # of the current (suspect) state.
                    repair_ctl.record(action)
                    if tel is not None:
                        tel.set_repair(action.row())
                    repair_exit = action
                    break
                # rungs 1+2: roll back to the last-known-good anchor
                # and skip the offending window (the shared
                # apply_rollback: restore, residual flush, suspect-save
                # quarantine, cursor jump)
                state = apply_rollback(
                    state, action.rollback_step, action.skip_to
                )
                global_step = action.skip_to
                # repair-generation salt: rebuild the step so dropout
                # masks and stochastic-rounding draws REDRAW on the
                # replayed span — a spike caused by one unlucky draw
                # heals on the redraw alone. Skipped when no stochastic
                # consumer exists: the rebuild would retrace for a
                # bit-identical program.
                needs_salt = (
                    float(getattr(model, "dropout", 0.0) or 0.0) > 0
                    or (step.grad_reducer is not None
                        and step.grad_reducer.method == "quantized")
                )
                if needs_salt:
                    step = build_step(
                        repair_policy.salted_seed(seed, action.salt)
                    )
                    if step.grad_reducer is not None:
                        state = step.grad_reducer.attach_residual(state)
                repair_ctl.record(action)
                if chaos_inj is not None:
                    # deterministic-bug drills (@*) re-arm: a bug that
                    # survives a rollback must keep biting until the
                    # budget circuit-breaks
                    chaos_inj.rearm()
                if tel is not None:
                    # sentry baseline/cooldown and pending health
                    # gathers describe the discarded trajectory
                    tel.reset_for_repair()
                    tel.set_repair(action.row())
                if gp is not None:
                    gp.add_repair(
                        time.perf_counter() - t_rep, action.replay_s
                    )
                last_save_t = time.monotonic()
            except BaseException as crash_exc:
                # flush the last completed step before the exception leaves:
                # the loss history and TSV then end at the step that actually
                # finished, not one row short — but never mask the original
                # exception with a fetch failure (e.g. the device itself died)
                if tel is not None:
                    # BEFORE the resolve: its on_step must not fetch a
                    # pending health gather that may sit queued behind
                    # the very collective that hung
                    tel.mark_crashing()
                if pending is not None:
                    try:
                        resolve(time.time())
                    except Exception:
                        pass
                    pending = None
                if tel is not None:
                    # crash-path run report (tpudist.telemetry.health):
                    # status + everything observed so far; never raises
                    tel.on_crash(crash_exc)
                raise
            else:
                if pending is not None:
                    resolve(time.time())
                    pending = None
                if preempt_signum is not None:
                    # graceful preemption: durability FIRST (the grace
                    # window can expire any second — the emergency
                    # checkpoint is synchronous, wait=True), then the run
                    # report with exit_reason="preempted"
                    if ckpt is not None and global_step > start_step:
                        t_save = time.perf_counter()
                        ckpt.save(state, wait=True)
                        if gp is not None:
                            gp.add_emergency_save(
                                time.perf_counter() - t_save
                            )
                    if tel is not None:
                        tel.finish(state.opt_state, status="preempted")
                elif repair_exit is not None:
                    # rung-3 exit: the directive is durable, the current
                    # state is suspect — no save; the report records the
                    # escalation before exit 77
                    if tel is not None:
                        tel.finish(state.opt_state, status="repair_restart")
                elif tel is not None:
                    tel.finish(state.opt_state)
            if (ckpt and preempt_signum is None and repair_exit is None
                    and global_step > start_step):
                ckpt.save(state)
    finally:
        # closed here, OUTSIDE the logger's context: the logger's __exit__
        # mirrors its TrainTime footer into the sink (dual-sink mode), so
        # the sink must outlive it (shutdown also stops the hang-watchdog
        # thread before the sink goes away)
        bringup.close()  # before the sink goes: its listeners write rows
        if guard is not None:
            guard.__exit__(None, None, None)
        if tel is not None:
            tel.shutdown()
        if ckpt:
            ckpt.close()
    if preempt_signum is not None:
        # everything durable (emergency checkpoint flushed, report
        # written, sink closed): hand the supervisor its exit code.
        # Preempted is a SystemExit(75) — scripts exit restartable with
        # no handler; library callers catch it for .state/.losses (the
        # checkpoint-less notebook run keeps its trained state)
        raise Preempted(preempt_signum, global_step,
                        state=state, losses=losses)
    if repair_exit is not None:
        # same discipline for the repair ladder's rung 3: directive and
        # report durable, exit with the restartable repair code (77) so
        # the supervisor relaunches and bring-up consumes the directive
        raise repair_mod.RepairRestart(repair_exit, global_step)
    return state, losses


def _padded_batches(loader, mesh: Mesh, key: str):
    """Yield ``(staged_batch, staged_row_mask, n_real_rows)`` with every
    batch padded (repeating the last row) to one constant row count and the
    padding masked — the one home for the ragged-final-batch math that both
    eval paths (:func:`evaluate`, :func:`evaluate_lm`) share.

    The pad target is the FIRST batch's row count (rounded up to the mesh's
    replica count), not merely the replica multiple: a ragged tail padded
    only to the replica count would present a new shape and trigger a fresh
    jit compile per distinct tail size per call. With a constant target the
    eval program compiles exactly once; the mask keeps the accounting exact.
    """
    dp = mesh_lib.data_parallel_size(mesh)
    target = None
    for batch in loader:
        # "_"-prefixed keys are per-step operands (e.g. the
        # DeviceCachedLoader's "_cache"), not row data: pass them through
        # to the compiled program untouched instead of fetching them to
        # host and "padding" them. Only the reserved prefix is exempt — a
        # foreign loader yielding jax.Arrays for ordinary row data keeps
        # the old np.asarray path.
        mesh_lib.check_reserved_device_keys(batch)
        passthrough = {
            k: v for k, v in batch.items() if k.startswith("_")
        }
        batch = {
            k: np.asarray(v)
            for k, v in batch.items()
            if k not in passthrough
        }
        n = batch[key].shape[0]
        if target is None:
            target = n + (-n % dp)
        # an oversize batch (foreign loader growing mid-stream) still pads to
        # its own replica multiple — one extra compile, never an error
        t = target if n <= target else n + (-n % dp)
        pad = t - n
        if pad:
            batch = {
                k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in batch.items()
            }
        mask = np.arange(t) < n
        batch = mesh_lib.shard_batch(batch, mesh)
        batch.update(passthrough)
        mask = mesh_lib.put_sharded(
            mask, mesh_lib.batch_sharding(mesh, extra_dims=0)
        )
        yield batch, mask, n


def evaluate_lm(
    model, state: TrainState, loader, mesh: Mesh | None = None,
    *, input_key: str = "tokens", chunk: int | None = None,
    input_transform: Callable | None = None,
) -> dict[str, float]:
    """Next-token CE and perplexity over a token-window loader — the LM
    counterpart of :func:`evaluate` (the reference's eval loop is
    classification-only and dormant, /root/reference/main.py:119-130).

    Scores EVERY window: a ragged final batch is padded to the mesh's
    replica count and masked out of both numerator and denominator.
    Multi-process accounting follows the global mask (see
    :func:`evaluate`), so per-process loaders may be identical full copies
    or disjoint shards — both score correctly, as long as every process
    yields the same number of batches (collectives run in lockstep).
    ``chunk`` scans the LM head over sequence chunks
    (:func:`tpudist.models.lm_utils.chunked_ce_sum`; nothing is
    differentiated here, so one head GEMM a chunk) so the [B,S,V] fp32
    logits never materialize — pass it whenever training needed
    ``chunked_lm_forward`` for the same reason, or eval will re-create the
    very HBM peak the training path avoided.
    ``input_transform`` mirrors :func:`make_train_step`'s hook (applied to
    the model INPUT only, never the CE targets) so a model trained through
    an in-graph transform evals through the same one.
    Returns ``{"loss": mean per-token CE, "perplexity": exp(loss)}``.
    """
    import math

    mesh = mesh or mesh_lib.create_mesh()

    if chunk:
        from tpudist.models.lm_utils import chunked_ce_sum, lm_head_weight

        @jax.jit
        def batch_ce(params, batch, mask):
            tokens = batch[input_key]
            inputs = _apply_input_transform(input_transform, tokens, batch)
            hidden = model.apply(
                {"params": params}, inputs, train=False, return_hidden=True
            )
            b, s = tokens.shape
            ce_sum = chunked_ce_sum(
                lm_head_weight(params), hidden[:, :-1], tokens[:, 1:],
                mask[:, None] * jnp.ones((b, s - 1)), chunk,
            )
            return ce_sum, jnp.sum(mask)
    else:

        @jax.jit
        def batch_ce(params, batch, mask):
            tokens = batch[input_key]
            inputs = _apply_input_transform(input_transform, tokens, batch)
            logits = model.apply({"params": params}, inputs, train=False)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            )
            return jnp.sum(jnp.where(mask[:, None], ce, 0.0)), jnp.sum(mask)

    total, positions = 0.0, 0
    for batch, mask, _ in _padded_batches(loader, mesh, input_key):
        s = batch[input_key].shape[1]
        # windows counted from the global mask, in-graph — same
        # replicated-or-sharded-safe accounting as evaluate()
        ce_sum, windows = batch_ce(state.params, batch, mask)
        total += float(ce_sum)
        positions += int(windows) * (s - 1)
    loss = total / max(positions, 1)
    # no silent clamp: a diverged model reports its true (astronomical)
    # perplexity, or inf past float range — never a cap masquerading as a
    # measurement
    ppl = math.exp(loss) if loss < 700.0 else float("inf")
    return {"loss": loss, "perplexity": ppl}


def evaluate(model, state: TrainState, loader, mesh: Mesh | None = None,
             *, input_key: str = "image", label_key: str = "label",
             input_transform: Callable | None = None) -> float:
    """Top-1 accuracy over a loader — the reference's dormant eval pass
    (/root/reference/main.py:119-130), alive and tested here.

    Scores EVERY sample: a final batch that doesn't divide the mesh's
    replica count is padded (repeating the last row) and the padding is
    masked out of the correct-count, so no val tail is silently dropped.

    Multi-process: both the hit-count and the denominator are sums over the
    global mask inside the compiled program, so each process's loader may
    be an identical full copy of the val set (the reference's convention,
    /root/reference/main.py:56-63) or its own disjoint shard (e.g. via
    ``DistributedSampler``) — both produce the correct global accuracy.
    The one requirement is lockstep: every process must yield the same
    number of batches, which both conventions satisfy.
    """
    mesh = mesh or mesh_lib.create_mesh()

    @jax.jit
    def count_correct(params, batch_stats, batch, mask):
        variables = {"params": params, "batch_stats": batch_stats}
        # same in-graph hook as make_train_step: a model trained on
        # device_normalize'd uint8 would otherwise silently score raw
        # 0..255 inputs here
        inputs = _apply_input_transform(input_transform, batch[input_key], batch)
        logits = model.apply(variables, inputs, train=False)
        hit = jnp.argmax(logits, axis=-1) == batch[label_key]
        # the denominator comes from the SAME global mask as the numerator,
        # in-graph: correct whether each process feeds an identical full val
        # loader (the reference's convention — every row counted
        # process_count times, in both sums) or its own disjoint shard. A
        # host-side `n × process_count` denominator would silently mis-scale
        # the sharded case.
        return jnp.sum(jnp.where(mask, hit, False)), jnp.sum(mask)

    cnt, total = 0, 0
    for batch, mask, _ in _padded_batches(loader, mesh, label_key):
        c, t = count_correct(state.params, state.batch_stats, batch, mask)
        cnt += int(c)
        total += int(t)
    return cnt / max(total, 1)
