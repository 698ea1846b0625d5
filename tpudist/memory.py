"""HBM accounting: byte budgets BEFORE compile, live device stats after.

Memory discipline (docs/LM_TRAINING.md "Fitting ~1B parameters"): at ~1B
params on a 16 GB chip the question "will it fit?" must be answerable
before the first (minutes-long) compile, and the answer must be checkable
against what the device actually allocated. Three layers:

1. **per-tree bytes, exact** — :func:`tree_bytes` / :func:`per_device_bytes`
   work on concrete arrays, ``jax.eval_shape`` results, or (shape-tree,
   sharding-tree) pairs, so the params/master/moments budget costs one
   trace, no device.
2. **activation estimate, analytic** — :func:`transformer_activation_bytes`
   models the saved-residual footprint per remat policy (documented coarse
   coefficients; an estimate, clearly labeled as one).
3. **live stats** — :func:`device_memory_stats` surfaces the runtime
   allocator's view (``bytes_in_use``/``peak_bytes_in_use``/``bytes_limit``
   on TPU; ``None`` on backends that don't report, e.g. CPU), logged by
   ``fit()`` through ``MetricsLogger.log_memory``.

:func:`train_state_budget` assembles 1+2 into one report:
bytes-per-param for params / moments / activations, replicated
vs ``shard_state``, against a stated HBM budget.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import numpy as np

from tpudist.utils.tree import tree_bytes, tree_size

__all__ = [
    "tree_bytes",
    "tree_size",
    "per_device_bytes",
    "state_bytes",
    "transformer_activation_bytes",
    "train_state_budget",
    "device_memory_stats",
    "xla_memory_stats",
    "budget_columns",
    "format_budget",
]


def per_device_bytes(tree, shardings=None) -> int:
    """Bytes ONE device holds for ``tree``.

    ``tree`` may be concrete placed arrays (their own ``.sharding`` is
    used) or a shape tree (``jax.eval_shape`` output) paired with a
    matching ``shardings`` tree. Replicated leaves count in full; sharded
    leaves count their largest single-device shard (ceil division — the
    padded shard is what the allocator actually reserves).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if shardings is not None:
        # flatten the shardings UP TO the value tree's structure: a
        # structural mismatch raises (never a silent zip truncation), and
        # a None left at a leaf position survives as "replicated" instead
        # of being dropped by tree_leaves and misaligning every later pair
        shard_leaves = treedef.flatten_up_to(shardings)
    else:
        shard_leaves = [getattr(x, "sharding", None) for x in leaves]
    total = 0
    for x, s in zip(leaves, shard_leaves):
        shape = tuple(np.shape(x)) if not hasattr(x, "shape") else tuple(x.shape)
        if s is not None and hasattr(s, "shard_shape"):
            try:
                shape = s.shard_shape(shape)
            except ValueError:
                # an indivisible dim (e.g. an unpadded vocab under a
                # tensor split): jax refuses the placement at runtime,
                # but the BUDGET question "what would one chip hold" is
                # still answerable — ceil per dim, the padded shard the
                # allocator would reserve
                shape = _ceil_shard_shape(shape, s)
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(x.dtype).itemsize
    return total


def _ceil_shard_shape(shape, sharding) -> tuple:
    """Ceil-division per-device shard shape from a NamedSharding's spec —
    the fallback for dims the mesh axes don't divide evenly."""
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return shape
    out = list(shape)
    for i, part in enumerate(spec):
        if part is None or i >= len(out):
            continue
        names = part if isinstance(part, tuple) else (part,)
        factor = 1
        for name in names:
            if name is not None:
                factor *= int(mesh.shape[name])
        out[i] = -(-out[i] // factor)
    return tuple(out)


def state_bytes(state, shardings=None) -> dict[str, dict[str, int]]:
    """Per-component byte table for a TrainState(-shaped) tree.

    Returns ``{component: {"global": bytes, "per_device": bytes}}`` for
    ``params`` / ``opt_state`` / ``batch_stats`` plus a ``total`` row.
    ``state`` may be concrete or an ``eval_shape`` result (then pass the
    matching ``shardings`` tree, e.g. from ``optim.shard_state``'s
    ``state_shardings`` — that pairing is how the pre-compile budget knows
    the moments will live at ~1/world_size per chip).
    """
    out: dict[str, dict[str, int]] = {}
    total_g = total_d = 0
    for name in ("params", "opt_state", "batch_stats"):
        sub = getattr(state, name, None)
        if sub is None:
            continue
        sh = getattr(shardings, name, None) if shardings is not None else None
        g = tree_bytes(sub)
        d = per_device_bytes(sub, sh)
        out[name] = {"global": g, "per_device": d}
        total_g += g
        total_d += d
    out["total"] = {"global": total_g, "per_device": total_d}
    return out


def transformer_activation_bytes(
    batch: int,
    seq: int,
    hidden: int,
    depth: int,
    *,
    num_heads: int | None = None,
    remat_policy: str | bool | None = "none",
    dtype_bytes: int = 2,
    ffn_mult: int = 4,
    attention_scores: bool = False,
) -> int:
    """ESTIMATED live activation bytes of one transformer microbatch's
    forward, as held for backward under ``remat_policy``.

    Coarse per-token-per-layer accounting (bf16 default), stated so the
    numbers are auditable rather than mysterious:

    - ``none``: every block internal is saved — residual in + 2 norms +
      qkv (3H) + attn out + proj in + mlp up (ffn_mult·H) + gelu
      (ffn_mult·H) + proj ≈ ``(8 + 2·ffn_mult)·H`` per layer;
    - ``dots_saveable``: dot/MXU outputs only — qkv (3H) + attn out +
      mlp up (ffn_mult·H) + proj ≈ ``(5 + ffn_mult)·H``. The "attn out"
      holds for the Pallas attention kernels too: their forward rules name
      ``o`` (and the [B,H,S] float32 ``lse``, not counted here) and the
      policy keeps the names (``tpudist/remat.py`` ``KERNEL_RESIDUALS``);
    - ``full`` / ``save_nothing`` (per-block checkpoint): the inter-block
      residual stream (1·H per layer) plus ONE block's internals live
      during its recompute.

    ``attention_scores=True`` adds the [B, heads, S, S] score matrix per
    layer (the XLA-attention path; the fused kernels never materialize
    it). Plus the embedding output once. This is an estimate for budget
    tables — the measured check is :func:`device_memory_stats`.
    """
    per_tok = {
        "none": (8 + 2 * ffn_mult) * hidden,
        "dots_saveable": (5 + ffn_mult) * hidden,
        "full": hidden,
        "save_nothing": hidden,
    }
    key = {False: "none", None: "none", True: "full"}.get(
        remat_policy, remat_policy
    )
    if key not in per_tok:
        raise ValueError(f"unknown remat policy {remat_policy!r}")
    tokens = batch * seq
    per_layer = per_tok[key] * tokens
    if attention_scores and key in ("none", "dots_saveable"):
        per_layer += (num_heads or 1) * batch * seq * seq
    total = depth * per_layer + tokens * hidden  # + embedding output
    if key in ("full", "save_nothing"):
        # one block's internals, alive during its backward recompute
        total += (8 + 2 * ffn_mult) * hidden * tokens
    return int(total) * dtype_bytes


def train_state_budget(
    model,
    tx,
    sample_input,
    *,
    batch: int,
    seq: int,
    world_size: int = 1,
    remat_policy: str | bool | None = "none",
    grad_dtype_bytes: int = 4,
    hbm_budget_bytes: int = 16 * 1024**3,
    workspace_fraction: float = 0.08,
    plan=None,
) -> dict[str, Any]:
    """The pre-compile fits-or-not report for one LM training config.

    One ``jax.eval_shape`` trace (no device, no compile — a ~1B model
    costs seconds on a laptop) yields exact params/opt-state bytes;
    activations come from :func:`transformer_activation_bytes` using the
    model's ``hidden_dim``/``depth``/``num_heads`` fields; gradients count
    one params-sized fp32 tree (the donated step's transient);
    ``workspace_fraction`` reserves allocator/fusion scratch. Optimizer
    state divides by ``world_size`` when ``tx`` is a
    ``tpudist.optim.shard_state`` wrapper (its own ``state_shardings``
    rule is consulted leaf-for-leaf — exact, not world_size-rounded).

    Returns a dict with per-component bytes (global and per-chip), the
    per-chip total, ``fits`` against ``hbm_budget_bytes``, and
    ``bytes_per_param`` — one row of a budget table.

    ``plan`` (:class:`tpudist.parallel.plan.ParallelPlan`) makes the
    whole table PER-CHIP under the composed placement: params and
    gradients count their largest single-chip shard (the plan's resolved
    metadata+fsdp shardings — exact, from the same ``eval_shape``),
    opt-state follows the plan's ZeRO-1 overlay (pass the
    ``plan.wrap_zero1``-wrapped ``tx``), and the activation ESTIMATE is
    scaled by the plan's axes (batch over ``data×fsdp``, depth over
    ``pipe``, block internals over ``tensor`` — coarse like the base
    estimate, labeled as one). This is the pre-compile answer to "does
    this geometry fit ONLY under the plan?".
    """
    import jax.numpy as jnp

    # boxed init so the plan can read the Megatron/pipe metadata; tree
    # math sees through the boxes, so the plan-less path is unchanged
    params_boxed = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.asarray(sample_input), train=False
        )["params"]
    )
    from flax import linen as nn

    params_shapes = nn.meta.unbox(params_boxed)
    n_params = tree_size(params_shapes)
    params_global = tree_bytes(params_shapes)
    params_bytes = params_global
    if plan is not None:
        params_bytes = per_device_bytes(
            params_shapes, plan.shardings(params_boxed)
        )
    opt_shapes = jax.eval_shape(tx.init, params_shapes)
    opt_global = tree_bytes(opt_shapes)
    if plan is not None:
        opt_per_chip = per_device_bytes(
            opt_shapes, plan.opt_state_shardings(params_boxed, tx)
        )
    elif hasattr(tx, "state_shardings"):
        opt_per_chip = per_device_bytes(
            opt_shapes, tx.state_shardings(params_shapes)
        )
    else:
        opt_per_chip = opt_global
    depth = int(getattr(model, "depth", 0) or 0)
    act_batch, act_depth, act_div = batch, depth, 1
    if plan is not None:
        # per-chip activation scaling, coarse by construction: each chip
        # sees batch/(data·fsdp) rows, depth/pipe layers, and 1/tensor of
        # every block-internal (qkv/ffn activations shard with their
        # kernels' output dims)
        act_batch = max(batch // (plan.data * plan.fsdp), 1)
        act_depth = max(-(-depth // plan.pipe), 1) if depth else depth
        act_div = plan.tensor
    acts = transformer_activation_bytes(
        act_batch, seq, int(getattr(model, "hidden_dim", 0) or 0),
        act_depth,
        num_heads=getattr(model, "num_heads", None),
        remat_policy=remat_policy,
        # "auto" may dispatch to the XLA path (shape-dependent), so it
        # counts the [B,H,S,S] scores too — over-budgeting is the safe
        # direction for a fits verdict; only an explicit kernel choice
        # (vmem/flash, which never materialize scores) drops the term
        attention_scores=getattr(model, "attn_impl", "xla") in ("xla", "auto"),
    ) // max(act_div, 1)
    # gradients are params-shaped transients: under a plan they live at
    # the params' sharded footprint (GSPMD reduce-scatters them), scaled
    # from the sharded params ratio so mixed fp32/bf16 trees stay honest
    grads = n_params * grad_dtype_bytes
    if plan is not None and params_global:
        grads = int(grads * params_bytes / params_global)
    subtotal = params_bytes + opt_per_chip + acts + grads
    per_chip_total = int(subtotal * (1.0 + workspace_fraction))
    out = {
        "n_params": int(n_params),
        "world_size": int(world_size),
        "remat_policy": str(remat_policy),
        "params_bytes": int(params_bytes),
        "opt_state_bytes_global": int(opt_global),
        "opt_state_bytes_per_chip": int(opt_per_chip),
        "grad_bytes": int(grads),
        "activation_bytes_est": int(acts),
        "workspace_bytes_est": int(per_chip_total - subtotal),
        "per_chip_total_bytes": per_chip_total,
        "hbm_budget_bytes": int(hbm_budget_bytes),
        "fits": bool(per_chip_total <= hbm_budget_bytes),
        "bytes_per_param": round(per_chip_total / max(n_params, 1), 2),
    }
    if plan is not None:
        out["params_bytes_global"] = int(params_global)
        out["plan"] = plan.describe()
        out.update(plan.axis_worlds())
    return out


def xla_memory_stats(compiled) -> dict[str, int] | None:
    """The compiler's own static HBM breakdown of a COMPILED program
    (``Compiled.memory_analysis()``, normalized by
    :func:`tpudist.telemetry.anatomy.program_memory`): argument / output /
    temp / generated-code bytes and the resident-sum ``peak_bytes``. The
    middle column of the budget table — between the pre-compile estimate
    and the live allocator — and fail-soft ``None`` on backends (or
    merely-lowered objects) that don't implement memory analysis."""
    from tpudist.telemetry.anatomy import program_memory

    return program_memory(compiled)


def budget_columns(report: Mapping[str, Any] | None = None, *,
                   compiled=None, device=None) -> dict[str, int | None]:
    """The three-source HBM comparison row: the
    pre-compile analytic ESTIMATE, the compiler's XLA-STATIC reservation,
    and the LIVE allocator peak — each ``None`` where its source is
    unavailable (no report / no compiled program / a CPU backend), never
    a fabricated number. Estimate ≫ static usually means a stale
    activation model; live ≫ static means fragmentation or an allocator
    the program doesn't own alone."""
    xla = xla_memory_stats(compiled) if compiled is not None else None
    live = device_memory_stats(device)
    return {
        "estimate_bytes": (
            None if report is None else report.get("per_chip_total_bytes")
        ),
        "xla_static_bytes": None if xla is None else xla.get("peak_bytes"),
        "live_peak_bytes": (
            None if live is None else live.get("peak_bytes_in_use")
        ),
    }


def format_budget(report: Mapping[str, Any], *,
                  xla_static_bytes: int | None = None,
                  live_peak_bytes: int | None = None) -> str:
    """One human line per component, GB with the fits verdict.
    ``xla_static_bytes`` /
    ``live_peak_bytes`` (from :func:`budget_columns`) append the measured
    columns next to the estimate when a compiled program / a reporting
    backend is at hand; ``None`` (the default, and what fail-soft sources
    return) leaves the line byte-identical to the estimate-only form."""
    gb = 1024**3

    def f(k):
        return f"{report[k] / gb:.2f}"

    line = (
        f"params {f('params_bytes')} GB + opt_state "
        f"{f('opt_state_bytes_per_chip')} GB/chip "
        f"(global {f('opt_state_bytes_global')}) + grads {f('grad_bytes')} "
        f"GB + acts~{f('activation_bytes_est')} GB (remat="
        f"{report['remat_policy']}) + ws~{f('workspace_bytes_est')} GB = "
        f"{f('per_chip_total_bytes')} GB/chip vs {f('hbm_budget_bytes')} GB"
        f" -> {'FITS' if report['fits'] else 'DOES NOT FIT'} "
        f"({report['bytes_per_param']} B/param, world={report['world_size']})"
    )
    if xla_static_bytes is not None:
        line += f" | xla-static {xla_static_bytes / gb:.2f} GB"
    if live_peak_bytes is not None:
        line += f" | live-peak {live_peak_bytes / gb:.2f} GB"
    return line


def device_memory_stats(device=None) -> dict[str, int] | None:
    """Live allocator stats for one device — ``bytes_in_use`` /
    ``peak_bytes_in_use`` / ``bytes_limit`` (whatever subset the backend
    reports), or ``None`` where unsupported (CPU). The measured
    counterpart of :func:`train_state_budget`."""
    device = device or jax.local_devices()[0]
    stats = device.memory_stats()
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_free_block_bytes")
    out = {k: int(v) for k, v in stats.items() if k in keep}
    return out or {k: int(v) for k, v in stats.items()}
