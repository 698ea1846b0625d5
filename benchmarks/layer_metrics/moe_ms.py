"""Device ms a traced step in the expert sublayers: exclusive time of the
ops under the program's ``h_<n>/moe_router``, ``moe_dispatch``,
``moe_experts`` and ``moe_combine`` scopes, forward, recomputed forward
and backward. Nothing where the program names no such scope.

``benchmarks/spans.py`` ``scope_ms`` folds an op's name stack to its first
two components, and two things hide a block's stages from that: per-block
recomputation puts ``checkpoint`` (and ``rematted_computation``) between
the model and the block in every backward op's stack, and XLA's own
grouped-product kernels (``ragged-dot-*`` custom calls) carry no stack at
all, only their op's name. :func:`block_scope_ms` reads the same trace
with the same exclusive times and looks for the block wherever it sits in
the stack; the ``ragged-dot`` kernels count under ``moe_experts``, the one
stage that calls them."""

import os
import re

from benchmarks import cell, spans, xplane

BLOCK = re.compile(r"^h_\d+$")
GROUPED_PRODUCT = "ragged-dot"
EXPERTS = "moe_experts"


def stage_of(tf_op: str, op: str) -> str | None:
    """``cca_mix`` for ``.../checkpoint/h_2/cca_mix/mul``: the component
    after the block's, whatever stands before it; ``None`` outside a
    block (or directly in it)."""
    if xplane.op_base(op).startswith(GROUPED_PRODUCT):
        return EXPERTS
    parts = [c for c in tf_op.split(";")[0].rstrip(":").split("/") if c]
    for i, part in enumerate(parts[:-1]):
        if BLOCK.match(part):
            return parts[i + 1]
    return None


def _fold(ctx) -> dict | None:
    """The first device's ops in the step window, folded by stage."""
    trace = spans.load(xplane.find_xplane(os.path.join(
        ctx["root"], cell.WORK_DIR, ctx["cell"]["name"], "trace")))
    device = min(trace["devices"])
    plane = trace["devices"][device]
    meta = trace["metadata"].get(device, {})
    window = xplane.step_window(plane["modules"])
    if window is None:
        return None
    lo, hi = window
    steps = sum(1 for _, start, _ in plane["modules"] if lo <= start < hi)
    ns = {}
    for op, start, end, own in xplane.exclusive_times(plane["ops"]):
        if end <= lo or start >= hi:
            continue
        stage = stage_of(meta.get(op, {}).get("tf_op", ""), op)
        if stage is not None:
            ns[stage] = ns.get(stage, 0) + own
    return {k: v / 1e6 / steps
            for k, v in sorted(ns.items(), key=lambda kv: -kv[1])}


def block_scope_ms(ctx) -> dict | None:
    """``{stage: device ms a traced step}`` over the first device's ops in
    the step window, all passes; made once a run. Nothing where the run
    was not traced."""
    if "block_scope_ms" not in ctx:
        ctx["block_scope_ms"] = _fold(ctx) if ctx.get("trace") else None
        if ctx["block_scope_ms"] is not None:
            cell.say(block_scope_ms=ctx["block_scope_ms"])
    return ctx["block_scope_ms"]


def stages_ms(ctx, wanted) -> float | None:
    """Sum over the stages ``wanted(name)`` accepts; nothing where the
    trace holds none of them."""
    table = block_scope_ms(ctx)
    found = [ms for name, ms in table.items() if wanted(name)] if table else []
    return sum(found) if found else None


def read(ctx):
    return stages_ms(ctx, lambda stage: stage.startswith("moe_"))
