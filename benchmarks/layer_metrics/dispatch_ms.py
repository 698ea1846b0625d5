"""Median host time to dispatch one step (``step_breakdown.dispatch_s``
rows of ``fit``'s telemetry, window steps only)."""

import statistics


def rows(ctx, field):
    first = ctx["window"].warmup_steps
    return [r[field] for r in ctx["telemetry_rows"]
            if r.get("kind") == "step_breakdown" and r.get("step", 0) > first
            and r.get(field) is not None]


def read(ctx):
    values = rows(ctx, "dispatch_s")
    return 1e3 * statistics.median(values) if values else None
