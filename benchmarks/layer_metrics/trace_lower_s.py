"""Seconds of ``fit``'s bring-up under a trace or a lowering (the union of
JAX's ``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``
intervals, from ``fit``'s entry to the return of the first dispatch, as the
program's own listener took them: the ``bringup`` telemetry row's
``trace_lower_s``): the seconds of ``compile_s`` that no compile cache can
remove. Nothing where the program writes no such row."""

from benchmarks.layer_metrics.fit_bringup_s import bringup_row


def read(ctx):
    row = bringup_row(ctx)
    return None if row is None else row["trace_lower_s"]
