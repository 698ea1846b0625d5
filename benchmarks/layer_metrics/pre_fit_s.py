"""Seconds from process start to ``fit``'s entry: the interpreter, the
imports, the TPU client, the harness's build of the cell and its weights —
the part of ``setup_s`` that is not the program's to shorten. ``fit`` gives
its entry on ``time.perf_counter`` in its ``bringup`` telemetry row
(``t_entry_perf``). Nothing where the program writes no such row."""

from benchmarks.layer_metrics.fit_bringup_s import bringup_row


def read(ctx):
    row = bringup_row(ctx)
    return None if row is None else row["t_entry_perf"] - ctx["t_start"]
