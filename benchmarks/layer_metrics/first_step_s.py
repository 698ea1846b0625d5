"""Seconds from the start of ``fit``'s loop to the return of the first
dispatch: the phases ``bringup/first_batch`` (the input pipeline's start
and the first batch) and ``bringup/first_dispatch`` (trace, lowering and
compile or cache load of the step) of the ``bringup`` telemetry row.
Nothing where the program writes no such row."""

from benchmarks.layer_metrics.fit_bringup_s import FIRST_STEP, phase_s

SPANS = FIRST_STEP


def read(ctx):
    return phase_s(ctx, SPANS)
