"""Mean time a step waited on the prefetch queue
(``step_breakdown.data_wait_s`` rows, window steps only)."""

import statistics

from benchmarks.layer_metrics.dispatch_ms import rows


def read(ctx):
    values = rows(ctx, "data_wait_s")
    return 1e3 * statistics.fmean(values) if values else None
