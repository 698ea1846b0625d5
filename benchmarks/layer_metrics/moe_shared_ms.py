"""Device ms a traced step in the shared expert: the ops under
``h_<n>/moe_shared`` (one SwiGLU on every token), forward, recomputed
forward and backward (``moe_ms``'s reading of the trace). Nothing where
the program names no such scope."""

from benchmarks.layer_metrics.moe_ms import stages_ms

STAGE = "moe_shared"


def read(ctx):
    return stages_ms(ctx, lambda stage: stage == STAGE)
