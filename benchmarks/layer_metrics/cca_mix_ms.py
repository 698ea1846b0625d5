"""Device ms a traced step in compressed convolutional attention outside
its kernel: the ops under ``h_<n>/cca_proj`` (the down-projection),
``cca_mix`` (q-k mean, convolutions, norms, rotary, value shift) and
``cca_out`` (the up-projection), forward, recomputed forward and backward
(``moe_ms``'s reading of the trace). Nothing where the program names no
such scope."""

from benchmarks.layer_metrics.moe_ms import stages_ms

STAGES = ("cca_proj", "cca_mix", "cca_out")


def read(ctx):
    return stages_ms(ctx, lambda stage: stage in STAGES)
