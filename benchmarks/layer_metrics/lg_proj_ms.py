"""Device ms a traced step in the Laguna block's attention outside its
kernel, both kinds of layer: the ops under ``h_<n>/lg_qkv`` (the fused
q/k/v projection), ``lg_gate`` (the output gate's projection and sigmoid),
``lg_rope`` (YaRN on half of each head in the full layers, plain rotary on
the whole head in the sliding ones) and ``lg_out`` (the gate's product and
the output projection), forward, recomputed forward and backward
(``moe_ms``'s reading of the trace). Nothing where the program names no
such scope."""

from benchmarks.layer_metrics.moe_ms import stages_ms

STAGES = ("lg_qkv", "lg_gate", "lg_rope", "lg_out")


def read(ctx):
    return stages_ms(ctx, lambda stage: stage in STAGES)
