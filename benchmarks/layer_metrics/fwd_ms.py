"""Device ms a traced step in the forward pass: exclusive time of the ops
whose JAX name stack holds ``jvp(`` and no ``transpose(`` (the loss head
included), read from the trace's op metadata by ``benchmarks/spans.py``."""

from benchmarks import spans


def read(ctx):
    return spans.pass_ms(ctx, "fwd")
