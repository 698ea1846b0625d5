"""Device ms a traced step in the backward pass: exclusive time of the ops
under ``transpose(jvp(``, the all-reduces GSPMD puts among them, and the
program's own ``grad_exchange`` (``benchmarks/spans.py``)."""

from benchmarks import spans


def read(ctx):
    return spans.pass_ms(ctx, "bwd")
