"""Mean main-thread ms a traced step in ``input/stage``: sharding one
batch and its ``device_put``."""

from benchmarks import spans


def read(ctx):
    return spans.span_ms(ctx, ("input/stage",))
