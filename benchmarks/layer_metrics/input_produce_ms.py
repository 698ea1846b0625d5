"""Mean ms of one ``input/produce``: one ``next()`` of the loader on the
producer thread (gather and host transforms) — the input layer's cost,
readable before it becomes a wait."""

from benchmarks import spans


def read(ctx):
    return spans.span_ms(ctx, ("input/produce",), "event_ms")
