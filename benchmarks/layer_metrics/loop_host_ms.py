"""Main-thread ms a traced step that must hide under one device step: the
dispatch (``tpudist_train``) and ``fit``'s bookkeeping spans, the waits
(``fit/next_batch``, ``fit/resolve_wait``) left out. Nothing where the
program emits no such span."""

from benchmarks import spans

SPANS = ("tpudist_train", "fit/log", "fit/health", "fit/memory_stats",
         "fit/checkpoint")


def read(ctx):
    return spans.span_ms(ctx, SPANS)
