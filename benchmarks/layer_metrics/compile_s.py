"""Seconds of set-up covered by tracing, lowering and backend compile or
cache load (union of JAX's monitoring events), process start to window
open."""


def read(ctx):
    return ctx["meter"].compile_seconds(
        ctx["t_start"], ctx["window"].opened_at
    )
