"""Device ms a traced step in the head and loss of a block-diffusion step:
exclusive time of the ops under the program's ``loss_head`` scope, the
sweep that takes the loss and its gradient and what the cotangent still
does (``benchmarks/spans.py`` ``scope_ms``, both passes). In this cell the
head sees the noised rows only, half the rows of its stack. Nothing where
the trace holds no such scope."""

from benchmarks import spans

SCOPE = "loss_head"


def read(ctx):
    out = spans.of(ctx)
    found = [ms for key, ms in out["scope_ms"].items()
             if key.split(":", 1)[1].split("/")[0] == SCOPE] if out else []
    return sum(found) if found else None
