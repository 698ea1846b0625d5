"""Device ms a traced step in the optimizer: exclusive time of the ops under
the program's ``optimizer``, ``grad_clip`` and ``cast`` scopes
(``benchmarks/spans.py``). Nothing where the program names no such scope."""

from benchmarks import spans


def read(ctx):
    return spans.pass_ms(ctx, "opt")
