"""The full causal attention kernel's share of its roofline in a stack
that also has sliding-window layers: the accepted reader
(``attn_roofline``) over the calls of the family's ``full_attention`` kind
(``KINDS``), which the trace names after the block's ``full_attn`` scope,
with their cost at the causal triangle's pairs and that kind's own head
count. Nothing where the program has no such kernel."""

from benchmarks.layer_metrics import attn_roofline

KIND = "full_attention"


def read(ctx):
    kinds = getattr(ctx["family"], "KINDS", {})
    if KIND not in kinds:
        return None
    return attn_roofline.read(dict(ctx, family=kinds[KIND]))
