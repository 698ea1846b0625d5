"""The sliding-window attention kernel's share of its roofline: the
accepted reader (``attn_roofline``) over the calls of the family's
``sliding_attention`` kind (``KINDS``), which the trace names after the
block's ``swa_attn`` scope, with their cost counted at the pairs the
window NEEDS (about ``W S`` a head), not at the tiles the kernel computes.
Prints the program's static counters beside the need: the share of the
``S²`` tiles computed (``computed_tile_share``), of the tiles whose block
is fetched (``fetched_tile_share``) and of the grid steps run
(``grid_step_share``). Nothing where the program has no such kernel."""

from benchmarks import cell
from benchmarks.layer_metrics import attn_roofline

KIND = "sliding_attention"


def read(ctx):
    kinds = getattr(ctx["family"], "KINDS", {})
    if KIND not in kinds:
        return None
    value = attn_roofline.read(dict(ctx, family=kinds[KIND]))
    shares = ctx["family"].tile_shares(ctx["config"], ctx["traffic"]) \
        if value is not None else None
    if shares:
        cell.say(**shares)
    return value
