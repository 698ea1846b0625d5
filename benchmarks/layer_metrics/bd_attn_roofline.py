"""The attention kernel's share of its roofline under the block-diffusion
mask: the accepted reader (``attn_roofline``) in the cell whose family
finds the kernel under the block's ``bd_attn`` scope (``ATTENTION_OPS``)
and counts its cost at the pairs the mask NEEDS, ``B (L² + L b)`` a head
over the ``2 L`` rows of the noised and the clean copy — not at the tiles
the kernel computes. Prints the program's static counter of those tiles
(``computed_tile_share``) beside the needed share. Nothing where the
program has no such kernel."""

from benchmarks import cell
from benchmarks.layer_metrics import attn_roofline


def read(ctx):
    value = attn_roofline.read(ctx)
    shares = getattr(ctx["family"], "tile_shares", lambda *a: None)(
        ctx["config"], ctx["traffic"]) if value is not None else None
    if shares:
        cell.say(**shares)
    return value
