"""The attention kernel's share of its roofline in compressed convolutional
attention: the accepted reader (``attn_roofline``) in the cell whose family
finds the kernel under the block's ``cca_attn`` scope (``ATTENTION_OPS``)
and counts its cost in the latent (8 query heads on 2 key/value heads,
causal half). The kernel's events include the forward that recomputation
runs again; the cost does not."""

from benchmarks.layer_metrics.attn_roofline import read  # noqa: F401
