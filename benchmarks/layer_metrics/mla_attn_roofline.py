"""The attention kernel's share of its roofline in latent attention (MLA):
the accepted reader (``attn_roofline``) in the cell whose family finds the
kernel under the block's ``mla_attn`` scope (``ATTENTION_OPS``) and counts
its cost at the widths the mathematics has — keys of 192, values of 128,
the rotary key once and not a head's worth each, causal half. The kernel's
events include the forward that recomputation runs again; the cost does
not."""

from benchmarks.layer_metrics.attn_roofline import read  # noqa: F401
