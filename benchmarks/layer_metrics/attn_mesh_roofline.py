"""The attention kernel's share of its roofline where the step runs on a
mesh: the accepted reader (``attn_roofline``), in the cell whose kernels
run inside a ``shard_map``. It finds them by the block's name, which the
program puts back around the kernel there (``multi_head_attention(name=)``);
a program without that names them ``shard_map.<k>``, and the reader gives
nothing. Per chip: the family's cost and the summed kernel time are both
one device's."""

from benchmarks.layer_metrics.attn_roofline import read  # noqa: F401
