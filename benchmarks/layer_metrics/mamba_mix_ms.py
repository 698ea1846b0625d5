"""Device ms a traced step in the Mamba-2 mixers outside their scan: the
ops under ``h_<n>/mamba_in_proj`` (the in projection to ``z``, ``xBC`` and
``dt``), ``mamba_conv`` (the causal depthwise convolution, its bias and
SiLU), ``mamba_gate_norm`` (``y ⊙ silu(z)`` and the RMSNorm over groups)
and ``mamba_out_proj`` (the out projection), forward, recomputed forward
and backward (``moe_ms``'s reading of the trace). Nothing where the
program names no such scope."""

from benchmarks.layer_metrics.moe_ms import stages_ms

STAGES = ("mamba_in_proj", "mamba_conv", "mamba_gate_norm", "mamba_out_proj")


def read(ctx):
    return stages_ms(ctx, lambda stage: stage in STAGES)
