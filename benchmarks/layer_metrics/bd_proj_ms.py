"""Device ms a traced step in block-diffusion attention outside its
kernel: the ops under ``h_<n>/attn_qkv`` (the fused q/k/v projection),
``attn_qk_norm`` (RMSNorm of every head's q and k), ``attn_rope`` (rotary
at the rows' positions) and ``attn_out`` (the output projection), over the
``2 L`` rows of both copies, forward, recomputed forward and backward
(``moe_ms``'s reading of the trace). Nothing where the program names no
such scope."""

from benchmarks.layer_metrics.moe_ms import stages_ms

STAGES = ("attn_qkv", "attn_qk_norm", "attn_rope", "attn_out")


def read(ctx):
    return stages_ms(ctx, lambda stage: stage in STAGES)
