"""Device ms a traced step in latent attention (MLA) outside its kernel:
the ops under ``h_<n>/mla_q`` (the query projection), ``mla_kv_down`` (to
the key/value latent and the rotary key), ``mla_kv_up`` (the latent up to
each head's key and value), ``mla_rope`` (rotary, q and k put together)
and ``mla_out`` (the output projection), forward, recomputed forward and
backward (``moe_ms``'s reading of the trace). Nothing where the program
names no such scope."""

from benchmarks.layer_metrics.moe_ms import stages_ms

STAGES = ("mla_q", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_out")


def read(ctx):
    return stages_ms(ctx, lambda stage: stage in STAGES)
