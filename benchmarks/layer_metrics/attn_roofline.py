"""The attention kernel's share of its roofline: the least time the chip
could take for the step's attention calls — per call the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from the call's shapes
(the family's ``attention_cost``), forward and backward — over the summed
device time of the kernel's events in the traced window. Nothing to read
(no kernel in the compiled step, or none under a name the family's pattern
knows) gives nothing."""

import re

from benchmarks import layers


def read(ctx):
    trace, family = ctx["trace"], ctx["family"]
    pattern = getattr(family, "ATTENTION_OPS", None)
    if not trace or not pattern or not trace["steps"]:
        return None
    custom = set(trace["custom_call_ops"])
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if name in custom and re.match(pattern, name))
    if seconds <= 0:
        return None
    peaks = layers.peaks(ctx)
    cost = family.attention_cost(ctx["config"], ctx["traffic"])
    least = sum(
        max(cost[d]["flops"] / peaks["bf16_flops_per_s"],
            cost[d]["bytes"] / peaks["hbm_bytes_per_s"])
        for d in ("fwd", "bwd")
    ) * cost["calls_per_step"] * trace["steps"]
    return 100.0 * least / seconds
