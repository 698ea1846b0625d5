"""The grouped expert products' share of their roofline: the least time the
chip could take for them — the family's ``expert_gemm_cost`` over the rows
the program's counter says were routed to held experts (not the
expectation), the larger of operations / peak FLOP/s and bytes / peak
bytes/s — over the device time under ``h_<n>/moe_experts`` a traced step:
the compiler's ``ragged-dot`` kernels (forward, recomputed forward,
backward) and the activation between them (``moe_ms``'s reading). Prints
the measured held share beside the expected one. Nothing where the
program has no such counter or scope."""

from benchmarks import cell, layers
from benchmarks.layer_metrics.moe_ms import EXPERTS, stages_ms


def read(ctx):
    family = ctx["family"]
    counters = getattr(family, "moe_counters", lambda ctx: None)(ctx)
    device_ms = stages_ms(ctx, lambda stage: stage == EXPERTS)
    if not counters or not device_ms:
        return None
    cost = family.expert_gemm_cost(ctx["config"], ctx["traffic"],
                                   counters["held_tokens"])
    peaks = layers.peaks(ctx)
    least_ms = 1e3 * max(cost["flops"] / peaks["bf16_flops_per_s"],
                         cost["bytes"] / peaks["hbm_bytes_per_s"])
    cell.say(held_share=counters["held_share"],
             held_share_expected=family.expected_held_share(ctx["config"]),
             held_tokens_a_layer=counters["held_tokens"],
             moe_rows=counters["rows"], expert_gemm_least_ms=least_ms,
             moe_experts_ms=device_ms)
    return 100.0 * least_ms / device_ms
