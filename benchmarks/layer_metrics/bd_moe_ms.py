"""Device ms a traced step in the expert layers of a block-diffusion step:
the ops under ``h_<n>/moe_router``, ``moe_dispatch``, ``moe_experts`` (the
compiler's ``ragged-dot`` kernels among them) and ``moe_combine``, forward,
recomputed forward and backward (``moe_ms``'s reading of the trace;
``moe_ms`` itself lists its cells). The layer sees the ROWS of the stack,
``2 L`` a sequence, not the ``L`` trained tokens: prints the measured share
of the (row, choice) pairs whose expert is held and the pairs a held
expert got beside the expected from the rows. Nothing where the program
names no such scope."""

from benchmarks import cell
from benchmarks.layer_metrics.moe_ms import stages_ms

STAGES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")


def read(ctx):
    value = stages_ms(ctx, lambda stage: stage in STAGES)
    family, config = ctx["family"], ctx["config"]
    counters = getattr(family, "moe_counters", lambda ctx: None)(ctx)
    if value is not None and counters:
        pairs = family.rows_per_step(ctx["traffic"], 1) \
            * config["num_experts_per_tok"]
        cell.say(held_share=counters["held_share"],
                 held_share_expected=family.expected_held_share(config),
                 rows_a_held_expert=counters["held_tokens"]
                 / config["num_experts_held"],
                 rows_a_held_expert_expected=pairs / config["num_experts"],
                 load_max_over_mean=counters["load_max_over_mean"],
                 moe_rows=counters["rows"])
    return value
