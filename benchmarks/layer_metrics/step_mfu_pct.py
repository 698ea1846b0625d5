"""Whole step's share of the chip's peak: the family's model operations per
token x the tokens of ALL steps in the window / window / chips / peak."""

from benchmarks import layers


def read(ctx):
    flops = ctx["family"].train_flops_per_token(ctx["config"], ctx["traffic"])
    rate = ctx["e2e"]["tokens_per_s_per_chip"]
    return 100.0 * flops * rate / layers.peaks(ctx)["bf16_flops_per_s"]
