"""The Kanana-2 cell's per-layer readers and its family's costs: the
name-stack fold finds the MLA stages and the shared expert under
recomputation, every new reader gives nothing on a program that lacks what
it reads (a recorded GPT-2 trace: what the parent's has), and the costs
follow the issue's arithmetic."""

import json
import types

import pytest

from benchmarks import layers
from benchmarks.families import kanana
from benchmarks.layer_metrics import moe_ms
from benchmarks.tests.test_spans import ONE_CHIP_SPANS, recorded, run_context

NEW = ("mla_attn_roofline", "mla_proj_ms", "moe_shared_ms", "moe_topk_ms")
TRAFFIC = {"per_chip_batch": 1, "seq_len": 8192}


def config() -> dict:
    with open(kanana.__file__.replace(
            "families/kanana.py", "configs/kanana-2-30b-a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tf_op,stage", [
    ("jit(step_fn)/jvp(Kanana)/h_0/mla_q/dot_general:", "mla_q"),
    ("jit(step_fn)/transpose(jvp(Kanana))/jvp(Kanana)/checkpoint/"
     "rematted_computation/h_3/mla_rope/concatenate:", "mla_rope"),
    ("jit(step_fn)/transpose(jvp(Kanana))/jvp(Kanana)/checkpoint/h_2/"
     "moe_shared/w_down/dot_general:", "moe_shared"),
    ("jit(step_fn)/jvp(Kanana)/h_1/mla_attn/transpose:", "mla_attn"),
])
def test_stage_of_a_name_stack(tf_op, stage):
    assert moe_ms.stage_of(tf_op, "%fusion.1 = f32[]") == stage


def test_readers_give_nothing_where_the_program_lacks_what_they_read(tmp_path):
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    ctx.update(family=kanana, config=config(), telemetry_rows=[],
               traffic=TRAFFIC, window=types.SimpleNamespace(warmup_steps=6),
               device_kind="TPU v5 lite")
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW])
    assert len(only["per_layer"]) == len(NEW)
    assert layers.read_all(only, {"name": "kanana2_30b_train_s8192"}, ctx) == {}


def test_costs_follow_the_issues_arithmetic():
    cfg = config()
    # 6 x 255.2M matmul weights a token (5 x 26.35M MLA, 37.75M dense, 4 x
    # 13.24M expert layer at 6 x 1/8 routed experts, 32.83M head) + 6 S H
    # (192 + 128) in 5 layers, the causal half not taken off: 12.5 + 20.6
    per_token = kanana.train_flops_per_token(cfg, TRAFFIC)
    weights = 5 * 26_345_472 + 37_748_736 + 4 * 13_238_272 + 32_833_536
    assert per_token == 6.0 * weights + 5 * 6.0 * 8192 * 32 * 320
    assert per_token * 8192 == pytest.approx(33.16e12, rel=1e-3)
    attn = kanana.attention_cost(cfg, TRAFFIC)
    # the issue's 687 GFLOP of an expert block's forward: causal half
    assert attn["fwd"]["flops"] == pytest.approx(687e9, rel=2e-3)
    assert attn["bwd"]["flops"] == 2 * attn["fwd"]["flops"]
    assert attn["calls_per_step"] == 5
    # bytes at the mathematics' widths: the rotary key once, not 32 times
    rows = 8192 * 2
    assert attn["fwd"]["bytes"] == rows * (
        32 * 192 + (32 * 128 + 64) + 2 * 32 * 128)
    cost = kanana.expert_gemm_cost(cfg, TRAFFIC, 6144.0)
    assert cost["flops"] == pytest.approx(4 * 9 * 2 * 6144 * 2048 * 768)
    assert kanana.expected_held_share(cfg) == 0.125


def test_bias_rule_puts_each_expert_among_the_chosen_of_its_share():
    """Minus the (k S / E)-th largest score of the sequence: exactly k S /
    E tokens of a sequence have a score at or over an expert's threshold."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(
        jax.random.normal(jax.random.key(0), (2, 256, 16)) + 0.2 * jnp.arange(16.0))
    biased = scores + kanana.sequence_quantile_bias(scores, top_k=4)
    assert ((biased >= 0).sum(axis=1) == 4 * 256 // 16).all()
