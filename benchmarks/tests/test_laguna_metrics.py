"""The Laguna cell's per-layer readers and its family's costs: the
name-stack fold finds the block's stages under recomputation, the cell's
three new readers and the expert layer's accepted readers it is appended
to give nothing on a program that lacks what they read (a recorded GPT-2
trace: what the parent's has), each kind's kernel pattern finds its own
calls and no other's, and the costs follow the issue's arithmetic."""

import json
import re
import types

import pytest

from benchmarks import layers
from benchmarks.families import laguna
from benchmarks.layer_metrics import moe_ms
from benchmarks.tests.test_spans import ONE_CHIP_SPANS, recorded, run_context

NEW = ("swa_attn_roofline", "full_attn_roofline", "lg_proj_ms")
# accepted readers of the expert layer that list the cell beside their own
APPENDED = ("moe_ms", "expert_gemm_roofline", "expert_load_max_over_mean",
            "moe_shared_ms")
TRAFFIC = {"per_chip_batch": 1, "seq_len": 8192}


def config() -> dict:
    with open(laguna.__file__.replace(
            "families/laguna.py", "configs/laguna-xs-2.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tf_op,stage", [
    ("jit(step_fn)/jvp(Laguna)/h_0/lg_qkv/dot_general:", "lg_qkv"),
    ("jit(step_fn)/transpose(jvp(Laguna))/jvp(Laguna)/checkpoint/"
     "rematted_computation/h_3/lg_gate/lg_gate/dot_general:", "lg_gate"),
    ("jit(step_fn)/transpose(jvp(Laguna))/jvp(Laguna)/checkpoint/h_2/"
     "lg_rope/concatenate:", "lg_rope"),
    ("jit(step_fn)/jvp(Laguna)/h_1/swa_attn/transpose:", "swa_attn"),
    ("jit(step_fn)/jvp(Laguna)/h_4/full_attn/transpose:", "full_attn"),
    ("jit(step_fn)/jvp(Laguna)/h_4/lg_out/mul:", "lg_out"),
])
def test_stage_of_a_name_stack(tf_op, stage):
    assert moe_ms.stage_of(tf_op, "%fusion.1 = f32[]") == stage


def test_readers_give_nothing_where_the_program_lacks_what_they_read(tmp_path):
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    ctx.update(family=laguna, config=config(), telemetry_rows=[],
               traffic=TRAFFIC, window=types.SimpleNamespace(warmup_steps=6),
               device_kind="TPU v5 lite")
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW + APPENDED])
    assert len(only["per_layer"]) == len(NEW + APPENDED)
    assert all(m["workloads"][-1] == "laguna_xs2_train_s8192"
               and (m["name"] in APPENDED) == (len(m["workloads"]) == 2)
               for m in only["per_layer"])
    assert layers.read_all(only, {"name": "laguna_xs2_train_s8192"}, ctx) == {}


def test_each_kinds_pattern_finds_its_own_kernel_only():
    names = ["swa_attn", "swa_attn.3", "full_attn", "full_attn.17",
             "bd_attn.2", "mla_attn.1", "h_3.2"]
    found = {kind: [n for n in names if re.match(ns.ATTENTION_OPS, n)]
             for kind, ns in laguna.KINDS.items()}
    assert found == {"sliding_attention": ["swa_attn", "swa_attn.3"],
                     "full_attention": ["full_attn", "full_attn.17"]}


def test_costs_follow_the_issues_arithmetic():
    cfg = config()
    per_token = laguna.train_flops_per_token(cfg, TRAFFIC)
    # matmul weights a token: the dense layer 92.28M (attention with the
    # gate 41.94M + SwiGLU 50.33M), three sliding layers of 54.53M
    # attention + 0.52M router + 3.15M shared + 8 x 1/16 x 3.15M routed,
    # one full of 41.94M + the same expert part, the 25.69M head
    attn = lambda h: 2048 * (h + 16) * 128 + 2 * h * 128 * 2048
    expert = 2048 * 256 + 3 * 2048 * 512 + 8 * (16 / 256) * 3 * 2048 * 512
    weights = attn(48) + 3 * 2048 * 8192 + 3 * (attn(64) + expert) \
        + attn(48) + expert + 12544 * 2048
    assert attn(48) == 41_943_040 and attn(64) == 54_525_952
    full, band = 8192 * 8193 // 2, 512 * 8192 - 512 * 511 // 2
    assert per_token == pytest.approx(
        6.0 * weights + 12.0 * 128 * (2 * 48 * full + 3 * 64 * band) / 8192,
        rel=1e-12)
    # the issue's "about 2.4e13 a step"
    assert per_token * 8192 == pytest.approx(2.31e13, rel=5e-3)
    sliding = laguna.KINDS["sliding_attention"].attention_cost(cfg, TRAFFIC)
    assert sliding["fwd"]["flops"] == 2 * 2.0 * band * 64 * 128
    assert sliding["bwd"]["flops"] == 2 * sliding["fwd"]["flops"]
    assert sliding["calls_per_step"] == 3
    # q and o at 64 heads, k and v at 8, bf16
    assert sliding["fwd"]["bytes"] == 8192 * 2 * (2 * 64 * 128 + 2 * 8 * 128)
    full_cost = laguna.KINDS["full_attention"].attention_cost(cfg, TRAFFIC)
    assert full_cost["fwd"]["flops"] == 2 * 2.0 * full * 48 * 128
    assert full_cost["calls_per_step"] == 2
    assert laguna.expected_held_share(cfg) == 1 / 16
    cost = laguna.expert_gemm_cost(cfg, TRAFFIC, 4096.0)
    assert cost["flops"] == pytest.approx(4 * 9 * 2 * 4096 * 2048 * 512)


def test_tile_shares_at_the_cells_shape():
    shares = laguna.tile_shares(config(), TRAFFIC)
    assert shares["blocks"] == [512, 512]
    assert shares["computed_tile_share"] == shares["fetched_tile_share"] \
        == 31 / 256
    assert shares["grid_step_share"] == 0.125 <= 0.25
    assert shares["needed_share"] == pytest.approx(0.06055, rel=1e-3)
