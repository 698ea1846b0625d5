"""The ZAYA1 cell's per-layer readers: the name-stack fold that finds a
block's stages under recomputation and under XLA's own grouped-product
kernels, on a recorded trace (a GPT-2 program: it has blocks, and none of
the new stages or counters, which is also what the parent's trace has)."""

import json
import types

import pytest

from benchmarks import layers
from benchmarks.families import zaya
from benchmarks.layer_metrics import moe_ms
from benchmarks.tests.test_spans import ONE_CHIP_SPANS, recorded, run_context

NEW = ("moe_ms", "expert_gemm_roofline", "cca_mix_ms", "cca_attn_roofline",
       "expert_load_max_over_mean")


@pytest.mark.parametrize("tf_op,op,stage", [
    ("jit(step_fn)/jvp(Zaya)/h_0/cca_mix/mul:", "%fusion.1 = f32[]", "cca_mix"),
    ("jit(step_fn)/transpose(jvp(Zaya))/jvp(Zaya)/checkpoint/"
     "rematted_computation/h_12/moe_router/norm/mul:", "%fusion.2 = f32[]",
     "moe_router"),
    ("jit(step_fn)/transpose(jvp(Zaya))/jvp(Zaya)/checkpoint/h_3/"
     "moe_combine/gather:;jit(step_fn)/optimizer/mul:", "%fusion.3 = f32[]",
     "moe_combine"),
    ("ragged-dot-none", "%ragged-dot-none.47 = bf16[16384,2048]{1,0} "
     "custom-call(%a, %b)", "moe_experts"),
    ("jit(step_fn)/optimizer/convert_element_type:", "%fusion.4 = f32[]",
     None),
    ("", "%copy-done.3 = f32[]", None),
])
def test_stage_of_a_name_stack(tf_op, op, stage):
    assert moe_ms.stage_of(tf_op, op) == stage


def test_fold_finds_the_blocks_stages_in_a_recorded_trace(tmp_path, capsys):
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    table = moe_ms.block_scope_ms(ctx)
    assert {"qkv", "mlp_fc", "mlp_proj", "out"} <= set(table)
    assert all(ms > 0 for ms in table.values())
    assert moe_ms.block_scope_ms(ctx) is table  # made once
    assert capsys.readouterr().out.count("block_scope_ms") == 1
    assert moe_ms.stages_ms(ctx, lambda s: s.startswith("mlp_")) == \
        pytest.approx(table["mlp_fc"] + table["mlp_proj"])


def test_readers_give_nothing_where_the_program_lacks_what_they_read(tmp_path):
    """A program without the stages, the counters or the kernel's name (the
    parent): every new metric is left out of the line, none raises."""
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    with open(zaya.__file__.replace("families/zaya.py",
                                    "configs/zaya1-8b.json")) as f:
        config = json.load(f)
    ctx.update(family=zaya, config=config, telemetry_rows=[],
               traffic={"per_chip_batch": 4, "seq_len": 4096},
               window=types.SimpleNamespace(warmup_steps=6),
               device_kind="TPU v5 lite")
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW])
    assert len(only["per_layer"]) == len(NEW)
    assert layers.read_all(only, {"name": "zaya1_8b_train_s4096"}, ctx) == {}


def test_counters_are_read_from_the_windows_moe_rows():
    rows = [
        {"kind": "moe", "step": 3, "h_0/tokens": [9e9] * 8,
         "h_0/held_share": 9.0, "h_0/load_max_over_mean": 9.0},  # warm-up
        {"kind": "moe", "step": 10, "h_0/tokens": [1000.0] * 8,
         "h_0/held_share": 0.49, "h_0/load_max_over_mean": 1.1,
         "h_1/tokens": [1100.0] * 8, "h_1/held_share": 0.53,
         "h_1/load_max_over_mean": 1.3},
        {"kind": "step_breakdown", "step": 10, "dispatch_s": 0.004},
    ]
    ctx = {"telemetry_rows": rows,
           "window": types.SimpleNamespace(warmup_steps=6)}
    got = zaya.moe_counters(ctx)
    assert got["held_tokens"] == pytest.approx(8400.0)
    assert got["held_share"] == pytest.approx(0.51)
    assert got["load_max_over_mean"] == pytest.approx(1.2)
    assert zaya.moe_counters(dict(ctx, telemetry_rows=rows[2:])) is None


def test_costs_follow_the_issues_arithmetic():
    with open(zaya.__file__.replace("families/zaya.py",
                                    "configs/zaya1-8b.json")) as f:
        config = json.load(f)
    traffic = {"per_chip_batch": 4, "seq_len": 4096}
    assert zaya.train_flops_per_token(config, traffic) == \
        pytest.approx(0.905e9, rel=2e-3)
    cost = zaya.expert_gemm_cost(config, traffic, 8192.0)
    assert cost["flops"] == pytest.approx(4 * 9 * 2 * 8192 * 2048 * 2048)
    attn = zaya.attention_cost(config, traffic)
    assert attn["bwd"]["flops"] == 2 * attn["fwd"]["flops"]
    assert attn["calls_per_step"] == 4
