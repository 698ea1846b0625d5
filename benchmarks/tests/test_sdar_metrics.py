"""The SDAR cell's per-layer readers and its family's costs: the name-stack
fold finds the block's stages under recomputation, the three readers of
what only this program names give nothing on a program that lacks it (a
recorded GPT-2 trace: what the parent's has) while the head's reader reads
the ``loss_head`` scope every program has, and the costs follow the issue's
arithmetic."""

import json
import types

import pytest

from benchmarks import layers, spans
from benchmarks.families import sdar
from benchmarks.layer_metrics import bd_head_ms, moe_ms
from benchmarks.tests.test_spans import ONE_CHIP_SPANS, recorded, run_context

NEW = ("bd_attn_roofline", "bd_proj_ms", "bd_moe_ms", "bd_head_ms")
TRAFFIC = {"per_chip_batch": 1, "seq_len": 4096}


def config() -> dict:
    with open(sdar.__file__.replace(
            "families/sdar.py", "configs/sdar-30b-a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tf_op,stage", [
    ("jit(step_fn)/jvp(Sdar)/h_0/attn_qkv/dot_general:", "attn_qkv"),
    ("jit(step_fn)/transpose(jvp(Sdar))/jvp(Sdar)/checkpoint/"
     "rematted_computation/h_3/attn_qk_norm/q_norm/mul:", "attn_qk_norm"),
    ("jit(step_fn)/transpose(jvp(Sdar))/jvp(Sdar)/checkpoint/h_2/"
     "attn_rope/concatenate:", "attn_rope"),
    ("jit(step_fn)/jvp(Sdar)/h_1/bd_attn/transpose:", "bd_attn"),
    ("jit(step_fn)/jvp(Sdar)/h_4/attn_out/dot_general:", "attn_out"),
])
def test_stage_of_a_name_stack(tf_op, stage):
    assert moe_ms.stage_of(tf_op, "%fusion.1 = f32[]") == stage


def test_readers_give_nothing_where_the_program_lacks_what_they_read(tmp_path):
    """On the recorded GPT-2 trace (no ``bd_attn`` kernel, no ``attn_*`` or
    ``moe_*`` stage) three of the four are left out of the line and none
    raises; ``bd_head_ms`` is the sum of that trace's ``loss_head`` rows."""
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    ctx.update(family=sdar, config=config(), telemetry_rows=[],
               traffic=dict(TRAFFIC, block_length=4),
               window=types.SimpleNamespace(warmup_steps=6),
               device_kind="TPU v5 lite")
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW])
    assert len(only["per_layer"]) == len(NEW)
    got = layers.read_all(only, {"name": "sdar_30b_bd_train_s4096"}, ctx)
    assert set(got) <= {"bd_head_ms"}
    rows = {k: v for k, v in spans.of(ctx)["scope_ms"].items()
            if k.split(":")[1].startswith("loss_head")}
    if rows:
        assert got["bd_head_ms"]["value"] == pytest.approx(sum(rows.values()))
        assert bd_head_ms.read(ctx) > 0
    else:
        assert got == {}


def test_costs_follow_the_issues_arithmetic():
    cfg = config()
    # a layer: attention 18.87M (fused q/k/v 10.49M + o 8.39M), router
    # 0.26M, 8 x 1/8 routed experts 4.72M; 2 rows a trained token; the
    # untied head 38.90M once; attention 12 (L + b) x 32 x 128 a layer
    per_token = sdar.train_flops_per_token(cfg, TRAFFIC)
    layer = 18_874_368 + 262_144 + 4_718_592
    assert per_token == 6.0 * (2 * 5 * layer + 38_895_616) \
        + 5 * 12.0 * (4096 + 4) * 32 * 128
    assert per_token * 4096 == pytest.approx(10.95e12, rel=1e-3)
    attn = sdar.attention_cost(cfg, TRAFFIC)
    pairs = 4096 * 4096 + 4096 * 4
    assert sdar.needed_pairs(cfg, TRAFFIC) == pairs
    assert attn["fwd"]["flops"] == 2 * 2.0 * pairs * 32 * 128
    assert attn["bwd"]["flops"] == 2 * attn["fwd"]["flops"]
    assert attn["calls_per_step"] == 5
    # bytes over the 2 L rows: q and o at 32 heads, k and v at 4
    rows = 8192 * 2
    assert attn["fwd"]["bytes"] == rows * (2 * 32 * 128 + 2 * 4 * 128)
    assert attn["bwd"]["bytes"] == 2 * attn["fwd"]["bytes"]
    cost = sdar.expert_gemm_cost(cfg, TRAFFIC, 8192.0)
    assert cost["flops"] == pytest.approx(5 * 9 * 2 * 8192 * 2048 * 768)
    assert sdar.expected_held_share(cfg) == 0.125
    assert sdar.tokens_per_step(TRAFFIC, 1) == 4096
    assert sdar.rows_per_step(TRAFFIC, 1) == 8192


def test_tile_shares_are_the_programs_counter_beside_the_need():
    shares = sdar.tile_shares(config(), TRAFFIC)
    assert shares["computed_tile_share"] == 0.375
    assert shares["blocks"] == [512, 1024]
    assert shares["needed_share"] == pytest.approx(0.2502, abs=1e-4)


def test_stream_is_the_programs_transform_on_uniform_clean_ids():
    import numpy as np

    cfg = config()
    traffic = json.load(open(sdar.__file__.replace(
        "families/sdar.py", "traffic/bd_train_s4096_b1.json")))
    make = sdar.make_stream(cfg, traffic, 1)
    batch = make(np.random.Generator(np.random.PCG64(2**31 + 5)))()
    again = make(np.random.Generator(np.random.PCG64(2**31 + 5)))()
    assert {k: (v.shape, str(v.dtype)) for k, v in batch.items()} == {
        "tokens": ((1, 4096), "int32"), "clean": ((1, 4096), "int32"),
        "loss_weight": ((1, 4096), "float32")}
    for key in batch:
        np.testing.assert_array_equal(batch[key], again[key])
    assert batch["clean"].max() < traffic["mask_id"] == cfg["vocab_size"] - 1
    masked = batch["tokens"] == traffic["mask_id"]
    np.testing.assert_array_equal(masked, batch["loss_weight"] > 0)
    assert 0.4 < masked.mean() < 0.6
