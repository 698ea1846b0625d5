"""The Nemotron-H cell's per-layer readers and its family's costs: the
name-stack fold finds the Mamba-2 block's stages under recomputation, the
cell's two new readers and the expert layer's accepted readers it is
appended to give nothing on a program that lacks what they read (a
recorded GPT-2 trace: what the parent's has), and the costs follow the
published widths' arithmetic."""

import json
import types

import pytest

from benchmarks import layers
from benchmarks.families import nemotron_h
from benchmarks.layer_metrics import moe_ms
from benchmarks.tests.test_spans import ONE_CHIP_SPANS, recorded, run_context

CELL = "nemotron3_nano_train_s8192"
NEW = ("ssd_roofline", "mamba_mix_ms")
# accepted readers of the expert layer that list the cell beside their own
APPENDED = ("moe_ms", "expert_gemm_roofline", "expert_load_max_over_mean",
            "moe_shared_ms")
TRAFFIC = {"per_chip_batch": 1, "seq_len": 8192}


def config() -> dict:
    with open(nemotron_h.__file__.replace(
            "families/nemotron_h.py",
            "configs/nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tf_op,stage", [
    ("jit(step_fn)/jvp(NemotronH)/h_0/mamba_in_proj/mamba_in_proj/"
     "dot_general:", "mamba_in_proj"),
    ("jit(step_fn)/transpose(jvp(NemotronH))/jvp(NemotronH)/checkpoint/"
     "rematted_computation/h_2/mamba_conv/mul:", "mamba_conv"),
    ("jit(step_fn)/jvp(NemotronH)/h_4/ssd_scan/pallas_call:", "ssd_scan"),
    ("jit(step_fn)/transpose(jvp(NemotronH))/h_4/ssd_scan/while/body/"
     "dot_general:", "ssd_scan"),
    ("jit(step_fn)/jvp(NemotronH)/h_0/mamba_gate_norm/mul:",
     "mamba_gate_norm"),
    ("jit(step_fn)/jvp(NemotronH)/h_5/gqa_attn/transpose:", "gqa_attn"),
])
def test_stage_of_a_name_stack(tf_op, stage):
    assert moe_ms.stage_of(tf_op, "%fusion.1 = f32[]") == stage


def test_readers_give_nothing_where_the_program_lacks_what_they_read(tmp_path):
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    ctx.update(family=nemotron_h, config=config(), telemetry_rows=[],
               traffic=TRAFFIC, window=types.SimpleNamespace(warmup_steps=6),
               device_kind="TPU v5 lite")
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW + APPENDED])
    assert len(only["per_layer"]) == len(NEW + APPENDED)
    assert all(m["workloads"][-1] == CELL for m in only["per_layer"])
    assert all((m["name"] in NEW) == (m["workloads"] == [CELL])
               for m in only["per_layer"])
    assert layers.read_all(only, {"name": CELL}, ctx) == {}


def test_costs_follow_the_published_arithmetic():
    cfg = config()
    assert nemotron_h.kinds(cfg) == "MEMEM*E"
    per_token = nemotron_h.train_flops_per_token(cfg, TRAFFIC)
    d = 2688
    mamba = d * (4096 + 6144 + 64) + 4096 * d           # in and out
    attention = d * (32 + 4) * 128 + 32 * 128 * d
    expert = d * 128 + 2 * d * 3712 + 6 * (8 / 128) * 2 * d * 1856
    assert (mamba, attention) == (38_707_200, 23_396_352)
    scan = nemotron_h.ssd_cost(cfg, TRAFFIC)
    L = 128
    assert scan["fwd"]["flops"] == 64 * (8 * 2 * L * L * 128 + 64 * (
        2 * L * L * 64 + 4 * L * 128 * 64))
    pairs = 8192 * 8193 // 2
    assert per_token == pytest.approx(
        6.0 * (3 * mamba + attention + 3 * expert + 16384 * d)
        + 3 * 3.0 * (scan["fwd"]["flops"] / 8192 + 2 * 4 * 6144)
        + 12.0 * 128 * 32 * pairs / 8192, rel=1e-12)
    # the Mamba-2 mixers' share of the step's operations: about two fifths
    mixers = 3 * (6.0 * mamba + 3.0 * (scan["fwd"]["flops"] / 8192
                                       + 2 * 4 * 6144))
    assert 0.40 < mixers / per_token < 0.48
    assert nemotron_h.expected_held_share(cfg) == 1 / 16
    cost = nemotron_h.expert_gemm_cost(cfg, TRAFFIC, 3072.0)
    assert cost["flops"] == pytest.approx(3 * 6 * 2 * 3072 * d * 1856)
    # one forward a chunk writes the state it starts from: 64 x 64 heads of
    # 128 x 64 float32, 134 MB a call
    assert scan["chunks"] == 64
    assert 2 * 8192 * 4096 * 2 + 2 * 8192 * 1024 * 2 \
        + 64 * 64 * 128 * 64 * 4 == scan["fwd"]["bytes"]
