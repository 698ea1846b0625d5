import json
import os
import re
import shutil

import pytest

from benchmarks import layers, spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ONE_CHIP = os.path.join(HERE, "data", "tiny_1chip.xplane.pb")
ONE_CHIP_SPANS = os.path.join(HERE, "data", "tiny_1chip_spans.xplane.pb")
FOUR_CHIP = os.path.join(HERE, "data", "tiny_4chip.xplane.pb")


def recorded(path):
    if not os.path.exists(path):
        pytest.skip(f"no recorded trace {os.path.basename(path)}")
    return path


def test_wire_reader_recovers_the_name_stack_the_profile_data_drops():
    meta = spans.op_metadata(ONE_CHIP)
    (plane,) = meta  # one chip, one device plane
    by_op = {xplane.op_name(k): v for k, v in meta[plane].items()}
    assert by_op["h_0.2"] == {
        "hlo_category": "custom-call",
        "tf_op": "jit(step_fn)/jvp(GPT2)/h_0/pallas_call:",
    }
    assert by_op["convolution_add_fusion.1"]["tf_op"] == (
        "jit(step_fn)/jvp(GPT2)/h_0/qkv/dot_general:")
    assert by_op["copy-done.14"] == {"hlo_category": "copy-done"}


@pytest.mark.parametrize("tf_op,which,scope", [
    ("jit(step_fn)/jvp(GPT2)/h_3/qkv/dot_general:", "fwd", "h_N/qkv"),
    ("jit(step_fn)/jvp(GPT2)/h_11/pallas_call:", "fwd", "h_N/pallas_call"),
    ("jit(step_fn)/transpose(jvp(GPT2))/h_0/ln_1/jit(_pad)/pad:", "bwd",
     "h_N/ln_N"),
    ("jit(step_fn)/jvp(loss_head)/while/body/dot_general:", "fwd",
     "loss_head/while"),
    ("jit(step_fn)/transpose(jvp(loss_head))/while/body/checkpoint/"
     "rematted_computation/dot_general:", "bwd", "loss_head/while"),
    ("jit(step_fn)/optimizer/pallas_call:", "opt", "optimizer/pallas_call"),
    ("jit(step_fn)/optimizer/grad_clip/mul:", "opt", "optimizer/grad_clip"),
    ("jit(step_fn)/jvp(Policy)/cast/convert_element_type:", "opt",
     "cast/convert_element_type"),
    ("grad_exchange/psum:", "bwd", "grad_exchange/psum"),
    ("jit(step_fn)/jvp(GPT2)/add:;jit(step_fn)/transpose(jvp(GPT2))/mul:",
     "fwd", "add"),
    ("state.params['wte']:", "other", "state.params['wte']"),
    ("", "other", "(none)"),
])
def test_pass_and_scope_of_a_name_stack(tf_op, which, scope):
    assert spans.pass_of(tf_op) == which
    assert spans.scope_of(tf_op) == scope


@pytest.mark.parametrize("path", [ONE_CHIP, ONE_CHIP_SPANS])
def test_passes_account_for_the_busy_time_of_the_recorded_trace(path):
    trace = spans.load(recorded(path))
    out = spans.reduce(trace)
    reduced = xplane.reduce(xplane.load(path), 1)
    assert out["steps"] == reduced["steps"]
    busy_ms = 1e3 * reduced["busy_s"] / reduced["steps"]
    assert sum(out["pass_ms"].values()) == pytest.approx(busy_ms, rel=1e-3)
    assert sum(out["scope_ms"].values()) == pytest.approx(busy_ms, rel=1e-3)
    assert sum(out["category_ms"].values()) == pytest.approx(busy_ms, rel=1e-3)
    assert out["pass_ms"]["fwd"] > 0 and out["pass_ms"]["bwd"] > 0
    assert out["scope_ms"]["fwd:h_N/pallas_call"] > 0  # the attention kernel
    if path == ONE_CHIP:  # recorded before the program named its scopes
        assert out["pass_ms"]["opt"] == 0 and out["host_span_ms"] == {}
    else:
        assert out["pass_ms"]["opt"] > 0
        assert out["scope_ms"]["opt:optimizer/pallas_call"] > 0
        assert out["other_pct"] < 30  # tiny: async copies weigh a quarter


def synthetic_host():
    main = [
        # step 1: next_batch holds a wait and a stage; then the dispatch
        ("fit/next_batch", 0, 40, 1), ("input/wait", 5, 15, 0),
        ("input/stage", 20, 35, 0), ("tpudist_train", 50, 80, 1),
        ("fit/resolve_wait", 90, 190, 0), ("fit/log", 190, 200, 0),
        # step 2
        ("fit/next_batch", 210, 220, 2), ("tpudist_train", 230, 250, 2),
    ]
    producer = [("input/produce", 0, 30, 0), ("input/produce", 30, 70, 1)]
    return {("/host:CPU", 0, "python3"): main,
            ("/host:CPU", 1, "python3"): producer}


def test_flat_timeline_gives_the_innermost_span():
    host = synthetic_host()
    pieces = spans.flat_timeline(host[("/host:CPU", 0, "python3")])
    assert pieces[:6] == [
        (0, 5, "fit/next_batch"), (5, 15, "input/wait"),
        (15, 20, "fit/next_batch"), (20, 35, "input/stage"),
        (35, 40, "fit/next_batch"), (50, 80, "tpudist_train"),
    ]


def test_host_spans_self_times_threads_and_gap_attribution():
    host = synthetic_host()
    out = spans.host_spans(host, 0, 300, steps=2)
    assert out["fit/next_batch"]["thread"] == "main"
    assert out["input/produce"]["thread"] == "producer"
    # 40 + 10 ns over two steps; self time leaves the wait and stage out
    assert out["fit/next_batch"]["ms"] == pytest.approx(25e-6)
    assert out["fit/next_batch"]["self_ms"] == pytest.approx(12.5e-6)
    assert out["fit/next_batch"]["per_step"] == 1.0
    # by identifier: each step and batch seen has its one event, none skipped
    for name in ("fit/next_batch", "tpudist_train", "input/produce"):
        assert out[name]["per_id"] == 1.0 and out[name]["ids_skipped"] == 0
    dropped = dict(host)
    dropped[("/host:CPU", 1, "python3")] = [
        ("input/produce", 0, 30, 0), ("input/produce", 60, 70, 2)]
    assert spans.host_spans(dropped, 0, 300, steps=2)[
        "input/produce"]["ids_skipped"] == 1
    assert out["input/produce"]["event_ms"] == pytest.approx(35e-6)
    assert out["tpudist_train"]["numbered"]
    # gaps: 10..60 (under next_batch pieces, wait, stage, then 10 ns of
    # nothing and 10 of the dispatch), 100..150 inside resolve_wait
    gaps = [(10, 60), (100, 150), (200, 205)]
    idle = spans.idle_by_span(gaps, host, top=2)
    assert idle["idle_ms"] == pytest.approx(100e-6)
    assert idle["ms"]["fit/resolve_wait"] == pytest.approx(50e-6)
    assert idle["ms"]["input/stage"] == pytest.approx(15e-6)
    assert idle["ms"]["tpudist_train"] == pytest.approx(10e-6)
    assert idle["ms"]["unattributed"] == pytest.approx(10e-6)
    assert idle["attributed_pct"] == pytest.approx(90.0)


def run_context(tmp_path, trace_file, chips):
    """What ``cell.run`` hands the readers of a traced run, over a recorded
    trace put where ``cell.py`` leaves the run's."""
    from benchmarks.tests.tiny import ROOT

    name = "tiny_gpt2"
    target = tmp_path / ".bench_work" / name / "trace/plugins/profile/0"
    target.mkdir(parents=True)
    shutil.copy(trace_file, target / "t.xplane.pb")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ctx = {"root": str(tmp_path), "cell": {"name": name}, "chips": chips,
           "trace": xplane.reduce(xplane.load(trace_file), chips)}
    return bench, ctx


NEW = ("fwd_ms", "bwd_ms", "opt_ms", "loop_host_ms", "input_produce_ms",
       "input_stage_ms")


def readers(bench, names=NEW + ("collective_exposed_pct",)):
    return dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in names])


def test_readers_leave_out_what_a_program_without_spans_cannot_give(
        tmp_path, capsys):
    bench, ctx = run_context(tmp_path, ONE_CHIP, 1)
    got = layers.read_all(readers(bench), {"name": "tiny_gpt2"}, ctx)
    # the parent's trace: passes by the name stack JAX always writes, no
    # optimizer scope, no program span — those metrics are left out
    assert sorted(got) == ["bwd_ms", "fwd_ms"]
    (line,) = [l for l in capsys.readouterr().out.splitlines()
               if "scope_ms" in l]  # computed and printed once
    assert json.loads(line)["host_span_ms"] == {}


def test_readers_on_a_trace_with_the_programs_spans(tmp_path):
    bench, ctx = run_context(tmp_path, recorded(ONE_CHIP_SPANS), 1)
    got = layers.read_all(readers(bench, NEW), {"name": "tiny_gpt2"}, ctx)
    assert sorted(got) == sorted(NEW)
    assert all(v["value"] > 0 and v["unit"] == "ms" for v in got.values())
    host = ctx["spans"]["host_span_ms"]
    for name in ("fit/next_batch", "input/wait", "input/stage",
                 "tpudist_train", "fit/health", "fit/resolve_wait",
                 "fit/log"):
        assert host[name]["thread"] == "main" and host[name]["numbered"]
        assert host[name]["per_step"] == pytest.approx(1.0, abs=0.35), name
        assert host[name]["per_id"] == 1.0 and host[name]["ids_skipped"] == 0
    assert host["input/produce"]["thread"] == "producer"
    assert host["input/produce"]["ids_skipped"] == 0


def test_load_leaves_out_the_next_that_found_its_stream_over(tmp_path):
    """The program tags such an event ``end`` (tpudist/telemetry/trace.py):
    it is no batch's work, and a near-zero event would pull a mean down."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    for batch, last in ((0, False), (1, False), (2, True)):
        with jax.profiler.TraceAnnotation("input/produce", batch=batch) as a:
            if last:
                a.set_metadata(end=1)
    jax.profiler.stop_trace()
    trace = spans.load(xplane.find_xplane(str(tmp_path)))
    (line,) = trace["host"].values()
    assert [(name, tag) for name, _, _, tag in line] == [
        ("input/produce", 0), ("input/produce", 1)]
    assert trace["devices"] == {}  # a CPU session has no TPU plane


def test_four_chip_trace_gives_the_exposed_collective_share(tmp_path):
    bench, ctx = run_context(tmp_path, recorded(FOUR_CHIP), 4)
    got = layers.read_all(readers(bench), {"name": "gpt2_medium_dp4_s1024"},
                          ctx)
    assert got["collective_exposed_pct"]["value"] > 0
    assert got["bwd_ms"]["value"] > got["fwd_ms"]["value"] > 0
    # the step's all-reduces, by XLA's category and under the backward pass
    assert ctx["spans"]["category_ms"]["all-reduce"] > 0
    assert ctx["trace"]["collective_ops"]
    # under a mesh the kernels run in a shard_map, inside which the program
    # gives the attention kernel its block's name back: found as off the mesh
    from benchmarks.families import gpt2

    kernels = [n for n in ctx["trace"]["custom_call_ops"]
               if re.match(gpt2.ATTENTION_OPS, n)]
    assert len(kernels) == 4  # two blocks, forward and backward
    assert ctx["spans"]["scope_ms"]["fwd:h_N/shard_map"] > 0
    # the producer's one event in this short trace is the next() that found
    # the stream over, tagged ``end``: left out, not read as a batch's work
    assert "input/produce" not in ctx["spans"]["host_span_ms"]


@pytest.mark.parametrize("cell, chips, trace_file, reported, silent", [
    ("gpt2_medium_train_s1024", 1, ONE_CHIP,
     "attn_roofline", "attn_mesh_roofline"),
    ("gpt2_medium_dp4_s1024", 4, FOUR_CHIP,
     "attn_mesh_roofline", "attn_roofline"),
])
def test_attention_roofline_is_read_under_one_name_a_cell(
        tmp_path, cell, chips, trace_file, reported, silent):
    """On the parent commit the four-chip cell's kernels are named
    ``shard_map.<k>`` and the accepted ``attn_roofline`` finds nothing, so it
    lists the one-chip cells; the same reader under ``attn_mesh_roofline``
    reads the four-chip cell, where this program names the kernels."""
    from benchmarks.families import gpt2
    from benchmarks.tests.tiny import ROOT

    bench, ctx = run_context(tmp_path, recorded(trace_file), chips)
    with open(os.path.join(ROOT, "benchmarks/configs/gpt2-medium.json")) as f:
        config = json.load(f)
    ctx.update(family=gpt2, config=config, device_kind="TPU v5 lite",
               traffic={"seq_len": 64, "per_chip_batch": 2})
    got = layers.read_all(readers(bench, (reported, silent)),
                          {"name": cell}, ctx)
    assert list(got) == [reported] and got[reported]["value"] > 0
