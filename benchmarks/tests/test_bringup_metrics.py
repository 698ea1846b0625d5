"""The bring-up readers (``pre_fit_s``, ``fit_bringup_s``, ``init_state_s``,
``first_step_s``, ``trace_lower_s``): on a hand-written ``bringup`` row they
read what the row says and print a line that adds up to ``setup_s``; on a
program that writes no such row (the parent's) they give nothing; on the tiny
GPT-2 cell's CPU twin the numbers and the printed remainder equal the run's
``setup_s``, and the program's compile seconds agree with the harness's own
listener over ``fit``'s interval."""

import json
import os
import time
import types

import pytest

from benchmarks import cell, layers, meter as meter_lib
from benchmarks.tests import tiny

NEW = ("pre_fit_s", "fit_bringup_s", "init_state_s", "first_step_s",
       "trace_lower_s")
PHASES = [  # name, seconds
    ("bringup/probe", 0.25), ("bringup/init_state", 4.0),
    ("bringup/place_params", 0.5), ("bringup/verify_replicas", 1.5),
    ("bringup/build_step", 0.125), ("bringup/restore", 0.0),
    ("bringup/telemetry", 0.625), ("bringup/first_batch", 0.5),
    ("bringup/first_dispatch", 12.0),
]


def row(t_entry_perf=108.0):
    phases, t = [], 0.0
    for name, seconds in PHASES:
        phases.append([name, t, seconds])
        t += seconds
    return {
        "kind": "bringup", "step": 1, "t_entry": 5.0,
        "t_entry_perf": t_entry_perf, "phases": phases, "total_s": t,
        "trace_lower_s": 9.5, "backend_s": 6.0, "cache_hits": 3,
        "cache_misses": 0, "cache_retrieval_s": 2.0,
        "compile": [{"fun": "step_fn", "n": 4, "trace_s": 6.0,
                     "lower_s": 2.0, "backend_s": 3.0, "hits": 1,
                     "misses": 0}],
    }


def bench():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        full = json.load(f)
    only = dict(full, per_layer=[m for m in full["per_layer"]
                                 if m["name"] in NEW])
    assert [m["name"] for m in only["per_layer"]] == list(NEW)
    return only


def context(rows, setup_s=30.0):
    return {
        "telemetry_rows": rows, "t_start": 100.0,
        "e2e": {"setup_s": setup_s},
        "meter": types.SimpleNamespace(
            compile_seconds=lambda since, until: 15.5),
    }


def printed(capsys):
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    (line,) = [r for r in lines if "bringup_phases" in r]
    return line


def test_every_new_metric_moves_setup_s_in_every_cell():
    for metric in bench()["per_layer"]:
        assert metric["moves"] == "setup_s" and metric["unit"] == "s"
        assert metric["layer"] == "bring-up" and "workloads" not in metric


def test_readers_on_a_hand_written_row(capsys):
    metrics = layers.read_all(
        bench(), {"name": "any"},
        context([{"kind": "step_breakdown", "step": 5}, row()]))
    assert {k: v["value"] for k, v in metrics.items()} == {
        "pre_fit_s": 8.0, "fit_bringup_s": 7.0, "init_state_s": 4.0,
        "first_step_s": 12.5, "trace_lower_s": 9.5}
    line = printed(capsys)
    assert line["remainder_s"] == 30.0 - 8.0 - 7.0 - 12.5
    assert sum(line["bringup_phases"].values()) == 7.0 + 12.5
    assert list(line["bringup_phases"]) == [name for name, _ in PHASES]
    assert line["compile_table"][0]["fun"] == "step_fn"
    assert (line["trace_lower_s"], line["backend_s"]) == (9.5, 6.0)
    assert line["compile_s_in_fit"] == 15.5


def test_readers_give_nothing_where_the_program_writes_no_row(capsys):
    rows = [{"kind": "step_breakdown", "step": 5, "dispatch_s": 0.003}]
    assert layers.read_all(bench(), {"name": "any"}, context(rows)) == {}
    assert "bringup_phases" not in capsys.readouterr().out


def with_telemetry(fit):
    """``fit`` as the harness's traced runs call it (rows only: nothing
    that changes the compiled step), without the profiler."""
    from tpudist.telemetry import TelemetryConfig

    def traced(*args, **kwargs):
        kwargs["telemetry"] = TelemetryConfig(
            health_metrics=False, guard_nonfinite=False, sentry=False,
            capture_on_anomaly=False, breakdown=True, mfu=False,
            run_report=False)
        return fit(*args, **kwargs)
    return traced


def test_tiny_cell_numbers_and_remainder_add_up_to_setup_s(
        monkeypatch, capsys):
    import jax

    import tpudist.train as train

    monkeypatch.setattr(train, "fit", with_telemetry(train.fit))
    devices = jax.devices()[:1]
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    meter = meter_lib.CompileMeter()
    t_start = time.perf_counter()
    result = cell.run(
        tiny.bench(), "tiny_gpt2", seed=2**31 + 11, seconds=0.3, trace=False,
        root=tiny.ROOT, t_start=t_start, devices=devices, report=report,
        limits=tiny.LIMITS)
    assert result["correct"], result["checks"]
    setup = result["metrics"]["setup_s"]["value"]
    rows = cell.telemetry_rows(os.path.join(
        tiny.ROOT, cell.WORK_DIR, "tiny_gpt2", "bench_telemetry_0.jsonl"))
    ctx = {"telemetry_rows": rows, "t_start": t_start,
           "e2e": {"setup_s": setup}, "meter": meter}
    capsys.readouterr()
    got = {k: v["value"]
           for k, v in layers.read_all(bench(), {"name": "any"}, ctx).items()}
    assert set(got) == set(NEW)
    line = printed(capsys)
    # five numbers and the remainder: setup_s, to a millisecond
    assert (got["pre_fit_s"] + got["fit_bringup_s"] + got["first_step_s"]
            + line["remainder_s"]) == pytest.approx(setup, abs=1e-3)
    # the remainder is the warm-up after the first dispatch: fit's entry
    # and the window's opening are on one clock
    assert 0.0 < line["remainder_s"] < setup
    assert 0.0 < got["pre_fit_s"] < setup
    assert sum(line["bringup_phases"].values()) == pytest.approx(
        got["fit_bringup_s"] + got["first_step_s"], abs=1e-9)
    assert 0.0 < got["init_state_s"] < got["fit_bringup_s"]
    # two listeners on the same events, over the same interval
    assert got["trace_lower_s"] + line["backend_s"] == pytest.approx(
        line["compile_s_in_fit"], abs=0.2)
    assert 0.0 < got["trace_lower_s"] <= line["compile_s_in_fit"] + 1e-3
    funs = {entry["fun"] for entry in line["compile_table"]}
    assert {"step_fn", "_init"} <= funs
