"""tools/stepscope.py: bucketed device-op attribution of profiler traces
(docs/OBSERVABILITY.md §9) — classification rules, the total-by-construction
attribution guarantee, boundedness verdicts, diff mode, and the
acceptance integration: a REAL ``jax.profiler`` capture of a jitted
program whose device time stepscope attributes >= 95% (here: 100%, the
catch-all makes it total) into named buckets."""

import gzip
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

_TOOLS = pathlib.Path(__file__).resolve().parent.parent / "tools"


def _load_stepscope():
    spec = importlib.util.spec_from_file_location(
        "stepscope", _TOOLS / "stepscope.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


stepscope = _load_stepscope()


# -- classification ----------------------------------------------------------


@pytest.mark.parametrize("name,args,bucket", [
    ("dot.3", None, "gemm"),
    ("convolution.1", None, "gemm"),
    ("%dot.7", None, "gemm"),
    ("all-reduce.2", None, "collective-comm"),
    ("reduce-scatter", None, "collective-comm"),
    ("all-gather.11", None, "collective-comm"),
    ("collective-permute.1", None, "collective-comm"),
    ("custom-call.4", {"long_name": "flash_attention kernel"},
     "attention-custom-call"),
    ("custom-call.9", {"tf_op": "pallas_call splash_mha"},
     "attention-custom-call"),
    ("fusion.12", None, "elementwise-other"),
    ("reduce.1", None, "elementwise-other"),
    ("copy.2", None, "elementwise-other"),
    ("broadcast", None, "elementwise-other"),
    # args.hlo_op wins over the event name (device lanes often carry a
    # framework label in `name` and the HLO op in args)
    ("ExecutorRun", {"hlo_op": "dot.4"}, "gemm"),
])
def test_classify(name, args, bucket):
    assert stepscope.classify(name, args) == bucket


def test_op_base_strips_suffix_and_sigil():
    assert stepscope.op_base("dot.3") == "dot"
    assert stepscope.op_base("%fusion.12") == "fusion"
    assert stepscope.op_base("all-reduce") == "all-reduce"


# -- aggregation on a synthetic trace ----------------------------------------


def _event(name, dur, pid=1, **args):
    return {"ph": "X", "pid": pid, "tid": 0, "ts": 0, "dur": dur,
            "name": name, "args": {"hlo_op": name, **args}}


def _synthetic_trace(tmp_path, fname="host.trace.json.gz", gemm_us=700,
                     coll_us=200, other_us=100):
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        _event("dot.1", gemm_us / 2), _event("dot.2", gemm_us / 2),
        _event("all-reduce.1", coll_us),
        _event("fusion.1", other_us / 2), _event("add.3", other_us / 2),
        # infra noise on the device process: must NOT count
        {"ph": "X", "pid": 1, "ts": 0, "dur": 9999,
         "name": "ThreadpoolListener", "args": {}},
        # python-tracer host event: no hlo args, non-device pid
        {"ph": "X", "pid": 99, "ts": 0, "dur": 5000, "name": "train_step",
         "args": {}},
    ]
    path = tmp_path / fname
    raw = json.dumps({"traceEvents": events}).encode()
    path.write_bytes(gzip.compress(raw) if fname.endswith(".gz") else raw)
    return path


def test_aggregate_buckets_and_excludes_infra(tmp_path):
    _synthetic_trace(tmp_path)
    summary = stepscope.summarize(tmp_path)
    assert summary["total_us"] == 1000.0  # infra + host events excluded
    assert summary["buckets"]["gemm"]["us"] == 700.0
    assert summary["buckets"]["collective-comm"]["us"] == 200.0
    assert summary["buckets"]["elementwise-other"]["us"] == 100.0
    assert stepscope.attributed_pct(summary) == 100.0
    # per-op totals merge the .N suffixes
    assert summary["ops"]["dot"]["count"] == 2
    assert summary["ops"]["dot"]["us"] == 700.0


def test_plain_json_and_gz_both_load(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    _synthetic_trace(tmp_path / "a", "h.trace.json")
    _synthetic_trace(tmp_path / "b", "h.trace.json.gz")
    sa = stepscope.summarize(tmp_path / "a")
    sb = stepscope.summarize(tmp_path / "b")
    assert sa["total_us"] == sb["total_us"] == 1000.0


def test_boundedness_verdicts():
    ridge = 240.0
    assert stepscope.boundedness("collective-comm", None, ridge) \
        == "interconnect-bound"
    assert stepscope.boundedness("elementwise-other", 500.0, ridge) \
        == "HBM-bound"
    assert stepscope.boundedness("gemm", 500.0, ridge) == "compute-bound"
    assert stepscope.boundedness("gemm", 50.0, ridge) == "HBM-bound"
    assert "unknown" in stepscope.boundedness("gemm", None, ridge)


def test_anatomy_intensity_reads_first_anatomy_row(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text(
        json.dumps({"kind": "heartbeat", "step": 1}) + "\n"
        + json.dumps({"kind": "anatomy", "program": "train_step",
                      "flops_scaled": 2.4e12, "bytes_accessed": 1e10})
        + "\n")
    assert stepscope.anatomy_intensity(p) == pytest.approx(240.0)
    empty = tmp_path / "e.jsonl"
    empty.write_text(json.dumps({"kind": "span"}) + "\n")
    assert stepscope.anatomy_intensity(empty) is None
    assert stepscope.anatomy_intensity(tmp_path / "missing.jsonl") is None


def test_report_and_cli(tmp_path, capsys):
    _synthetic_trace(tmp_path)
    rc = stepscope.main([str(tmp_path), "--ai", "500", "--top", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "100.0% attributed" in out
    assert "compute-bound" in out          # gemm at ai 500 vs ridge ~240
    assert "interconnect-bound" in out
    assert "dot" in out


def test_cli_missing_trace_exits_2(tmp_path, capsys):
    assert stepscope.main([str(tmp_path / "nothing")]) == 2


def test_diff_mode_regressions_first(tmp_path, capsys):
    before, after = tmp_path / "before", tmp_path / "after"
    before.mkdir(), after.mkdir()
    _synthetic_trace(before, gemm_us=700, coll_us=200, other_us=100)
    # after: collectives tripled (the regression), gemm unchanged
    _synthetic_trace(after, gemm_us=700, coll_us=600, other_us=100)
    rc = stepscope.main(["--diff", str(before), str(after)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "+0.400 ms" in out  # the 400us collective delta, sign marked
    lines = [l for l in out.splitlines() if l.strip().startswith(
        ("dot", "all-reduce", "fusion", "add"))]
    assert lines[0].strip().startswith("all-reduce")  # regressions first
    assert stepscope.main(["--diff", str(before)]) == 2  # needs two


# -- acceptance: a real profiler capture -------------------------------------


def test_real_capture_attributes_95pct(tmp_path):
    """jax.profiler on a jitted GEMM+elementwise program: stepscope's
    buckets must attribute >= 95% of device-op time (the catch-all makes
    it exactly 100%), with the GEMM bucket visibly populated."""

    @jax.jit
    def f(a, b):
        return jnp.tanh(a @ b).sum()

    a = jnp.ones((256, 256), jnp.float32)
    b = jnp.ones((256, 256), jnp.float32)
    f(a, b).block_until_ready()  # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            f(a, b).block_until_ready()
    summary = stepscope.summarize(tmp_path)
    assert summary is not None and summary["total_us"] > 0
    assert stepscope.attributed_pct(summary) >= 95.0
    assert summary["buckets"]["gemm"]["us"] > 0
    named = (summary["buckets"]["gemm"]["us"]
             + summary["buckets"]["elementwise-other"]["us"])
    assert named / summary["total_us"] > 0.5
