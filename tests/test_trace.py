"""The span layer and live metrics exporter (tpudist.telemetry.trace,
docs/OBSERVABILITY.md §8): span row schema, run_id plumbing, the serve
tracer's exact phase telescoping (queued + prefill + decode + preempted ==
total) under preemption and speculative decoding, SLO-sample parity
(span-derived TTFT/TPOT bit-equal to the ServeStats deques), the
byte-identity contract with the features off, and the Prometheus text
endpoint."""

import json
import pathlib
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpudist.models.gpt2 import GPT2
from tpudist.resilience.exitcodes import RUN_ID_ENV, ensure_run_id, run_id
from tpudist.serve import ServeEngine
from tpudist.telemetry import TelemetrySink
from tpudist.telemetry.trace import MetricsExporter, ServeTracer, Tracer


def _gpt2(max_seq_len=64):
    return GPT2(vocab_size=64, max_seq_len=max_seq_len, hidden_dim=32,
                depth=2, num_heads=4)


def _params(model, seed=0):
    import jax

    return model.init(
        jax.random.key(seed), np.zeros((1, 8), np.int32), train=False
    )["params"]


def _prompts(lens, vocab=64, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    return [rng.integers(0, vocab, (p,)).astype(np.int32) for p in lens]


def _rows(path):
    return [json.loads(l) for l in pathlib.Path(path).read_text().splitlines()]


def _spans(path, name=None):
    out = [r for r in _rows(path) if r["kind"] == "span"]
    return out if name is None else [r for r in out if r["name"] == name]


# -- Tracer (train-side) -----------------------------------------------------


def test_tracer_span_and_instant_schema(tmp_path):
    sink_clock = iter([50.0, 51.0]).__next__
    sink = TelemetrySink(tmp_path / "t.jsonl", rank=2, clock=sink_clock)
    tr = Tracer(sink, cat="train", process_index=3, generation=1,
                clock=lambda: 100.0)
    tr.span("step", 0.25, step=7, data_wait_s=0.01)
    tr.instant("repair", step=8, cause="loss_spike")
    sink.close()
    rows = _rows(tmp_path / "t.jsonl")
    assert rows[0] == {
        "v": 1, "t": 50.0, "kind": "span", "rank": 2, "step": 7,
        "name": "step", "cat": "train", "ph": "X",
        "t0": 99.75, "dur_s": 0.25,  # t0 defaults to now - dur_s
        "process_index": 3, "generation": 1, "data_wait_s": 0.01,
    }
    assert rows[1]["ph"] == "i" and rows[1]["dur_s"] == 0.0
    assert rows[1]["t0"] == 100.0 and rows[1]["cause"] == "loss_spike"


# -- run_id plumbing ---------------------------------------------------------


def test_run_id_minted_once_and_inherited(monkeypatch):
    env = {}
    rid = ensure_run_id(env)
    assert env[RUN_ID_ENV] == rid and len(rid) == 12
    assert ensure_run_id(env) == rid  # idempotent — relaunches inherit
    assert run_id(env) == rid
    assert run_id({}) is None and run_id({RUN_ID_ENV: "  "}) is None


def test_sink_appends_run_id_last(tmp_path, monkeypatch):
    monkeypatch.delenv(RUN_ID_ENV, raising=False)
    clock = iter([1.0, 2.0]).__next__
    with TelemetrySink(tmp_path / "a.jsonl", clock=clock) as sink:
        sink.write("health", 1, loss=0.5)
    bare = _rows(tmp_path / "a.jsonl")[0]
    assert "run_id" not in bare  # no env, no explicit id: byte-identical

    monkeypatch.setenv(RUN_ID_ENV, "envid0000000")
    clock = iter([1.0, 2.0]).__next__
    with TelemetrySink(tmp_path / "b.jsonl", clock=clock) as sink:
        assert sink.run_id == "envid0000000"  # env fallback
        sink.write("health", 1, loss=0.5)
    row = json.loads((tmp_path / "b.jsonl").read_text())
    assert list(row)[-1] == "run_id"  # appended AFTER existing fields
    assert {k: v for k, v in row.items() if k != "run_id"} == bare


# -- serve tracer: exact phase telescoping -----------------------------------


def test_serve_tracer_phases_telescope_exactly(tmp_path):
    """Synthetic lifecycle with a preemption, on dyadic timestamps so
    float addition is exact: the four phases must sum to the total."""
    sink = TelemetrySink(tmp_path / "s.jsonl", clock=lambda: 0.0)
    tr = ServeTracer(sink)
    t = lambda k: k / 1024.0  # dyadic — exact float arithmetic
    tr.on_submit(7, t(0), lane=2)
    tr.on_admit(7, t(10), pool_occupancy=0.5)
    tr.on_first_token(7, t(30), slot=1, prefix_hit=2, prefix_lookup=4)
    tr.on_spec(7, 8, 6)
    tr.on_preempt(7, t(50), pool_occupancy=1.0)
    tr.on_resume(7, t(90), slot=0)
    tr.on_done(7, t(130), 12, pool_occupancy=0.25)
    sink.close()
    spans = _spans(tmp_path / "s.jsonl")
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert [s["name"] for s in spans] == [
        "queued", "prefill", "decode", "preempt", "preempted", "decode",
        "request",
    ]
    req = by_name["request"][0]
    assert req["queued_s"] == t(10) and req["prefill_s"] == t(20)
    assert req["decode_s"] == t(20) + t(40)  # both decode segments
    assert req["preempt_s"] == t(40) and req["preempts"] == 1
    total = req["queued_s"] + req["prefill_s"] + req["decode_s"] \
        + req["preempt_s"]
    assert total == req["dur_s"] == t(130)  # EXACT, not approx
    assert req["ttft_s"] == t(30) and req["tpot_s"] == t(100) / 11
    assert req["lane"] == 2 and req["tokens"] == 12
    assert req["spec_drafted"] == 8 and req["spec_accepted"] == 6
    assert req["prefix_hit_blocks"] == 2 and req["prefix_lookup_blocks"] == 4
    # the two decode segments individually cover the decode total
    assert sum(s["dur_s"] for s in by_name["decode"]) == req["decode_s"]


# -- engine integration ------------------------------------------------------


def test_engine_spans_reconcile_with_stats(tmp_path):
    """Real engine, traced: every retired request has a terminal span
    whose phase sum matches its total within float addition error, and
    the span-derived TTFT/TPOT samples are BIT-EQUAL to the ServeStats
    SLO deques (the tracer reuses the exact clock readings)."""
    model = _gpt2()
    params = _params(model)
    sink = TelemetrySink(tmp_path / "e.jsonl")
    eng = ServeEngine(model, params, max_slots=2, seed=0, sink=sink,
                      stats_every=5, trace=True)
    prompts = _prompts([6, 10, 4, 8], seed=3)
    rids = [eng.submit(p, 6 + i, priority=i % 2)
            for i, p in enumerate(prompts)]
    eng.run()
    sink.close()
    reqs = _spans(tmp_path / "e.jsonl", "request")
    assert sorted(r["rid"] for r in reqs) == sorted(rids)
    for r in reqs:
        phase_sum = (r["queued_s"] + r["prefill_s"] + r["decode_s"]
                     + r["preempt_s"])
        assert abs(phase_sum - r["dur_s"]) < 1e-9
    # bit-equal SLO parity: same floats, same arithmetic
    assert sorted(r["ttft_s"] for r in reqs) == sorted(eng.stats.ttft)
    assert sorted(r["tpot_s"] for r in reqs if r["tpot_s"] is not None) \
        == sorted(eng.stats.tpot)
    # percentiles derived from spans == the serve_summary percentiles
    snap = eng.stats.snapshot()
    assert snap["ttft_p50"] == round(
        float(np.percentile([r["ttft_s"] for r in reqs], 50)), 6
    )
    # queue-wait samples == the queued-phase spans of first admissions
    assert sorted(s["dur_s"] for s in _spans(tmp_path / "e.jsonl", "queued")) \
        == sorted(eng.stats.queue_wait)
    # the tick backbone exists and carries the scheduler state
    ticks = _spans(tmp_path / "e.jsonl", "tick")
    assert ticks and all("queue_depth" in s and "tokens" in s for s in ticks)


def test_engine_trace_preemption_cycle(tmp_path):
    """The paged eviction cycle (pool runs dry mid-decode), traced: the
    preempted request's span decomposition includes the preemption gap
    and still telescopes to its total."""
    model = _gpt2()
    params = _params(model, 1)
    sink = TelemetrySink(tmp_path / "p.jsonl")
    eng = ServeEngine(model, params, max_slots=3, seed=0, paged=True,
                      block_size=8, n_blocks=8, watermark_blocks=0,
                      prefix_cache=False, sink=sink, trace=True)
    for p in _prompts([6, 6, 6], seed=5):
        eng.submit(p, 12)
    eng.run()
    sink.close()
    assert eng.stats.preemptions > 0
    path = tmp_path / "p.jsonl"
    assert len(_spans(path, "preempt")) == eng.stats.preemptions
    assert len(_spans(path, "preempted")) == eng.stats.preemptions
    reqs = _spans(path, "request")
    assert len(reqs) == 3
    preempted = [r for r in reqs if r["preempts"] > 0]
    assert preempted
    for r in reqs:
        phase_sum = (r["queued_s"] + r["prefill_s"] + r["decode_s"]
                     + r["preempt_s"])
        assert abs(phase_sum - r["dur_s"]) < 1e-9
        assert (r["preempt_s"] > 0) == (r["preempts"] > 0)
    assert sorted(r["ttft_s"] for r in reqs) == sorted(eng.stats.ttft)


def test_engine_trace_speculative(tmp_path):
    """Traced speculative engine: the per-request spec accounting on the
    terminal spans sums to the ServeStats lifetime totals."""
    from tpudist.serve import early_exit_draft

    model = _gpt2()
    params = _params(model)
    draft, dparams = early_exit_draft(model, params, 1)
    sink = TelemetrySink(tmp_path / "sp.jsonl")
    eng = ServeEngine(model, params, max_slots=2, seed=0, sink=sink,
                      draft_model=draft, draft_params=dparams, spec_k=3,
                      trace=True)
    for p in _prompts([6, 9], seed=2):
        eng.submit(p, 10)
    eng.run()
    sink.close()
    reqs = _spans(tmp_path / "sp.jsonl", "request")
    assert len(reqs) == 2
    assert sum(r["spec_drafted"] for r in reqs) == eng.stats.spec_drafted
    assert sum(r["spec_accepted"] for r in reqs) == eng.stats.spec_accepted
    assert eng.stats.spec_drafted > 0
    for r in reqs:
        phase_sum = (r["queued_s"] + r["prefill_s"] + r["decode_s"]
                     + r["preempt_s"])
        assert abs(phase_sum - r["dur_s"]) < 1e-9


# -- byte-identity with the features off -------------------------------------


def test_serve_stream_byte_identical_with_trace_off(tmp_path, monkeypatch):
    """The standing telemetry contract: with tracing and metrics off the
    stream is byte-identical — and with them ON, the only difference is
    APPENDED span rows (frozen clocks make both runs deterministic)."""
    monkeypatch.delenv(RUN_ID_ENV, raising=False)
    model = _gpt2()
    params = _params(model)
    prompts = _prompts([5, 7, 4], seed=1)

    def run(path, **kw):
        sink = TelemetrySink(path, clock=lambda: 50.0)
        eng = ServeEngine(model, params, max_slots=2, seed=0, sink=sink,
                          stats_every=3, clock=lambda: 100.0, **kw)
        out = {r: eng.submit(p, 5) for r, p in enumerate(prompts)}
        eng.run()
        eng.close()
        sink.close()
        return out

    run(tmp_path / "off.jsonl")
    run(tmp_path / "off2.jsonl")
    run(tmp_path / "on.jsonl", trace=True, metrics_port=0)
    off = (tmp_path / "off.jsonl").read_bytes()
    assert off == (tmp_path / "off2.jsonl").read_bytes()  # deterministic
    on_lines = (tmp_path / "on.jsonl").read_bytes().splitlines(keepends=True)
    stripped = b"".join(
        l for l in on_lines if json.loads(l)["kind"] != "span"
    )
    assert stripped == off  # tracing only ADDS rows, never perturbs


def test_telemetry_stream_byte_identical_with_trace_off(tmp_path, monkeypatch):
    """Same contract on the train-side Telemetry driver: attaching a
    Tracer + exporter adds span rows and changes nothing else."""
    from tpudist.telemetry import Telemetry, TelemetryConfig

    monkeypatch.delenv(RUN_ID_ENV, raising=False)

    def run(path, traced):
        sink = TelemetrySink(path, clock=lambda: 9.0)
        tel = Telemetry(TelemetryConfig(), sink, log_every=2, n_chips=1)
        if traced:
            tel.tracer = Tracer(sink, clock=lambda: 77.0)
            tel.exporter = MetricsExporter(0)
        for g in range(1, 6):
            tel.on_step(g, {"loss": 1.0 / g}, epoch=0, interval_s=0.5,
                        data_wait_s=0.01, dispatch_s=0.2)
        tel.shutdown()

    run(tmp_path / "off.jsonl", traced=False)
    run(tmp_path / "on.jsonl", traced=True)
    off = (tmp_path / "off.jsonl").read_bytes()
    on_lines = (tmp_path / "on.jsonl").read_bytes().splitlines(keepends=True)
    stripped = b"".join(
        l for l in on_lines if json.loads(l)["kind"] != "span"
    )
    assert stripped == off
    # and the traced stream got a span for EVERY resolved step — the
    # timeline backbone is per-step, not log_every-thinned
    steps = [json.loads(l) for l in on_lines
             if json.loads(l)["kind"] == "span"]
    assert [s["step"] for s in steps] == [1, 2, 3, 4, 5]
    assert all(s["name"] == "step" and s["dur_s"] == 0.5 for s in steps)


def test_engine_off_constructs_nothing(tmp_path):
    model = _gpt2()
    eng = ServeEngine(model, _params(model), max_slots=2, seed=0)
    assert eng.tracer is None and eng.exporter is None
    assert eng.metrics_port is None
    with pytest.raises(ValueError):
        ServeEngine(model, _params(model), trace=True)  # needs a sink


# -- metrics exporter --------------------------------------------------------


def test_metrics_exporter_end_to_end():
    with MetricsExporter(0, host="127.0.0.1") as exp:
        assert exp.port > 0
        exp.set(step=3, mfu=0.41, update_skips_total=2, gone=1.0)
        exp.set(gone=None)  # None clears
        exp.add_collector(lambda: {"serve_queue_depth": 5,
                                   "serve_ttft_p50": None,
                                   "bad:name": 1.5})
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/metrics", timeout=10
        ).read().decode()
        assert "tpudist_step 3" in body
        assert "tpudist_mfu 0.41" in body
        assert "# TYPE tpudist_mfu gauge" in body
        # _total suffix types as counter
        assert "# TYPE tpudist_update_skips_total counter" in body
        assert "tpudist_serve_queue_depth 5" in body
        assert "gone" not in body and "ttft_p50" not in body
        assert "tpudist_bad_name 1.5" in body  # sanitized
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=10
            )


def test_metrics_exporter_collector_failure_is_contained():
    with MetricsExporter(0, host="127.0.0.1") as exp:
        exp.set(ok=1.0)
        exp.add_collector(lambda: (_ for _ in ()).throw(RuntimeError("x")))
        assert "tpudist_ok 1" in exp.render()  # scrape survives


def test_engine_metrics_endpoint_serves_live_stats(tmp_path):
    model = _gpt2()
    sink = TelemetrySink(tmp_path / "m.jsonl")
    eng = ServeEngine(model, _params(model), max_slots=2, seed=0,
                      sink=sink, metrics_port=0)
    for p in _prompts([5, 6], seed=4):
        eng.submit(p, 4)
    eng.run()
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{eng.metrics_port}/metrics", timeout=10
    ).read().decode()
    assert "tpudist_serve_completed 2" in body
    assert "tpudist_serve_ttft_p50" in body
    assert "# TYPE tpudist_serve_preemptions_total counter" in body
    eng.close()
    sink.close()
    assert eng.exporter is None  # closed and detached


# -- the one span helper: a profiler annotation always, a row too with a
# -- Tracer (docs/OBSERVABILITY.md §8) ----------------------------------------


def _capture(tmp_path, body):
    """Run ``body`` under a CPU profiler session; returns the parsed trace
    and the session's ``profile_start_time`` (unix ns)."""
    import glob

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "prof/plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    start = next(
        dict(p.stats)["profile_start_time"] for p in data.planes
        if p.name == "Task Environment"
    )
    return data, start


def _host_events(data, name):
    return [(line, e, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name == name]


def test_span_helper_traceme_and_row_share_a_timeline(tmp_path):
    """One call, two sinks: the TraceMe event in the xplane and the span
    row agree on name, step, duration and — through the documented clock
    mapping — start, each within a millisecond."""
    from tpudist.telemetry.trace import span

    sink = TelemetrySink(tmp_path / "s.jsonl")
    tracer = Tracer(sink)

    def body():
        with span("fit/probe", step=7, tracer=tracer, shard=3):
            time.sleep(0.02)

    data, profile_start = _capture(tmp_path, body)
    sink.close()
    ((_, event, stats),) = _host_events(data, "fit/probe")
    assert stats["step_num"] == 7 and stats["shard"] == 3
    (row,) = _spans(tmp_path / "s.jsonl", "fit/probe")
    assert row["step"] == 7 and row["shard"] == 3 and row["ph"] == "X"
    assert abs(row["dur_s"] * 1e9 - event.duration_ns) < 1e6
    # the mapping: a row starts (t - dur_s) on the wall clock; an xplane
    # host event starts start_ns after the session's profile_start_time
    row_start_ns = (row["t"] - row["dur_s"]) * 1e9 - profile_start
    assert abs(row_start_ns - event.start_ns) < 1e6


def test_span_helper_row_name_tags_and_step_marker(tmp_path):
    """``row=`` keeps a stream's older name; without a tracer no row is
    written; ``marks_step`` makes the event a StepTraceAnnotation
    (``_r``), which XProf groups device work by."""
    from tpudist.telemetry.trace import TRAIN_STEP, span

    sink = TelemetrySink(tmp_path / "s.jsonl", clock=lambda: 50.0)
    tracer = Tracer(sink, clock=lambda: 77.0, process_index=1)

    def body():
        with span("fit/checkpoint", step=4, tracer=tracer, row="checkpoint"):
            pass
        with span("fit/log", step=4):  # no tracer: annotation only
            pass
        with span(TRAIN_STEP, step=5, marks_step=True):
            pass

    data, _ = _capture(tmp_path, body)
    sink.close()
    assert [r["name"] for r in _spans(tmp_path / "s.jsonl")] == ["checkpoint"]
    (row,) = _spans(tmp_path / "s.jsonl")
    assert row == {
        "v": 1, "t": 50.0, "kind": "span", "rank": 0, "step": 4,
        "name": "checkpoint", "cat": "train", "ph": "X", "t0": 77.0,
        "dur_s": 0.0, "process_index": 1, "generation": 0,
    }
    assert len(_host_events(data, "fit/checkpoint")) == 1
    assert len(_host_events(data, "fit/log")) == 1
    ((_, _, stats),) = _host_events(data, TRAIN_STEP)
    assert stats["_r"] == 1 and stats["step_num"] == 5


def test_prefetch_spans_carry_the_batch_ordinal(tmp_path):
    """``prefetch_to_mesh``'s three spans: one produce, wait and stage per
    batch, tagged 0..n-1, as rows and as events. The ``next()`` that finds
    the stream ended (the producer's sixth, the consumer's sixth wait) is
    no batch's work: no row, and its event is tagged ``end``."""
    import numpy as np

    from tpudist.data.loader import prefetch_to_mesh
    from tpudist.mesh import create_mesh

    sink = TelemetrySink(tmp_path / "s.jsonl")
    batches = [{"x": np.full((8, 2), i, np.float32)} for i in range(5)]
    staged = []
    data, _ = _capture(tmp_path, lambda: staged.extend(prefetch_to_mesh(
        iter(batches), create_mesh(), tracer=Tracer(sink))))
    sink.close()
    assert [int(b["x"][0, 0]) for b in staged] == [0, 1, 2, 3, 4]
    path = tmp_path / "s.jsonl"
    for name in ("input/produce", "input/wait", "input/stage"):
        assert [s["batch"] for s in _spans(path, name)] == [0, 1, 2, 3, 4]
        events = sorted((st["batch"], st.get("end"))
                        for _, _, st in _host_events(data, name))
        last = [] if name == "input/stage" else [(5, 1)]
        assert events == [(n, None) for n in range(5)] + last, name


def test_span_that_meets_the_end_of_its_stream_is_tagged_not_counted(tmp_path):
    """``TimedIterator``'s last ``next()`` raises StopIteration through
    ``fit/next_batch``: the event says so (``end``), there is no row, and
    the steps that did get a batch keep one span each."""
    from tpudist.telemetry import TimedIterator

    sink = TelemetrySink(tmp_path / "s.jsonl")
    timed = TimedIterator(iter("ab"), step=10, tracer=Tracer(sink))
    got = []
    data, _ = _capture(tmp_path, lambda: got.extend(timed))
    sink.close()
    assert got == ["a", "b"]
    assert [r["step"] for r in _spans(tmp_path / "s.jsonl")] == [11, 12]
    events = sorted((st["step_num"], st.get("end"))
                    for _, _, st in _host_events(data, "fit/next_batch"))
    assert events == [(11, None), (12, None), (13, 1)]
