"""fit()-level telemetry integration: the JSONL stream's row kinds, the
NaN flight recorder end-to-end (in-graph skip → sentry event → armed trace
window), the automatic HBM-row cadence, and — the contract the whole
subsystem hangs off — the reference TSV staying byte-identical in format
when telemetry is off."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist.data.loader import DataLoader
from tpudist.models.gpt2 import GPT2
from tpudist.telemetry import TelemetryConfig
from tpudist.telemetry.trace import BRINGUP_SPANS, FIT_SPANS
from tpudist.train import fit, lm_loss

VOCAB = 256
POISON = 255  # the sentinel token the poisoned loss turns into NaN


def _tiny_lm():
    return GPT2(vocab_size=VOCAB, max_seq_len=16, hidden_dim=32, depth=1,
                num_heads=2)


def _loader(poison_row: int | None = None, n: int = 64, batch: int = 16):
    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, POISON - 1, (n, 16)).astype(np.int32)
    if poison_row is not None:
        tokens[poison_row, 0] = POISON
    return DataLoader({"tokens": tokens}, batch)


def _poisoned_loss(logits, tokens):
    base = lm_loss(logits, tokens)
    return jnp.where(jnp.any(tokens == POISON), jnp.float32(jnp.nan), base)


def _rows(path):
    return [json.loads(l) for l in pathlib.Path(path).read_text().splitlines()]


def test_fit_telemetry_stream_has_all_row_kinds(tmp_path):
    # an explicit peak wins over the device table (which has no CPU row)
    cfg = TelemetryConfig(heartbeat_every=4, peak_flops=197e12)
    state, losses = fit(
        _tiny_lm(), optax.adam(1e-3), _loader(), epochs=3, job_id="TS",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), telemetry=cfg,
        profile=False,
    )
    assert len(losses) == 12 and all(np.isfinite(losses))
    rows = _rows(tmp_path / "TS_telemetry_0.jsonl")
    kinds = {r["kind"] for r in rows}
    # the acceptance triple: grad-norm, MFU, and step-breakdown rows
    assert {"run_meta", "health", "mfu", "step_breakdown", "throughput",
            "heartbeat", "run_summary", "train_time"} <= kinds
    assert all(r["v"] == 1 and r["rank"] == 0 for r in rows)

    health = [r for r in rows if r["kind"] == "health"]
    # log_every=5 cadence over 12 steps → steps 5 and 10
    assert [r["step"] for r in health] == [5, 10]
    for r in health:
        assert r["grad_norm"] > 0 and r["param_norm"] > 0
        assert r["nonfinite_grad_count"] == 0 and r["update_skipped"] == 0
        # counts are documented as integers: the host resolve must not
        # float()-launder them into 0.0
        assert isinstance(r["nonfinite_grad_count"], int)
        assert isinstance(r["update_skipped"], int)

    mfu = [r for r in rows if r["kind"] == "mfu"]
    assert [r["step"] for r in mfu] == [5, 10]
    from tpudist.telemetry import flops

    want = flops.gpt2_train_flops(
        16.0 * 16, hidden=32, depth=1, vocab=VOCAB, seq=16
    )
    for r in mfu:
        assert r["flops_per_step"] == want
        assert r["mfu"] > 0 and r["tokens_per_sec"] > 0
    (meta,) = [r for r in rows if r["kind"] == "run_meta"]
    assert meta["device_kind"] == jax.devices()[0].device_kind
    assert meta["peak_flops_per_chip"] == 197e12

    bd = [r for r in rows if r["kind"] == "step_breakdown"]
    assert [r["step"] for r in bd] == [5, 10]
    for r in bd:
        assert r["interval_s"] > 0 and r["dispatch_s"] > 0
        assert r["data_wait_s"] >= 0
        # the barrier-timed field is gone: interval_s is the device-bound
        # step time, and the row holds nothing the loop had to block for
        assert "device_s" not in r

    beats = [r for r in rows if r["kind"] == "heartbeat"]
    assert [r["step"] for r in beats] == [4, 8, 12]

    summary = [r for r in rows if r["kind"] == "run_summary"]
    assert len(summary) == 1 and summary[0]["anomaly_events"] == 0
    # the sink is ordered: train_time (the logger's mirrored footer) is last
    assert rows[-1]["kind"] == "train_time" and rows[-1]["seconds"] > 0


def test_fit_nan_flight_recorder_end_to_end(tmp_path):
    """Injected NaN: the in-graph guard skips the update, training
    continues finite, the sentry logs one structured anomaly per poisoned
    epoch pass, and the profiler captures an on-demand window."""
    # row 36 lands in batch index 2 of every epoch (rows 32..47)
    state, losses = fit(
        _tiny_lm(), optax.adam(1e-3), _loader(poison_row=36), epochs=2,
        job_id="NA", batch_size=16, loss_fn=_poisoned_loss,
        input_key="tokens", label_key="tokens", log_dir=str(tmp_path),
        telemetry=TelemetryConfig(capture_steps=2, cooldown_steps=1),
        profile=True,
    )
    # steps 3 and 7 are the poisoned ones: loss NaN, everything else finite
    assert len(losses) == 8
    assert not np.isfinite(losses[2]) and not np.isfinite(losses[6])
    finite = [l for i, l in enumerate(losses) if i not in (2, 6)]
    assert all(np.isfinite(finite))
    # the skipped update did not poison params: later losses keep improving
    assert finite[-1] < finite[0]

    rows = _rows(tmp_path / "NA_telemetry_0.jsonl")
    anomalies = [r for r in rows if r["kind"] == "anomaly"]
    assert [a["step"] for a in anomalies] == [3, 7]
    for a in anomalies:
        assert a["event"] == "nonfinite"
        assert a["loss"] is None  # NaN serialized as null, strict JSON
        assert a["update_skipped"] == 1
        assert a["profiler_armed"] is True
    summary = next(r for r in rows if r["kind"] == "run_summary")
    assert summary["anomaly_events"] == 2

    # a trace window was captured (scheduled and/or armed; sub-second
    # windows may share one timestamped dir — same caveat as
    # test_profiling.py)
    profile_root = tmp_path / "log_NA" / "plugins" / "profile"
    assert profile_root.exists() and any(
        f.suffix == ".pb" for d in profile_root.iterdir() for f in d.rglob("*")
    )


def test_fit_telemetry_off_keeps_reference_tsv_contract(tmp_path):
    """telemetry=False (the default): no JSONL stream exists, and the TSV
    holds ONLY the reference contract's lines — header, data rows, the
    HBM/TrainTime tagged footers. Byte-format compatibility is what the
    baseline comparison tooling parses."""
    from tpudist.metrics import HEADER

    fit(
        _tiny_lm(), optax.adam(1e-3), _loader(), epochs=2, job_id="OFF",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), profile=False,
    )
    assert not list(tmp_path.glob("*telemetry*"))
    lines = (tmp_path / "OFF_16_0.log").read_text().splitlines()
    assert lines[0] == HEADER.strip()
    assert lines[-1].startswith("TrainTime\t")
    for row in lines[1:-1]:
        fields = row.split("\t")
        if fields[0] in ("HBM",):
            continue
        # a reference data row: datetime, g_step, g_img, loss, ex/sec
        assert len(fields) == 5
        int(fields[1]), int(fields[2])
        float(fields[3]), float(fields[4])


def test_fit_memory_log_cadence_respects_backend(tmp_path):
    """memory_log_every=None auto-disables on CPU (no allocator stats —
    zero probe calls), and an explicit cadence still writes nothing where
    the backend reports nothing (log_memory's own no-op guard)."""
    from tpudist.memory import device_memory_stats

    assert device_memory_stats() is None  # this suite runs on CPU: auto-off
    fit(
        _tiny_lm(), optax.adam(1e-3), _loader(), epochs=1, job_id="MEM",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), profile=False,
        memory_log_every=2,
    )
    assert "HBM" not in (tmp_path / "MEM_16_0.log").read_text()


def test_fit_telemetry_respects_config_toggles(tmp_path):
    """health_metrics/breakdown/mfu off ⇒ those rows are absent while the
    sentry still watches the loss stream."""
    cfg = TelemetryConfig(
        health_metrics=False, guard_nonfinite=False, breakdown=False,
        mfu=False,
    )
    fit(
        _tiny_lm(), optax.adam(1e-3), _loader(), epochs=1, job_id="TG",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), telemetry=cfg,
        profile=False,
    )
    rows = _rows(tmp_path / "TG_telemetry_0.jsonl")
    kinds = {r["kind"] for r in rows}
    assert "health" not in kinds and "mfu" not in kinds
    assert "step_breakdown" not in kinds and "run_meta" not in kinds
    assert "run_summary" in kinds


def test_fit_reduce_streams_comm_rows(tmp_path):
    """fit(reduce='quantized', telemetry=...): the one-time `comm` setup row
    (bucket geometry + measured standalone probe) lands in the stream, and
    every step_breakdown row carries the comm column pair — comm_bytes from
    the compiled step's metrics via the delayed fetch, comm_s from the
    probe."""
    state, losses = fit(
        _tiny_lm(), optax.adam(1e-3), _loader(), epochs=3, job_id="CR",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), profile=False,
        reduce="quantized", telemetry=TelemetryConfig(sentry=False),
    )
    assert state.comm_residual is not None
    rows = _rows(tmp_path / "CR_telemetry_0.jsonl")
    comm = [r for r in rows if r["kind"] == "comm"]
    assert len(comm) == 1
    assert comm[0]["method"] == "quantized" and comm[0]["world"] == 8
    assert comm[0]["probe_s"] > 0
    # the ≥3x wire-compression claim, recorded per run
    assert comm[0]["fp32_bytes_per_step"] >= 3 * comm[0]["bytes_per_step"]
    bd = [r for r in rows if r["kind"] == "step_breakdown"]
    assert bd
    for r in bd:
        assert r["comm_bytes"] == comm[0]["bytes_per_step"]
        assert r["comm_s"] == comm[0]["probe_s"]
    # health rows see the dequantized-grad counters, still clean ints
    health = [r for r in rows if r["kind"] == "health"]
    assert health and all(r["nonfinite_grad_count"] == 0 for r in health)


def test_fit_moe_rows_and_real_moe_mfu(tmp_path):
    """Router observability end-to-end (docs/OBSERVABILITY.md §1): a
    sparse fit() writes 'moe' rows on the health cadence — per-layer load
    fractions [E] summing to 1 − dropped — and its 'mfu' rows carry the
    ACTIVE-param flops counter (MoE MFU is a real number, not None)."""
    model = GPT2(vocab_size=VOCAB, max_seq_len=16, hidden_dim=32, depth=2,
                 num_heads=2, num_experts=4, capacity_factor=2.0)
    state, losses = fit(
        model, optax.adam(1e-3), _loader(), epochs=2, job_id="MO",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), telemetry=True,
        profile=False,
    )
    assert all(np.isfinite(losses))
    rows = _rows(tmp_path / "MO_telemetry_0.jsonl")
    moe = [r for r in rows if r["kind"] == "moe"]
    assert moe  # cadence steps of the 8-step run
    for r in moe:
        load = r["h_1/load"]
        assert isinstance(load, list) and len(load) == 4
        np.testing.assert_allclose(sum(load), 1.0 - r["h_1/dropped"],
                                   rtol=1e-5)
        assert np.isfinite(r["h_1/aux"])
    mfu = [r for r in rows if r["kind"] == "mfu"]
    assert mfu
    from tpudist.telemetry import flops

    want = flops.gpt2_moe_train_flops(
        16.0 * 16, hidden=32, depth=2, vocab=VOCAB, seq=16,
        num_experts=4, moe_every=2, top_k=2,
    )
    for r in mfu:
        assert r["flops_per_step"] == want
        # default peak on the CPU: no published peak to be a share of, so
        # the field is null — never a v5e utilisation from a CPU run
        assert r["mfu"] is None and r["tokens_per_sec"] > 0
    (meta,) = [r for r in rows if r["kind"] == "run_meta"]
    assert meta["peak_flops_per_chip"] is None


# -- the loop's spans: one helper, the profiler's timeline and the stream ---

# the main thread's top-level spans: the declared ones but the input
# pipeline's, which nest under fit/next_batch or run on the producer thread
MAIN_SPANS = tuple(n for n in FIT_SPANS if not n.startswith("input/"))


def _traced_fit(tmp_path, prefixes=("fit/", "input/", "tpudist_train"),
                **kw):
    """A 4-step ``fit(profile=False)`` under a profiler session the TEST
    started — any session will do, as the benchmark's does."""
    import glob

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=options)
    try:
        fit(
            _tiny_lm(), optax.adam(1e-3), _loader(), epochs=1, job_id="SP",
            batch_size=16, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", log_dir=str(tmp_path), profile=False,
            memory_log_every=1, checkpoint_dir=str(tmp_path / "ck"),
            checkpoint_every=1, **kw,
        )
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "prof/plugins/profile/*/*.xplane.pb"))
    by_name = {}
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    by_name.setdefault(e.name, []).append(
                        (i, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return by_name


def test_fit_spans_once_a_step_on_the_profilers_timeline(tmp_path):
    """Every span of docs/OBSERVABILITY.md §8's table shows once a step
    with its step_num, under a session fit did not start; the main
    thread's top-level spans do not overlap, the input spans nest under
    fit/next_batch, and input/produce runs on another thread's line."""
    spans = _traced_fit(tmp_path, telemetry=TelemetryConfig(trace=True))
    # the next() that ends the epoch is no step's work: tagged, set aside
    ended = {name: [s for *_, s in events if "end" in s]
             for name, events in spans.items()}
    spans = {name: [e for e in events if "end" not in e[3]]
             for name, events in spans.items()}
    assert [s["step_num"] for s in ended.pop("fit/next_batch")] == [5]
    assert [s["batch"] for s in ended.pop("input/wait")] == [4]
    assert [s["batch"] for s in ended.pop("input/produce")] == [4]
    assert not any(ended.values())
    assert set(spans) == set(FIT_SPANS)  # declared = emitted, both ways
    for name in MAIN_SPANS:
        steps = sorted(s["step_num"] for _, _, _, s in spans[name])
        assert steps == [1, 2, 3, 4], name
    (main,) = {line for name in MAIN_SPANS for line, *_ in spans[name]}
    top = sorted((a, b, name) for name in MAIN_SPANS
                 for _, a, b, _ in spans[name])
    for (_, end, first), (start, _, second) in zip(top, top[1:]):
        assert end <= start, (first, second)
    parents = [(a, b) for _, a, b, _ in spans["fit/next_batch"]]
    for name in ("input/wait", "input/stage"):
        assert {line for line, *_ in spans[name]} == {main}
        for _, a, b, _ in spans[name]:
            assert any(pa <= a and b <= pb for pa, pb in parents), name
    assert sorted(s["batch"] for *_, s in spans["input/stage"]) == [0, 1, 2, 3]
    assert {line for line, *_ in spans["input/produce"]} != {main}
    assert sorted(s["batch"] for *_, s in spans["input/produce"]) == [
        0, 1, 2, 3]


def test_fit_span_rows_ride_the_stream_with_trace_on(tmp_path):
    """trace=True: the same spans are rows too, one a step, the save under
    its older name; the per-step `step` row no longer carries device_s."""
    _traced_fit(tmp_path, telemetry=TelemetryConfig(trace=True))
    rows = [r for r in _rows(tmp_path / "SP_telemetry_0.jsonl")
            if r["kind"] == "span"]
    names = {r["name"] for r in rows}
    assert "fit/checkpoint" not in names
    for name in ("tpudist_train", "fit/health", "fit/resolve_wait",
                 "fit/log", "fit/memory_stats", "checkpoint", "step"):
        assert [r["step"] for r in rows if r["name"] == name] == [
            1, 2, 3, 4], name
    assert all("device_s" not in r for r in rows)
    assert all(r["cat"] == "train" and r["ph"] == "X" for r in rows
               if r["name"].startswith(("fit/", "input/")))


def test_fit_stream_without_trace_has_no_span_rows_and_no_device_s(tmp_path):
    """trace off (the default): spans are profiler annotations only, so
    the stream holds the kinds and fields it held before, less the
    barrier-timed device_s."""
    fit(
        _tiny_lm(), optax.adam(1e-3), _loader(), epochs=2, job_id="NS",
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), profile=False,
        telemetry=TelemetryConfig(mfu=False),
    )
    rows = _rows(tmp_path / "NS_telemetry_0.jsonl")
    assert [r["kind"] for r in rows] == [
        "bringup", "throughput", "health", "step_breakdown", "run_summary",
        "train_time",
    ]
    assert [k for k in rows[3] if k != "run_id"] == [
        "v", "t", "kind", "rank", "step", "interval_s", "data_wait_s",
        "dispatch_s"]


def _scope_paths(lowered):
    import re

    return set(re.findall(r'loc\("(jit\(step_fn\)[^"]*)"',
                          lowered.as_text(debug_info=True)))


def test_lowered_step_names_the_new_scopes_and_leaves_attention_bare():
    """named_scope is metadata: the lowered step's op paths hold
    optimizer, grad_clip and loss_head, and nothing sits between a block's
    flax scope and its attention pallas_call (the benchmark finds the
    kernel by the name XLA derives from that scope)."""
    import re

    from tpudist import mesh as mesh_lib
    from tpudist.models.gpt2 import chunked_lm_forward
    from tpudist.optim import make_optimizer
    from tpudist.train import create_train_state, make_train_step

    mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
    model = GPT2(vocab_size=VOCAB, max_seq_len=32, hidden_dim=32, depth=2,
                 num_heads=4, dtype=jnp.bfloat16, attn_impl="vmem",
                 mesh=mesh, dropout=0.0)
    for fused in (True, False):
        tx = make_optimizer(
            1e-3, optimizer="adam", weight_decay=0.1, clip_norm=1.0,
            fused=fused, compute_dtype=jnp.bfloat16 if fused else None)
        state = create_train_state(
            model, 0, jnp.zeros((1, 32), jnp.int32), tx, mesh=mesh)
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", fused="all" if fused else None,
            forward_loss=chunked_lm_forward(model, chunk=16))
        paths = _scope_paths(step.jitted.lower(
            state, step.stage({"tokens": np.zeros((4, 32), np.int32)})))
        assert any("/optimizer/grad_clip/" in p for p in paths), fused
        assert any("/jvp(loss_head)/" in p for p in paths)
        assert any("/transpose(jvp(loss_head))/" in p for p in paths)
        if fused:
            # the one-pass update is plain XLA under the scope: its ops
            # are named, and it brings no kernel of its own
            assert "jit(step_fn)/optimizer/sqrt" in paths
            assert not any("/optimizer/" in p and p.endswith("pallas_call")
                           for p in paths)
        kernels = {p for p in paths if p.endswith("/pallas_call")
                   and re.search(r"/h_\d+/", p)}
        attention = {p for p in kernels if re.search(r"/h_\d+/pallas_call$", p)}
        assert {p.split("/")[1] + "/" + p.split("/")[2] for p in attention} == {
            "jvp(GPT2)/h_0", "jvp(GPT2)/h_1",
            "transpose(jvp(GPT2))/h_0", "transpose(jvp(GPT2))/h_1"}
        # the others in a block are the fused norms, under scopes of their own
        assert all(re.search(r"/h_\d+/ln_[12]/pallas_call$", p)
                   for p in kernels - attention)


def test_lowered_mesh_step_keeps_the_blocks_name_on_the_attention_kernel():
    """On a multi-device mesh the kernel runs inside a shard_map, whose
    body starts the name stack afresh: the block's name is put back around
    the kernel there (tests/test_tpu_compile.py shows XLA then names the
    call ``h_<n>.<k>``); the fused norms' kernels stay bare."""
    import re

    from tpudist import mesh as mesh_lib
    from tpudist.train import create_train_state, make_train_step

    mesh = mesh_lib.create_mesh(devices=jax.devices()[:4])
    model = GPT2(vocab_size=VOCAB, max_seq_len=32, hidden_dim=32, depth=2,
                 num_heads=4, dtype=jnp.bfloat16, attn_impl="vmem",
                 mesh=mesh, dropout=0.0)
    tx = optax.adam(1e-3)
    state = create_train_state(
        model, 0, jnp.zeros((1, 32), jnp.int32), tx, mesh=mesh)
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", fused="ln")
    text = step.jitted.lower(
        state, step.stage({"tokens": np.zeros((8, 32), np.int32)})
    ).as_text(debug_info=True)
    kernels = set(re.findall(r'loc\("([^"]*pallas_call)"', text))
    assert kernels == {"h_0/pallas_call", "h_1/pallas_call", "pallas_call"}


def test_lowered_explicit_reducer_and_policy_casts_are_named():
    """grad_exchange names the exchange the program writes itself (the
    explicit reducer); cast names the precision policy's casts."""
    from tpudist import amp, mesh as mesh_lib
    from tpudist.train import create_train_state, make_train_step

    mesh = mesh_lib.create_mesh()
    model = _tiny_lm()
    tx = optax.adam(1e-3)
    state = create_train_state(
        model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh=mesh)
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", reduce="bucketed")
    state = step.grad_reducer.attach_residual(state)
    lowered = step.jitted.lower(
        state, step.stage({"tokens": np.zeros((16, 16), np.int32)}))
    # a shard_map body's name stack starts afresh: no jit(step_fn) prefix
    assert 'loc("grad_exchange/psum"' in lowered.as_text(debug_info=True)
    cast = jax.jit(amp.BF16_COMPUTE.cast_to_compute).lower(
        {"w": jnp.ones((2, 2))}).as_text(debug_info=True)
    assert "/cast/" in cast


# -- the bring-up account: contiguous phases, a compile table, one row ------


def _listeners():
    """JAX's registered monitoring listeners: (durations, events)."""
    from jax._src import monitoring

    return (len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners()))


def _fit(tmp_path, job, loader=None, **kw):
    return fit(
        _tiny_lm(), kw.pop("tx", optax.adam(1e-3)), loader or _loader(),
        epochs=kw.pop("epochs", 1), job_id=job, batch_size=16,
        loss_fn=kw.pop("loss_fn", lm_loss), input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), profile=False, **kw)


def test_fit_writes_one_bringup_row_after_step_one(tmp_path):
    """Telemetry on, trace off: exactly one ``bringup`` row, written when
    the first dispatch has returned — before any row of step 1 — whose
    phases are the declared ones in the declared order, each starting
    where the one before ended, from ``fit``'s entry, summing to
    ``total_s``; the compile table names the step function; and the
    stream holds no ``span`` row and, the run being steady, no
    ``recompile`` warning."""
    import time

    before = time.monotonic(), time.perf_counter()
    _fit(tmp_path, "BU", epochs=2, telemetry=TelemetryConfig(mfu=False))
    rows = _rows(tmp_path / "BU_telemetry_0.jsonl")
    assert rows[0]["kind"] == "bringup" and rows[0]["step"] == 1
    (row,) = [r for r in rows if r["kind"] == "bringup"]
    assert not [r for r in rows if r["kind"] in ("span", "warning")]

    assert [name for name, _, _ in row["phases"]] == list(BRINGUP_SPANS)
    assert row["phases"][0][1] == 0.0
    for (_, t0, dur_s), (name, t1, _) in zip(row["phases"], row["phases"][1:]):
        assert t1 == pytest.approx(t0 + dur_s, abs=1e-9), name
        assert dur_s >= 0.0
    assert sum(d for _, _, d in row["phases"]) == pytest.approx(
        row["total_s"], abs=1e-9)
    # the entry on both clocks, read inside this call of fit
    assert before[0] <= row["t_entry"] <= time.monotonic() - row["total_s"]
    assert before[1] <= row["t_entry_perf"] \
        <= time.perf_counter() - row["total_s"]

    table = {entry["fun"]: entry for entry in row["compile"]}
    assert len(row["compile"]) <= 13 and "other" in table
    for fun in ("step_fn", "_init"):  # the step and the state's init
        assert table[fun]["n"] >= 1
        assert table[fun]["trace_s"] > 0 and table[fun]["lower_s"] > 0
        assert table[fun]["backend_s"] > 0
    assert 0 < row["trace_lower_s"] <= sum(
        entry["trace_s"] + entry["lower_s"] for entry in row["compile"])
    assert row["backend_s"] > 0
    # the union of all three lies inside fit's bring-up
    assert row["trace_lower_s"] + row["backend_s"] <= row["total_s"]
    assert row["cache_hits"] >= 0 and row["cache_misses"] >= 0
    # the first dispatch compiles the step: most of its phase, and more
    # than every phase before the loop but the state's init
    phase = {name: dur_s for name, _, dur_s in row["phases"]}
    assert phase["bringup/first_dispatch"] >= table["step_fn"]["backend_s"]
    assert phase["bringup/init_state"] >= table["_init"]["backend_s"]


def test_fit_bringup_phases_on_the_profilers_timeline_without_telemetry(
        tmp_path):
    """Telemetry off: the phases are profiler annotations all the same —
    a session around ``fit`` shows each once, in the declared order, on
    the main thread's line, none overlapping the next, step 1's dispatch
    inside the last."""
    events = _traced_fit(tmp_path, prefixes=("bringup/", "tpudist_train"))
    phases = sorted((a, b, name, line) for name, found in events.items()
                    if name.startswith("bringup/")
                    for line, a, b, _ in found)
    assert [name for _, _, name, _ in phases] == list(BRINGUP_SPANS)
    for (_, end, first, _), (start, _, second, _) in zip(phases, phases[1:]):
        assert end <= start, (first, second)
    (line,) = {line for *_, line in phases}
    (first_step,) = [(a, b) for at, a, b, stats in events["tpudist_train"]
                     if stats["step_num"] == 1 and at == line]
    start, end, name, _ = phases[-1]
    assert name == "bringup/first_dispatch"
    assert start <= first_step[0] and first_step[1] <= end
    assert not list(tmp_path.glob("*telemetry*"))


def test_fit_bringup_phases_replay_as_span_rows_with_trace_on(tmp_path):
    """trace=True: the phases are ``span`` rows too, on the tracer's clock
    (``t_entry`` + the row's offsets), the ones closed before the sink was
    up replayed in order; the loop's spans of step 1 nest in the last two."""
    _fit(tmp_path, "BT", telemetry=TelemetryConfig(trace=True))
    rows = _rows(tmp_path / "BT_telemetry_0.jsonl")
    (row,) = [r for r in rows if r["kind"] == "bringup"]
    spans = [r for r in rows if r["kind"] == "span"]
    phases = [r for r in spans if r["name"].startswith("bringup/")]
    assert [r["name"] for r in phases] == list(BRINGUP_SPANS)
    for r, (name, offset, dur_s) in zip(phases, row["phases"]):
        assert r["ph"] == "X" and r["cat"] == "train"
        assert r["t0"] == pytest.approx(row["t_entry"] + offset, abs=1e-9)
        assert r["dur_s"] == dur_s
    by_name = {r["name"]: r for r in phases}

    def inside(child, parent):
        return (parent["t0"] <= child["t0"] and child["t0"] + child["dur_s"]
                <= parent["t0"] + parent["dur_s"])

    (first_next,) = [r for r in spans if r["name"] == "fit/next_batch"
                     and r["step"] == 1]
    (first_step,) = [r for r in spans if r["name"] == "tpudist_train"
                     and r["step"] == 1]
    assert inside(first_next, by_name["bringup/first_batch"])
    assert inside(first_step, by_name["bringup/first_dispatch"])


def _raising_init(params):
    raise RuntimeError("no optimizer state today")


def _raising_loss(logits, tokens):
    raise RuntimeError("no loss today")


@pytest.mark.parametrize("telemetry", [False, True])
@pytest.mark.parametrize("raises", [None, "in_init_state", "in_first_dispatch"])
def test_fit_leaves_no_listener_behind(tmp_path, monkeypatch, telemetry,
                                       raises):
    """With telemetry off ``fit`` registers no ``jax.monitoring`` listener,
    keeps no buffer and writes no row; with it on, its listeners are there
    while it runs and gone when it returns or raises, wherever."""
    from tpudist.telemetry import trace

    made, during = [], []

    class Watched(trace.Bringup):
        def __init__(self, **kw):
            super().__init__(**kw)
            made.append(self)
            during.append(_listeners())

    monkeypatch.setattr(trace, "Bringup", Watched)
    kw = {"in_init_state": {"tx": optax.GradientTransformation(
              _raising_init, None)},
          "in_first_dispatch": {"loss_fn": _raising_loss}}.get(raises, {})
    before = _listeners()
    if raises:
        with pytest.raises(RuntimeError, match="today"):
            _fit(tmp_path, "LS", telemetry=telemetry, **kw)
    else:
        _fit(tmp_path, "LS", telemetry=telemetry)
    assert _listeners() == before
    (bringup,) = made
    own = 1 if telemetry else 0
    assert during == [(before[0] + own, before[1] + own)]
    if not telemetry:
        assert bringup.phases is None
        assert not list(tmp_path.glob("*telemetry*"))
    else:
        rows = _rows(tmp_path / "LS_telemetry_0.jsonl") if (
            tmp_path / "LS_telemetry_0.jsonl").exists() else []
        assert len([r for r in rows if r["kind"] == "bringup"]) == (
            0 if raises else 1)
        # what it kept is whole: every phase entered was closed
        names = [name for name, _, _ in bringup.phases]
        assert names == list(BRINGUP_SPANS)[:len(names)]
        assert len(names) == {None: 9, "in_init_state": 2,
                              "in_first_dispatch": 9}[raises]


class _Ragged:
    """A sized loader whose batches have the row counts it is given."""

    batch_size = 16

    def __init__(self, rows):
        self.rows = rows

    def __len__(self):
        return len(self.rows)

    def __iter__(self):
        rng = np.random.Generator(np.random.PCG64(0))
        for n in self.rows:
            yield {"tokens": rng.integers(0, 254, (n, 16)).astype(np.int32)}


@pytest.mark.parametrize("rows, want", [
    ([16, 16, 8, 16, 16], [3]),   # the third batch has another shape
    ([16, 16, 16, 16, 16], []),   # a steady run
])
def test_fit_names_the_step_that_recompiled(tmp_path, rows, want):
    """A compile that ends after the first dispatch has returned is a
    ``recompile`` warning with the step being dispatched, the function's
    name and its trace / lower / backend seconds, and with trace=True an
    instant on the timeline; a steady run has none."""
    _fit(tmp_path, "RC", _Ragged(rows), telemetry=TelemetryConfig(trace=True))
    stream = _rows(tmp_path / "RC_telemetry_0.jsonl")
    warned = [r for r in stream if r["kind"] == "warning"
              and r["tag"] == "recompile"]
    assert [r["step"] for r in warned] == want
    for r in warned:
        assert r["fun"] == "step_fn"
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
    instants = [r for r in stream if r["kind"] == "span"
                and r["name"] == "recompile"]
    assert [(r["step"], r["ph"], r["fun"]) for r in instants] == [
        (s, "i", "step_fn") for s in want]
    # the bring-up's own compile of the step is in its row, not a warning
    (row,) = [r for r in stream if r["kind"] == "bringup"]
    assert "step_fn" in {entry["fun"] for entry in row["compile"]}


@pytest.mark.parametrize("case", ["fresh", "resumed", "aot_cache"])
def test_goodput_bringup_counts_from_fits_entry(tmp_path, case):
    """The run report's ``goodput.bringup_s`` is what its docstring says:
    fit entry → first loop iteration. With the restore, the cache load and
    the AOT path's compile it equals the sum of the seven phases before
    the loop, and the partition still sums to ``total_s``."""
    from tpudist.resilience.goodput import COMPONENTS

    kw = {}
    if case == "resumed":
        kw = {"checkpoint_dir": str(tmp_path / "ck"), "checkpoint_every": 2}
        _fit(tmp_path, "G0", telemetry=True, **kw)
        kw["epochs"] = 2
    elif case == "aot_cache":
        kw = {"compile_cache": str(tmp_path / "cc")}
    _fit(tmp_path, "GP", telemetry=True, **kw)
    goodput = json.loads((tmp_path / "GP_report.json").read_text())["goodput"]
    (row,) = [r for r in _rows(tmp_path / "GP_telemetry_0.jsonl")
              if r["kind"] == "bringup"]
    before_loop = sum(dur_s for name, _, dur_s in row["phases"]
                      if "fit_bringup_s" in BRINGUP_SPANS[name])
    booked = goodput["bringup_s"] + goodput["restore_s"] \
        + goodput["cache_load_s"]
    if case == "aot_cache":
        # compiled at bring-up (a miss): booked there, and iteration 1 is
        # an ordinary step
        assert goodput["compile_s"] > 0
        booked += goodput["compile_s"]
    else:
        # iteration 1 whole: the two phases of the first step and its rest
        assert goodput["compile_s"] >= row["total_s"] - before_loop - 5e-3
    if case == "resumed":
        assert goodput["restore_s"] > 0 and row["step"] == 5
    assert booked == pytest.approx(before_loop, abs=5e-3)
    assert goodput["total_s"] >= row["total_s"]
    assert goodput["productive_step_s"] + sum(
        goodput[c] for c in COMPONENTS) == pytest.approx(
            goodput["total_s"], abs=1e-5)
