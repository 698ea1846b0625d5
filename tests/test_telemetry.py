"""Telemetry subsystem units (tpudist.telemetry): the analytic FLOPs
counters (shared by examples/mfu_probe.py and fit()'s MFU rows), the
JSONL sink's strict-JSON contract, the
NaN/divergence sentry's firing rules, and the in-step health metrics /
non-finite update guard inside the compiled train step."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.telemetry import (
    NanSentry,
    TelemetryConfig,
    TelemetrySink,
    TimedIterator,
    build_telemetry,
    flops,
)


# -- flops counters ----------------------------------------------------------


def test_gpt2_counter_matches_hand_math():
    # the bench_gpt2_wide hand model this counter replaced, verbatim
    t, h, depth, vocab, seq = 8192.0, 1536, 12, 50257, 1024
    hand = (
        6.0 * t * (depth * 12 * h * h + vocab * h)
        + depth * 12.0 * t * seq * h
    )
    assert flops.gpt2_train_flops(
        t, hidden=h, depth=depth, vocab=vocab, seq=seq
    ) == hand


def test_llama_counter_matches_hand_math():
    t, d, depth, ffn, vocab, seq, kv = 4096.0, 768, 12, 2048, 32000, 1024, 4
    dh = d // 12
    layer_p = 2 * d * d + 2 * d * (kv * dh) + 3 * d * ffn
    hand = 6.0 * t * (depth * layer_p + vocab * d) + depth * 12.0 * t * seq * d
    assert flops.llama_train_flops(
        t, hidden=d, depth=depth, ffn_dim=ffn, vocab=vocab, seq=seq,
        num_heads=12, num_kv_heads=kv,
    ) == hand


def test_bert_counter_matches_hand_math():
    bt, bd, bvocab, bseq = 2048.0, 768, 30522, 512
    hand = (
        6.0 * bt * (12 * 12 * bd * bd + bd * bd + bvocab * bd)
        + 12 * 12.0 * bt * bseq * bd
    )
    assert flops.bert_train_flops(
        bt, hidden=bd, depth=12, vocab=bvocab, seq=bseq
    ) == hand


def test_t5_counter_matches_hand_math():
    # bench_t5's hand model, verbatim
    h, ffn, enc_d, dec_d, vocab = 512, 1024, 8, 8, 32128
    enc_len, dec_len = 482, 103
    te, td = 64.0 * enc_len, 64.0 * dec_len
    attn_p, mlp_p = 4 * h * h, 3 * h * ffn
    gemm = 3.0 * 2.0 * (
        te * enc_d * (attn_p + mlp_p)
        + td * dec_d * (attn_p + mlp_p)
        + dec_d * (2 * h * h * td + 2 * h * h * te)
        + td * vocab * h
    )
    attn = 6.0 * 2.0 * (
        te * enc_len * h * enc_d
        + td * dec_len * h * dec_d
        + td * enc_len * h * dec_d
    )
    assert flops.t5_train_flops(
        te, td, hidden=h, ffn_dim=ffn, enc_depth=enc_d, dec_depth=dec_d,
        vocab=vocab, enc_len=enc_len, dec_len=dec_len,
    ) == gemm + attn


def test_mfu_zero_duration_guard():
    assert flops.mfu(1e12, 0.0, peak=197e12) == 0.0
    assert flops.mfu(1e12, -1.0, peak=197e12) == 0.0
    assert flops.mfu(197e12, 1.0, peak=197e12, n_chips=1) == pytest.approx(1.0)
    assert flops.mfu(197e12, 1.0, peak=197e12, n_chips=8) == pytest.approx(1 / 8)


def test_dispatch_reads_model_geometry():
    from tpudist.models.gpt2 import GPT2
    from tpudist.models.llama import Llama

    model = GPT2(vocab_size=64, hidden_dim=32, depth=2, num_heads=2)
    assert model.flops_counter == "gpt2"
    batch = {"tokens": np.zeros((4, 16), np.int32)}
    assert flops.train_step_flops(model, batch) == flops.gpt2_train_flops(
        64.0, hidden=32, depth=2, vocab=64, seq=16
    )
    assert flops.tokens_per_step(model, batch) == 64

    # grad-accum staged layout [accum, micro, seq] counts all rows
    staged = {"tokens": np.zeros((2, 4, 16), np.int32)}
    assert flops.train_step_flops(model, staged) == flops.gpt2_train_flops(
        128.0, hidden=32, depth=2, vocab=64, seq=16
    )

    # llama's None ffn_dim mirrors the model's own SwiGLU sizing
    lm = Llama(vocab_size=64, hidden_dim=96, depth=1, num_heads=2)
    ffn = -(-8 * 96 // 3 // 256) * 256
    assert flops.train_step_flops(lm, batch) == flops.llama_train_flops(
        64.0, hidden=96, depth=1, ffn_dim=ffn, vocab=64, seq=16,
        num_heads=2, num_kv_heads=2,
    )


def test_dispatch_returns_none_not_zero():
    from tpudist.models.gpt2 import GPT2
    from tpudist.models.resnet import BottleneckBlock, ResNet, resnet18

    # no counter tag at all
    assert flops.train_step_flops(object(), {"tokens": np.zeros((1, 4))}) is None
    # tagged model, missing batch key (index-only DeviceCachedLoader batch)
    model = GPT2(vocab_size=64, hidden_dim=32, depth=1, num_heads=2)
    assert flops.train_step_flops(model, {"_idx": np.zeros(4)}) is None
    assert flops.tokens_per_step(model, {"_idx": np.zeros(4)}) is None
    # MoE GPT-2: the dense counter would miscount routed experts — sparse
    # geometries carry their own active-param counter instead of None
    moe = GPT2(vocab_size=64, hidden_dim=32, depth=2, num_heads=2,
               num_experts=4)
    assert moe.flops_counter == "gpt2_moe"
    # non-50-layer basic-block resnet: tagged, but the geometry has no
    # counter — None, never a guessed constant
    r18 = resnet18(num_classes=10)
    assert r18.flops_counter == "resnet"
    imgs = {"image": np.zeros((8, 224, 224, 3), np.float32)}
    assert flops.train_step_flops(r18, imgs, input_key="image") is None
    # the real ResNet-50 geometry does count
    r50 = ResNet(stage_sizes=(3, 4, 6, 3), block_cls=BottleneckBlock)
    assert flops.train_step_flops(r50, imgs, input_key="image") == pytest.approx(
        3.0 * flops.RESNET50_FWD_FLOPS_224 * 8
    )
    assert flops.tokens_per_step(r50, imgs, input_key="image") == 8


def test_moe_dispatch_reads_active_geometry():
    """Sparse models get REAL MFU numerators: the dispatch reads the MoE
    knobs off the model and routes to the active-param counters — the
    sparse count sits strictly between "experts were free" (dense count)
    and "every expert ran" (top_k < E)."""
    from tpudist.models.gpt2 import GPT2
    from tpudist.models.llama import Llama

    batch = {"tokens": np.zeros((4, 16), np.int32)}
    moe = GPT2(vocab_size=64, hidden_dim=32, depth=2, num_heads=2,
               num_experts=4, moe_every=2, moe_top_k=2)
    got = flops.train_step_flops(moe, batch)
    assert got == flops.gpt2_moe_train_flops(
        64.0, hidden=32, depth=2, vocab=64, seq=16,
        num_experts=4, moe_every=2, top_k=2,
    )
    dense = flops.gpt2_train_flops(64.0, hidden=32, depth=2, vocab=64,
                                   seq=16)
    assert got > dense  # router + the second active expert aren't free

    lm = Llama(vocab_size=64, hidden_dim=96, depth=2, num_heads=2,
               ffn_dim=64, num_experts=4, moe_every=1, moe_top_k=2)
    assert lm.flops_counter == "llama_moe"
    got = flops.train_step_flops(lm, batch)
    assert got == flops.llama_moe_train_flops(
        64.0, hidden=96, depth=2, ffn_dim=64, vocab=64, seq=16,
        num_heads=2, num_kv_heads=2, num_experts=4, moe_every=1, top_k=2,
    )


def test_t5_and_vit_dispatch():
    from tpudist.models.t5 import T5
    from tpudist.models.vit import ViT

    t5 = T5()
    batch = {
        "enc_tokens": np.zeros((4, 20), np.int32),
        "dec_tokens": np.zeros((4, 8), np.int32),
    }
    assert t5.flops_counter == "t5"
    assert flops.train_step_flops(t5, batch) == flops.t5_train_flops(
        80.0, 32.0, hidden=256, ffn_dim=512, enc_depth=4, dec_depth=4,
        vocab=512, enc_len=20, dec_len=8,
    )
    assert flops.tokens_per_step(t5, batch) == 80 + 32

    vit = ViT(hidden_dim=64, depth=2, num_heads=2, mlp_dim=256, patch_size=16)
    imgs = {"image": np.zeros((2, 224, 224, 3), np.float32)}
    seq = (224 // 16) ** 2 + 1
    assert flops.train_step_flops(vit, imgs, input_key="image") == flops.vit_train_flops(
        2.0 * seq, hidden=64, depth=2, seq=seq
    )
    # non-4x mlp: no tag, no fabricated numerator
    odd = ViT(hidden_dim=64, depth=2, num_heads=2, mlp_dim=128)
    assert odd.flops_counter is None


def test_mfu_probe_reads_the_flops_modules_gemm_table():
    """mfu_probe re-exports the flops module's GEMM table (its peak
    resolves through flops.device_peaks at run time)."""
    import importlib.util
    import pathlib

    repo = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "mfu_probe", repo / "examples" / "mfu_probe.py"
    )
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.gpt2_step_shapes is flops.gpt2_step_shapes
    shapes = flops.gpt2_step_shapes(1024, 768)
    assert len(shapes) == 15  # 5 GEMMs x (fwd, dgrad, wgrad)
    assert ("qkv fwd", 1024, 768, 3 * 768) in shapes


# -- sink --------------------------------------------------------------------


def test_sink_rows_are_strict_json(tmp_path):
    path = tmp_path / "t.jsonl"
    clock = iter([100.0, 101.5]).__next__
    with TelemetrySink(path, rank=3, clock=clock) as sink:
        sink.write("health", 7, loss=float("nan"), grad_norm=np.float32(2.5))
        sink.write("heartbeat", 8, note="x", big=np.int64(12))
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in rows] == ["health", "heartbeat"]
    assert rows[0] == {
        "v": 1, "t": 100.0, "kind": "health", "rank": 3, "step": 7,
        # NaN must become null — a bare NaN literal breaks json.loads
        "loss": None, "grad_norm": 2.5,
    }
    assert rows[1]["big"] == 12 and rows[1]["note"] == "x"


def test_sink_numpy_integers_stay_integers(tmp_path):
    """Counts (nonfinite_grad_count etc.) arrive as numpy scalars; the
    JSONL must keep them integers — 5, not 5.0 — for strict schema
    consumers, while float scalars stay floats."""
    path = tmp_path / "t.jsonl"
    with TelemetrySink(path) as sink:
        sink.write("health", 1, count=np.int32(5), norm=np.float32(1.5))
    row = json.loads(path.read_text())
    assert row["count"] == 5 and isinstance(row["count"], int)
    assert isinstance(row["norm"], float)


def test_sink_flushes_per_write(tmp_path):
    """The flight-recorder contract: the anomaly row must be on disk the
    moment write() returns (it has to survive the crash it describes)."""
    path = tmp_path / "t.jsonl"
    sink = TelemetrySink(path)
    sink.write("anomaly", 5, event="nonfinite")
    assert json.loads(path.read_text())["event"] == "nonfinite"
    sink.close()


# -- sentry ------------------------------------------------------------------


def test_sentry_fires_on_nonfinite_and_skips_window():
    s = NanSentry(window=8, min_steps=2, cooldown=4)
    assert s.observe(0, 1.0) is None
    assert s.observe(1, 1.1) is None
    ev = s.observe(2, float("nan"))
    assert ev["event"] == "nonfinite" and ev["step"] == 2
    # cooldown: the very next nonfinite is suppressed...
    assert s.observe(3, float("inf")) is None
    # ...and expires
    ev2 = s.observe(7, float("nan"), update_skipped=1)
    assert ev2["event"] == "nonfinite" and ev2["update_skipped"] == 1
    assert len(s.events) == 2


def test_sentry_fires_on_nonfinite_grad_count_with_finite_loss():
    s = NanSentry(min_steps=2)
    s.observe(0, 1.0)
    ev = s.observe(1, 1.0, nonfinite_count=17)
    assert ev["event"] == "nonfinite" and ev["nonfinite_grad_count"] == 17


def test_sentry_fires_on_guard_skip_with_finite_loss():
    """With health_metrics=False the compiled step reports no
    nonfinite_grad_count; the in-graph guard's update_skipped is then the
    only nonfinite signal and must fire on its own."""
    s = NanSentry(min_steps=2)
    s.observe(0, 1.0)
    ev = s.observe(1, 1.0, update_skipped=1)
    assert ev["event"] == "nonfinite" and ev["update_skipped"] == 1


def test_sentry_spike_detection_and_baseline_isolation():
    s = NanSentry(window=16, sigma=6.0, min_steps=8, cooldown=2)
    for i in range(8):
        assert s.observe(i, 1.0 + 0.01 * (i % 2)) is None
    ev = s.observe(8, 50.0)
    assert ev["event"] == "loss_spike"
    assert ev["loss"] == 50.0 and ev["threshold"] < 50.0
    # the spike must NOT have been pushed into the window: an identical
    # spike after cooldown still fires (the baseline didn't drift up)
    ev2 = s.observe(11, 50.0)
    assert ev2 is not None and ev2["event"] == "loss_spike"
    # normal losses keep flowing silently
    assert s.observe(14, 1.0) is None


def test_sentry_cooldown_keeps_anomalous_losses_out_of_window():
    """A diverging run that keeps emitting elevated losses DURING cooldown
    must not fold them into the baseline: after the quiet period the
    still-elevated loss fires again (the window held its pre-spike mean)."""
    s = NanSentry(window=16, sigma=6.0, min_steps=8, cooldown=4)
    for i in range(8):
        assert s.observe(i, 1.0 + 0.01 * (i % 2)) is None
    assert s.observe(8, 50.0)["event"] == "loss_spike"
    for i in range(9, 12):  # cooldown: suppressed rows, still anomalous
        assert s.observe(i, 50.0 + i) is None
    ev = s.observe(12, 70.0)  # cooldown over, baseline did NOT drift up
    assert ev is not None and ev["event"] == "loss_spike"
    assert ev["window_mean"] < 1.1


def test_config_step_kwargs_maps_to_compiled_step_knobs():
    from tpudist.telemetry import TelemetryConfig

    assert TelemetryConfig().step_kwargs() == {
        "telemetry": True, "guard_nonfinite": True,
    }
    assert TelemetryConfig(
        health_metrics=False, guard_nonfinite=True
    ).step_kwargs() == {"telemetry": False, "guard_nonfinite": True}


def test_sink_appends_across_restarts(tmp_path):
    """A checkpoint-resume reopening the same job_id's stream must not
    truncate a prior attempt's anomaly rows — the other half of the
    flight-recorder contract (the evidence has to outlive the restart)."""
    path = tmp_path / "t.jsonl"
    with TelemetrySink(path) as sink:
        sink.write("anomaly", 5, event="nonfinite")
    with TelemetrySink(path) as sink:  # the restarted attempt
        sink.write("heartbeat", 1)
    kinds = [json.loads(l)["kind"] for l in path.read_text().splitlines()]
    assert kinds == ["anomaly", "heartbeat"]


def test_sentry_plateau_does_not_fire_on_ulp_jitter():
    """Zero-variance window (converged/plateaued run): the spread floor
    keeps one-ulp jitter from registering as a spike, while a real
    excursion still fires."""
    s = NanSentry(window=16, sigma=8.0, min_steps=8)
    for i in range(12):
        assert s.observe(i, 2.0) is None
    assert s.observe(12, 2.0 + 1e-7) is None  # noise, not divergence
    ev = s.observe(13, 2.1)
    assert ev is not None and ev["event"] == "loss_spike"


def test_sentry_quiet_before_min_steps():
    s = NanSentry(min_steps=16)
    for i in range(10):
        assert s.observe(i, 1.0 if i % 2 else 100.0) is None  # no baseline yet


# -- timed iterator ----------------------------------------------------------


def test_timed_iterator_measures_wait():
    import time as _time

    def slow():
        yield 1
        _time.sleep(0.05)
        yield 2

    it = TimedIterator(slow())
    assert next(it) == 1
    fast_wait = it.last_wait_s
    assert next(it) == 2
    assert it.last_wait_s >= 0.04 > fast_wait
    with pytest.raises(StopIteration):
        next(it)


# -- in-step metrics + guard in the compiled step ---------------------------


def _lm_setup(guard: bool, telemetry: bool = True, skip_wrapper: bool = False):
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, make_train_step

    mesh = mesh_lib.create_mesh()
    model = GPT2(vocab_size=64, max_seq_len=8, hidden_dim=16, depth=1,
                 num_heads=2)
    tx = optax.adam(1e-2)
    if skip_wrapper:
        from tpudist.amp import skip_nonfinite

        tx = skip_nonfinite(tx)
    state = create_train_state(model, 0, jnp.zeros((1, 8), jnp.int32), tx, mesh)

    def loss_fn(logits, tokens):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        ).mean()
        # token 63 is the poison sentinel
        return jnp.where(jnp.any(tokens == 63), jnp.float32(jnp.nan), ce)

    step = make_train_step(
        model, tx, mesh, loss_fn=loss_fn, input_key="tokens",
        label_key="tokens", telemetry=telemetry, guard_nonfinite=guard,
    )
    return state, step


def test_in_step_health_metrics_match_host_norms():
    state, step = _lm_setup(guard=False)
    batch = {"tokens": (np.arange(8 * 8, dtype=np.int32).reshape(8, 8) % 60)}
    params_before = jax.tree_util.tree_map(np.asarray, state.params)
    new_state, metrics = step(state, batch)
    for k in ("loss", "grad_norm", "param_norm", "update_norm",
              "nonfinite_grad_count"):
        assert k in metrics
    assert int(metrics["nonfinite_grad_count"]) == 0
    # param_norm is the PRE-update global norm — recompute on host
    host_pnorm = math.sqrt(sum(
        float(jnp.sum(jnp.square(x)))
        for x in jax.tree_util.tree_leaves(params_before)
    ))
    # rel 1e-3: fp32 accumulation order differs between the fused in-graph
    # reduction and the host loop
    assert float(metrics["param_norm"]) == pytest.approx(host_pnorm, rel=1e-3)
    assert float(metrics["grad_norm"]) > 0
    assert float(metrics["update_norm"]) > 0


def test_guard_skips_poisoned_update_and_advances_step(
    no_persistent_compile_cache,
):
    state, step = _lm_setup(guard=True)
    clean = {"tokens": (np.arange(8 * 8, dtype=np.int32).reshape(8, 8) % 60)}
    poison = {"tokens": np.full((8, 8), 63, np.int32)}

    state, m = step(state, clean)
    assert int(m["update_skipped"]) == 0
    params_before = jax.tree_util.tree_map(np.asarray, state.params)
    opt_before = jax.tree_util.tree_map(np.asarray, state.opt_state)
    step_before = int(state.step)

    state, m = step(state, poison)
    assert not np.isfinite(float(m["loss"]))
    assert int(m["update_skipped"]) == 1
    # params AND optimizer state kept their pre-step values...
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        params_before, state.params,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        opt_before, state.opt_state,
    )
    # ...but the step counter advanced (data position / resume math exact)
    assert int(state.step) == step_before + 1

    # training continues: the next clean step moves params again
    state, m = step(state, clean)
    assert int(m["update_skipped"]) == 0
    moved = any(
        not np.array_equal(a, np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(params_before),
            jax.tree_util.tree_leaves(state.params),
        )
    )
    assert moved


def test_guard_preserves_skip_wrapper_counter(no_persistent_compile_cache):
    """The guard's opt-state freeze must NOT revert amp.skip_nonfinite's
    increment: after a poisoned step the counter reads 1 (so
    amp.skipped_steps and the run-summary's optimizer_nonfinite_skips stay
    truthful with the guard on) while the wrapped INNER state keeps its
    pre-step values like every other opt-state leaf."""
    from tpudist.amp import maybe_skipped_steps

    state, step = _lm_setup(guard=True, skip_wrapper=True)
    clean = {"tokens": (np.arange(8 * 8, dtype=np.int32).reshape(8, 8) % 60)}
    poison = {"tokens": np.full((8, 8), 63, np.int32)}

    state, _ = step(state, clean)
    assert maybe_skipped_steps(state.opt_state) == 0
    inner_before = jax.tree_util.tree_map(np.asarray, state.opt_state[0])

    state, m = step(state, poison)
    assert int(m["update_skipped"]) == 1
    assert maybe_skipped_steps(state.opt_state) == 1
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        inner_before, state.opt_state[0],
    )


def test_step_without_telemetry_keeps_reference_metrics_shape():
    """telemetry/guard off ⇒ the metrics pytree is exactly {"loss"} — the
    compiled program's output signature matches previous rounds."""
    state, step = _lm_setup(guard=False, telemetry=False)
    batch = {"tokens": (np.arange(8 * 8, dtype=np.int32).reshape(8, 8) % 60)}
    _, metrics = step(state, batch)
    assert set(metrics) == {"loss"}


# -- build_telemetry ---------------------------------------------------------


def test_build_telemetry_off_is_none(tmp_path):
    assert build_telemetry(
        False, job_id="J", log_dir=str(tmp_path), rank=0, world_size=1,
        log_every=5, n_chips=1,
    ) is None
    assert not list(tmp_path.iterdir())  # no sink file either


def test_build_telemetry_writes_per_rank_stream(tmp_path):
    tel = build_telemetry(
        TelemetryConfig(sentry=False), job_id="J", log_dir=str(tmp_path),
        rank=2, world_size=4, log_every=5, n_chips=8,
    )
    assert tel.sentry is None
    assert (tmp_path / "J_telemetry_2.jsonl").exists()
    tel.sink.close()


def test_heartbeat_every_zero_disables_heartbeats(tmp_path):
    """0 means OFF — the same off-switch contract as fit's
    memory_log_every; an `or`-style default would eat the 0."""
    from tpudist.telemetry import TelemetryConfig

    tel = build_telemetry(
        TelemetryConfig(heartbeat_every=0, mfu=False, sentry=False),
        job_id="J", log_dir=str(tmp_path), rank=0, world_size=1,
        log_every=1, n_chips=1,
    )
    for s in range(1, 6):
        tel.on_step(s, {"loss": 1.0}, epoch=0, interval_s=0.1)
    tel.sink.close()
    rows = [json.loads(l) for l in
            (tmp_path / "J_telemetry_0.jsonl").read_text().splitlines()]
    assert not any(r["kind"] == "heartbeat" for r in rows)


def test_maybe_skipped_steps_reads_amp_wrapper():
    from tpudist.amp import maybe_skipped_steps, skip_nonfinite

    params = {"w": jnp.ones(3)}
    tx = skip_nonfinite(optax.adam(1e-3))
    s = tx.init(params)
    assert maybe_skipped_steps(s) == 0
    _, s = tx.update({"w": jnp.full(3, jnp.nan)}, s, params)
    assert maybe_skipped_steps(s) == 1
    # a bare optax chain has no counter: None, not a fabricated 0
    assert maybe_skipped_steps(optax.adam(1e-3).init(params)) is None


# -- explicit-reduction comm accounting + link-bound diagnosis ---------------


def _bare_tel(tmp_path, **cfg_kw):
    return build_telemetry(
        TelemetryConfig(mfu=False, sentry=False, **cfg_kw), job_id="J",
        log_dir=str(tmp_path), rank=0, world_size=1, log_every=1, n_chips=1,
    )


def test_set_comm_writes_setup_row_and_breakdown_columns(tmp_path):
    """set_comm: one self-describing `comm` row (method/bucket geometry,
    fp32-equivalent bytes, measured probe), then every step_breakdown row
    carries the live comm_bytes (from the step's metrics — the delayed
    fetch) and the probe-derived comm_s column."""
    tel = _bare_tel(tmp_path)
    tel.set_comm(
        {"method": "quantized", "world": 8, "bytes_per_step": 1000,
         "fp32_bytes_per_step": 4000}, probe_s=0.0123,
    )
    tel.on_step(1, {"loss": 1.0, "comm_bytes": 1000.0}, epoch=0,
                interval_s=0.1, dispatch_s=0.05)
    tel.sink.close()
    rows = [json.loads(l) for l in
            (tmp_path / "J_telemetry_0.jsonl").read_text().splitlines()]
    comm = [r for r in rows if r["kind"] == "comm"]
    assert len(comm) == 1
    assert comm[0]["method"] == "quantized"
    assert comm[0]["fp32_bytes_per_step"] == 4000
    assert comm[0]["probe_s"] == 0.0123
    bd = [r for r in rows if r["kind"] == "step_breakdown"]
    assert bd and bd[0]["comm_bytes"] == 1000.0
    assert bd[0]["comm_s"] == 0.0123


def test_breakdown_rows_unchanged_without_comm(tmp_path):
    """Feature off ⇒ step_breakdown rows carry exactly the pre-existing
    fields — no null comm columns leaking into old dashboards."""
    tel = _bare_tel(tmp_path)
    tel.on_step(1, {"loss": 1.0}, epoch=0, interval_s=0.1, dispatch_s=0.05)
    tel.sink.close()
    rows = [json.loads(l) for l in
            (tmp_path / "J_telemetry_0.jsonl").read_text().splitlines()]
    bd = [r for r in rows if r["kind"] == "step_breakdown"][0]
    assert "comm_bytes" not in bd and "comm_s" not in bd
    assert not any(r["kind"] == "comm" for r in rows)


def test_link_bound_warning_fires_once_with_hint(tmp_path):
    """The fit() H2D diagnosis: staging the observed batch at the probed
    link rate would eat most of the step — ONE tagged warning row pointing
    at DeviceCachedLoader, not a silently slow run."""
    tel = _bare_tel(tmp_path)
    tel.h2d_mbps = 10.0  # a collapsed link
    tel.observe_batch({"image": np.zeros((256, 224, 224, 3), np.uint8)})
    for s in range(1, 4):
        tel.on_step(s, {"loss": 1.0}, epoch=0, interval_s=0.1,
                    dispatch_s=0.05)
    tel.sink.close()
    rows = [json.loads(l) for l in
            (tmp_path / "J_telemetry_0.jsonl").read_text().splitlines()]
    warns = [r for r in rows if r["kind"] == "warning"]
    assert len(warns) == 1  # one-shot, not a row per step
    assert warns[0]["tag"] == "h2d_link_bound"
    assert "DeviceCachedLoader" in warns[0]["hint"]
    assert warns[0]["h2d_mbps"] == 10.0
    assert warns[0]["est_staging_s"] > 0.5 * warns[0]["interval_s"]


def test_link_bound_warning_quiet_on_healthy_link(tmp_path):
    tel = _bare_tel(tmp_path)
    tel.h2d_mbps = 10_000.0  # healthy PCIe-class link
    tel.observe_batch({"image": np.zeros((16, 32, 32, 3), np.uint8)})
    for s in range(1, 5):  # past the warm-up skip: really evaluated
        tel.on_step(s, {"loss": 1.0}, epoch=0, interval_s=0.1,
                    dispatch_s=0.05)
    tel.sink.close()
    rows = [json.loads(l) for l in
            (tmp_path / "J_telemetry_0.jsonl").read_text().splitlines()]
    assert not any(r["kind"] == "warning" for r in rows)


def test_link_bound_warning_survives_compile_inflated_first_steps(tmp_path):
    """The first resolved intervals carry the jit compile (tens of seconds)
    — staging looks negligible against them. A one-shot check armed there
    would be permanently suppressed on exactly the link-bound runs it
    exists for; the warm-up skip keeps the diagnosis alive until
    steady-state intervals arrive."""
    tel = _bare_tel(tmp_path)
    tel.h2d_mbps = 10.0
    tel.observe_batch({"image": np.zeros((256, 224, 224, 3), np.uint8)})
    # steps 1-2: compile-inflated intervals where staging is <50%
    for s in (1, 2):
        tel.on_step(s, {"loss": 1.0}, epoch=0, interval_s=60.0,
                    dispatch_s=59.0)
    # steady state: staging dominates — the warning must still fire
    tel.on_step(3, {"loss": 1.0}, epoch=0, interval_s=0.1, dispatch_s=0.05)
    tel.sink.close()
    rows = [json.loads(l) for l in
            (tmp_path / "J_telemetry_0.jsonl").read_text().splitlines()]
    warns = [r for r in rows if r["kind"] == "warning"]
    assert len(warns) == 1 and warns[0]["tag"] == "h2d_link_bound"
