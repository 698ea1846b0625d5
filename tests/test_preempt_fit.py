"""The graceful-preemption story IN-PROCESS (tier-1, no subprocess
world): a chaos-injected SIGTERM at step k makes fit() finish the
in-flight step, write a synchronous emergency checkpoint at exactly k,
flush the run report with ``exit_reason="preempted"`` and a goodput
section whose components sum to wall time, and raise ``Preempted`` (the
SystemExit-75 the supervisor restarts on); a second fit() over the same
checkpoint dir — generation 1, same argv including the chaos spec —
resumes at k+1 and reproduces the uninterrupted run's loss trajectory
BIT-identically, through the int8-quantized gradient all-reduce + ZeRO-1
sharded optimizer (the paths with the most resume-sensitive state: the
error-feedback residual and the sharded Adam mirrors).

Model choice: the BN-free tiny MLP of test_dp_equivalence, not a
transformer — determinism is the point, and the resume runs cache-less
(``no_persistent_compile_cache``): XLA:CPU has been observed to
misexecute cache-LOADED executables on exactly the donated-step-on-
restored-arrays pattern the resume path is made of (the same documented
wart the guard tests opt out for; fresh compiles of the MLP cost
seconds)."""

import json
import signal

import numpy as np
import optax
import pytest
from flax import linen as nn

from tpudist.checkpoint import latest_step
from tpudist.data.loader import DataLoader
from tpudist.resilience import GENERATION_ENV, Preempted
from tpudist.telemetry import TelemetryConfig
from tpudist.train import fit

GOODPUT_PARTS = ("bringup_s", "restore_s", "compile_s", "data_wait_s",
                 "checkpoint_s", "productive_step_s")


class _TinyMlp(nn.Module):
    """Non-divisible leaf sizes (37/10) so the quantized layout's
    pad-and-slice math and ZeRO-1's pad-and-reshape both exercise."""

    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dense(10)(nn.relu(nn.Dense(37)(x)))


def _loader(batch: int = 16):
    rng = np.random.default_rng(0)
    data = {
        "image": rng.normal(size=(64, 13)).astype(np.float32),
        "label": (rng.random(64) * 10).astype(np.int32),
    }
    return DataLoader(data, batch)


def _fit(tmp_path, job_id, ckpt_dir, *, chaos=None, epochs=4,
         telemetry=False, **kw):
    return fit(
        _TinyMlp(), optax.adam(1e-2), _loader(), epochs=epochs,
        job_id=job_id, batch_size=16, log_dir=str(tmp_path),
        telemetry=telemetry, profile=False,
        checkpoint_dir=None if ckpt_dir is None else str(ckpt_dir),
        chaos=chaos,
        # the acceptance combination: quantized AR (error-feedback
        # residual in the train state) + ZeRO-1 sharded Adam mirrors
        reduce="quantized", shard_opt_state=True, **kw,
    )


def _goodput_sums(goodput):
    parts = sum(goodput[k] for k in GOODPUT_PARTS)
    assert parts == pytest.approx(goodput["total_s"], rel=0.01), goodput


def test_chaos_sigterm_emergency_checkpoint_then_bit_identical_resume(
        tmp_path, monkeypatch, no_persistent_compile_cache):
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    cfg = TelemetryConfig(sentry=False, mfu=False, heartbeat_every=4)

    # the uninterrupted reference: same model/data/optimizer/reduction —
    # and the same telemetry config, because guard_nonfinite changes the
    # COMPILED PROGRAM (the in-graph select guard) and bit-identity only
    # holds between identical programs — its own checkpoint dir, run end
    # to end: 4 epochs x 4 batches
    ref_state, ref_losses = _fit(
        tmp_path, "Ref", tmp_path / "ref_ckpt", checkpoint_every=4,
        telemetry=cfg,
    )
    assert len(ref_losses) == 16

    # generation 0: SIGTERM lands after step 6 completes (between the
    # step-based saves at 4 and 8) — fit must write the emergency
    # checkpoint AT 6, report "preempted", and exit restartable
    with pytest.raises(Preempted) as ei:
        _fit(tmp_path, "PR", tmp_path / "ckpt", chaos="sigterm@6",
             checkpoint_every=4, telemetry=cfg)
    assert ei.value.code == 75
    assert ei.value.step == 6
    assert latest_step(tmp_path / "ckpt") == 6

    report = json.loads((tmp_path / "PR_report.json").read_text())
    assert report["status"] == "preempted"
    assert report["exit_reason"] == "preempted"
    assert report["generation"] == 0
    goodput = report["goodput"]
    _goodput_sums(goodput)
    assert goodput["emergency_save_s"] > 0
    assert goodput["steps"] == 6

    # generation 1: the supervisor's relaunch — same argv (chaos spec
    # included: it is generation-0-gated and must NOT re-fire at the
    # resume step), TPUDIST_RESTART_GENERATION=1 exported
    monkeypatch.setenv(GENERATION_ENV, "1")
    state, losses = _fit(
        tmp_path, "PR", tmp_path / "ckpt", chaos="sigterm@6",
        checkpoint_every=4, telemetry=cfg,
    )
    assert int(state.step) == 16
    # resumed at k+1: exactly the 10 remaining steps, and the trajectory
    # through quantized-AR + ZeRO-1 is BIT-identical to the uninterrupted
    # run's tail — the emergency checkpoint lost nothing
    assert len(losses) == 10
    assert losses == ref_losses[6:]

    # the final report aggregates both lives of the job
    report = json.loads((tmp_path / "PR_report.json").read_text())
    assert report["exit_reason"] == "completed"
    assert report["generation"] == 1
    gens = report["goodput"]["generations"]
    assert [g["generation"] for g in gens] == [0, 1]
    assert gens[0]["exit_reason"] == "preempted"
    assert gens[1]["restore_s"] > 0  # the resume actually restored
    cum = report["goodput"]["cumulative"]
    assert cum["restart_overhead_s"] > 0
    assert cum["wall_s"] >= gens[0]["total_s"] + gens[1]["total_s"]

    # heartbeats from both generations share the append-mode stream,
    # attributable by the appended generation field
    rows = [
        json.loads(l)
        for l in (tmp_path / "PR_telemetry_0.jsonl").read_text().splitlines()
    ]
    beat_gens = {r["generation"] for r in rows if r["kind"] == "heartbeat"}
    assert beat_gens == {0, 1}


def test_preempt_without_checkpointing_still_reports_and_exits_75(
        tmp_path, monkeypatch):
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    cfg = TelemetryConfig(sentry=False, mfu=False)
    with pytest.raises(Preempted) as ei:
        fit(
            _TinyMlp(), optax.adam(1e-2), _loader(), epochs=2,
            job_id="NC", batch_size=16, log_dir=str(tmp_path),
            telemetry=cfg, profile=False, chaos="sigterm@3",
        )
    assert ei.value.code == 75
    # the checkpoint-less library caller keeps the trained state: fit's
    # would-be return value rides the exception
    assert ei.value.state is not None and int(ei.value.state.step) == 3
    assert len(ei.value.losses) == 3
    report = json.loads((tmp_path / "NC_report.json").read_text())
    assert report["exit_reason"] == "preempted"
    assert report["goodput"]["emergency_save_s"] == 0  # nothing to save to


def test_chaos_crash_runs_the_real_crash_path(tmp_path):
    from tpudist.resilience import ChaosCrash

    cfg = TelemetryConfig(sentry=False, mfu=False)
    with pytest.raises(ChaosCrash, match="step 3"):
        fit(
            _TinyMlp(), optax.adam(1e-2), _loader(), epochs=2,
            job_id="CC", batch_size=16, log_dir=str(tmp_path),
            telemetry=cfg, profile=False, chaos="crash@3",
        )
    report = json.loads((tmp_path / "CC_report.json").read_text())
    assert report["status"] == "crashed:ChaosCrash"
    assert report["exit_reason"] == "crashed:ChaosCrash"


def test_time_based_checkpoint_cadence(tmp_path):
    # checkpoint_every_s alone (no step cadence): every step takes longer
    # than the microscopic period, so every boundary saves — the
    # wall-clock knob works without the step knob
    state, losses = fit(
        _TinyMlp(), optax.adam(1e-2), _loader(), epochs=1,
        job_id="TS", batch_size=16, log_dir=str(tmp_path), profile=False,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=0,
        checkpoint_every_s=1e-6,
    )
    assert len(losses) == 4
    assert latest_step(tmp_path / "ckpt") == 4
    steps = sorted(
        int(d.name) for d in (tmp_path / "ckpt").iterdir()
        if d.is_dir() and d.name.isdigit()
    )
    # max_to_keep=3, saved at every boundary: the tail of 1..4 remains
    assert steps == [2, 3, 4]


def test_sigterm_during_stalled_input_pipeline_still_preempts_gracefully(
        tmp_path, monkeypatch):
    """The realistic worst case: the preemption notice lands while the
    loop is BLOCKED on a stalled data source. The prefetch wait polls the
    guard flag, ends the stream early, and fit takes the emergency-
    checkpoint path — instead of absorbing the signal and hanging until
    the scheduler's SIGKILL."""
    import os as _os
    import threading
    import time as _time

    monkeypatch.delenv(GENERATION_ENV, raising=False)

    stalled = threading.Event()

    class StallingLoader(DataLoader):
        """Yields 2 batches, then the source wedges (60 s ≫ the test)."""

        def __iter__(self):
            it = super().__iter__()
            for i, b in enumerate(it):
                if i == 2:
                    stalled.set()
                    _time.sleep(60)
                yield b

    def _kill_once_blocked():
        # deterministic: fire only after the stall began AND step 1's
        # cadence checkpoint is durable. The prefetch generator tops its
        # queue up BEFORE yielding the next staged batch, so once the
        # producer stalls the consumer is provably blocked inside the
        # prefetch wait (step 2 cannot have dispatched).
        stalled.wait(60)
        for _ in range(600):
            if (latest_step(tmp_path / "ckpt") or 0) >= 1:
                break
            _time.sleep(0.1)
        _os.kill(_os.getpid(), signal.SIGTERM)

    killer = threading.Thread(target=_kill_once_blocked, daemon=True)
    killer.start()
    t0 = _time.monotonic()
    with pytest.raises(Preempted) as ei:
        fit(
            _TinyMlp(), optax.adam(1e-2), StallingLoader(
                _loader().dataset, 16
            ), epochs=2, job_id="ST", batch_size=16,
            log_dir=str(tmp_path), profile=False,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1,
        )
    # exited within the poll cadence, not the 60 s stall
    assert _time.monotonic() - t0 < 40
    assert ei.value.code == 75
    # the one completed pre-stall step is persisted; nothing after (the
    # trip is checked before the next dispatch)
    assert int(ei.value.state.step) == 1
    assert latest_step(tmp_path / "ckpt") == 1


def test_preempt_false_keeps_default_signal_disposition(tmp_path):
    before = signal.getsignal(signal.SIGTERM)
    seen = []

    class SpyLoader(DataLoader):
        def __iter__(self):
            seen.append(signal.getsignal(signal.SIGTERM))
            return super().__iter__()

    rng = np.random.default_rng(0)
    data = {
        "image": rng.normal(size=(32, 13)).astype(np.float32),
        "label": (rng.random(32) * 10).astype(np.int32),
    }
    fit(
        _TinyMlp(), optax.adam(1e-2), SpyLoader(data, 16),
        epochs=1, job_id="NP", batch_size=16, log_dir=str(tmp_path),
        profile=False, preempt=False,
    )
    assert seen and all(h == before for h in seen)
    assert signal.getsignal(signal.SIGTERM) == before
