"""fit()-level run-health integration: fleet aggregation + the divergence
probe riding a real training loop, the end-of-run report on the normal and
crash paths, the simulated-hang watchdog with crash forensics, and — the
acceptance contract — health features OFF leaving the JSONL stream's row
kinds exactly as before (heartbeats gain identity fields, existing fields
byte-identical)."""

import json
import pathlib
import time

import numpy as np
import optax
import pytest

from tpudist.data.loader import DataLoader
from tpudist.models.gpt2 import GPT2
from tpudist.telemetry import TelemetryConfig
from tpudist.train import fit, lm_loss

VOCAB = 256


def _tiny_lm():
    return GPT2(vocab_size=VOCAB, max_seq_len=16, hidden_dim=32, depth=1,
                num_heads=2)


def _loader(n: int = 64, batch: int = 16):
    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, VOCAB - 2, (n, 16)).astype(np.int32)
    return DataLoader({"tokens": tokens}, batch)


def _fit(loader, tmp_path, job_id, cfg, epochs=3):
    return fit(
        _tiny_lm(), optax.adam(1e-3), loader, epochs=epochs, job_id=job_id,
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", log_dir=str(tmp_path), telemetry=cfg,
        profile=False,
    )


def _rows(path):
    return [json.loads(l) for l in pathlib.Path(path).read_text().splitlines()]


def test_fit_health_stream_and_report(tmp_path):
    cfg = TelemetryConfig(aggregate_every=3, divergence_every=3,
                          heartbeat_every=4, peak_flops=197e12)
    state, losses = _fit(_loader(), tmp_path, "HS", cfg)
    assert len(losses) == 12

    rows = _rows(tmp_path / "HS_telemetry_0.jsonl")
    fleet = [r for r in rows if r["kind"] == "fleet"]
    # aggregation cadence 3 over 12 steps; each gather resolves one
    # cadence later, the last at finish()'s flush
    assert [r["step"] for r in fleet] == [3, 6, 9, 12]
    for r in fleet:
        assert r["per_rank_step"].keys() == {"0"}
        assert r["per_rank_interval_s"]["0"] > 0
    # healthy run: the detectors stay silent
    assert not any(r["kind"] in ("straggler", "divergence", "watchdog")
                   for r in rows)

    report = json.loads((tmp_path / "HS_report.json").read_text())
    assert report["status"] == "completed"
    assert report["steps_observed"] == 12
    assert report["step_time_s"]["p50"] > 0
    assert report["step_time_s"]["n"] == 12
    assert report["skipped_steps"] == 0
    # the probe ran (cadence 3, delayed resolve) and found nothing
    assert report["divergence_checks"] >= 3
    assert report["divergence_events"] == []
    assert report["straggler_events"] == []
    assert report["watchdog"] is None
    assert report["per_rank_last_seen"] == {"0": 12}
    assert report["telemetry_segments"] == [
        str(tmp_path / "HS_telemetry_0.jsonl")
    ]
    assert report["mfu"] is not None and report["mfu"]["p50"] > 0


class SleepyLoader:
    """Stalls once at a chosen (epoch, batch) — the simulated hang. The
    stall sits on the SECOND epoch so bring-up's compile (which runs
    before the watchdog's first beat, by design) cannot absorb it."""

    def __init__(self, inner, stall_epoch=1, stall_at=1, stall_s=1.5):
        self.inner = inner
        self.batch_size = inner.batch_size
        self.stall_epoch, self.stall_at, self.stall_s = (
            stall_epoch, stall_at, stall_s
        )
        self._epoch = -1

    def __len__(self):
        return len(self.inner)

    def probe(self):
        # fit's shape probe must not consume a training pass of the epoch
        # counter — the stall has to land on a REAL second epoch, after
        # compile (which legitimately runs before the first beat)
        return next(iter(self.inner))

    def __iter__(self):
        self._epoch += 1
        for i, b in enumerate(self.inner):
            if self._epoch == self.stall_epoch and i == self.stall_at:
                time.sleep(self.stall_s)
            yield b


def test_fit_hang_watchdog_writes_crash_forensics(tmp_path):
    """A mid-run stall longer than the deadline trips the watchdog: a
    `watchdog` row lands in the stream, the per-rank crash report holds
    every thread's stack and the last-seen steps, the end-of-run report
    records the trip — and the run (a stall, not a death) still
    completes."""
    # stall at batch 3 of the second epoch: by then step 5's cadence rows
    # have RESOLVED (the prefetch generator tops its queue up before
    # yielding, so a stall at batch k blocks the loop ~2 batches early),
    # making the crash report's last_rows capture non-trivial — the tail
    # is read BEFORE the watchdog row is written, by crash-path design
    # the deadline leaves a healthy step room: under six busy workers a
    # tiny step has taken over 0.4 s of wall clock, tripped the one-shot
    # watchdog early and left the forensics of the wrong moment
    loader = SleepyLoader(_loader(), stall_epoch=1, stall_at=3, stall_s=4.0)
    cfg = TelemetryConfig(hang_timeout_s=1.5, sentry=False, mfu=False)
    state, losses = _fit(loader, tmp_path, "HG", cfg, epochs=2)
    assert len(losses) == 8  # the stall resolved; training finished

    crash = json.loads((tmp_path / "HG_crash_0.json").read_text())
    assert crash["job"] == "HG" and crash["rank"] == 0
    assert crash["trip"]["age_s"] > 1.5
    assert crash["trip"]["last_step"] >= 1
    assert any("MainThread" in k for k in crash["thread_stacks"])
    assert all(isinstance(v, list) and v
               for v in crash["thread_stacks"].values())
    # resolve-side last-seen trails the dispatch-side beat by the one
    # in-flight step of the delayed metrics pipeline
    last = crash["trip"]["last_step"]
    assert crash["per_rank_last_seen"]["0"] in (last, last - 1)
    assert isinstance(crash["last_rows"], list) and crash["last_rows"]

    rows = _rows(tmp_path / "HG_telemetry_0.jsonl")
    wd = [r for r in rows if r["kind"] == "watchdog"]
    assert len(wd) == 1  # one-shot
    assert wd[0]["age_s"] > 1.5 and wd[0]["timeout_s"] == 1.5

    report = json.loads((tmp_path / "HG_report.json").read_text())
    # the watchdog wrote a report at trip time; finish() overwrote it with
    # the final status, KEEPING the trip on record
    assert report["status"] == "completed"
    assert report["watchdog"] is not None
    assert report["watchdog"]["timeout_s"] == 1.5


def test_fit_crash_path_writes_report(tmp_path):
    """An exception mid-training still produces the report, stamped with
    the crash status — the 'why did it die' answer for non-hang deaths."""

    class PoisonLoader:
        def __init__(self, inner, explode_at=5):
            self.inner, self.explode_at = inner, explode_at
            self.batch_size = inner.batch_size
            self._n = 0

        def __len__(self):
            return len(self.inner)

        def __iter__(self):
            for b in self.inner:
                self._n += 1
                if self._n > self.explode_at:
                    raise RuntimeError("loader died")
                yield b

    cfg = TelemetryConfig(aggregate_every=2, sentry=False, mfu=False)
    with pytest.raises(RuntimeError, match="loader died"):
        _fit(PoisonLoader(_loader()), tmp_path, "CR", cfg, epochs=3)
    report = json.loads((tmp_path / "CR_report.json").read_text())
    assert report["status"] == "crashed:RuntimeError"
    assert report["steps_observed"] >= 1
    assert report["step_time_s"]["p50"] > 0


def test_fit_health_off_keeps_stream_kinds_and_extends_heartbeat(tmp_path):
    """Default TelemetryConfig (health detectors off): no fleet /
    straggler / divergence / watchdog rows — the pre-PR kind set (and the one
    ``bringup`` row every telemetry run writes) —
    while heartbeat rows carry the new identity fields APPENDED after the
    byte-identical existing ones, and the run report exists as a separate
    file (never a stream row)."""
    cfg = TelemetryConfig(heartbeat_every=4)
    _fit(_loader(), tmp_path, "OFF", cfg)
    rows = _rows(tmp_path / "OFF_telemetry_0.jsonl")
    kinds = {r["kind"] for r in rows}
    assert kinds <= {"run_meta", "health", "mfu", "step_breakdown",
                     "throughput", "memory", "anomaly", "heartbeat",
                     "train_time", "run_summary", "comm", "warning",
                     "bringup"}
    beats = [r for r in rows if r["kind"] == "heartbeat"]
    assert [r["step"] for r in beats] == [4, 8, 12]
    for r in beats:
        # existing fields, existing order, then the identity triple
        assert list(r)[:7] == ["v", "t", "kind", "rank", "step", "epoch",
                               "interval_s"]
        assert r["process_index"] == 0
        assert isinstance(r["host"], str) and r["host"]
        assert r["mono"] > 0
    # report file exists; the stream has no 'report' row
    assert (tmp_path / "OFF_report.json").exists()
    assert not any(r["kind"] == "report" for r in rows)


def test_fit_health_report_disabled(tmp_path):
    cfg = TelemetryConfig(run_report=False)
    _fit(_loader(), tmp_path, "NR", cfg, epochs=1)
    assert not (tmp_path / "NR_report.json").exists()


def test_fit_jsonl_rotation_via_config(tmp_path):
    """jsonl_max_bytes wires through fit: the stream rotates into numbered
    segments and the report's segment list reassembles it."""
    cfg = TelemetryConfig(jsonl_max_bytes=500, sentry=False,
                          heartbeat_every=1)
    _fit(_loader(), tmp_path, "RT", cfg)
    segs = sorted(tmp_path.glob("RT_telemetry_0.jsonl.*"))
    assert segs  # small cap: at least one sealed segment
    report = json.loads((tmp_path / "RT_report.json").read_text())
    assert len(report["telemetry_segments"]) == len(segs) + 1
    assert report["telemetry_segments"][-1] == str(
        tmp_path / "RT_telemetry_0.jsonl"
    )
    # every segment line is still strict JSON
    for p in report["telemetry_segments"]:
        for line in pathlib.Path(p).read_text().splitlines():
            json.loads(line)
