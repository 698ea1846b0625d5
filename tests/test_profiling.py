"""Windowed profiler schedule — the reference's torch.profiler schedule
(wait/warmup/active/repeat, /root/reference/main.py:70-78) re-expressed over
jax.profiler; these tests pin the window math with real trace captures."""

import jax
import jax.numpy as jnp

from tpudist.profiling import WindowedProfiler
from tpudist.telemetry.trace import TRAIN_STEP, span


def _trace_dirs(root):
    base = root / "plugins" / "profile"
    return sorted(base.iterdir()) if base.exists() else []


def _run(profiler, n_steps):
    x = jnp.arange(8.0)
    with profiler as p:
        for _ in range(n_steps):
            jax.block_until_ready(jnp.sum(x * x))
            p.step()


def test_single_window_captures_after_skip(tmp_path):
    p = WindowedProfiler("T", wait=1, warmup=1, active=2, repeat=1,
                         log_dir=tmp_path)
    _run(p, 8)
    dirs = _trace_dirs(tmp_path)
    assert len(dirs) == 1  # one capture window
    assert any(f.suffix == ".pb" for f in dirs[0].rglob("*"))


def test_disabled_writes_nothing(tmp_path):
    p = WindowedProfiler("T", enabled=False, log_dir=tmp_path)
    _run(p, 8)
    assert not _trace_dirs(tmp_path)
    assert not any(tmp_path.iterdir())  # not even the directory


def test_repeat_cycles_run_and_then_stop(tmp_path):
    p = WindowedProfiler("T", wait=1, warmup=0, active=2, repeat=2,
                         log_dir=tmp_path)
    _run(p, 10)
    # both cycles completed, no third window opened, traces were written
    # (sub-second cycles can land in one timestamped dir, so >= 1)
    assert p._cycle == 2 and not p._tracing
    assert len(_trace_dirs(tmp_path)) >= 1


def test_trace_contains_python_stacks_and_step_annotations(tmp_path):
    """with_stack parity (/root/reference/main.py:77): a captured window
    must carry host-side python-tracer events and the per-step TraceMe
    annotation, not just the device timeline."""
    p = WindowedProfiler("T", wait=0, warmup=0, active=4, repeat=1,
                         log_dir=tmp_path)
    x = jnp.arange(8.0)
    with p:
        for i in range(6):
            with span(TRAIN_STEP, step=i, marks_step=True):
                jax.block_until_ready(jnp.sum(x * x))
            p.step()
    blob = b"".join(
        f.read_bytes() for d in _trace_dirs(tmp_path) for f in d.rglob("*.pb")
    )
    assert b"python" in blob  # the python-tracer (with_stack) host plane
    assert b"tpudist_train" in blob  # StepTraceAnnotation events


def test_multi_cycle_schedule_with_nonzero_skip(tmp_path):
    """repeat=2 with wait+warmup > 0: each cycle re-runs the FULL
    wait→warmup→active schedule (torch schedule semantics: the skip phase
    repeats per cycle, it is not a one-time prefix). With skip=2/active=2
    the windows are steps [3,4] and [7,8]; both must complete and no third
    may open."""
    p = WindowedProfiler("T", wait=1, warmup=1, active=2, repeat=2,
                         log_dir=tmp_path)
    x = jnp.arange(8.0)
    tracing = []
    with p:
        for _ in range(10):
            jax.block_until_ready(jnp.sum(x * x))
            p.step()
            tracing.append(p._tracing)
    # open after the 2-step skip, closed 2 actives later — twice, then done
    assert tracing == [False, True, True, False, False, True, True, False,
                       False, False]
    assert p._cycle == 2 and not p._tracing
    assert len(_trace_dirs(tmp_path)) >= 1  # sub-second windows may share


def test_arm_opens_window_after_schedule_exhausted(tmp_path):
    """The flight-recorder path (tpudist.telemetry): an anomaly arms an
    on-demand window even after every scheduled repeat has run, the window
    closes itself after its step count, and the scheduled state machine is
    left exactly where it froze."""
    p = WindowedProfiler("T", wait=0, warmup=0, active=1, repeat=1,
                         log_dir=tmp_path)
    x = jnp.arange(8.0)
    with p:
        for _ in range(3):
            jax.block_until_ready(jnp.sum(x * x))
            p.step()
        assert p._cycle == 1 and not p._tracing  # schedule done
        assert p.arm(2) is True
        assert p._tracing
        jax.block_until_ready(jnp.sum(x * x))
        p.step()
        assert p._tracing  # 1 of 2 armed steps consumed
        jax.block_until_ready(jnp.sum(x * x))
        p.step()
        assert not p._tracing and p._armed == 0  # armed window self-closed
        assert p._cycle == 1  # scheduled counters untouched
    assert len(_trace_dirs(tmp_path)) >= 1


def test_arm_while_tracing_reports_true_without_extending(tmp_path):
    """An anomaly inside an already-recording window is already in a
    trace: arm() must not restart or extend anything, only report True."""
    p = WindowedProfiler("T", wait=0, warmup=0, active=4, repeat=1,
                         log_dir=tmp_path)
    x = jnp.arange(8.0)
    with p:
        jax.block_until_ready(jnp.sum(x * x))
        p.step()
        assert p._tracing
        assert p.arm(10) is True
        assert p._armed == 0  # scheduled window keeps owning the trace
        for _ in range(3):
            jax.block_until_ready(jnp.sum(x * x))
            p.step()
        assert not p._tracing  # closed by the SCHEDULE, not 10 steps later


def test_armed_window_flushed_on_exit_keeps_schedule_counters(tmp_path):
    """A run ending mid-anomaly-capture: __exit__ must flush the armed
    window through step()'s close path, not _stop() — the scheduled
    cycle/step counters stay where they froze instead of consuming a
    scheduled repeat that never ran."""
    p = WindowedProfiler("T", wait=0, warmup=0, active=1, repeat=1,
                         log_dir=tmp_path)
    x = jnp.arange(8.0)
    with p:
        for _ in range(2):
            jax.block_until_ready(jnp.sum(x * x))
            p.step()
        assert p._cycle == 1 and not p._tracing  # schedule done
        assert p.arm(6) is True
        jax.block_until_ready(jnp.sum(x * x))
        p.step()
        assert p._tracing and p._armed == 5  # window still open at exit
    assert not p._tracing and p._armed == 0
    assert p._cycle == 1 and p._step == 0  # scheduled counters untouched
    assert len(_trace_dirs(tmp_path)) >= 1


def test_arm_disabled_or_degenerate_reports_false(tmp_path):
    p = WindowedProfiler("T", enabled=False, log_dir=tmp_path)
    assert p.arm(4) is False
    enabled = WindowedProfiler("T", wait=5, warmup=0, active=1,
                               log_dir=tmp_path / "e")
    assert enabled.arm(0) is False  # a zero-step window records nothing
    assert not enabled._tracing
    assert not _trace_dirs(tmp_path)


def test_armed_window_does_not_disturb_pending_schedule(tmp_path):
    """Arming BEFORE the scheduled window has opened: the armed capture
    runs, and the scheduled window still opens at its own step count
    afterwards (the schedule counter freezes during the armed window)."""
    p = WindowedProfiler("T", wait=1, warmup=1, active=2, repeat=1,
                         log_dir=tmp_path)
    x = jnp.arange(8.0)
    with p:
        assert p.arm(1) is True
        jax.block_until_ready(jnp.sum(x * x))
        p.step()  # consumes the armed window; _step still 0
        assert not p._tracing and p._step == 0
        tracing = []
        for _ in range(4):
            jax.block_until_ready(jnp.sum(x * x))
            p.step()
            tracing.append(p._tracing)
        assert tracing == [False, True, True, False]  # skip=2, active=2
        assert p._cycle == 1
    assert len(_trace_dirs(tmp_path)) >= 1


def test_short_run_flushes_open_window_on_exit(tmp_path):
    """A run that ends mid-window still writes its trace (the reference's
    profiler context flushes on __exit__ the same way)."""
    p = WindowedProfiler("T", wait=1, warmup=1, active=50, repeat=1,
                         log_dir=tmp_path)
    _run(p, 5)  # window opens at step 2, run ends at 5 < 2+50
    assert len(_trace_dirs(tmp_path)) == 1
