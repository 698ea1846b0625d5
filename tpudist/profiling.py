"""Windowed profiler tracing.

Replaces ``torch.profiler.profile(schedule=schedule(wait=2, warmup=2,
active=6, repeat=1), tensorboard_trace_handler('./log_{jobId}'))``
(/root/reference/main.py:70-78,115) with :mod:`jax.profiler`: after
``wait + warmup`` steps are skipped, a single ``active``-step window is
captured via ``start_trace``/``stop_trace`` into ``./log_{jobId}`` — the
same per-job directory convention — producing TensorBoard/XProf-viewable
traces with the TPU device timeline and HLO ops (Kineto's CUPTI role is
played by the XLA runtime's own instrumentation; SURVEY.md §2.10).

Usage mirrors the reference: wrap training in the context manager and call
``p.step()`` once per iteration.

``with_stack=True`` (the default, matching the reference's
``with_stack=True`` at /root/reference/main.py:77) turns on the profiler's
python tracer, so captured windows carry host-side python call stacks
alongside the device timeline — the Kineto python-stack capability,
natively. ``fit`` brackets each step's dispatch in a ``StepTraceAnnotation``
(:data:`tpudist.telemetry.trace.TRAIN_STEP`, through the one span helper)
so XProf's step-time view can attribute device work to training steps.
"""

from __future__ import annotations

import logging
import threading
from pathlib import Path

import jax

logger = logging.getLogger(__name__)


class WindowedProfiler:
    def __init__(
        self,
        job_id: str,
        *,
        wait: int = 2,
        warmup: int = 2,
        active: int = 6,
        repeat: int = 1,
        log_dir: str | Path | None = None,
        enabled: bool = True,
        with_stack: bool = True,
    ):
        # torch semantics: skip `wait`, then `warmup` (instrument, discard),
        # then record `active` steps; `repeat` cycles. jax.profiler has no
        # warmup/active distinction, so the capture window is `active` steps
        # beginning after wait+warmup.
        self.skip = wait + warmup
        self.active = active
        self.repeat = repeat
        self.log_dir = str(log_dir if log_dir is not None else f"./log_{job_id}")
        self.enabled = enabled
        self.with_stack = with_stack
        self._step = 0
        self._cycle = 0
        self._tracing = False
        self._armed = 0  # remaining steps of an on-demand (arm()) window
        # serializes the state machine against flush_armed(), which the
        # hang watchdog calls from ITS thread: without it, a stall that
        # resolves mid-flush lets the resumed main thread's step() race
        # the teardown into a second stop_trace (which raises)
        self._mutex = threading.Lock()

    def __enter__(self):
        # wait+warmup == 0 means "capture from the first step" — the window
        # must open before any step() call
        if self.enabled and self.repeat > 0 and self.skip == 0:
            self._start()
        return self

    def _start(self) -> None:
        Path(self.log_dir).mkdir(parents=True, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        if self.with_stack:
            options.python_tracer_level = 1
            options.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._tracing = True

    def arm(self, active_steps: int) -> bool:
        """Open an on-demand capture window NOW for the next
        ``active_steps`` iterations — the telemetry flight recorder's
        anomaly capture (tpudist.telemetry), independent of the
        wait/warmup/active schedule and usable even after every scheduled
        ``repeat`` cycle has run. While a window (scheduled or armed) is
        already recording, the anomaly is already in a trace: the call
        extends nothing and reports True. Returns False when disabled —
        the caller logs ``profiler_armed: false`` rather than losing the
        anomaly event itself."""
        if not self.enabled or active_steps <= 0:
            return False
        with self._mutex:
            # same mutex as step()/flush_armed(): an arm racing the
            # watchdog thread's flush must either land before the close
            # (and be flushed with it) or open a fresh window after it —
            # never overlap a start with an in-flight stop, and never
            # report "already tracing" about a window being torn down
            if self._tracing:
                return True
            self._armed = active_steps
            self._start()
            return True

    def step(self) -> None:
        """Advance the schedule; call once per training iteration
        (the ``p.step()`` of /root/reference/main.py:115)."""
        with self._mutex:
            if self._armed:
                # an armed window counts its own steps and leaves the
                # scheduled state machine (cycle/step counters) exactly
                # where it froze
                self._armed -= 1
                if self._armed <= 0 and self._tracing:
                    self._close_armed()
                return
            if not self.enabled or self._cycle >= self.repeat:
                return
            self._step += 1
            if self._tracing and self._step >= self.skip + self.active:
                self._stop()
                if self._cycle < self.repeat and self.skip == 0:
                    self._start()
            elif not self._tracing and self._step == self.skip:
                self._start()

    def flush_armed(self) -> None:
        """Close a currently-armed on-demand window NOW, flushing its
        trace to disk — the hang watchdog's crash path
        (tpudist.telemetry.health): a hung job's armed anomaly window
        would otherwise die unwritten with the process. Scheduled windows
        are left alone (their cycle accounting belongs to the main
        thread); no-op when nothing is armed. Safe from the watchdog
        thread: the mutex makes the close atomic against a resumed main
        thread's step()."""
        with self._mutex:
            if self._tracing and self._armed:
                self._close_armed()

    def _close_armed(self) -> None:
        # the armed-window teardown, shared by step()'s countdown and
        # __exit__'s flush: closes the trace WITHOUT touching the scheduled
        # cycle/step counters (contrast _stop)
        self._armed = 0
        jax.profiler.stop_trace()
        self._tracing = False
        logger.info("anomaly-armed trace written to %s", self.log_dir)

    def _stop(self) -> None:
        # block_until_ready is implicit: stop_trace flushes what the runtime
        # has; callers log loss each step so device work is already synced.
        jax.profiler.stop_trace()
        self._tracing = False
        self._cycle += 1
        self._step = 0
        logger.info("profiler trace written to %s", self.log_dir)

    def __exit__(self, *exc):
        with self._mutex:
            if self._tracing:
                if self._armed:
                    # a run ending mid-anomaly-capture must not consume a
                    # scheduled repeat that never ran
                    self._close_armed()
                else:
                    self._stop()
