"""Training-health telemetry: in-step metrics, NaN flight recorder,
step-time breakdown, MFU accounting, and a structured JSONL sink.

The reference's observability surface is a rank-0 TSV of loss and
examples/sec plus one profiler window (``tpudist/metrics.py``,
``tpudist/profiling.py`` — reproduced exactly and untouched). That answers
"how fast"; this subsystem answers the three questions a production run
dies without (docs/OBSERVABILITY.md):

- **is training healthy?** — global grad-norm, param-norm, update-norm and
  non-finite counts computed INSIDE the jit-compiled SPMD step
  (``make_train_step(telemetry=True)``): a handful of reductions XLA fuses
  into the existing gradient psum path, fetched through the same
  one-step-delayed async pipeline as the loss — zero extra host syncs.
  Its share of a step is not measured on the chip (the benchmark's traced
  runs turn it off).
- **why did it die?** — :class:`NanSentry`, the flight recorder: the
  in-graph guard (``make_train_step(guard_nonfinite=True)``) skips the
  poisoned update the step it happens (params/opt-state/BN stats keep
  their pre-step values, the step counter still advances so data position
  stays exact); the host sentry then emits a structured ``anomaly`` event
  and arms :class:`~tpudist.profiling.WindowedProfiler` for an on-demand
  trace window around the anomaly. Rolling-window loss-spike detection
  catches divergence that never reaches NaN.
- **where does the time go?** — per-step data-wait / dispatch /
  device-compute attribution in ``fit()`` plus per-process heartbeat rows,
  so a slow input pipeline, a dispatch-bound host, and a multi-host
  straggler all look different in the log. MFU rows combine the analytic
  counters (:mod:`tpudist.telemetry.flops`) with measured step time.

Everything lands in a per-process JSONL stream (:class:`TelemetrySink`)
NEXT TO the reference TSV, which stays byte-identical when telemetry is
off. Enable with ``fit(..., telemetry=True)`` or pass a
:class:`TelemetryConfig` to tune knobs.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import numbers
import threading
import time
from pathlib import Path
from typing import Any, Mapping

from tpudist.telemetry import flops
from tpudist.telemetry.trace import span

__all__ = [
    "TelemetryConfig",
    "TelemetrySink",
    "NanSentry",
    "TimedIterator",
    "Telemetry",
    "build_telemetry",
    "flops",
]

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Knobs for the telemetry subsystem; the defaults are what
    ``fit(..., telemetry=True)`` runs.

    ``health_metrics``/``guard_nonfinite`` steer the compiled step (norms
    in-graph; skip poisoned updates). ``sentry`` drives the host-side
    flight recorder: non-finite loss/grads fire an event; a loss
    counts as a spike when it exceeds the rolling window's mean by
    ``spike_sigma`` standard deviations (window of ``spike_window`` recent
    finite losses, armed only after ``spike_min_steps`` observations);
    ``cooldown_steps`` suppresses event storms after a detection.
    ``capture_steps`` sizes the on-demand profiler window an anomaly arms.
    ``peak_flops`` is PER-CHIP peak (``None`` → the running chip's row of
    ``flops.DEVICE_PEAKS``; on the CPU there is none and the MFU field is
    null). ``heartbeat_every`` is in steps
    (``None`` → 10× the TSV log cadence; ``0`` → no heartbeat rows, the
    same off-switch contract as ``fit``'s ``memory_log_every``).
    ``jsonl_dir`` overrides where the sink writes (``None`` → fit's
    ``log_dir``).

    The run-health fields (:mod:`tpudist.telemetry.health`) default OFF so
    the JSONL/TSV streams stay byte-identical unless asked for:
    ``aggregate_every`` (steps between cross-process folds; 0 = off) with
    ``straggler_ratio``/``straggler_patience`` tuning the one-shot
    straggler rule; ``divergence_every`` (steps between replica-checksum
    probes; 0 = off); ``hang_timeout_s`` (step deadline for the watchdog;
    ``None`` = off). ``run_report`` (on) writes ``{job}_report.json`` at
    run end / crash — a separate file, never a stream row.
    ``jsonl_max_bytes`` caps each JSONL segment before rotation
    (``None`` = one unbounded file, the pre-rotation contract);
    :func:`tpudist.telemetry.health.health_config` is the one-call
    production preset (``main.py --health``).

    ``hang_action`` escalates the watchdog: ``"report"`` (default, the
    pre-resilience behavior) writes the forensics and lets a resolving
    stall finish the run; ``"exit"`` additionally terminates the process
    with :data:`tpudist.resilience.EXIT_HANG` (76) AFTER the crash
    file/report/row are on disk — the restartable code
    ``tpudist.launch``'s supervisor relaunches from the last checkpoint,
    closing the detection → forensics → recovery loop.
    """

    health_metrics: bool = True
    guard_nonfinite: bool = True
    sentry: bool = True
    spike_window: int = 32
    spike_sigma: float = 8.0
    spike_min_steps: int = 16
    cooldown_steps: int = 16
    capture_on_anomaly: bool = True
    capture_steps: int = 6
    breakdown: bool = True
    mfu: bool = True
    peak_flops: float | None = None
    heartbeat_every: int | None = None
    jsonl_dir: str | None = None
    # run-health layer (tpudist.telemetry.health) — off by default
    aggregate_every: int = 0
    straggler_ratio: float = 1.5
    straggler_patience: int = 3
    divergence_every: int = 0
    hang_timeout_s: float | None = None
    hang_action: str = "report"
    run_report: bool = True
    jsonl_max_bytes: int | None = None
    # span layer (tpudist.telemetry.trace) — off by default; on, fit()
    # re-emits the step breakdown, checkpoint saves, health probes, and
    # repair/reshard events as `span` rows on the same sink
    trace: bool = False
    # program-anatomy layer (tpudist.telemetry.anatomy) — off by default.
    # `anatomy` makes fit() introspect the compiled step at bring-up (one
    # `anatomy` row; a stale-counter `warning` when the analytic FLOPs
    # counter drifts from XLA's count beyond `anatomy_tolerance`).
    # `regression_detect` arms the in-run step-time sentinel: rolling
    # median over `regression_window` intervals vs the post-compile
    # baseline, one-shot `perf_regression` row past `regression_threshold`
    anatomy: bool = False
    anatomy_tolerance: float = 0.1
    regression_detect: bool = False
    regression_threshold: float = 0.25
    regression_window: int = 16

    def step_kwargs(self) -> dict:
        """The ``make_train_step`` knobs this config implies — the ONE
        mapping from config fields to compiled-step behavior (``fit()``
        passes these through verbatim)."""
        return {
            "telemetry": self.health_metrics,
            "guard_nonfinite": self.guard_nonfinite,
        }


def _json_safe(v):
    """JSONL rows must stay strict-JSON parseable: non-finite floats become
    null (a ``NaN`` literal breaks downstream ``json.loads``), numpy
    scalars become python numbers, containers (the run-health fleet row's
    per-rank maps) recurse element-wise."""
    if isinstance(v, Mapping):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    if isinstance(v, bool) or v is None or isinstance(v, (str, int)):
        return v
    try:
        f = float(v)
    except (TypeError, ValueError):
        return str(v)
    if not math.isfinite(f):
        return None
    # numpy integer scalars are not python ints (the early return above)
    # but ARE Integral — keep counts like nonfinite_grad_count integers in
    # the JSONL, not 5.0
    return int(f) if isinstance(v, numbers.Integral) else f


class TelemetrySink:
    """Append-only structured JSONL writer — one file per process
    (``{job_id}_telemetry_{rank}.jsonl``), one object per line:
    ``{"v": 1, "t": <unix seconds>, "kind": ..., "rank": ..., "step": ...,
    <kind-specific fields>}``. Kinds written by ``fit()``: ``health``,
    ``step_breakdown``, ``mfu``, ``throughput``, ``memory``, ``anomaly``,
    ``heartbeat``, ``train_time``, ``run_meta``, ``comm`` (explicit
    gradient reduction's one-time wire accounting), ``fusion`` (one-time
    step-fusion config: which fusions — the Pallas fused LN, the one-pass
    optimizer — the compiled step engaged, and the compute-copy dtype),
    ``warning`` (tagged one-shot diagnoses, e.g. ``h2d_link_bound``,
    ``checkpoint_fallback``), ``reshard`` (one-time elastic-resume record:
    cross-world-size ZeRO-1 relayout, residual flush, cursor remap),
    ``compile_cache`` (one-time AOT executable-cache outcome:
    hit/miss/bytes/load_s), ``repair`` (one record per executed repair
    action — cause, rollback step, skipped window, action taken:
    ``tpudist.resilience.repair``), ``anatomy`` (one-shot per-program
    compiler introspection: XLA-counted FLOPs/bytes and the static HBM
    breakdown, cross-checked against the analytic counters —
    ``tpudist.telemetry.anatomy``), ``perf_regression`` (the in-run
    step-time sentinel's one-shot verdict). The serving engine
    (``tpudist.serve``) writes ``serve``/``serve_summary`` SLO rows
    through the same sink — TTFT/TPOT percentiles, slot utilization,
    and in paged mode the block-pool triple (``pool_occupancy``,
    ``prefix_hit_rate``, ``preemptions``). Schema glossary in docs/OBSERVABILITY.md. Rows flush per write, and the file opens in
    APPEND mode — both halves of the flight-recorder contract: the anomaly
    row must survive the crash it describes, including a checkpoint-resume
    of the same job_id truncating the evidence before anyone read it.
    Attempts are separable by the ``t`` timestamps.

    ``max_bytes`` caps the ACTIVE file's size: when the next row would
    exceed it, the file rotates to the next numbered segment
    (``X.jsonl`` → ``X.jsonl.1``, ``.2``, …; the base path is always the
    live tail) so a multi-day run never grows one unbounded file.
    :meth:`segments` lists the segment chain oldest→active (the run
    report records it); ``None`` (default) keeps the single-file
    contract byte-identical. Writes are serialized by a lock (the hang
    watchdog writes its ``watchdog`` row from the monitor thread while
    the main thread may be mid-row), and the last 256 rows are kept in a
    host ring buffer (:meth:`tail`) — the crash report's "what was the
    run doing" evidence, readable even when the filesystem is the thing
    that hung."""

    TAIL_ROWS = 256

    def __init__(self, path: str | Path, *, rank: int = 0, clock=time.time,
                 max_bytes: int | None = None, run_id: str | None = None):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.rank = rank
        self._clock = clock
        self.max_bytes = max_bytes
        # the job's stable run id: explicit > launcher env (TPUDIST_RUN_ID)
        # > absent. When set, every row gains a `run_id` field APPENDED
        # after its existing fields (the heartbeat append-only discipline)
        # so offline stitching (tools/tracelens.py) can group the segments
        # of one logical job — including relaunched generations, which
        # inherit the id via the supervisor env — without filename
        # heuristics. A bare sink with no launcher stays byte-identical.
        if run_id is None:
            from tpudist.resilience.exitcodes import run_id as _env_run_id

            run_id = _env_run_id()
        self.run_id = run_id
        self._lock = threading.Lock()
        self._tail: collections.deque = collections.deque(
            maxlen=self.TAIL_ROWS
        )
        self._size = self.path.stat().st_size if self.path.exists() else 0
        # monotonic: max existing + 1, never the first free gap — an
        # operator deleting old segments mid-run must not make the NEWEST
        # data inherit the OLDEST position in the chain
        self._next_segment = 1 + max(
            (n for _, n in self._numbered_segments()), default=0
        )
        self._file = open(self.path, "a", encoding="utf-8")

    def write(self, kind: str, step: int | None = None, **fields) -> dict:
        row: dict[str, Any] = {
            "v": SCHEMA_VERSION,
            "t": round(float(self._clock()), 6),
            "kind": kind,
            "rank": self.rank,
        }
        if step is not None:
            row["step"] = int(step)
        row.update({k: _json_safe(v) for k, v in fields.items()})
        if self.run_id is not None:
            row["run_id"] = self.run_id
        line = json.dumps(row) + "\n"
        # the cap is in BYTES on disk: a non-ASCII hostname or event
        # string is longer in UTF-8 than in characters, and len(line)
        # would under-count every such row until the segment overshoots
        nbytes = len(line.encode("utf-8"))
        with self._lock:
            if (self.max_bytes and self._size
                    and self._size + nbytes > self.max_bytes):
                self._rotate()
            self._file.write(line)
            self._file.flush()
            self._size += nbytes
            self._tail.append(row)
        return row

    def _numbered_segments(self) -> list[tuple[Path, int]]:
        out = []
        for p in self.path.parent.glob(f"{self.path.name}.*"):
            try:
                out.append((p, int(p.name[len(self.path.name) + 1:])))
            except ValueError:
                continue  # foreign suffix, not a segment
        return sorted(out, key=lambda t: t[1])

    def _rotate(self) -> None:
        # called under the lock; the active file is full — seal it as the
        # next numbered segment and start a fresh active file. Renaming
        # the SEALED file (not the active one) keeps the base path stable
        # for tailing dashboards across rotations.
        self._file.close()
        self.path.rename(
            self.path.with_name(f"{self.path.name}.{self._next_segment}")
        )
        self._next_segment += 1
        self._file = open(self.path, "a", encoding="utf-8")
        self._size = 0

    def segments(self) -> list[Path]:
        """Existing segment files oldest→newest (numeric order, tolerant
        of cleanup gaps), the active file last — what the run report
        records so a reader can reassemble the full stream after
        rotation."""
        sealed = [p for p, _ in self._numbered_segments()]
        return sealed + ([self.path] if self.path.exists() else [])

    def tail(self, n: int = TAIL_ROWS, *,
             lock_timeout: float | None = None) -> list[dict]:
        """The most recent rows (host ring buffer) — crash forensics.

        ``lock_timeout`` bounds the wait for the write lock: the hang
        watchdog reads the tail while the main thread may be wedged
        INSIDE ``write`` (a hung filesystem) holding the lock forever.
        On timeout the deque is read lockless — appends are atomic, and
        the rare concurrent-mutation ``RuntimeError`` degrades to an
        empty tail rather than a deadlocked crash handler."""
        acquired = self._lock.acquire(
            timeout=-1 if lock_timeout is None else lock_timeout
        )
        try:
            try:
                rows = list(self._tail)
            except RuntimeError:
                rows = []
        finally:
            if acquired:
                self._lock.release()
        return rows[-n:]

    def close(self) -> None:
        if not self._file.closed:
            self._file.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NanSentry:
    """Host-side anomaly detector over the per-step loss stream.

    :meth:`observe` returns an event dict (``event``: ``"nonfinite"`` or
    ``"loss_spike"``) or ``None``. Non-finite loss, a non-zero in-step
    non-finite-gradient count, or an in-graph guard skip
    (``update_skipped``) fires ``nonfinite``. Spikes fire when a
    finite loss exceeds the rolling window's ``mean + sigma·std`` (and the
    window has seen ``min_steps`` losses) — the "diverging but not yet
    NaN" signal. Anomalous losses are NOT pushed into the window (one
    spike must not drag the baseline up), and ``cooldown`` steps of
    silence follow each event — for BOTH kinds — so a NaN'd-out or
    diverging run emits a handful of rows, not one per step (the in-graph
    skip counter still sees every poisoned step).
    """

    def __init__(self, *, window: int = 32, sigma: float = 8.0,
                 min_steps: int = 16, cooldown: int = 16):
        self.sigma = sigma
        self.min_steps = max(min_steps, 2)
        self.cooldown = cooldown
        self._window: collections.deque[float] = collections.deque(maxlen=window)
        self._quiet_until = -1
        self.events: list[dict] = []

    def observe(self, step: int, loss: float, *, nonfinite_count: int = 0,
                update_skipped: int = 0) -> dict | None:
        event = None
        if (not math.isfinite(loss) or nonfinite_count > 0
                or update_skipped > 0):
            # update_skipped is its own trigger: with health_metrics=False
            # the compiled step reports no nonfinite_grad_count, and a
            # bf16 backward can overflow gradients under a finite loss —
            # the in-graph guard's skip is then the only signal
            event = {
                "event": "nonfinite",
                "loss": loss,
                "nonfinite_grad_count": int(nonfinite_count),
                "update_skipped": int(update_skipped),
            }
        elif len(self._window) >= self.min_steps:
            mean = sum(self._window) / len(self._window)
            var = sum((x - mean) ** 2 for x in self._window) / len(self._window)
            std = math.sqrt(var)
            # floor the spread: a zero-variance plateau (converged run,
            # bf16-quantized loss) must not turn one-ulp jitter into a
            # recurring spike event — anything within 1e-6 relative of the
            # mean is noise, not divergence
            spread = max(std, 1e-6 * abs(mean), 1e-12)
            threshold = mean + self.sigma * spread
            if loss > threshold:
                event = {
                    "event": "loss_spike",
                    "loss": loss,
                    "window_mean": mean,
                    "window_std": std,
                    "threshold": threshold,
                    "update_skipped": int(update_skipped),
                }
        if event is not None:
            # anomalous either way — the loss must stay OUT of the baseline
            # window even when cooldown suppresses the event row, or a
            # still-elevated post-spike run drags the mean up and silences
            # every later detection
            if step < self._quiet_until:
                return None  # cooldown: a NaN'd-out/diverging run emits a
                # handful of rows, not one per step — the skipped-update
                # counter still accumulates in-graph, so nothing is lost,
                # only deduplicated
            event["step"] = int(step)
            self._quiet_until = step + self.cooldown
            self.events.append(event)
            return event
        if math.isfinite(loss):
            self._window.append(loss)
        return None

    def reset(self) -> None:
        """Forget the baseline window and cooldown — the repair loop's
        rollback rewound the trajectory, so losses observed on the
        discarded (possibly poisoned) span must not seed the spike
        baseline of the repaired one, and a live cooldown must not
        silence a fresh post-repair incident. Event history is kept (it
        is the report's evidence)."""
        self._window.clear()
        self._quiet_until = -1


class TimedIterator:
    """Wrap a batch iterator and record the wall seconds the consumer spent
    blocked in ``next()`` — fit()'s data-wait attribution. With the
    prefetch queue healthy this is ~0; when it grows toward the step time
    the run is input-bound, visible per step (the benchmark reads it as
    ``input_wait_ms``)."""

    def __init__(self, iterator, *, step: int = 0, tracer=None):
        self._it = iter(iterator)
        self.last_wait_s = 0.0
        self._step = step  # the last step dispatched: a batch feeds the next
        self._tracer = tracer

    def __iter__(self):
        return self

    def __next__(self):
        self._step += 1
        t0 = time.perf_counter()
        try:
            # parent of prefetch_to_mesh's input/wait and input/stage; the
            # StopIteration that ends the epoch tags the event ``end``
            with span("fit/next_batch", step=self._step,
                      tracer=self._tracer):
                return next(self._it)
        finally:
            self.last_wait_s = time.perf_counter() - t0


class Telemetry:
    """The host half of the subsystem — owns the sink and sentry, driven by
    ``fit()`` once per resolved step (one step after dispatch, on the same
    delayed pipeline as the TSV rows). Scalar rows (``health``,
    ``step_breakdown``, ``mfu``) are written by rank 0 at the TSV's
    ``log_every`` cadence; ``heartbeat`` rows are written by EVERY process
    (that is their point: a straggler host is visible by comparing its
    heartbeat wall-clock drift against its peers'); ``anomaly`` rows are
    written by whichever rank observed the anomaly, every time."""

    def __init__(self, config: TelemetryConfig, sink: TelemetrySink, *,
                 model=None, input_key: str = "tokens", profiler=None,
                 rank: int = 0, world_size: int = 1, log_every: int = 5,
                 n_chips: int = 1):
        self.config = config
        self.sink = sink
        self.profiler = profiler
        self.rank = rank
        self.world_size = world_size
        self.log_every = max(int(log_every), 1)
        self.n_chips = max(int(n_chips), 1)
        import jax as _jax

        device = _jax.devices()[0]
        self.device_kind = device.device_kind
        # the CPU has no published peak to be a fraction of: the MFU field
        # stays null there, never another chip's share
        self.peak_flops = config.peak_flops or (
            None if device.platform == "cpu"
            else flops.device_peaks(device.device_kind)[0]
        )
        # None → auto (10x the TSV cadence); 0 → off — the same contract
        # as fit()'s memory_log_every, so `or` (which eats the 0) won't do
        self.heartbeat_every = (
            config.heartbeat_every if config.heartbeat_every is not None
            else self.log_every * 10
        )
        self.sentry = (
            NanSentry(
                window=config.spike_window, sigma=config.spike_sigma,
                min_steps=config.spike_min_steps,
                cooldown=config.cooldown_steps,
            )
            if config.sentry else None
        )
        self._model = model
        self._input_key = input_key
        self._flops_per_step: float | None = None
        self._tokens_per_step: int | None = None
        self._sized = False
        # explicit-gradient-reduction accounting (tpudist.parallel.dp):
        # set_comm() fills these; step_breakdown rows then carry the comm
        # column. None ⇒ feature off ⇒ rows byte-identical to before.
        self._comm: dict | None = None
        self._comm_probe_s: float | None = None
        # H2D link probe (MB/s, fit() fills on accelerator backends) + the
        # staged-batch byte count observe_batch measures: together they
        # decide the one-shot link-bound warning row
        self.h2d_mbps: float | None = None
        self._batch_bytes: int | None = None
        self._link_warned = False
        self._link_checks = 0
        # run-health layer (tpudist.telemetry.health.RunHealth), attached
        # by build_telemetry when any health knob (or the run report) is
        # on; None keeps every health path a no-op
        self.health = None
        # detector event bus: every sentry/divergence VERDICT is published
        # to these callbacks (the repair controller subscribes) — the
        # detectors stay pure observers, the subscribers decide what a
        # verdict is worth
        self._listeners: list = []
        # executed-repair record (tpudist.resilience.repair): this
        # generation's rows via set_repair; repair_history, when fit
        # attaches the controller's live cross-generation list, is what
        # the report's `repairs` section prefers
        self.repair_events: list[dict] = []
        self.repair_history: list[dict] | None = None
        # goodput tracker (tpudist.resilience.goodput), attached by fit();
        # the run report's `goodput` section reads it. None = no section.
        self.goodput = None
        # running skipped-update total — the exporter's counter surface
        self._skips_total = 0
        # span layer (tpudist.telemetry.trace.Tracer), attached by
        # build_telemetry when config.trace; None keeps every span path a
        # no-op and the streams byte-identical
        self.tracer = None
        # in-run perf-regression sentinel (tpudist.telemetry.anatomy) —
        # None (the default) keeps on_step's path byte-identical
        if config.regression_detect:
            from tpudist.telemetry.anatomy import StepTimeRegressionDetector

            self.regression = StepTimeRegressionDetector(
                window=config.regression_window,
                threshold=config.regression_threshold,
            )
        else:
            self.regression = None
        # live-metrics exporter (tpudist.telemetry.trace.MetricsExporter),
        # attached by fit(metrics_port=); on_step pushes host-side gauges
        # into it — no device syncs, no extra rows
        self.exporter = None
        # restart generation (TPUDIST_RESTART_GENERATION, exported by the
        # supervisor; 0 on a first launch): stamps heartbeat rows and the
        # run report so streams sharing one append-mode file are
        # attributable across the lives of the job
        from tpudist.resilience import restart_generation

        self.generation = restart_generation()
        # heartbeat identity fields: process_index + hostname + a
        # monotonic clock let the cross-process aggregator (and humans)
        # align per-rank timelines — rank alone is ambiguous once
        # global_rank counts replicas instead of hosts
        import socket

        self._host = socket.gethostname()
        try:
            import jax as _jax

            self.process_index = int(_jax.process_index())
        except Exception:
            self.process_index = int(rank)

    # -- wiring ------------------------------------------------------------

    def add_listener(self, fn) -> None:
        """Subscribe to detector verdicts: ``fn(event)`` is called with
        every sentry anomaly (``{"detector": "sentry", "event":
        "nonfinite"|"loss_spike", ...}``) and every divergence-probe
        verdict (``{"detector": "divergence", ...}``) as they resolve.
        Exceptions propagate — a subscriber is run logic, not logging."""
        self._listeners.append(fn)

    def _publish(self, event: Mapping[str, Any]) -> None:
        for fn in list(self._listeners):
            fn(event)

    def set_repair(self, info: Mapping[str, Any]) -> None:
        """One ``repair`` row per executed repair action
        (``tpudist.resilience.repair``): cause, rollback step, skipped
        window, action taken. Every rank records the event (the report's
        history source); rank 0 writes the row."""
        info = dict(info)
        self.repair_events.append(info)
        if self.rank == 0:
            self.sink.write("repair", info.get("skip_from"), **info)
        if self.tracer is not None:
            self.tracer.instant(
                "repair", step=info.get("skip_from"),
                cause=info.get("cause"), action=info.get("action"),
            )

    def reset_for_repair(self) -> None:
        """The repair loop just rolled the trajectory back: clear the
        sentry's spike baseline/cooldown and drop the health layer's
        in-flight delayed fetches — a pending divergence probe or
        aggregation gather describes the DISCARDED state and must not
        re-trigger (or mis-describe) the repaired trajectory."""
        if self.sentry is not None:
            self.sentry.reset()
        if self.health is not None:
            self.health.reset_pipelines()

    def set_fusion(self, info: Mapping[str, Any]) -> None:
        """One-time ``fusion`` row (rank 0): the step-fusion layer's
        resolved configuration (``make_train_step``'s ``step.fused_info``
        — ``ln``/``optimizer`` booleans + ``compute_dtype``), written at
        bring-up so every throughput/mfu row in the stream is attributable
        to the kernel set that produced it. Not written unless ``fit`` got
        a ``fused=`` request — streams stay byte-identical otherwise."""
        if self.rank == 0:
            self.sink.write("fusion", **dict(info))

    def set_comm(self, stats: Mapping[str, Any] | None,
                 probe_s: float | None = None) -> None:
        """Attach the explicit-reduction wire accounting
        (``GradReducer.comm_stats``) and the measured standalone
        reduce-only probe. Rank 0 writes a one-time ``comm`` row so the
        stream is self-describing: per-step rows carry only the live
        numbers, the setup row carries the method/bucket geometry and the
        fp32-equivalent bytes the compression is quoted against."""
        if not stats:
            return
        self._comm = dict(stats)
        self._comm_probe_s = probe_s
        if self.rank == 0:
            self.sink.write(
                "comm",
                probe_s=None if probe_s is None else round(probe_s, 6),
                **self._comm,
            )

    def set_reshard(self, info: Mapping[str, Any]) -> None:
        """One-time ``reshard`` row: an elastic resume re-laid the
        world-bound state onto a different world size
        (``tpudist.resilience.elastic``) — old/new world, how many
        ZeRO-1 leaves moved, whether the error-feedback residual banks
        were flushed, and the sampler-cursor remap. Every rank writes its
        own row (each rank restored its own shards); absent unless a
        reshard actually happened, so streams stay byte-identical."""
        self.sink.write("reshard", **dict(info))
        if self.tracer is not None:
            self.tracer.instant(
                "reshard",
                old_world=info.get("old_world"),
                new_world=info.get("new_world"),
            )

    def set_compile_cache(self, info: Mapping[str, Any]) -> None:
        """One-time ``compile_cache`` row (rank 0): the AOT executable
        cache's bring-up outcome (``tpudist.compile_cache``) — hit/miss,
        payload bytes, measured load/compile/store seconds. Only written
        when ``fit`` got a ``compile_cache=`` request."""
        if self.rank == 0:
            self.sink.write("compile_cache", **dict(info))

    def set_bringup(self, step: int, info: Mapping[str, Any]) -> None:
        """The one ``bringup`` row (rank 0), written when the first
        dispatch has returned: ``fit``'s entry on both clocks, the
        contiguous phases from there (``trace.BRINGUP_SPANS``) and the
        compile table by function name (``trace.Bringup.finish``).
        Written whenever telemetry is on, whatever ``trace`` says."""
        if self.rank == 0:
            self.sink.write("bringup", step, **dict(info))

    def recompiled(self, step: int, fun: str, **seconds: float) -> None:
        """A compile that ended after bring-up did: a ``recompile``
        warning naming the function, the step being dispatched and the
        trace / lower / backend seconds, and with ``trace`` an instant on
        the timeline."""
        self.warn("recompile", step, fun=fun, **seconds)
        if self.tracer is not None:
            self.tracer.instant("recompile", step=step, fun=fun)

    def set_anatomy(self, info: Mapping[str, Any] | None) -> None:
        """One ``anatomy`` row per introspected program (rank 0): XLA's
        own FLOPs/bytes count and static HBM breakdown for a compiled
        train/serve program (:func:`tpudist.telemetry.anatomy
        .analyze_train_step`), with the analytic cross-check fields when a
        counter exists. When the counter's drift against XLA exceeds
        ``config.anatomy_tolerance`` a ``stale_flops_counter`` warning row
        follows, naming the counter — the MFU-honesty alarm. ``None``
        (introspection unavailable) writes nothing; only written when
        ``fit``/serve got an anatomy request, so streams stay
        byte-identical otherwise."""
        if info is None or self.rank != 0:
            return
        self.sink.write("anatomy", **dict(info))
        drift = info.get("flops_drift")
        if drift is not None and abs(drift) > self.config.anatomy_tolerance:
            self.warn(
                "stale_flops_counter",
                program=info.get("program"),
                flops_counter=info.get("flops_counter"),
                xla_flops=info.get("flops_scaled"),
                analytic_flops=info.get("analytic_flops"),
                drift=round(drift, 4),
                tolerance=self.config.anatomy_tolerance,
                hint="tpudist/telemetry/flops.py's analytic counter "
                     "disagrees with XLA's cost analysis for this program "
                     "— the MFU rows' numerator is stale",
            )

    def warn(self, tag: str, step: int | None = None, **fields) -> None:
        """A tagged one-shot ``warning`` row (same schema as the
        h2d_link_bound diagnosis): the home for bring-up diagnoses other
        subsystems hand fit() — e.g. ``checkpoint_fallback`` when the
        newest checkpoint failed to deserialize and the restore walked
        back a step."""
        self.sink.write("warning", step, tag=tag, **fields)

    def observe_batch(self, batch: Mapping[str, Any]) -> None:
        """Size the MFU numerator from the first staged batch's GLOBAL
        shapes (once; analytic counters, no device work). Also records the
        staged batch's PER-HOST byte volume — the numerator of the
        link-bound check: staged arrays are global, but each host only
        ships its own shard over its own link, so the global nbytes must
        be divided by the process count or an 8-host run would see an
        8x-inflated staging estimate and warn on healthy links."""
        if self._batch_bytes is None:
            try:
                import jax as _jax

                self._batch_bytes = int(sum(
                    v.nbytes for k, v in batch.items()
                    if not k.startswith("_") and hasattr(v, "nbytes")
                ) / max(_jax.process_count(), 1))
            except Exception:
                self._batch_bytes = 0
        if self._sized or not self.config.mfu:
            return
        self._sized = True
        self._flops_per_step = flops.train_step_flops(
            self._model, batch, input_key=self._input_key
        )
        self._tokens_per_step = flops.tokens_per_step(
            self._model, batch, input_key=self._input_key
        )
        if self.rank == 0:
            self.sink.write(
                "run_meta",
                flops_per_step=self._flops_per_step,
                tokens_per_step=self._tokens_per_step,
                peak_flops_per_chip=self.peak_flops,
                device_kind=self.device_kind,
                n_chips=self.n_chips,
                world_size=self.world_size,
                flops_counter=getattr(self._model, "flops_counter", None),
            )

    # -- per-step drive ----------------------------------------------------

    def on_step(self, step: int, metrics: Mapping[str, float], *, epoch: int,
                interval_s: float, data_wait_s: float | None = None,
                dispatch_s: float | None = None) -> dict | None:
        """Record one RESOLVED step (host-side scalar values). Returns the
        anomaly event if the sentry fired, else None."""
        loss = float(metrics.get("loss", float("nan")))
        nonfinite = int(metrics.get("nonfinite_grad_count", 0) or 0)
        skipped = int(metrics.get("update_skipped", 0) or 0)
        self._skips_total += skipped
        cadence = step % self.log_every == 0
        mfu_val = None

        if self.rank == 0 and cadence:
            health = {
                k: metrics[k]
                for k in ("grad_norm", "param_norm", "update_norm",
                          "nonfinite_grad_count", "update_skipped")
                if k in metrics
            }
            if health:
                self.sink.write("health", step, loss=loss, **health)
            if self.config.breakdown and dispatch_s is not None:
                extra = {}
                if self._comm is not None:
                    # the comm column: the setup row's exact host integer
                    # is preferred over the compiled step's fp32 metric
                    # (whose 24-bit mantissa rounds GB-scale counts by up
                    # to ~128 bytes); the time is the one-shot standalone
                    # probe — an unoverlapped upper bound, not a per-step
                    # measurement (in-graph collectives cannot be timed
                    # from the host without a barrier)
                    extra = {
                        "comm_bytes": self._comm.get(
                            "bytes_per_step", metrics.get("comm_bytes")
                        ),
                        "comm_s": (
                            None if self._comm_probe_s is None
                            else round(self._comm_probe_s, 6)
                        ),
                    }
                self.sink.write(
                    "step_breakdown", step,
                    interval_s=round(interval_s, 6),
                    data_wait_s=round(data_wait_s or 0.0, 6),
                    dispatch_s=round(dispatch_s, 6),
                    **extra,
                )
            moe = {
                k[len("moe/"):]: v for k, v in metrics.items()
                if k.startswith("moe/")
            }
            if moe:
                # router observability (docs/OBSERVABILITY.md §1): one row
                # per cadence step with every MoE layer's dispatched load
                # fractions [E], dropped-choice rate, and unscaled aux-loss
                # value — the step metrics carry them as '<layer>/load',
                # '<layer>/dropped', '<layer>/aux' (tpudist.train)
                self.sink.write("moe", step, **moe)
            if self._flops_per_step is not None and interval_s > 0:
                # 8 decimals: a tiny CPU-test model's true MFU is ~1e-8
                # and must not round to a fake 0.0
                mfu_val = None if self.peak_flops is None else round(
                    flops.mfu(
                        self._flops_per_step, interval_s,
                        peak=self.peak_flops, n_chips=self.n_chips,
                    ), 8)
                self.sink.write(
                    "mfu", step,
                    mfu=mfu_val,
                    flops_per_step=self._flops_per_step,
                    step_time_s=round(interval_s, 6),
                    tokens_per_sec=(
                        None if self._tokens_per_step is None
                        else round(self._tokens_per_step / interval_s, 2)
                    ),
                )

        if (not self._link_warned and self.h2d_mbps and self._batch_bytes
                and interval_s > 0):
            # link-bound diagnosis: when just STAGING the
            # batch at the probed H2D rate would eat more than half the
            # observed step interval, the run is link-bound, and the framework
            # mitigation is DeviceCachedLoader (stage the set to HBM once;
            # per-step H2D becomes index-only). The first two resolved
            # intervals are skipped (they carry the jit compile, which
            # dwarfs any staging cost and would mask the diagnosis
            # permanently); after warm-up every step is checked until the
            # warning fires — a link can also COLLAPSE mid-run — and it
            # fires at most once: tagged row + one stderr line instead of
            # failing silently slow.
            self._link_checks += 1
            staging_s = self._batch_bytes / (self.h2d_mbps * 1e6)
            if self._link_checks > 2 and staging_s > 0.5 * interval_s:
                self._link_warned = True
                import sys

                self.sink.write(
                    "warning", step, tag="h2d_link_bound",
                    h2d_mbps=round(self.h2d_mbps, 1),
                    batch_bytes=self._batch_bytes,
                    est_staging_s=round(staging_s, 6),
                    interval_s=round(interval_s, 6),
                    hint="per-step H2D staging dominates the step; stage "
                         "the dataset to HBM once with DeviceCachedLoader "
                         "(tpudist/data/device_cache.py) or pack+cache for "
                         "streaming sets (tpudist/data/packed.py)",
                )
                print(
                    f"tpudist: H2D link-bound run (probe "
                    f"{self.h2d_mbps:.0f} MB/s, batch "
                    f"{self._batch_bytes / 1e6:.1f} MB ≈ {staging_s:.3f}s "
                    f"of a {interval_s:.3f}s step) — consider "
                    "DeviceCachedLoader (tpudist/data/device_cache.py)",
                    file=sys.stderr, flush=True,
                )

        event = None
        if self.sentry is not None:
            event = self.sentry.observe(
                step, loss, nonfinite_count=nonfinite, update_skipped=skipped
            )
            if event is not None:
                armed = False
                if self.config.capture_on_anomaly and self.profiler is not None:
                    armed = bool(self.profiler.arm(self.config.capture_steps))
                self.sink.write(
                    "anomaly", step, epoch=epoch, profiler_armed=armed,
                    **{k: v for k, v in event.items() if k != "step"},
                )
                # detector → event bus: the repair loop (and any other
                # subscriber) acts on the verdict the row records
                self._publish({"detector": "sentry", **event})

        if self.regression is not None and self.rank == 0:
            # in-run slowdown sentinel: collectives equalize interval_s
            # fleet-wide, so one observing rank suffices — and one row
            verdict = self.regression.observe(interval_s)
            if verdict is not None:
                self.sink.write("perf_regression", step, epoch=epoch,
                                **verdict)
                if self.tracer is not None:
                    self.tracer.instant("perf_regression", step=step)

        if self.heartbeat_every and step % self.heartbeat_every == 0:
            # every process writes its own heartbeat — the cross-host
            # straggler signal. Existing fields stay byte-identical; the
            # identity/clock triple (process_index, host, mono) is
            # appended so per-rank timelines can be aligned (wall clocks
            # skew across hosts; time.monotonic deltas do not)
            # generation rides AFTER the identity triple — the same
            # append-only discipline: existing fields byte-identical,
            # new ones appended (0 on a never-restarted run)
            self.sink.write("heartbeat", step, epoch=epoch,
                            interval_s=round(interval_s, 6),
                            process_index=self.process_index,
                            host=self._host,
                            mono=round(time.monotonic(), 6),
                            generation=self.generation)

        if self.tracer is not None:
            # one `span` row per RESOLVED step, per rank — the timeline form
            # of the step_breakdown row, with the host-side attribution as
            # args. t0 is on the tracer's monotonic clock (the heartbeat
            # `mono` domain), so tracelens aligns ranks the same way it
            # aligns heartbeats.
            self.tracer.span(
                "step", interval_s, step=step,
                data_wait_s=round(data_wait_s or 0.0, 6),
                dispatch_s=None if dispatch_s is None else round(dispatch_s, 6),
            )
            if event is not None:
                self.tracer.instant(
                    "anomaly", step=step, event=event.get("event")
                )

        if self.exporter is not None:
            # live scrape surface: host-side scalars only — everything here
            # was already fetched for the rows above, zero extra device work
            self.exporter.set(
                step=step,
                loss=loss if math.isfinite(loss) else None,
                step_time_s=round(interval_s, 6),
                data_wait_s=round(data_wait_s or 0.0, 6),
                mfu=mfu_val,
                tokens_per_sec=(
                    None
                    if (self._tokens_per_step is None or interval_s <= 0)
                    else round(self._tokens_per_step / interval_s, 2)
                ),
                anomaly_events_total=(
                    len(self.sentry.events) if self.sentry else 0
                ),
                update_skips_total=self._skips_total,
                repair_events_total=len(self.repair_events),
            )

        if self.health is not None:
            # host_s is the rank-LOCAL share of the step (input wait +
            # dispatch) — the scalar that actually differs on a straggling
            # host, since lockstep collectives equalize interval_s fleet-
            # wide (tpudist.telemetry.health.CrossProcessAggregator)
            self.health.observe_interval(
                step, interval_s,
                host_s=(data_wait_s or 0.0) + (dispatch_s or 0.0),
                mfu=mfu_val, skipped=skipped,
            )
        return event

    # -- run-health passthroughs (fit()'s loop-side hooks) -----------------

    def beat(self, step: int) -> None:
        """Feed the hang watchdog — once per loop iteration."""
        if self.health is not None:
            self.health.beat(step)

    def observe_state(self, step: int, state) -> None:
        """Drive the replica-divergence probe (dispatch side; resolves one
        cadence later on the delayed pipeline)."""
        if self.health is not None:
            self.health.observe_state(step, state)
            if (self.tracer is not None and self.config.divergence_every
                    and step % self.config.divergence_every == 0):
                self.tracer.instant("probe", step=step, probe="divergence")

    def mark_crashing(self) -> None:
        """fit()'s exception handler calls this FIRST, before flushing the
        final pending step: from here on no health path may dispatch or
        resolve a collective (a fetch queued behind the hung collective
        the crash interrupted would block the crash handler forever)."""
        if self.health is not None:
            self.health.crashing = True

    def on_crash(self, exc: BaseException | None = None) -> None:
        """fit()'s exception path: snapshot the run report with a crash
        status before the exception propagates. Never raises — forensics
        must not mask the original failure."""
        if self.health is None:
            return
        label = type(exc).__name__ if exc is not None else "exception"
        try:
            # drain=False: a pending gather/probe fetch behind a HUNG
            # collective would block this very crash handler forever —
            # the crashed report comes from host-side state only
            self.health.finish(status=f"crashed:{label}", drain=False)
        except Exception:
            pass

    def shutdown(self) -> None:
        """fit()'s finally-path teardown: stop the watchdog thread, then
        close the sink (which the logger's mirrored footer must precede —
        same ordering contract as before)."""
        if self.health is not None:
            self.health.shutdown()
        if self.exporter is not None:
            self.exporter.close()
        self.sink.close()

    def finish(self, opt_state=None, status: str = "completed") -> None:
        """Final summary row (rank 0): sentry event count and — when the
        optimizer chain carries an ``amp.skip_nonfinite`` wrapper — its
        skip counter (one host fetch, at run end only). With run-health
        on, also drains the delayed aggregation/probe pipelines (all
        ranks — they hold already-dispatched collectives' results) and
        writes the end-of-run report. ``status`` stamps the report
        (``"preempted"`` from fit's graceful-preemption path — still a
        clean drain: nothing is hung, the collectives resolve)."""
        skips = None
        if self.rank == 0 and opt_state is not None:
            from tpudist.amp import maybe_skipped_steps

            skips = maybe_skipped_steps(opt_state)
        if self.rank == 0:
            self.sink.write(
                "run_summary",
                anomaly_events=len(self.sentry.events) if self.sentry else 0,
                optimizer_nonfinite_skips=skips,
            )
        if self.health is not None:
            self.health.finish(status=status, optimizer_skips=skips)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.sink.close()


def build_telemetry(
    telemetry: bool | TelemetryConfig,
    *,
    job_id: str,
    log_dir: str,
    rank: int,
    world_size: int,
    log_every: int,
    n_chips: int,
    profiler=None,
    model=None,
    input_key: str = "tokens",
    mesh=None,
) -> Telemetry | None:
    """fit()'s constructor: ``False`` → None (telemetry entirely off, the
    reference TSV contract byte-identical), ``True`` → defaults, a
    :class:`TelemetryConfig` → as configured. ``mesh`` enables the
    replica-divergence probe (it needs the device mesh to build its
    shard_map); the other health pieces work without it."""
    if not telemetry:
        return None
    config = telemetry if isinstance(telemetry, TelemetryConfig) else TelemetryConfig()
    out_dir = Path(config.jsonl_dir or log_dir)
    # the job's stable run id: the launcher's env export when supervised
    # (one id across all ranks and relaunched generations), else minted
    # here — WITHOUT touching os.environ, so one fit() call in a long
    # process (a test suite) cannot leak its id into the next
    from tpudist.resilience.exitcodes import run_id as _env_run_id

    rid = _env_run_id()
    if rid is None:
        import uuid

        rid = uuid.uuid4().hex[:12]
    sink = TelemetrySink(
        out_dir / f"{job_id}_telemetry_{rank}.jsonl",
        rank=rank, max_bytes=config.jsonl_max_bytes, run_id=rid,
    )
    tel = Telemetry(
        config, sink, model=model, input_key=input_key, profiler=profiler,
        rank=rank, world_size=world_size, log_every=log_every, n_chips=n_chips,
    )
    if config.trace:
        from tpudist.telemetry.trace import Tracer

        tel.tracer = Tracer(
            sink, cat="train",
            process_index=tel.process_index, generation=tel.generation,
        )
    if (config.run_report or config.aggregate_every
            or config.divergence_every or config.hang_timeout_s):
        from tpudist.telemetry.health import RunHealth

        tel.health = RunHealth(
            config, sink, job_id=job_id, log_dir=str(out_dir), mesh=mesh,
            rank=rank, profiler=profiler, tel=tel,
        )
    return tel
