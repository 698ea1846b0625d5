"""The measured window: the loader that feeds ``fit`` and ends the stream,
the recorder ``fit`` calls once per resolved step, and the arithmetic that
turns the recorder's rows into the end-to-end metrics."""

from __future__ import annotations

import contextlib
import statistics
import time

import numpy as np


class Window:
    """Shared by loader and recorder. The window opens when the last
    warm-up step resolves and closes at the last resolve of the stream; the
    loader stops yielding once ``seconds`` have passed since it opened."""

    def __init__(self, warmup_steps: int, seconds: float):
        if warmup_steps < 1:
            raise ValueError("the window needs at least one warm-up step")
        self.warmup_steps = int(warmup_steps)
        self.seconds = float(seconds)
        self.opened_at: float | None = None

    def expired(self, now: float) -> bool:
        return self.opened_at is not None and now - self.opened_at >= self.seconds


def _annotation(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class WindowLoader:
    """Batches drawn from the seed, already the cell's shape. One epoch:
    ``warmup_steps`` batches, then more until the window has lasted its
    seconds. No ``__len__``: the stream's length is set by the clock."""

    def __init__(self, make_stream, seed: int, window: Window, *,
                 keep_first: int = 0, annotate: bool = False):
        self._make_stream = make_stream
        self._seed = int(seed)
        self._window = window
        self._keep_first = keep_first
        self._annotate = annotate
        self.first_batches: list[dict] = []
        self.yielded = 0
        self.batch_size = len(next(iter(self.probe().values())))

    def probe(self) -> dict:
        """One batch for ``fit``'s shape probe, off the run's stream."""
        return self._make_stream(np.random.Generator(
            np.random.PCG64([self._seed, 0x70726F6265])
        ))()

    def __iter__(self):
        next_batch = self._make_stream(
            np.random.Generator(np.random.PCG64(self._seed))
        )
        while True:
            if (self.yielded >= self._window.warmup_steps
                    and self._window.expired(time.perf_counter())):
                return
            with _annotation("bench_loader_next", self._annotate):
                batch = next_batch()
            if len(self.first_batches) < self._keep_first:
                self.first_batches.append(batch)
            self.yielded += 1
            yield batch


def make_recorder(window: Window, *, log_every: int = 5, on_step=None,
                  annotate: bool = False):
    """A ``MetricsLogger`` that writes no file: it keeps
    ``(step, loss, perf_counter())`` for every resolved step and opens the
    window. ``on_step(step, t)`` runs after each row (the traced run starts
    and stops the profiler from it)."""
    from tpudist.metrics import MetricsLogger

    class Recorder(MetricsLogger):
        def __init__(self):  # no TSV: the base class would open one
            self.log_every = log_every
            self.print_every = 10
            self.rows: list[tuple[int, float, float]] = []
            self.memory_rows: list[dict] = []
            self._sink = None

        def start_timer(self) -> None:
            pass

        def log_step(self, global_step, loss_value, step_duration) -> None:
            with _annotation("bench_recorder", annotate):
                t = time.perf_counter()
                self.rows.append((int(global_step), float(loss_value), t))
                if global_step == window.warmup_steps:
                    window.opened_at = t
                if on_step is not None:
                    on_step(int(global_step), t)

        def print_progress(self, epoch, idx, loss_value) -> None:
            pass

        def log_memory(self, stats, peak_bytes_in_use=None) -> None:
            if stats:
                self.memory_rows.append(dict(stats))

        def finish(self) -> float:
            return 0.0

        def __exit__(self, *exc):
            pass

    return Recorder()


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), on plain floats."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def window_metrics(rows, window: Window, *, tokens_per_step: int,
                   chips: int) -> dict:
    """End-to-end numbers over ALL steps resolved in the window.

    ``rows`` are the recorder's ``(step, loss, t)``. The window runs from
    the resolve of the last warm-up step to the last resolve; every
    resolve-to-resolve interval in it counts, stalls included."""
    if window.opened_at is None:
        raise RuntimeError(
            f"the window never opened: {len(rows)} steps resolved, "
            f"{window.warmup_steps} warm-up steps wanted"
        )
    times = [t for step, _, t in rows if step >= window.warmup_steps]
    steps = len(times) - 1
    if steps < 2:
        raise RuntimeError(f"only {steps} steps resolved inside the window")
    length = times[-1] - times[0]
    intervals = [b - a for a, b in zip(times, times[1:])]
    return {
        "steps": steps,
        "window_s": length,
        "tokens_per_s_per_chip": steps * tokens_per_step / length / chips,
        "step_ms_p90": 1e3 * percentile(intervals, 90),
        "step_ms_median": 1e3 * statistics.median(intervals),
        "step_ms_max": 1e3 * max(intervals),
    }
