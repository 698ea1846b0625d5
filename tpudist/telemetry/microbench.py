"""Differential on-device microbenchmark timing — the measurement skeleton
behind ``examples/mfu_probe.py`` and
``examples/kernel_probe.py``, factored here so every probe measures the
same way.

The problem it solves: each device call carries a fixed cost (dispatch,
the value fetch that syncs it) that swamps a sub-millisecond kernel under
a naive ``time(run(n))/n``. Three ingredients fix it:

- **differential timing** — ``(t(4n) − t(n)) / 3n`` cancels every
  per-call fixed cost (dispatch, the value-fetch sync);
- **adaptive iteration counts** — sized from an optimistic per-iteration
  estimate so the differential itself spans ~1.5 s of device time, far
  above per-call jitter;
- **plausibility retries** — a non-positive or faster-than-physics
  differential is jitter, not measurement: retry with a doubled budget,
  and return NaN (never a fake number) if it stays noisy.

Callers provide ``timed(n) -> seconds`` (median wall time for ``n``
iterations, compiled and synchronized by a VALUE fetch — ``float(out)``).
:func:`anti_hoist_scan` builds the standard iteration body:
one jitted ``lax.scan`` whose operand is scaled per-iteration (defeats
loop-invariant hoisting) and whose result feeds an accumulator (defeats
dead-code elimination).
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np


def adaptive_iters(est_iter_s: float, *, budget_s: float = 0.5,
                   lo: int = 64, hi: int = 8192) -> int:
    """Iteration count whose single-``n`` timing is ~``budget_s`` of device
    time under the caller's optimistic per-iteration estimate (the
    differential then spans ``3n`` ≈ 3 budgets)."""
    if est_iter_s <= 0:
        return hi
    return int(np.clip(budget_s / est_iter_s, lo, hi))


def differential_iter_seconds(timed: Callable[[int], float],
                              iters: int) -> float:
    """One differential sample: ``(timed(4n) − timed(n)) / 3n``."""
    return (timed(4 * iters) - timed(iters)) / (3 * iters)


def measure_iter_seconds(
    timed: Callable[[int], float],
    est_iter_s: float,
    *,
    budget_s: float = 0.5,
    floor_s: float | None = None,
    attempts: int = 3,
    lo: int = 64,
    hi: int = 8192,
    max_iters: int = 16384,
) -> float:
    """Robust seconds-per-iteration via the differential method.

    ``floor_s``: the fastest physically-plausible per-iteration time
    (e.g. ``flops / (1.05·peak)`` or ``bytes / (1.05·peak_bw)``); a
    differential below it — or non-positive — is timing jitter and
    triggers a doubled-budget retry. Returns NaN after ``attempts``
    persistently-noisy tries: a missing number, never a fake one.
    """
    iters = adaptive_iters(est_iter_s, budget_s=budget_s, lo=lo, hi=hi)
    for _ in range(attempts):
        dt = differential_iter_seconds(timed, iters)
        if dt > 0 and (floor_s is None or dt >= floor_s):
            return dt
        iters = min(iters * 2, max_iters)
    return float("nan")


def anti_hoist_scan(body: Callable, operand, *, reps: int = 5):
    """Build ``timed(n)`` for :func:`measure_iter_seconds` from a kernel
    invocation.

    ``body(scaled_operand) -> array`` is the work to time; it runs inside
    one jitted ``lax.scan`` of ``n`` iterations with the operand scaled
    per-iteration (``×(1 + i·1e-6)`` — no hoisting) and the FULL result
    accumulated as the scan carry (a scalar carry would let XLA slice the
    work down to one element — the whole output must stay live). One
    element of the accumulator is fetched at the end. ``timed(n)``
    compiles once per distinct ``n``, then returns the median of ``reps``
    runs, each synchronized by the value fetch.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x, scales):
        shape = jax.eval_shape(body, x)

        def step(acc, s):
            out = body(x * s.astype(x.dtype))
            return acc + out.astype(jnp.float32), None

        acc, _ = jax.lax.scan(
            step, jnp.zeros(shape.shape, jnp.float32), scales
        )
        return jnp.ravel(acc)[0]

    def timed(n_iters: int) -> float:
        scales = jnp.asarray(1.0 + np.arange(n_iters) * 1e-6, jnp.float32)
        run(operand, scales).block_until_ready()  # compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run(operand, scales))  # value fetch = real sync on remote
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    return timed
