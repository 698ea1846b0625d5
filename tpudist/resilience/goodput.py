"""Goodput accounting: where did the wall time of this job's life go?

Under preemption the headline metric is not tokens/sec but **goodput** —
the fraction of wall time spent making forward progress once compile,
checkpoint save/restore, data stalls, and restart/resume overhead are
paid (the operational regime of the TPUv4 pjit experience reports:
recovery time, not peak rate, determines useful throughput at pod
scale). :class:`GoodputTracker` partitions one ``fit()`` call's wall time
into disjoint components and aggregates them ACROSS restart generations
through the ``{job}_report.json`` each generation leaves behind:

- ``bringup_s`` — fit entry → first loop iteration (state init, replica
  verification, telemetry bring-up), minus the restore below; ``fit``
  builds the tracker on its first lines, so with ``restore_s``,
  ``cache_load_s`` and the AOT path's ``compile_s`` this is the sum of
  the seven phases before the loop in the ``bringup`` telemetry row
  (``tpudist.telemetry.trace.BRINGUP_SPANS``);
- ``restore_s`` — checkpoint restore (the resume read);
- ``compile_s`` — the first loop iteration wall time (jit traces and
  compiles synchronously on first call, so iteration 1 *is* the compile,
  plus one ordinary step — an upper bound, noted not subtracted). With
  the AOT path (``fit(compile_cache=...)``) compilation happens at
  bring-up instead and is added explicitly; :meth:`GoodputTracker
  .set_precompiled` then keeps iteration 1 an ordinary step;
- ``cache_load_s`` — seconds bring-up BLOCKED on the AOT executable
  deserialization (``tpudist.compile_cache``): a warm start's analogue
  of compile time. The load runs on a side thread overlapped with the
  restore, so only the non-hidden join wait is booked — the partition
  stays disjoint (the full thread duration rides the telemetry
  ``compile_cache`` row as ``load_s``). Kept as its own component so a
  cache-hit first iteration is never mislabeled ``compile_s`` —
  ``restart_overhead_s`` still counts it (it is restart cost), but the
  cold-vs-warm A/B stays readable;
- ``data_wait_s`` — seconds the loop blocked on the batch iterator
  (steady-state iterations only; iteration 1's wait is inside
  ``compile_s``);
- ``checkpoint_s`` — seconds blocked in checkpoint saves, including the
  synchronous emergency save (also reported separately as
  ``emergency_save_s``, a subset of ``checkpoint_s``);
- ``repair_s`` — seconds spent executing in-process repairs
  (``tpudist.resilience.repair``: the anchored-checkpoint restore, the
  residual flush, the cursor jump);
- ``repair_replay_s`` — the wall seconds of STEP WORK a repair's rollback
  discarded (measured step intervals of the rolled-back span). Those
  seconds were counted productive while they ran; booking them here
  reclassifies them out of the productive residual, which is the honest
  price of a repair — the repaired run re-earns that progress on clean
  data. A second-order overlap with ``data_wait_s`` (the discarded
  steps' input waits are in both) is accepted: the residual clamps at
  zero and the repair tests read this component, not the residual;
- ``productive_step_s`` — the residual: total minus everything above.
  Computing productive time as the residual is what makes the components
  sum to the generation's wall time *exactly* (the report's acceptance
  contract), and it is the honest definition — any second not spent on
  an identified overhead was available to the step pipeline.

Cross-generation: each generation's summary carries a ``generations``
list (its own entry appended to the predecessors' — loaded from the
previous report via :meth:`GoodputTracker.load_previous`) and a
``cumulative`` block whose ``restart_overhead_s`` prices recovery: the
inter-generation wall gaps (supervisor backoff + process spawn) plus
every resumed generation's bring-up/restore/compile plus every emergency
save.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Callable

__all__ = ["GoodputTracker"]

# the disjoint partition of one generation's wall time; productive is the
# residual so the sum is exact by construction
COMPONENTS = (
    "bringup_s",
    "restore_s",
    "compile_s",
    "cache_load_s",
    "data_wait_s",
    "checkpoint_s",
    "repair_s",
    "repair_replay_s",
)


class GoodputTracker:
    def __init__(self, *, generation: int = 0,
                 clock: Callable[[], float] = time.perf_counter,
                 wall: Callable[[], float] = time.time):
        self.generation = int(generation)
        self._clock = clock
        self._wall = wall
        self._t0 = clock()
        self.start_wall = wall()
        self._parts = {k: 0.0 for k in COMPONENTS}
        self.emergency_save_s = 0.0
        self.repairs = 0
        self.steps = 0
        self._loop_t: float | None = None
        self._first_step_done = False
        self._precompiled = False
        self._warm = False
        self._prior: list[dict] = []

    # -- wiring ------------------------------------------------------------

    def load_previous(self, report_path: str | Path) -> None:
        """Carry forward the previous generations' entries from the report
        the last life of this job wrote (same job_id, same log_dir — the
        sink's append-mode precedent). Malformed/absent files are ignored:
        goodput is accounting, never a crash source."""
        try:
            report = json.loads(Path(report_path).read_text())
            gens = report["goodput"]["generations"]
            self._prior = [dict(g) for g in gens if isinstance(g, dict)]
        except Exception:
            self._prior = []

    def add(self, component: str, seconds: float) -> None:
        self._parts[component] += max(float(seconds), 0.0)

    def add_emergency_save(self, seconds: float) -> None:
        """The preemption path's synchronous save: counted inside
        ``checkpoint_s`` (the partition stays disjoint) and surfaced
        separately — it is the per-incident recovery cost."""
        self.add("checkpoint_s", seconds)
        self.emergency_save_s += max(float(seconds), 0.0)

    def add_repair(self, overhead_s: float, replay_s: float = 0.0) -> None:
        """One executed repair (``tpudist.resilience.repair``):
        ``overhead_s`` is the machinery (restore + flush + cursor jump),
        ``replay_s`` the discarded step work the rollback threw away —
        both reclassified out of the productive residual."""
        self.add("repair_s", overhead_s)
        self.add("repair_replay_s", replay_s)
        self.repairs += 1

    def set_precompiled(self, warm: bool = False) -> None:
        """The step executable exists BEFORE the loop (AOT path:
        ``tpudist.compile_cache`` compiled it at bring-up on a miss, or
        deserialized it on a hit): iteration 1 is an ordinary step and
        must not be attributed to ``compile_s``. ``warm`` marks a cache
        hit — the entry's ``warm_start`` field."""
        self._precompiled = True
        self._warm = bool(warm)

    def clear_precompiled(self) -> None:
        """The precompiled executable was REJECTED at first call (the
        AOT wrapper fell back to tracing): iteration 1 will pay a real
        trace+compile after all, so the attribution reverts to the cold
        contract — and the generation stops claiming a warm start (the
        cache load it did pay stays booked in ``cache_load_s``)."""
        self._precompiled = False
        self._warm = False

    def loop_started(self) -> None:
        """The epoch loop is about to run: everything so far that isn't
        already attributed (restore, compile/cache work on the AOT path,
        early checkpoint work) is bring-up."""
        self._loop_t = self._clock()
        self._parts["bringup_s"] = max(
            (self._loop_t - self._t0)
            - self._parts["restore_s"] - self._parts["checkpoint_s"]
            - self._parts["compile_s"] - self._parts["cache_load_s"],
            0.0,
        )

    def step_boundary(self, data_wait_s: float = 0.0) -> None:
        """Called once per completed loop iteration. The first iteration
        is attributed whole to ``compile_s`` (jit compiles synchronously
        inside it) — UNLESS the executable was precompiled/cache-loaded
        at bring-up (:meth:`set_precompiled`), in which case iteration 1
        is an ordinary step and contributes its measured data wait like
        any other; later iterations contribute their measured data
        wait."""
        self.steps += 1
        now = self._clock()
        if not self._first_step_done:
            self._first_step_done = True
            if not self._precompiled:
                base = self._loop_t if self._loop_t is not None else self._t0
                self._parts["compile_s"] = max(now - base, 0.0)
                return
        self.add("data_wait_s", data_wait_s)

    # -- report ------------------------------------------------------------

    def _entry(self, exit_reason: str) -> dict:
        total = self._clock() - self._t0
        overhead = sum(self._parts.values())
        entry = {
            "generation": self.generation,
            "exit_reason": exit_reason,
            "total_s": round(total, 6),
            "productive_step_s": round(max(total - overhead, 0.0), 6),
            **{k: round(v, 6) for k, v in self._parts.items()},
            "emergency_save_s": round(self.emergency_save_s, 6),
            "warm_start": bool(self._warm),
            "repairs": self.repairs,
            "steps": self.steps,
            "start_wall": round(self.start_wall, 3),
            "end_wall": round(self._wall(), 3),
        }
        return entry

    def summary(self, exit_reason: str = "completed") -> dict:
        """The report's ``goodput`` section. Safe to call repeatedly (the
        watchdog snapshots mid-run, finish() writes the final one): each
        call recomputes from live counters without mutating history."""
        entry = self._entry(exit_reason)
        gens = self._prior + [entry]
        gaps = [
            max(b.get("start_wall", 0.0) - a.get("end_wall", 0.0), 0.0)
            for a, b in zip(gens, gens[1:])
        ]
        resumed = gens[1:]
        restart_overhead = (
            sum(gaps)
            + sum(g.get("bringup_s", 0.0) + g.get("restore_s", 0.0)
                  + g.get("compile_s", 0.0) + g.get("cache_load_s", 0.0)
                  for g in resumed)
            + sum(g.get("emergency_save_s", 0.0) for g in gens)
        )
        total = sum(g.get("total_s", 0.0) for g in gens) + sum(gaps)
        productive = sum(g.get("productive_step_s", 0.0) for g in gens)
        out = dict(entry)
        out["productive_frac"] = round(
            entry["productive_step_s"] / max(entry["total_s"], 1e-9), 6
        )
        out["generations"] = gens
        out["cumulative"] = {
            "wall_s": round(total, 6),
            "productive_step_s": round(productive, 6),
            "restart_gap_s": round(sum(gaps), 6),
            "restart_overhead_s": round(restart_overhead, 6),
            "repair_overhead_s": round(
                sum(g.get("repair_s", 0.0) + g.get("repair_replay_s", 0.0)
                    for g in gens), 6
            ),
            "repairs": sum(int(g.get("repairs", 0) or 0) for g in gens),
            "productive_frac": round(
                productive / total if total > 0 else 0.0, 6
            ),
        }
        return out
