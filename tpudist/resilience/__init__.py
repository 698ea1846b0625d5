"""Resilience: the layer that turns failures into bounded-cost events.

tpudist can *detect* sick jobs (the run-health layer) and *persist* state
(the Orbax checkpointer); this package connects detection to action so a
preemption, a hang, or a crash costs a bounded amount of work instead of
the whole run:

- :mod:`~tpudist.resilience.exitcodes` — the trainer↔supervisor exit-code
  contract (75 = preempted/resume, 76 = watchdog hang, else crash);
- :mod:`~tpudist.resilience.preempt` — SIGTERM/SIGINT trapped as a
  signal-safe flag; ``fit()`` finishes the in-flight step, writes a
  synchronous emergency checkpoint, flushes the run report with
  ``exit_reason="preempted"``, and raises :class:`Preempted` (exit 75);
- :mod:`~tpudist.resilience.supervisor` — restart policy for
  ``tpudist.launch``: restartable-code fast path, exponential backoff
  with jitter for crashes, a rolling restart-budget window, and the
  ``TPUDIST_RESTART_GENERATION`` counter;
- :mod:`~tpudist.resilience.goodput` — wall-time partitioning (productive
  step time vs compile/checkpoint/data-wait/restart overhead), aggregated
  across generations into the run report's ``goodput`` section;
- :mod:`~tpudist.resilience.chaos` — deterministic crash/hang/SIGTERM/
  checkpoint-corruption injection (``main.py --chaos``, the recovery
  tests);
- :mod:`~tpudist.resilience.repair` — the self-healing loop
  (``fit(repair=...)``): detector verdicts (replica divergence, skip
  streaks, sustained loss spikes) execute an in-process escalation
  ladder — roll back to the last-known-good ANCHORED checkpoint, skip
  the offending data window with a redrawn RNG salt, exit 77 for a
  supervised relaunch on a repeat trigger, and circuit-break a
  deterministic poison on a rolling repair budget (docs/MULTIHOST.md
  "Recovering from loss spikes and SDCs");
- :mod:`~tpudist.resilience.elastic` — cross-world-size checkpoint
  resharding (``fit(elastic=True)``): ZeRO-1 pad-and-reshape layouts
  re-laid onto the surviving mesh, error-feedback residual flushed,
  sampler cursor remapped — a preempted world resumes on whatever
  hardware is left (docs/MULTIHOST.md "Resuming on a different world
  size"). The AOT executable cache that makes the relaunch cheap lives
  in :mod:`tpudist.compile_cache`.

Operational recipe: docs/MULTIHOST.md "Surviving preemption".
"""

from tpudist.resilience.chaos import (
    ChaosCrash,
    ChaosInjector,
    ChaosSpec,
    corrupt_latest_checkpoint,
    flip_param_bit,
    make_injector,
    parse_chaos,
)
from tpudist.resilience.elastic import (
    ElasticRefusal,
    elastic_mismatch,
    remap_step,
    reshard_restore,
)
from tpudist.resilience.exitcodes import (
    EXIT_CRASH,
    EXIT_HANG,
    EXIT_HISTORY_ENV,
    EXIT_INTERRUPT,
    EXIT_OK,
    EXIT_PREEMPTED,
    EXIT_REPAIR,
    GENERATION_ENV,
    RESTARTABLE,
    RUN_ID_ENV,
    ensure_run_id,
    exit_history,
    is_restartable,
    restart_generation,
    run_id,
)
from tpudist.resilience.goodput import GoodputTracker
from tpudist.resilience.preempt import Preempted, PreemptionGuard
from tpudist.resilience.repair import (
    RepairController,
    RepairExhausted,
    RepairPolicy,
    RepairRestart,
    resolve_policy,
)
from tpudist.resilience.supervisor import (
    BackoffPolicy,
    RestartBudget,
    Supervisor,
    classify,
)

__all__ = [
    "EXIT_OK",
    "EXIT_CRASH",
    "EXIT_PREEMPTED",
    "EXIT_HANG",
    "EXIT_REPAIR",
    "EXIT_INTERRUPT",
    "RESTARTABLE",
    "GENERATION_ENV",
    "EXIT_HISTORY_ENV",
    "RUN_ID_ENV",
    "is_restartable",
    "restart_generation",
    "exit_history",
    "run_id",
    "ensure_run_id",
    "Preempted",
    "PreemptionGuard",
    "BackoffPolicy",
    "RestartBudget",
    "Supervisor",
    "classify",
    "GoodputTracker",
    "ChaosCrash",
    "ChaosSpec",
    "ChaosInjector",
    "make_injector",
    "parse_chaos",
    "corrupt_latest_checkpoint",
    "flip_param_bit",
    "ElasticRefusal",
    "elastic_mismatch",
    "remap_step",
    "reshard_restore",
    "RepairPolicy",
    "RepairController",
    "RepairRestart",
    "RepairExhausted",
    "resolve_policy",
]
