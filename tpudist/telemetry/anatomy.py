"""Program anatomy: what XLA actually compiled, checked against what we claim.

The MFU rows (docs/OBSERVABILITY.md §5) and the memory budget tables
(``tpudist/memory.py``) both rest on hand-maintained analytic models —
``tpudist/telemetry/flops.py``'s counters and ``tpudist/memory.py``'s
activation estimates. Nothing verified them against the compiled program
until now. This module asks the compiler directly, once, at bring-up:

- :func:`program_costs` / :func:`program_memory` normalize
  ``Compiled.cost_analysis()`` / ``Compiled.memory_analysis()`` across the
  jax versions and backends we run on (list-of-dict vs dict; backends
  without memory analysis) into plain fail-soft dicts.
- :func:`analyze_train_step` produces the one-shot ``anatomy`` row for the
  train step: XLA-counted FLOPs (scaled by ``grad_accum`` — HLO cost
  analysis counts a ``lax.scan`` body ONCE, so the raw number is 1/G of
  the work the step performs), bytes accessed, and the static HBM
  breakdown, cross-checked against the analytic counter. Drift beyond
  tolerance means a counter went stale against a model edit — the MFU
  numbers are lying — and ``Telemetry.set_anatomy`` turns that into a
  ``warning`` row naming the counter.
- :class:`StepTimeRegressionDetector` is the in-run half of the regression
  sentinel (across runs the driver's ledger judges): a rolling
  median of observed step times against the post-compile baseline, firing
  a one-shot ``perf_regression`` row on sustained slowdown — the
  mid-run drift (data pipeline, thermal, host contention) that per-step
  logs show but nothing flags.

Everything here is observe-only and off by default: no knob set, no code
in this module runs and every stream stays byte-identical.
"""

from __future__ import annotations

from typing import Any, Mapping

__all__ = [
    "program_costs",
    "program_memory",
    "analyze_program",
    "analyze_train_step",
    "flops_drift",
    "StepTimeRegressionDetector",
]


def _first_mapping(obj) -> Mapping[str, Any] | None:
    """``cost_analysis()`` returns a dict on new jax, ``[dict]`` on the
    versions we pin; both collapse to the one per-program mapping."""
    if isinstance(obj, Mapping):
        return obj
    if isinstance(obj, (list, tuple)) and obj and isinstance(obj[0], Mapping):
        return obj[0]
    return None


def program_costs(compiled_or_lowered) -> dict[str, float] | None:
    """XLA's own operation count for a compiled (or merely lowered)
    program: ``{"flops", "bytes_accessed", "transcendentals"}``, or
    ``None`` where the backend doesn't implement cost analysis. Works on
    both ``Compiled`` and ``Lowered`` objects — lowering is enough for
    costs (not for memory), which is what makes the jit-path fallback
    free of a second compile."""
    try:
        cost = _first_mapping(compiled_or_lowered.cost_analysis())
    except Exception:
        return None
    if cost is None:
        return None
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = cost.get(key)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out if "flops" in out else None


def program_memory(compiled) -> dict[str, int] | None:
    """The static HBM breakdown of a compiled program, from
    ``Compiled.memory_analysis()``: argument / output / temp / alias /
    generated-code bytes plus ``peak_bytes`` — the sum of the resident
    pieces (args + outputs + temps + code), the closest static analogue
    of the allocator's live peak the API exposes. ``None`` (fail-soft)
    on backends or objects without memory analysis — a ``Lowered`` lands
    here, as do plugin backends that return nothing."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out = {}
    for attr, name in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes",
                        "generated_code_bytes")):
        v = getattr(mem, attr, None)
        if isinstance(v, (int, float)):
            out[name] = int(v)
    if not out:
        return None
    out["peak_bytes"] = (out.get("argument_bytes", 0)
                         + out.get("output_bytes", 0)
                         + out.get("temp_bytes", 0)
                         + out.get("generated_code_bytes", 0)
                         - out.get("alias_bytes", 0))
    return out


def analyze_program(name: str, *, compiled=None, lowered=None,
                    grad_accum: int = 1) -> dict[str, Any] | None:
    """One program's anatomy dict: costs from whichever of ``compiled`` /
    ``lowered`` answers (compiled preferred — it has memory too), memory
    from ``compiled`` only. ``grad_accum`` scales the FLOPs/bytes into
    per-step units (HLO counts the scan body once); the raw count is kept
    alongside so the row stays auditable. Returns ``None`` when neither
    object yields costs — the caller should skip the row, not fabricate
    one."""
    costs = None
    aot = False
    if compiled is not None:
        costs = program_costs(compiled)
        aot = costs is not None
    if costs is None and lowered is not None:
        costs = program_costs(lowered)
    if costs is None:
        return None
    g = max(int(grad_accum), 1)
    info: dict[str, Any] = {
        "program": name,
        "flops": costs["flops"],
        "flops_scaled": costs["flops"] * g,
        "grad_accum": g,
        "aot": aot,
    }
    if "bytes_accessed" in costs:
        info["bytes_accessed"] = costs["bytes_accessed"] * g
    if "transcendentals" in costs:
        info["transcendentals"] = costs["transcendentals"] * g
    mem = program_memory(compiled) if compiled is not None else None
    if mem is not None:
        info.update(mem)
    return info


def flops_drift(xla_flops: float, analytic: float | None) -> float | None:
    """Signed relative drift of the analytic counter against XLA's count
    (positive = analytic overcounts). ``None`` when there is no counter
    to check — an absent counter is not a stale counter."""
    if analytic is None or not xla_flops:
        return None
    return (analytic - xla_flops) / xla_flops


def analyze_train_step(step, state, staged, *, model=None,
                       input_key: str = "tokens", grad_accum: int = 1,
                       allow_compile: bool = False) -> dict[str, Any] | None:
    """The train step's ``anatomy`` row payload.

    ``step`` is ``make_train_step``'s product (or ``compile_cache``'s
    wrapper around it — same attributes): when its ``.aot`` holder carries
    the already-compiled executable, full cost + memory analysis comes for
    free; otherwise the step is lowered (cheap, no compile) for costs
    only, unless ``allow_compile=True`` (tests) pays for the compile to
    get memory too. ``staged`` must be the staged batch the step actually
    runs on (``step.stage``'s output — grad-accum reshape applied), and
    ``grad_accum`` its accumulation factor so the scan-counted-once FLOPs
    scale back to per-step units.

    The analytic cross-check and the activation estimate ride along when
    ``model`` is given: ``analytic_flops`` from the ``flops_counter``
    dispatch (on the UNstaged shapes the counter understands — the staged
    tree works too, ``_rows`` flattens leading dims) and
    ``activation_bytes_est`` from ``transformer_activation_bytes`` for
    transformer geometries. All fail-soft: a model without a counter just
    omits the fields.
    """
    exe = None
    holder = getattr(step, "aot", None)
    if isinstance(holder, Mapping):
        exe = holder.get("exe")
    lowered = None
    if exe is None:
        try:
            lowered = step.jitted.lower(state, staged)
        except Exception:
            return None
        if allow_compile:
            try:
                exe = lowered.compile()
            except Exception:
                exe = None
    info = analyze_program("train_step", compiled=exe, lowered=lowered,
                           grad_accum=grad_accum)
    if info is None:
        return None
    if model is not None:
        from tpudist.telemetry import flops as flops_mod

        analytic = flops_mod.train_step_flops(model, staged,
                                              input_key=input_key)
        if analytic is not None:
            info["analytic_flops"] = float(analytic)
            drift = flops_drift(info["flops_scaled"], analytic)
            if drift is not None:
                info["flops_drift"] = drift
            info["flops_counter"] = getattr(model, "flops_counter", None)
        est = _activation_estimate(model, staged, input_key)
        if est is not None:
            info["activation_bytes_est"] = est
    return info


def _activation_estimate(model, staged, input_key) -> int | None:
    """``memory.py``'s analytic activation bytes for the staged
    microbatch, for side-by-side reading against ``temp_bytes`` in the
    anatomy row. Token-transformer geometries only; anything else (vision,
    index-only batches) returns ``None`` rather than a wrong number."""
    hidden = getattr(model, "hidden_dim", None)
    depth = getattr(model, "depth", None)
    if not hidden or not depth:
        return None
    try:
        shape = staged[input_key].shape
    except (KeyError, TypeError, AttributeError):
        return None
    if len(shape) < 2:
        return None
    seq = int(shape[-1])
    # staged layout is [accum, micro, seq] (grad-accum) or [batch, seq]
    # (flat): either way the dim before seq is the per-pass microbatch —
    # the batch whose activations are live at once
    micro = int(shape[-2])
    try:
        from tpudist.memory import transformer_activation_bytes

        return transformer_activation_bytes(
            micro, seq, int(hidden), int(depth),
            num_heads=getattr(model, "num_heads", None),
            remat_policy=getattr(model, "remat_policy", "none") or "none",
        )
    except Exception:
        return None


class StepTimeRegressionDetector:
    """In-run slowdown sentinel over observed step intervals.

    Feed every measured interval (seconds) to :meth:`observe`. The first
    ``warmup`` intervals are discarded (compile + cache warmness), the
    next ``baseline_steps`` form the post-compile baseline (median), and
    from then on a rolling median over the last ``window`` intervals is
    compared against ``baseline · (1 + threshold)``. After ``patience``
    CONSECUTIVE exceedances :meth:`observe` returns a one-shot payload
    (then never again — one row per run, matching the other one-shot
    telemetry rows); otherwise ``None``. Median-of-window on both sides
    makes a single GC pause or host hiccup invisible — only a sustained
    shift fires.
    """

    def __init__(self, *, warmup: int = 2, baseline_steps: int = 8,
                 window: int = 16, threshold: float = 0.25,
                 patience: int = 3) -> None:
        self.warmup = max(int(warmup), 0)
        self.baseline_steps = max(int(baseline_steps), 1)
        self.window = max(int(window), 1)
        self.threshold = float(threshold)
        self.patience = max(int(patience), 1)
        self.baseline: float | None = None
        self._seen = 0
        self._baseline_buf: list[float] = []
        self._window_buf: list[float] = []
        self._hits = 0
        self.fired = False

    @staticmethod
    def _median(xs: list[float]) -> float:
        s = sorted(xs)
        n = len(s)
        mid = n // 2
        return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])

    def observe(self, interval_s: float) -> dict[str, Any] | None:
        if self.fired or interval_s <= 0.0:
            return None
        self._seen += 1
        if self._seen <= self.warmup:
            return None
        if self.baseline is None:
            self._baseline_buf.append(float(interval_s))
            if len(self._baseline_buf) >= self.baseline_steps:
                self.baseline = self._median(self._baseline_buf)
            return None
        self._window_buf.append(float(interval_s))
        if len(self._window_buf) > self.window:
            self._window_buf.pop(0)
        if len(self._window_buf) < self.window:
            return None
        rolling = self._median(self._window_buf)
        if rolling > self.baseline * (1.0 + self.threshold):
            self._hits += 1
        else:
            self._hits = 0
            return None
        if self._hits < self.patience:
            return None
        self.fired = True
        return {
            "baseline_s": self.baseline,
            "rolling_median_s": rolling,
            "slowdown_pct": round(
                (rolling / self.baseline - 1.0) * 100.0, 2),
            "window": self.window,
            "threshold": self.threshold,
        }
