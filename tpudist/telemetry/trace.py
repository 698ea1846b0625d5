"""The span layer: structured timeline rows riding :class:`TelemetrySink`,
plus the live Prometheus exporter — the two observability surfaces PR 19
adds on top of the existing per-rank JSONL streams (docs/OBSERVABILITY.md
§8).

Everything the subsystem already measures is an *aggregate* — percentile
rows, breakdown averages, heartbeat intervals. A span row is the same
measurement kept *attributed*: one row per interval (or event) with a
start, a duration, and the identity of the thing that spent the time, so
``tools/tracelens.py`` can stitch the per-rank streams into a Chrome/
Perfetto timeline and a per-request latency decomposition.

One row schema for every span (kind ``span``, docs/OBSERVABILITY.md §8)::

    {"v": 1, "t": <wall>, "kind": "span", "rank": R, ["step": S,]
     "name": ..., "cat": "train"|"serve", "ph": "X"|"i",
     "t0": <span-clock start>, "dur_s": <seconds>, <tags...>}

``ph`` follows the Chrome trace-event phases: ``"X"`` is a complete span,
``"i"`` an instant event (``dur_s`` 0). ``t0``/``dur_s`` are on the
emitter's *span clock* — ``time.monotonic`` for train spans (the heartbeat
``mono`` domain) and the :class:`~tpudist.serve.stats.ServeStats` clock
(``time.perf_counter``) for serve spans. Span clocks are never wall time;
the row's own ``t`` (written at span close) is the wall anchor tracelens
uses to place each clock domain on a shared timeline.

Span values are NOT rounded: the serve tracer reuses the exact clock
readings :class:`ServeStats` sampled, so TTFT/TPOT derived from the spans
are bit-equal to the SLO samples (the parity test pins this), and a
request's phase spans telescope exactly — ``queued + prefill + decode +
preempted == total`` to float addition error.

Both features are strictly opt-in: with ``trace`` off and no
``metrics_port``, no tracer or exporter is constructed and every existing
stream stays byte-identical (the standing telemetry contract).

:class:`span` is the one way ``fit`` and the input pipeline mark an
interval of host work: a ``jax.profiler.TraceAnnotation`` always (on the
profiler's clock, next to the device lines, whenever any profiler session
records) and a ``span`` row as well when the run has a :class:`Tracer`.
:class:`Bringup` strings ``fit``'s bring-up into contiguous phases through
it and, with telemetry, keeps JAX's compile events by function name: the
one ``bringup`` row (docs/OBSERVABILITY.md §8).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Mapping

import jax

__all__ = ["Tracer", "ServeTracer", "MetricsExporter", "span", "Bringup",
           "TRAIN_STEP", "FIT_SPANS", "BRINGUP_SPANS", "STEP_SCOPES",
           "BLOCK_SCOPES", "MOE_COUNTERS", "SSD_COUNTERS"]

# the step marker XProf's step-time view groups device work by; the name
# predates the ``fit/...`` spans and the benchmark's gap labels quote it
TRAIN_STEP = "tpudist_train"

# Host spans (:class:`span`) of ``fit`` and the input pipeline, one a step,
# each with what the benchmark reads from it. The call sites spell the
# names themselves (``train.py``, ``telemetry/__init__.py``,
# ``data/loader.py``): this table is the contract, and
# tests/test_telemetry_fit.py and tests/test_benchmark_contract.py hold
# the program and ``benchmarks/`` to it:
FIT_SPANS = {
    "fit/next_batch": "host_span_ms",     # the loop's next(); the input spans nest in it
    "fit/resolve_wait": "idle_by_span",   # blocked on the lagged step's metrics
    "fit/log": "loop_host_ms",
    "fit/health": "loop_host_ms",
    "fit/memory_stats": "loop_host_ms",
    "fit/checkpoint": "loop_host_ms",
    "input/produce": "input_produce_ms",  # producer thread: one next() of the loader
    "input/wait": "host_span_ms",         # main thread, on the producer's queue
    "input/stage": "input_stage_ms",      # sharding one batch and its device_put
    TRAIN_STEP: "loop_host_ms",           # the step's dispatch
}
# Phases of ``fit``'s bring-up (:class:`Bringup`), main thread, in order,
# disjoint and contiguous from ``fit``'s entry to the return of the first
# dispatch; each with the benchmark metrics that read it off the
# ``bringup`` row (all of them move ``setup_s``; docs/OBSERVABILITY.md §8):
BRINGUP_SPANS = {
    # plan / mesh resolution, the loader's probe(), init_input
    "bringup/probe": ("fit_bringup_s",),
    # create_train_state: eval_shape for the shardings, jit(_init) traced,
    # lowered, compiled or loaded, dispatched
    "bringup/init_state": ("init_state_s", "fit_bringup_s"),
    # the init_params device_put tree, refresh_fused_compute
    "bringup/place_params": ("fit_bringup_s",),
    # verify_replicas: the first point that blocks on the device
    "bringup/verify_replicas": ("fit_bringup_s",),
    # build_step, attach_residual, run_meta
    "bringup/build_step": ("fit_bringup_s",),
    # AOT cache load, Checkpointer, restore or reshard, cc.finish
    "bringup/restore": ("fit_bringup_s",),
    # logger, profiler, build_telemetry, the H2D and comm probes, anatomy,
    # the first log_memory: the observer's own cost
    "bringup/telemetry": ("fit_bringup_s",),
    # prefetch_to_mesh start-up and the first next(); fit/next_batch nests
    "bringup/first_batch": ("first_step_s",),
    # the first step(state, batch): trace + lower + compile or load;
    # tpudist_train step 1 nests
    "bringup/first_dispatch": ("first_step_s",),
}
# JAX's monitoring events the bring-up's compile table is built from (the
# ones ``benchmarks/meter.py`` listens to from outside), each with the
# column it fills:
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_TABLE_ROWS = 12  # functions the row names; the rest is ``other``

# Device scopes of the train step (``jax.named_scope``: metadata only) by
# which a trace reader splits a step into passes. The modules that enter
# them (``train.py``, ``optim.py``, ``amp.py``, ``comm.py``,
# ``models/lm_utils.py``, ``models/bert.py``) sit below this one and spell
# the names themselves; tests/test_benchmark_contract.py finds each in a
# lowered step's name stacks:
STEP_SCOPES = {
    "optimizer": "opt_ms",     # the update, around ``tx.update`` and ``apply_updates``
    "grad_clip": "opt_ms",     # the global-norm clip, under ``optimizer``
    "cast": "opt_ms",          # the mixed-precision wrapper's compute copy
    "loss_head": "fwd_ms",     # head and loss; ``transpose(`` ops to ``bwd_ms``
    "grad_exchange": "bwd_ms",  # the explicit reducer's exchange
}

# Device scopes inside a block of ``tpudist.models.zaya`` (``cca_*``), of
# ``tpudist.models.kanana`` (``mla_*``, ``moe_shared``) and of
# ``tpudist.models.sdar`` (``attn_*``, ``bd_attn``) and of
# ``tpudist.models.laguna`` (``lg_*``, ``swa_attn``, ``full_attn``) and of
# ``tpudist.models.nemotron_h`` (``mamba_*``, ``ssd_scan``, ``gqa_*``), the
# ``moe_*`` of ``parallel/ep.py`` ``dropless_moe`` in all five — flax
# module names and ``jax.named_scope``s (metadata only), direct children of
# the block ``h_<n>`` so that a trace reader that folds an op's name stack
# to its first two components (``benchmarks/spans.py`` ``scope_of``) keeps
# them apart — each with the benchmark metric that reads it
# (docs/OBSERVABILITY.md §8; tests/test_zaya.py, tests/test_kanana.py,
# tests/test_sdar.py, tests/test_laguna.py and tests/test_nemotron_h.py
# hold the models to them; ``moe_topk_ms`` reads the four ``moe_ms`` stages
# in the Kanana-2 cell, ``bd_moe_ms`` in the SDAR cell; ``block_scope_ms``
# is no metric but the traced run's printed table of every stage):
BLOCK_SCOPES = {
    "cca_proj": "cca_mix_ms",    # CCA's down-projection (q, k, v_a, v_b)
    "cca_mix": "cca_mix_ms",     # q-k mean, convolutions, norms, rotary, value shift
    "cca_attn": "cca_attn_roofline",  # the attention call; its kernel is ``cca_attn.<k>``
    "cca_out": "cca_mix_ms",     # CCA's up-projection
    "moe_router": "moe_ms",      # router MLP, softmax, selection
    "moe_dispatch": "moe_ms",    # sort by expert, group sizes, gather
    "moe_experts": "moe_ms",     # the grouped products and the activation (also ``expert_gemm_roofline``)
    "moe_combine": "moe_ms",     # un-sort, gate
    "moe_shared": "moe_shared_ms",  # the shared expert, on every token
    "mla_q": "mla_proj_ms",      # MLA's query projection
    "mla_kv_down": "mla_proj_ms",  # down to the key/value latent and the one rotary key
    "mla_kv_up": "mla_proj_ms",  # the latent up to each head's k_nope and v
    "mla_rope": "mla_proj_ms",   # rotary on q_rope / k_rope; q and k put together
    "mla_attn": "mla_attn_roofline",  # the attention call; its kernel is ``mla_attn.<k>``
    "mla_out": "mla_proj_ms",    # MLA's output projection
    "attn_qkv": "bd_proj_ms",    # SDAR's fused q/k/v projection
    "attn_qk_norm": "bd_proj_ms",  # RMSNorm of every head's q and k
    "attn_rope": "bd_proj_ms",   # rotary on q and k at the rows' positions
    "bd_attn": "bd_attn_roofline",  # the masked attention call; its kernel is ``bd_attn.<k>``
    "attn_out": "bd_proj_ms",    # SDAR's output projection
    "lg_qkv": "lg_proj_ms",      # Laguna's fused q/k/v projection (either kind)
    "lg_gate": "lg_proj_ms",     # the output gate's projection and sigmoid
    "lg_rope": "lg_proj_ms",     # rotary: YaRN on half a head (full), plain (sliding)
    "swa_attn": "swa_attn_roofline",  # the sliding-window call; its kernel is ``swa_attn.<k>``
    "full_attn": "full_attn_roofline",  # the full causal call; its kernel is ``full_attn.<k>``
    "lg_out": "lg_proj_ms",      # the gate's product and the output projection
    "mamba_in_proj": "mamba_mix_ms",  # Mamba-2's in projection (z, xBC, dt)
    "mamba_conv": "mamba_mix_ms",  # the causal depthwise convolution, its bias, SiLU
    "ssd_scan": "ssd_roofline",  # softplus, A, the chunked scan kernel and its backward
    "mamba_gate_norm": "mamba_mix_ms",  # y x silu(z), the RMSNorm over groups
    "mamba_out_proj": "mamba_mix_ms",  # Mamba-2's out projection
    "gqa_qkv": "block_scope_ms",  # Nemotron-H's fused q/k/v projection
    "gqa_attn": "block_scope_ms",  # its causal attention call; the kernel is ``gqa_attn.<k>``
    "gqa_out": "block_scope_ms",  # its output projection
}
# Counters the dropless expert layer sows into ``moe_stats`` (a ``moe`` row
# field ``h_<n>/<counter>`` a logged step), each with its metric:
MOE_COUNTERS = {
    "tokens": "expert_gemm_roofline",  # rows routed to each held expert
    "held_share": "expert_gemm_roofline",  # share of rows whose expert is held (printed beside the expected)
    "load_max_over_mean": "expert_load_max_over_mean",  # over the held experts
    # rows of the chunks that ran / T·k (``ep.row_chunks``): 1 / n_chunks on
    # an even step, more where the router paid for a further chunk
    "row_share_computed": "moe_ms",
}
# Counters a Mamba-2 block sows into ``moe_stats`` beside them (the same
# ``moe`` rows), each with its metric:
SSD_COUNTERS = {
    # mean over heads and chunks of sum_chunk dt·A: the log of what a state
    # keeps across one chunk (printed beside the scan's roofline)
    "ssd_log_carry": "ssd_roofline",
}


class Tracer:
    """Span emitter for the training loop (and any host-side code that
    thinks in intervals): ``span`` writes a completed interval, ``instant``
    a point event. Spans are stamped with ``process_index``/``generation``
    so multi-rank, multi-generation streams align (the same identity pair
    heartbeat rows carry), and ``t0`` is on ``time.monotonic`` — wall
    clocks skew across hosts, monotonic deltas do not."""

    def __init__(self, sink, *, cat: str = "train", process_index: int = 0,
                 generation: int = 0, clock=time.monotonic):
        self.sink = sink
        self.cat = cat
        self.process_index = int(process_index)
        self.generation = int(generation)
        self._clock = clock

    def span(self, name: str, dur_s: float, *, t0: float | None = None,
             step: int | None = None, **tags) -> dict:
        """One completed interval. ``t0`` defaults to ``now - dur_s`` —
        the caller measured a duration and is reporting it at close, the
        common shape in ``fit()`` (interval_s, checkpoint save time)."""
        if t0 is None:
            t0 = self._clock() - dur_s
        return self.sink.write(
            "span", step, name=name, cat=self.cat, ph="X",
            t0=float(t0), dur_s=float(dur_s),
            process_index=self.process_index, generation=self.generation,
            **tags,
        )

    def instant(self, name: str, *, step: int | None = None, **tags) -> dict:
        """One point event (repair, reshard, anomaly, probe)."""
        return self.sink.write(
            "span", step, name=name, cat=self.cat, ph="i",
            t0=float(self._clock()), dur_s=0.0,
            process_index=self.process_index, generation=self.generation,
            **tags,
        )


class span:
    """One interval of host work, one call per site, two sinks.

    Always a ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` for
    ``marks_step``): about a microsecond while no profiler session is on,
    and an event on the profiler's own clock — the one the device lines of
    the same ``.xplane.pb`` are aligned to — while one is, whoever started
    it. ``step`` and ``tags`` become the event's stats (``step_num``).

    With ``tracer`` (the run's :class:`Tracer`, ``TelemetryConfig(trace=
    True)``) the close additionally writes the ``span`` row, named ``row``
    where the stream's name differs from the annotation's. The two clocks
    (docs/OBSERVABILITY.md §8): an xplane host event starts
    ``line.timestamp_ns + offset_ps / 1000`` ns after the trace's
    ``profile_start_time`` (unix ns, on the ``Task Environment`` plane); a
    row's start on that axis is ``(t - dur_s) * 1e9 - profile_start_time``.

    A ``next()`` that finds its stream at an end is no piece of work for a
    step: :meth:`ends_stream` (called for a ``StopIteration`` that passes
    through) tags the event ``end=1``, which readers leave out, and writes
    no row.
    """

    __slots__ = ("name", "step", "tags", "tracer", "row", "_annotation", "_t0")

    def __init__(self, name: str, *, step: int | None = None,
                 tracer: Tracer | None = None, row: str | None = None,
                 marks_step: bool = False, **tags):
        self.name = name
        self.step = step
        self.tags = tags
        self.tracer = tracer
        self.row = row or name
        kind = (jax.profiler.StepTraceAnnotation if marks_step
                else jax.profiler.TraceAnnotation)
        stats = tags if step is None else {"step_num": int(step), **tags}
        self._annotation = kind(name, **stats)

    def __enter__(self):
        if self.tracer is not None:
            self._t0 = self.tracer._clock()
        self._annotation.__enter__()
        return self

    def ends_stream(self) -> None:
        self._annotation.set_metadata(end=1)
        self.tracer = None

    def __exit__(self, *exc):
        if exc[0] is StopIteration:
            self.ends_stream()
        self._annotation.__exit__(*exc)
        if self.tracer is not None:
            self.tracer.span(
                self.row, self.tracer._clock() - self._t0, t0=self._t0,
                step=self.step, **self.tags,
            )
        return False


def _union_s(intervals, since: float, until: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[since, until]`` (trace, lowering and compile-or-load nest)."""
    total, covered_to = 0.0, since
    for start, end in sorted(intervals):
        start, end = max(start, covered_to), min(end, until)
        if end > start:
            total += end - start
            covered_to = end
    return total


class Bringup:
    """``fit``'s bring-up account: contiguous phases and, with telemetry,
    a compile table (``BRINGUP_SPANS``; docs/OBSERVABILITY.md §8).

    :meth:`enter` closes the open phase and opens the next, each a
    :class:`span` — a ``jax.profiler.TraceAnnotation`` always. With
    ``observe`` (the run has telemetry) the spans report to this object as
    they would to a :class:`Tracer`: it reads ``time.monotonic`` (the
    tracer's clock) once a boundary, so a phase starts on the reading its
    predecessor ended on, and the first on the entry reading; it keeps
    ``(name, t0, dur_s)`` and, from :meth:`attach` on, hands each to the
    run's tracer as a ``span`` row. With ``observe`` only, listeners on
    JAX's compile events (``COMPILE_EVENTS``, ``CACHE_EVENTS``: the ones
    the benchmark's meter trusts, with the ``fun_name`` kept) fill a table
    by function name. :meth:`finish` — the first dispatch has returned —
    closes the last phase and returns the ``bringup`` row's fields; a
    backend compile or cache load that ends later is a recompile and goes
    to ``on_recompile(fun, trace_s=, lower_s=, backend_s=)``, from the
    thread that compiled. :meth:`close` (``fit``'s ``finally``)
    unregisters the listeners. Without ``observe``: no buffer, no
    listener, no row.
    """

    def __init__(self, *, observe: bool, on_recompile=None):
        self.phases: list[tuple[str, float, float]] | None = None
        self._open: span | None = None
        self._tracer: Tracer | None = None
        self._listening = observe
        if not observe:
            return
        self.phases = []
        self.t_entry = self._now = time.monotonic()
        self.t_entry_perf = time.perf_counter()
        self._on_recompile = on_recompile
        self._lock = threading.Lock()
        self._finished = False
        self._funs: dict[str, dict] = {}
        self._intervals: dict[str, list] = {c: [] for c in
                                            COMPILE_EVENTS.values()}
        self._cache = {"hits": 0, "misses": 0, "retrieval_s": 0.0}
        # hits and misses come without a name, inside the backend interval
        # of the function they belong to: held by thread until it ends
        self._unclaimed: dict[int, dict] = {}
        self._late: dict[str, dict] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    # -- phases: what :class:`span` asks of a tracer ------------------------

    def _clock(self) -> float:
        return self._now

    def span(self, name: str, dur_s: float, *, t0: float, **_) -> None:
        self.phases.append((name, t0, dur_s))
        if self._tracer is not None:
            self._tracer.span(name, dur_s, t0=t0)

    def enter(self, name: str) -> None:
        self.close_phase()
        self._open = span(
            name, tracer=self if self.phases is not None else None
        ).__enter__()

    def close_phase(self) -> None:
        if self._open is not None:
            if self.phases is not None:
                self._now = time.monotonic()
            self._open.__exit__(None, None, None)
            self._open = None

    def attach(self, tracer: Tracer | None) -> None:
        """The sink is up: replay the phases closed before it was."""
        if tracer is not None and self.phases is not None:
            for name, t0, dur_s in self.phases:
                tracer.span(name, dur_s, t0=t0)
            self._tracer = tracer

    # -- the compile table --------------------------------------------------

    def _duration(self, event: str, seconds: float, fun_name: str = "?",
                  **_) -> None:
        column = COMPILE_EVENTS.get(event)
        if column is None:
            if event == CACHE_RETRIEVAL_EVENT:
                with self._lock:
                    self._cache["retrieval_s"] += seconds
            return
        end = time.monotonic()  # the listener fires as the event ends
        # the trace event says ``step_fn``, lowering and the backend
        # ``jit(step_fn)``: one row a function
        fun_name = fun_name.removeprefix("jit(").removesuffix(")")
        recompiled = None
        with self._lock:
            table = self._late if self._finished else self._funs
            row = table.get(fun_name)
            if row is None:
                row = table[fun_name] = {
                    "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                    "hits": 0, "misses": 0, "calls": dict.fromkeys(
                        COMPILE_EVENTS.values(), 0)}
            row[column] += seconds
            row["calls"][column] += 1
            if column == "backend_s":
                for key, count in self._unclaimed.pop(
                        threading.get_ident(), {}).items():
                    row[key] += count
            if not self._finished:
                self._intervals[column].append((end - seconds, end))
            elif column == "backend_s":
                # the traces that nest in this compile end with it
                recompiled = row
                self._late.clear()
        if recompiled is not None and self._on_recompile is not None:
            self._on_recompile(
                fun_name, trace_s=recompiled["trace_s"],
                lower_s=recompiled["lower_s"],
                backend_s=recompiled["backend_s"])

    def _event(self, event: str, **_) -> None:
        key = CACHE_EVENTS.get(event)
        if key is not None:
            with self._lock:
                self._cache[key] += 1
                held = self._unclaimed.setdefault(threading.get_ident(), {})
                held[key] = held.get(key, 0) + 1

    def finish(self) -> dict | None:
        """The first dispatch has returned: the ``bringup`` row's fields
        (``None`` without ``observe``)."""
        self.close_phase()
        if self.phases is None:
            return None
        with self._lock:
            self._finished = True
            funs, intervals = self._funs, self._intervals
            self._funs = self._intervals = None
        since, until = self.t_entry, self._now
        traced = intervals["trace_s"] + intervals["lower_s"]
        trace_lower = _union_s(traced, since, until)
        # the backend seconds outside every trace and lowering (a constant
        # folded while tracing compiles inside the trace's interval), so
        # that the two add up to the union of all three
        backend = _union_s(
            traced + intervals["backend_s"], since, until) - trace_lower
        columns = ("n", "trace_s", "lower_s", "backend_s", "hits", "misses")
        for row in funs.values():
            # times the function went through its most repeated stage
            row["n"] = max(row.pop("calls").values())
        table = sorted(
            ({"fun": fun, **{c: row[c] for c in columns}}
             for fun, row in funs.items()),
            key=lambda r: -(r["trace_s"] + r["lower_s"] + r["backend_s"]))
        rest = table[COMPILE_TABLE_ROWS:]
        table = table[:COMPILE_TABLE_ROWS]
        if rest:
            table.append({"fun": "other", **{
                c: sum(r[c] for r in rest) for c in columns}})
        return {
            "t_entry": self.t_entry, "t_entry_perf": self.t_entry_perf,
            "phases": [[name, t0 - since, dur_s]
                       for name, t0, dur_s in self.phases],
            "total_s": until - since,
            "trace_lower_s": trace_lower, "backend_s": backend,
            "cache_hits": self._cache["hits"],
            "cache_misses": self._cache["misses"],
            "cache_retrieval_s": self._cache["retrieval_s"],
            "compile": table,
        }

    def close(self) -> None:
        self.close_phase()
        if self._listening:
            self._listening = False
            jax.monitoring.unregister_event_duration_listener(self._duration)
            jax.monitoring.unregister_event_listener(self._event)


class _Req:
    """Per-request span state: the open phase boundaries and the tag
    accumulators the terminal ``request`` span reports."""

    __slots__ = (
        "lane", "t_submit", "t_admit", "t_first", "t_preempt", "seg_t0",
        "decode_s", "preempt_s", "slot", "preempts", "prefix_hit",
        "prefix_lookup", "spec_drafted", "spec_accepted",
    )

    def __init__(self, lane: int, t_submit: float):
        self.lane = lane
        self.t_submit = t_submit
        self.t_admit: float | None = None
        self.t_first: float | None = None
        self.t_preempt: float | None = None
        self.seg_t0: float | None = None  # open decode segment's start
        self.decode_s = 0.0
        self.preempt_s = 0.0
        self.slot: int | None = None
        self.preempts = 0
        self.prefix_hit: int | None = None
        self.prefix_lookup: int | None = None
        self.spec_drafted = 0
        self.spec_accepted = 0


class ServeTracer:
    """Per-request lifecycle spans for :class:`tpudist.serve.ServeEngine`.

    The engine drives one hook per scheduler transition, passing the EXACT
    clock reading its :class:`ServeStats` call returned — the tracer never
    reads the clock for a phase boundary itself, so span-derived TTFT/TPOT
    reconcile bit-equal with the SLO samples.

    A request's phases telescope over its lifetime::

        queued    submit → first admission (prefill dispatch)
        prefill   first admission → first token
        decode    first token → retire, minus the preempted gaps
        preempted each eviction → its re-admission (the queue wait the
                  preemption cost; the replay prefill compute lands in
                  the decode segment that follows — it produces tokens)

    so ``queued + prefill + decode + preempted == retire - submit``
    exactly. Each closed phase is a ``span`` row; retire additionally
    emits the terminal ``request`` span carrying the full decomposition
    plus the request's identity tags (lane, slot, prefix-cache outcome,
    speculative counts, preempt count)."""

    def __init__(self, sink, *, rank: int = 0):
        self.sink = sink
        self.rank = rank
        self._req: dict[int, _Req] = {}

    # -- emission ---------------------------------------------------------

    def _span(self, name: str, t0: float, t1: float, *, step=None, **tags):
        self.sink.write(
            "span", step, name=name, cat="serve", ph="X",
            t0=float(t0), dur_s=float(t1 - t0), **tags,
        )

    def _instant(self, name: str, t: float, *, step=None, **tags):
        self.sink.write(
            "span", step, name=name, cat="serve", ph="i",
            t0=float(t), dur_s=0.0, **tags,
        )

    # -- request lifecycle (engine-driven) --------------------------------

    def on_submit(self, rid: int, t: float, *, lane: int = 0) -> None:
        self._req[rid] = _Req(lane, t)

    def on_admit(self, rid: int, t: float, *,
                 pool_occupancy: float | None = None) -> None:
        """First admission: the queued phase closes, prefill begins."""
        st = self._req.get(rid)
        if st is None or st.t_admit is not None:
            return
        st.t_admit = t
        self._span("queued", st.t_submit, t, rid=rid, lane=st.lane,
                   pool_occupancy=pool_occupancy)

    def on_first_token(self, rid: int, t: float, *,
                       slot: int | None = None,
                       prefix_hit: int | None = None,
                       prefix_lookup: int | None = None) -> None:
        """Prefill produced the first token; the decode phase opens."""
        st = self._req.get(rid)
        if st is None or st.t_first is not None:
            return
        st.t_first = t
        st.slot = slot
        st.prefix_hit = prefix_hit
        st.prefix_lookup = prefix_lookup
        st.seg_t0 = t
        self._span("prefill", st.t_admit if st.t_admit is not None else t, t,
                   rid=rid, slot=slot, prefix_hit_blocks=prefix_hit,
                   prefix_lookup_blocks=prefix_lookup)

    def on_preempt(self, rid: int, t: float, *,
                   pool_occupancy: float | None = None) -> None:
        """Eviction back to the queue: the open decode segment closes,
        the preempted phase opens."""
        st = self._req.get(rid)
        if st is None:
            return
        if st.seg_t0 is not None:
            st.decode_s += t - st.seg_t0
            self._span("decode", st.seg_t0, t, rid=rid, slot=st.slot)
            st.seg_t0 = None
        st.t_preempt = t
        st.preempts += 1
        self._instant("preempt", t, rid=rid, slot=st.slot,
                      pool_occupancy=pool_occupancy)
        st.slot = None

    def on_resume(self, rid: int, t: float, *, slot: int | None = None,
                  pool_occupancy: float | None = None) -> None:
        """Re-admission of a preempted request: the preempted phase
        closes, decode resumes (the replay prefill runs inside the new
        decode segment — it is re-producing the request's progress)."""
        st = self._req.get(rid)
        if st is None or st.t_preempt is None:
            return
        st.preempt_s += t - st.t_preempt
        self._span("preempted", st.t_preempt, t, rid=rid,
                   pool_occupancy=pool_occupancy)
        st.t_preempt = None
        st.seg_t0 = t
        st.slot = slot

    def set_slot(self, rid: int, slot: int) -> None:
        """The pool assigned (or reassigned) the request's slot — recorded
        after the first-token hook, which fires before insertion."""
        st = self._req.get(rid)
        if st is not None:
            st.slot = slot

    def on_spec(self, rid: int, drafted: int, accepted: int) -> None:
        """One verify sweep's outcome for THIS request (the per-request
        split of ``ServeStats.on_spec``'s batch totals)."""
        st = self._req.get(rid)
        if st is not None:
            st.spec_drafted += int(drafted)
            st.spec_accepted += int(accepted)

    def on_done(self, rid: int, t: float, n_tokens: int, *,
                pool_occupancy: float | None = None) -> None:
        """Retire: close the open decode segment and emit the terminal
        ``request`` span with the exact phase decomposition."""
        st = self._req.pop(rid, None)
        if st is None:
            return
        if st.seg_t0 is not None:
            st.decode_s += t - st.seg_t0
            self._span("decode", st.seg_t0, t, rid=rid, slot=st.slot,
                       tokens=n_tokens)
        queued_s = (
            (st.t_admit - st.t_submit) if st.t_admit is not None else 0.0
        )
        prefill_s = (
            (st.t_first - st.t_admit)
            if (st.t_first is not None and st.t_admit is not None) else 0.0
        )
        ttft_s = (
            (st.t_first - st.t_submit) if st.t_first is not None else None
        )
        tpot_s = (
            (t - st.t_first) / (n_tokens - 1)
            if (st.t_first is not None and n_tokens > 1) else None
        )
        self._span(
            "request", st.t_submit, t,
            rid=rid, lane=st.lane, slot=st.slot, tokens=n_tokens,
            queued_s=queued_s, prefill_s=prefill_s,
            decode_s=st.decode_s, preempt_s=st.preempt_s,
            ttft_s=ttft_s, tpot_s=tpot_s, preempts=st.preempts,
            prefix_hit_blocks=st.prefix_hit,
            prefix_lookup_blocks=st.prefix_lookup,
            spec_drafted=st.spec_drafted, spec_accepted=st.spec_accepted,
            pool_occupancy=pool_occupancy,
        )

    # -- scheduler ticks --------------------------------------------------

    def on_tick(self, step: int, t0: float, t1: float, *, active: int,
                queue_depth: int, emitted: int) -> None:
        """One scheduler tick (admit + dispatch + process): the decode
        timeline's backbone — token counts per tick, batch occupancy."""
        self._span("tick", t0, t1, step=step, active=active,
                   queue_depth=queue_depth, tokens=emitted)


def _metric_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() or ch == "_") else "_")
    s = "".join(out)
    return ("_" + s) if s[:1].isdigit() else s


class MetricsExporter:
    """Opt-in live scrape surface: a stdlib ``ThreadingHTTPServer`` on a
    daemon thread serving Prometheus text exposition at ``/metrics``.

    Two sources, both host-side only (never a device sync):

    - **pushed gauges** — ``set(step=..., mfu=...)``; the training loop
      pushes the scalars it already fetched for its telemetry rows.
    - **pull collectors** — ``add_collector(fn)``; ``fn()`` runs AT SCRAPE
      TIME and returns a mapping (the serving engine registers a
      ``ServeStats.snapshot()`` reader, so request traffic pays zero
      per-token cost for the endpoint).

    ``port=0`` binds an ephemeral port (tests); the bound port is
    ``self.port``. ``None`` values are skipped (a metric with no sample
    yet is absent, not 0 — absence is what alerting rules can see).
    Metrics are namespaced ``tpudist_``; names ending ``_total`` are typed
    ``counter``, everything else ``gauge``."""

    def __init__(self, port: int = 0, *, host: str = "0.0.0.0",
                 namespace: str = "tpudist"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.namespace = namespace
        self._lock = threading.Lock()
        self._gauges: dict[str, float] = {}
        self._collectors: list[Callable[[], Mapping]] = []
        exporter = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — http.server's contract
                if self.path.split("?")[0] not in ("/metrics", "/"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = exporter.render().encode("utf-8")
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # silence per-scrape stderr spam
                pass

        self._server = ThreadingHTTPServer((host, port), _Handler)
        self._server.daemon_threads = True
        self.port = int(self._server.server_address[1])
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="tpudist-metrics",
            daemon=True,
        )
        self._thread.start()

    def set(self, **gauges) -> None:
        """Merge pushed gauge values (``None`` clears a key)."""
        with self._lock:
            for k, v in gauges.items():
                if v is None:
                    self._gauges.pop(k, None)
                else:
                    self._gauges[k] = v

    def add_collector(self, fn: Callable[[], Mapping]) -> None:
        """Register a scrape-time reader; later collectors win key ties."""
        self._collectors.append(fn)

    def render(self) -> str:
        with self._lock:
            merged: dict[str, float] = dict(self._gauges)
        for fn in list(self._collectors):
            try:
                merged.update({
                    k: v for k, v in dict(fn()).items() if v is not None
                })
            except Exception:
                continue  # a scrape must never take the server down
        lines = []
        for key in sorted(merged):
            v = merged[key]
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            name = f"{self.namespace}_{_metric_name(key)}"
            kind = "counter" if name.endswith("_total") else "gauge"
            lines.append(f"# HELP {name} tpudist live metric: {key}")
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} {float(v):g}")
        return "\n".join(lines) + "\n"

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
