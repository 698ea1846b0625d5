"""Seconds of ``fit``'s bring-up before its loop: the seven phases
``bringup/probe`` … ``bringup/telemetry`` of the program's ``bringup``
telemetry row (``tpudist/telemetry/trace.py`` ``BRINGUP_SPANS``). Prints one
informational line: the phases, the compile table by function, and the
remainder ``setup_s − pre_fit_s − fit_bringup_s − first_step_s`` (the first
step's run, the other warm-up steps, the tap's small compiles), so that the
numbers are seen to add up to ``setup_s``; and the program's union of trace,
lowering and backend seconds beside the harness's own ``compile_s`` over
``fit``'s interval. Nothing where the program writes no such row."""

from benchmarks import cell

SPANS = ("bringup/probe", "bringup/init_state", "bringup/place_params",
         "bringup/verify_replicas", "bringup/build_step", "bringup/restore",
         "bringup/telemetry")
FIRST_STEP = ("bringup/first_batch", "bringup/first_dispatch")


def bringup_row(ctx):
    rows = [r for r in ctx["telemetry_rows"] if r.get("kind") == "bringup"]
    return rows[0] if rows else None


def phase_s(ctx, names):
    row = bringup_row(ctx)
    if row is None:
        return None
    return sum(dur_s for name, _, dur_s in row["phases"] if name in names)


def read(ctx):
    row = bringup_row(ctx)
    if row is None:
        return None
    value = phase_s(ctx, SPANS)
    first_step = phase_s(ctx, FIRST_STEP)
    entry = row["t_entry_perf"]
    pre_fit = entry - ctx["t_start"]
    setup = ctx["e2e"]["setup_s"]
    cell.say(
        bringup_phases={name: dur_s for name, _, dur_s in row["phases"]},
        compile_table=row["compile"], setup_s=setup, pre_fit_s=pre_fit,
        fit_bringup_s=value, first_step_s=first_step,
        remainder_s=setup - pre_fit - value - first_step,
        trace_lower_s=row["trace_lower_s"], backend_s=row["backend_s"],
        # the harness's listener on the same events, over fit's interval
        compile_s_in_fit=ctx["meter"].compile_seconds(
            entry, entry + row["total_s"]),
        cache_hits=row["cache_hits"], cache_misses=row["cache_misses"],
        cache_retrieval_s=row["cache_retrieval_s"],
    )
    return value
