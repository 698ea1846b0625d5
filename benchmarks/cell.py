"""One run of one cell: look for the chip, build the cell from its data
files, make ONE call of ``tpudist.train.fit`` with the harness's loader and
recorder as its only hooks, reduce what it left to metrics, compare its
first steps with the plain reference, print the result."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time

from benchmarks import compare, window as window_lib

WORK_DIR = ".bench_work"  # inside the checkout, in .gitignore
TRACE_TAIL_S = 5.0  # the profiler runs over the window's last seconds


class Refused(Exception):
    """The run cannot be a measurement: no result line, exit code != 0."""


def say(**row) -> None:
    """One informational JSON line, before the last."""
    print(json.dumps(row), flush=True)


def load_cell(bench: dict, name: str, root: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    traffic_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.join(root, entry["file"]))), "traffic")
    with open(os.path.join(traffic_dir, cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def find_devices(chips: int):
    import jax

    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if report["platform"] != "tpu" or report["count"] < chips:
        raise Refused(f"needs {chips} TPU chip(s); JAX reports {report}")
    return devices[:chips], report


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def telemetry_rows(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def reference_first_steps(family, config, traffic, shapes, seed, batches,
                          precision="float32") -> dict:
    """The plain reference over the same seeded weights and the same first
    batches, on one device, in blocks of rows."""
    import jax

    from benchmarks import weights
    from benchmarks.reference import first_steps

    params = weights.generate(shapes, seed)
    flat = dict(zip(weights.leaf_paths(params),
                    jax.tree_util.tree_leaves(params)))
    del params
    return first_steps.first_steps(
        family.reference_loss_sum(config, precision), flat, batches,
        config["recipe"],
        block_rows=traffic.get("reference_block_rows", 2),
    )


def program_first_steps(recorder, tap, config, n: int) -> dict:
    moments, change = tap.readings()
    b1 = config["recipe"]["optimizer"]["b1"]
    return {
        "losses": [loss for _, loss, _ in recorder.rows[:n]],
        # Adam's first moment after one step is (1 - b1) x the gradient the
        # optimizer got (after clipping)
        "grad_norms": {k: v / (1.0 - b1) for k, v in moments.items()},
        "change_norms": change,
    }


def run(bench: dict, name: str, *, seed: int, seconds: float, trace: bool,
        root: str, t_start: float, devices=None, report=None,
        limits: dict | None = None, on_compared=None) -> dict:
    """Everything but the look for the chip. Returns the result object.
    ``on_compared(program, reference, again, batches)`` is for the tools that set
    and test the limits: ``again(precision, batches)`` runs the reference
    once more, over other batches or in another precision."""
    import jax
    import numpy as np

    from benchmarks import meter as meter_lib, tap as tap_lib, weights

    cell, config, traffic = load_cell(bench, name, root)
    chips = cell["chips"]
    if devices is None:
        devices, report = find_devices(chips)
    meter = meter_lib.CompileMeter()
    from tpudist import mesh as mesh_lib
    from tpudist.train import fit
    from tpudist.utils.cache import place_compile_cache

    cache_dir = place_compile_cache()
    work = os.path.join(root, WORK_DIR, name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    mesh = (mesh_lib.create_mesh() if len(jax.devices()) == chips
            else mesh_lib.create_mesh(devices=devices))
    built = family.build(config, traffic, mesh)
    say(platform=report["platform"], device_kind=report["kind"],
        device_count=report["count"], chips_used=chips,
        mesh={k: v for k, v in mesh.shape.items() if v > 1},
        attn=f"{config['recipe']['attn']} -> {built['attn']}",
        compile_cache=cache_dir)

    n_check = traffic.get("check_steps", 3)
    win = window_lib.Window(traffic["warmup_steps"], seconds)
    loader = window_lib.WindowLoader(
        family.make_stream(config, traffic, chips), seed, win,
        keep_first=n_check, annotate=trace,
    )
    tracer = None
    if trace:
        from benchmarks import xplane

        tracer = xplane.TraceControl(
            os.path.join(work, "trace"), win,
            tail_s=min(TRACE_TAIL_S, 0.5 * seconds),
        )
    recorder = window_lib.make_recorder(
        win, log_every=10 if trace else 5,
        on_step=tracer.on_step if tracer else None, annotate=trace,
    )
    telemetry = False
    if trace:
        from tpudist.telemetry import TelemetryConfig

        # rows only: nothing that changes the compiled step
        telemetry = TelemetryConfig(
            health_metrics=False, guard_nonfinite=False, sentry=False,
            capture_on_anomaly=False, breakdown=True, mfu=False,
            run_report=False, jsonl_dir=work,
        )
    replicated = mesh_lib.replicated_sharding(mesh)
    init = weights.generate(built["param_shapes"], seed, replicated)
    # a second copy for the tap: ``fit`` may alias ``init`` into its state
    # and the first step then donates it
    tap = tap_lib.StepTap(
        weights.generate(built["param_shapes"], seed, replicated),
        change_after=n_check,
    )
    job = "bench"
    try:
        with tap.installed():
            state, _ = fit(
                built["model"], built["tx"], loader, epochs=1, mesh=mesh,
                # fit's own seed only keys the init it throws away (and
                # dropout, which is off): one value, so that program caches
                seed=0, job_id=job, profile=False,
                log_dir=work, telemetry=telemetry, metrics_logger=recorder,
                init_params=init, **built["fit"],
            )
    finally:
        if tracer is not None:
            tracer.close()
    del init
    t_end = time.perf_counter()

    if win.opened_at is None:
        raise Refused(f"the window never opened ({len(recorder.rows)} steps)")
    in_window = meter.compiles_between(win.opened_at, recorder.rows[-1][2])
    if in_window:
        raise Refused(f"{in_window} compilation(s) inside the window")
    rows = recorder.rows
    if tracer is not None and tracer.started_at is not None:
        # the rate of a traced run is that of the window's untraced head
        rows = [r for r in rows if r[2] <= tracer.started_at]
    e2e = window_lib.window_metrics(
        rows, win,
        tokens_per_step=family.tokens_per_step(traffic, chips), chips=chips,
    )
    e2e["setup_s"] = win.opened_at - t_start
    peak = memory_peak(devices)
    losses = [loss for _, loss, _ in recorder.rows]
    failed = sum(1 for s, loss, _ in recorder.rows
                 if s > win.warmup_steps and not np.isfinite(loss))
    say(steps_resolved=len(recorder.rows), window_steps=e2e["steps"],
        window_s=e2e["window_s"], step_ms_median=e2e["step_ms_median"],
        step_ms_max=e2e["step_ms_max"], cache_hits=meter.hits,
        cache_misses=meter.misses,
        compile_s=meter.compile_seconds(t_start, win.opened_at),
        peak_bytes_in_use=peak, fit_s=t_end - t_start)
    say(loss_trace=[round(x, 5) for x in losses[:8]]
        + ["..."] + [round(x, 5) for x in losses[-4:]])

    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "chips": chips,
        "family": family, "recorder": recorder, "window": win, "e2e": e2e,
        "meter": meter, "t_start": t_start, "root": root,
        "device_kind": report["kind"],
        "telemetry_rows": telemetry_rows(
            os.path.join(work, f"{job}_telemetry_0.jsonl")),
        "trace": None,
    }
    program = program_first_steps(recorder, tap, config, n_check)
    del state, tap
    # only now, with the peak read and the trainer's state freed
    t_ref = time.perf_counter()
    reference = reference_first_steps(
        family, config, traffic, built["param_shapes"], seed,
        loader.first_batches,
    )
    numbers = compare.compare_first_steps(program, reference)
    if on_compared is not None:
        on_compared(program, reference, lambda precision, batches:
                    reference_first_steps(family, config, traffic,
                                          built["param_shapes"], seed,
                                          batches, precision),
                    loader.first_batches)
    if limits is None:
        with open(os.path.join(
                root, os.path.dirname(bench["command"][1]), "limits",
                name + ".json")) as f:
            limits = json.load(f)["limits"]
    correct, checks = compare.judge(numbers, limits)
    where = dict(numbers["_where"])
    where["dead_leaves"] = len(where["dead_leaves"])
    say(reference_s=time.perf_counter() - t_ref, where=where,
        program_losses=program["losses"], reference_losses=reference["losses"])

    device = dict(report, memory_peak_bytes=peak)
    result = {"correct": bool(correct and not failed),
              "attempted": e2e["steps"], "failed": failed}
    if trace:
        from benchmarks import layers, xplane

        reduced = xplane.reduce_dir(tracer.directory, chips=chips)
        ctx["trace"] = reduced
        say(traced_steps=reduced["steps"],
            custom_call_ops_per_step=len(reduced["custom_call_ops"]),
            collective_ops_per_step=len(reduced["collective_ops"]))
        result["metrics"] = layers.read_all(bench, cell, ctx)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["device"] = device
        result["breakdown"] = reduced["breakdown"]
        shutil.rmtree(tracer.directory, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": units[m["name"]]}
            for m in bench["end_to_end"]
            if name in m.get("workloads", [name])
        }
        result["device"] = device
    result["checks"] = checks
    return result


def main(bench, name, *, seed, seconds, trace, root, t_start) -> int:
    try:
        result = run(bench, name, seed=seed, seconds=seconds, trace=trace,
                     root=root, t_start=t_start)
    except Refused as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    for check, row in result["checks"].items():
        limit = "not compared" if row["limit"] is None else row["limit"]
        print(f"check {check}: {row['value']} (limit {limit})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
