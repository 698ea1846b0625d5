"""Per-layer metrics: one small reader each, in ``layer_metrics/<name>.py``,
found by the metric's name in BENCHMARK.json. A reader takes the run's
context (recorder, window, telemetry rows, reduced trace, meter, data
files) and returns a number, or ``None`` where it finds nothing to read —
the metric is then left out of the line."""

from __future__ import annotations

import importlib


def read_all(bench: dict, cell: dict, ctx: dict) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if cell["name"] not in metric.get("workloads", [cell["name"]]):
            continue
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{metric['name']}"
        )
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def peaks(ctx: dict) -> dict:
    """This chip's row of ``peaks.json``; an unknown kind is an error."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    kind = ctx["device_kind"]
    if kind not in table:
        raise KeyError(f"no published peak for device kind {kind!r} in "
                       "benchmarks/peaks.json")
    return table[kind]
