"""The chunked state-space scan's share of its roofline: the least time the
chip could take for the step's scan calls — per call the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from the call's shapes
(the family's ``ssd_cost``), forward and backward — over the device time a
traced step spends under ``h_<n>/ssd_scan``: the forward kernel (a Pallas
call the trace names after that scope, ``ssd_scan.<k>``, or
``jvp_ssd_scan_.<k>`` where a transform wraps it) and the backward's
XLA contractions and chunk recurrence, with the time step's softplus and
``A`` beside them (``moe_ms``'s reading of the name stacks). Prints the
program's ``ssd_log_carry`` counter (what a state keeps across a chunk, in
logs) and the chunks a call. Nothing where the program has no such scope
or kernel."""

import os
import re

from benchmarks import cell, layers, spans, xplane
from benchmarks.layer_metrics.moe_ms import stage_of

STAGE = "ssd_scan"
KERNEL = re.compile(r"^(jvp_)?ssd_scan_?(\.\d+)?$")


def _device_ms(ctx) -> float | None:
    """Device ms a traced step of the ops under the scope, or of the
    kernel by its own name where its event carries no name stack; the
    first device's ops in the step window, exclusive times."""
    trace = spans.load(xplane.find_xplane(os.path.join(
        ctx["root"], cell.WORK_DIR, ctx["cell"]["name"], "trace")))
    device = min(trace["devices"])
    plane = trace["devices"][device]
    meta = trace["metadata"].get(device, {})
    window = xplane.step_window(plane["modules"])
    if window is None:
        return None
    lo, hi = window
    steps = sum(1 for _, start, _ in plane["modules"] if lo <= start < hi)
    ns = sum(own for op, start, end, own in xplane.exclusive_times(plane["ops"])
             if not (end <= lo or start >= hi) and (
                 stage_of(meta.get(op, {}).get("tf_op", ""), op) == STAGE
                 or KERNEL.match(xplane.op_name(op))))
    return ns / 1e6 / steps if ns else None


def read(ctx):
    family = ctx["family"]
    cost_of = getattr(family, "ssd_cost", None)
    if cost_of is None or not ctx.get("trace"):
        return None
    device_ms = _device_ms(ctx)
    if not device_ms:
        return None
    cost = cost_of(ctx["config"], ctx["traffic"])
    peaks = layers.peaks(ctx)
    least_ms = 1e3 * cost["calls_per_step"] * sum(
        max(cost[d]["flops"] / peaks["bf16_flops_per_s"],
            cost[d]["bytes"] / peaks["hbm_bytes_per_s"])
        for d in ("fwd", "bwd"))
    counters = getattr(family, "ssd_counters", lambda ctx: None)(ctx) or {}
    cell.say(ssd_scan_ms=device_ms, ssd_least_ms=least_ms,
             ssd_chunks_a_call=cost["chunks"], **counters)
    return 100.0 * least_ms / device_ms
