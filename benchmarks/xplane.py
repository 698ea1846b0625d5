"""From the profiler's ``.xplane.pb`` to numbers: device busy/idle union,
exclusive time per operation, the exposed share of collectives, the longest
idle gaps and what the host was doing in them. Reads the file with nothing
but ``jax.profiler.ProfileData``.

    python3 -m benchmarks.xplane <file.xplane.pb>     # survey by hand

(op-name bucketing follows ``tools/stepscope.py``; that tool reads
``*.trace.json.gz``, which the installed JAX does not write.)"""

from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "send", "recv",
)
# host spans that label an idle gap, most specific first
HOST_LABELS = ("bench_loader_next", "bench_recorder", "tpudist_train")


class TraceControl:
    """Starts the profiler at the first resolve of the window's last
    ``tail_s`` seconds, from the recorder's hook (the program is
    untouched), and stops it once ``fit`` has returned: starting costs a
    stall of seconds and stopping more, so the untraced head of the window
    stays clean for the rate and the stop falls outside it."""

    def __init__(self, directory: str, window, *, tail_s: float):
        self.directory = directory
        self.window = window
        self.tail_s = tail_s
        self.started_at: float | None = None
        self.active = False

    def on_step(self, step: int, t: float) -> None:
        import jax

        opened = self.window.opened_at
        if (self.started_at is None and opened is not None
                and t - opened >= self.window.seconds - self.tail_s):
            self.started_at = t
            os.makedirs(self.directory, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # no per-call Python events
            options.host_tracer_level = 2    # TraceAnnotations
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self.active = True

    def close(self) -> None:
        import jax

        if self.active:
            self.active = False
            jax.profiler.stop_trace()


def op_name(text: str) -> str:
    """The device trace names an op by its whole HLO text,
    ``%fusion.12 = bf16[...] fusion(...)``: keep ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_base(text: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``; ``h_0.2`` -> ``h_0``."""
    base = op_name(text)
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def is_collective(text: str) -> bool:
    return op_name(text).lower().startswith(COLLECTIVES)


def is_custom_call(text: str) -> bool:
    return " custom-call(" in text


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted, non-overlapping ``(start, end)``."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def exclusive_times(events) -> list[tuple[str, int, int, int]]:
    """``(name, start, end, self_ns)`` for events of one line: an event's
    own time is its duration less that of the events nested in it."""
    out, stack = [], []
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(end, stack[-1][2]) - start
        stack.append([name, start, end, end - start])
    out.extend(tuple(s) for s in reversed(stack))
    return out


def _line_events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [(name, start, end), ...]}``, times in ns."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: _line_events(line) for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                devices[plane.name] = {
                    "ops": lines[OPS_LINE],
                    "modules": lines.get(MODULES_LINE, []),
                }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_line_events(line))
    return {"devices": devices, "host": host}


def step_window(modules) -> tuple[int, int] | None:
    """From the start of the first to the start of the last execution of
    the module that took most device time: whole steps, with every gap
    between them."""
    by_name: dict[str, list] = {}
    for name, start, end in modules:
        by_name.setdefault(op_base(name), []).append((start, end))
    if not by_name:
        return None
    main = max(by_name.values(), key=lambda v: sum(b - a for a, b in v))
    if len(main) < 2:
        return None
    starts = sorted(a for a, _ in main)
    return starts[0], starts[-1]


def label_gap(gap, host_events) -> str:
    """What the host was doing during ``gap``: the harness's own spans or
    ``fit``'s step annotation where one overlaps, else the host span that
    covers most of it, else ``unattributed``."""
    lo, hi = gap
    best_named, best_any, any_cover = None, None, 0
    for name, start, end in host_events:
        cover = min(end, hi) - max(start, lo)
        if cover <= 0:
            continue
        for rank, label in enumerate(HOST_LABELS):
            if name.startswith(label) and (
                    best_named is None or rank < best_named[0]):
                best_named = (rank, label)
        # a span far longer than the gap (a whole-run scope) says nothing
        if cover > any_cover and end - start < 50 * (hi - lo):
            best_any, any_cover = name, cover
    if best_named is not None:
        return best_named[1]
    return best_any or "unattributed"


def reduce(trace: dict, chips: int) -> dict:
    """Busy and window seconds averaged over the devices, the exposed
    collective share, the ten operations with most exclusive time and the
    ten longest idle gaps."""
    devices = sorted(trace["devices"])[:chips]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane with ops")
    busy_ns = window_ns = exposed_ns = 0
    op_ns: dict[str, int] = {}
    gaps: list[tuple[int, int]] = []
    custom_calls: set[str] = set()
    collectives: set[str] = set()
    steps = 0
    for i, name in enumerate(devices):
        plane = trace["devices"][name]
        span = step_window(plane["modules"]) or (
            min(s for _, s, _ in plane["ops"]),
            max(e for _, _, e in plane["ops"]),
        )
        lo, hi = span
        if i == 0:
            steps = sum(1 for _, start, _ in plane["modules"]
                        if lo <= start < hi)
        busy = clip(union((s, e) for _, s, e in plane["ops"]), lo, hi)
        busy_ns += sum(b - a for a, b in busy)
        window_ns += hi - lo
        for op, start, end, self_ns in exclusive_times(plane["ops"]):
            if end <= lo or start >= hi:
                continue
            if is_collective(op):
                exposed_ns += self_ns
            if i == 0:
                name = op_name(op)
                op_ns[name] = op_ns.get(name, 0) + self_ns
                if is_custom_call(op):
                    custom_calls.add(name)
                if is_collective(op):
                    collectives.add(name)
        if i == 0:
            edges = [lo] + [t for ab in busy for t in ab] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    n = len(devices)
    by_kind: dict[str, int] = {}
    for name, ns in op_ns.items():
        kind = re.sub(r"\d+", "N", op_base(name))  # h_3.2 -> h_N
        by_kind[kind] = by_kind.get(kind, 0) + ns
    top_ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    by_label: dict[str, float] = {}
    for gap in longest:
        label = label_gap(gap, trace["host"])
        by_label[label] = by_label.get(label, 0.0) + (gap[1] - gap[0]) / 1e9
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": window_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "devices": n,
        "steps": steps,
        # exclusive seconds in the window, by op name
        "op_seconds": {k: v / 1e9 for k, v in op_ns.items()},
        "custom_call_ops": sorted(custom_calls),
        "collective_ops": sorted(collectives),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops],
            "idle_gaps": sorted(([k, v] for k, v in by_label.items()),
                                key=lambda kv: -kv[1]),
        },
    }


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb in {directory}")
    return files[-1]


def reduce_dir(directory: str, chips: int) -> dict:
    return reduce(load(find_xplane(directory)), chips)


def survey(path: str, out=sys.stdout) -> None:
    """Planes, lines and the heaviest event names: read one trace by hand
    before writing code against it."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total: dict[str, list] = {}
            for e in events:
                rec = total.setdefault(e.name, [0, 0])
                rec[0] += e.duration_ns
                rec[1] += 1
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{first / 1e9:.6f}..{last / 1e9:.6f} s", file=out)
            for name, (ns, count) in sorted(
                    total.items(), key=lambda kv: -kv[1][0])[:25]:
                print(f"    {ns / 1e6:10.3f} ms x{count:<6} {name[:140]}",
                      file=out)


if __name__ == "__main__":
    survey(sys.argv[1])
