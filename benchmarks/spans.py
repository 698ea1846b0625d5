"""The second reading of a run's ``.xplane.pb``: what ``xplane.py`` cannot
see through ``jax.profiler.ProfileData``.

Device side: the profiler stores, per HLO instruction, the JAX name stack
(``tf_op``: ``jit(step_fn)/transpose(jvp(GPT2))/h_0/qkv/dot_general:``) and
XLA's ``hlo_category`` in the device plane's ``event_metadata``;
``ProfileData`` drops both. A reader of the protobuf wire format (the four
messages that hold them; needs no package) recovers them, and every op's
exclusive time is then split by pass — forward (``jvp(`` and no
``transpose(``), backward (``transpose(``, and the program's own
``grad_exchange``), optimizer (``optimizer``, ``grad_clip``, ``cast``) —
and by scope. Host side: the program's spans (``fit/...``, ``input/...``,
``tpudist_train``; ``tpudist/telemetry/trace.py``) with their ``step_num``,
per thread, with self times, and the longest device idle gaps attributed to
them. Same step window as ``xplane.py`` (``step_window``).

    python3 -m benchmarks.spans <file.xplane.pb>      # the reduction, as JSON
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

from benchmarks import xplane

PROGRAM_SPANS = ("fit/", "input/", "tpudist_train")
OPT_SCOPES = frozenset(("optimizer", "grad_clip", "cast"))
EXCHANGE_SCOPE = "grad_exchange"
PASSES = ("fwd", "bwd", "opt", "other")
OP_STATS = ("tf_op", "hlo_category")  # what is kept of an op's metadata
TOP_SCOPES = 40  # entries of scope_ms that are printed


# -- the wire format: XSpace.planes=1; XPlane.name=2, .event_metadata=4,
# -- .stat_metadata=5 (maps: key=1, value=2); XEventMetadata.name=2, .stats=5;
# -- XStatMetadata.name=2; XStat.metadata_id=1, .str_value=5, .ref_value=7


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane message")
        yield key >> 3, value


def _map_entry(buf) -> tuple[int, bytes]:
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def op_metadata(path: str) -> dict:
    """``{plane name: {event name: {stat name: text}}}`` for the device
    planes of the file at ``path``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(_map_entry(value)[1])
            elif number == 5:
                key, meta = _map_entry(value)
                stat_names[key] = bytes(
                    dict(_fields(meta)).get(2, b"")).decode()
        if not name.startswith(xplane.DEVICE_PLANE):
            continue
        by_event = out.setdefault(name, {})
        for meta in events:
            event_name, stats = "", {}
            for number, value in _fields(meta):
                if number == 2:
                    event_name = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1))
                    if key not in OP_STATS:
                        continue
                    if 5 in stat:
                        stats[key] = bytes(stat[5]).decode()
                    elif 7 in stat:
                        stats[key] = stat_names.get(stat[7], "")
            if stats and not by_event.get(event_name, {}).get("tf_op"):
                by_event[event_name] = stats
    return out


# -- classification -----------------------------------------------------------


def _components(tf_op: str) -> list[str]:
    """A fusion of several sources lists them all, ``a/b:;c/d:``: the first
    stands for it."""
    return [c for c in tf_op.split(";")[0].rstrip(":").split("/") if c]


def _bare(component: str) -> str:
    """``transpose(jvp(loss_head))`` -> ``loss_head``."""
    return re.sub(r"^(?:\w+\()+|\)+$", "", component)


def pass_of(tf_op: str) -> str:
    parts = _components(tf_op)
    bare = {_bare(c) for c in parts}
    if EXCHANGE_SCOPE in bare:
        return "bwd"
    if bare & OPT_SCOPES:
        return "opt"
    if any(c.startswith("transpose(") for c in parts):
        return "bwd"
    if any(c.startswith("jvp(") for c in parts):
        return "fwd"
    return "other"


def scope_of(tf_op: str) -> str:
    """The first two path components under the model (or under one of the
    program's own scopes), layer numbers folded: ``h_N/qkv``,
    ``h_N/pallas_call``, ``loss_head/while``, ``optimizer/grad_clip``."""
    parts = _components(tf_op)
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    if not parts:
        return "(none)"
    head = _bare(parts[0])
    named = head in OPT_SCOPES or head in (EXCHANGE_SCOPE, "loss_head")
    if "(" in parts[0] and not named:
        parts = parts[1:]  # jvp(GPT2): the model itself
    else:
        parts = [head] + parts[1:]
    parts = [re.sub(r"_\d+$", "_N", p) for p in parts[:2]]
    return "/".join(parts) or "(top)"


# -- loading ------------------------------------------------------------------


def load(path: str) -> dict:
    """Device ops and modules as ``xplane.load`` gives them, the metadata
    of the ops, and the program's host spans by line:
    ``(name, start, end, step_num | batch | None)``, ns. A span tagged
    ``end`` (a ``next()`` that found its stream over) is left out."""
    import jax

    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = []
            for e in line.events:
                if not e.name.startswith(PROGRAM_SPANS):
                    continue
                stats = dict(e.stats)
                if "end" not in stats:
                    spans.append((
                        e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns),
                        stats.get("step_num", stats.get("batch")),
                    ))
            if spans:
                host[(plane.name, i, line.name)] = spans
    return {"devices": xplane.load(path)["devices"], "host": host,
            "metadata": op_metadata(path)}


# -- reduction ----------------------------------------------------------------


def thread_names(host: dict) -> dict:
    """``main`` is the line that dispatches the steps, ``producer`` the one
    that runs the user's iterator; any other keeps its line's name."""
    count = lambda spans, name: sum(1 for s in spans if s[0] == name)
    names = {}
    main = max(host, key=lambda k: count(host[k], "tpudist_train"),
               default=None)
    for key, spans in host.items():
        if key == main and count(spans, "tpudist_train"):
            names[key] = "main"
        elif count(spans, "input/produce"):
            names[key] = "producer"
        else:
            names[key] = key[2]
    return names


def flat_timeline(spans) -> list[tuple[int, int, str]]:
    """One thread's nested spans as disjoint ``(start, end, innermost
    name)`` pieces, in time order."""
    pieces, stack = [], []  # stack of [name, end, cursor]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, cursor = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, name))
            if stack:
                stack[-1][2] = end

    for name, start, end, _ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack:
            if start > stack[-1][2]:
                pieces.append((stack[-1][2], start, stack[-1][0]))
            stack[-1][2] = max(stack[-1][2], start)
        stack.append([name, end, start])
    close(float("inf"))
    return sorted(pieces)


def host_spans(host: dict, lo: int, hi: int, steps: int) -> dict:
    """Per span name: the thread, mean ms a traced step (duration and self
    time), mean ms an event, and events a step — over the events that
    start in the window. ``per_step`` falls short of 1 where the stream
    ends before the trace does (the prefetch runs ``depth`` batches ahead,
    the producer further) and for work of the step before (``fit/log``), so
    the count by identifier is given too, over the whole trace: ``per_id``
    events for each ``step_num`` or ``batch`` seen, and ``ids_skipped``,
    the identifiers between the first and last seen that no event carries
    (a periodic span, ``fit/memory_stats``, skips by design)."""
    out = {}
    names = thread_names(host)
    for key, spans in host.items():
        inside = [s for s in spans if lo <= s[1] < hi]
        selfs = {(n, a, b): own for n, a, b, own in xplane.exclusive_times(
            [(n, a, b) for n, a, b, _ in inside])}
        for name in sorted({s[0] for s in inside}):
            mine = [s for s in inside if s[0] == name]
            total = sum(b - a for _, a, b, _ in mine)
            own = sum(selfs[(n, a, b)] for n, a, b, _ in mine)
            numbered = [tag for *_, tag in mine if tag is not None]
            ids = [tag for n, *_, tag in spans
                   if n == name and tag is not None]
            seen = len(set(ids))
            out[name] = {
                "thread": names[key],
                "ms": total / 1e6 / steps,
                "self_ms": own / 1e6 / steps,
                "event_ms": total / 1e6 / len(mine),
                "per_step": len(mine) / steps,
                "numbered": len(numbered) == len(mine),
                "per_id": len(ids) / seen if seen else None,
                "ids_skipped": (max(ids) - min(ids) + 1 - seen
                                if seen else None),
            }
    return out


def idle_by_span(gaps, host: dict, top: int = 10) -> dict:
    """The ``top`` longest device gaps, each split among the main thread's
    innermost program spans that overlap it; ``attributed_pct`` is the
    share of that idle time that some program span covers."""
    names = thread_names(host)
    main = next((k for k, v in names.items() if v == "main"), None)
    timeline = flat_timeline(host[main]) if main else []
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ms, total, covered = {}, 0, 0
    for lo, hi in longest:
        total += hi - lo
        for start, end, name in timeline:
            if start >= hi:
                break
            cover = min(end, hi) - max(start, lo)
            if cover > 0:
                ms[name] = ms.get(name, 0.0) + cover / 1e6
                covered += cover
    if total > covered:
        ms["unattributed"] = (total - covered) / 1e6
    return {
        "ms": dict(sorted(ms.items(), key=lambda kv: -kv[1])),
        "idle_ms": total / 1e6,
        "attributed_pct": 100.0 * covered / total if total else None,
    }


def reduce(trace: dict) -> dict | None:
    """Everything per traced step of the first device (data-parallel
    replicas run the same program) and of the one host."""
    devices = sorted(trace["devices"])
    if not devices:
        return None
    plane = trace["devices"][devices[0]]
    meta = trace["metadata"].get(devices[0], {})
    window = xplane.step_window(plane["modules"])
    if window is None:
        return None
    lo, hi = window
    steps = sum(1 for _, start, _ in plane["modules"] if lo <= start < hi)
    pass_ns = dict.fromkeys(PASSES, 0)
    scope_ns, category_ns, other_ns = {}, {}, {}
    for op, start, end, self_ns in xplane.exclusive_times(plane["ops"]):
        if end <= lo or start >= hi:
            continue
        stats = meta.get(op, {})
        tf_op = stats.get("tf_op", "")
        which = pass_of(tf_op)
        pass_ns[which] += self_ns
        key = f"{which}:{scope_of(tf_op)}"
        scope_ns[key] = scope_ns.get(key, 0) + self_ns
        category = stats.get("hlo_category", "(none)")
        category_ns[category] = category_ns.get(category, 0) + self_ns
        if which == "other":
            name = "/".join(_components(tf_op)) or xplane.op_base(op)
            other_ns[name] = other_ns.get(name, 0) + self_ns
    per_step = lambda table: {
        k: v / 1e6 / steps
        for k, v in sorted(table.items(), key=lambda kv: -kv[1])
    }
    busy = xplane.clip(xplane.union((s, e) for _, s, e in plane["ops"]),
                       lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]
    busy_ms = sum(b - a for a, b in busy) / 1e6 / steps
    return {
        "steps": steps,
        "busy_ms": busy_ms,
        "pass_ms": {k: v / 1e6 / steps for k, v in pass_ns.items()},
        "other_pct": (100.0 * pass_ns["other"] / 1e6 / steps / busy_ms
                      if busy_ms else None),
        "other_top": dict(list(per_step(other_ns).items())[:5]),
        "scope_ms": per_step(scope_ns),
        "category_ms": per_step(category_ns),
        "host_span_ms": host_spans(trace["host"], lo, hi, steps),
        "idle_by_span": idle_by_span(gaps, trace["host"]),
    }


# -- for the per-layer readers ------------------------------------------------


def of(ctx: dict) -> dict | None:
    """The reduction of this run's trace, made once (the readers share it
    through ``ctx``) while ``cell.py`` still holds the trace directory;
    prints the informational line. Nothing where the run was not traced."""
    if "spans" not in ctx:
        ctx["spans"] = None
        if ctx.get("trace"):
            from benchmarks import cell

            t0 = time.perf_counter()
            out = reduce(load(xplane.find_xplane(os.path.join(
                ctx["root"], cell.WORK_DIR, ctx["cell"]["name"], "trace"))))
            if out is not None:
                shown = dict(out, scope_ms=dict(
                    list(out["scope_ms"].items())[:TOP_SCOPES]))
                cell.say(spans_s=time.perf_counter() - t0, **shown)
            ctx["spans"] = out
    return ctx["spans"]


def pass_ms(ctx: dict, which: str) -> float | None:
    out = of(ctx)
    return (out["pass_ms"][which] or None) if out else None


def span_ms(ctx: dict, names, field: str = "ms") -> float | None:
    """Sum of ``field`` over the spans ``names`` that the trace holds;
    nothing where it holds none of them (a program without the spans)."""
    out = of(ctx)
    found = [out["host_span_ms"][n][field] for n in names
             if n in out["host_span_ms"]] if out else []
    return sum(found) if found else None


if __name__ == "__main__":
    json.dump(reduce(load(sys.argv[1])), sys.stdout, indent=1)
    print()
