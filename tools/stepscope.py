"""stepscope: measured per-op attribution of device time in a profiler
capture (docs/OBSERVABILITY.md §9).

``WindowedProfiler`` (and any ``jax.profiler`` trace) writes a Chrome
trace-event file — ``<host>.trace.json.gz`` under
``{log_dir}/plugins/profile/<timestamp>/`` — next to the xplane protobuf.
The JSON side is parseable with nothing but the stdlib, and its XLA op
events (``ph == "X"`` with an ``hlo_op`` arg, or events on a device-named
process) carry what a roofline argument reasons about by hand: which HLO
ops the step's time actually went to. This tool
is the measured other half of ``tpudist/telemetry/anatomy.py``'s static
counts:

1. **bucket** — device-op time into GEMM / collective-comm /
   attention-custom-call / elementwise-other (HLO name + metadata
   heuristics; the last bucket is the explicit catch-all, so attribution
   is total by construction and the report prints the named share).
2. **bound** — classify each bucket compute- vs HBM-bound: GEMM/attention
   from the program's arithmetic intensity (an ``anatomy`` telemetry row's
   ``flops_scaled / bytes_accessed``, or ``--ai``) against the chip's
   ridge point (``--peak-flops / --hbm-gbps``); collectives are
   interconnect-bound and elementwise HBM-bound by construction.
3. **top-K** — the heaviest individual ops with bucket, time share, and
   call count.
4. **diff** — A/B mode (``--diff A B``): per-bucket and per-op deltas
   between two captures, largest regressions first — the measured form of
   "what got slower".

Usage::

    python tools/stepscope.py TRACE_DIR [--top K]
        [--anatomy FILE.jsonl]   arithmetic intensity from an anatomy row
        [--ai FLOPS_PER_BYTE]    ... or given directly
        [--peak-flops F] [--hbm-gbps G]   ridge point (default v5e bf16)
    python tools/stepscope.py --diff BEFORE_DIR AFTER_DIR [--top K]

Stdlib only — like tracelens, this must run on a laptop holding nothing
but the downloaded log directory.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

# chip defaults for the ridge point: TPU v5e bf16 peak over HBM bandwidth
# (197 TFLOP/s / 819 GB/s ≈ 240 FLOPs/byte). Overridable per chip; the
# tool cannot import tpudist (stdlib-only), so the constant is restated
# here with its source.
DEFAULT_PEAK_FLOPS = 197e12
DEFAULT_HBM_GBPS = 819.0

BUCKETS = ("gemm", "collective-comm", "attention-custom-call",
           "elementwise-other")

_GEMM_PREFIXES = ("dot", "convolution", "cublas", "gemm")
_COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "send", "recv",
    "partition-id", "replica-id",
)
_ATTENTION_HINTS = ("attention", "flash", "mha", "pallas", "splash",
                    "paged_attention")
# host/infra lanes that appear on device-named processes in some backends
# but are runtime plumbing, not HLO work
_INFRA_NAMES = ("ThreadpoolListener", "ThunkExecutor", "TaskDispatcher",
                "ExecuteThunks", "Barrier")


# -- trace loading -----------------------------------------------------------

def find_trace_files(path) -> list[Path]:
    """Every Chrome-trace JSON under ``path`` (a file, a profile dir, or a
    log dir holding ``plugins/profile/<ts>/``), sorted for determinism."""
    p = Path(path)
    if p.is_file():
        return [p]
    found = set()
    for pat in ("*.trace.json.gz", "*.trace.json"):
        found.update(p.rglob(pat))
    return sorted(found)


def load_events(path) -> list[dict]:
    p = Path(path)
    opener = gzip.open if p.suffix == ".gz" else open
    with opener(p, "rt", encoding="utf-8") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    return [e for e in events if isinstance(e, dict)]


def _process_names(events) -> dict[int, str]:
    names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            names[e.get("pid")] = str((e.get("args") or {}).get("name", ""))
    return names


def device_op_events(events) -> list[dict]:
    """The HLO-op execution events: complete (``X``) events carrying an
    ``hlo_op``/``hlo_module`` arg (XLA's own annotation — present on CPU
    and GPU device lanes), plus, for backends that drop the args, named
    events on a device-named process that aren't known runtime plumbing.
    Python-tracer and host-infra events never qualify."""
    pnames = _process_names(events)
    ops = []
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        args = e.get("args") or {}
        if "hlo_op" in args or "hlo_module" in args:
            ops.append(e)
            continue
        pname = pnames.get(e.get("pid"), "").lower()
        if ("device" in pname or "tpu" in pname or "gpu" in pname):
            name = str(e.get("name", ""))
            if name and not any(i in name for i in _INFRA_NAMES):
                ops.append(e)
    return ops


# -- bucketing ---------------------------------------------------------------

def op_base(name: str) -> str:
    """``dot.3`` → ``dot``; ``fusion.12.clone`` → ``fusion`` — the HLO
    opcode-ish base the bucket rules match on."""
    out = name.split(".")[0] if name else name
    return out.strip("%")


def classify(name: str, args: dict | None = None) -> str:
    """One of :data:`BUCKETS` for an op event. ``elementwise-other`` is
    the explicit catch-all (fusions, reduces, copies, converts) — every
    device op lands in a named bucket, by construction."""
    base = op_base(str(name)).lower()
    hlo = op_base(str((args or {}).get("hlo_op", ""))).lower()
    key = hlo or base
    blob = " ".join(
        str(v) for v in (name, hlo, (args or {}).get("long_name", ""),
                         (args or {}).get("tf_op", ""))
    ).lower()
    if any(key.startswith(p) for p in _COLLECTIVE_PREFIXES):
        return "collective-comm"
    if any(h in blob for h in _ATTENTION_HINTS):
        return "attention-custom-call"
    if any(key.startswith(p) for p in _GEMM_PREFIXES):
        return "gemm"
    return "elementwise-other"


def aggregate(op_events) -> dict:
    """Bucket + per-op totals: ``{"total_us", "buckets": {bucket:
    {"us", "count"}}, "ops": {op base name: {"us", "count", "bucket"}}}``.
    Durations are trace microseconds summed across device lanes."""
    buckets = {b: {"us": 0.0, "count": 0} for b in BUCKETS}
    ops: dict[str, dict] = {}
    total = 0.0
    for e in op_events:
        dur = float(e.get("dur", 0.0))
        args = e.get("args") or {}
        name = str(args.get("hlo_op") or e.get("name") or "?")
        bucket = classify(name, args)
        base = op_base(name)
        total += dur
        buckets[bucket]["us"] += dur
        buckets[bucket]["count"] += 1
        rec = ops.setdefault(base, {"us": 0.0, "count": 0, "bucket": bucket})
        rec["us"] += dur
        rec["count"] += 1
    return {"total_us": total, "buckets": buckets, "ops": ops}


def attributed_pct(summary) -> float:
    """Share of device time in the named buckets — 100.0 by construction
    of the catch-all; printed so the guarantee is visible, not assumed."""
    total = summary["total_us"]
    if total <= 0:
        return 0.0
    named = sum(b["us"] for b in summary["buckets"].values())
    return 100.0 * named / total


# -- boundedness -------------------------------------------------------------

def anatomy_intensity(path) -> float | None:
    """Arithmetic intensity (FLOPs/byte) from the first ``anatomy`` row in
    a telemetry JSONL — the program-level ``flops_scaled/bytes_accessed``
    the static analysis recorded at bring-up."""
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if row.get("kind") != "anatomy":
                    continue
                flops = row.get("flops_scaled") or row.get("flops")
                bytes_ = row.get("bytes_accessed")
                if flops and bytes_:
                    return float(flops) / float(bytes_)
    except OSError:
        return None
    return None


def boundedness(bucket: str, ai: float | None, ridge: float) -> str:
    """compute- vs HBM-bound per bucket: collectives are interconnect-
    bound and elementwise ops HBM-bound by construction (O(1) FLOPs/byte
    is far under any ridge); GEMM/attention compare the program's
    arithmetic intensity against the ridge point, or answer "unknown"
    when no intensity was given — never a guessed verdict."""
    if bucket == "collective-comm":
        return "interconnect-bound"
    if bucket == "elementwise-other":
        return "HBM-bound"
    if ai is None:
        return "unknown (pass --anatomy or --ai)"
    return "compute-bound" if ai >= ridge else "HBM-bound"


# -- reports -----------------------------------------------------------------

def render_report(summary, *, top=10, ai=None, ridge=None,
                  out=None) -> None:
    w = (sys.stdout if out is None else out).write
    total = summary["total_us"]
    w(f"stepscope: {total / 1e3:.3f} ms device-op time, "
      f"{sum(b['count'] for b in summary['buckets'].values())} op "
      f"executions, {attributed_pct(summary):.1f}% attributed to named "
      "buckets\n")
    if ai is not None and ridge is not None:
        w(f"arithmetic intensity {ai:.1f} FLOPs/byte vs ridge "
          f"{ridge:.1f} — program is "
          f"{'compute' if ai >= ridge else 'HBM'}-bound overall\n")
    w("\nbucket                      time(ms)   share    ops   verdict\n")
    for name in BUCKETS:
        b = summary["buckets"][name]
        share = 100.0 * b["us"] / total if total > 0 else 0.0
        w(f"{name:<26}{b['us'] / 1e3:>10.3f}{share:>7.1f}%"
          f"{b['count']:>7}   "
          f"{boundedness(name, ai, ridge or float('inf'))}\n")
    w(f"\ntop {top} ops by device time:\n")
    ranked = sorted(summary["ops"].items(), key=lambda kv: -kv[1]["us"])
    for name, rec in ranked[:top]:
        share = 100.0 * rec["us"] / total if total > 0 else 0.0
        w(f"  {name:<32}{rec['us'] / 1e3:>10.3f} ms{share:>7.1f}%"
          f"  x{rec['count']:<5} {rec['bucket']}\n")


def render_diff(before, after, *, top=10, out=None) -> None:
    """Per-bucket and per-op deltas, regressions (time grew) first — the
    A/B answer to "what got slower between these two captures"."""
    w = (sys.stdout if out is None else out).write
    tb, ta = before["total_us"], after["total_us"]
    dt = ta - tb
    pct = 100.0 * dt / tb if tb > 0 else 0.0
    w(f"stepscope diff: device-op time {tb / 1e3:.3f} -> {ta / 1e3:.3f} ms "
      f"({dt / 1e3:+.3f} ms, {pct:+.1f}%)\n")
    w("\nbucket                      before(ms)  after(ms)   delta(ms)\n")
    for name in BUCKETS:
        b = before["buckets"][name]["us"]
        a = after["buckets"][name]["us"]
        w(f"{name:<26}{b / 1e3:>11.3f}{a / 1e3:>11.3f}"
          f"{(a - b) / 1e3:>+12.3f}\n")
    deltas = []
    for name in set(before["ops"]) | set(after["ops"]):
        b = before["ops"].get(name, {}).get("us", 0.0)
        a = after["ops"].get(name, {}).get("us", 0.0)
        bucket = (after["ops"].get(name) or before["ops"].get(name))["bucket"]
        deltas.append((a - b, name, b, a, bucket))
    deltas.sort(key=lambda t: -t[0])
    w(f"\ntop {top} op deltas (regressions first):\n")
    for d, name, b, a, bucket in deltas[:top]:
        w(f"  {name:<32}{b / 1e3:>9.3f} -> {a / 1e3:>9.3f} ms "
          f"({d / 1e3:+.3f})  {bucket}\n")


def summarize(path) -> dict | None:
    """Load + aggregate every trace file under ``path``; ``None`` (with a
    stderr note) when nothing parseable is there."""
    files = find_trace_files(path)
    if not files:
        print(f"stepscope: no .trace.json[.gz] under {path}",
              file=sys.stderr)
        return None
    ops = []
    for f in files:
        try:
            ops.extend(device_op_events(load_events(f)))
        except (OSError, json.JSONDecodeError, EOFError) as exc:
            print(f"stepscope: skipping unreadable {f}: {exc}",
                  file=sys.stderr)
    if not ops:
        print(f"stepscope: no device-op events in {len(files)} trace "
              f"file(s) under {path}", file=sys.stderr)
        return None
    return aggregate(ops)


# -- CLI ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bucket device-op time in a jax profiler capture "
        "(GEMM / collective / attention / elementwise) with compute- vs "
        "HBM-bound verdicts (docs/OBSERVABILITY.md §9)"
    )
    ap.add_argument("paths", nargs="+",
                    help="trace file / profile dir / log dir "
                    "(two dirs with --diff)")
    ap.add_argument("--diff", action="store_true",
                    help="A/B mode: compare exactly two captures")
    ap.add_argument("--top", default=10, type=int,
                    help="rows in the per-op tables")
    ap.add_argument("--anatomy", default=None,
                    help="telemetry JSONL holding an `anatomy` row — the "
                    "program's FLOPs/bytes set the arithmetic intensity")
    ap.add_argument("--ai", default=None, type=float,
                    help="arithmetic intensity (FLOPs/byte) directly")
    ap.add_argument("--peak-flops", default=DEFAULT_PEAK_FLOPS, type=float,
                    help="chip peak FLOP/s for the ridge point")
    ap.add_argument("--hbm-gbps", default=DEFAULT_HBM_GBPS, type=float,
                    help="chip HBM bandwidth (GB/s) for the ridge point")
    args = ap.parse_args(argv)

    if args.diff:
        if len(args.paths) != 2:
            print("stepscope: --diff needs exactly two capture paths",
                  file=sys.stderr)
            return 2
        before = summarize(args.paths[0])
        after = summarize(args.paths[1])
        if before is None or after is None:
            return 2
        render_diff(before, after, top=args.top)
        return 0

    ai = args.ai
    if ai is None and args.anatomy:
        ai = anatomy_intensity(args.anatomy)
        if ai is None:
            print(f"stepscope: no usable anatomy row in {args.anatomy}",
                  file=sys.stderr)
    ridge = args.peak_flops / (args.hbm_gbps * 1e9)
    rc = 0
    for path in args.paths:
        summary = summarize(path)
        if summary is None:
            rc = 2
            continue
        if len(args.paths) > 1:
            print(f"== {path}")
        render_report(summary, top=args.top, ai=ai, ridge=ridge)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
