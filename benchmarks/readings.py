"""The readings a cell's limits are set from, taken on the chip at the
cell's own size (no measured window is needed: ``--seconds`` is short).

    python3 benchmarks/readings.py --workload <name> --seeds 11,12,... \\
        [--control 3] [--out FILE]

For every seed: the program's numbers against the float32 reference (the
lower reading). For the first ``--control`` seeds also, with the reference
put in the program's place: the control (the reference computed one
precision below what the configuration states) and the faults a training
cell can have — half of the batch left out with the mean taken over the
rest, and, on several chips, the exchange left out (every chip keeping the
gradient of its own rows). A state returned unchanged reads 1 on
``change_norm_gap`` by construction and needs no run. One JSON line each."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOWER = {"float32": "bfloat16", "bfloat16": "fp8"}


def faulty_batches(batches, chips: int) -> dict:
    half = lambda b: {k: v[: len(v) // 2] for k, v in b.items()}
    out = {"half_batch": [half(b) for b in batches]}
    if chips > 1:
        shard = lambda b: {k: v[: len(v) // chips] for k, v in b.items()}
        out["no_exchange"] = [shard(b) for b in batches]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--leaves", default=None,
                    help="file for the first seed's per-leaf norms")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import cell, compare

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec, config, _ = cell.load_cell(bench, args.workload, ROOT)
    control = LOWER[config["recipe"]["compute_dtype"]]
    sink = open(args.out, "a") if args.out else None

    def emit(**row):
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        def extra(program, reference, again, batches):
            strip = lambda n: {k: v for k, v in n.items() if k[0] != "_"}
            emit(seed=seed, kind="program", **strip(
                compare.compare_first_steps(program, reference)))
            if i >= args.control:
                return
            lower = again(control, batches)
            if i == 0 and args.leaves:
                with open(args.leaves, "w") as f:
                    json.dump({"program": program, "reference": reference,
                               "control": lower}, f)
            emit(seed=seed, kind="control", precision=control, **strip(
                compare.compare_first_steps(lower, reference)))
            for fault, bad in faulty_batches(batches, spec["chips"]).items():
                emit(seed=seed, kind="fault", fault=fault, **strip(
                    compare.compare_first_steps(again("float32", bad),
                                                reference)))

        result = cell.run(
            bench, args.workload, seed=seed, seconds=args.seconds,
            trace=False, root=ROOT, t_start=time.perf_counter(),
            on_compared=extra,
        )
        emit(seed=seed, kind="judged", correct=result["correct"],
             **{k: v["value"] for k, v in result["checks"].items()})
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
