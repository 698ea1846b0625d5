"""Run one cell of BENCHMARK.json once, in this process, and print one
JSON result as the last line of standard output.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Names no model, cell or metric: the cell is looked up in BENCHMARK.json,
its configuration, traffic, family, limits and per-layer readers in the
files that entry names."""

from __future__ import annotations

import time

T_START = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "tpudist")):
        print("benchmarks/run.py: the system under test (tpudist/) is not "
              "in this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmarks import cell

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return cell.main(
        bench, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), root=ROOT, t_start=T_START,
    )


if __name__ == "__main__":
    sys.exit(main())
