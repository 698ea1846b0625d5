"""Record the small chip traces the yardstick tests read
(``data/tiny_*.xplane.pb``): one traced run of the tiny GPT-2 preset
through the whole harness on the attached TPU, its ``.xplane.pb`` copied out
before ``cell.py`` removes the run's trace directory.

    python3 benchmarks/tests/record_tiny.py --chips 4 --out <file.xplane.pb>

``--steps`` bounds the trace: the profiler runs over about that many steps
(a tiny step is host-bound: ``STEP_MS``)."""

import argparse
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
STEP_MS = 2.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()
    from benchmarks import cell, xplane
    from benchmarks.tests import tiny

    devices, report = cell.find_devices(args.chips)
    reduce_dir = xplane.reduce_dir

    def keep(directory, chips):
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copy(xplane.find_xplane(directory), args.out)
        return reduce_dir(directory, chips)

    xplane.reduce_dir = keep
    # the profiler covers the last half of the window
    seconds = 2 * args.steps * STEP_MS / 1e3
    result = cell.run(
        tiny.bench(args.chips), "tiny_gpt2", seed=2**31 + 11,
        seconds=seconds, trace=True, root=ROOT,
        t_start=time.perf_counter(), devices=devices, report=report,
        limits=dict(tiny.LIMITS, loss_gap=None, grad_norm_gap=None,
                    change_norm_gap=None, change_norm_gap_median=None),
    )
    print({k: result[k] for k in ("correct", "attempted", "device")})
    print(f"{args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
