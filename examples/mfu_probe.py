"""Per-GEMM MXU-utilization probe: an operator's tool, outside the benchmark
(its readings are in no ledger line).

How near its peak a GPT-2 step's GEMMs run depends on their width: this
probe times each GEMM shape of the step in isolation on the chip, plus the
same block mix at wider hidden sizes (the "would a bigger model hit higher MFU" experiment).

Method: each shape runs inside ONE jitted ``lax.scan`` of ``iters``
matmuls whose left operand is scaled per-iteration (defeats loop-invariant
hoisting) and accumulated (defeats dead-code elimination); timing is
sync'd by fetching a scalar of the result. The per-iteration time is
DIFFERENTIAL — ``(t(4n) − t(n)) / 3n`` — so per-call fixed costs cancel
instead of polluting sub-millisecond GEMMs. Per-shape report: achieved
TFLOP/s and fraction of the chip's bf16 peak.

Run on the chip::

    python examples/mfu_probe.py            # per-GEMM table + hidden sweep
    python examples/mfu_probe.py --peak 197e12
"""

from __future__ import annotations

import argparse
import os
import sys

import jax.numpy as jnp
import numpy as np

# runnable as a plain script from anywhere: put the repo root (one level up)
# on sys.path when tpudist isn't pip-installed
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# single source of truth for the analytic counters, the GEMM-shape table,
# and the device peak table (tpudist.telemetry.flops): this file keeps only the
# CLI — the math it times lives with the MFU accounting of fit()'s
# telemetry, and the differential-timing
# skeleton (adaptive iters, (t(4n)−t(n))/3n, anti-hoisting operands,
# plausibility retries) lives in tpudist.telemetry.microbench so this
# probe and examples/kernel_probe.py measure the same way
from tpudist.telemetry import microbench  # noqa: E402
from tpudist.telemetry.flops import device_peaks, gpt2_step_shapes  # noqa: E402


def time_gemm(m: int, k: int, n: int, *, reps: int = 5, peak: float,
              hbm_bw: float) -> float:
    """Median achieved FLOP/s for a bf16 [m,k]x[k,n] matmul.

    Differential timing (tpudist.telemetry.microbench) cancels per-call
    fixed costs (dispatch, the value-fetch sync); iteration counts are
    ADAPTIVE so the differential spans ~1.5 s of device time, far above
    per-call jitter (a fixed small count read impossible >100%-peak values
    through the noise)."""
    rng = np.random.Generator(np.random.PCG64(0))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.bfloat16)

    timed = microbench.anti_hoist_scan(lambda xs: xs @ w, x, reps=reps)
    flops = 2.0 * m * k * n
    # optimistic per-iter estimate (50% of peak, bandwidth floor included)
    est = max(flops / (0.5 * peak),
              2.0 * (m * k + k * n + m * n) / hbm_bw)
    dt = microbench.measure_iter_seconds(
        timed, est, floor_s=flops / (1.05 * peak)
    )
    return flops / dt if dt > 0 else float("nan")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--peak", type=float, default=None,
                    help="chip bf16 peak FLOP/s (default: the running "
                    "chip's row of tpudist.telemetry.flops.DEVICE_PEAKS)")
    ap.add_argument("--tokens", type=int, default=8192,
                    help="GEMM rows = microbatch tokens of the step "
                    "(8 seqs x 1024)")
    ap.add_argument("--sweep", default="768,1024,1536,2048",
                    help="hidden sizes for the wider-GEMM block-mix sweep")
    args = ap.parse_args()
    table_peak, hbm_bw, _ = device_peaks()
    args.peak = args.peak or table_peak

    print(f"# per-GEMM MXU utilization at tokens={args.tokens} "
          f"(bf16, peak {args.peak / 1e12:.0f} TFLOP/s)")
    print(f"{'shape':24s} {'M':>7s} {'K':>6s} {'N':>6s} "
          f"{'TFLOP/s':>8s} {'%peak':>6s}")
    for name, m, k, n in gpt2_step_shapes(args.tokens, 768):
        fl = time_gemm(m, k, n, peak=args.peak, hbm_bw=hbm_bw)
        print(f"{name:24s} {m:7d} {k:6d} {n:6d} "
              f"{fl / 1e12:8.1f} {100 * fl / args.peak:5.1f}%")

    print("\n# block GEMM mix vs hidden width (fwd shapes, wider d)")
    print(f"{'hidden':>6s} {'weighted TFLOP/s':>16s} {'%peak':>6s}")
    for d in [int(s) for s in args.sweep.split(",")]:
        total_flops, total_time = 0.0, 0.0
        for name, m, k, n in gpt2_step_shapes(args.tokens, d)[:-3:3]:
            # fwd block GEMMs only (dgrad/wgrad track them; head excluded:
            # its width is vocab-fixed)
            fl = time_gemm(m, k, n, reps=3, peak=args.peak, hbm_bw=hbm_bw)
            if not np.isfinite(fl):
                continue  # persistently-noisy shape: excluded, not faked
            f = 2.0 * m * k * n
            total_flops += f
            total_time += f / fl
        eff = total_flops / total_time if total_time else float("nan")
        print(f"{d:6d} {eff / 1e12:16.1f} {100 * eff / args.peak:5.1f}%")


if __name__ == "__main__":
    main()
