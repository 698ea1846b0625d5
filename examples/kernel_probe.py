"""Achieved-HBM-bandwidth probe for the step-fusion layer: an operator's
tool, outside the benchmark (its readings are in no ledger line).

The fused LN kernel and the one-pass AdamW update (tpudist/ops/layernorm.py;
tpudist/ops/fused_update.py — plain XLA since PR 26, one loop fusion a
leaf) are bandwidth-bound, so their figure of merit is GB/s against the
chip's HBM roofline (v5e: 819 GB/s), not FLOP/s. This probe times each
in isolation with the same differential method as examples/mfu_probe.py
(tpudist.telemetry.microbench: adaptive iters, ``(t(4n)−t(n))/3n``,
anti-hoisting operands, plausibility retries) and reports
bytes-moved / second.

Byte accounting (the numerator) is the kernel's mandatory HBM traffic:

- LN forward, residual variant: read x + y, write out + r → 4·N·D·dsize
  (+ the [D] vectors, negligible);
- LN backward: read r + g (+ gr), write dr → 3–4 passes;
- fused AdamW: read g/m/v/p (4×4 B), write m'/v'/u (3×4 B) + the bf16
  copy (2 B) → 30 B/element.

Run on the chip::

    python examples/kernel_probe.py                 # default shapes
    python examples/kernel_probe.py --rows 32768 --hidden 1024 --bw 819e9

On CPU it still runs (the LN kernel interprets) — the GB/s are then host
numbers, useful only as a smoke test.
"""

from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpudist.telemetry import microbench  # noqa: E402

V5E_HBM_BW = 819e9  # bytes/s: the published v5e peak (benchmarks/peaks.json)


def _measure(body, operand, nbytes, *, bw, reps):
    timed = microbench.anti_hoist_scan(body, operand, reps=reps)
    est = nbytes / (0.3 * bw)  # optimistic: 30% of the roofline
    dt = microbench.measure_iter_seconds(
        timed, est, floor_s=nbytes / (1.05 * bw)
    )
    return nbytes / dt if dt > 0 else float("nan")


def probe_ln(rows: int, hidden: int, dtype, *, bw: float, reps: int):
    """Fused residual-add+LN forward and forward+backward GB/s."""
    from tpudist.ops.layernorm import fused_layernorm

    rng = np.random.Generator(np.random.PCG64(0))
    x = jnp.asarray(rng.standard_normal((rows, hidden)), dtype)
    y = jnp.asarray(rng.standard_normal((rows, hidden)), dtype)
    scale = jnp.asarray(rng.standard_normal(hidden), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(hidden), jnp.float32)
    dsize = jnp.dtype(dtype).itemsize
    fwd_bytes = 4 * rows * hidden * dsize  # read x,y; write out,r

    def fwd(xs):
        n, r = fused_layernorm(xs, scale, bias, residual=y, eps=1e-5)
        return n + r  # keep both outputs live

    fwd_gbps = _measure(fwd, x, fwd_bytes, bw=bw, reps=reps)

    # fwd+bwd: fwd traffic + read r,g,gr + write dr (cotangents for both
    # outputs are the same buffer)
    full_bytes = fwd_bytes + 4 * rows * hidden * dsize

    def fwdbwd(xs):
        def loss(xs):
            n, r = fused_layernorm(xs, scale, bias, residual=y, eps=1e-5)
            return jnp.sum(n.astype(jnp.float32)) + jnp.sum(
                r.astype(jnp.float32)
            )

        return jax.grad(loss)(xs)

    full_gbps = _measure(fwdbwd, x, full_bytes, bw=bw, reps=reps)
    return fwd_gbps, full_gbps


def probe_fused_update(n_elems: int, *, bw: float, reps: int,
                       compute_dtype=jnp.bfloat16):
    """One-pass AdamW update GB/s over one ``n_elems`` fp32 leaf."""
    from tpudist.ops.fused_update import fused_leaf_update

    rng = np.random.Generator(np.random.PCG64(1))
    leaf = lambda: jnp.asarray(rng.standard_normal(n_elems), jnp.float32)
    g, m, v, p = leaf(), leaf(), leaf(), leaf()
    copy_b = jnp.dtype(compute_dtype).itemsize if compute_dtype else 0
    nbytes = n_elems * (4 * 4 + 3 * 4 + copy_b)  # r g/m/v/p, w m'/v'/u, copy

    def body(gs):
        u, m2, v2, c = fused_leaf_update(
            gs, m, v, p, jnp.float32(1e-3), jnp.float32(0.1),
            jnp.float32(0.001), b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
            compute_dtype=compute_dtype,
        )
        out = u + m2 + v2
        if c is not None:
            out = out + c.astype(jnp.float32)
        return out

    return _measure(body, g, nbytes, bw=bw, reps=reps)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=8192,
                    help="LN rows = tokens of one microbatch (8 x 1024)")
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--elems", type=int, default=8_000_000,
                    help="fused-update leaf size (~a GPT-2 124M block pair)")
    ap.add_argument("--bw", type=float, default=V5E_HBM_BW,
                    help="HBM roofline bytes/s (default v5e 819e9)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--bf16", action="store_true",
                    help="probe the LN kernel at bf16 activations")
    args = ap.parse_args()

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    print(f"# step-fusion kernel HBM bandwidth vs the "
          f"{args.bw / 1e9:.0f} GB/s roofline (backend: "
          f"{jax.default_backend()})")
    print(f"{'kernel':34s} {'GB/s':>9s} {'%roofline':>10s}")

    fwd, full = probe_ln(args.rows, args.hidden, dtype,
                         bw=args.bw, reps=args.reps)
    for name, g in [
        (f"ln fwd (res+LN, {args.rows}x{args.hidden})", fwd),
        ("ln fwd+bwd", full),
    ]:
        print(f"{name:34s} {g / 1e9:9.1f} {100 * g / args.bw:9.1f}%")

    upd = probe_fused_update(args.elems, bw=args.bw, reps=args.reps)
    name = f"fused adamw ({args.elems / 1e6:.0f}M elems)"
    print(f"{name:34s} {upd / 1e9:9.1f} {100 * upd / args.bw:9.1f}%")


if __name__ == "__main__":
    main()
