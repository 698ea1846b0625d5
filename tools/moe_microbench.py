"""Microbenchmark of one relu² expert layer's grouped products
(``tpudist/parallel/ep.py`` ``GroupedExperts``) on the chip, at the
Nemotron-H cell's shape, with the products run at three pairs of widths.

    python3 tools/moe_microbench.py [--rounds 5] [--reps 10] [--out FILE]

The layer is the cell's: 8 held experts of 1,856 on a model width of
2,688, bfloat16 compute over float32 parameters, the sorted rows as the
``(chunk 0, the other chunks)`` pair of 6,144 + 43,008 rows that
``dropless_moe`` hands it, 8 groups of 384 rows in chunk 0 (the cell's
~3,077 live rows; the further chunks hold none and do not run). Each
variant fixes the widths the products run at (``ep.grouped_widths``,
patched while it is traced): ``2688x1856`` as the parameters are,
``2688x2048`` the expert width padded, ``2816x2048`` both. Times,
interleaved over ``--rounds`` rounds of ``--reps`` calls each (ms a call,
``block_until_ready``): the forward, and the forward + backward (the
gradients of the rows and of both weights). For each variant, the
compiler's tiling of every grouped-product kernel in both compiled
programs (``ragged_dot_tiling``, rows x contraction x output), and its
agreement with the unpadded variant (relative RMS of the live rows'
output and of each gradient). Refuses to run off the chip. One JSON line a
result."""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D, FF, HELD = 2688, 1856, 8
CHUNK_ROWS, N_CHUNKS, GROUP_ROWS = 6144, 8, 384
VARIANTS = {"2688x1856": (2688, 1856), "2688x2048": (2688, 2048),
            "2816x2048": (2816, 2048)}


def rel_rms(got, want):
    import jax.numpy as jnp

    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from tpudist.parallel import ep

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "platform": device.platform}))
        return 1
    sink = open(args.out, "a") if args.out else None

    def emit(**row):
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")

    emit(device=device.device_kind, d=D, ff=FF, held=HELD,
         chunk_rows=CHUNK_ROWS, group_rows=GROUP_ROWS)
    layer = ep.GroupedExperts(HELD, FF, jnp.bfloat16, act="relu2")
    k = jax.random.split(jax.random.key(0), 3)
    rows = jax.random.normal(k[0], (CHUNK_ROWS, D)).astype(jnp.bfloat16)
    live = jnp.arange(CHUNK_ROWS)[:, None] < HELD * GROUP_ROWS
    xs = (jnp.where(live, rows, 0),
          jnp.zeros(((N_CHUNKS - 1) * CHUNK_ROWS, D), jnp.bfloat16))
    sizes = jnp.full((HELD,), GROUP_ROWS, jnp.int32)
    params = layer.init(k[1], xs, sizes)["params"]
    cot = jnp.where(live, jax.random.normal(k[2], (CHUNK_ROWS, D)), 0)

    def variant(widths):
        """The forward and the forward + backward, compiled while
        ``grouped_widths`` answers ``widths`` (new functions each time:
        JAX's trace cache is keyed by the function)."""
        def forward(params, xs):
            return layer.apply({"params": params}, xs, sizes)[0]

        def loss(params, xs):
            return jnp.sum(forward(params, xs).astype(jnp.float32) * cot)

        rule = ep.grouped_widths
        ep.grouped_widths = lambda d, ff: widths
        try:
            return (jax.jit(forward).lower(params, xs).compile(),
                    jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
                        params, xs).compile())
        finally:
            ep.grouped_widths = rule

    fns, tilings, results = {}, {}, {}
    for name, widths in VARIANTS.items():
        fwd, both = variant(widths)
        tilings[name] = {
            f: re.findall(r'ragged_dot_tiling="([0-9,]+)"', c.as_text())
            for f, c in (("fwd", fwd), ("fwd_bwd", both))}
        fns[name + "_fwd"], fns[name + "_fwd_bwd"] = fwd, both
        # rows past the live ones are not defined (the caller masks them)
        results[name] = (jnp.where(live, fwd(params, xs), 0),
                         both(params, xs)[1])
    emit(tiling=tilings, rule=ep.grouped_widths(D, FF))

    times = {name: [] for name in fns}
    for _ in range(args.rounds):
        for name, fn in fns.items():
            t = time.perf_counter()
            for _ in range(args.reps):
                out = fn(params, xs)
            jax.block_until_ready(out)
            times[name].append(1e3 * (time.perf_counter() - t) / args.reps)
    emit(ms_a_call={k: sorted(v)[len(v) // 2] for k, v in times.items()},
         rounds=times)

    want_y, want_g = results["2688x1856"]
    for name, (y, g) in results.items():
        emit(agreement=name, y=rel_rms(y, want_y),
             d_rows=rel_rms(g[1][0], want_g[1][0]),
             **{f"d_{n}": rel_rms(g[0][n], want_g[0][n])
                for n in ("w_up", "w_down")})
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
