"""Microbenchmark of the chunked state-space scan (``tpudist/ops/ssd.py``) on
the chip, at a Mamba-2 layer's shape, and its agreement with the
sequential recurrence.

    python3 tools/ssd_microbench.py [--seq 8192] [--rounds 5] [--out FILE]

Times, interleaved over ``--rounds`` rounds of ``--reps`` calls each (ms a
call, ``block_until_ready``): the kernel's forward and forward + backward
(:func:`ssd_scan`, the backward its chunk-parallel XLA), and the same
mathematics as XLA contractions alone (:func:`ssd_chunked`, JAX's own
gradient). Agreement, under Mamba-2's published initialisation (``A ~
-U[1, 16]``, ``dt`` log-uniform on ``[1e-3, 1e-1]``: many heads keep state
across a chunk of 128), with ``D = 0`` (the skip holds no state): ``y``
and the gradient of every input of the kernel against the float32
sequential recurrence of ``benchmarks/reference/nemotron_h.py``
(``precision("highest")``), as the RMS of the gap over the RMS of the
reference, over all heads and for the worst head; beside it the same
recurrence with its state held in bfloat16, the control a tolerance has
to fail. Refuses to run off the chip. One JSON line a result."""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def inputs(key, *, seq, heads=64, head_dim=64, groups=8, state=128):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(key, 6)
    bf = jnp.bfloat16
    x = jax.random.normal(k[0], (1, seq, heads, head_dim)).astype(bf)
    # B, C of unit-variance products C_t . B_s
    scale = state ** -0.25
    B = (scale * jax.random.normal(k[1], (1, seq, groups, state))).astype(bf)
    C = (scale * jax.random.normal(k[2], (1, seq, groups, state))).astype(bf)
    A = -jax.random.uniform(k[3], (heads,), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(k[4], (1, seq, heads),
                                    minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    D = jax.random.normal(k[5], (heads,))
    return x, dt, A, B, C, D


def rel_rms(got, want, axis=None):
    """RMS of the gap over the RMS of ``want`` (per head where ``axis``
    names the others)."""
    import jax.numpy as jnp

    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return jnp.sqrt(jnp.mean((got - want) ** 2, axis=axis)
                    / jnp.mean(want ** 2, axis=axis))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.nemotron_h import recurrence
    from tpudist.ops.ssd import ssd_chunked, ssd_scan

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

    emit(device=device.device_kind, seq=args.seq)
    args_ = inputs(jax.random.key(0), seq=args.seq)
    cot = jax.random.normal(jax.random.key(1), args_[0].shape).astype(
        jnp.bfloat16)

    def value_and_grads(fn):
        def loss(*a):
            return jnp.sum(fn(*a).astype(jnp.float32) * cot)
        return jax.jit(jax.value_and_grad(loss, argnums=range(6)))

    fns = {
        "kernel_fwd": jax.jit(ssd_scan),
        "kernel_fwd_bwd": value_and_grads(ssd_scan),
        "xla_fwd": jax.jit(ssd_chunked),
        "xla_fwd_bwd": value_and_grads(ssd_chunked),
    }
    for name, fn in fns.items():
        t = time.perf_counter()
        jax.block_until_ready(fn(*args_))
        emit(compiled=name, s=time.perf_counter() - t)
    times = {name: [] for name in fns}
    for _ in range(args.rounds):
        for name, fn in fns.items():
            t = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*args_)
            jax.block_until_ready(out)
            times[name].append(1e3 * (time.perf_counter() - t) / args.reps)
    emit(ms_a_call={k: sorted(v)[len(v) // 2] for k, v in times.items()},
         rounds={k: v for k, v in times.items()})

    # agreement with the sequential recurrence, float32, with D = 0: the
    # skip carries no state and would dilute a state's error
    args_ = args_[:5] + (jnp.zeros_like(args_[5]),)
    f32 = [a.astype(jnp.float32) for a in args_]

    def bf16_state(x, dt, A, B, C, D):
        """The recurrence with its state rounded to bfloat16 each step."""
        per = x.shape[2] // B.shape[2]
        heads = lambda v: jnp.moveaxis(jnp.repeat(v, per, axis=2), 1, 0)

        def step(state, inp):
            xt, dtt, bt, ct = inp
            # reduce_precision: a bf16 round trip the compiler may not drop
            state = jax.lax.reduce_precision(
                jnp.exp(dtt * A)[..., None, None] * state
                + dtt[..., None, None] * bt[..., :, None] * xt[..., None, :],
                exponent_bits=8, mantissa_bits=7)
            return state, jnp.einsum("bhn,bhnp->bhp", ct, state) \
                + D[:, None] * xt

        b, _, h, p = x.shape
        _, y = jax.lax.scan(step, jnp.zeros((b, h, B.shape[3], p)), (
            jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0), heads(B),
            heads(C)))
        return jnp.moveaxis(y, 0, 1)

    with jax.default_matmul_precision("highest"):
        want_y = jax.jit(recurrence)(*f32)
        want = value_and_grads(recurrence)(*f32)[1]
        low_y = jax.jit(bf16_state)(*f32)
    got_y = fns["kernel_fwd"](*args_)
    got = fns["kernel_fwd_bwd"](*args_)[1]
    xla_y = fns["xla_fwd"](*args_)
    names = ("x", "dt", "A", "B", "C", "D")
    per_head = lambda got: jnp.max(rel_rms(got, want_y, axis=(0, 1, 3)))
    emit(agreement="published_init, D = 0",
         y_kernel=float(rel_rms(got_y, want_y)),
         y_xla=float(rel_rms(xla_y, want_y)),
         y_bf16_state=float(rel_rms(low_y, want_y)),
         y_worst_head_kernel=float(per_head(got_y)),
         y_worst_head_xla=float(per_head(xla_y)),
         y_worst_head_bf16_state=float(per_head(low_y)),
         grads_kernel={n: float(rel_rms(g, w))
                       for n, g, w in zip(names, got, want)},
         log_carry=float(128 * jnp.mean(args_[1] * args_[2])))
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
