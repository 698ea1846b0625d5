"""Communication primitives: gradient bucketing, int8 quantization, and the
int8-wire ring all-reduce.

The reference's DDP Reducer flattens gradients into fixed-size buckets and
all-reduces each bucket asynchronously as backward produces it (SURVEY.md
§2.5) — in fp32, because NCCL reduces in the tensor's dtype. tpudist's
default path hands the whole reduction to XLA (one implicit psum from the
global-batch loss), which is optimal on ICI but bandwidth-bound on
multi-slice DCN links, where cross-slice gradient reduction becomes the
dominant step-time term once per-chip batch is fixed (arXiv:2204.06514 §5).
EQuARX (arXiv:2506.17615) shows a quantized all-reduce recovers most of that
bandwidth at negligible quality cost. This module is the primitive layer for
that path — :mod:`tpudist.parallel.dp` builds the train-step integration on
top of it:

- :class:`BucketLayout`: the DDP-bucket equivalent — a params-shaped tree
  flattened into ``[n_buckets, bucket_size]`` fp32 rows, zero-padded, with
  the bucket count rounded up to the reduce axis size so the ring can chunk
  evenly (the padding IS the "empty bucket" case and reduces as exact
  zeros).
- :func:`quantize_bucket` / :func:`dequantize`: symmetric int8 with one
  fp32 scale per bucket; stochastic rounding (unbiased — the property the
  error-feedback convergence argument needs) when a key is passed,
  round-to-nearest otherwise.
- :func:`ring_allreduce_quantized`: the EQuARX-style all-reduce as an
  explicit ring — reduce-scatter then all-gather via ``lax.ppermute``, ONE
  int8 payload (+ per-bucket fp32 scales) per hop, accumulation in fp32 on
  every hop (the "fp32 master accumulation": partial sums are dequantized,
  added in fp32, and re-quantized only for the wire). Every element crosses
  the link as 1 byte instead of 4, which is the whole point on a DCN-bound
  mesh; :meth:`BucketLayout.wire_bytes` does the exact accounting.

Also here (it is link plumbing, not data plumbing):
:func:`measure_h2d_mbps`, the host→device bandwidth probe ``fit()``
uses to tag link-bound runs instead of failing silently slow.
"""

from __future__ import annotations

import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from jax.lax import axis_size

# DDP's default bucket is 25 MB; ours is element-denominated so the int8 and
# fp32 accounting share it: 4 Mi elements = 16 MB fp32 / 4 MB int8 per
# bucket. Big enough that the per-bucket fp32 scale is <0.0001% overhead,
# small enough that a 124M-param model still spreads over ~30 buckets.
DEFAULT_BUCKET_ELEMS = 4 * 1024 * 1024


class BucketLayout:
    """How a gradient pytree maps onto fixed-size reduction buckets.

    ``flatten`` concatenates every leaf (raveled, cast fp32) into one vector,
    zero-pads it to ``n_buckets * bucket_size``, and views it as
    ``[n_buckets, bucket_size]``; ``unflatten`` inverts exactly.
    ``n_buckets`` is rounded up to a multiple of ``world`` so the ring
    all-reduce can split the buckets into ``world`` equal chunks — the
    rounding is what creates all-zero padding buckets, which quantize to
    q=0/scale=1 and cost wire bytes but no correctness (the "empty bucket"
    degenerate case is a first-class citizen, not an error).

    Shapes only — a layout built from a concrete tree, a tracer tree, or a
    ``jax.eval_shape`` result is the same layout.
    """

    def __init__(self, tree, world: int, bucket_size: int = DEFAULT_BUCKET_ELEMS):
        if world < 1:
            raise ValueError(f"world must be >= 1, got {world}")
        if bucket_size < 1:
            raise ValueError(f"bucket_size must be >= 1, got {bucket_size}")
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            raise ValueError("BucketLayout needs at least one leaf")
        self.shapes = [tuple(np.shape(x)) for x in leaves]
        self.dtypes = [jnp.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype
                       for x in leaves]
        self.sizes = [math.prod(s) for s in self.shapes]
        self.total = sum(self.sizes)
        self.world = int(world)
        # cap the bucket at the model's per-chunk share: a model smaller
        # than world × bucket_size would otherwise pad to world full-size
        # buckets and reduce megabytes of zeros for kilobytes of grads
        self.bucket_size = max(1, min(
            int(bucket_size), -(-self.total // self.world)
        ))
        n = -(-self.total // self.bucket_size)  # ceil
        self.n_buckets = n + (-n % self.world)
        self.padded_total = self.n_buckets * self.bucket_size
        self.buckets_per_chunk = self.n_buckets // self.world

    def flatten(self, tree) -> jax.Array:
        """Tree → ``[n_buckets, bucket_size]`` fp32 buckets."""
        leaves = self.treedef.flatten_up_to(tree)
        flat = jnp.concatenate(
            [jnp.ravel(x).astype(jnp.float32) for x in leaves]
        )
        flat = jnp.pad(flat, (0, self.padded_total - self.total))
        return flat.reshape(self.n_buckets, self.bucket_size)

    def unflatten(self, buckets: jax.Array):
        """``[n_buckets, bucket_size]`` buckets → tree (original dtypes)."""
        flat = jnp.ravel(buckets)
        leaves, off = [], 0
        for shape, size, dtype in zip(self.shapes, self.sizes, self.dtypes):
            leaves.append(
                jax.lax.dynamic_slice_in_dim(flat, off, size)
                .reshape(shape).astype(dtype)
            )
            off += size
        return self.treedef.unflatten(leaves)

    # -- wire-byte accounting ---------------------------------------------

    def wire_bytes(self, method: str, *, reductions: int = 1) -> int:
        """Bytes THIS replica moves over the link per step.

        ``"quantized"``: the explicit ring — ``2·(world-1)`` hops (RS + AG),
        each carrying one chunk of ``padded_total/world`` int8 elements plus
        its ``buckets_per_chunk`` fp32 scales.
        ``"bucketed"``: the explicit fp32 all-reduce at the classic
        bandwidth-optimal AR cost, ``2·(world-1)/world · N · 4`` — the same
        bytes XLA's implicit psum moves, so it doubles as the fp32 baseline
        the quantized ratio is quoted against.
        ``reductions`` scales for schedules that reduce more than once per
        step (the double-buffered grad-accumulation overlap reduces every
        microbatch, so its wire bytes are ``grad_accum`` times the
        single reduction's).
        """
        w, n = self.world, self.padded_total
        if w == 1:
            return 0
        if method == "quantized":
            per = 2 * (w - 1) * (n // w + self.buckets_per_chunk * 4)
        elif method == "bucketed":
            per = round(2 * (w - 1) / w * n * 4)
        else:
            raise ValueError(f"no wire accounting for method {method!r}")
        return per * reductions


def quantize_bucket(x: jax.Array, key: jax.Array | None = None):
    """Symmetric int8 quantization along the last axis (one scale per
    bucket): ``q = round(x / scale)`` with ``scale = amax/127``.

    With ``key``, rounding is stochastic — ``floor(y + u)``, ``u~U[0,1)`` —
    so ``E[dequantize(q)] = x`` exactly; the unbiasedness is what lets the
    error-feedback residual argument go through (the carried error is
    zero-mean noise, not drift). An all-zero bucket (padding, or a dead
    layer) gets scale 1 and q=0: exact. A NON-FINITE bucket keeps its
    non-finite amax as the scale, so the dequantized value is non-finite
    too: a NaN amax would otherwise fail the ``amax > 0`` test, fall back
    to scale 1, and cast the NaN to int8 0 — LAUNDERING a poisoned
    gradient into finite garbage that no downstream non-finite guard
    (which all run on the dequantized values) could ever catch. Returns
    ``(q int8, scale fp32)`` with scale shaped ``[..., 1]`` for
    broadcast-dequantization.
    """
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    scale = jnp.where(jnp.isfinite(amax), scale, amax).astype(jnp.float32)
    y = x / scale
    if key is None:
        q = jnp.round(y)
    else:
        q = jnp.floor(y + jax.random.uniform(key, y.shape))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ring_allreduce_quantized(
    chunks: jax.Array, axis_name: str, key: jax.Array
) -> jax.Array:
    """int8-wire ring all-reduce — call INSIDE ``shard_map``.

    ``chunks``: this replica's full local value, ``[world, bpc, B]`` fp32
    (``BucketLayout`` buckets viewed as ``world`` ring chunks). Returns the
    element-wise SUM over the ``axis_name`` replicas, bit-identical on every
    replica (each chunk's final owner quantizes the finished sum once and
    that one ``(q, scale)`` pair is what every replica — owner included —
    dequantizes, so replicated params stay replicated to the bit).

    Reduce-scatter phase: ``world-1`` hops; each hop quantizes the running
    partial sum (per-bucket scale, stochastic rounding), ships int8+scales
    one neighbor over, and the receiver dequantizes and adds in fp32 — the
    fp32 master accumulation; quantization exists only on the wire.
    All-gather phase: ``world-1`` more hops broadcasting each finished
    chunk's int8 form around the ring.

    ``key`` must already be folded with this replica's ``axis_index`` (each
    replica quantizes different values, so the stochastic-rounding noise
    must be independent across replicas — a shared key would correlate it).
    """
    w = axis_size(axis_name)
    if w == 1:
        return chunks
    rank = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % w) for j in range(w)]

    def rs_hop(acc, s):
        # send the chunk whose partial sum we just extended; receive our
        # predecessor's and extend it with our local contribution
        send_idx = (rank - s) % w
        blk = jax.lax.dynamic_index_in_dim(acc, send_idx, 0, keepdims=False)
        q, scale = quantize_bucket(blk, jax.random.fold_in(key, s))
        q = jax.lax.ppermute(q, axis_name, perm)
        scale = jax.lax.ppermute(scale, axis_name, perm)
        recv_idx = (rank - s - 1) % w
        upd = jax.lax.dynamic_index_in_dim(
            acc, recv_idx, 0, keepdims=False
        ) + dequantize(q, scale)
        return jax.lax.dynamic_update_index_in_dim(acc, upd, recv_idx, 0), None

    acc, _ = jax.lax.scan(rs_hop, chunks, jnp.arange(w - 1))

    # after w-1 hops, chunk (rank+1) % w holds the full sum on this rank
    own = (rank + 1) % w
    q0, s0 = quantize_bucket(
        jax.lax.dynamic_index_in_dim(acc, own, 0, keepdims=False),
        jax.random.fold_in(key, w),
    )
    out = jnp.zeros_like(chunks)
    out = jax.lax.dynamic_update_index_in_dim(out, dequantize(q0, s0), own, 0)

    def ag_hop(carry, s):
        out, q, scale = carry
        q = jax.lax.ppermute(q, axis_name, perm)
        scale = jax.lax.ppermute(scale, axis_name, perm)
        idx = (rank - s) % w  # hop s delivers the chunk owned by rank-s-1
        out = jax.lax.dynamic_update_index_in_dim(
            out, dequantize(q, scale), idx, 0
        )
        return (out, q, scale), None

    (out, _, _), _ = jax.lax.scan(ag_hop, (out, q0, s0), jnp.arange(w - 1))
    return out


def reduce_buckets(
    buckets: jax.Array,
    residual: jax.Array | None,
    layout: BucketLayout,
    axis_name: str,
    key: jax.Array,
    *,
    method: str,
):
    """One gradient reduction over ``axis_name`` — call INSIDE ``shard_map``.

    ``buckets``: this replica's local gradient buckets (``layout.flatten``
    output). Returns ``(mean_buckets, new_residual)`` where ``mean_buckets``
    is the cross-replica MEAN (what the optimizer consumes) and
    ``new_residual`` carries the error feedback (``None`` in/out when EF is
    off or the method is exact).

    ``"bucketed"`` is the explicit fp32 path: one ``lax.psum`` per call —
    exact, no residual; it isolates the restructuring (explicit reduction,
    double-buffered overlap) from the quantization so the two levers can be
    A/B'd independently. ``"quantized"`` quantizes ONCE locally (per-bucket
    int8, stochastic rounding), banks ``x - dequantize(Q(x))`` as the next
    step's residual, and ring-all-reduces the quantized value with int8 on
    every hop. The residual is added BEFORE quantization — error feedback:
    what one step drops, a later step transmits.
    """
    if method not in ("bucketed", "quantized"):
        raise ValueError(f"unknown reduce method {method!r}")
    # the exchange the program writes itself, named for the device trace
    with jax.named_scope("grad_exchange"):
        if method == "bucketed":
            mean = jax.lax.psum(buckets, axis_name) / axis_size(axis_name)
            return mean, residual
        x = buckets if residual is None else buckets + residual
        q0, s0 = quantize_bucket(x, jax.random.fold_in(key, 0))
        xq = dequantize(q0, s0)
        new_residual = None if residual is None else x - xq
        w = axis_size(axis_name)
        chunks = xq.reshape(w, layout.buckets_per_chunk, layout.bucket_size)
        total = ring_allreduce_quantized(
            chunks, axis_name, jax.random.fold_in(key, 1)
        )
        mean = total.reshape(layout.n_buckets, layout.bucket_size) / w
        return mean, new_residual


def measure_h2d_mbps(nbytes: int = 8 * 1024 * 1024) -> float:
    """Host→device link bandwidth, MB/s, by staging one ``nbytes`` buffer.

    Synced by fetching the buffer's last byte back, so the copy has
    landed when the clock stops. One 8 MB probe costs nothing next to a
    run; ``fit()`` uses it to tag staging-bound runs in telemetry instead
    of failing silently slow.
    """
    probe = np.zeros(max(int(nbytes), 1024), dtype=np.uint8)
    t0 = time.perf_counter()
    int(np.asarray(jax.device_put(probe)[-1]))
    return probe.nbytes / 1e6 / (time.perf_counter() - t0)


def multislice_dcn(devices: Any = None) -> bool:
    """True when the visible devices span more than one slice — i.e. the
    ``data`` axis crosses DCN, the regime where the quantized path pays
    (``reduce="auto"``'s decision input). Single-slice / CPU → False."""
    devices = jax.devices() if devices is None else devices
    return len({getattr(d, "slice_index", 0) for d in devices}) > 1
