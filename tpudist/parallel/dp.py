"""Explicit data-parallel gradient reduction: the DDP Reducer, TPU-native.

The framework default leaves the gradient all-reduce to XLA: the train step's
loss is the mean over the *global* batch, so ``jax.grad`` produces
already-reduced gradients and GSPMD inserts (and overlaps) the psum — the
right call on an ICI-only mesh, where the compiler's scheduling beats
anything hand-rolled. On a multi-slice pod the ``data`` axis crosses DCN and
the fp32 reduction becomes the dominant step-time term (arXiv:2204.06514);
this module is the opt-in explicit path for exactly that regime
(``make_train_step(reduce=...)``):

- gradients are computed PER REPLICA inside one ``shard_map`` over the
  ``data`` axis (the loss is the local-shard mean; its cross-replica mean —
  one scalar psum — reproduces the global-batch loss exactly);
- they are flattened into fixed-size buckets (:class:`tpudist.comm
  .BucketLayout` — the DDP-bucket equivalent) and all-reduced explicitly:
  ``"bucketed"`` as fp32 psum (isolates the restructuring), ``"quantized"``
  as int8 on the wire with per-bucket scales, stochastic rounding, fp32
  master accumulation, and an error-feedback residual carried in the train
  state (:func:`tpudist.comm.ring_allreduce_quantized` — the EQuARX recipe,
  arXiv:2506.17615) so convergence tracks fp32 within tolerance;
- with ``grad_accum > 1`` the reduction is double-buffered inside the
  accumulation scan: iteration ``i`` reduces microbatch ``i-1``'s buckets
  while computing microbatch ``i``'s forward/backward — the two have no
  data dependency, so XLA's scheduler overlaps the collective with compute
  (the async-bucket overlap DDP's C++ Reducer implements with hooks). The
  first iteration reduces the zero-initialized pending buffer, which doubles
  as the residual flush; one final reduction after the scan drains the last
  microbatch — ``grad_accum + 1`` reductions per step. Configurations
  WITHOUT a residual (``"bucketed"``, or ``error_feedback=False``) have
  nothing to flush and nothing the overlap's extra bytes would buy: they
  accumulate locally and reduce once after the scan — the implicit path's
  schedule, explicit. The EF path's trade in bytes: int8 pays for the extra
  reductions; fp32 would not.

Semantics vs the implicit path: identical gradients for ``"bucketed"`` (up
to fp32 reduction order) for deterministic forwards; models with
``dropout > 0`` draw independent per-REPLICA masks (the step key folded
with ``axis_index`` — DDP's exact dropout semantics) instead of the
implicit path's one global-batch draw, so dropout trajectories are
equivalent in distribution, not bitwise. ``"quantized"`` adds zero-mean
quantization noise bounded by the per-bucket scale, compensated across
steps by the residual.
Batch-norm: inside ``shard_map`` each replica computes LOCAL batch
statistics and the updated running stats are psum-averaged — the mean of
per-shard means IS the global batch mean (equal shards), the variance is
the DDP-default within-shard variance, not SyncBN's global one. ZeRO-1
(``shard_opt_state``) composes: grads come back replicated and dequantized,
so XLA's weight-update-sharding decomposition adds only the params
all-gather ZeRO-1 already pays — no second gradient reduction.

Restrictions (enforced loudly): pure DP only — params replicated, ``fsdp``
axis size 1, no ``batch_spec`` overrides (context-parallel models keep the
implicit path), no ``"_"``-prefixed device operands (DeviceCachedLoader
rides the implicit path), and models must NOT wrap their own ``shard_map``
(pass ``mesh=None`` to the model zoo: inside the reduction's manual region
the batch is already local, which is exactly what the kernels want).
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist import comm
from tpudist.mesh import DATA_AXIS, FSDP_AXIS

METHODS = ("none", "bucketed", "quantized", "auto")


def resolve_method(method: str, mesh: Mesh) -> str:
    """``"auto"`` → ``"quantized"`` when THIS mesh's ``data`` axis crosses
    DCN, ``"none"`` otherwise — on an ICI-only reduction the implicit XLA
    psum is already bandwidth-optimal in fp32 and the quantization would
    spend quality on bytes nothing is short of. The check walks one
    data-axis column of ``mesh.devices`` (not ``jax.devices()``: a mesh
    confined to one slice of a multi-slice attach — the other slice held
    by another job, or mapped to a model axis — reduces over ICI and must
    stay on the implicit path). A mesh with one ``data`` replica has
    nothing to reduce: always ``"none"``.

    ``"auto"`` on a mesh with ANY real non-data axis also resolves
    ``"none"`` — routing, not refusal: the explicit reducer cannot run on
    such a mesh anyway, axis by axis — ``fsdp`` trips the
    replicated-params guard below, ``tensor``/``pipe``/``expert`` models
    shard params (make_train_step's state-sharding guard), and ``seq``
    (context-parallel) models require the ``batch_spec`` the explicit
    path refuses — so an "auto" that resolved ``"quantized"`` there
    would only turn bring-up into a crash. Even a DCN-crossing data axis
    keeps the implicit GSPMD reduction on composed meshes; only an
    EXPLICIT ``"bucketed"``/``"quantized"`` request refuses loudly (the
    guards name the fix)."""
    if method not in METHODS:
        raise ValueError(f"reduce must be one of {METHODS}, got {method!r}")
    if int(mesh.shape[DATA_AXIS]) <= 1:
        return "none"
    if method == "auto":
        import numpy as np

        if any(
            int(size) > 1
            for name, size in mesh.shape.items()
            if name != DATA_AXIS
        ):
            return "none"
        data_column = np.asarray(mesh.devices).reshape(
            int(mesh.shape[DATA_AXIS]), -1
        )[:, 0]
        return "quantized" if comm.multislice_dcn(data_column) else "none"
    return method


class GradReducer:
    """The explicit-reduction engine ``make_train_step(reduce=...)`` builds.

    Holds the static configuration (mesh, method, bucket size, error
    feedback, stochastic-rounding seed); the bucket layout is derived on
    demand from whatever params tree it is shown (concrete, tracer, or
    eval_shape — same shapes, same layout), so construction needs no
    params.
    """

    def __init__(
        self,
        mesh: Mesh,
        method: str,
        *,
        bucket_size: int = comm.DEFAULT_BUCKET_ELEMS,
        error_feedback: bool = True,
        seed: int = 0,
    ):
        if method not in ("bucketed", "quantized"):
            raise ValueError(
                f"GradReducer method must be 'bucketed' or 'quantized', got "
                f"{method!r} (resolve 'auto' via resolve_method first)"
            )
        if int(mesh.shape[FSDP_AXIS]) != 1:
            raise ValueError(
                "explicit gradient reduction is pure-DP: it reduces over "
                "the 'data' axis only and requires replicated params, but "
                f"the mesh has fsdp={int(mesh.shape[FSDP_AXIS])} — keep "
                "reduce='none' (GSPMD already reduce-scatters per layer "
                "over 'fsdp'), or move those devices to the data axis "
                "(MeshConfig(data=-1, fsdp=1) / ParallelPlan.build("
                "data=-1)) before asking for the explicit wire format"
            )
        self.mesh = mesh
        self.method = method
        self.bucket_size = int(bucket_size)
        # error feedback only means something when the wire is lossy
        self.error_feedback = bool(error_feedback) and method == "quantized"
        self.seed = int(seed)
        self.world = int(mesh.shape[DATA_AXIS])

    # -- layout / residual -------------------------------------------------

    def layout_for(self, params) -> comm.BucketLayout:
        return comm.BucketLayout(
            params, self.world, bucket_size=self.bucket_size
        )

    def attach_residual(self, state):
        """Return ``state`` with a zeroed error-feedback residual in
        ``comm_residual`` — ``[world, n_buckets, bucket_size]`` fp32,
        sharded over ``data`` so each replica stores only its own slice
        (the residual is PER-REPLICA local state: each replica's
        quantization error differs). Allocated sharded directly on the
        devices; the full array never exists on the host. No-op when the
        method needs no residual."""
        if not self.error_feedback:
            return state
        layout = self.layout_for(state.params)
        sh = self.residual_sharding()
        shape = (self.world, layout.n_buckets, layout.bucket_size)
        zeros = jax.jit(
            lambda: jnp.zeros(shape, jnp.float32), out_shardings=sh
        )()
        return state.replace(comm_residual=zeros)

    def residual_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(DATA_AXIS))

    # -- the in-step compute path -----------------------------------------

    def compute(self, grad_fn: Callable, params, batch_stats, rows, step,
                residual, grad_accum: int):
        """The explicit-path replacement for the train step's gradient
        block: local forward/backward per replica, explicit bucket
        reduction, replicated outputs.

        ``grad_fn``: ``(params, stats, batch, step) → ((loss, new_stats),
        grads)`` — exactly ``make_train_step``'s ``value_and_grad``.
        ``rows``: the staged batch dict (global arrays; leading dim —
        second with ``grad_accum > 1`` — sharded over ``data``). Returns
        ``(loss, grads, new_stats, new_residual)``, all replicated except
        the residual (``None`` when error feedback is off); grads are the
        cross-replica mean, dequantized — the values every downstream
        consumer (optimizer, non-finite guard, telemetry norms) sees.
        """
        layout = self.layout_for(params)
        use_ef = self.error_feedback
        if use_ef and residual is None:
            raise ValueError(
                "reduce='quantized' with error feedback needs the residual "
                "in the train state — initialize it once with "
                "step.grad_reducer.attach_residual(state) (fit() does this "
                "automatically)"
            )
        axis, method, world, seed = DATA_AXIS, self.method, self.world, self.seed

        def local_grad_fn(*args):
            # flax turns a Partitioned box's names into a sharding
            # constraint wherever a mesh is in context, and shard_map puts
            # its all-manual mesh there. Every `param` call re-evaluates
            # the partitioned initializer for its shape check, so the
            # model's `tensor` annotations would be applied inside the
            # manual region (DenseGeneral: to its flattened 2-D kernel),
            # which jax refuses. The region is per-replica code — no
            # logical sharding applies
            with jax.sharding.use_abstract_mesh(
                jax.sharding.AbstractMesh((), ())
            ):
                return grad_fn(*args)

        def local(params, stats, rows, step, res):
            # res: [1, n_buckets, bucket_size] block (or a zeros dummy when
            # EF is off — kept in the signature so both variants share one
            # spec tuple)
            r = res[0] if use_ef else None
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(seed), step),
                jax.lax.axis_index(axis),
            )
            if grad_accum == 1:
                (loss, new_stats), g = local_grad_fn(params, stats, rows, step)
                mean, r = comm.reduce_buckets(
                    layout.flatten(g), r, layout, axis,
                    jax.random.fold_in(key, 0), method=method,
                )
            elif use_ef:
                zeros = jnp.zeros(
                    (layout.n_buckets, layout.bucket_size), jnp.float32
                )

                def micro(carry, xs):
                    pending, rsum, stats, lsum, r = carry
                    mb, i = xs
                    # double buffer: reduce microbatch i-1's buckets (no
                    # data dependency on this iteration's grad_fn, so XLA
                    # overlaps the collective with the forward/backward);
                    # i=0 reduces the zero init, which flushes the residual
                    reduced, r = comm.reduce_buckets(
                        pending, r, layout, axis,
                        jax.random.fold_in(key, i), method=method,
                    )
                    rsum = rsum + reduced
                    (l, stats), g = local_grad_fn(
                        params, stats, mb, step * grad_accum + i
                    )
                    return (layout.flatten(g), rsum, stats, lsum + l, r), None

                carry = (zeros, zeros, stats, jnp.zeros((), jnp.float32), r)
                (pending, rsum, new_stats, lsum, r), _ = jax.lax.scan(
                    micro, carry, (rows, jnp.arange(grad_accum))
                )
                # drain the last microbatch's pending buckets
                reduced, r = comm.reduce_buckets(
                    pending, r, layout, axis,
                    jax.random.fold_in(key, grad_accum), method=method,
                )
                mean = (rsum + reduced) / grad_accum
                loss = lsum / grad_accum
            else:
                # no residual to flush (bucketed, or EF off): the zeroth
                # double-buffer reduction would move a full bucket set of
                # exact zeros — accumulate locally instead and reduce ONCE
                # after the scan (the implicit path's schedule, explicit).
                # Per-micro overlap is the quantized+EF path's trade; here
                # it would only buy accum× the bytes for nothing.
                def micro(carry, xs):
                    gsum, stats, lsum = carry
                    mb, i = xs
                    (l, stats), g = local_grad_fn(
                        params, stats, mb, step * grad_accum + i
                    )
                    return (gsum + layout.flatten(g), stats, lsum + l), None

                zeros = jnp.zeros(
                    (layout.n_buckets, layout.bucket_size), jnp.float32
                )
                (gsum, new_stats, lsum), _ = jax.lax.scan(
                    micro, (zeros, stats, jnp.zeros((), jnp.float32)),
                    (rows, jnp.arange(grad_accum)),
                )
                mean, r = comm.reduce_buckets(
                    gsum, None, layout, axis,
                    jax.random.fold_in(key, 0), method=method,
                )
                mean = mean / grad_accum
                loss = lsum / grad_accum
            # scalar psum: the cross-replica mean of local-shard means IS
            # the global-batch mean (equal shards by construction)
            loss = jax.lax.psum(loss, axis) / world
            # running BN stats: mean-of-means is the exact global batch
            # mean; variance stays within-shard (DDP-default, not SyncBN)
            new_stats = jax.tree_util.tree_map(
                lambda s: jax.lax.psum(s, axis) / world, new_stats
            )
            res_out = r[None] if use_ef else res
            return loss, mean, new_stats, res_out

        if use_ef:
            res_in = residual
        else:
            # structural dummy so the EF-on and EF-off programs share one
            # signature; [world, 1, 1] keeps it a few bytes per replica
            res_in = jnp.zeros((world, 1, 1), jnp.float32)
        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(), P(None, axis) if grad_accum > 1 else P(axis),
                      P(), P(axis)),
            out_specs=(P(), P(), P(), P(axis)),
            check_vma=False,
        )
        loss, mean_buckets, new_stats, res_out = fn(
            params, batch_stats, rows, step, res_in
        )
        grads = layout.unflatten(mean_buckets)
        return loss, grads, new_stats, (res_out if use_ef else None)

    # -- accounting / probing ---------------------------------------------

    def reductions_per_step(self, grad_accum: int) -> int:
        # the double-buffered EF scan reduces per microbatch plus the
        # residual flush; without a residual the step accumulates locally
        # and reduces once (no zeros-flush collective to pay for)
        if grad_accum == 1 or not self.error_feedback:
            return 1
        return grad_accum + 1

    def comm_stats(self, params, grad_accum: int = 1) -> dict[str, Any]:
        """Host-side wire accounting for one step at this configuration:
        the actual method's bytes, the same-schedule fp32 bytes (the
        apples-to-apples A/B the ≥3× compression claim is quoted against),
        and the single-AR fp32 bytes XLA's implicit path would move (the
        absolute baseline — with microbatch overlap the explicit path
        trades some of its 4× bytes win for latency hiding)."""
        layout = self.layout_for(params)
        r = self.reductions_per_step(grad_accum)
        return {
            "method": self.method,
            "world": self.world,
            "bucket_size": layout.bucket_size,
            "n_buckets": layout.n_buckets,
            "grad_elems": layout.total,
            "error_feedback": self.error_feedback,
            "reductions_per_step": r,
            "bytes_per_step": layout.wire_bytes(self.method, reductions=r),
            "fp32_bytes_per_step": layout.wire_bytes("bucketed", reductions=r),
            "implicit_fp32_bytes_per_step": layout.wire_bytes(
                "bucketed", reductions=1
            ),
        }

    def time_probe(self, params, grad_accum: int = 1, iters: int = 3) -> float:
        """Measured seconds of one step's reductions, STANDALONE: the
        reduce-only program (no model compute to overlap with) run on
        zeroed buckets, synced by value fetch. An upper bound on the
        per-step comm cost — with the double-buffered scan, part of it
        hides behind the microbatch compute. This is the ``comm`` column
        fit()'s step-time breakdown carries; one small compile, run once
        at bring-up."""
        layout = self.layout_for(params)
        axis, method, seed, use_ef = (
            DATA_AXIS, self.method, self.seed, self.error_feedback
        )

        def local(buckets, res):
            key = jax.random.fold_in(
                jax.random.key(seed), jax.lax.axis_index(axis)
            )
            mean, r = comm.reduce_buckets(
                buckets[0], res[0] if use_ef else None, layout, axis, key,
                method=method,
            )
            return mean, (r[None] if use_ef else res)

        fn = jax.jit(shard_map(
            local, mesh=self.mesh,
            in_specs=(P(axis), P(axis)), out_specs=(P(), P(axis)),
            check_vma=False,
        ))
        shape = (self.world, layout.n_buckets, layout.bucket_size)
        sh = self.residual_sharding()
        buckets = jax.jit(
            lambda: jnp.zeros(shape, jnp.float32), out_shardings=sh
        )()
        res = buckets if use_ef else jax.jit(
            lambda: jnp.zeros((self.world, 1, 1), jnp.float32),
            out_shardings=sh,
        )()
        best = float("inf")
        for _ in range(max(iters, 1) + 1):  # first run includes the compile
            t0 = time.perf_counter()
            mean, res = fn(buckets, res)
            float(mean[0, 0])  # a value fetch: the sync that ends the timing
            best = min(best, time.perf_counter() - t0)
        return best * self.reductions_per_step(grad_accum)


_UINT_OF_SIZE = {1: "uint8", 2: "uint16", 4: "uint32"}


def _bit_checksum(x) -> jax.Array:
    """Order-independent uint32 wraparound sum of a block's raw BITS —
    exact, so a single flipped bit anywhere in the block changes the value
    (a float sum would hide a low-mantissa flip in a 100M-element tree
    under fp32 accumulation error). Modular uint32 arithmetic keeps the
    reduction deterministic and cheap; bool widens to uint8, 8-byte leaves
    bitcast to a trailing pair of uint32 words."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = jnp.dtype(x.dtype).itemsize
    if size == 8:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    else:
        u = jax.lax.bitcast_convert_type(
            x, jnp.dtype(_UINT_OF_SIZE[size])
        )
    return jnp.sum(u.astype(jnp.uint32), dtype=jnp.uint32)


def make_divergence_probe(state, mesh: Mesh):
    """Compiled replica-divergence probe over the ``data`` axis — the
    in-graph detector for the silent multi-host failure mode where
    "data-parallel" replicas desync (missed collective, bit corruption,
    a host restarting from the wrong step) and the job quietly trains W
    different models (tpudist.telemetry.health drives it at a cadence).

    Built from a placed ``state`` (a :class:`~tpudist.train.TrainState`,
    or any pytree of mesh-placed arrays); the probe keys off each leaf's
    ACTUAL sharding, so it composes with every reduction path — implicit
    XLA psum, the explicit ``GradReducer`` shard_map (whose per-replica
    dropout/quantization must still produce bit-identical replicated
    params), and ZeRO-1 ``shard_opt_state``:

    - leaves whose spec does NOT touch ``data``/``fsdp`` (params, BN
      stats, replicated opt leaves — possibly TP-sharded over other axes)
      are REPLICATED across data replicas by contract: each replica's
      local copy is bit-checksummed and all-gathered over ``data``
      within its mesh column, and the WORST column's verdict is pmax'd
      across the remaining axes — ``replica_divergence`` counts replicas
      disagreeing with replica 0 (a desync in a TP column other than 0
      still surfaces in the fetched scalar; a fully-desynced replica
      counts once, not once per column — max, not sum, so the count
      stays a replica count). Any single-bit desync is visible within
      one probe; desyncs confined to DIFFERENT columns may under-count
      but never read zero.
    - leaves sharded over ``data``/``fsdp`` (ZeRO-1's ``[world, ...]``
      Adam mirrors) hold a DIFFERENT shard per replica — no redundancy to
      compare, so they contribute an all-axes-psum'd global checksum
      (``sharded_checksum``, drift-over-restarts evidence for the crash
      report) and an all-axes-psum'd non-finite element count folded into
      ``state_nonfinite`` (plus the worst device's replicated-leaf
      count), the realistic corruption signal for unreplicated state —
      counted no matter which mesh coordinate holds the poisoned shard.

    Returns ``None`` when the mesh has one ``data`` replica (nothing to
    compare), else a jitted ``probe(state) -> {"replica_divergence",
    "replica_checksum", "sharded_checksum", "state_nonfinite"}`` whose
    scalars ride ``copy_to_host_async`` like the step metrics. Cost: one
    bandwidth-bound read of the state plus scalar collectives at the
    probe's cadence (its share of a step: not measured on the chip).
    """
    if int(mesh.shape[DATA_AXIS]) <= 1:
        return None

    def _tree(s):
        if hasattr(s, "params"):
            return (s.params, getattr(s, "batch_stats", ()), s.opt_state)
        return s

    leaves = jax.tree_util.tree_leaves(_tree(state))
    rep_idx, sh_idx, rep_specs, sh_specs = [], [], [], []
    for i, x in enumerate(leaves):
        spec = getattr(getattr(x, "sharding", None), "spec", None)
        if spec is None:
            continue  # host scalars / unplaced leaves: nothing to probe
        names: set = set()
        for part in spec:
            names.update(part if isinstance(part, tuple) else (part,))
        names.discard(None)
        if names & {DATA_AXIS, FSDP_AXIS}:
            sh_idx.append(i)
            sh_specs.append(spec)
        else:
            rep_idx.append(i)
            rep_specs.append(spec)

    all_axes = tuple(mesh.axis_names)
    other_axes = tuple(n for n in all_axes if n != DATA_AXIS)

    def local(rep, sharded):
        cks = jnp.uint32(0)
        nonfin = jnp.int32(0)
        for x in rep:
            cks = cks + _bit_checksum(x)
            if jnp.issubdtype(x.dtype, jnp.inexact):
                nonfin = nonfin + jnp.sum(
                    ~jnp.isfinite(x), dtype=jnp.int32
                )
        # the cross-replica comparison happens WITHIN each data column
        # (devices sharing the other axes' coordinates hold the same
        # logical block); the WORST column's verdict is then pmax'd
        # across the remaining axes so every device — including the one
        # the fetched scalar comes from — reports fleet-wide detection
        # (out_specs=P() must be true, not asserted). Max, not sum: a
        # fully-desynced replica corrupts every TP column and must count
        # as ONE bad replica, not tensor-size of them (a sum would tell
        # the operator 8 replicas diverged on an 8-way-TP mesh when one
        # did); independent desyncs confined to different columns may
        # under-count, but never read zero.
        gathered = jax.lax.all_gather(cks, DATA_AXIS)
        column = jnp.sum((gathered != gathered[0]).astype(jnp.int32))
        diverged = (
            jax.lax.pmax(column, other_axes) if other_axes else column
        )
        # replica 0's checksum (uniform along data even when a replica
        # diverged), fleet-summed over the other axes — drift evidence
        rep_cks = (
            jax.lax.psum(gathered[0], other_axes)
            if other_axes else gathered[0]
        )
        scks = jnp.uint32(0)
        snf = jnp.int32(0)
        for x in sharded:
            scks = scks + _bit_checksum(x)
            if jnp.issubdtype(x.dtype, jnp.inexact):
                snf = snf + jnp.sum(~jnp.isfinite(x), dtype=jnp.int32)
        # sharded-group sums cover EVERY axis: a ZeRO-1/fsdp shard's NaN
        # must surface no matter which mesh coordinate holds it (a leaf
        # replicated along some axis gets counted once per holding device
        # — over-reporting, never missing)
        scks = jax.lax.psum(scks, all_axes)
        snf = jax.lax.psum(snf, all_axes)
        # replicated-leaf non-finites: the worst device's count (replicas
        # hold copies, so a sum would inflate world-fold; max is uniform
        # and exact on a healthy fleet)
        nonfin = jax.lax.pmax(nonfin, all_axes)
        return {
            "replica_divergence": diverged,
            "replica_checksum": rep_cks,
            "sharded_checksum": scks,
            "state_nonfinite": nonfin + snf,
        }

    fn = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(tuple(rep_specs), tuple(sh_specs)),
        out_specs=P(),
        check_vma=False,
    ))

    def probe(state):
        leaves = jax.tree_util.tree_leaves(_tree(state))
        return fn(
            tuple(leaves[i] for i in rep_idx),
            tuple(leaves[i] for i in sh_idx),
        )

    return probe


def make_reducer(
    reduce: "str | GradReducer",
    mesh: Mesh,
    *,
    bucket_size: int = comm.DEFAULT_BUCKET_ELEMS,
    error_feedback: bool = True,
    seed: int = 0,
) -> GradReducer | None:
    """``make_train_step``'s constructor: a method name (``"none"`` /
    ``"bucketed"`` / ``"quantized"`` / ``"auto"``) or an already-built
    :class:`GradReducer` → the reducer to use, or ``None`` for the implicit
    XLA path (``"none"``, ``"auto"`` off DCN, or a 1-replica mesh)."""
    if isinstance(reduce, GradReducer):
        return reduce
    method = resolve_method(reduce, mesh)
    if method == "none":
        return None
    return GradReducer(
        mesh, method,
        bucket_size=bucket_size, error_feedback=error_feedback, seed=seed,
    )
