"""Unit tests for the communication-efficiency layer's primitives
(tpudist.comm) and the explicit DP reducer's configuration surface
(tpudist.parallel.dp) — layout/quantization math on arrays, the int8-wire
ring on the 8-fake-device mesh. The train-step integration (trajectories,
composition with ZeRO-1 / skip_nonfinite) lives in test_dp_equivalence.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from tpudist import comm
from tpudist import mesh as mesh_lib
from tpudist.parallel import dp


# ---------------------------------------------------------------------------
# BucketLayout
# ---------------------------------------------------------------------------

def test_layout_roundtrip_non_divisible_leaves():
    """Leaf sizes chosen to divide NOTHING evenly: flatten/unflatten must be
    exact anyway (the pad-and-slice math is the bucket boundary case)."""
    tree = {
        "a": jnp.arange(37, dtype=jnp.float32).reshape(37),
        "b": jnp.arange(7 * 13, dtype=jnp.float32).reshape(7, 13) * 0.5,
        "c": jnp.asarray(3.25, jnp.float32),  # scalar leaf
    }
    layout = comm.BucketLayout(tree, world=8, bucket_size=16)
    buckets = layout.flatten(tree)
    assert buckets.shape == (layout.n_buckets, layout.bucket_size)
    assert layout.n_buckets % 8 == 0
    out = layout.unflatten(buckets)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(tree[k]))


def test_layout_single_leaf_and_dtype_restore():
    tree = {"w": jnp.ones((5, 11), jnp.bfloat16)}
    layout = comm.BucketLayout(tree, world=8, bucket_size=4)
    out = layout.unflatten(layout.flatten(tree))
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["w"], np.float32), np.ones((5, 11), np.float32)
    )


def test_layout_padding_is_zero_and_cap_prevents_blowup():
    """A model smaller than world × bucket_size must not pad to world
    full-size buckets: the bucket caps at the per-chunk share, and the
    padding that remains is exact zeros (the 'empty bucket' case)."""
    tree = {"w": jnp.ones(898, jnp.float32)}
    layout = comm.BucketLayout(tree, world=8, bucket_size=4 * 1024 * 1024)
    assert layout.bucket_size == -(-898 // 8)  # capped at ceil(total/world)
    assert layout.padded_total < 2 * 898 + 8 * layout.bucket_size
    flat = np.asarray(layout.flatten(tree)).ravel()
    np.testing.assert_array_equal(flat[898:], 0.0)
    np.testing.assert_array_equal(flat[:898], 1.0)


def test_layout_rejects_empty_tree_and_bad_sizes():
    with pytest.raises(ValueError):
        comm.BucketLayout({}, world=8)
    with pytest.raises(ValueError):
        comm.BucketLayout({"a": jnp.ones(4)}, world=0)
    with pytest.raises(ValueError):
        comm.BucketLayout({"a": jnp.ones(4)}, world=2, bucket_size=0)


def test_wire_bytes_quantized_beats_fp32_3x():
    layout = comm.BucketLayout({"w": jnp.ones(10_000)}, world=8,
                               bucket_size=1024)
    q = layout.wire_bytes("quantized")
    f = layout.wire_bytes("bucketed")
    assert q > 0 and f > 0
    assert f / q >= 3.0, (f, q)
    # schedules scale linearly; world=1 has no wire
    assert layout.wire_bytes("quantized", reductions=5) == 5 * q
    solo = comm.BucketLayout({"w": jnp.ones(10_000)}, world=1)
    assert solo.wire_bytes("quantized") == 0
    with pytest.raises(ValueError):
        layout.wire_bytes("nope")


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------

def test_quantize_deterministic_roundtrip_bound():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 256)),
                    jnp.float32)
    q, scale = comm.quantize_bucket(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(comm.dequantize(q, scale) - x))
    # round-to-nearest: error bounded by scale/2 per bucket
    assert (err <= np.asarray(scale) / 2 + 1e-7).all()


def test_quantize_zero_bucket_is_exact():
    x = jnp.zeros((3, 64), jnp.float32)
    q, scale = comm.quantize_bucket(x, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(scale), 1.0)


def test_quantize_propagates_nonfinite_instead_of_laundering():
    """A poisoned bucket must DEQUANTIZE non-finite: NaN amax fails the
    amax>0 test, so a naive scale fallback of 1.0 would cast the NaN to
    int8 0 and hand every downstream non-finite guard (they all run on
    the dequantized values) finite garbage — and bank NaN into the
    error-feedback residual forever. The scale keeps the non-finite amax
    so detection fires. Clean buckets in the same call stay exact."""
    x = jnp.asarray([[1.0, np.nan, 3.0, -2.0],
                     [1.0, 2.0, 3.0, -2.0],
                     [np.inf, 1.0, 0.0, 0.0]], jnp.float32)
    q, scale = comm.quantize_bucket(x)
    deq = np.asarray(comm.dequantize(q, scale))
    assert not np.isfinite(deq[0]).all()   # NaN bucket stays detectable
    assert not np.isfinite(deq[2]).all()   # inf bucket too
    np.testing.assert_allclose(deq[1], np.asarray(x)[1], atol=3 / 127 / 2)


def test_stochastic_rounding_is_unbiased():
    """E[dequantize(Q(x))] = x — the property the error-feedback argument
    rests on. Averaging over many keys must converge toward x well beyond
    what a biased (round-down/round-up) scheme could."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 128)),
                    jnp.float32)

    @jax.jit
    def deq(key):
        q, s = comm.quantize_bucket(x, key)
        return comm.dequantize(q, s)

    n = 512
    acc = np.zeros((1, 128), np.float64)
    for i in range(n):
        acc += np.asarray(deq(jax.random.key(i)), np.float64)
    mean = acc / n
    _, scale = comm.quantize_bucket(x)
    # one-draw error is ±scale; the n-average's std is ~scale/sqrt(n)
    tol = float(np.asarray(scale).ravel()[0]) * 6 / np.sqrt(n)
    np.testing.assert_allclose(mean, np.asarray(x, np.float64), atol=tol)


# ---------------------------------------------------------------------------
# the int8-wire ring on the 8-device mesh
# ---------------------------------------------------------------------------

def _ring_mesh():
    return mesh_lib.create_mesh()


def _run_ring(locals_np, fn_name="sum"):
    """Drive ring_allreduce_quantized inside shard_map: input [w, w, bpc, B]
    sharded on dim 0 = each replica's full local [w, bpc, B] value."""
    mesh = _ring_mesh()
    w = locals_np.shape[0]

    def body(x, key):
        local = x[0]
        k = jax.random.fold_in(key, jax.lax.axis_index("data"))
        return comm.ring_allreduce_quantized(local, "data", k)[None]

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data"),
        check_vma=False,
    )
    x = jax.device_put(locals_np, NamedSharding(mesh, P("data")))
    return np.asarray(jax.jit(fn)(x, jax.random.key(7)))


def test_ring_allreduce_sums_and_replicas_agree():
    w, bpc, B = 8, 2, 32
    locals_np = np.random.default_rng(0).normal(
        size=(w, w, bpc, B)).astype(np.float32)
    out = _run_ring(locals_np)
    expect = locals_np.sum(axis=0)
    # per-element error: each hop requantizes at per-bucket scale; with 2w
    # hops the accumulated noise stays a small multiple of the largest scale
    scale = np.abs(expect).max() / 127
    np.testing.assert_allclose(out[0], expect, atol=16 * scale)
    for r in range(1, w):
        # the bit-identical-replicas contract: every rank dequantizes the
        # SAME broadcast (q, scale), so replicated params stay replicated
        np.testing.assert_array_equal(out[r], out[0])


def test_reduce_buckets_bucketed_is_exact_mean():
    mesh = _ring_mesh()
    w = 8
    tree = {"w": jnp.ones(37)}
    layout = comm.BucketLayout(tree, world=w, bucket_size=8)
    locals_np = np.random.default_rng(1).normal(
        size=(w, layout.n_buckets, layout.bucket_size)).astype(np.float32)

    def body(x):
        mean, res = comm.reduce_buckets(
            x[0], None, layout, "data", jax.random.key(0), method="bucketed"
        )
        assert res is None
        return mean[None]

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False,
    )
    x = jax.device_put(locals_np, NamedSharding(mesh, P("data")))
    out = np.asarray(jax.jit(fn)(x))
    for r in range(w):
        np.testing.assert_allclose(
            out[r], locals_np.mean(axis=0), rtol=1e-6, atol=1e-6
        )


def test_reduce_buckets_error_feedback_banks_quantization_error():
    """new_residual must equal (x + old_residual) - dequantize(Q(...)):
    what the wire dropped this call is exactly what the next call adds."""
    mesh = _ring_mesh()
    w = 8
    layout = comm.BucketLayout({"w": jnp.ones(64)}, world=w, bucket_size=8)
    shape = (w, layout.n_buckets, layout.bucket_size)
    rng = np.random.default_rng(2)
    buckets_np = rng.normal(size=shape).astype(np.float32)
    res_np = rng.normal(size=shape).astype(np.float32) * 0.01

    def body(b, r):
        key = jax.random.fold_in(
            jax.random.key(3), jax.lax.axis_index("data")
        )
        mean, new_r = comm.reduce_buckets(
            b[0], r[0], layout, "data", key, method="quantized"
        )
        # reconstruct the transmitted value with the same key stream
        x = b[0] + r[0]
        q, s = comm.quantize_bucket(x, jax.random.fold_in(key, 0))
        expect_r = x - comm.dequantize(q, s)
        return mean[None], new_r[None], expect_r[None]

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")), check_vma=False,
    )
    sh = NamedSharding(mesh, P("data"))
    mean, new_r, expect_r = jax.jit(fn)(
        jax.device_put(buckets_np, sh), jax.device_put(res_np, sh)
    )
    np.testing.assert_allclose(
        np.asarray(new_r), np.asarray(expect_r), rtol=1e-6, atol=1e-7
    )
    # and the mean tracks the true mean of (x + residual)
    true = (buckets_np + res_np).mean(axis=0)
    scale = np.abs(buckets_np + res_np).max() / 127
    np.testing.assert_allclose(np.asarray(mean)[0], true, atol=20 * scale)


# ---------------------------------------------------------------------------
# GradReducer configuration surface
# ---------------------------------------------------------------------------

def test_resolve_method_rules():
    mesh8 = _ring_mesh()
    mesh1 = mesh_lib.create_mesh(devices=jax.devices()[:1])
    assert dp.resolve_method("none", mesh8) == "none"
    assert dp.resolve_method("bucketed", mesh8) == "bucketed"
    assert dp.resolve_method("quantized", mesh8) == "quantized"
    # CPU fake devices are single-slice: auto keeps the implicit path
    assert dp.resolve_method("auto", mesh8) == "none"
    # a 1-replica mesh has nothing to reduce, whatever was asked
    assert dp.resolve_method("quantized", mesh1) == "none"
    with pytest.raises(ValueError):
        dp.resolve_method("int4", mesh8)


def test_make_reducer_and_validation():
    mesh8 = _ring_mesh()
    assert dp.make_reducer("none", mesh8) is None
    assert dp.make_reducer("auto", mesh8) is None  # single-slice CPU
    r = dp.make_reducer("quantized", mesh8, bucket_size=32)
    assert isinstance(r, dp.GradReducer) and r.world == 8
    assert dp.make_reducer(r, mesh8) is r  # prebuilt passes through
    # bucketed never carries a residual
    rb = dp.make_reducer("bucketed", mesh8)
    assert rb.error_feedback is False
    # pure-DP guard: an fsdp-bearing mesh shards params — refused
    fsdp_mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=4, fsdp=2))
    with pytest.raises(ValueError, match="fsdp"):
        dp.GradReducer(fsdp_mesh, "quantized")
    with pytest.raises(ValueError, match="auto"):
        dp.GradReducer(mesh8, "auto")


def test_attach_residual_sharded_over_data():
    mesh = _ring_mesh()
    from tpudist.train import TrainState

    params = {"w": jnp.ones(100, jnp.float32)}
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=(), comm_residual=None,
    )
    r = dp.make_reducer("quantized", mesh, bucket_size=16)
    state = r.attach_residual(state)
    layout = r.layout_for(params)
    assert state.comm_residual.shape == (
        8, layout.n_buckets, layout.bucket_size
    )
    assert state.comm_residual.sharding.spec == P("data")
    np.testing.assert_array_equal(np.asarray(state.comm_residual), 0.0)
    # bucketed: no residual, state untouched
    state2 = dp.make_reducer("bucketed", mesh).attach_residual(state)
    assert state2 is state


def test_comm_stats_accounting():
    mesh = _ring_mesh()
    params = {"w": jnp.ones(10_000, jnp.float32)}
    r = dp.make_reducer("quantized", mesh, bucket_size=1024)
    s1 = r.comm_stats(params, grad_accum=1)
    s4 = r.comm_stats(params, grad_accum=4)
    assert s1["reductions_per_step"] == 1
    # the double-buffered EF scan drains one extra (residual-flush)
    # reduction
    assert s4["reductions_per_step"] == 5
    assert s4["bytes_per_step"] == 5 * s1["bytes_per_step"]
    assert s1["fp32_bytes_per_step"] >= 3 * s1["bytes_per_step"]
    assert s4["implicit_fp32_bytes_per_step"] == s1["fp32_bytes_per_step"]
    # residual-free configs have nothing to flush and nothing the per-micro
    # overlap's extra bytes would buy: one reduction, whatever the accum
    no_ef = dp.make_reducer("quantized", mesh, error_feedback=False)
    assert no_ef.comm_stats(params, grad_accum=4)["reductions_per_step"] == 1
    bucketed = dp.make_reducer("bucketed", mesh)
    assert bucketed.comm_stats(params, grad_accum=4)["reductions_per_step"] == 1


def test_h2d_probe_and_multislice_detection():
    mbps = comm.measure_h2d_mbps(1024 * 1024)
    assert mbps > 0
    assert comm.multislice_dcn() is False  # CPU fake devices: one slice
