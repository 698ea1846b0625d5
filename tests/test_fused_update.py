"""One-pass fused AdamW (tpudist/ops/fused_update.py, optim.fused_adamw)
pinned against the optax reference chain — bit for bit per leaf shape, with
bf16 gradients on f32 masters and the bf16 copy; ulp-level over compounding
steps of a mixed tree — plus the guard that nothing params-sized is padded,
reshaped or sliced around the update, the compute-copy contract, edge
leaves (1-element, odd sizes), and the skip_nonfinite / decay-mask / clip /
schedule compositions."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist.optim import (
    FusedAdamWState,
    decay_mask,
    fused_adamw,
    fused_compute_params,
    find_fused,
    make_optimizer,
    refresh_fused_compute,
)


def _tree(seed=0):
    r = np.random.Generator(np.random.PCG64(seed))
    return {
        # sizes off every tile: a last dimension past 128, an odd 1-D leaf
        "w": jnp.asarray(r.standard_normal((40, 130)), jnp.float32),
        "big": jnp.asarray(r.standard_normal(9001), jnp.float32),
        # a bias under one (8, 128) tile
        "b": jnp.asarray(r.standard_normal(7), jnp.float32),
        # the 1-element edge leaf
        "one": jnp.asarray(r.standard_normal(1)[0], jnp.float32),
    }


def _grads(params, seed):
    r = np.random.Generator(np.random.PCG64(seed))
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(r.standard_normal(p.shape), p.dtype) * 0.1,
        params,
    )


def _run(tx, params, n_steps=5, grad_dtype=None, compiler_options=None):
    state = tx.init(params)

    @functools.partial(jax.jit, compiler_options=compiler_options)
    def step(p, s, g):
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    for i in range(n_steps):
        grads = _grads(params, 100 + i)
        if grad_dtype is not None:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(grad_dtype), grads
            )
        params, state = step(params, state, grads)
    return params, state


@pytest.mark.parametrize("wd,clip,sched", [
    (0.0, None, False),        # plain adam
    (0.1, None, False),        # adamw + decay mask
    (0.1, 1.0, True),          # + global-norm clip + lr schedule
], ids=["adam", "adamw_mask", "clip_sched"])
def test_matches_optax_chain(wd, clip, sched):
    params = _tree()
    lr = optax.cosine_decay_schedule(1e-2, 50) if sched else 1e-2
    ftx = fused_adamw(lr, weight_decay=wd, mask=decay_mask if wd else None,
                      clip_norm=clip)
    parts = ([optax.clip_by_global_norm(clip)] if clip else []) + [
        optax.adamw(lr, weight_decay=wd, mask=decay_mask) if wd
        else optax.adam(lr)
    ]
    rtx = optax.chain(*parts) if len(parts) > 1 else parts[0]

    fp, fs = _run(ftx, params)
    rp, rs = _run(rtx, params)
    # the update is optax-order arithmetic in one expression per leaf; with
    # four leaves in one program it can differ from optax by an ulp of XLA
    # fusion reassociation across 5 compounding Adam steps, no more (the
    # bars are absolute, at ~2.0-magnitude params: ~1-4 float32 ulps)
    for key in ("b", "one"):
        np.testing.assert_allclose(
            np.asarray(fp[key]), np.asarray(rp[key]), atol=5e-7, rtol=0
        )
    for key in ("w", "big"):
        np.testing.assert_allclose(
            np.asarray(fp[key]), np.asarray(rp[key]), atol=1e-6, rtol=0
        )


# the leaf shapes the cells and the other families have: GPT-2/BERT's qkv
# and out kernels (heads split), a vocabulary no tile divides, a bias, a
# conv kernel, a leaf under one (8, 128) tile
LEAF_SHAPES = [(1024, 3, 16, 64), (16, 64, 1024), (1027, 256), (4096,),
               (7, 7, 3, 64), (5, 13)]


@pytest.mark.parametrize(
    "shape", LEAF_SHAPES, ids=["x".join(map(str, s)) for s in LEAF_SHAPES]
)
def test_leaf_shapes_bit_equal_optax_chain(shape):
    """The cells' regime, leaf by leaf: bf16 gradients (taken with respect
    to the bf16 copy) on f32 masters and moments, clip, masked decay, the
    bf16 copy. Bit-equal to the optax chain given the same gradients —
    clipped in f32 and rounded back to bf16, then upcast to f32, as
    ``adamw_math`` takes them (optax alone would take the norm and form
    ``(1−b1)·g`` and ``g²`` in bf16)."""
    r = np.random.Generator(np.random.PCG64(3))
    params = {"kernel": jnp.asarray(r.standard_normal(shape), jnp.float32)}
    ftx = fused_adamw(1e-2, weight_decay=0.1, mask=decay_mask, clip_norm=1.0,
                      compute_dtype=jnp.bfloat16)
    cast = lambda dtype: optax.stateless(lambda u, _: jax.tree_util.tree_map(
        lambda t: t.astype(dtype), u))
    rtx = optax.chain(
        cast(jnp.float32),
        optax.clip_by_global_norm(1.0),
        cast(jnp.bfloat16),
        cast(jnp.float32),
        optax.adamw(1e-2, weight_decay=0.1, mask=decay_mask),
    )

    fp, fs = _run(ftx, params, n_steps=3, grad_dtype=jnp.bfloat16)
    rp, rs = _run(rtx, params, n_steps=3, grad_dtype=jnp.bfloat16)
    adam = rs[-1][0]
    for got, want in [(fp, rp), (fs.mu, adam.mu), (fs.nu, adam.nu)]:
        assert got["kernel"].dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(got["kernel"]), np.asarray(want["kernel"])
        )
    assert fs.compute["kernel"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(fs.compute["kernel"], np.float32),
        np.asarray(rp["kernel"].astype(jnp.bfloat16), np.float32),
    )


def test_clip_of_bfloat16_gradients_takes_the_norm_in_float32():
    """A norm rounded to bf16 scales every leaf by one error (0.3% here,
    where 2.006 lies between bf16's 2.0 and 2.016): the clipped gradient's
    global norm is the clip's to rounding of its elements."""
    r = np.random.Generator(np.random.PCG64(5))
    grads = {k: r.standard_normal(shape) for k, shape in
             [("w", (64, 130)), ("b", (130,))]}
    scale = 2.006 / np.sqrt(sum(np.sum(g * g) for g in grads.values()))
    grads = {k: jnp.asarray(g * scale, jnp.bfloat16) for k, g in grads.items()}
    norm = np.sqrt(sum(np.sum(np.asarray(g, np.float64) ** 2)
                       for g in grads.values()))
    assert abs(float(jnp.bfloat16(norm)) - norm) / norm > 2e-3
    params = {k: jnp.zeros(g.shape, jnp.float32) for k, g in grads.items()}
    tx = fused_adamw(1e-3, b1=0.9, clip_norm=1.0)
    _, state = tx.update(grads, tx.init(params), params)
    clipped = np.sqrt(sum(np.sum(np.asarray(m, np.float64) ** 2)
                          for m in state.mu.values())) / (1 - 0.9)
    assert clipped == pytest.approx(1.0, abs=2e-4)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_update_moves_no_leaf_through_another_layout():
    """The mechanism, checked where no chip is needed: the update of a small
    GPT-2 tree holds no ``pad``, and no op that lays a leaf out anew (a
    reshape, a slice, a transpose, a concatenation of something leaf-sized).
    On a TPU each of those is a read and a write of the leaf (PERF.md §6,
    PR 26: 20 ms of a 190 ms step) around an update that is elementwise."""
    from flax import linen as nn

    from tpudist.models.gpt2 import GPT2

    model = GPT2(vocab_size=97, max_seq_len=32, hidden_dim=48, depth=2,
                 num_heads=4)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((2, 16), jnp.int32),
                           train=False)
    )["params"])
    tx = fused_adamw(1e-2, weight_decay=0.1, mask=decay_mask, clip_norm=1.0,
                     compute_dtype=jnp.bfloat16)
    grads = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16), params
    )

    def update(g, s, p):
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    jaxpr = jax.make_jaxpr(update)(grads, jax.eval_shape(tx.init, params),
                                   params)
    smallest = min(p.size for p in jax.tree_util.tree_leaves(params))
    assert smallest > 1
    relayouts = {"reshape", "slice", "dynamic_slice", "transpose",
                 "concatenate", "gather", "squeeze", "copy"}
    seen = set()
    for eqn in _eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        seen.add(name)
        assert name != "pad", eqn
        if name in relayouts:
            sizes = [v.aval.size for v in eqn.invars if hasattr(v, "aval")]
            assert max(sizes, default=0) < smallest, eqn
    # the walk saw the update: the moments' multiply-adds and the root
    assert {"mul", "add", "sqrt", "div"} <= seen


def test_decay_mask_actually_masks():
    """1-D leaves (mask False) must see NO decay: pin by diffing a decayed
    vs undecayed run on a zero gradient (pure-decay signal)."""
    params = _tree()
    zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
    tx = fused_adamw(1e-2, weight_decay=0.5, mask=decay_mask)
    u, _ = tx.update(zero_g, tx.init(params), params)
    assert float(jnp.max(jnp.abs(u["b"]))) == 0.0       # masked: no decay
    assert float(jnp.max(jnp.abs(u["one"]))) == 0.0
    assert float(jnp.max(jnp.abs(u["w"]))) > 0.0        # decayed


def test_compute_copy_is_cast_of_post_update_master():
    params = _tree()
    tx = fused_adamw(1e-2, compute_dtype=jnp.bfloat16)
    state = tx.init(params)
    copy = fused_compute_params(state, params)
    for c, p in zip(jax.tree_util.tree_leaves(copy),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(
            np.asarray(c, np.float32),
            np.asarray(p.astype(jnp.bfloat16), np.float32),
        )
    new_p, new_s = _run(tx, params, n_steps=3)
    copy = fused_compute_params(new_s, new_p)
    assert copy is not None
    for c, p in zip(jax.tree_util.tree_leaves(copy),
                    jax.tree_util.tree_leaves(new_p)):
        # BIT-identical to casting the post-update master — the invariant
        # that makes the copy-forward exactly the per-op-cast forward
        np.testing.assert_array_equal(
            np.asarray(c, np.float32),
            np.asarray(p.astype(jnp.bfloat16), np.float32),
        )


def test_no_copy_state_carries_zero_extra_leaves():
    params = _tree()
    tx = fused_adamw(1e-2)
    state = tx.init(params)
    assert state.compute == ()
    assert fused_compute_params(state, params) is None
    n_params = len(jax.tree_util.tree_leaves(params))
    # count + mu + nu, nothing else
    assert len(jax.tree_util.tree_leaves(state)) == 1 + 2 * n_params


def test_skip_nonfinite_freezes_fused_state():
    from tpudist.amp import skip_nonfinite, skipped_steps

    params = _tree()
    tx = skip_nonfinite(fused_adamw(1e-2, compute_dtype=jnp.bfloat16))
    assert find_fused(tx) is not None  # detection walks the wrapper
    state = tx.init(params)
    nan_g = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.nan, p.dtype), params
    )
    u, state2 = jax.jit(tx.update)(nan_g, state, params)
    assert skipped_steps(state2) == 1
    assert all(
        bool(jnp.all(x == 0)) for x in jax.tree_util.tree_leaves(u)
    )
    for a, b in zip(jax.tree_util.tree_leaves(state2[0].mu),
                    jax.tree_util.tree_leaves(state[0].mu)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the compute copy is state too: a poisoned step must not corrupt it
    for a, b in zip(jax.tree_util.tree_leaves(state2[0].compute),
                    jax.tree_util.tree_leaves(state[0].compute)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )


def test_refresh_fused_compute_recasts_and_declines():
    params = _tree()
    tx = fused_adamw(1e-2, compute_dtype=jnp.bfloat16)
    state = tx.init(params)
    warm = jax.tree_util.tree_map(lambda p: p + 1.0, params)
    fresh = refresh_fused_compute(state, warm)
    for c, p in zip(jax.tree_util.tree_leaves(fresh.compute),
                    jax.tree_util.tree_leaves(warm)):
        np.testing.assert_array_equal(
            np.asarray(c, np.float32),
            np.asarray(p.astype(jnp.bfloat16), np.float32),
        )
    # a foreign state passes through untouched
    foreign = optax.adam(1e-2).init(params)
    assert refresh_fused_compute(foreign, params) is foreign


def test_extraction_declines_shape_mismatch():
    """The copy is used ONLY when params-shaped leaf-for-leaf — a ZeRO-1
    pad-stored (or otherwise re-laid-out) copy must be declined whole."""
    params = _tree()
    tx = fused_adamw(1e-2, compute_dtype=jnp.bfloat16)
    state = tx.init(params)
    bad = state._replace(
        compute={**state.compute, "w": state.compute["w"].reshape(-1)}
    )
    assert fused_compute_params(bad, params) is None


def test_make_optimizer_fused_routes_and_validates():
    tx = make_optimizer(1e-3, fused=True, weight_decay=0.1, clip_norm=1.0,
                        compute_dtype=jnp.bfloat16)
    assert find_fused(tx) is not None
    tx2 = make_optimizer(1e-3, fused=True, skip_nonfinite_updates=True)
    assert find_fused(tx2) is not None
    with pytest.raises(ValueError, match="fused=True"):
        make_optimizer(1e-3, fused=True, optimizer="sgd")


def test_update_requires_params():
    tx = fused_adamw(1e-2)
    params = _tree()
    with pytest.raises(ValueError, match="requires params"):
        tx.update(_grads(params, 0), tx.init(params))


def test_boxed_init_preserves_partitioning_metadata():
    """create_train_state runs tx.init on flax-BOXED params; the moments
    and the compute copy must come out boxed with the same metadata (the
    property that lets TP/ZeRO shardings derive from the state tree)."""
    from flax import linen as nn

    boxed = {
        "w": nn.Partitioned(jnp.ones((4, 2048)), names=("tensor", None)),
        "b": jnp.zeros((3,)),
    }
    tx = fused_adamw(1e-2, compute_dtype=jnp.bfloat16)
    state = jax.eval_shape(tx.init, boxed)
    assert isinstance(state, FusedAdamWState)
    mu_w = jax.tree_util.tree_leaves(
        state.mu["w"], is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )[0]
    assert isinstance(mu_w, nn.Partitioned)
    assert mu_w.names == ("tensor", None)


def test_zero1_shard_state_composition_exact():
    """shard_state(fused_adamw) must produce the identical trajectory to
    plain fused_adamw — ZeRO-1 is a layout change, not a math change."""
    from tpudist import mesh as mesh_lib
    from tpudist.optim import shard_state

    mesh = mesh_lib.create_mesh()
    params = _tree()
    plain = fused_adamw(1e-2, weight_decay=0.1, mask=decay_mask,
                        compute_dtype=jnp.bfloat16)
    sharded = shard_state(plain, mesh, min_size=8)
    # Both programs are compiled with XLA's fusion pass off, so each runs
    # the update's arithmetic op by op, as written. Fused, they are the same
    # HLO arithmetic in two fusion structures — around the padded moments of
    # ``big`` XLA:CPU recomputes m' and v' inside the fusion that divides
    # them — and LLVM contracts multiply-adds to FMAs by what shares a loop:
    # 1 ulp on 65-136 of ``big``'s 9001 masters from step 2 on, moments and
    # copy bit-equal, and nothing at all with the ISA held under FMA
    # (PERF.md §6, PR 26). The math is what this test is about.
    as_written = {"xla_disable_hlo_passes": "fusion"}
    pp, ps = _run(plain, params, n_steps=4, compiler_options=as_written)
    sp, ss = _run(sharded, params, n_steps=4, compiler_options=as_written)
    for a, b in zip(jax.tree_util.tree_leaves(pp),
                    jax.tree_util.tree_leaves(sp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the state too, moments and copy, once the stored leaves are back in
    # the params' shapes (``big`` is the one that is padded and reshaped)
    assert {k for k, p in params.items()
            if ss.mu[k].shape != p.shape} == {"big"}
    for got, want in [(ss.mu, ps.mu), (ss.nu, ps.nu),
                      (ss.compute, ps.compute)]:
        for key, p in params.items():
            back = jnp.ravel(got[key])[:p.size].reshape(p.shape)
            assert back.dtype == want[key].dtype
            np.testing.assert_array_equal(
                np.asarray(back, np.float32), np.asarray(want[key], np.float32)
            )
