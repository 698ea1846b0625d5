"""Mixture-of-experts / expert parallelism (tpudist.parallel.ep).

The reference has no MoE (SURVEY.md §2.12) — these tests pin down the
routing math and the expert-sharded execution path the same way
test_dp_equivalence pins down DP: sharded ≡ unsharded, dispatch ≡ a
per-token reference computation.
"""

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from tpudist import mesh as mesh_lib
from tpudist.parallel.ep import (
    MoEMlp, Routing, dropless_moe, expert_capacity, top_k_dispatch,
)


def test_expert_capacity():
    # ceil(2*64/8)=16, ×1.25 → 20
    assert expert_capacity(64, 8, top_k=2, capacity_factor=1.25) == 20
    assert expert_capacity(3, 8, top_k=1, capacity_factor=1.0) == 1


def test_dispatch_matches_per_token_reference():
    """With ample capacity, MoE output == Σ_k gate_k · FFN_{e_k}(token)."""
    rng = np.random.Generator(np.random.PCG64(0))
    T, E, d = 16, 4, 8
    probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(T, E)), jnp.float32))
    capacity = T  # nothing can drop
    dispatch, combine, _ = top_k_dispatch(probs, 2, capacity)

    # every token assigned to exactly 2 experts, each in exactly one slot
    np.testing.assert_allclose(np.sum(dispatch, axis=(1, 2)), 2.0, rtol=1e-6)
    # combine weights renormalize the top-2 gates to 1
    np.testing.assert_allclose(np.sum(combine, axis=(1, 2)), 1.0, rtol=1e-5)

    # no slot double-booked
    assert np.max(np.sum(dispatch, axis=0)) <= 1.0 + 1e-6

    # dispatch→expert→combine reproduces per-token top-2 mixture
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, d, d)), jnp.float32)
    slots = jnp.einsum("tec,td->ecd", dispatch, x)
    out = jnp.einsum("ecd,edf->ecf", slots, w)
    y = jnp.einsum("tec,ecd->td", combine, out)

    top2 = np.argsort(-np.asarray(probs), axis=1)[:, :2]
    for t in range(T):
        e0, e1 = top2[t]
        g0, g1 = float(probs[t, e0]), float(probs[t, e1])
        g0, g1 = g0 / (g0 + g1), g1 / (g0 + g1)
        want = g0 * (x[t] @ w[e0]) + g1 * (x[t] @ w[e1])
        np.testing.assert_allclose(np.asarray(y[t]), np.asarray(want), rtol=2e-5, atol=1e-5)


def test_capacity_dropping():
    """Tokens beyond an expert's capacity contribute zero (not garbage)."""
    T, E = 8, 2
    # all tokens want expert 0
    probs = jnp.tile(jnp.asarray([[0.9, 0.1]], jnp.float32), (T, 1))
    dispatch, combine, _ = top_k_dispatch(probs, 1, capacity=3)
    # exactly 3 tokens land (token order), the rest drop
    assert float(jnp.sum(dispatch)) == 3.0
    np.testing.assert_allclose(
        np.sum(np.asarray(dispatch), axis=(1, 2)), [1, 1, 1, 0, 0, 0, 0, 0]
    )
    # dropped tokens have zero combine weight → residual passes them through
    assert float(jnp.sum(combine[3:])) == 0.0


def test_aux_loss_balanced_is_one():
    T, E = 64, 8
    probs = jnp.full((T, E), 1.0 / E, jnp.float32)
    # break argmax ties deterministically across experts
    probs = probs + jax.nn.one_hot(jnp.arange(T) % E, E) * 1e-4
    _, _, aux = top_k_dispatch(probs, 1, capacity=T)
    assert abs(float(aux) - 1.0) < 1e-2


def test_moe_layer_runs_and_sows_aux():
    layer = MoEMlp(num_experts=4, top_k=2, capacity_factor=2.0)
    x = jnp.asarray(
        np.random.Generator(np.random.PCG64(1)).normal(size=(2, 8, 16)), jnp.float32
    )
    variables = layer.init(jax.random.key(0), x)
    y, updates = layer.apply(variables, x, mutable=["losses"])
    assert y.shape == x.shape
    (aux,) = jax.tree_util.tree_leaves(updates["losses"])
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_expert_sharded_equals_unsharded():
    """The same MoE GPT-2 step on an expert=4 mesh and a 1-device mesh
    produces the same loss — expert parallelism changes placement, not math."""
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    rng = np.random.Generator(np.random.PCG64(2))
    tokens = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}

    losses = {}
    for name, cfg in {
        "single": mesh_lib.MeshConfig(data=1),
        "ep": mesh_lib.MeshConfig(data=2, expert=4),
    }.items():
        devices = jax.devices()[: 1 if name == "single" else 8]
        mesh = mesh_lib.create_mesh(cfg, devices=devices)
        model = GPT2(
            vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
            num_heads=2, num_experts=4, moe_every=1, capacity_factor=2.0,
            mesh=mesh,
        )
        tx = optax.adam(1e-3)
        state = create_train_state(
            model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", state_sharding=state_shardings_of(state),
        )
        state, metrics = step(state, tokens)
        losses[name] = float(metrics["loss"])

    assert np.isfinite(losses["single"])
    np.testing.assert_allclose(losses["single"], losses["ep"], rtol=2e-5)


# ---------------------------------------------------------------------------
# index dispatch: the einsum oracle is the bit-checked reference


def _layer(**kw):
    kw.setdefault("num_experts", 4)
    kw.setdefault("top_k", 2)
    kw.setdefault("capacity_factor", 2.0)
    return MoEMlp(**kw)


def _x(shape=(2, 16, 16), seed=5):
    rng = np.random.Generator(np.random.PCG64(seed))
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _unboxed_params(layer, x, seed=0):
    from flax import linen as nn

    return nn.meta.unbox(layer.init(jax.random.key(seed), x)["params"])


def test_index_dispatch_forward_parity():
    """fp32, top_k=2: dispatch and the expert FFN outputs are BIT-identical
    between impls (same slot contents, same einsums); the final gate-mix
    matches to ≤1 ulp — the einsum oracle's contraction accumulates with
    FMA (one rounding per term) where the index path's explicit
    multiply-add rounds the product first (ep._index_combine docstring)."""
    x = _x()
    ein, idx = _layer(dispatch_impl="einsum"), _layer(dispatch_impl="index")
    params = {"params": _unboxed_params(ein, x)}
    y_e = np.asarray(ein.apply(params, x))
    y_i = np.asarray(idx.apply(params, x))
    np.testing.assert_allclose(y_e, y_i, rtol=0, atol=5e-7)
    # …and the ulp-level agreement is real agreement, not a loose bar:
    # outputs are O(0.1), so 5e-7 is a handful of ulps
    assert np.max(np.abs(y_e)) > 0.05


@pytest.mark.slow
def test_index_dispatch_grad_parity():
    """Backward parity: the gather's transpose is a scatter-add, so expert
    and router grads match the einsum oracle to fp32 reduction-order
    tolerance (the loss includes the sowed aux, exercising the routing
    grads too)."""
    x = _x()

    def loss_fn(layer):
        def f(p):
            y, upd = layer.apply({"params": p}, x, mutable=["losses"])
            aux = sum(jax.tree_util.tree_leaves(upd["losses"]), 0.0)
            return jnp.sum(y * y) + aux
        return f

    ein, idx = _layer(dispatch_impl="einsum"), _layer(dispatch_impl="index")
    params = _unboxed_params(ein, x)
    g_e = jax.grad(loss_fn(ein))(params)
    g_i = jax.grad(loss_fn(idx))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_e),
                    jax.tree_util.tree_leaves(g_i)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


@pytest.mark.slow
def test_moe_dense_equivalence_when_experts_identical():
    """The dense-equivalence oracle: with every expert holding the SAME
    weights and capacity ample, top-2 routing is a no-op — the renormalized
    gates sum to 1 and the layer equals one dense gelu FFN."""
    x = _x((2, 8, 12), seed=7)
    for impl in ("einsum", "index"):
        layer = _layer(num_experts=4, capacity_factor=4.0,
                       dispatch_impl=impl)
        params = _unboxed_params(layer, x)
        params["w1"] = jnp.tile(params["w1"][:1], (4, 1, 1))
        params["w2"] = jnp.tile(params["w2"][:1], (4, 1, 1))
        y = layer.apply({"params": params}, x)
        want = jnp.einsum(
            "bsf,fd->bsd",
            jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["w1"][0])),
            params["w2"][0],
        )
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5
        )


@pytest.mark.slow
def test_capacity_drop_deterministic_and_impl_identical():
    """capacity_factor < 1 forces drops; both impls drop the SAME tokens
    (priority is token order — deterministic), so outputs are bit-stable
    run-to-run, agree across impls (to the combine's ulp — see the
    forward-parity test), and the dropped rate really is > 0."""
    x = _x((2, 32, 8), seed=9)
    outs = {}
    for impl in ("einsum", "index"):
        layer = _layer(num_experts=2, capacity_factor=0.5,
                       dispatch_impl=impl)
        params = {"params": _unboxed_params(layer, x)}
        y1, sown = layer.apply(params, x, mutable=["moe_stats"])
        y2 = layer.apply(params, x)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
        outs[impl] = np.asarray(y1)
        (dropped,) = [
            leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(sown["moe_stats"])[0]
            if any(getattr(p, "key", None) == "dropped" for p in path)
        ]
        assert float(dropped) > 0.0
    np.testing.assert_allclose(
        outs["einsum"], outs["index"], rtol=0, atol=5e-7
    )


@pytest.mark.slow
def test_index_sharded_matches_einsum_oracle():
    """The headline composition: index dispatch under a data×expert×tensor
    mesh (the explicit shard_map all-to-all) trains the same loss as the
    single-device einsum oracle."""
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    rng = np.random.Generator(np.random.PCG64(4))
    tokens = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}
    losses = {}
    for name, (cfg, n_dev, impl) in {
        "oracle": (mesh_lib.MeshConfig(data=1), 1, "einsum"),
        "sharded": (mesh_lib.MeshConfig(data=2, expert=2, tensor=2), 8,
                    "index"),
    }.items():
        mesh = mesh_lib.create_mesh(cfg, devices=jax.devices()[:n_dev])
        model = GPT2(
            vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
            num_heads=2, num_experts=4, moe_every=1, capacity_factor=2.0,
            moe_dispatch=impl, mesh=mesh,
        )
        tx = optax.adam(1e-3)
        # the shard_map path runs at init too: the sample batch must
        # divide the mesh's (data, fsdp) axes, unlike the GSPMD paths'
        # usual (1, S) probe
        state = create_train_state(
            model, 0, jnp.zeros((2, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", state_sharding=state_shardings_of(state),
        )
        state, metrics = step(state, tokens)
        losses[name] = float(metrics["loss"])
    assert np.isfinite(losses["oracle"])
    np.testing.assert_allclose(losses["sharded"], losses["oracle"], rtol=2e-5)


# ---------------------------------------------------------------------------
# router hardening: z-loss + jitter (off by default, byte-inert when off)


@pytest.mark.slow
def test_router_z_loss_sown_and_shrinks_logit_norms():
    x = _x()
    layer = _layer(router_z_loss=1.0)
    params = _unboxed_params(layer, x)
    # inflate the router so the z-loss has norm to shrink
    params["router"] = params["router"] * 10.0

    def zloss(p):
        _, upd = layer.apply({"params": p}, x, mutable=["losses"])
        return upd["losses"]["moe_router_z_loss"]

    before = float(zloss(params))
    assert np.isfinite(before) and before > 0
    g = jax.grad(lambda p: zloss(p))(params)
    after = float(zloss(jax.tree_util.tree_map(
        lambda a, b: a - 1e-2 * b, params, g
    )))
    assert after < before, f"z-loss did not shrink: {before} -> {after}"
    # off by default: the losses collection carries ONLY the aux loss
    off = _layer()
    _, upd = off.apply({"params": params}, x, mutable=["losses"])
    assert set(upd["losses"]) == {"moe_aux_loss"}


def test_router_jitter_gating():
    x = _x()
    jit_layer = _layer(router_jitter=0.2)
    params = {"params": _unboxed_params(jit_layer, x)}
    base = np.asarray(_layer().apply(params, x))
    # eval (deterministic=True) and the default (None): byte-identical to
    # the jitter-free layer — the knob is train-only
    np.testing.assert_array_equal(
        np.asarray(jit_layer.apply(params, x, deterministic=True)), base
    )
    np.testing.assert_array_equal(np.asarray(jit_layer.apply(params, x)), base)
    # train without an rng stream: a loud refusal, not silent determinism
    with pytest.raises(ValueError, match="dropout' rng"):
        jit_layer.apply(params, x, deterministic=False)
    # train with the stream: the routing input actually moves
    noisy = np.asarray(jit_layer.apply(
        params, x, deterministic=False, rngs={"dropout": jax.random.key(1)}
    ))
    assert not np.array_equal(noisy, base)


# ---------------------------------------------------------------------------
# composition: chunked CE, remat, step metrics


@pytest.mark.slow
def test_chunked_forward_carries_moe_aux():
    """chunked_lm_forward on an MoE model: the sowed aux loss survives the
    fused path — total == chunked-CE + aux, matching the plain forward."""
    from tpudist.models.gpt2 import GPT2, chunked_lm_forward
    from tpudist.train import lm_loss

    model = GPT2(
        vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2, num_heads=2,
        num_experts=4, moe_every=1, capacity_factor=2.0,
    )
    rng = np.random.Generator(np.random.PCG64(6))
    tokens = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)
    params = model.init(jax.random.key(0), tokens, train=False)["params"]
    fwd = chunked_lm_forward(model, chunk=8)
    chunked, _ = fwd(params, {}, {"tokens": tokens})
    logits, upd = model.apply(
        {"params": params}, tokens, train=True, mutable=["losses"]
    )
    aux = sum(jax.tree_util.tree_leaves(upd["losses"]), 0.0)
    want = lm_loss(logits, tokens) + aux
    assert float(aux) > 0  # the chunked total really includes a live aux
    np.testing.assert_allclose(float(chunked), float(want), rtol=1e-5)


@pytest.mark.slow
def test_moe_composes_with_remat_policy():
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, lm_loss, make_train_step

    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=2, expert=2), devices=jax.devices()[:4]
    )
    rng = np.random.Generator(np.random.PCG64(8))
    batch = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}
    losses = {}
    for policy in (None, "dots_saveable"):
        model = GPT2(
            vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
            num_heads=2, num_experts=4, capacity_factor=2.0,
            moe_dispatch="index", remat_policy=policy, mesh=mesh,
        )
        tx = optax.adam(1e-3)
        # the shard_map dispatch runs at init too: the sample batch must
        # divide the mesh's (data, fsdp) axes.
        state = create_train_state(
            model, 0, jnp.zeros((2, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens",
        )
        state, metrics = step(state, batch)
        losses[policy] = float(metrics["loss"])
    np.testing.assert_allclose(
        losses["dots_saveable"], losses[None], rtol=1e-6
    )


@pytest.mark.slow
def test_moe_step_metrics_behind_telemetry_flag():
    """Router stats ride the step metrics ONLY under telemetry=True
    (docs/OBSERVABILITY.md §1): load is per-expert [E] summing to
    1 − dropped; with telemetry off the keys are absent entirely."""
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, lm_loss, make_train_step

    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=2), devices=jax.devices()[:2]
    )
    model = GPT2(
        vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2, num_heads=2,
        num_experts=4, capacity_factor=2.0, mesh=mesh,
    )
    tx = optax.adam(1e-3)
    state = create_train_state(model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh)
    rng = np.random.Generator(np.random.PCG64(11))
    batch = {"tokens": rng.integers(0, 64, (4, 16)).astype(np.int32)}
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", telemetry=True,
    )
    state, metrics = step(state, batch)  # the step donates its input state
    # depth 2, moe_every 2 → block h_1 is the MoE block
    load = np.asarray(metrics["moe/h_1/load"])
    dropped = float(metrics["moe/h_1/dropped"])
    assert load.shape == (4,)
    np.testing.assert_allclose(float(load.sum()), 1.0 - dropped, rtol=1e-5)
    assert np.isfinite(float(metrics["moe/h_1/aux"]))
    plain = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens",
    )
    _, metrics = plain(state, batch)
    assert not [k for k in metrics if k.startswith("moe/")]


def test_moe_gpt2_loss_decreases():
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, lm_loss, make_train_step

    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, expert=4))
    model = GPT2(
        vocab_size=32, max_seq_len=16, hidden_dim=32, depth=2, num_heads=2,
        num_experts=4, capacity_factor=2.0, mesh=mesh,
    )
    tx = optax.adam(1e-2)
    state = create_train_state(model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh)
    from tpudist.train import state_shardings_of

    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", state_sharding=state_shardings_of(state),
    )
    rng = np.random.Generator(np.random.PCG64(3))
    batch = {"tokens": rng.integers(0, 32, (8, 16)).astype(np.int32)}
    first = None
    for _ in range(20):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first, f"loss did not decrease: {first} -> {last}"


# -- the dropless layer over held experts (ep.dropless_moe) -------------------


class _Dropless(nn.Module):
    """``dropless_moe`` under a parent of its own, as a block calls it."""

    routing: Routing
    ffn_dim: int

    @nn.compact
    def __call__(self, u, r=None):
        return dropless_moe(self, u, r, routing=self.routing,
                            ffn_dim=self.ffn_dim)


def _moe_inputs(T=48, d=16, seed=0):
    return jax.random.normal(jax.random.key(seed), (2, T // 2, d), jnp.float32)


@pytest.mark.parametrize("top_k", [1, 2])
def test_dropless_matches_einsum_oracle_when_nothing_drops(top_k):
    """Sorted rows + one grouped product ≡ the one-hot einsum layer given a
    capacity no token exceeds (one group, capacity = every token): outputs
    and the gradients of the input, the router and every expert weight.
    Float32; 1e-5 covers the different summation orders."""
    E, ff = 4, 24
    x = _moe_inputs()
    oracle = MoEMlp(num_experts=E, top_k=top_k, capacity_factor=float(E),
                    ffn_dim=ff, expert_act="swiglu", num_groups=1,
                    dispatch_impl="einsum")
    theirs = nn.meta.unbox(oracle.init(jax.random.key(1), x)["params"])
    ours = {"moe_router": {"kernel": theirs["router"]},
            "moe_experts": {k: theirs[k]
                            for k in ("w_gate", "w_up", "w_down")}}
    layer = _Dropless(Routing(E, top_k=top_k), ff)

    def f_ours(p, x):
        return layer.apply({"params": p}, x)[0]

    def f_theirs(p, x):
        return oracle.apply({"params": p}, x)

    np.testing.assert_allclose(f_ours(ours, x), f_theirs(theirs, x),
                               rtol=1e-5, atol=1e-6)
    probe = jax.random.normal(jax.random.key(2), x.shape)
    g_ours = jax.grad(lambda p, x: jnp.sum(f_ours(p, x) * probe),
                      argnums=(0, 1))(ours, x)
    g_theirs = jax.grad(lambda p, x: jnp.sum(f_theirs(p, x) * probe),
                        argnums=(0, 1))(theirs, x)
    np.testing.assert_allclose(g_ours[1], g_theirs[1], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(g_ours[0]["moe_router"]["kernel"],
                               g_theirs[0]["router"], rtol=1e-4, atol=1e-6)
    for k in ("w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(g_ours[0]["moe_experts"][k],
                                   g_theirs[0][k], rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("E,held", [(4, None), (128, (0, 16))],
                         ids=["all_of_4", "16_of_128_in_3_chunks"])
def test_dropless_drops_no_token_when_the_router_collapses(E, held):
    """A router forced onto one expert: every token is computed by it (the
    capacity layer at factor 1 zeroes all but T/E of them), and the
    counters say so — also where the sorted rows are cut into chunks sized
    for an eighth of them and the one expert fills every chunk."""
    d, ff = 16, 24
    x = jnp.abs(_moe_inputs(d=d)) + 0.1  # positive: column 2 always wins
    layer = _Dropless(Routing(E, top_k=1, held=held), ff)
    params = layer.init(jax.random.key(1), x)["params"]
    collapse = jnp.zeros((d, E)).at[:, 2].set(1.0)
    params["moe_router"]["kernel"] = collapse
    (y, _), sown = layer.apply({"params": params}, x, mutable=["moe_stats"])
    w = params["moe_experts"]
    gate = jax.nn.softmax(x @ collapse)[..., 2:3]
    dense = (jax.nn.silu(x @ w["w_gate"][2]) * (x @ w["w_up"][2])) \
        @ w["w_down"][2]
    np.testing.assert_allclose(y, gate * dense, rtol=1e-5, atol=1e-6)
    T = x.shape[0] * x.shape[1]
    count = layer.routing.held_range[1]
    stats = sown["moe_stats"]
    np.testing.assert_array_equal(
        stats["tokens"][0], [T if e == 2 else 0 for e in range(count)])
    assert float(stats["held_share"][0]) == 1.0
    assert float(stats["load_max_over_mean"][0]) == pytest.approx(count)
    assert float(stats["row_share_computed"][0]) == 1.0
    if held is not None:
        return

    capped = MoEMlp(num_experts=E, top_k=1, capacity_factor=1.0, ffn_dim=ff,
                    expert_act="swiglu", num_groups=1)
    theirs = {"router": collapse, **w}
    kept = jnp.any(capped.apply({"params": theirs}, x) != 0, axis=-1)
    assert int(jnp.sum(kept)) == T // E  # the rest were dropped


# (held, num_experts) -> the chunks the sorted rows are cut into at T = 64
_HELD = {"16_of_128": ((0, 16), 128, 4), "8_of_16": ((8, 8), 16, 1),
         "all": (None, 16, 1)}
_ROUTINGS = ("even", "collapsed", "edge", "none")


def _routed(x, kernel, routing, how):
    """``x`` and a router kernel that send the ``T·k`` choices where the
    case wants them: three leading features say what kind a token is —
    ``full`` (every choice on a held expert), ``one`` (its first choice on
    the first held expert, the others on experts not held), ``none``."""
    first, count = routing.held_range
    T = x.shape[0] * x.shape[1]
    held = jnp.zeros(routing.num_experts, bool).at[first:first + count].set(True)
    kinds = {"collapsed": (T, 0), "edge": (T // 4, 1), "none": (0, 0)}
    if how == "even":
        return x, kernel, None
    full, one = kinds[how]
    kind = jnp.where(jnp.arange(T) < full, 0,
                     jnp.where(jnp.arange(T) < full + one, 1, 2))
    x = x.at[..., :3].set(jax.nn.one_hot(kind, 3).reshape(*x.shape[:2], 3))
    kernel = kernel.at[0].set(jnp.where(held, 0.0, -30.0))
    kernel = kernel.at[1].set(jnp.where(held, -30.0, 0.0).at[first].set(30.0))
    kernel = kernel.at[2].set(jnp.where(held, -30.0, 0.0))
    if routing.held is None:  # nothing is "not held": every row is live
        return x, kernel, T * routing.top_k
    return x, kernel, min(count, routing.top_k) * full + one


@pytest.mark.parametrize(
    "top_k,held,how",
    [(k, h, how) for k in (1, 6, 8) for h in _HELD for how in _ROUTINGS
     if not (h == "all" and how == "none")])
def test_chunked_layer_is_the_one_chunk_layer(top_k, held, how, monkeypatch):
    """The sorted rows in chunks, the live ones computed, against the same
    function with the rows in ONE chunk (the layer with no control flow):
    the output and the gradients of the tokens, the router and every expert
    weight, whether the live rows fill a fraction of chunk 0 (``even``),
    every chunk (``collapsed``), chunk 0 and ONE row of the next
    (``edge``) or nothing (``none``). No row is dropped in any of them, and
    ``row_share_computed`` says what was paid. Float32; 1e-5 covers the
    sums taken chunk by chunk."""
    from tpudist.parallel import ep

    held_range, E, n_chunks = _HELD[held]
    routing = Routing(E, top_k=top_k, held=held_range)
    layer = _Dropless(routing, 24)
    x = _moe_inputs(T=64)
    rows = 64 * top_k
    chunk_rows = rows // n_chunks
    assert ep.row_chunks(rows, routing.held_range[1], E) \
        == (chunk_rows, n_chunks)
    params = layer.init(jax.random.key(1), x)["params"]
    x, kernel, n_live = _routed(x, params["moe_router"]["kernel"], routing,
                                how)
    params["moe_router"]["kernel"] = kernel
    probe = jax.random.normal(jax.random.key(2), x.shape)

    @jax.jit
    def run(params, x):
        def loss(params, x):
            (y, _), sown = layer.apply({"params": params}, x,
                                       mutable=["moe_stats"])
            return jnp.sum(y * probe), (y, sown["moe_stats"])
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)

    (_, (y, stats)), grads = run(params, x)
    if n_live is not None:
        assert int(jnp.sum(stats["tokens"][0])) == n_live
    live = int(jnp.sum(stats["tokens"][0]))
    ran = max(1, -(-live // chunk_rows))
    assert float(stats["row_share_computed"][0]) == ran / n_chunks
    if how == "edge" and n_chunks > 1:
        assert live == chunk_rows + 1 and ran == 2

    monkeypatch.setattr(ep, "row_chunks", lambda rows, count, E: (rows, 1))
    run.clear_cache()
    (_, (y1, stats1)), grads1 = run(params, x)
    assert float(stats1["row_share_computed"][0]) == 1.0
    np.testing.assert_array_equal(stats["tokens"][0], stats1["tokens"][0])
    np.testing.assert_allclose(y, y1, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        grads, grads1)
    if live:
        assert float(jnp.max(jnp.abs(y))) > 0


def _eqns(jaxpr):
    """Every equation of a jaxpr, the bodies of its loops, branches and
    custom rules included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _gather_rows(jaxpr, width):
    """Row counts of every ``[..rows, width]`` gather in a jaxpr, and the
    names of the control-flow primitives in it."""
    shapes = [eqn.outvars[0].aval.shape for eqn in _eqns(jaxpr)
              if eqn.primitive.name == "gather"]
    rows = [int(np.prod(shape[:-1])) for shape in shapes
            if len(shape) >= 2 and shape[-1] == width]
    control = {eqn.primitive.name for eqn in _eqns(jaxpr)
               if eqn.primitive.name in ("while", "cond", "scan")}
    return rows, control


@pytest.mark.parametrize("rows,top_k,count,E,want", [
    (49_152, 6, 16, 128, (12_288, 4)),   # kanana2_30b_train_s8192
    (65_536, 8, 16, 128, (16_384, 4)),   # sdar_30b_bd_train_s4096
    (16_384, 1, 8, 16, (16_384, 1)),     # zaya1_8b_train_s4096
])
def test_row_chunks_is_what_a_traced_layer_gathers(rows, top_k, count, E,
                                                   want):
    """The static counter: ``row_chunks`` at the three expert cells' row
    counts, against the gathers of a traced forward and backward at those
    rows — the dispatch and the combine's backward gather ``chunk_rows``
    rows, only the combine and the dispatch's backward still gather all
    ``T·k`` — and ONE chunk is the layer with no control flow at all."""
    from tpudist.parallel import ep

    chunk_rows, n_chunks = ep.row_chunks(rows, count, E)
    assert (chunk_rows, n_chunks) == want
    d = 8
    layer = _Dropless(Routing(E, top_k=top_k, held=(0, count)), 8)
    x = jax.ShapeDtypeStruct((1, rows // top_k, d), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]

    def loss(params, x):
        return jnp.sum(layer.apply({"params": params}, x)[0])

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    gathered, control = _gather_rows(traced.jaxpr, d)
    assert set(gathered) == {chunk_rows, rows}
    if n_chunks == 1:
        assert not control
    else:
        # chunk 0 straight and the loop's body, forward and backward twice
        # (the rows, then the rows' gradients); chunk 0 and a further
        # chunk for each of the two gathers that stay ``T·k`` rows wide
        assert gathered.count(chunk_rows) == 4 and gathered.count(rows) == 4
        assert control == {"while"}


def test_selection_bias_moves_the_choice_and_nothing_else():
    """``Routing.selection_bias`` decides WHICH expert a token takes; the
    gate stays the chosen expert's unbiased probability and the router's
    gradient is that gate's. A bias of nought is the plain top-1."""
    E, d, ff = 4, 16, 24
    x = _moe_inputs(d=d)
    plain = _Dropless(Routing(E, top_k=1), ff)
    params = plain.init(jax.random.key(1), x)["params"]
    onto_1 = lambda logits: jnp.zeros_like(logits).at[..., 1].set(1e3)
    forced = _Dropless(Routing(E, top_k=1, selection_bias=onto_1), ff)
    nought = _Dropless(Routing(E, top_k=1, selection_bias=jnp.zeros_like), ff)
    w = params["moe_experts"]

    def dense(router, x):
        gate = jax.nn.softmax(x @ router)[..., 1:2]
        return gate * ((jax.nn.silu(x @ w["w_gate"][1]) * (x @ w["w_up"][1]))
                       @ w["w_down"][1])

    def ours(layer, router, x):
        p = dict(params, moe_router={"kernel": router})
        return layer.apply({"params": p}, x)[0]

    router = params["moe_router"]["kernel"]
    np.testing.assert_allclose(ours(forced, router, x), dense(router, x),
                               rtol=1e-5, atol=1e-6)
    probe = jax.random.normal(jax.random.key(2), x.shape)
    got = jax.grad(lambda r: jnp.sum(ours(forced, r, x) * probe))(router)
    want = jax.grad(lambda r: jnp.sum(dense(r, x) * probe))(router)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(ours(nought, router, x),
                                  ours(plain, router, x))


@pytest.mark.parametrize("selection_bias", [None, "sequence_quantile"])
def test_two_shares_add_up_to_the_uncut_reference_layer(selection_bias):
    """The share test: experts 0-7 and 8-15 as two shares of the program's
    layer, each told what it holds and given its own half of the weights,
    add up to what the plain reference gives for the whole layer of 16 —
    the router (MLP, carried state, top-1) runs alike on both and is
    counted once; by the plain top-1, and by the ZAYA1 cell's bias on the
    selection (the family's function in the program, the reference's own
    lines there). Float32, 1e-5: summation order only."""
    from benchmarks.families import zaya as family
    from benchmarks.reference import zaya as reference
    E, d, ff, R = 16, 16, 24, 8
    x = jax.random.normal(jax.random.key(0), (2, 64, d), jnp.float32)
    r_prev = jax.random.normal(jax.random.key(3), (2, 64, R), jnp.float32)
    routing = dict(top_k=1, router="mlp", router_width=R,
                   selection_bias=family.SELECTION_BIAS[selection_bias])
    whole = _Dropless(Routing(E, **routing), ff)
    params = whole.init(jax.random.key(1), x, r_prev)["params"]
    # biases and scales off their initial 0 / 1, as the harness draws them
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.key(p.size), p.shape),
        params)

    flat = {"moe_router/" + "/".join(k.key for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(
                params["moe_router"])[0]}
    flat.update({f"moe_experts/{k}": v
                 for k, v in params["moe_experts"].items()})
    want, want_r = reference.expert_sublayer(
        x, r_prev, flat, num_experts=E, first=0, count=E, eps=1e-5,
        selection_bias=selection_bias)

    total, shares = 0.0, []
    for first in (0, 8):
        mine = dict(params, moe_experts={
            k: v[first:first + 8] for k, v in params["moe_experts"].items()})
        share = _Dropless(Routing(E, held=(first, 8), **routing), ff)
        (y, r), sown = share.apply({"params": mine}, x, r_prev,
                                   mutable=["moe_stats"])
        np.testing.assert_allclose(r, want_r, rtol=1e-5, atol=1e-6)
        total = total + y
        shares.append(float(sown["moe_stats"]["held_share"][0]))
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert sum(shares) == pytest.approx(1.0)
    assert 0.0 < shares[0] < 1.0  # both halves hold some of the tokens
    if selection_bias:  # ... about half each (loosely: 4 tokens an expert)
        assert abs(shares[0] - 0.5) < 0.2


def test_routing_says_what_it_cannot_hold():
    with pytest.raises(ValueError, match="held"):
        Routing(16, held=(12, 8))
    with pytest.raises(ValueError, match="scoring"):
        Routing(16, scoring="tanh")
    assert Routing(16, scoring="sigmoid").routed_scale == 1.0
    assert Routing(16).held_range == (0, 16)
    assert Routing(16, held=(8, 8)).held_range == (8, 8)


class _Relu2(nn.Module):
    """``dropless_moe`` of squared-ReLU experts beside a shared one."""

    routing: Routing
    ffn_dim: int

    @nn.compact
    def __call__(self, u):
        return dropless_moe(self, u, routing=self.routing,
                            ffn_dim=self.ffn_dim, shared_dim=12,
                            expert_act="relu2")[0]


@pytest.mark.parametrize("held,E,how", [((0, 4), 32, "collapsed"),
                                        ((8, 8), 16, "even")])
def test_relu2_experts_are_the_dense_oracle(held, E, how):
    """``expert_act="relu2"``: ``relu(x·w_up)²·w_down``, two matrices a
    held expert and the shared one, against the same layer written densely
    from the program's own selection — output and the gradients of the
    tokens, the router and every weight — through the chunked path (4 of
    32 held: 4 chunks, every one of them run when the router sends all
    choices to held experts) and the one-chunk path (8 of 16). Float32,
    1e-5: summation order only."""
    from tpudist.parallel import ep
    from tpudist.parallel.ep import relu2, select_experts

    routing = Routing(E, top_k=6, held=held, scoring="sigmoid",
                      routed_scale=2.5)
    assert ep.row_chunks(64 * 6, held[1], E)[1] == (4 if E == 32 else 1)
    layer = _Relu2(routing, 24)
    x = _moe_inputs(T=64)
    params = layer.init(jax.random.key(1), x)["params"]
    assert set(params["moe_experts"]) == {"w_up", "w_down"}
    assert set(params["moe_shared"]) == {"w_up", "w_down"}
    x, kernel, _ = _routed(x, params["moe_router"]["kernel"], routing, how)
    params["moe_router"]["kernel"] = kernel
    first, count = routing.held_range
    probe = jax.random.normal(jax.random.key(2), x.shape)

    def dense(params, x):
        with jax.default_matmul_precision("highest"):
            idx, gates = select_experts(x @ params["moe_router"]["kernel"],
                                        routing)
            w, s = params["moe_experts"], params["moe_shared"]
            y = relu2(x @ s["w_up"]["kernel"]) @ s["w_down"]["kernel"]
            for e in range(count):
                gate = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
                y = y + gate[..., None] * (relu2(x @ w["w_up"][e])
                                           @ w["w_down"][e])
            return y

    def ours(params, x):
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": params}, x)

    run = lambda f: jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(f(p, x) * probe), argnums=(0, 1)))(params, x)
    (got, got_grads), (want, want_grads) = run(ours), run(dense)
    np.testing.assert_allclose(ours(params, x), dense(params, x),
                               rtol=1e-5, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        got_grads, want_grads)
    assert got == pytest.approx(float(want), rel=1e-5)


def test_swiglu_experts_are_untouched_by_the_relu2_form():
    """The default stays the SiLU-gated layer: its parameters and its
    traced program are those of ``expert_act="swiglu"`` named outright,
    with three grouped products a chunk where ``relu2`` has two and no
    gate."""
    from tpudist.parallel import ep

    routing = Routing(128, top_k=6, held=(0, 16), scoring="sigmoid")
    x = _moe_inputs(T=64)

    def traced(layer):
        params = layer.init(jax.random.key(1), x)["params"]
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(layer.apply({"params": p}, x)[0])))(params))
        return params, text

    class Named(nn.Module):
        @nn.compact
        def __call__(self, u):
            return dropless_moe(self, u, routing=routing, ffn_dim=24,
                                expert_act="swiglu")

    default, named = traced(_Dropless(routing, 24)), traced(Named())
    assert jax.tree.map(jnp.shape, default[0]) \
        == jax.tree.map(jnp.shape, named[0])
    assert default[1] == named[1]
    assert "logistic" in default[1]
    forward = lambda act: ep._gated_ffn_live_fwd if act == "swiglu" \
        else ep._relu2_ffn_live_fwd
    chunk = lambda n: (jnp.zeros((96, 16)), jnp.zeros((288, 16)))
    ws = {"swiglu": [jnp.zeros((16, 16, 24))] * 2 + [jnp.zeros((16, 24, 16))],
          "relu2": [jnp.zeros((16, 16, 24)), jnp.zeros((16, 24, 16))]}
    for act, products in (("swiglu", 3), ("relu2", 2)):
        jaxpr = jax.make_jaxpr(forward(act))(
            chunk(4), *ws[act], jnp.zeros(16, jnp.int32))
        # chunk 0 straight, and once more in the loop over the others
        assert sum(eqn.primitive.name.startswith("ragged_dot")
                   for eqn in _eqns(jaxpr.jaxpr)) == 2 * products, act


# the expert cells' configurations -> the (model, expert) widths their
# grouped products run at
_CELL_WIDTHS = {
    "nemotron-3-nano-30b-a3b": (2816, 2048),
    "kanana-2-30b-a3b": (2048, 768),
    "sdar-30b-a3b": (2048, 768),
    "laguna-xs-2": (2048, 512),
    "zaya1-8b": (2048, 2048),
}


def _cell_config(name):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f"benchmarks/configs/{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,widths,want", [
    *((name, None, want) for name, want in _CELL_WIDTHS.items()),
    ("tiny", (16, 24), (16, 24)),
    ("tiny_padded", (240, 464), (256, 512)),
])
def test_grouped_widths_at_the_cells_widths(name, widths, want):
    """The static counter: ``grouped_widths`` at each expert cell's
    published widths (its configuration file) and at two test widths.
    Nemotron-H's 2,688 and 1,856 — tiles 128 wide in the compiler's
    grouped-product kernel — run at 2,816 x 2,048, 1.156x the products'
    work; no other cell's width moves."""
    from tpudist.parallel import ep

    if widths is None:
        config = _cell_config(name)
        widths = config["hidden_size"], config["moe_intermediate_size"]
    got = ep.grouped_widths(*widths)
    assert got == want
    ratio = got[0] * got[1] / (widths[0] * widths[1])
    if name.startswith("nemotron"):
        assert ratio == pytest.approx(1.156, abs=1e-3)
    assert 1.0 <= ratio <= (9 / 8) ** 2


class _Experts(nn.Module):
    """``dropless_moe`` of one ``expert_act`` in one compute ``dtype``."""

    routing: Routing
    ffn_dim: int
    act: str
    dtype: Any

    @nn.compact
    def __call__(self, u):
        return dropless_moe(self, u, routing=self.routing,
                            ffn_dim=self.ffn_dim, dtype=self.dtype,
                            expert_act=self.act)[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("held,E,how", [((0, 4), 32, "collapsed"),
                                        ((8, 8), 16, "even")],
                         ids=["chunked", "one_chunk"])
@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_padded_widths_are_the_unpadded_layer(act, held, E, how, dtype,
                                              monkeypatch):
    """A model width of 240 and an expert width of 464 run their grouped
    products at 256 x 512 (``grouped_widths``): the parameters keep their
    shapes, and the output and the gradients of the tokens and of every
    expert weight are those of the same layer run at its own widths —
    through the chunked path (every chunk run) and the one-chunk path,
    in both activations. The added columns and rows are exact noughts:
    float32 to 1e-5 (summation order), bfloat16 to one rounding of its
    8-bit mantissa, 2^-7, each of the largest value as well."""
    from tpudist.parallel import ep

    d, ff = 240, 464
    assert ep.grouped_widths(d, ff) == (256, 512)
    routing = Routing(E, top_k=6, held=held, scoring="sigmoid")
    layer = _Experts(routing, ff, act, dtype)
    x = _moe_inputs(T=64, d=d)
    params = layer.init(jax.random.key(1), x)["params"]
    count = held[1]
    shapes = {k: v.shape for k, v in params["moe_experts"].items()}
    assert shapes == {"w_up": (count, d, ff), "w_down": (count, ff, d),
                      **({"w_gate": (count, d, ff)} if act == "swiglu"
                         else {})}
    x, kernel, _ = _routed(x, params["moe_router"]["kernel"], routing, how)
    params["moe_router"]["kernel"] = kernel
    probe = jax.random.normal(jax.random.key(2), x.shape)

    def loss(p, x):
        y = layer.apply({"params": p}, x)
        return jnp.sum(y.astype(jnp.float32) * probe), y

    def run():
        traced = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).trace(
            params, x)
        # the stacked weights ``[count, in, out]`` the products take
        widths = {eqn.invars[1].aval.shape[1:] for eqn in _eqns(traced.jaxpr)
                  if eqn.primitive.name.startswith("ragged_dot")
                  and eqn.invars[1].aval.ndim == 3}
        grads, y = traced.lower().compile()(params, x)
        return y, grads, widths

    y, grads, widths = run()
    assert widths == {(256, 512), (512, 256)}
    monkeypatch.setattr(ep, "grouped_widths", lambda d, ff: (d, ff))
    y1, grads1, widths1 = run()
    assert widths1 == {(d, ff), (ff, d)}
    assert float(jnp.max(jnp.abs(y1))) > 0

    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * float(np.max(np.abs(b))))

    close(y, y1)
    jax.tree.map(close, grads, grads1)


@pytest.mark.parametrize("name", [*_CELL_WIDTHS, "tiny"])
def test_widths_that_do_not_qualify_trace_to_the_unpadded_program(name,
                                                                  monkeypatch):
    """At a width ``grouped_widths`` leaves alone, the expert layer's
    forward and backward trace to the program the layer had before widths
    were padded: the same jaxpr text as with the rule switched off (the
    products at their own widths, no pad, no slice) — the four other
    expert cells' widths and routings, at 64 tokens. At Nemotron-H's
    widths the texts differ: there the rule engages."""
    from tpudist.parallel import ep

    if name == "tiny":
        d, ff, act, held, E, k = 16, 24, "swiglu", (0, 4), 32, 2
    else:
        config = _cell_config(name)
        d, ff = config["hidden_size"], config["moe_intermediate_size"]
        E = config.get("n_routed_experts") or config["num_experts"]
        k = config["num_experts_per_tok"]
        held = (0, config["num_experts_held"])
        act = "relu2" if name.startswith("nemotron") else "swiglu"
    layer = _Experts(Routing(E, top_k=k, held=held), ff, act, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 64, d), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]

    def text():
        return str(jax.make_jaxpr(jax.grad(
            lambda p, x: jnp.sum(layer.apply({"params": p}, x)
                                 .astype(jnp.float32)),
            argnums=(0, 1)))(params, x))

    padded = text()
    monkeypatch.setattr(ep, "grouped_widths", lambda d, ff: (d, ff))
    assert (padded != text()) == name.startswith("nemotron")
