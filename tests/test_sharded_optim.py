"""The memory-discipline layer's correctness contracts.

1. ZeRO-1 optimizer-state sharding (``tpudist.optim.shard_state``,
   arXiv:2004.13336): the sharded-state Adam step must be NUMERICALLY the
   replicated step — sharding is placement, not math — on an emulated
   multi-device mesh, including leaves whose shapes do NOT divide the mesh
   (the pad-and-reshape path), while per-device optimizer-state bytes
   shrink ~world_size×.
2. Named remat policies (``tpudist.remat``): every policy preserves loss
   and gradients exactly, stored-residual bytes order
   ``save_nothing ≤ full ≤ dots_saveable ≤ none`` (strictly at the ends),
   and the jit-lowered cost analysis shows the complementary recompute-
   FLOP ordering.

Self-contained models (no tpudist.models import): the contracts are
framework-level; the model zoo's ``remat_policy`` wiring has its own test
in ``tests/test_remat_models.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from tpudist import mesh as mesh_lib
from tpudist import memory, optim
from tpudist.remat import POLICY_NAMES, checkpoint as remat_checkpoint
from tpudist.train import (
    create_train_state, make_train_step, state_shardings_of,
)


class OddMLP(nn.Module):
    """Dims chosen so the Adam mirrors hold every ZeRO-1 layout: (8, 64)
    and (64, 8) kernels divide a 4-way mesh; the (7, 5) kernel and the
    7/5-sized biases divide by NOTHING and must take the pad-and-reshape
    path; adam's count is a replicated scalar."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = jnp.tanh(nn.Dense(64, name="wide")(x))
        x = jnp.tanh(nn.Dense(7, name="odd_in")(x))
        x = jnp.tanh(nn.Dense(5, name="odd_out")(x))
        return nn.Dense(8, name="head")(x)


def _mesh4():
    return mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=4), devices=jax.devices()[:4]
    )


def _batch(i):
    rng = np.random.Generator(np.random.PCG64(i))
    return {
        "x": rng.standard_normal((16, 8)).astype(np.float32),
        "y": rng.integers(0, 8, 16).astype(np.int32),
    }


def test_shard_state_step_matches_replicated():
    """3 Adam steps, shard_state vs replicated, same data: losses and
    final params agree to fp tolerance (reduce-scatter vs all-reduce
    reduction order is the only daylight)."""
    mesh = _mesh4()
    model = OddMLP()
    x0 = jnp.zeros((4, 8))
    tx_r = optax.adam(1e-3)
    tx_s = optim.shard_state(optax.adam(1e-3), mesh, min_size=1)

    state_r = create_train_state(model, 0, x0, tx_r, mesh)
    state_s = create_train_state(model, 0, x0, tx_s, mesh)

    step_r = make_train_step(model, tx_r, mesh, input_key="x", label_key="y")
    step_s = make_train_step(
        model, tx_s, mesh, input_key="x", label_key="y",
        state_sharding=state_shardings_of(state_s),
    )
    for i in range(3):
        b = _batch(i)
        state_r, mr = step_r(state_r, b)
        state_s, ms = step_s(state_s, b)
        np.testing.assert_allclose(
            float(mr["loss"]), float(ms["loss"]), rtol=1e-5
        )
    for a, b_ in zip(
        jax.tree_util.tree_leaves(state_r.params),
        jax.tree_util.tree_leaves(state_s.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-5, rtol=1e-4
        )


def test_shard_state_layout_padded_and_sharded():
    """Non-divisible leaves are stored [world, cols] over 'data'; divisible
    leaves keep their shape with the largest divisible dim sharded; the
    scalar count stays replicated. Born that way out of create_train_state
    (no replicated intermediate)."""
    mesh = _mesh4()
    model = OddMLP()
    tx = optim.shard_state(optax.adam(1e-3), mesh, min_size=1)
    state = create_train_state(model, 0, jnp.zeros((4, 8)), tx, mesh)

    mu = state.opt_state[0].mu  # ScaleByAdamState of the chained adam
    # (7, 5) kernel -> flattened 35, padded to 4x9
    odd = mu["odd_out"]["kernel"]
    assert odd.shape == (4, 9)
    assert odd.sharding.spec == P("data", None)
    # (8, 64) kernel keeps its shape, largest divisible dim sharded
    wide = mu["wide"]["kernel"]
    assert wide.shape == (8, 64)
    assert mesh_lib.DATA_AXIS in tuple(wide.sharding.spec)
    # count scalar replicated
    count = state.opt_state[0].count
    assert count.shape == ()
    assert count.sharding.spec == P()
    # pad region is zeros and stays zeros after a step (the update
    # round-trips through the natural layout)
    step = make_train_step(
        model, tx, mesh, input_key="x", label_key="y",
        state_sharding=state_shardings_of(state),
    )
    state, _ = step(state, _batch(0))
    tail = np.asarray(state.opt_state[0].mu["odd_out"]["kernel"]).reshape(-1)[35:]
    np.testing.assert_array_equal(tail, 0.0)


def test_shard_state_per_device_bytes_shrink_world_x():
    """The ZeRO-1 memory claim, measured leaf-for-leaf: per-device
    optimizer-state bytes at ~1/world of replicated (padding + the scalar
    count are the only slack)."""
    mesh = _mesh4()
    model = OddMLP()
    tx_r = optax.adam(1e-3)
    tx_s = optim.shard_state(optax.adam(1e-3), mesh, min_size=1)
    state_r = create_train_state(model, 0, jnp.zeros((4, 8)), tx_r, mesh)
    state_s = create_train_state(model, 0, jnp.zeros((4, 8)), tx_s, mesh)
    rep = memory.per_device_bytes(state_r.opt_state)
    shr = memory.per_device_bytes(state_s.opt_state)
    world = 4
    assert shr < rep / (world - 1), (rep, shr)
    # and the pre-compile budget (shapes + shardings, no arrays) agrees
    # with the placed reality
    shapes = jax.eval_shape(
        tx_s.init,
        jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.zeros((4, 8)),
                               train=False)["params"]
        ),
    )
    predicted = memory.per_device_bytes(
        shapes,
        tx_s.state_shardings(
            jax.eval_shape(
                lambda: model.init(jax.random.key(0), jnp.zeros((4, 8)),
                                   train=False)["params"]
            )
        ),
    )
    assert predicted == shr


def test_shard_state_requires_params_at_update():
    mesh = _mesh4()
    tx = optim.shard_state(optax.adam(1e-3), mesh, min_size=1)
    params = {"w": jnp.zeros((7, 5))}
    state = tx.init(params)
    with pytest.raises(ValueError, match="params"):
        tx.update({"w": jnp.zeros((7, 5))}, state)


# ---------------------------------------------------------------------------
# remat policies
# ---------------------------------------------------------------------------


def _policy_funcs(policy):
    """A 6-block residual MLP with per-block checkpointing under
    ``policy`` — the shape where the policies measurably differ (dots are
    4x the boundary width)."""

    def block(h, w):
        w1, w2 = w
        u = jnp.tanh(h @ w1)
        return h + jnp.tanh(u @ w2)

    lay = remat_checkpoint(block, policy)

    def f(params, x):
        h = x
        for w in params:
            h = lay(h, w)
        return (h ** 2).mean()

    return f


def _mlp_params():
    rng = np.random.Generator(np.random.PCG64(0))
    h = 64
    params = [
        (
            jnp.asarray(rng.standard_normal((h, 4 * h)) * 0.05, jnp.float32),
            jnp.asarray(rng.standard_normal((4 * h, h)) * 0.05, jnp.float32),
        )
        for _ in range(6)
    ]
    x = jnp.asarray(rng.standard_normal((32, h)), jnp.float32)
    return params, x


def test_remat_policies_preserve_values_and_grads():
    params, x = _mlp_params()
    ref_v, ref_g = jax.jit(jax.value_and_grad(_policy_funcs("none")))(params, x)
    for policy in ("full", "dots_saveable", "save_nothing", True, False):
        v, g = jax.jit(jax.value_and_grad(_policy_funcs(policy)))(params, x)
        np.testing.assert_allclose(float(v), float(ref_v), rtol=1e-6)
        for a, b in zip(
            jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(ref_g)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )


def test_remat_policy_memory_ordering():
    """The policy contract: STORED-residual bytes (jax's own
    saved-residual accounting — what autodiff will keep live for
    backward; exact and backend-independent) order
    ``save_nothing ≤ full ≤ dots_saveable ≤ none``, strictly at the ends.

    Each policy's grad is also ``jax.jit(...).lower(...).compile()``'d and
    its cost analysis read — proving every policy produces a compilable
    step with a live cost model. The OPTIMIZED-HLO numbers themselves are
    deliberately not the ordering anchor: XLA:CPU's CSE undoes remat
    recompute where it is profitable on that backend (measured: identical
    flops for none/full/save_nothing, temp bytes that move the other way),
    which is exactly why the stored-bytes contract is asserted at the
    autodiff layer where the policy actually acts.
    """
    from jax._src.ad_checkpoint import saved_residuals

    params, x = _mlp_params()
    saved = {}
    for policy in POLICY_NAMES:
        f = _policy_funcs(policy)
        res = saved_residuals(f, params, x)
        saved[policy] = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a, _ in res
        )
        comp = jax.jit(jax.value_and_grad(f)).lower(params, x).compile()
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        assert float(ca["flops"]) > 0, (policy, ca)
    assert (
        saved["save_nothing"] <= saved["full"]
        <= saved["dots_saveable"] <= saved["none"]
    ), saved
    assert saved["save_nothing"] < saved["dots_saveable"] < saved["none"], saved


def test_remat_policy_through_train_step():
    """make_train_step accepts every named policy (and the legacy bool)
    and produces the same loss."""
    mesh = _mesh4()
    model = OddMLP()
    tx = optax.adam(1e-3)
    b = _batch(0)
    losses = {}
    for policy in ("none", "full", "dots_saveable", "save_nothing", True):
        state = create_train_state(model, 0, jnp.zeros((4, 8)), tx, mesh)
        step = make_train_step(
            model, tx, mesh, input_key="x", label_key="y", remat=policy
        )
        _, metrics = step(state, b)
        losses[str(policy)] = float(metrics["loss"])
    ref = losses["none"]
    for k, v in losses.items():
        np.testing.assert_allclose(v, ref, rtol=1e-6, err_msg=k)


def test_remat_unknown_policy_refused():
    with pytest.raises(ValueError, match="unknown remat policy"):
        remat_checkpoint(lambda x: x, "dots")


class _ListLoader:
    """Minimal fit()-shaped loader: a fixed batch list, re-iterable."""

    def __init__(self, batches, batch_size):
        self.batches = batches
        self.batch_size = batch_size

    def __iter__(self):
        return iter(self.batches)

    def __len__(self):
        return len(self.batches)


def test_fit_shard_opt_state_end_to_end(tmp_path):
    """fit(shard_opt_state=True): the one-flag surface — trains, losses
    finite, and the returned state's big moments really live sharded over
    'data' (default min_size keeps the small leaves replicated)."""

    class WideMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = jnp.tanh(nn.Dense(256, name="wide")(x))  # (8,256) ≥ min_size
            return nn.Dense(8, name="head")(x)

    from tpudist.train import fit

    mesh = _mesh4()
    loader = _ListLoader([_batch(i) for i in range(4)], batch_size=4)
    state, losses = fit(
        WideMLP(), optax.adam(1e-3), loader, epochs=1, mesh=mesh,
        batch_size=4, input_key="x", label_key="y", shard_opt_state=True,
        profile=False, log_dir=str(tmp_path), job_id="Z1",
    )
    assert len(losses) == 4
    assert np.isfinite(losses).all()
    mu = state.opt_state[0].mu
    assert mesh_lib.DATA_AXIS in tuple(mu["wide"]["kernel"].sharding.spec)
    assert mu["head"]["bias"].sharding.spec == P()  # below min_size


def test_shard_state_composes_with_remat_step():
    """The full memory-discipline recipe in one compiled step: ZeRO-1
    state + whole-forward dots_saveable remat — still numerically the
    plain step."""
    mesh = _mesh4()
    model = OddMLP()
    tx_plain = optax.adam(1e-3)
    tx = optim.shard_state(optax.adam(1e-3), mesh, min_size=1)
    state_p = create_train_state(model, 0, jnp.zeros((4, 8)), tx_plain, mesh)
    state = create_train_state(model, 0, jnp.zeros((4, 8)), tx, mesh)
    step_p = make_train_step(model, tx_plain, mesh, input_key="x", label_key="y")
    step = make_train_step(
        model, tx, mesh, input_key="x", label_key="y",
        remat="dots_saveable", state_sharding=state_shardings_of(state),
    )
    b = _batch(3)
    state_p, mp = step_p(state_p, b)
    state, ms = step(state, b)
    np.testing.assert_allclose(float(mp["loss"]), float(ms["loss"]), rtol=1e-5)
