"""FSDP (ZeRO-3 sharded state) correctness: a step with params/opt-state
sharded over the ``fsdp`` axis must be numerically equivalent to the fully
replicated DP step — sharding is placement, not math (tpudist.parallel.fsdp).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from tpudist import mesh as mesh_lib
from tpudist.data.cifar import synthetic_cifar, to_tensor
from tpudist.mesh import FSDP_AXIS
from tpudist.models import resnet18
from tpudist.parallel.fsdp import fsdp_spec, shard_state
from tpudist.train import create_train_state, make_train_step


def _batch(n=16, seed=0):
    data = synthetic_cifar(n=n, num_classes=10, seed=seed)
    return to_tensor({"image": data["image"], "label": data["label"]})


def test_fsdp_spec_picks_largest_divisible_dim():
    assert fsdp_spec((3, 3, 64, 128), 4) == P(None, None, None, FSDP_AXIS)
    assert fsdp_spec((256, 64), 4) == P(FSDP_AXIS, None)
    # too small -> replicated
    assert fsdp_spec((64,), 4) == P()
    # nothing divisible -> replicated
    assert fsdp_spec((3, 5, 7), 4, min_size=1) == P()
    # fsdp axis of 1 -> replicated
    assert fsdp_spec((256, 64), 1) == P()


def test_fsdp_actually_shards_and_matches_dp():
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, fsdp=4))
    model = resnet18(num_classes=10, small_inputs=True)
    tx = optax.adam(1e-3)
    state = create_train_state(model, 0, jnp.zeros((1, 32, 32, 3)), tx, mesh)

    # independent state for the DP control: shard_state's device_put aliases
    # replicated leaves, and the donating train step would delete them from
    # under the control run
    state_dp = create_train_state(model, 0, jnp.zeros((1, 32, 32, 3)), tx, mesh)

    fsdp_state, shardings = shard_state(state, mesh)
    # at least the big conv kernels must really be sharded over fsdp
    sharded = [
        s for s in jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec")
        )
        if FSDP_AXIS in tuple(s.spec)
    ]
    assert len(sharded) > 10

    step_fsdp = make_train_step(model, tx, mesh, state_sharding=shardings)
    step_dp = make_train_step(model, tx, mesh)

    losses_f, losses_d = [], []
    st_f, st_d = fsdp_state, state_dp
    for i in range(2):
        b = _batch(16, seed=i)
        st_f, mf = step_fsdp(st_f, b)
        st_d, md = step_dp(st_d, b)
        losses_f.append(float(mf["loss"]))
        losses_d.append(float(md["loss"]))
    np.testing.assert_allclose(losses_f, losses_d, rtol=2e-4)
    for a, b_ in zip(
        jax.tree_util.tree_leaves(st_f.params),
        jax.tree_util.tree_leaves(st_d.params),
    ):
        # after 2 Adam steps fp reduction-order noise is amplified through
        # sqrt/eps (same chaos bound as test_8dev_dp_equals_1dev step 2)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-3, rtol=1e-2)


def test_fsdp_state_memory_is_sharded():
    """Each device holds ~1/fsdp of every sharded leaf (the ZeRO memory win)."""
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=1, fsdp=8))
    model = resnet18(num_classes=10, small_inputs=True)
    tx = optax.adam(1e-3)
    state = create_train_state(model, 0, jnp.zeros((1, 32, 32, 3)), tx, mesh)
    fsdp_state, _ = shard_state(state, mesh)
    # find a big kernel and check its per-device shard shape
    big = [
        x for x in jax.tree_util.tree_leaves(fsdp_state.params)
        if x.size >= 64 * 64 * 9
    ]
    assert big
    for x in big:
        local = x.addressable_shards[0].data
        assert local.size * 8 == x.size, (x.shape, local.shape)


def test_compose_fsdp_3d_matches_unsharded():
    """dp x fsdp x tensor composition: TP kernels keep their Megatron specs,
    replicated leaves gain fsdp specs, loss matches the 1-device run."""
    from tpudist.models.gpt2 import GPT2
    from tpudist.parallel.fsdp import compose_fsdp
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    rng = np.random.Generator(np.random.PCG64(13))
    batch = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}

    losses = {}
    for name in ("single", "3d"):
        if name == "single":
            mesh = mesh_lib.create_mesh(
                mesh_lib.MeshConfig(data=1), devices=jax.devices()[:1]
            )
        else:
            mesh = mesh_lib.create_mesh(
                mesh_lib.MeshConfig(data=2, fsdp=2, tensor=2)
            )
        model = GPT2(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
                     num_heads=4)
        tx = optax.adam(1e-3)
        state = create_train_state(
            model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
        )
        if name == "3d":
            state, shardings = compose_fsdp(state, mesh, min_size=256)
            # TP annotation survives composition...
            qkv = shardings.params["h_0"]["qkv"]["kernel"].spec
            assert mesh_lib.TENSOR_AXIS in qkv, qkv
            # ...and an unannotated leaf (positional embedding) gained fsdp
            wpe = shardings.params["wpe"].spec
            assert mesh_lib.FSDP_AXIS in wpe, wpe
        else:
            shardings = state_shardings_of(state)
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", state_sharding=shardings,
        )
        state, metrics = step(state, batch)
        losses[name] = float(metrics["loss"])
    np.testing.assert_allclose(losses["single"], losses["3d"], rtol=2e-5)
