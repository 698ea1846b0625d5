"""Pipeline parallelism: GPipe schedule ≡ sequential layer stack.

The correctness contract: running stacked blocks through the pipelined
shard_map schedule (tpudist.parallel.pp) must produce the same outputs and
gradients as a plain sequential lax.scan over the layers — the pipeline is
an execution schedule, not a numerical change. Mirrors the DP-equivalence
strategy of SURVEY.md §4 on the 8-fake-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.parallel.pp import pipeline_apply, stacked_param_shardings




def _mlp_block(p, h):
    # simple residual block: h + gelu(h @ w1) @ w2
    return h + jax.nn.gelu(h @ p["w1"]) @ p["w2"]


def _stacked_mlp_params(rng, layers, d, hidden):
    k1, k2 = jax.random.split(rng)
    scale = 1.0 / np.sqrt(d)
    return {
        "w1": jax.random.normal(k1, (layers, d, hidden)) * scale,
        "w2": jax.random.normal(k2, (layers, hidden, d)) * scale,
    }


def _sequential(params, x):
    def layer(h, p):
        return _mlp_block(p, h), None

    out, _ = jax.lax.scan(layer, x, params)
    return out


@pytest.mark.parametrize("pipe,num_micro", [(2, 4), (4, 8)])
def test_pipeline_forward_matches_sequential(pipe, num_micro):
    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=8 // pipe, pipe=pipe)
    )
    layers, d, hidden = 8, 16, 32
    params = _stacked_mlp_params(jax.random.key(0), layers, d, hidden)
    x = jax.random.normal(jax.random.key(1), (16, 4, d))

    got = jax.jit(
        lambda p, x: pipeline_apply(_mlp_block, p, x, mesh, num_micro=num_micro)
    )(params, x)
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_pipeline_grads_match_sequential():
    pipe, num_micro = 4, 4
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, pipe=pipe))
    layers, d, hidden = 4, 8, 16
    params = _stacked_mlp_params(jax.random.key(2), layers, d, hidden)
    x = jax.random.normal(jax.random.key(3), (8, 2, d))
    y = jax.random.normal(jax.random.key(4), (8, 2, d))

    def loss_pp(p):
        return jnp.mean((pipeline_apply(_mlp_block, p, x, mesh, num_micro=num_micro) - y) ** 2)

    def loss_seq(p):
        return jnp.mean((_sequential(p, x) - y) ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(params)
    g_seq = jax.grad(loss_seq)(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        ),
        g_pp, g_seq,
    )


def test_pipeline_params_actually_sharded():
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, pipe=4))
    params = _stacked_mlp_params(jax.random.key(0), 8, 8, 16)
    placed = jax.device_put(params, stacked_param_shardings(params, mesh))
    # each stage holds 2 of the 8 layers: local shard = layers/pipe on dim 0
    shard = placed["w1"].addressable_shards[0]
    assert shard.data.shape == (2, 8, 16)


def test_pipelined_gpt2_train_step():
    """Full compiled train step on PipelinedGPT2 over a data×pipe mesh:
    pipe-sharded stacked blocks + Adam moments, loss finite and decreasing."""
    from tpudist.models.gpt2 import PipelinedGPT2
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, pipe=4))
    model = PipelinedGPT2(
        mesh, num_micro=2, vocab_size=64, max_seq_len=16,
        hidden_dim=32, depth=4, num_heads=4,
    )
    tx = optax.adam(1e-2)
    state = create_train_state(model, 0, jnp.zeros((2, 16), jnp.int32), tx, mesh)
    # stacked blocks (and their Adam mirrors) must be pipe-sharded
    spec = state.params["blocks"]["qkv"]["kernel"].sharding.spec
    assert spec[0] == mesh_lib.PIPELINE_AXIS

    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", state_sharding=state_shardings_of(state),
    )
    rng = np.random.Generator(np.random.PCG64(0))
    batch = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


_GPT2_CFG = dict(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=4,
                 num_heads=4)


def test_pipelined_gpt2_matches_plain_numerically():
    """PipelinedGPT2 computes the IDENTICAL function as same-seed plain
    GPT2: init-by-conversion (stack_gpt2_params) re-layouts the same param
    leaves, and the GPipe schedule is an execution order, not a numerical
    change — so logits and loss must agree to float tolerance."""
    from flax import linen as nn

    from tpudist.models.gpt2 import GPT2, PipelinedGPT2
    from tpudist.train import lm_loss

    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, pipe=4))
    plain = GPT2(**_GPT2_CFG)
    piped = PipelinedGPT2(mesh, num_micro=4, **_GPT2_CFG)
    rng = np.random.Generator(np.random.PCG64(7))
    tokens = jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)

    v_plain = nn.meta.unbox(plain.init(jax.random.key(0), tokens))
    v_piped = nn.meta.unbox(piped.init(jax.random.key(0), tokens))
    logits_plain = plain.apply(v_plain, tokens, train=False)
    logits_piped = jax.jit(
        lambda v, t: piped.apply(v, t, train=False)
    )(v_piped, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_piped), np.asarray(logits_plain),
        rtol=2e-5, atol=2e-5,
    )
    np.testing.assert_allclose(
        float(lm_loss(logits_piped, tokens)),
        float(lm_loss(logits_plain, tokens)), rtol=1e-5,
    )


def test_pipelined_train_step_agrees_with_dp():
    """Same-seed PP and DP train steps report the same loss — the local
    mirror of the dryrun's PP agreement leg."""
    from tpudist.models.gpt2 import GPT2, PipelinedGPT2
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    rng = np.random.Generator(np.random.PCG64(3))
    batch = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}

    def first_loss(mesh, model):
        tx = optax.adam(1e-3)
        state = create_train_state(
            model, 0, jnp.zeros((8, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", state_sharding=state_shardings_of(state),
        )
        _, metrics = step(state, batch)
        return float(metrics["loss"])

    loss_dp = first_loss(mesh_lib.create_mesh(), GPT2(**_GPT2_CFG))
    mesh_pp = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, pipe=4))
    loss_pp = first_loss(
        mesh_pp, PipelinedGPT2(mesh_pp, num_micro=4, **_GPT2_CFG)
    )
    assert abs(loss_pp - loss_dp) / abs(loss_dp) < 2e-5


def test_1f1b_matches_gpipe_and_unrolled():
    """The 2-stage schedule triple the composition grid pins: 1F1B,
    GPipe, and the plain unrolled stack must agree on outputs AND
    gradients — a schedule is an execution order, not a numerical change.
    Runs on a pipe-only 2-device mesh (auto axes trivial), so it holds on
    old jax too."""
    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=1, pipe=2), devices=jax.devices()[:2]
    )
    layers, d, hidden, num_micro = 4, 8, 16, 4
    params = _stacked_mlp_params(jax.random.key(5), layers, d, hidden)
    x = jax.random.normal(jax.random.key(6), (8, 2, d))
    y = jax.random.normal(jax.random.key(7), (8, 2, d))

    def loss(schedule):
        def f(p):
            out = pipeline_apply(
                _mlp_block, p, x, mesh, num_micro=num_micro,
                schedule=schedule,
            )
            return jnp.mean((out - y) ** 2)

        return f

    def loss_seq(p):
        return jnp.mean((_sequential(p, x) - y) ** 2)

    l_ref, g_ref = jax.value_and_grad(loss_seq)(params)
    for schedule in ("gpipe", "1f1b"):
        l, g = jax.jit(jax.value_and_grad(loss(schedule)))(params)
        np.testing.assert_allclose(float(l), float(l_ref), rtol=2e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            ),
            g, g_ref,
        )


def test_pipelined_gpt2_1f1b_full_train_step():
    """PipelinedGPT2(schedule='1f1b') through the ordinary compiled train
    step: same-seed first loss identical to the GPipe schedule (the
    custom_vjp backward is exact), and training decreases the loss."""
    from tpudist.models.gpt2 import PipelinedGPT2
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=1, pipe=2), devices=jax.devices()[:2]
    )
    rng = np.random.Generator(np.random.PCG64(9))
    batch = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}

    def run(schedule, n_steps):
        model = PipelinedGPT2(
            mesh, num_micro=4, schedule=schedule, **_GPT2_CFG
        )
        tx = optax.adam(1e-2)
        state = create_train_state(
            model, 0, jnp.zeros((8, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens", state_sharding=state_shardings_of(state),
        )
        losses = []
        for _ in range(n_steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses

    l_1f1b = run("1f1b", 4)
    l_gpipe = run("gpipe", 1)
    assert abs(l_1f1b[0] - l_gpipe[0]) / abs(l_gpipe[0]) < 2e-5
    assert np.isfinite(l_1f1b).all() and l_1f1b[-1] < l_1f1b[0]


def test_pipeline_rejects_unknown_schedule():
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=2, pipe=4))
    params = _stacked_mlp_params(jax.random.key(0), 8, 8, 16)
    x = jax.random.normal(jax.random.key(1), (8, 2, 8))
    with pytest.raises(ValueError, match="schedule"):
        pipeline_apply(
            _mlp_block, params, x, mesh, num_micro=4, schedule="2f2b"
        )


def test_pipelined_gpt2_with_tensor_parallel_stages():
    """PP x TP: the pipe-manual shard_map leaves 'tensor' under GSPMD, so
    Megatron-sharded stage params must still compute the plain model's
    function (parallel/pp.py's composition claim, made real)."""
    from flax import linen as nn

    from tpudist.models.gpt2 import GPT2, PipelinedGPT2
    from tpudist.train import (
        create_train_state, lm_loss, make_train_step, state_shardings_of,
    )

    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=2, pipe=2, tensor=2)
    )
    model = PipelinedGPT2(mesh, num_micro=4, **_GPT2_CFG)
    tx = optax.adam(1e-3)
    state = create_train_state(
        model, 0, jnp.zeros((8, 16), jnp.int32), tx, mesh
    )
    # stage params must be BOTH pipe-sharded (layer dim) and tensor-sharded
    # (Megatron dims): qkv kernel [depth, d, 3, heads, dh] -> ('pipe', ...,
    # 'tensor', ...)
    spec = state.params["blocks"]["qkv"]["kernel"].sharding.spec
    assert spec[0] == mesh_lib.PIPELINE_AXIS
    assert mesh_lib.TENSOR_AXIS in tuple(spec)

    rng = np.random.Generator(np.random.PCG64(3))
    batch = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int32)}
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", state_sharding=state_shardings_of(state),
    )
    _, metrics = step(state, batch)
    loss_pptp = float(metrics["loss"])

    # DP reference: same seed, same batch, plain model on the pure-DP mesh
    plain = GPT2(**_GPT2_CFG)
    v_plain = nn.meta.unbox(plain.init(jax.random.key(0), batch["tokens"]))
    loss_ref = float(
        lm_loss(plain.apply(v_plain, batch["tokens"], train=False),
                batch["tokens"])
    )
    assert abs(loss_pptp - loss_ref) / abs(loss_ref) < 2e-5
