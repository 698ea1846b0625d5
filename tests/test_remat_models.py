"""The model zoo's per-block ``remat_policy`` wiring (GPT-2, Llama) and
the ~1B-param HBM budget of docs/LM_TRAINING.md's recipe.

Per-block remat must be a pure memory/flop trade: identical loss and
gradients, identical param NAMES (interop/checkpoints depend on the
``h_{i}``/``layer_{i}`` layout), in both the unrolled and scanned layouts.
The budget test: exact eval_shape state bytes at
the 1536×36 (~1.1B-param) geometry, replicated provably over 16 GB,
shard_state + remat under it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist import memory, optim
from tpudist.models.gpt2 import GPT2
from tpudist.models.llama import Llama
from tpudist.train import create_train_state, lm_loss, make_train_step


def _loss_and_grad(model, tokens):
    params = model.init(
        jax.random.key(0), tokens, train=False
    )["params"]

    @jax.jit
    def lg(p, t):
        return jax.value_and_grad(
            lambda p_: lm_loss(model.apply({"params": p_}, t, train=True), t)
        )(p)

    return params, lg(params, tokens)


@pytest.mark.parametrize("policy", ["full", "dots_saveable", "save_nothing"])
def test_gpt2_block_remat_preserves_function_and_names(policy):
    rng = np.random.Generator(np.random.PCG64(5))
    tokens = jnp.asarray(rng.integers(0, 64, (2, 16)), jnp.int32)
    kw = dict(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
              num_heads=4)
    p_ref, (v_ref, g_ref) = _loss_and_grad(GPT2(**kw), tokens)
    p_rm, (v_rm, g_rm) = _loss_and_grad(
        GPT2(**kw, remat_policy=policy), tokens
    )
    # same param tree (names unchanged under nn.remat)
    assert jax.tree_util.tree_structure(p_ref) == jax.tree_util.tree_structure(p_rm)
    np.testing.assert_allclose(float(v_ref), float(v_rm), rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_rm)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_llama_block_remat_unrolled_and_scanned():
    """remat_policy preserves the function WITHIN each layout (scan and
    unrolled init derive per-layer rngs differently, so cross-layout
    losses legitimately differ — the remat contract is per-layout)."""
    rng = np.random.Generator(np.random.PCG64(7))
    tokens = jnp.asarray(rng.integers(0, 64, (2, 16)), jnp.int32)
    kw = dict(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
              num_heads=4, num_kv_heads=2, ffn_dim=64)
    _, (v_ref, _) = _loss_and_grad(Llama(**kw), tokens)
    _, (v_unrolled, _) = _loss_and_grad(
        Llama(**kw, remat_policy="dots_saveable"), tokens
    )
    np.testing.assert_allclose(float(v_ref), float(v_unrolled), rtol=1e-6)
    # scanned layout: remat_policy rides the scanned body — same function
    # as the un-rematted SCANNED model, and as the legacy remat_layers
    _, (v_scan_ref, _) = _loss_and_grad(
        Llama(**kw, scan_layers=True), tokens
    )
    _, (v_scan, _) = _loss_and_grad(
        Llama(**kw, scan_layers=True, remat_policy="save_nothing"), tokens
    )
    np.testing.assert_allclose(float(v_scan_ref), float(v_scan), rtol=1e-6)
    _, (v_legacy, _) = _loss_and_grad(
        Llama(**kw, scan_layers=True, remat_layers=True), tokens
    )
    np.testing.assert_allclose(float(v_scan_ref), float(v_legacy), rtol=1e-6)


def test_gpt2_remat_policy_trains_through_step():
    """remat_policy through the full compiled train step (the fit()
    surface), composed with ZeRO-1 shard_state on a 4-dev mesh."""
    from tpudist.train import state_shardings_of

    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=4), devices=jax.devices()[:4]
    )
    model = GPT2(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
                 num_heads=4, remat_policy="dots_saveable")
    tx = optim.shard_state(optax.adam(1e-3), mesh)
    state = create_train_state(
        model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
    )
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", state_sharding=state_shardings_of(state),
    )
    # a LEARNABLE stream (repeating token) so "loss drops" is a property
    # of the step, not of luck against uniform noise
    batch = {"tokens": np.full((8, 16), 7, np.int32)}
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # it trains


@pytest.mark.slow
def test_1b_budget_replicated_over_sharded_under_16gb():
    """The claim of docs/LM_TRAINING.md "Fitting ~1B parameters", from
    shapes alone: GPT-2 1536×36 (~1.1B params) replicated Adam does NOT fit
    16 GB; ZeRO-1 over 8 replicas + per-block save_nothing remat does
    (an accounting from shapes; nothing of it is measured on the chip).
    eval_shape only — no arrays are materialized (the trace of the
    36-layer model is the slow part, hence the marker)."""
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=8))
    model = GPT2(hidden_dim=1536, depth=36, num_heads=16)
    tokens = np.zeros((1, 16), np.int32)
    tx = optax.adam(1e-3)
    replicated = memory.train_state_budget(
        model, tx, tokens, batch=4, seq=1024, world_size=1,
        remat_policy="none",
    )
    sharded = memory.train_state_budget(
        model, optim.shard_state(tx, mesh), tokens, batch=4, seq=1024,
        world_size=8, remat_policy="save_nothing",
    )
    assert replicated["n_params"] > 1.0e9
    assert not replicated["fits"], memory.format_budget(replicated)
    assert sharded["fits"], memory.format_budget(sharded)
    # the moments really shrink ~world_size x (exact leaf accounting)
    ratio = (
        replicated["opt_state_bytes_per_chip"]
        / sharded["opt_state_bytes_per_chip"]
    )
    assert ratio > 7.0, ratio
