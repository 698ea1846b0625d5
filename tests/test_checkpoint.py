"""Checkpoint / resume tests.

Capability extension over the reference (which persists nothing —
SURVEY.md §5): round-trip fidelity, sharded-state restore, and exact-resume
semantics of fit() (same losses as an uninterrupted run, since the sampler
order is deterministic per epoch).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.checkpoint import Checkpointer, latest_step
from tpudist.data.cifar import synthetic_cifar, to_tensor
from tpudist.data.loader import DataLoader
from tpudist.data.sampler import DistributedSampler
from tpudist.models import resnet18
from tpudist.models.gpt2 import GPT2
from tpudist.train import (
    create_train_state, fit, lm_loss, make_train_step, state_shardings_of,
)

def _tiny_state(mesh):
    model = resnet18(num_classes=10, small_inputs=True)
    tx = optax.adam(1e-3)
    state = create_train_state(model, 0, jnp.zeros((1, 32, 32, 3)), tx, mesh)
    return model, tx, state


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a, b,
    )


def test_roundtrip_identity(tmp_path):
    mesh = mesh_lib.create_mesh()
    model, tx, state = _tiny_state(mesh)
    step = make_train_step(model, tx, mesh)
    batch = to_tensor(synthetic_cifar(n=16, num_classes=10))
    state, _ = step(state, batch)

    with Checkpointer(tmp_path / "ckpt") as c:
        c.save(state, wait=True)
        assert c.latest_step() == 1
        fresh = _tiny_state(mesh)[2]  # different values, same structure
        restored = c.restore(like=fresh)
    _assert_trees_equal(restored, state)
    assert latest_step(tmp_path / "ckpt") == 1


def test_restore_respects_sharded_placement(tmp_path):
    """A TP-sharded GPT-2 state restores onto its original shardings (no
    silent all-replication)."""
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=4, tensor=2))
    lm = GPT2(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=1, num_heads=2)
    tx = optax.adam(1e-3)
    state = create_train_state(lm, 0, jnp.zeros((1, 8), jnp.int32), tx, mesh)
    step = make_train_step(lm, tx, mesh, loss_fn=lm_loss, input_key="tokens",
                           label_key="tokens",
                           state_sharding=state_shardings_of(state))
    tokens = {"tokens": np.arange(8 * 8, dtype=np.int32).reshape(8, 8) % 64}
    state, _ = step(state, tokens)

    with Checkpointer(tmp_path / "tp") as c:
        c.save(state, wait=True)
        fresh = create_train_state(lm, 1, jnp.zeros((1, 8), jnp.int32), tx, mesh)
        restored = c.restore(like=fresh)
    _assert_trees_equal(restored, state)
    flat_new, _ = jax.tree_util.tree_flatten(restored)
    flat_old, _ = jax.tree_util.tree_flatten(state)
    for new, old in zip(flat_new, flat_old):
        assert new.sharding.is_equivalent_to(old.sharding, new.ndim)


def test_max_to_keep(tmp_path):
    mesh = mesh_lib.create_mesh()
    _, _, state = _tiny_state(mesh)
    with Checkpointer(tmp_path / "gc", max_to_keep=2) as c:
        for s in (1, 2, 3, 4):
            c.save(state, step=s, wait=True)
        assert c.latest_step() == 4
        steps = sorted(int(p.name) for p in (tmp_path / "gc").iterdir()
                       if p.name.isdigit())
        assert steps == [3, 4]


def _run_fit(tmp_path, epochs, ckpt_dir=None, every=0, tag="a"):
    model = resnet18(num_classes=10, small_inputs=True)
    data = synthetic_cifar(n=128, num_classes=10)
    loader = DataLoader(
        data, 32, sampler=DistributedSampler(128, num_replicas=1, rank=0),
        transform=to_tensor,
    )
    return fit(
        model, optax.adam(1e-3), loader,
        epochs=epochs, job_id=f"CK{tag}", batch_size=32,
        profile=False, log_dir=str(tmp_path),
        checkpoint_dir=None if ckpt_dir is None else str(ckpt_dir),
        checkpoint_every=every,
    )


def test_fit_resume_matches_uninterrupted(tmp_path):
    """Train 1 epoch + resume for the 2nd ≡ training 2 epochs straight:
    identical per-step losses (deterministic init, sampler, and updates)."""
    full_state, full_losses = _run_fit(tmp_path / "full", epochs=2)

    ckpt = tmp_path / "resume" / "ckpt"
    _, first = _run_fit(tmp_path / "resume", epochs=1, ckpt_dir=ckpt, tag="b")
    assert latest_step(ckpt) == 4  # 128/32 steps saved at end of epoch 0
    state2, second = _run_fit(tmp_path / "resume", epochs=2, ckpt_dir=ckpt, tag="b")

    np.testing.assert_allclose(
        np.asarray(first + second), np.asarray(full_losses), rtol=2e-4, atol=2e-5
    )
    assert int(state2.step) == int(full_state.step) == 8
    _assert_trees_equal(state2.params, full_state.params)


def test_resume_rejects_changed_geometry(tmp_path):
    """Resuming with a different batch size must fail loudly: state.step
    would map to the wrong data position and silently re-train on consumed
    samples."""
    ckpt = tmp_path / "geo"
    _run_fit(tmp_path, epochs=1, ckpt_dir=ckpt, tag="g")

    model = resnet18(num_classes=10, small_inputs=True)
    data = synthetic_cifar(n=128, num_classes=10)
    loader16 = DataLoader(
        data, 16, sampler=DistributedSampler(128, num_replicas=1, rank=0),
        transform=to_tensor,
    )
    with pytest.raises(ValueError, match="geometry"):
        fit(model, optax.adam(1e-3), loader16, epochs=2, job_id="CKg2",
            batch_size=16, profile=False, log_dir=str(tmp_path),
            checkpoint_dir=str(ckpt))


def test_loader_iter_from_skips_at_index_level():
    from unittest import mock

    data = synthetic_cifar(n=96, num_classes=10)
    loader = DataLoader(
        data, 16, sampler=DistributedSampler(96, num_replicas=1, rank=0),
        transform=to_tensor,
    )
    tail = list(loader.iter_from(4))
    full = list(loader)
    assert len(tail) == 2
    for a, b in zip(tail, full[4:]):
        np.testing.assert_array_equal(a["image"], b["image"])
    # skipped batches are never materialized: the native/python gather runs
    # exactly len(tail) times
    with mock.patch("tpudist.data.native.native_batch", return_value=None) as nb:
        assert len(list(loader.iter_from(4))) == 2
        assert nb.call_count == 2


def test_fit_resume_mid_epoch(tmp_path):
    """checkpoint_every mid-epoch: the resumed run skips exactly the
    consumed batches and finishes the epoch (step counts line up)."""
    ckpt = tmp_path / "mid"
    full_state, full_losses = _run_fit(tmp_path, epochs=1, ckpt_dir=ckpt,
                                       every=3, tag="c")
    # wipe nothing; resuming a finished run trains zero steps
    state, losses = _run_fit(tmp_path, epochs=1, ckpt_dir=ckpt, tag="c")
    assert losses == []
    assert int(state.step) == 4

    # drop back to the step-3 checkpoint and resume the last batch
    import shutil

    shutil.rmtree(ckpt / "4")
    state, losses = _run_fit(tmp_path, epochs=1, ckpt_dir=ckpt, tag="c")
    assert len(losses) == 1
    np.testing.assert_allclose(losses[0], full_losses[3], rtol=2e-4, atol=2e-5)
