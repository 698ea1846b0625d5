"""Eval-path contracts (tpudist.train.evaluate/_padded_batches/fit):

- constant-shape eval batches: a ragged val tail must NOT present a new
  shape to jit (one compile per eval regardless of val-set size);
- the ``input_transform`` hook: a model trained through an in-graph
  transform (uint8 loader + device_normalize) must eval through the same
  one;
- fit()'s delayed-metric flush: the last completed step's loss lands in the
  history/TSV even when a later step or the loader raises.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.train import _padded_batches, create_train_state, evaluate, fit


def _tiny_model():
    from flax import linen as nn

    class Mlp(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = x.reshape(x.shape[0], -1)
            return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))

    return Mlp()


def _ragged_loader(n_rows: int, batch: int, feat: int = 12, seed: int = 0):
    rng = np.random.Generator(np.random.PCG64(seed))
    data = {
        "image": rng.random((n_rows, feat), np.float32),
        "label": rng.integers(0, 10, n_rows).astype(np.int32),
    }

    def batches():
        for i in range(0, n_rows, batch):
            yield {k: v[i : i + batch] for k, v in data.items()}

    return batches


def test_padded_batches_constant_shape():
    """Every yielded batch — including the ragged tail — carries the FIRST
    batch's (replica-rounded) row count, so the downstream jit sees one
    shape; the mask still counts exactly the real rows."""
    mesh = mesh_lib.create_mesh()
    loader = _ragged_loader(n_rows=16 * 2 + 7, batch=16)
    shapes, real = set(), 0
    for batch, mask, n in _padded_batches(loader(), mesh, "label"):
        shapes.add(batch["label"].shape[0])
        real += int(np.asarray(mask).sum())
        assert batch["image"].shape[0] == batch["label"].shape[0]
    assert shapes == {16}, shapes
    assert real == 39


def test_evaluate_compiles_once_despite_ragged_tail(
    caplog, no_persistent_compile_cache,
):
    model = _tiny_model()
    mesh = mesh_lib.create_mesh()
    state = create_train_state(
        model, 0, jnp.zeros((1, 12)), optax.adam(1e-3), mesh
    )
    loader = _ragged_loader(n_rows=16 * 3 + 5, batch=16)
    with caplog.at_level(logging.WARNING):
        with jax.log_compiles():
            evaluate(model, state, loader(), mesh)
    compiles = [
        r for r in caplog.records
        # message format varies across jax versions: "Compiling
        # jit(count_correct)" vs "Compiling count_correct with global
        # shapes" — match the invariant part
        if r.getMessage().startswith("Compiling")
        and "count_correct" in r.getMessage()
    ]
    assert len(compiles) == 1, [r.getMessage() for r in compiles]


def test_evaluate_input_transform_matches_host_transform():
    """uint8 loader + in-graph transform ≡ host-side float loader: the eval
    counterpart of make_train_step(input_transform=...)."""
    model = _tiny_model()
    mesh = mesh_lib.create_mesh()
    state = create_train_state(
        model, 0, jnp.zeros((1, 12)), optax.adam(1e-3), mesh
    )
    rng = np.random.Generator(np.random.PCG64(3))
    raw = rng.integers(0, 256, (40, 12), dtype=np.uint8)
    labels = rng.integers(0, 10, 40).astype(np.int32)

    def u8_batches():
        for i in range(0, 40, 16):
            yield {"image": raw[i : i + 16], "label": labels[i : i + 16]}

    def f32_batches():
        for i in range(0, 40, 16):
            yield {
                "image": raw[i : i + 16].astype(np.float32) / 255.0,
                "label": labels[i : i + 16],
            }

    acc_host = evaluate(model, state, f32_batches(), mesh)
    acc_graph = evaluate(
        model, state, u8_batches(), mesh,
        input_transform=lambda x: x.astype(jnp.float32) / 255.0,
    )
    assert acc_host == acc_graph


def test_fit_flushes_pending_loss_on_midrun_failure(tmp_path):
    """When step k+1's batch never arrives (loader raises), step k's
    already-computed loss must still be resolved into the history and TSV —
    not dropped with the exception."""
    model = _tiny_model()
    mesh = mesh_lib.create_mesh()
    rng = np.random.Generator(np.random.PCG64(4))

    class ExplodingLoader:
        batch_size = 16
        n_good = 3

        def __iter__(self):
            for i in range(self.n_good):
                yield {
                    "image": rng.random((16, 12), np.float32),
                    "label": rng.integers(0, 10, 16).astype(np.int32),
                }
            raise RuntimeError("disk died")

    from tpudist.metrics import MetricsLogger

    logger = MetricsLogger(
        "FlushJob", 16, 0, 1, log_every=1, log_dir=str(tmp_path)
    )
    with pytest.raises(RuntimeError, match="disk died"):
        fit(
            model, optax.adam(1e-3), ExplodingLoader(),
            epochs=1, mesh=mesh, profile=False,
            log_dir=str(tmp_path), metrics_logger=logger,
        )

    log = tmp_path / "FlushJob_16_0.log"
    lines = log.read_text().splitlines()
    rows = [
        l for l in lines[1:] if l and not l.startswith("TrainTime")
    ]
    # all 3 completed steps' rows present — the 3rd is the flushed pending —
    # and the footer survived the exception via the context manager
    assert len(rows) == 3, lines
    assert any(l.startswith("TrainTime") for l in lines), lines
