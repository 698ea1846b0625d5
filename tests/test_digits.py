"""The bundled real-image dataset (tpudist/data/digits.py)."""

import numpy as np
import pytest

from tpudist.data.digits import load_digits_dataset


def test_shapes_dtypes_and_range():
    d = load_digits_dataset(train=True)
    assert d["image"].shape == (1437, 32, 32, 3)
    assert d["image"].dtype == np.uint8
    assert d["label"].dtype == np.int32
    assert d["image"].max() > 200 and d["image"].min() == 0
    assert set(np.unique(d["label"])) == set(range(10))


def test_split_is_disjoint_and_deterministic():
    a = load_digits_dataset(train=True)
    b = load_digits_dataset(train=False)
    assert len(a["label"]) + len(b["label"]) == 1797
    # the flattened images are unique enough to key on bytes
    train_keys = {x.tobytes() for x in a["image"]}
    overlap = sum(x.tobytes() in train_keys for x in b["image"])
    # real handwritten digits contain a handful of byte-identical duplicates
    # across the corpus; the SPLIT itself is index-disjoint by construction
    assert overlap <= 3
    a2 = load_digits_dataset(train=True)
    np.testing.assert_array_equal(a["image"], a2["image"])
    np.testing.assert_array_equal(a["label"], a2["label"])


@pytest.mark.slow  # real convergence run (~minutes on one CPU core)
def test_trains_above_chance_quickly():
    import jax.numpy as jnp
    import optax

    from tpudist import mesh as mesh_lib
    from tpudist.data.cifar import to_tensor
    from tpudist.data.loader import DataLoader
    from tpudist.models import resnet18
    from tpudist.train import create_train_state, evaluate, make_train_step

    mesh = mesh_lib.create_mesh()
    data = load_digits_dataset(train=True)
    loader = DataLoader(data, 64, transform=to_tensor)
    model = resnet18(num_classes=10, small_inputs=True)
    tx = optax.adam(1e-3)
    state = create_train_state(model, 0, jnp.zeros((1, 32, 32, 3)), tx, mesh)
    step = make_train_step(model, tx, mesh)
    for _ in range(2):
        for batch in loader:
            state, _ = step(state, batch)
    val = load_digits_dataset(train=False)
    val_loader = DataLoader(val, 64, transform=to_tensor, drop_remainder=False)
    acc = evaluate(model, state, val_loader, mesh)
    assert acc > 0.5, f"2 epochs on real digits should beat 50%, got {acc}"
