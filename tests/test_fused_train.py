"""Step-fusion integration: make_train_step(fused=) /
fit(fused=) — trajectory equivalence of the fully-fused step against the
unfused reference (the acceptance bar: 24-step GPT-2, composed with ZeRO-1
shard_opt_state, the quantized reducer, and guard_nonfinite in one test
each), the compile-count pin (fused= introduces no recompiles across
steps), the resolve contract, the telemetry ``fusion`` row, and the
warm-start compute-copy refresh."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.models.gpt2 import GPT2, chunked_lm_forward
from tpudist.optim import fused_adamw, shard_state
from tpudist.train import create_train_state, fit, lm_loss, make_train_step

N_STEPS = 24


def _model(**kw):
    return GPT2(vocab_size=97, max_seq_len=32, hidden_dim=48, depth=2,
                num_heads=4, **kw)


def _batches(n=N_STEPS, rows=8, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    return [rng.integers(0, 97, (rows, 16)).astype(np.int32)
            for _ in range(n)]


def _trajectory(mesh, fused, tx, model=None, **kw):
    model = model or _model()
    state = create_train_state(
        model, 0, jnp.zeros((8, 16), jnp.int32), tx, mesh
    )
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", fused=fused, **kw,
    )
    if step.grad_reducer is not None:
        state = step.grad_reducer.attach_residual(state)
    losses = []
    for b in _batches():
        state, metrics = step(state, {"tokens": b})
        losses.append(float(metrics["loss"]))
    return np.asarray(losses), state, step


# the repo's equivalence bar for same-math trajectory pins (the quantized
# suite uses 8% for a LOSSY wire; the fused step is exact math, so the bar
# here is float32-accumulation tight): losses within 1e-4 relative. Params
# get an ABSOLUTE bar of one lr (1e-3): on near-zero-gradient coordinates
# Adam's direction is mhat/(sqrt(vhat)+eps) of two tiny numbers, so an
# ulp-level forward difference can legally swing a coordinate by up to
# ±lr per step without moving the loss — relative-to-leaf-scale bars
# false-alarm on exactly those coordinates.
def _assert_equivalent(l_ref, l_fused, s_ref, s_fused, lr=1e-3):
    np.testing.assert_allclose(l_fused, l_ref, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s_fused.params),
                    jax.tree_util.tree_leaves(s_ref.params)):
        assert float(jnp.max(jnp.abs(a - b))) < lr


def test_fused_all_matches_unfused_24_steps():
    mesh = mesh_lib.create_mesh()
    l0, s0, _ = _trajectory(mesh, None, optax.adam(1e-3))
    l1, s1, step = _trajectory(
        mesh, "all", fused_adamw(1e-3, compute_dtype=jnp.float32)
    )
    assert step.fused == {"ln", "optimizer"}
    assert step.fused_info == {
        "ln": True, "optimizer": True, "compute_dtype": "float32",
    }
    _assert_equivalent(l0, l1, s0, s1)


def test_fused_all_with_shard_opt_state():
    """ZeRO-1 composition: the fused update runs on the sharded-state
    layout (restored in-graph); trajectory pinned to the unfused run."""
    mesh = mesh_lib.create_mesh()
    l0, s0, _ = _trajectory(mesh, None, optax.adam(1e-3))
    l1, s1, _ = _trajectory(
        mesh, "all",
        shard_state(fused_adamw(1e-3, compute_dtype=jnp.float32), mesh),
    )
    _assert_equivalent(l0, l1, s0, s1)


def test_fused_all_with_quantized_reducer():
    """Explicit int8 quantized all-reduce composition: fused vs unfused
    through the SAME lossy wire — the deltas must come from the wire, not
    the fusion, so the two quantized runs pin each other tightly."""
    mesh = mesh_lib.create_mesh()
    l0, s0, _ = _trajectory(mesh, None, optax.adam(1e-3),
                            reduce="quantized")
    l1, s1, _ = _trajectory(
        mesh, "all", fused_adamw(1e-3, compute_dtype=jnp.float32),
        reduce="quantized",
    )
    # the int8 wire's stochastic rounding resolves ulp-level gradient
    # differences into occasionally-different draws, so the param bar is a
    # few lr rather than one (the loss bar — the convergence signal —
    # stays at the exact-math tightness)
    _assert_equivalent(l0, l1, s0, s1, lr=5e-3)


def test_fused_all_with_guard_nonfinite():
    mesh = mesh_lib.create_mesh()
    l0, s0, _ = _trajectory(mesh, None, optax.adam(1e-3),
                            guard_nonfinite=True)
    l1, s1, _ = _trajectory(
        mesh, "all", fused_adamw(1e-3, compute_dtype=jnp.float32),
        guard_nonfinite=True,
    )
    _assert_equivalent(l0, l1, s0, s1)


def test_fused_chunked_ce_odd_chunk():
    """fused LN + the chunked-CE forward at a chunk that does NOT divide
    the 15 predicted positions (odd last chunk) — the rebuild hook must
    hand the fused clone to the chunked forward, and the numbers must
    match the plain fused path."""
    mesh = mesh_lib.create_mesh()
    model = _model()
    l1, s1, _ = _trajectory(
        mesh, "all", fused_adamw(1e-3, compute_dtype=jnp.float32),
        model=model,
    )
    l2, s2, step = _trajectory(
        mesh, "all", fused_adamw(1e-3, compute_dtype=jnp.float32),
        model=model, forward_loss=chunked_lm_forward(model, chunk=7),
    )
    assert "ln" in step.fused
    np.testing.assert_allclose(l2, l1, rtol=1e-4, atol=1e-5)


def test_fused_no_recompiles_across_steps():
    """Compile-count pin: fused= must not add jit cache entries beyond the
    unfused baseline's, and the count must be stable from step 2 on (no
    per-step retraces — e.g. a schedule or bias-correction scalar leaking
    in as a python value would recompile every step)."""
    mesh = mesh_lib.create_mesh()

    def count(fused, tx):
        model = _model()
        state = create_train_state(
            model, 0, jnp.zeros((8, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(model, tx, mesh, loss_fn=lm_loss,
                               input_key="tokens", label_key="tokens",
                               fused=fused)
        sizes = []
        for b in _batches(6):
            state, _ = step(state, {"tokens": b})
            sizes.append(step.jitted._cache_size())
        return sizes

    base = count(None, optax.adam(1e-3))
    fused = count("all", fused_adamw(1e-3, compute_dtype=jnp.float32))
    assert fused[-1] == base[-1]
    assert fused[1:] == [fused[1]] * len(fused[1:])  # stable after step 2


def test_resolve_fused_contract():
    from tpudist.train import resolve_fused

    model, ftx = _model(), fused_adamw(1e-3)
    assert resolve_fused(None, model, ftx) == frozenset()
    assert resolve_fused("none", model, ftx) == frozenset()
    assert resolve_fused("auto", model, ftx) == {"ln", "optimizer"}
    assert resolve_fused("auto", model, optax.adam(1e-3)) == {"ln"}
    assert resolve_fused("ln", model, optax.adam(1e-3)) == {"ln"}
    with pytest.raises(ValueError, match="fused_adamw"):
        resolve_fused("optimizer", model, optax.adam(1e-3))
    with pytest.raises(ValueError, match="fused_ln"):
        from tpudist.models.resnet import resnet18

        resolve_fused("ln", resnet18(), ftx)
    # a resnet under "auto" quietly fuses only what exists
    from tpudist.models.resnet import resnet18

    assert resolve_fused("auto", resnet18(), ftx) == {"optimizer"}
    with pytest.raises(ValueError, match="expected"):
        resolve_fused("everything", model, ftx)


def test_foreign_forward_loss_without_rebuild():
    """An EXPLICIT ln request with a rebuild-less forward_loss must refuse
    (running unfused against an explicit request would be a benchmark
    lying); "auto" — best-effort by contract — declines the LN side with
    a warning and keeps whatever else resolved."""
    mesh = mesh_lib.create_mesh()
    plain = lambda params, stats, batch: (jnp.float32(0.0), stats)
    with pytest.raises(ValueError, match="rebuild"):
        make_train_step(_model(), optax.adam(1e-3), mesh, fused="ln",
                        forward_loss=plain)
    with pytest.warns(UserWarning, match="declining LN fusion"):
        step = make_train_step(
            _model(), fused_adamw(1e-3), mesh, fused="auto",
            forward_loss=plain,
        )
    assert step.fused == {"optimizer"}
    assert step.fused_info["ln"] is False


def test_fit_fused_writes_fusion_row(tmp_path):
    from tpudist.data.loader import DataLoader

    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, 97, (32, 16)).astype(np.int32)
    state, losses = fit(
        _model(), fused_adamw(1e-3, compute_dtype=jnp.float32),
        DataLoader({"tokens": tokens}, 16),
        epochs=2, job_id="FU", batch_size=16, loss_fn=lm_loss,
        input_key="tokens", label_key="tokens", fused="all",
        log_dir=str(tmp_path), telemetry=True, profile=False,
    )
    assert len(losses) == 4 and all(np.isfinite(losses))
    rows = [json.loads(l) for l in pathlib.Path(
        tmp_path / "FU_telemetry_0.jsonl").read_text().splitlines()]
    fusion = [r for r in rows if r["kind"] == "fusion"]
    assert len(fusion) == 1
    assert fusion[0]["ln"] is True and fusion[0]["optimizer"] is True
    assert fusion[0]["compute_dtype"] == "float32"


def test_fit_unfused_stream_has_no_fusion_row(tmp_path):
    """fused=None keeps the stream byte-compatible: no fusion row."""
    from tpudist.data.loader import DataLoader

    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, 97, (32, 16)).astype(np.int32)
    fit(
        _model(), optax.adam(1e-3), DataLoader({"tokens": tokens}, 16),
        epochs=1, job_id="NF", batch_size=16, loss_fn=lm_loss,
        input_key="tokens", label_key="tokens",
        log_dir=str(tmp_path), telemetry=True, profile=False,
    )
    rows = [json.loads(l) for l in pathlib.Path(
        tmp_path / "NF_telemetry_0.jsonl").read_text().splitlines()]
    assert not [r for r in rows if r["kind"] == "fusion"]


def test_fit_warm_start_refreshes_compute_copy(tmp_path):
    """init_params replaces the masters AFTER tx.init cast the copy; the
    first fused step must see a copy of the WARM params, or the whole
    first step trains the discarded random init."""
    from tpudist.data.loader import DataLoader
    from tpudist.optim import fused_compute_params

    from flax import linen as nn

    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, 97, (16, 16)).astype(np.int32)
    model = _model()
    # unboxed, like every real warm-start source (tpudist.interop)
    warm = nn.meta.unbox(
        model.init(jax.random.key(123), tokens[:1], train=False)["params"]
    )
    state, _ = fit(
        model,
        # lr=0: params stay == the warm start, so the copy must too
        fused_adamw(0.0, compute_dtype=jnp.bfloat16),
        DataLoader({"tokens": tokens}, 16),
        epochs=1, job_id="WS", batch_size=16, loss_fn=lm_loss,
        input_key="tokens", label_key="tokens", fused="all",
        log_dir=str(tmp_path), profile=False, init_params=warm,
    )
    copy = fused_compute_params(state.opt_state, state.params)
    assert copy is not None
    for c, p in zip(jax.tree_util.tree_leaves(copy),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(
            np.asarray(c, np.float32),
            np.asarray(p.astype(jnp.bfloat16), np.float32),
        )
