"""Elastic restart: cross-world-size checkpoint resharding
(tpudist/resilience/elastic.py + Checkpointer.restore(reshard=True) +
fit(elastic=True)) and the corrupt-checkpoint fallback walk — all
in-process on sub-meshes of the 8 fake CPU devices, so the ZeRO-1
pad-and-reshape relayout, the residual flush, the meta-validation
matrix, and the commit protocol are tier-1.

Tolerance note for the end-to-end trajectory pins: a resumed world of a
DIFFERENT size runs a different psum reduction tree and (under
reduce="quantized") folds different replica indices into the stochastic-
rounding stream, so post-resume losses track the same-data-order
reference within a documented tolerance, not bitwise — the BIT-exact pin
is the state-level one (`_logical_opt_state`: the resharded optimizer
mirrors equal the checkpoint's logical values exactly)."""

import json
import math

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import optax
import pytest
from flax import linen as nn
from jax.sharding import Mesh

from tpudist import mesh as mesh_lib
from tpudist.checkpoint import Checkpointer, latest_step
from tpudist.data.loader import DataLoader
from tpudist.optim import _zero1_layout, shard_state
from tpudist.resilience import GENERATION_ENV, Preempted
from tpudist.resilience.elastic import (
    ElasticRefusal,
    elastic_mismatch,
    refusal_reason,
    remap_step,
)
from tpudist.telemetry import TelemetryConfig
from tpudist.train import (
    create_train_state,
    fit,
    make_train_step,
    state_shardings_of,
)


def _mesh(n: int) -> Mesh:
    devs = np.array(jax.devices()[:n])
    return Mesh(
        devs.reshape(n, 1, 1, 1, 1, 1),
        (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS, mesh_lib.PIPELINE_AXIS,
         mesh_lib.EXPERT_AXIS, mesh_lib.SEQUENCE_AXIS,
         mesh_lib.TENSOR_AXIS),
    )


class _Mlp(nn.Module):
    """Layer widths chosen so the ZeRO-1 layout matrix is fully covered
    across worlds 4 and 8: (13,96)/(96,84) shard at both (96 divides 8),
    (84,35)=2940 has no 8-divisible dim (pad@8) but 84 divides 4
    (shard@4) — the classification-change case — and (35,10)/biases stay
    replicated below min_size."""

    @nn.compact
    def __call__(self, x, train=False):
        h = nn.relu(nn.Dense(96)(x))
        h = nn.relu(nn.Dense(84)(h))
        h = nn.relu(nn.Dense(35)(h))
        return nn.Dense(10)(h)


def _data(rows: int = 64):
    rng = np.random.default_rng(0)
    return {
        "image": rng.normal(size=(rows, 13)).astype(np.float32),
        "label": (rng.random(rows) * 10).astype(np.int32),
    }


def _build(world: int, *, reduce="quantized"):
    mesh = _mesh(world)
    tx = shard_state(optax.adam(1e-2), mesh)
    state = create_train_state(
        _Mlp(), 0, jnp.zeros((world, 13)), tx, mesh
    )
    step = make_train_step(
        _Mlp(), tx, mesh, reduce=reduce,
        state_sharding=state_shardings_of(state),
    )
    if step.grad_reducer is not None:
        state = step.grad_reducer.attach_residual(state)
    return mesh, tx, state, step


def _logical_opt_state(tx, state):
    """The stored opt state un-padded back to natural shapes on host —
    the world-size-free view both sides of a reshard must agree on
    bit-for-bit."""
    refs = jax.eval_shape(tx.inner.init, state.params)
    world = int(tx.mesh.shape[mesh_lib.DATA_AXIS])

    def restore(leaf, ref):
        mode, _ = _zero1_layout(ref.shape, world, 1024)
        x = np.asarray(leaf)
        if mode != "pad":
            return x
        return x.ravel()[: math.prod(ref.shape)].reshape(ref.shape)

    return jtu.tree_map(restore, state.opt_state, refs)


def _meta(world: int, spe: int = 4, **over) -> dict:
    m = {
        "steps_per_epoch": spe, "batch_size": 16, "world_size": 8,
        "grad_accum": 1, "shard_opt_state": True, "reduce": "quantized",
        "data_world": world,
    }
    m.update(over)
    return m


def _reshard_roundtrip(tmp_path, old_world, new_world):
    mesh_o, tx_o, state_o, step_o = _build(old_world)
    batch = {k: v[:16] for k, v in _data().items()}
    for _ in range(3):
        state_o, _ = step_o(state_o, step_o.stage(batch))
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.write_meta(_meta(old_world))
        ck.save(state_o, wait=True)

    mesh_n, tx_n, like, step_n = _build(new_world)
    events = []
    with Checkpointer(tmp_path / "ckpt") as ck:
        state_n = ck.restore(
            like=like, reshard=True, run_meta=_meta(new_world),
            mesh=mesh_n, on_event=events.append,
        )
    return tx_o, state_o, tx_n, state_n, step_n, events, batch


@pytest.mark.parametrize("old_world,new_world", [(8, 4), (4, 8)])
def test_zero1_reshard_roundtrip(tmp_path, old_world, new_world):
    """The exactness pin: after an 8→4 (and 4→8) reshard, params,
    batch-stats, and the LOGICAL values of every ZeRO-1 optimizer leaf —
    pad-and-reshape leaves un-padded, classification-change leaves
    included — are bit-identical to the checkpoint's; the residual banks
    come back zeroed at the NEW world's layout; and the restored state
    steps (the shardings really landed where the new step wants them)."""
    tx_o, state_o, tx_n, state_n, step_n, events, batch = (
        _reshard_roundtrip(tmp_path, old_world, new_world)
    )
    assert jtu.tree_all(jtu.tree_map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        state_o.params, state_n.params,
    ))
    a = _logical_opt_state(tx_o, state_o)
    b = _logical_opt_state(tx_n, state_n)
    assert jtu.tree_all(jtu.tree_map(
        lambda x, y: x.shape == y.shape and bool((x == y).all()), a, b
    ))
    # residual: world-bound → flushed to zeros at the NEW layout
    res = np.asarray(state_n.comm_residual)
    assert res.shape[0] == new_world and not res.any()
    assert int(state_n.step) == int(state_o.step)
    (ev,) = [e for e in events if e["tag"] == "reshard"]
    assert ev["old_world"] == old_world and ev["new_world"] == new_world
    assert ev["residual_flushed"] is True
    assert ev["resharded_leaves"] >= 2  # the pad-layout mu/nu leaves moved
    # and the new world actually trains on the resharded state
    state_n, metrics = step_n(state_n, step_n.stage(batch))
    assert np.isfinite(float(metrics["loss"]))


def test_reshard_handles_non_divisible_leaves(tmp_path):
    """The (84,35) kernel is pad-stored at world 8 ([8,368], 4 zeros of
    tail padding) but naturally sharded at world 4 — the classification-
    change case where the flat prefix must be the logical leaf exactly."""
    tx_o, state_o, tx_n, state_n, _, events, _ = _reshard_roundtrip(
        tmp_path, 8, 4
    )
    (ev,) = [e for e in events if e["tag"] == "reshard"]
    assert "opt_state/0/mu/Dense_2/kernel" in ev["resharded"]
    mu_o = _logical_opt_state(tx_o, state_o)[0].mu
    mu_n = _logical_opt_state(tx_n, state_n)[0].mu
    k = "Dense_2"
    assert mu_n[k]["kernel"].shape == (84, 35)
    assert (mu_o[k]["kernel"] == mu_n[k]["kernel"]).all()


def test_meta_matrix_reshard_vs_refusal():
    """The validation matrix: world-shaped differences reshard, semantic
    differences refuse, equality is not a mismatch at all."""
    base = _meta(8)
    # pure world resize (device count, world_size, steps_per_epoch,
    # batch_size): valid elastic mismatches
    assert elastic_mismatch(base, _meta(4))
    assert elastic_mismatch(base, _meta(8, spe=8, world_size=4))
    assert elastic_mismatch(base, _meta(8, batch_size=8))
    # semantic changes: refused, with the offending keys named
    assert "reduce" in refusal_reason(base, _meta(8, reduce="none"))
    assert "shard_opt_state" in refusal_reason(
        base, {k: v for k, v in _meta(8).items() if k != "shard_opt_state"}
    )
    # unknown future keys default-deny
    assert "mystery" in refusal_reason(base, dict(base, mystery=1))
    # no difference → no mismatch
    assert not elastic_mismatch(base, dict(base))
    # legacy metas predate data_world: a pre-elastic checkpoint resuming
    # at its own unchanged geometry must MATCH (no refusal, no
    # gratuitous reshard-commit), while a real resize still mismatches
    from tpudist.resilience.elastic import meta_matches

    legacy = {k: v for k, v in base.items() if k != "data_world"}
    assert meta_matches(legacy, base)
    assert not elastic_mismatch(legacy, base)
    assert not meta_matches(legacy, _meta(4, world_size=4))
    assert elastic_mismatch(legacy, _meta(4, world_size=4))


def test_expert_world_resize_default_denied():
    """The expert axis is a MODEL axis: the expert-scattered FFN stacks
    were written under their placement and have no reshard path, so an
    expert_world resize refuses with the same named hint as fsdp/tensor/
    pipe — and a legacy meta (pre expert recording) compares as 1."""
    saved = _meta(8, expert_world=2)
    reason = refusal_reason(saved, _meta(8, expert_world=4))
    assert reason is not None
    assert "expert_world 2 -> 4" in reason
    assert "only the data axis is elastic" in reason
    # legacy meta (no expert_world) at an unchanged all-dense geometry:
    # no refusal; resumed onto an expert-split mesh: default-denied
    legacy = _meta(8)
    assert refusal_reason(legacy, _meta(8, expert_world=1)) is None
    reason = refusal_reason(legacy, _meta(8, expert_world=2))
    assert reason is not None and "expert_world 1 -> 2" in reason


def test_refused_reshard_raises_elastic_refusal(tmp_path):
    """A non-resize mismatch must raise the refusal — never be mistaken
    for corruption and silently walked past by the fallback."""
    mesh_o, _, state_o, step_o = _build(8)
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.write_meta(_meta(8))
        ck.save(state_o, wait=True)
    mesh_n, _, like, _ = _build(4)
    with Checkpointer(tmp_path / "ckpt") as ck:
        with pytest.raises(ElasticRefusal, match="reduce"):
            ck.restore(
                like=like, reshard=True, mesh=mesh_n, fallback=True,
                run_meta=_meta(4, reduce="none"),
            )


def test_remap_step_cursor():
    # same steps/epoch → identity (the fixed-global-batch drill)
    assert remap_step(6, _meta(8, spe=4), _meta(4, spe=4)) == (6, True)
    # halved global batch → doubled steps/epoch → doubled counter, exact
    assert remap_step(6, _meta(8, spe=4), _meta(4, spe=8)) == (12, True)
    # doubled global batch → halved counter, exact at even steps
    assert remap_step(6, _meta(4, spe=8), _meta(8, spe=4)) == (3, True)
    # inexact ratio rounds DOWN (re-consume the partial batch, never skip)
    step, exact = remap_step(5, _meta(4, spe=8), _meta(8, spe=4))
    assert (step, exact) == (2, False)
    # missing steps_per_epoch (unsized loader) degrades to identity
    assert remap_step(7, {"steps_per_epoch": None}, _meta(8)) == (7, True)


def _fit_kwargs(tmp_path, world, job_id, **kw):
    cfg = TelemetryConfig(sentry=False, mfu=False, heartbeat_every=4)
    return dict(
        epochs=4, mesh=_mesh(world), job_id=job_id, batch_size=16,
        log_dir=str(tmp_path), telemetry=cfg, profile=False,
        reduce="quantized", shard_opt_state=True, **kw,
    )


def test_fit_elastic_resumes_8_to_4(tmp_path, monkeypatch,
                                    no_persistent_compile_cache):
    """The acceptance drill in-process: an 8-device ZeRO-1 +
    quantized-AR run is preempted at step 6; ``fit(elastic=True)`` on a
    4-device mesh reshards, commits (old-geometry step dirs replaced by
    the new-world save), and runs to completion with the post-resume
    trajectory tracking the uninterrupted 8-device reference (same data
    order; tolerance documented in the module docstring — the first
    resumed step, computed from bit-identical params, is pinned tight).
    Cache-less via no_persistent_compile_cache: XLA:CPU has been observed
    to abort executing persistent-cache-LOADED executables on the donated-
    step-on-restored-arrays pattern (test_preempt_fit's documented
    wart)."""
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    _, ref_losses = fit(
        _Mlp(), optax.adam(1e-2), DataLoader(_data(), 16),
        **_fit_kwargs(tmp_path, 8, "Ref"),
    )
    with pytest.raises(Preempted) as ei:
        fit(
            _Mlp(), optax.adam(1e-2), DataLoader(_data(), 16),
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=4,
            chaos="sigterm@6", **_fit_kwargs(tmp_path, 8, "EL"),
        )
    assert ei.value.step == 6

    # without elastic=True the resize still refuses, now with the hint
    with pytest.raises(ValueError, match="elastic=True"):
        fit(
            _Mlp(), optax.adam(1e-2), DataLoader(_data(), 16),
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=4,
            **_fit_kwargs(tmp_path, 4, "EL"),
        )

    monkeypatch.setenv(GENERATION_ENV, "1")
    state, losses = fit(
        _Mlp(), optax.adam(1e-2), DataLoader(_data(), 16),
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=4,
        chaos="sigterm@6", elastic=True,
        **_fit_kwargs(tmp_path, 4, "EL"),
    )
    assert int(state.step) == 16 and len(losses) == 10
    # step 7's loss is computed from the bit-identically restored params
    # (fp reduction order across 4-vs-8 devices is the only delta)
    assert losses[0] == pytest.approx(ref_losses[6], rel=1e-5)
    np.testing.assert_allclose(losses, ref_losses[6:], rtol=0.08)

    # the reshard was recorded, and the commit replaced the old-geometry
    # steps: everything on disk is new-world from the remapped step on
    rows = [
        json.loads(l)
        for l in (tmp_path / "EL_telemetry_0.jsonl").read_text().splitlines()
    ]
    reshard_rows = [r for r in rows if r["kind"] == "reshard"]
    assert len(reshard_rows) == 1
    assert reshard_rows[0]["old_world"] == 8
    assert reshard_rows[0]["new_world"] == 4
    steps_on_disk = sorted(
        int(d.name) for d in (tmp_path / "ckpt").iterdir()
        if d.is_dir() and d.name.isdigit()
    )
    assert min(steps_on_disk) >= 6 and max(steps_on_disk) == 16
    assert not (tmp_path / "ckpt" / "_pre_reshard").exists()
    report = json.loads((tmp_path / "EL_report.json").read_text())
    gens = report["goodput"]["generations"]
    assert [g["exit_reason"] for g in gens] == ["preempted", "completed"]
    assert gens[1]["restore_s"] > 0


def test_corrupt_checkpoint_falls_back_to_previous_step(tmp_path):
    """The satellite: a truncated newest step dir (the mid-write
    preemption shape, injected via the chaos helper) makes restore walk
    back to the previous saved step, emitting a checkpoint_fallback
    event — never poisoning the resume."""
    from tpudist.resilience.chaos import corrupt_latest_checkpoint

    mesh, tx, state, step = _build(8, reduce="none")
    batch = {k: v[:16] for k, v in _data().items()}
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.save(state, step=4, wait=True)
        for _ in range(2):
            state, _ = step(state, step.stage(batch))
        ck.save(state, step=8, wait=True)
        assert corrupt_latest_checkpoint(tmp_path / "ckpt") == 8
        assert ck.latest_step() == 8  # still points at the poisoned step

        _, _, like, _ = _build(8, reduce="none")
        events = []
        restored = ck.restore(
            like=like, fallback=True, on_event=events.append
        )
        assert int(restored.step) == 0  # the step-4 save held step 0's state
        (ev,) = [e for e in events if e["tag"] == "checkpoint_fallback"]
        assert ev["failed_step"] == 8 and ev["next_step"] == 4
        # without the fallback the corruption propagates
        with pytest.raises(Exception):
            ck.restore(like=like, step=8)
        # fit's cleanup: setting the torn step ASIDE (never deleting —
        # the failure may have been transient I/O) unblocks orbax's
        # monotonic save order (a cadence save at 6 < 8 was refused
        # while the corpse held latest_step)
        assert ck.save(state, step=6, wait=True) is False
        assert ck.quarantine_failed_step(8) is True
        assert ck.latest_step() == 4
        assert (tmp_path / "ckpt" / "_failed" / "8").is_dir()  # preserved
        assert ck.save(state, step=6, wait=True) is True
        assert ck.latest_step() == 6


def test_chaos_corrupt_spec_parses_and_fires(tmp_path):
    from tpudist.resilience import ChaosCrash, ChaosSpec, make_injector

    spec = ChaosSpec.parse("corrupt@3")
    assert spec.kind == "corrupt" and spec.step == 3
    mesh, _, state, _ = _build(4, reduce="none")
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.save(state, step=3, wait=True)
    sizes_before = {
        f: f.stat().st_size
        for f in (tmp_path / "ckpt" / "3").rglob("*") if f.is_file()
    }
    inj = make_injector("corrupt@3").bind(tmp_path / "ckpt")
    inj.generation = 0
    assert inj.maybe_fire(2) is False
    with pytest.raises(ChaosCrash, match="corrupted newest checkpoint"):
        inj.maybe_fire(3)
    # every file of the newest step really was truncated
    for f, before in sizes_before.items():
        assert f.stat().st_size == before // 2
    # unbound injector refuses loudly instead of corrupting nothing
    with pytest.raises(ChaosCrash, match="checkpoint_dir"):
        make_injector("corrupt@0").maybe_fire(0)


def test_atomic_meta_write_replaces_not_truncates(tmp_path, monkeypatch):
    """write_meta goes through tmp + os.replace: a crash mid-write can
    leave a stray tmp file but NEVER a torn tpudist_meta.json."""
    import os

    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.write_meta({"world_size": 8})
        assert ck.read_meta() == {"world_size": 8}

        real_replace = os.replace
        calls = []

        def spy(src, dst):
            calls.append((str(src), str(dst)))
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", spy)
        ck.write_meta({"world_size": 4})
        assert ck.read_meta() == {"world_size": 4}
        assert any(dst.endswith("tpudist_meta.json") for _, dst in calls)
        # the interrupted-write shape: the target never sees partial text
        monkeypatch.setattr(
            os, "replace",
            lambda *a: (_ for _ in ()).throw(OSError("disk gone")),
        )
        with pytest.raises(OSError):
            ck.write_meta({"world_size": 2})
        assert ck.read_meta() == {"world_size": 4}  # old meta intact
        leftovers = list((tmp_path / "ckpt").glob(".tpudist_meta.*"))
        assert leftovers == []  # tmp cleaned up on the failure path


def test_interrupted_reshard_commit_rolls_back(tmp_path):
    """Crash-window drill for the commit protocol: quarantined old steps
    with NO new-world save yet must roll back to a restorable directory
    (recover_interrupted_reshard), and a clean directory reports no
    interrupted commit."""
    mesh, tx, state, _ = _build(4, reduce="none")
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.save(state, step=4, wait=True)
        ck.quarantine_steps(commit_meta=_meta(8))  # ... process dies here
        assert ck.latest_step() is None
    with Checkpointer(tmp_path / "ckpt") as ck:
        assert ck.recover_interrupted_reshard() == "rolled_back"
        assert ck.latest_step() == 4
        # nothing left to recover
        assert ck.recover_interrupted_reshard() is None
        _, _, like, _ = _build(4, reduce="none")
        restored = ck.restore(like=like)
        assert int(restored.step) == 0


def test_interrupted_commit_after_save_adopts_marker_meta(tmp_path):
    """The other crash window: the barrier-save LANDED but the meta flip
    did not. The next bring-up must adopt the commit marker's meta — NOT
    re-reshard the already-new-world checkpoint (which would
    double-remap the cursor and collide the quarantine rename with the
    occupied step number)."""
    mesh, tx, state, _ = _build(4, reduce="none")
    new_meta = _meta(4, world_size=4)
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.write_meta(_meta(8))  # the OLD geometry
        ck.save(state, step=4, wait=True)
        ck.quarantine_steps(commit_meta=new_meta)
        ck.save(state, step=4, wait=True)  # the new-world barrier-save
        # ... and the process dies BEFORE write_meta(new_meta)
        assert ck.read_meta() == _meta(8)
    with Checkpointer(tmp_path / "ckpt") as ck:
        assert ck.recover_interrupted_reshard() == "completed"
        # the live step is now correctly described by the marker's meta
        # and the quarantine (old dirs + marker) is gone
        assert ck.read_meta() == new_meta
        assert ck.latest_step() == 4
        assert not (tmp_path / "ckpt" / "_pre_reshard").exists()
        # a second bring-up sees a clean, consistent directory
        assert ck.recover_interrupted_reshard() is None


def test_aot_step_routes_ragged_tail_to_jit(tmp_path,
                                            no_persistent_compile_cache):
    """A drop_remainder=False loader's short final batch must not kill a
    compile_cache run: the AOT wrapper routes off-shape batches to the
    jit path per call and keeps the executable for full batches."""
    from tpudist import compile_cache as cc_mod

    mesh, tx, state, step = _build(8, reduce="none")
    full = {k: v[:16] for k, v in _data().items()}
    ragged = {k: v[:8] for k, v in _data().items()}
    staged_full = step.stage(full)
    exe = step.jitted.lower(state, staged_full).compile()
    wrapped = cc_mod.wrap_step(step, exe, expected_batch=staged_full)
    state, m1 = wrapped(state, full)     # validates the executable
    state, m2 = wrapped(state, ragged)   # off-shape → jit, not a crash
    state, m3 = wrapped(state, full)     # back on the executable
    assert all(np.isfinite(float(m["loss"])) for m in (m1, m2, m3))
    assert wrapped.aot["exe"] is not None  # never demoted
