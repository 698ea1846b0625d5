"""The self-healing repair loop (tpudist.resilience.repair): unit
coverage for the policy engine (anchor promotion/demotion, skip-streak
arithmetic, sustained-spike rule, repeat-escalation, budget
circuit-breaker), the new chaos kinds (multi-spec parse, nanburst batch
poisoning, bitflip SDC injection), keep_last retention, and the
IN-PROCESS fit() drills the acceptance demands: a chaos-poisoned run
that detects, rolls back to the anchored checkpoint, skips the window,
books the repair row, and finishes with finite loss — state-level EQUAL
to a clean reference that simply never saw the skipped window (no
stochastic consumer → the repair salt legally changes nothing).

The fit drills run cache-less (``no_persistent_compile_cache``): the
rollback path is donated-step-on-restored-arrays, the exact pattern
XLA:CPU has been observed to misexecute from cache-LOADED executables
(the documented wart test_preempt_fit opts out for)."""

import json
import math

import numpy as np
import optax
import pytest
from flax import linen as nn

import jax
import jax.numpy as jnp

from tpudist import mesh as mesh_lib
from tpudist.data.loader import DataLoader
from tpudist.resilience import (
    GENERATION_ENV,
    ChaosCrash,
    ChaosInjector,
    ChaosSpec,
    RepairExhausted,
    RepairPolicy,
    RepairRestart,
    flip_param_bit,
    parse_chaos,
    resolve_policy,
)
from tpudist.resilience.repair import RepairController
from tpudist.telemetry import TelemetryConfig
from tpudist.train import fit


class _TinyMlp(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))


def _data(n=64):
    rng = np.random.default_rng(0)
    return {
        "image": rng.normal(size=(n, 13)).astype(np.float32),
        "label": (rng.random(n) * 10).astype(np.int32),
    }


# -- policy / chaos parsing --------------------------------------------------

def test_resolve_policy_coercions():
    assert resolve_policy(None) is None and resolve_policy(False) is None
    assert resolve_policy(True) == RepairPolicy()
    assert resolve_policy({"skip_window": 3}).skip_window == 3
    p = RepairPolicy(skip_streak=5)
    assert resolve_policy(p) is p
    with pytest.raises(ValueError):
        resolve_policy("yes")
    # salt 0 is the pristine seed — a never-repaired run's programs are
    # bit-identical to a repair-less one
    assert RepairPolicy().salted_seed(7, 0) == 7
    assert RepairPolicy().salted_seed(7, 2) != RepairPolicy().salted_seed(7, 1)


def test_parse_chaos_multi_and_single_compat():
    # single-spec strings parse byte-compatibly with ChaosSpec.parse
    assert parse_chaos("crash@12") == [ChaosSpec.parse("crash@12")]
    specs = parse_chaos("bitflip@10,nanburst:3@20")
    assert [s.kind for s in specs] == ["bitflip", "nanburst"]
    assert specs[0].step == 10 and specs[1].step == 20
    assert specs[1].count == 3
    # nanburst defaults to a 1-step burst; bitflip takes no ':n'
    assert parse_chaos("nanburst@4")[0].count == 1
    for bad in ("", ",", "bitflip:2@4", "nanburst:0@4", "sigterm:3@4"):
        with pytest.raises(ValueError):
            parse_chaos(bad)


def test_multi_spec_injector_fires_each_once_and_rearm():
    kills = []
    inj = ChaosInjector(
        parse_chaos("sigterm@3,crash@5@*"), generation=0,
        kill=lambda pid, sig: kills.append(sig),
    )
    assert inj.maybe_fire(3) is True and len(kills) == 1
    assert inj.maybe_fire(4) is False  # sigterm one-shot, crash not due
    with pytest.raises(ChaosCrash):
        inj.maybe_fire(5)
    assert inj.fired
    # rearm re-arms ONLY the @* deterministic-bug spec
    inj.rearm()
    assert inj.maybe_fire(3) is False  # the gen-pinned sigterm stays spent
    with pytest.raises(ChaosCrash):
        inj.maybe_fire(6)


def test_nanburst_wrap_poisons_exact_step_window():
    inj = ChaosInjector(parse_chaos("nanburst:2@6"), generation=0)
    batches = [
        {"image": np.ones((4, 3), np.float32), "label": np.zeros(4, np.int64)}
        for _ in range(8)
    ]
    # first batch trains step 5: poisoned steps are 7 and 8 only
    out = list(inj.wrap_batches(iter(batches), 5))
    poisoned = [i for i, b in enumerate(out)
                if not np.isfinite(b["image"]).all()]
    assert [5 + i for i in poisoned] == [7, 8]
    # the source batches are not mutated in place
    assert all(np.isfinite(b["image"]).all() for b in batches)
    # a generation-gated burst never poisons in generation 1
    gen1 = ChaosInjector(parse_chaos("nanburst:2@6"), generation=1)
    out1 = list(gen1.wrap_batches(iter(batches), 5))
    assert all(np.isfinite(b["image"]).all() for b in out1)


def test_nanburst_refuses_float_free_batch():
    inj = ChaosInjector(parse_chaos("nanburst@1"), generation=0)
    out = inj.wrap_batches(
        iter([{"tokens": np.zeros((2, 4), np.int32)}]), 2
    )
    with pytest.raises(ChaosCrash, match="no float"):
        list(out)


def test_flip_param_bit_visible_to_divergence_probe():
    from flax.core import FrozenDict

    from tpudist.parallel.dp import make_divergence_probe
    from tpudist.train import TrainState

    mesh = mesh_lib.create_mesh()
    repl = mesh_lib.replicated_sharding(mesh)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=jax.device_put(
            {"w": np.arange(32, dtype=np.float32)}, repl
        ),
        batch_stats=FrozenDict(), opt_state=(),
    )
    probe = make_divergence_probe(state, mesh)
    clean = {k: int(v) for k, v in probe(state).items()}
    assert clean["replica_divergence"] == 0
    flipped, info = flip_param_bit(state, mesh=mesh)
    assert info["leaf"].endswith("w") and info["flipped_locally"]
    bad = {k: int(v) for k, v in probe(flipped).items()}
    # exactly one replica disagrees — and replica 0 (the comparison
    # base) is never the corrupted one
    assert bad["replica_divergence"] == 1
    assert bad["replica_checksum"] == clean["replica_checksum"]
    # the value barely moved (one low mantissa bit): the SDC is silent
    # to every magnitude-based detector
    a = np.asarray(state.params["w"], np.float64)
    b = np.asarray(flipped.params["w"], np.float64)
    assert np.allclose(a, b, rtol=1e-5)


def test_flip_param_bit_refuses_unreplicated_state():
    from flax.core import FrozenDict

    from tpudist.train import TrainState

    mesh = mesh_lib.create_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=jax.device_put(
            {"w": np.arange(32, dtype=np.float32)}, sharded
        ),
        batch_stats=FrozenDict(), opt_state=(),
    )
    with pytest.raises(ChaosCrash, match="no fully-replicated"):
        flip_param_bit(state, mesh=mesh)


# -- controller units --------------------------------------------------------

class _FakeCkpt:
    def __init__(self, steps=(), anchor=None):
        self.steps = sorted(steps)
        self.anchor = anchor
        self.anchor_writes = []

    def read_anchor(self):
        return self.anchor

    def write_anchor(self, step):
        self.anchor = int(step)
        self.anchor_writes.append(int(step))

    def all_steps(self):
        return list(self.steps)


def _controller(tmp_path, policy=None, ckpt=None, clock=None, gen=0):
    ctl = RepairController(
        policy or RepairPolicy(anchor_clean_steps=3, skip_streak=3,
                               skip_window=4, repeat_window=6,
                               max_repairs=3, budget_window_s=600.0),
        tmp_path, generation=gen,
        **({"clock": clock} if clock else {}),
    )
    ctl.bind(ckpt if ckpt is not None else _FakeCkpt())
    return ctl


def _clean(step):
    return step, {"loss": 1.0, "update_skipped": 0, "nonfinite_grad_count": 0}


def test_anchor_promotion_and_demotion(tmp_path):
    ckpt = _FakeCkpt()
    ctl = _controller(tmp_path, ckpt=ckpt)
    ctl.on_save(4)
    for s in (5, 6):
        ctl.observe_step(*_clean(s))
    assert ctl.anchored is None  # 2 clean steps < K=3
    ctl.observe_step(*_clean(7))
    assert ctl.anchored == 4 and ckpt.anchor == 4  # promoted + persisted
    # a save followed by an UNHEALTHY step before K clean ones is
    # demoted — a checkpoint written mid-incubating-spike can never
    # become the rollback target
    ctl.on_save(8)
    ctl.observe_step(9, {"loss": float("nan")})
    for s in (10, 11, 12, 13):
        ctl.observe_step(*_clean(s))
    assert ctl.anchored == 4  # 8 never promotes
    # the next healthy save promotes normally
    ctl.on_save(14)
    for s in (15, 16, 17):
        ctl.observe_step(*_clean(s))
    assert ctl.anchored == 14


def test_skip_streak_trigger_arithmetic(tmp_path):
    ctl = _controller(tmp_path)
    # 2 skipped steps, then clean: streak resets, no trigger
    ctl.observe_step(5, {"loss": 1.0, "update_skipped": 1})
    ctl.observe_step(6, {"loss": 1.0, "update_skipped": 1})
    ctl.observe_step(*_clean(7))
    assert ctl.triggered is None
    # 3 consecutive (streak == policy.skip_streak) trigger; a lone
    # nonfinite grad count counts toward the same streak
    ctl.observe_step(8, {"loss": 1.0, "update_skipped": 1})
    ctl.observe_step(9, {"loss": float("inf")})
    ctl.observe_step(10, {"loss": 1.0, "nonfinite_grad_count": 2})
    trig = ctl.take_trigger()
    assert trig["cause"] == "skip_streak" and trig["streak"] == 3
    assert ctl.triggered is None  # consumed


def test_sustained_spike_trigger_vs_single_spike(tmp_path):
    ctl = _controller(tmp_path, policy=RepairPolicy(
        spike_patience=2, spike_window_steps=10))
    ctl.on_detection({"detector": "sentry", "event": "loss_spike",
                      "step": 5, "loss": 9.0})
    assert ctl.triggered is None  # one spike is news, not a verdict
    # a spike outside the window ages out
    ctl.on_detection({"detector": "sentry", "event": "loss_spike",
                      "step": 40, "loss": 9.0})
    assert ctl.triggered is None
    ctl.on_detection({"detector": "sentry", "event": "loss_spike",
                      "step": 45, "loss": 9.0})
    assert ctl.take_trigger()["cause"] == "loss_spike"
    # divergence triggers immediately — an SDC has no benign reading
    ctl.on_detection({"detector": "divergence", "step": 50,
                      "replica_divergence": 1, "state_nonfinite": 0})
    assert ctl.take_trigger()["cause"] == "sdc_divergence"
    # sentry 'nonfinite' events are left to the skip-streak arithmetic
    ctl.on_detection({"detector": "sentry", "event": "nonfinite",
                      "step": 55})
    assert ctl.triggered is None


def test_plan_rollback_then_repeat_restart_and_salt(tmp_path):
    clock = lambda: 1000.0
    ckpt = _FakeCkpt(steps=[2, 4, 8], anchor=8)
    ctl = _controller(tmp_path, ckpt=ckpt, clock=clock)
    assert ctl.salt == 0
    a1 = ctl.plan({"cause": "sdc_divergence"}, 12, max_step=100)
    assert (a1.kind, a1.rollback_step, a1.anchored) == ("rollback", 8, True)
    assert (a1.skip_from, a1.skip_to, a1.salt) == (12, 16, 1)
    assert a1.discarded_steps == 4
    ctl.record(a1)
    assert ctl.salt == 1
    # a trigger within repeat_window of the resume point escalates
    a2 = ctl.plan({"cause": "sdc_divergence"}, 20, max_step=100)
    assert a2.kind == "restart" and a2.salt == 2
    ctl.record(a2)
    assert ctl.pending is not None and ctl.pending["action"] == "restart"
    # the durable record round-trips into a fresh controller (the next
    # generation's bring-up), which consumes the directive
    ctl2 = _controller(tmp_path, ckpt=ckpt, clock=clock, gen=1)
    assert ctl2.salt == 2
    d = ctl2.consume_pending()
    assert d["skip_to"] == a2.skip_to
    assert ctl2.pending is None
    ctl3 = _controller(tmp_path, ckpt=ckpt, clock=clock, gen=1)
    assert ctl3.pending is None  # consumption is durable
    # far past the repeat window, the next trigger is a fresh incident
    a3 = ctl2.plan({"cause": "loss_spike"}, 80, max_step=100)
    assert a3.kind == "rollback"
    # skip_to clamps at the end of the run
    a4 = ctl2.plan({"cause": "loss_spike"}, 99, max_step=100)
    assert a4.skip_to == 100


def test_budget_circuit_breaker(tmp_path):
    now = {"t": 1000.0}
    ckpt = _FakeCkpt(steps=[4], anchor=4)
    ctl = _controller(
        tmp_path, ckpt=ckpt, clock=lambda: now["t"],
        policy=RepairPolicy(max_repairs=2, budget_window_s=100.0,
                            repeat_window=0, skip_window=0),
    )
    ctl.record(ctl.plan({"cause": "a"}, 10, max_step=1000))
    now["t"] += 10
    ctl.record(ctl.plan({"cause": "b"}, 50, max_step=1000))
    now["t"] += 10
    with pytest.raises(RepairExhausted, match="budget exhausted"):
        ctl.plan({"cause": "c"}, 90, max_step=1000)
    # the window ROLLS: once the old entries age out, repairs resume
    now["t"] += 200
    assert ctl.plan({"cause": "d"}, 130, max_step=1000).kind == "rollback"
    # max_repairs=0 disables the breaker entirely
    ctl0 = _controller(
        tmp_path, ckpt=ckpt,
        policy=RepairPolicy(max_repairs=0, repeat_window=0, skip_window=0),
    )
    for s in (10, 50, 90, 130):
        ctl0.record(ctl0.plan({"cause": "x"}, s, max_step=1000))


def test_no_rollback_target_exhausts(tmp_path):
    ctl = _controller(tmp_path, ckpt=_FakeCkpt(steps=[]))
    with pytest.raises(RepairExhausted, match="no checkpoint"):
        ctl.plan({"cause": "sdc_divergence"}, 5, max_step=100)


def test_supervisor_handles_exit_77_and_exports_history():
    from tpudist.resilience import EXIT_HISTORY_ENV, Supervisor, exit_history

    env = {}
    seen = []

    def run_world(generation):
        seen.append((generation, env.get(EXIT_HISTORY_ENV)))
        return [77, 77, 1][generation]

    sup = Supervisor(run_world, max_restarts=0, log=lambda m: None,
                     environ=env)
    # 77 rides the restartable fast path (no crash budget consumed);
    # the terminal crash (budget-exhausted poison) ends the job
    assert sup.run() == 1
    assert sup.exit_history == [77, 77, 1]
    # each relaunched generation saw its predecessors' exit codes
    assert seen == [(0, None), (1, "77"), (2, "77,77")]
    assert exit_history({EXIT_HISTORY_ENV: "77,77"}) == [77, 77]
    assert exit_history({EXIT_HISTORY_ENV: "garbage,75"}) == [75]
    assert exit_history({}) == []


def test_goodput_repair_components_sum_exactly():
    from tpudist.resilience import GoodputTracker
    from tpudist.resilience.goodput import COMPONENTS

    class _Clock:
        now = 0.0

        def __call__(self):
            return self.now

    clk, wall = _Clock(), _Clock()
    gp = GoodputTracker(generation=0, clock=clk, wall=wall)
    gp.loop_started()
    clk.now = 1.0
    gp.step_boundary()
    gp.add_repair(0.5, 2.0)
    clk.now = 8.0
    s = gp.summary("completed")
    assert s["repair_s"] == 0.5 and s["repair_replay_s"] == 2.0
    assert s["repairs"] == 1
    parts = sum(s[k] for k in COMPONENTS) + s["productive_step_s"]
    assert parts == pytest.approx(s["total_s"], rel=1e-9)
    assert s["cumulative"]["repair_overhead_s"] == pytest.approx(2.5)


def test_keep_last_prunes_and_anchor_is_exempt(tmp_path):
    from flax.core import FrozenDict

    from tpudist.checkpoint import Checkpointer
    from tpudist.train import TrainState

    def _state(step):
        return TrainState(
            step=jnp.asarray(step, jnp.int32),
            params={"w": jnp.full((4,), float(step))},
            batch_stats=FrozenDict(), opt_state={"m": jnp.zeros(4)},
        )

    with Checkpointer(tmp_path / "ck", keep_last=2) as ckpt:
        ckpt.save(_state(1), wait=True)
        ckpt.save(_state(2), wait=True)
        ckpt.write_anchor(2)
        for s in (3, 4, 5):
            ckpt.save(_state(s), wait=True)
        # newest 2 plus the anchored step survive; 1/3 pruned
        assert ckpt.all_steps() == [2, 4, 5]
        assert ckpt.read_anchor() == 2
        restored = ckpt.restore(like=_state(0), step=2)
        assert float(restored.params["w"][0]) == 2.0


def test_keep_last_protects_anchor_candidates_until_promotion(tmp_path):
    """Regression: with a save cadence denser than keep_last x
    anchor_clean_steps, retention used to delete a save BEFORE its
    promotion window elapsed — the later promotion then stamped the
    anchor file with a step dir that no longer existed, and the first
    rollback died on a missing checkpoint instead of self-healing. The
    controller's protect hook (bind wires Checkpointer.protect_steps)
    keeps candidates alive until they promote or demote."""
    from flax.core import FrozenDict

    from tpudist.checkpoint import Checkpointer
    from tpudist.train import TrainState

    def _state(s):
        return TrainState(
            step=jnp.asarray(s, jnp.int32),
            params={"w": jnp.full((4,), float(s))},
            batch_stats=FrozenDict(), opt_state={"m": jnp.zeros(4)},
        )

    with Checkpointer(tmp_path / "ck", keep_last=2) as ckpt:
        ctl = RepairController(
            RepairPolicy(anchor_clean_steps=10), tmp_path / "ck"
        ).bind(ckpt)
        # saves every 2 steps, clean health throughout: step 2's
        # promotion window (12) outlives keep_last=2 by several saves
        for s in range(1, 15):
            if s % 2 == 0:
                ckpt.save(_state(s), wait=True)
                ctl.on_save(s)
            ctl.observe_step(*_clean(s))
        assert ctl.anchored is not None
        # the promoted anchor step (and any still-pending candidates)
        # survived retention — the rollback target is restorable
        assert ctl.anchored in ckpt.all_steps()
        ckpt.restore(like=_state(0), step=ctl.anchored)
        # a DEMOTED candidate stops being protected: the next save's
        # prune reclaims it
        ctl.observe_step(15, {"loss": float("nan")})
        ckpt.save(_state(16), wait=True)
        assert len(ckpt.all_steps()) <= 2 + 1  # newest 2 + anchor


def test_fit_repair_requires_checkpointing(tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        fit(_TinyMlp(), optax.adam(1e-2), DataLoader(_data(), 16),
            epochs=1, job_id="RV", log_dir=str(tmp_path), profile=False,
            repair=True)
    with pytest.raises(ValueError, match="cadence"):
        fit(_TinyMlp(), optax.adam(1e-2), DataLoader(_data(), 16),
            epochs=1, job_id="RV", log_dir=str(tmp_path), profile=False,
            checkpoint_dir=str(tmp_path / "ck"), repair=True)


# -- the in-process drills ---------------------------------------------------

def _rows(path):
    return [json.loads(l) for l in path.read_text().splitlines()]


def _fit_kwargs(tmp_path, job, **over):
    kw = dict(
        epochs=8, job_id=job, batch_size=16, log_dir=str(tmp_path),
        profile=False,
        checkpoint_dir=str(tmp_path / f"{job}_ckpt"), checkpoint_every=2,
        repair={"skip_window": 4, "anchor_clean_steps": 2,
                "skip_streak": 3, "repeat_window": 8, "max_repairs": 3},
    )
    kw.update(over)
    return kw


def test_bitflip_full_loop_detect_rollback_skip_finish(
        tmp_path, monkeypatch, no_persistent_compile_cache):
    """The acceptance drill, no supervisor involved: an SDC at step 9 is
    caught by the divergence probe, state rolls back to the ANCHORED
    save, the cursor skips the window, the repair row/report/goodput all
    book it, and the run finishes with finite loss."""
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    cfg = TelemetryConfig(sentry=False, mfu=False, heartbeat_every=0,
                          divergence_every=2)
    state, losses = fit(
        _TinyMlp(), optax.adam(1e-2), DataLoader(_data(), 16),
        telemetry=cfg, chaos="bitflip@9",
        **_fit_kwargs(tmp_path, "BF"),
    )
    assert int(state.step) == 32
    assert all(math.isfinite(l) for l in losses)
    rows = _rows(tmp_path / "BF_telemetry_0.jsonl")
    div = [r for r in rows if r["kind"] == "divergence"]
    rep = [r for r in rows if r["kind"] == "repair"]
    assert div and div[0]["replica_divergence"] == 1
    assert len(rep) == 1
    r = rep[0]
    assert r["action"] == "rollback"
    assert r["cause"]["cause"] == "sdc_divergence"
    assert r["anchored"] is True
    # the anchor predates the flip: a save written while the SDC
    # incubated must never be the rollback target
    assert r["rollback_step"] <= 9
    # the skip actually skips: past the trigger by the policy window
    assert r["skip_to"] == r["skip_from"] + 4
    # losses: 32 scheduled steps minus the discarded span's resolved
    # rows plus nothing double-counted — every recorded loss is finite
    report = json.loads((tmp_path / "BF_report.json").read_text())
    assert report["status"] == "completed"
    assert [e["action"] for e in report["repairs"]] == ["rollback"]
    good = report["goodput"]
    assert good["repairs"] == 1
    assert good["repair_s"] > 0
    # partition stays exact with the new components
    parts = sum(good[k] for k in (
        "bringup_s", "restore_s", "compile_s", "cache_load_s",
        "data_wait_s", "checkpoint_s", "repair_s", "repair_replay_s",
        "productive_step_s",
    ))
    assert parts == pytest.approx(good["total_s"], rel=0.01)
    # the anchored step survived keep_last retention
    from tpudist.checkpoint import Checkpointer

    with Checkpointer(tmp_path / "BF_ckpt") as ck:
        assert ck.read_anchor() in ck.all_steps()


def test_nanburst_skip_streak_repairs_and_heals(
        tmp_path, monkeypatch, no_persistent_compile_cache):
    """Three consecutive poisoned steps defeat the single-step guard
    (each one is skipped, but the streak never ends inside the burst's
    window on a replay) — the skip-streak trigger rolls back and jumps
    PAST the burst, so the repaired run never sees those batches and
    finishes clean."""
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    cfg = TelemetryConfig(sentry=False, mfu=False, heartbeat_every=0)
    state, losses = fit(
        _TinyMlp(), optax.adam(1e-2), DataLoader(_data(), 16),
        telemetry=cfg, chaos="nanburst:3@6",
        **_fit_kwargs(tmp_path, "NB"),
    )
    assert int(state.step) == 32
    rep = [r for r in _rows(tmp_path / "NB_telemetry_0.jsonl")
           if r["kind"] == "repair"]
    assert len(rep) == 1
    assert rep[0]["cause"]["cause"] == "skip_streak"
    assert rep[0]["cause"]["streak"] == 3
    # the burst window [7, 9] sits inside the skipped span
    assert rep[0]["rollback_step"] <= 6
    assert rep[0]["skip_to"] > 9
    # the tail of the run is clean: every loss after the repair finite
    assert all(math.isfinite(l) for l in losses[-10:])


def test_repair_equivalence_to_clean_reference(
        tmp_path, monkeypatch, no_persistent_compile_cache):
    """A chaos-poisoned run that auto-repairs must MATCH a clean
    reference run that simply never saw the skipped window. No dropout
    and no stochastic rounding → the repair salt legally changes
    nothing, so the pin is state-level EXACT (same compiled program,
    same data sequence: batches [0, A) then [S, N))."""
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    cfg = TelemetryConfig(sentry=False, mfu=False, heartbeat_every=0,
                          divergence_every=2)
    data = _data()
    state, losses = fit(
        _TinyMlp(), optax.adam(1e-2), DataLoader(data, 16),
        telemetry=cfg, chaos="bitflip@9", seed=0,
        **_fit_kwargs(tmp_path, "EQ"),
    )
    rep = [r for r in _rows(tmp_path / "EQ_telemetry_0.jsonl")
           if r["kind"] == "repair"]
    assert len(rep) == 1
    anchor, skip_to = rep[0]["rollback_step"], rep[0]["skip_to"]

    # the reference: the same compiled-step config (telemetry +
    # guard_nonfinite change the program) driven by hand over the same
    # deterministic batch order, applying steps 1..anchor then
    # skip_to+1..N — the trajectory that never saw the skipped window
    from tpudist.train import (
        create_train_state, make_train_step, state_shardings_of,
    )

    mesh = mesh_lib.create_mesh()
    tx = optax.adam(1e-2)
    init_input = jnp.zeros(
        (mesh_lib.data_parallel_size(mesh), 13), jnp.float32
    )
    ref = create_train_state(_TinyMlp(), 0, init_input, tx, mesh)
    step_fn = make_train_step(
        _TinyMlp(), tx, mesh, dropout_seed=0,
        telemetry=True, guard_nonfinite=True,
        state_sharding=state_shardings_of(ref),
    )
    batches = list(DataLoader(data, 16))
    spe, total = len(batches), 8 * len(batches)
    for g in list(range(1, anchor + 1)) + list(range(skip_to + 1, total + 1)):
        ref, _ = step_fn(ref, batches[(g - 1) % spe])

    for path, a, b in zip(
        jax.tree_util.tree_leaves_with_path(state.params),
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(ref.params),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=str(path)
        )
    for a, b in zip(jax.tree_util.tree_leaves(state.opt_state),
                    jax.tree_util.tree_leaves(ref.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_repeat_trigger_exits_77_and_directive_resumes(
        tmp_path, monkeypatch, no_persistent_compile_cache):
    """Rung 3 in-process: a deterministic (@*-re-armed) SDC re-fires
    inside the repaired window → fit persists the rollback-and-skip
    directive and raises RepairRestart (SystemExit 77, restartable);
    the relaunched generation consumes the directive at bring-up
    (restores the ANCHOR, not the suspect newest save, and resumes past
    the wider skip)."""
    monkeypatch.delenv(GENERATION_ENV, raising=False)
    cfg = TelemetryConfig(sentry=False, mfu=False, heartbeat_every=0,
                          divergence_every=2)
    kw = _fit_kwargs(
        tmp_path, "RT", epochs=10, telemetry=cfg, chaos="bitflip@9@*",
        repair={"skip_window": 2, "anchor_clean_steps": 2,
                "repeat_window": 8, "max_repairs": 5},
    )
    loader = DataLoader(_data(), 16)
    with pytest.raises(RepairRestart) as ei:
        fit(_TinyMlp(), optax.adam(1e-2), loader, **kw)
    assert ei.value.code == 77
    blob = json.loads(
        (tmp_path / "RT_ckpt" / "tpudist_repair.json").read_text()
    )
    assert blob["pending"]["action"] == "restart"
    assert [e["action"] for e in blob["history"]] == ["rollback", "restart"]
    report = json.loads((tmp_path / "RT_report.json").read_text())
    assert report["status"] == "repair_restart"

    # generation 1 (the supervisor's relaunch): directive consumed, the
    # @* poison refires and the run keeps repairing within budget
    monkeypatch.setenv(GENERATION_ENV, "1")
    directive = dict(blob["pending"])
    try:
        state, _ = fit(_TinyMlp(), optax.adam(1e-2), loader, **kw)
        final = int(state.step)
    except RepairRestart:
        final = None  # escalated again before the budget — also valid
    blob = json.loads(
        (tmp_path / "RT_ckpt" / "tpudist_repair.json").read_text()
    )
    # the directive was consumed durably and a resume row was booked
    rows = _rows(tmp_path / "RT_telemetry_0.jsonl")
    resumes = [r for r in rows if r["kind"] == "repair"
               and r.get("action") == "resume"]
    assert len(resumes) == 1
    assert resumes[0]["rollback_step"] == directive["rollback_step"]
    assert resumes[0]["skip_to"] == directive["skip_to"]
    if final is not None:
        assert final == 40
