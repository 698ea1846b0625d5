"""The crash-recovery story end-to-end: a training process dies mid-run,
the launcher's --max_restarts relaunches the world, and fit() resumes from
the last checkpoint at the exact step — losses continue, no data is
re-trained or skipped (tpudist/launch.py + tpudist/train.py + checkpoint)."""

import json
import os
import subprocess
import sys
import textwrap
import pytest

pytestmark = pytest.mark.slow  # subprocess world: cold-compiles its own jax programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import json, os, sys

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpudist import create_mesh, init_from_env
    from tpudist.data.cifar import synthetic_cifar, to_tensor
    from tpudist.data.loader import DataLoader
    from tpudist.models import resnet18
    from tpudist.train import fit

    ctx = init_from_env()
    mesh = create_mesh()
    out_dir = os.environ["OUT_DIR"]
    crash_marker = os.path.join(out_dir, "crashed_once")

    from tpudist.checkpoint import latest_step

    ckpt_dir = os.path.join(out_dir, "ckpt")

    class CrashingLoader(DataLoader):
        # first generation: hard-die mid-run, deterministically AFTER a
        # checkpoint is durable on disk (gating on latest_step avoids any
        # race with the async save) and before the run completes
        def iter_from(self, start_batch):
            for i, b in enumerate(super().iter_from(start_batch), start=start_batch):
                yield b
                if (
                    not os.path.exists(crash_marker)
                    and latest_step(ckpt_dir) is not None
                ):
                    open(crash_marker, "w").close()
                    os.kill(os.getpid(), 9)  # hard kill, no cleanup

    data = synthetic_cifar(8 * 16, num_classes=10)  # 16 batches/epoch
    loader = CrashingLoader(data, 8, transform=to_tensor)
    model = resnet18(num_classes=10, small_inputs=True)
    state, losses = fit(
        model, optax.adam(1e-3), loader,
        epochs=2, mesh=mesh, profile=False,
        job_id="Crash", log_dir=out_dir,
        checkpoint_dir=ckpt_dir, checkpoint_every=4,
    )
    with open(os.path.join(out_dir, f"done_{ctx.process_index}.json"), "w") as f:
        json.dump({"final_step": int(state.step), "n_losses": len(losses)}, f)
""")


_RESILIENCE_CHILD = textwrap.dedent("""
    import json, os

    import jax
    import numpy as np
    import optax
    from flax import linen as nn

    from tpudist import create_mesh, init_from_env
    from tpudist.data.loader import DataLoader
    from tpudist.telemetry import TelemetryConfig
    from tpudist.train import fit

    ctx = init_from_env()
    mesh = create_mesh()
    out = os.environ["OUT_DIR"]

    class TinyMlp(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(10)(nn.relu(nn.Dense(37)(x)))

    rng = np.random.default_rng(0)
    data = {
        "image": rng.normal(size=(64, 13)).astype(np.float32),
        "label": (rng.random(64) * 10).astype(np.int32),
    }
    # per-process disjoint rows in a multi-process world; the full set in
    # a single-process one (16 steps either way: 4 epochs x 4 batches of
    # the global batch 16)
    rows = {k: v[ctx.process_index::ctx.process_count] for k, v in data.items()}
    loader = DataLoader(rows, 16 // ctx.process_count)
    cfg = TelemetryConfig(
        sentry=False, mfu=False, heartbeat_every=4,
        hang_timeout_s=float(os.environ.get("HANG_TIMEOUT_S", 0)) or None,
        hang_action=os.environ.get("HANG_ACTION", "report"),
        # the repair/SDC drills need the replica-divergence probe
        divergence_every=int(os.environ.get("DIV_EVERY", 0) or 0),
    )
    state, losses = fit(
        TinyMlp(), optax.adam(1e-2), loader,
        epochs=int(os.environ.get("EPOCHS", 4)), mesh=mesh, profile=False,
        job_id="SP", log_dir=out, batch_size=16,
        world_size=ctx.world_size, global_rank=ctx.process_index,
        telemetry=cfg,
        checkpoint_dir=os.path.join(out, "ckpt"),
        checkpoint_every=int(os.environ.get("CKPT_EVERY", 4)),
        chaos=os.environ.get("CHAOS") or None,
        # the self-healing drills: rollback-and-skip repair loop
        repair=(json.loads(os.environ["REPAIR"])
                if os.environ.get("REPAIR") else None),
        # the elastic/warm-start drills: cross-world resume + AOT cache
        reduce=os.environ.get("REDUCE", "none"),
        shard_opt_state=bool(os.environ.get("SHARD_OPT")),
        elastic=bool(os.environ.get("ELASTIC")),
        compile_cache=os.environ.get("COMPILE_CACHE") or None,
    )
    # only the generation that runs to completion reaches this line (a
    # preempted/hung generation exits 75/76 from inside fit)
    with open(os.path.join(out, f"done_{ctx.process_index}.json"), "w") as f:
        json.dump({
            "final_step": int(state.step),
            "n_losses": len(losses),
            "generation": int(os.environ.get("TPUDIST_RESTART_GENERATION", -1)),
            "losses": [float(l) for l in losses],
        }, f)
""")


def _launch_resilience_child(tmp_path, env_extra, launch_args, timeout=600):
    script = tmp_path / "child.py"
    script.write_text(_RESILIENCE_CHILD)
    env = dict(os.environ)
    env["OUT_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra)
    return subprocess.run(
        [
            sys.executable, "-m", "tpudist.launch", *launch_args,
            f"--master_port={29500 + os.getpid() % 499 + 1}",
            str(script),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_chaos_sigterm_supervised_resume(tmp_path):
    """The preemption drill through the REAL supervisor: generation 0
    traps the chaos SIGTERM after step 6, writes its emergency checkpoint
    and exits 75; the launcher restarts it (max_restarts=0 — the
    restartable fast path needs no crash budget) with generation=1, which
    resumes at step 7 and completes. The report aggregates both lives."""
    r = _launch_resilience_child(
        tmp_path, {"CHAOS": "sigterm@6"},
        ["--nproc_per_node=1", "--emulate-devices=4", "--max_restarts=0"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rc=75 (restartable); restarting generation 1" in r.stderr
    done = json.loads((tmp_path / "done_0.json").read_text())
    assert (done["final_step"], done["n_losses"], done["generation"]) == (
        16, 10, 1)

    report = json.loads((tmp_path / "SP_report.json").read_text())
    assert report["generation"] == 1
    assert report["exit_reason"] == "completed"
    gens = report["goodput"]["generations"]
    assert [g["generation"] for g in gens] == [0, 1]
    assert gens[0]["exit_reason"] == "preempted"
    assert gens[0]["emergency_save_s"] > 0
    assert report["goodput"]["cumulative"]["restart_overhead_s"] > 0
    # both lives share the append-mode telemetry stream, attributable by
    # the heartbeat generation field
    rows = [
        json.loads(l)
        for l in (tmp_path / "SP_telemetry_0.jsonl").read_text().splitlines()
    ]
    assert {r_["generation"] for r_ in rows if r_["kind"] == "heartbeat"} == {0, 1}


def test_watchdog_exit_escalation_supervised_restart(tmp_path):
    """Detection → forensics → recovery, end to end: a chaos hang at step
    5 trips the watchdog (1 s deadline), hang_action='exit' terminates the
    wedged generation with 76 AFTER the crash file lands, the supervisor
    relaunches, and generation 1 resumes from the step-4 checkpoint to
    completion."""
    r = _launch_resilience_child(
        tmp_path,
        {"CHAOS": "hang:120@5", "HANG_TIMEOUT_S": "1.0",
         "HANG_ACTION": "exit"},
        ["--nproc_per_node=1", "--emulate-devices=4", "--max_restarts=0"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rc=76 (restartable); restarting generation 1" in r.stderr
    crash = json.loads((tmp_path / "SP_crash_0.json").read_text())
    assert crash["trip"]["timeout_s"] == 1.0
    done = json.loads((tmp_path / "done_0.json").read_text())
    assert done["final_step"] == 16 and done["generation"] == 1
    # generation 1 resumed from the last cadence checkpoint (step 4):
    # the hung steps 5 re-ran, nothing before 4 did
    assert done["n_losses"] == 12
    report = json.loads((tmp_path / "SP_report.json").read_text())
    assert report["exit_reason"] == "completed"
    assert [g["exit_reason"] for g in report["goodput"]["generations"]] == [
        "hang", "completed"
    ]


def test_deterministic_crash_exhausts_restart_budget(tmp_path):
    """The circuit breaker: a world that dies identically every generation
    must exhaust the rolling restart budget and exit non-zero — never spin
    (even with a huge --max_restarts)."""
    script = tmp_path / "crashy.py"
    script.write_text("import sys; sys.exit(9)\n")
    r = subprocess.run(
        [
            sys.executable, "-m", "tpudist.launch", "--nproc_per_node=1",
            "--max_restarts=100", "--restart_budget=2",
            "--restart_window=600", "--backoff_base=0.05",
            "--backoff_max=0.1", str(script),
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 9
    assert r.stderr.count("restarting") == 2
    assert "restart budget exhausted" in r.stderr


def test_chaos_sigterm_two_process_world_resumes(tmp_path):
    """The preemption drill on a 2-process emulated world: every rank's
    chaos injector self-SIGTERMs at the same lockstep step boundary, both
    write their shards of the emergency checkpoint, both exit 75, and the
    supervised relaunch resumes the world at k+1 to completion."""
    r = _launch_resilience_child(
        tmp_path, {"CHAOS": "sigterm@6"},
        ["--nproc_per_node=2", "--emulate-devices=2", "--max_restarts=0"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "restarting generation 1" in r.stderr
    done = json.loads((tmp_path / "done_0.json").read_text())
    assert (done["final_step"], done["n_losses"], done["generation"]) == (
        16, 10, 1)
    report = json.loads((tmp_path / "SP_report.json").read_text())
    assert report["generation"] == 1
    assert report["goodput"]["generations"][0]["exit_reason"] == "preempted"


def test_repair_restart_escalation_and_budget_circuit_breaker(tmp_path):
    """The self-healing ladder under the REAL supervisor, against a
    DETERMINISTIC poison (``bitflip@5@*`` re-arms after every repair and
    every relaunch): generation 0 repairs in-process once (rollback to
    the anchored save + skip), the re-poisoned state re-triggers inside
    the repeat window → exit 77 with a durable rollback-and-skip
    directive; the supervisor relaunches on the restartable fast path;
    generation 1 consumes the directive, the poison bites again, and the
    rolling repair budget (max_repairs=2) circuit-breaks the job to a
    NON-ZERO exit instead of spinning forever."""
    r = _launch_resilience_child(
        tmp_path,
        {
            "CHAOS": "bitflip@5@*",
            "DIV_EVERY": "2",
            "CKPT_EVERY": "2",
            "EPOCHS": "10",
            "REPAIR": json.dumps({
                "skip_window": 2, "anchor_clean_steps": 5,
                "repeat_window": 8, "max_repairs": 2,
            }),
        },
        ["--nproc_per_node=1", "--emulate-devices=4", "--max_restarts=0"],
    )
    # the circuit breaker turned the deterministic poison into a
    # terminal non-zero exit — never rc 0, never an endless 77 loop
    assert r.returncode != 0, r.stdout + r.stderr
    assert "rc=77 (restartable); restarting generation 1" in r.stderr
    blob = json.loads(
        (tmp_path / "ckpt" / "tpudist_repair.json").read_text()
    )
    actions = [e["action"] for e in blob["history"]]
    assert "rollback" in actions and "restart" in actions
    # every rollback targeted a PRE-flip save: the anchored retention
    # never handed back a checkpoint written while the SDC incubated
    assert all(e["rollback_step"] <= 5 for e in blob["history"])
    report = json.loads((tmp_path / "SP_report.json").read_text())
    assert report["status"] == "crashed:RepairExhausted"
    assert report["generation"] == 1
    # one file reconstructs the incident timeline: the full repair
    # history plus the supervisor's per-generation exit codes
    assert [e["action"] for e in report["repairs"]] == actions
    assert report["supervisor_exit_history"] == [77]


def test_crash_restart_resumes_from_checkpoint(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = dict(os.environ)
    env["OUT_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "tpudist.launch",
            "--nproc_per_node=1", "--emulate-devices=4",
            f"--master_port={29500 + os.getpid() % 499 + 1}",
            "--max_restarts=1", str(script),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "restarting (1/1)" in r.stderr
    assert (tmp_path / "crashed_once").exists()
    got = json.loads((tmp_path / "done_0.json").read_text())
    # 2 epochs × 16 batches: the full run always ends at step 32
    assert got["final_step"] == 32, got
    # the relaunched fit() resumed from a durable checkpoint (multiple of
    # checkpoint_every=4, at least step 4) — NOT a from-scratch retrain
    assert got["n_losses"] < 32, got
    assert got["n_losses"] % 4 == 0, got


def test_elastic_supervised_resume_on_halved_world(tmp_path):
    """The elastic drill: generation 0 runs ZeRO-1 + quantized-AR on 8
    emulated devices and is chaos-SIGTERM'd after step 6; the launcher's
    per-generation ``--emulate-devices=8,4`` relaunches generation 1 on a
    HALVED world, where ``fit(elastic=True)`` reshards the checkpoint
    onto the 4-device mesh and completes. Losses after the resume track
    an uninterrupted same-data-order reference run within tolerance
    (rtol 0.08 — a resized world runs a different psum tree and draws
    different stochastic-rounding bits; the tier-1 state-level pin in
    test_elastic.py is exact)."""
    # reference: the same child, uninterrupted, on the original 8 devices
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    r = _launch_resilience_child(
        ref_dir, {"REDUCE": "quantized", "SHARD_OPT": "1"},
        ["--nproc_per_node=1", "--emulate-devices=8", "--max_restarts=0"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    ref = json.loads((ref_dir / "done_0.json").read_text())
    assert ref["n_losses"] == 16

    r = _launch_resilience_child(
        tmp_path,
        {"CHAOS": "sigterm@6", "REDUCE": "quantized", "SHARD_OPT": "1",
         "ELASTIC": "1"},
        ["--nproc_per_node=1", "--emulate-devices=8,4", "--max_restarts=0"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rc=75 (restartable); restarting generation 1" in r.stderr
    done = json.loads((tmp_path / "done_0.json").read_text())
    # global batch is device-count-free here (fixed 16-row loader), so
    # the cursor remap is identity: 10 steps remain after the resume
    assert (done["final_step"], done["n_losses"], done["generation"]) == (
        16, 10, 1)
    import numpy as np

    np.testing.assert_allclose(
        done["losses"], ref["losses"][6:], rtol=0.08
    )
    # the reshard really happened (and onto the halved world)
    rows = [
        json.loads(l)
        for l in (tmp_path / "SP_telemetry_0.jsonl").read_text().splitlines()
    ]
    (reshard,) = [r_ for r_ in rows if r_["kind"] == "reshard"]
    assert reshard["old_world"] == 8 and reshard["new_world"] == 4
    assert reshard["residual_flushed"] is True
    report = json.loads((tmp_path / "SP_report.json").read_text())
    assert [g["exit_reason"] for g in report["goodput"]["generations"]] == [
        "preempted", "completed"
    ]


def test_chaos_corrupt_supervised_fallback_resume(tmp_path):
    """The corrupt@step drill end-to-end: at step 7 the injector settles
    the async saves, truncates the newest checkpoint (step 6), and
    crashes — the torn-dir shape of dying mid-write. The supervised
    relaunch (a crash, so it needs --max_restarts) finds step 6
    undeserializable, falls back to step 4 with a checkpoint_fallback
    warning row, and completes: 12 post-resume steps, nothing before 4
    re-trained."""
    r = _launch_resilience_child(
        tmp_path, {"CHAOS": "corrupt@7", "CKPT_EVERY": "2"},
        ["--nproc_per_node=1", "--emulate-devices=4", "--max_restarts=1"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "restarting (1/1)" in r.stderr
    done = json.loads((tmp_path / "done_0.json").read_text())
    assert done["final_step"] == 16 and done["generation"] == 1
    assert done["n_losses"] == 12  # resumed from 4, not the corrupted 6
    rows = [
        json.loads(l)
        for l in (tmp_path / "SP_telemetry_0.jsonl").read_text().splitlines()
    ]
    fallbacks = [
        r_ for r_ in rows
        if r_["kind"] == "warning" and r_.get("tag") == "checkpoint_fallback"
    ]
    assert fallbacks and fallbacks[0]["failed_step"] == 6
    assert fallbacks[0]["next_step"] == 4


def test_warm_cache_supervised_restart_skips_compile(tmp_path):
    """The warm-restart drill: with ``compile_cache`` set, generation 0
    misses (AOT-compiles at bring-up and stores the executable) and the
    relaunched generation 1 hits — its goodput books cache_load_s with
    compile_s == 0 (iteration 1 was an ordinary step, not a mislabeled
    compile)."""
    r = _launch_resilience_child(
        tmp_path,
        {"CHAOS": "sigterm@6", "COMPILE_CACHE": str(tmp_path / "cc")},
        ["--nproc_per_node=1", "--emulate-devices=4", "--max_restarts=0"],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    done = json.loads((tmp_path / "done_0.json").read_text())
    assert (done["final_step"], done["generation"]) == (16, 1)
    rows = [
        json.loads(l)
        for l in (tmp_path / "SP_telemetry_0.jsonl").read_text().splitlines()
    ]
    cc_rows = [r_ for r_ in rows if r_["kind"] == "compile_cache"]
    assert [r_["hit"] for r_ in cc_rows] == [False, True]
    assert cc_rows[1]["compile_s"] == 0 and cc_rows[1]["load_s"] > 0
    report = json.loads((tmp_path / "SP_report.json").read_text())
    gen0, gen1 = report["goodput"]["generations"]
    assert gen0["warm_start"] is False and gen0["compile_s"] > 0
    assert gen1["warm_start"] is True
    assert gen1["compile_s"] == 0
    # goodput books the non-overlapped join wait (may be ~0 when the
    # load hid entirely behind the restore); the row's load_s is the
    # deserialization itself — and it must undercut the cold compile,
    # which is the drill's whole point
    assert gen1["cache_load_s"] >= 0
    assert 0 < cc_rows[1]["load_s"] < gen0["compile_s"]
