"""Launcher contract (tpudist.launch) — the torch.distributed.launch
equivalent (SURVEY.md §2.2, /root/reference/README.md:12-35).

Locks the env-var/argv contract (MASTER_ADDR/PORT, RANK, WORLD_SIZE,
LOCAL_RANK exported; --local_rank injected) and the fail-fast policy (one
dead rank terminates the world) without paying a jax bring-up — the full
multi-process training path is exercised by the e2e smoke recipes.
"""

import json
import os
import subprocess
import sys
import textwrap
import time
import pytest

pytestmark = pytest.mark.slow  # subprocess world: cold-compiles its own jax programs


def _run_launcher(tmp_path, extra_args, script_body, script_args=()):
    script = tmp_path / "child.py"
    script.write_text(script_body)
    cmd = [
        sys.executable, "-m", "tpudist.launch", *extra_args,
        str(script), *script_args,
    ]
    return subprocess.run(
        cmd, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )


def test_env_and_argv_contract(tmp_path):
    body = textwrap.dedent("""
        import json, os, sys
        out = {
            "env": {k: os.environ.get(k) for k in
                    ["MASTER_ADDR", "MASTER_PORT", "RANK", "WORLD_SIZE",
                     "LOCAL_RANK"]},
            "argv": sys.argv[1:],
        }
        path = os.path.join(os.environ["OUT_DIR"], f"r{os.environ['RANK']}.json")
        with open(path, "w") as f:
            json.dump(out, f)
    """)
    env_dir = tmp_path / "out"
    env_dir.mkdir()
    os.environ["OUT_DIR"] = str(env_dir)
    try:
        r = _run_launcher(
            tmp_path,
            ["--nproc_per_node=2", "--emulate-devices=1", "--nnode=2",
             "--node_rank=1",
             "--master_addr=10.0.0.1", "--master_port=29777"],
            body, ["--batch_size", "16"],
        )
    finally:
        del os.environ["OUT_DIR"]
    assert r.returncode == 0, r.stderr

    # node_rank=1 of 2x2 → global ranks 2 and 3
    for local_rank, rank in ((0, 2), (1, 3)):
        got = json.loads((env_dir / f"r{rank}.json").read_text())
        assert got["env"] == {
            "MASTER_ADDR": "10.0.0.1",
            "MASTER_PORT": "29777",
            "RANK": str(rank),
            "WORLD_SIZE": "4",
            "LOCAL_RANK": str(local_rank),
        }
        # --local_rank injected FIRST, user args preserved (reference
        # launcher contract, consumed at /root/reference/main.py:24)
        assert got["argv"] == [f"--local_rank={local_rank}", "--batch_size", "16"]


def test_fail_fast_terminates_world(tmp_path):
    body = textwrap.dedent("""
        import os, sys, time
        if os.environ["RANK"] == "1":
            sys.exit(3)
        time.sleep(60)  # rank 0 would hang the world; launcher must kill it
    """)
    t0 = time.time()
    r = _run_launcher(
        tmp_path, ["--nproc_per_node=2", "--emulate-devices=1"], body
    )
    assert r.returncode == 3
    assert time.time() - t0 < 30, "launcher did not fail fast"


def test_emulate_devices_env(tmp_path):
    body = textwrap.dedent("""
        import os, sys
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "--xla_force_host_platform_device_count=4" in os.environ["XLA_FLAGS"]
    """)
    r = _run_launcher(
        tmp_path, ["--nproc_per_node=2", "--emulate-devices=4"], body
    )
    assert r.returncode == 0, r.stderr


def test_max_restarts_recovers_transient_failure(tmp_path):
    """--max_restarts relaunches the node's world after a non-zero exit —
    the elastic-recovery extension over the reference's fail-fast; with the
    trainer's checkpoint resume this is the crash-recovery story."""
    body = textwrap.dedent("""
        import os, sys
        marker = os.path.join(os.environ["OUT_DIR"], "crashed_once")
        if not os.path.exists(marker):
            if os.environ["RANK"] == "1":
                open(marker, "w").close()
                sys.exit(7)   # transient: first generation loses rank 1
            import time; time.sleep(20)  # rank 0 waits to be terminated
        # second generation: everyone succeeds
    """)
    os.environ["OUT_DIR"] = str(tmp_path)
    try:
        r = _run_launcher(
            tmp_path,
            ["--nproc_per_node=2", "--emulate-devices=1", "--max_restarts=2"],
            body,
        )
    finally:
        del os.environ["OUT_DIR"]
    assert r.returncode == 0, r.stderr
    assert "restarting (1/2)" in r.stderr
    assert (tmp_path / "crashed_once").exists()


def test_sigterm_suppresses_restart(tmp_path):
    """SIGTERM to the LAUNCHER (scheduler preemption / supervisor stop) must
    shut the world down without burning restart attempts: the children's
    resulting non-zero exits are launcher-initiated, not failures."""
    import signal

    script = tmp_path / "child.py"
    script.write_text("import time; time.sleep(60)\n")
    p = subprocess.Popen(
        [sys.executable, "-m", "tpudist.launch", "--nproc_per_node=2",
         "--emulate-devices=1", "--max_restarts=5", str(script)],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # wait until both children actually exist (a fixed sleep races handler
    # installation on a loaded machine)
    for _ in range(100):
        ps = subprocess.run(
            ["ps", "--ppid", str(p.pid), "-o", "pid="],
            capture_output=True, text=True,
        )
        if len(ps.stdout.split()) >= 2:
            break
        time.sleep(0.2)
    p.send_signal(signal.SIGTERM)
    try:
        _, err = p.communicate(timeout=30)
    except subprocess.TimeoutExpired:
        p.kill()
        raise AssertionError("launcher kept restarting after SIGTERM")
    assert "restarting" not in err, err


def test_max_restarts_exhausted_reports_failure(tmp_path):
    body = textwrap.dedent("""
        import sys
        sys.exit(9)  # deterministic failure: every generation dies
    """)
    r = _run_launcher(
        tmp_path,
        ["--nproc_per_node=2", "--emulate-devices=1", "--max_restarts=1"],
        body,
    )
    assert r.returncode == 9
    assert r.stderr.count("restarting") == 1
