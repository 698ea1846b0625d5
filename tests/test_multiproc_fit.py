"""Multi-process fit() equivalence (round-2 verdict item 4).

The training loop itself — not just evaluate() — runs in a real 2-process
world (2 × 4 emulated devices via tpudist.launch) and must compute the
SAME loss sequence as the 1-process × 8-device run on the same global
data: per-host sharded loaders through make_array_from_process_local_data,
verify_replicas' real multi-process branch, rank-0-only TSV rows, and
multi-process Orbax checkpointing with resume — all exercised in their
multi-process form.

Matches /root/reference/README.md:17-35 (the 2-node recipe) and
main.py:83 (DDP's rank-consistency check at wrap time).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # subprocess world: cold-compiles its own jax programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import json, os, sys

    import jax
    import optax

    from tpudist import create_mesh, init_from_env
    from tpudist.data.cifar import synthetic_cifar, to_tensor
    from tpudist.data.loader import DataLoader
    from tpudist.data.sampler import DistributedSampler
    from tpudist.models import resnet18
    from tpudist.train import fit

    ctx = init_from_env()
    mesh = create_mesh()
    epochs = int(os.environ.get("FIT_EPOCHS", "2"))
    ckpt_dir = os.environ.get("FIT_CKPT_DIR") or None

    data = synthetic_cifar(n=64, num_classes=10)  # deterministic (seed 0)
    # per-host sharded loading: each process gathers ONLY its rank's rows
    sampler = DistributedSampler(
        64, num_replicas=ctx.process_count, rank=ctx.process_index, seed=7
    )
    per_proc_batch = 16 // ctx.process_count
    loader = DataLoader(data, per_proc_batch, sampler=sampler,
                        transform=to_tensor)

    model = resnet18(num_classes=10, small_inputs=True)
    # lr small enough that losses stay O(1) across the run: collective
    # reduction order differs between world topologies, so trajectories
    # diverge chaotically once the loss nears zero — at O(1) losses the
    # per-step fp noise stays ~1e-6 and cross-topology agreement is tight
    state, losses = fit(
        model, optax.adam(1e-4), loader,
        epochs=epochs, mesh=mesh, profile=False, seed=0,
        job_id="MPF", log_dir=os.environ["OUT_DIR"],
        checkpoint_dir=ckpt_dir, checkpoint_every=3,
    )
    out = {
        "rank": ctx.process_index,
        "world": ctx.process_count,
        "losses": losses,
        "final_step": int(state.step),
    }
    path = os.path.join(
        os.environ["OUT_DIR"], f"fit_{ctx.process_index}.json"
    )
    with open(path, "w") as f:
        json.dump(out, f)
""")


def _launch(tmp_path, nproc, devices_per_proc, out_dir, *, epochs=2,
            ckpt_dir="", port_off=0):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env["OUT_DIR"] = str(out_dir)
    env["FIT_EPOCHS"] = str(epochs)
    env["FIT_CKPT_DIR"] = ckpt_dir
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    port = 29600 + (os.getpid() + port_off) % 300
    r = subprocess.run(
        [
            sys.executable, "-m", "tpudist.launch",
            f"--nproc_per_node={nproc}",
            f"--emulate-devices={devices_per_proc}",
            f"--master_port={port}", str(script),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r


def test_two_process_fit_matches_single_process(tmp_path):
    one = tmp_path / "one"
    two = tmp_path / "two"
    _launch(tmp_path, 1, 8, one, port_off=0)
    _launch(tmp_path, 2, 4, two, ckpt_dir=str(tmp_path / "ck"), port_off=1)

    la = json.loads((one / "fit_0.json").read_text())["losses"]
    lb0 = json.loads((two / "fit_0.json").read_text())
    lb1 = json.loads((two / "fit_1.json").read_text())

    # 4 steps/epoch x 2 epochs, every process records every step
    assert len(la) == len(lb0["losses"]) == len(lb1["losses"]) == 8
    # both ranks of the 2-process world agree bitwise (same compiled
    # program, same global arrays)
    np.testing.assert_array_equal(lb0["losses"], lb1["losses"])
    # and the 2-process world computes the 1-process losses: identical
    # global batches (same sampler permutation, rank-strided), identical
    # init (seed init + verify_replicas' real branch ran). Row order within
    # the device array and the collective reduction order differ between
    # topologies, so agreement is numerical: tight at step 1 (the
    # same-function certificate), and within an fp-noise-amplification band
    # across the trajectory
    assert abs(la[0] - lb0["losses"][0]) < 2e-5, (la[0], lb0["losses"][0])
    np.testing.assert_allclose(la, lb0["losses"], rtol=0.05, atol=1e-3)

    # rank-0-only TSV rows (the reference's contract, main.py:65-67,107):
    # both ranks write header+footer, only rank 0 writes data rows
    log0 = (two / "MPF_2_0.log").read_text().splitlines()
    log1 = (two / "MPF_2_1.log").read_text().splitlines()
    rows0 = [l for l in log0[1:] if not l.startswith("TrainTime")]
    rows1 = [l for l in log1[1:] if not l.startswith("TrainTime")]
    assert len(rows0) >= 1, log0
    assert rows1 == [], log1


def test_two_process_checkpoint_resumes(tmp_path):
    """The 2-process world's Orbax checkpoint restores into a NEW 2-process
    world, which resumes training exactly where the old one stopped."""
    two = tmp_path / "two"
    ck = str(tmp_path / "ck")
    _launch(tmp_path, 2, 4, two, epochs=2, ckpt_dir=ck, port_off=2)
    first = json.loads((two / "fit_0.json").read_text())
    assert first["final_step"] == 8

    # relaunch with epochs=3 and the same checkpoint_dir: restores step 8,
    # trains ONLY epoch 3's 4 steps
    three = tmp_path / "three"
    _launch(tmp_path, 2, 4, three, epochs=3, ckpt_dir=ck, port_off=3)
    resumed = json.loads((three / "fit_0.json").read_text())
    assert resumed["final_step"] == 12
    assert len(resumed["losses"]) == 4
    # training actually continued from the restored params, not a fresh
    # init: the resumed first loss sits well below the from-scratch first
    fresh_first = first["losses"][0]
    assert resumed["losses"][0] < fresh_first
