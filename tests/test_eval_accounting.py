"""Multi-process eval accounting (tpudist.train.evaluate).

Round-1 review finding: the denominator assumed every process feeds an
identical full-copy val loader, so a per-process SHARDED loader silently
mis-scaled accuracy. The fix counts both hits and the denominator from the
global padding mask in-graph. This test launches a real 2-process world
(4 emulated devices each) and requires the replicated-loader and
sharded-loader conventions to report the SAME accuracy on the same val set.
"""

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
    import optax

    from tpudist import create_mesh, init_from_env
    from tpudist.data.cifar import to_tensor
    from tpudist.data.digits import load_digits_dataset
    from tpudist.data.loader import DataLoader
    from tpudist.data.sampler import DistributedSampler
    from tpudist.models import resnet18
    from tpudist.train import create_train_state, evaluate

    ctx = init_from_env()
    mesh = create_mesh()
    model = resnet18(num_classes=10, small_inputs=True)
    state = create_train_state(
        model, 0, jnp.zeros((1, 32, 32, 3)), optax.adam(1e-3), mesh
    )

    val = load_digits_dataset(train=False)  # 360 rows, divisible by 2 procs

    # convention A (the reference's): every process iterates the FULL set
    rep_loader = DataLoader(val, 60, transform=to_tensor, drop_remainder=False)
    acc_rep = evaluate(model, state, rep_loader, mesh)

    # convention B: each process iterates its own disjoint shard; same
    # number of batches per process (6) keeps the collectives in lockstep
    sampler = DistributedSampler(
        len(val["label"]), num_replicas=ctx.process_count,
        rank=ctx.process_index, shuffle=False,
    )
    sh_loader = DataLoader(
        val, 30, sampler=sampler, transform=to_tensor, drop_remainder=False
    )
    acc_sh = evaluate(model, state, sh_loader, mesh)

    if ctx.process_index == 0:
        out = {"acc_rep": acc_rep, "acc_sh": acc_sh}
        with open(os.path.join(os.environ["OUT_DIR"], "acc.json"), "w") as f:
            json.dump(out, f)
""")


def test_sharded_and_replicated_val_loaders_agree(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = dict(os.environ)
    env["OUT_DIR"] = str(tmp_path)
    # the child script lives in tmp_path, so the repo must be importable
    # via PYTHONPATH rather than sys.path[0]
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    port = 29500 + os.getpid() % 500  # avoid colliding with a parallel run
    r = subprocess.run(
        [
            sys.executable, "-m", "tpudist.launch",
            "--nproc_per_node=2", "--emulate-devices=4",
            f"--master_port={port}", str(script),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads((tmp_path / "acc.json").read_text())
    # same 360 rows scored once (sharded) or twice-identically (replicated):
    # identical accuracy, and both in [0, 1]
    assert got["acc_rep"] == got["acc_sh"], got
    assert 0.0 <= got["acc_rep"] <= 1.0
