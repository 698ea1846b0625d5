"""Multi-process RotatingDeviceCache equivalence.

The rotation's multi-process contract: the (seed, epoch) shard plan is
global, every process stages the SAME shard pixels, and per batch each
rank contributes its stride of the global within-shard order — so a
2-process world must compute the same loss sequence as the 1-process
world on the same data (the same global batch SET per step; row order
within the device array differs, which the global-batch mean is
invariant to). Mirrors tests/test_multiproc_fit.py's strategy for the
host loaders.
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
    import numpy as np
    import optax

    from tpudist import create_mesh, init_from_env
    from tpudist.data.cifar import synthetic_cifar
    from tpudist.data.device_cache import RotatingDeviceCache
    from tpudist.models import resnet18
    from tpudist.train import fit

    ctx = init_from_env()
    mesh = create_mesh()

    data = synthetic_cifar(n=64, num_classes=10)  # deterministic (seed 0)
    per_proc_batch = 16 // ctx.process_count
    rot = RotatingDeviceCache(
        data, per_proc_batch, shard_rows=32, mesh=mesh, seed=7,
    )
    model = resnet18(num_classes=10, small_inputs=True)
    state, losses = fit(
        model, optax.adam(1e-4), rot,
        epochs=2, mesh=mesh, profile=False, seed=0,
        batch_size=per_proc_batch, job_id="ROT",
        log_dir=os.environ["OUT_DIR"],
        input_transform=rot.input_transform(
            lambda x: x.astype(np.float32) / 255.0
        ),
    )
    out = {"rank": ctx.process_index, "world": ctx.process_count,
           "losses": losses, "final_step": int(state.step)}
    with open(os.path.join(
        os.environ["OUT_DIR"], f"rot_{ctx.process_index}.json"
    ), "w") as f:
        json.dump(out, f)
""")


def _launch(tmp_path, nproc, devices_per_proc, out_dir, *, port_off=0):
    script = tmp_path / "child_rot.py"
    script.write_text(_CHILD)
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env["OUT_DIR"] = str(out_dir)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    port = 29450 + (os.getpid() + port_off) % 300
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


def test_two_process_rotation_matches_single_process(tmp_path):
    one = tmp_path / "one"
    two = tmp_path / "two"
    _launch(tmp_path, 1, 8, one, port_off=0)
    _launch(tmp_path, 2, 4, two, port_off=1)

    la = json.loads((one / "rot_0.json").read_text())["losses"]
    lb0 = json.loads((two / "rot_0.json").read_text())["losses"]
    lb1 = json.loads((two / "rot_1.json").read_text())["losses"]

    # (64 rows / 32 shard_rows) shards x (32 / 16 global batch) = 4
    # steps/epoch x 2 epochs
    assert len(la) == len(lb0) == len(lb1) == 8
    # both ranks of the 2-process world agree bitwise
    np.testing.assert_array_equal(lb0, lb1)
    # the 2-process world computes the 1-process losses: same global batch
    # SET per step (rank strides partition the same shard window), same
    # seed init — step-1 agreement is the same-function certificate,
    # trajectory agreement is numerical (fp noise amplification)
    assert abs(la[0] - lb0[0]) < 2e-5, (la[0], lb0[0])
    np.testing.assert_allclose(la, lb0, rtol=0.05, atol=1e-3)
