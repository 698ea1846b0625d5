"""Driver-contract test for ``__graft_entry__.dryrun_multichip``.

Round 1's only multi-chip artifact recorded failure (``ok=false``) because
``dryrun_multichip`` asserted 8 devices instead of provisioning them. This
test runs the function exactly the way the driver does — a fresh
interpreter with NO jax platform env vars and no conftest help — and
requires the self-provisioning path (re-exec onto a virtual CPU mesh) to
bring up all legs. Simulates the reference's multi-machine recipe
(/root/reference/README.md:17-35).
"""

import os
import subprocess
import sys
import pytest

pytestmark = pytest.mark.slow  # subprocess world: cold-compiles its own jax programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# env vars that would "help" (or hinder) the child; the driver sets none of
# them, so neither does this test
_SCRUBBED = (
    "JAX_PLATFORMS",
    "XLA_FLAGS",
    "_TPUDIST_DRYRUN_INPROC",
    "JAX_PLATFORM_NAME",
)


def test_dryrun_multichip_provisions_own_mesh():
    env = {k: v for k, v in os.environ.items() if k not in _SCRUBBED}
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import __graft_entry__; __graft_entry__.dryrun_multichip(8)",
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=880,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    # every leg of the strategy matrix must have run in the child
    for leg in (
        "DP+accum: ok",
        "CKPT(save+restore+step): ok",
        "TP: ok",
        "LLAMA(tp): ok",
        "LLAMA(scan+remat,tp): ok",
        "BERT(mlm,tp): ok",
        "PP: ok",
        "SP(ring): ok",
        "SP(ulysses): ok",
        "EP(moe): ok",
        "EP(llama-moe): ok",
        "FSDP: ok",
        "3D(dp*fsdp*tp): ok",
    ):
        assert leg in out, f"missing dryrun leg {leg!r} in output:\n{out}"
