"""``correct`` for the SDAR family on the CPU at a tiny preset: the plain
reference (``benchmarks/reference/sdar.py``) against the repo's model
through a whole run of the harness — the program's host-side block
corruption in the input path, the noised and the clean copy through one
stack under the block-diffusion mask, grouped-query attention with q/k
norms, softmax top-2 of 8 experts on a held share of 4 under the family's
bias on the selection, the 1/t-weighted loss over the noised rows — and the
control in the precision below. (Beside ``test_correct.py``, whose presets
are fixed in ``tiny.py``.)"""

import time

from benchmarks import cell, compare
from benchmarks.tests import tiny

HERE = "benchmarks/tests/configs/"


def bench() -> dict:
    out = tiny.bench()
    out["configs"] = [{"name": "sdar-tiny", "file": HERE + "sdar-tiny.json"}]
    out["workloads"] = [{"name": "tiny_sdar", "config": "sdar-tiny",
                         "traffic": "tiny_bd", "chips": 1}]
    return out


def run(**kw) -> dict:
    import jax

    devices = jax.devices()[:1]
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    return cell.run(
        bench(), "tiny_sdar", seed=2**31 + 13, seconds=0.3, trace=False,
        root=tiny.ROOT, t_start=time.perf_counter(), devices=devices,
        report=report, limits=tiny.LIMITS, **kw,
    )


def test_reference_agrees_with_the_program_and_control_does_not():
    seen = {}

    def extra(program, reference, again, batches):
        control = again("bfloat16", batches)  # one below the stated float32
        seen["control"] = compare.compare_first_steps(control, reference)
        seen["batch"] = batches[0]

    result = run(on_compared=extra)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    ok, table = compare.judge(seen["control"], tiny.LIMITS)
    assert not ok, table
    # the batches went through the program's transform: three arrays, the
    # mask id where the weight is positive and never among the clean ids
    batch = seen["batch"]
    assert set(batch) == {"tokens", "clean", "loss_weight"}
    assert ((batch["tokens"] == 255) == (batch["loss_weight"] > 0)).all()
    assert (batch["clean"] < 255).all()
