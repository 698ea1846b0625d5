"""``correct`` for the Laguna family on the CPU at a tiny preset: the plain
reference (``benchmarks/reference/laguna.py``) against the repo's model
through a whole run of the harness — full and sliding-window layers with
their own head counts, YaRN past its original positions, the gated output,
the dense layer, sigmoid top-2 of 8 experts on a held share of 4 under the
family's bias on the selection, the shared expert, the untied head — and
the control in the precision below. (Beside ``test_correct.py``, whose
presets are fixed in ``tiny.py``.)"""

import time

from benchmarks import cell, compare
from benchmarks.tests import tiny

HERE = "benchmarks/tests/configs/"


def bench() -> dict:
    out = tiny.bench()
    out["configs"] = [{"name": "laguna-tiny",
                       "file": HERE + "laguna-tiny.json"}]
    out["workloads"] = [{"name": "tiny_laguna", "config": "laguna-tiny",
                         "traffic": "tiny_train", "chips": 1}]
    return out


def run(**kw) -> dict:
    import jax

    devices = jax.devices()[:1]
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    return cell.run(
        bench(), "tiny_laguna", seed=2**31 + 12, seconds=0.3, trace=False,
        root=tiny.ROOT, t_start=time.perf_counter(), devices=devices,
        report=report, limits=tiny.LIMITS, **kw,
    )


def test_reference_agrees_with_the_program_and_control_does_not():
    seen = {}

    def extra(program, reference, again, batches):
        control = again("bfloat16", batches)  # one below the stated float32
        seen["control"] = compare.compare_first_steps(control, reference)

    result = run(on_compared=extra)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    ok, table = compare.judge(seen["control"], tiny.LIMITS)
    assert not ok, table
