"""The test-only presets as a BENCHMARK.json-shaped dict, and one driver
that skips the harness's look for a chip and runs the rest of a run."""

import contextlib
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the tiny presets compute in float32 on the CPU: rounding-level limits
LIMITS = {"loss_gap": 1e-5, "grad_norm_gap": 1e-4, "change_norm_gap": 1e-3,
          "change_norm_gap_median": 1e-4, "grad_norm_gap_median": None}


def bench(chips: int = 1) -> dict:
    here = "benchmarks/tests/configs/"
    return {
        "command": ["python3", "benchmarks/run.py"],
        "configs": [
            {"name": "gpt2-tiny", "file": here + "gpt2-tiny.json"},
            {"name": "bert-tiny", "file": here + "bert-tiny.json"},
        ],
        "workloads": [
            {"name": "tiny_gpt2", "config": "gpt2-tiny",
             "traffic": "tiny_train", "chips": chips},
            {"name": "tiny_bert", "config": "bert-tiny",
             "traffic": "tiny_mlm", "chips": chips},
        ],
        "end_to_end": [
            {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
            {"name": "step_ms_p90", "unit": "ms"},
            {"name": "setup_s", "unit": "s"},
        ],
        "per_layer": [],
    }


def run(name: str, *, chips: int = 1, seed: int = 2**31 + 7,
        seconds: float = 0.3, **kw) -> dict:
    import jax

    from benchmarks import cell

    devices = jax.devices()[:chips]
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    return cell.run(
        bench(chips), name, seed=seed, seconds=seconds, trace=False,
        root=ROOT, t_start=time.perf_counter(), devices=devices,
        report=report, limits=LIMITS, **kw,
    )


@contextlib.contextmanager
def broken_step(break_it):
    """The timed path broken underneath: every step ``fit`` builds is
    ``break_it(step)`` for the length of the block."""
    import tpudist.train as train

    original = train.make_train_step

    def make_train_step(*args, **kwargs):
        step = original(*args, **kwargs)
        broken = break_it(step)
        broken.__dict__.update(step.__dict__)
        return broken

    train.make_train_step = make_train_step
    try:
        yield
    finally:
        train.make_train_step = original
