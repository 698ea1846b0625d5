"""``correct`` on the CPU at the tiny presets: the plain references against
the repo's models through a whole run of the harness, the control in the
precision below, and the faults a training cell can have planted under the
timed path."""

import numpy as np
import pytest

from benchmarks import compare
from benchmarks.tests import tiny


@pytest.mark.parametrize("name", ["tiny_gpt2", "tiny_bert"])
def test_reference_agrees_with_the_program_and_control_does_not(name):
    seen = {}

    def extra(program, reference, again, batches):
        control = again("bfloat16", batches)  # one below the stated float32
        seen["control"] = compare.compare_first_steps(control, reference)

    result = tiny.run(name, on_compared=extra)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "tokens_per_s_per_chip", "step_ms_p90", "setup_s"}
    ok, table = compare.judge(seen["control"], tiny.LIMITS)
    assert not ok, table


def unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        out, metrics = step(state, batch)
        return kept.replace(step=out.step), metrics
    return broken


def half_batch(step):
    def broken(state, batch):
        return step(state, {k: v[: len(v) // 2] for k, v in batch.items()})
    return broken


def no_exchange(chips):
    def plant(step):
        def broken(state, batch):
            # every chip gets the first chip's rows: each then holds the
            # gradient of its own rows only, as if nothing were exchanged
            mine = {k: np.tile(v[: len(v) // chips],
                               (chips,) + (1,) * (v.ndim - 1))
                    for k, v in batch.items()}
            return step(state, mine)
        return broken
    return plant


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_a_broken_step_is_not_correct(fault):
    with tiny.broken_step(fault):
        result = tiny.run("tiny_gpt2")
    assert not result["correct"], result["checks"]


def test_exchange_left_out_is_not_correct():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    assert tiny.run("tiny_gpt2", chips=4)["correct"]
    with tiny.broken_step(no_exchange(4)):
        result = tiny.run("tiny_gpt2", chips=4)
    assert not result["correct"], result["checks"]
