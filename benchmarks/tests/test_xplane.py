import os

import pytest

from benchmarks import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_clip():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert xplane.clip([(0, 4), (5, 10)], 3, 7) == [(3, 4), (5, 7)]


def test_exclusive_time_takes_children_off_their_parent():
    events = [("while", 0, 100), ("fusion.1", 10, 40), ("all-reduce.2", 50, 70),
              ("fusion.3", 120, 130)]
    got = {n: s for n, _, _, s in xplane.exclusive_times(events)}
    assert got == {"while": 50, "fusion.1": 30, "all-reduce.2": 20,
                   "fusion.3": 10}


def test_names():
    assert xplane.op_base("%fusion.123") == "fusion"
    assert xplane.op_base("h_0.2") == "h_0"
    assert xplane.op_name("%h_0.2 = (bf16[8]) custom-call(%x)") == "h_0.2"
    assert xplane.is_custom_call("%h_0.2 = (bf16[8]) custom-call(%x)")
    assert xplane.is_collective("%all-reduce-start.7")
    assert not xplane.is_collective("fusion.7")


def synthetic(chips=2):
    # two steps of 100 ns starting at 0 and 150, a third starting at 300;
    # in each: 60 ns compute, 20 ns all-reduce, 20 ns idle, then a 50 ns gap
    ops, modules = [], []
    for k in range(3):
        t = 150 * k
        modules.append(("jit_step_fn.1", t, t + 100))
        ops += [("fusion.1", t, t + 60), ("all-reduce.5", t + 60, t + 80)]
    dev = {"ops": ops, "modules": modules}
    host = [("bench_recorder", 105, 140), ("PjitFunction(step_fn)", 255, 300)]
    return {"devices": {f"/device:TPU:{i}": dev for i in range(chips)},
            "host": host}


def test_reduce_busy_idle_exposed_and_gaps():
    out = xplane.reduce(synthetic(), chips=2)
    # window: first step's start to the last step's start = 300 ns
    assert out["window_s"] == pytest.approx(300e-9)
    assert out["busy_s"] == pytest.approx(160e-9)
    assert out["collective_exposed_s"] == pytest.approx(40e-9)
    assert out["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(120e-9)]
    gaps = dict(map(tuple, out["breakdown"]["idle_gaps"]))
    assert gaps["bench_recorder"] == pytest.approx(70e-9)
    assert gaps["PjitFunction(step_fn)"] == pytest.approx(70e-9)


@pytest.mark.parametrize("name,chips", [("tiny_1chip.xplane.pb", 1),
                                        ("tiny_4chip.xplane.pb", 4)])
def test_recorded_tpu_trace(name, chips):
    path = os.path.join(HERE, "data", name)
    if not os.path.exists(path):
        pytest.skip(f"no recorded trace {name}")
    out = xplane.reduce(xplane.load(path), chips)
    assert out["devices"] == chips and out["steps"] >= 3
    # the tiny GPT-2's kernels, under the names the trace gives them
    assert any(n.startswith("h_0") for n in out["custom_call_ops"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["breakdown"]["device_ops"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    if chips > 1:
        assert out["collective_exposed_s"] > 0
