import json
import os
import subprocess
import sys

from benchmarks.tests.tiny import ROOT


def test_run_refuses_the_cpu_and_prints_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        name = json.load(f)["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", name, "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not any(line.startswith("{") and "correct" in line
                   for line in proc.stdout.splitlines())


def test_benchmark_json_names_only_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = os.path.join(ROOT, bench["paths"][0])
    for config in bench["configs"]:
        with open(os.path.join(ROOT, config["file"])) as f:
            family = json.load(f)["family"]
        assert os.path.exists(os.path.join(base, "families", family + ".py"))
    for cell in bench["workloads"]:
        for sub in ("traffic", "limits"):
            name = cell["traffic"] if sub == "traffic" else cell["name"]
            assert os.path.exists(os.path.join(base, sub, name + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        assert metric["moves"] in e2e
        assert os.path.exists(os.path.join(
            base, "layer_metrics", metric["name"] + ".py"))
