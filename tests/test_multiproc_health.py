"""Run-health in a REAL 2-process world (2 × 4 emulated devices via
tpudist.launch): the cross-process aggregator's in-graph gather feeding
rank 0's straggler detection against an injected slow rank (and staying
silent on a healthy fleet), and the replica-divergence probe catching a
per-replica param perturbation injected on rank 1 only — the multi-host
forms of the single-process tests in test_health.py."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess world: cold-compiles its own jax programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_STRAGGLER_CHILD = textwrap.dedent("""
    import json, os, time

    import jax
    import numpy as np
    import optax

    from tpudist import create_mesh, init_from_env
    from tpudist.data.loader import DataLoader
    from tpudist.models.gpt2 import GPT2
    from tpudist.telemetry import TelemetryConfig
    from tpudist.train import fit, lm_loss

    ctx = init_from_env()
    mesh = create_mesh()
    sleep_s = float(os.environ.get("RANK1_SLEEP_S", "0"))

    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, 254, (64, 16)).astype(np.int32)
    # per-host disjoint rows (the straggler signal must come from the
    # TIMING skew, not from data divergence)
    rows = tokens[ctx.process_index::ctx.process_count]
    inner = DataLoader({"tokens": rows}, 16 // ctx.process_count)

    class PerBatchSleeper:
        # rank 1's input pipeline is slow EVERY batch — the persistent
        # straggler; rank 0's is instant
        def __init__(self, inner, s):
            self.inner, self.s = inner, s
            self.batch_size = inner.batch_size
        def __len__(self):
            return len(self.inner)
        def __iter__(self):
            for b in self.inner:
                if self.s:
                    time.sleep(self.s)
                yield b

    loader = PerBatchSleeper(
        inner, sleep_s if ctx.process_index == 1 else 0.0
    )
    model = GPT2(vocab_size=256, max_seq_len=16, hidden_dim=32, depth=1,
                 num_heads=2)
    cfg = TelemetryConfig(aggregate_every=2, straggler_patience=2,
                          mfu=False, sentry=False, heartbeat_every=0)
    state, losses = fit(
        model, optax.adam(1e-3), loader, epochs=4, mesh=mesh,
        batch_size=16, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", job_id="MH", profile=False, seed=0,
        log_dir=os.environ["OUT_DIR"], telemetry=cfg,
        world_size=ctx.process_count, global_rank=ctx.process_index,
    )
    assert len(losses) == 16
""")

_DIVERGENCE_CHILD = textwrap.dedent("""
    import json, os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import FrozenDict
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudist import create_mesh, init_from_env
    from tpudist.parallel.dp import make_divergence_probe
    from tpudist.train import TrainState
    from jax import shard_map

    ctx = init_from_env()
    mesh = create_mesh()
    repl = NamedSharding(mesh, P())
    clean_w = jax.jit(
        lambda: jnp.arange(64, dtype=jnp.float32), out_shardings=repl
    )()

    # desync ONE device's "replicated" copy inside a compiled program:
    # out_specs=P() claims replication while device 5 (a process-1 chip)
    # holds a perturbed copy — exactly the silent-desync failure mode,
    # produced the way real desync is (by device computation, not by a
    # host constructing inconsistent buffers)
    gmesh = Mesh(np.asarray(jax.devices()), ("g",))

    def perturb_device_5(x):
        i = jax.lax.axis_index("g")
        return x + jnp.float32(1e-3) * (i == 5).astype(jnp.float32)

    bad_w = jax.jit(
        shard_map(perturb_device_5, mesh=gmesh, in_specs=P(),
                  out_specs=P(), check_vma=False),
        out_shardings=NamedSharding(gmesh, P()),
    )(clean_w)

    def probe_counts(w):
        state = TrainState(
            step=jnp.zeros((), jnp.int32), params={"w": w},
            batch_stats=FrozenDict(), opt_state=(),
        )
        probe = make_divergence_probe(state, mesh)
        return {k: int(v) for k, v in probe(state).items()}

    clean = probe_counts(clean_w)
    desynced = probe_counts(bad_w)
    out = os.path.join(
        os.environ["OUT_DIR"], f"div_{ctx.process_index}.json"
    )
    with open(out, "w") as f:
        json.dump({"clean": clean, "desynced": desynced}, f)
""")


def _launch(tmp_path, child_src, out_dir, *, env_extra=None, port_off=0):
    script = tmp_path / "child.py"
    script.write_text(child_src)
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env["OUT_DIR"] = str(out_dir)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    port = 29650 + (os.getpid() + port_off) % 300
    r = subprocess.run(
        [
            sys.executable, "-m", "tpudist.launch",
            "--nproc_per_node=2", "--emulate-devices=4",
            f"--master_port={port}", str(script),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r


def _rows(path):
    return [json.loads(l) for l in open(path)]


def test_straggler_fires_on_slow_rank_and_not_on_healthy(tmp_path):
    slow = tmp_path / "slow"
    _launch(tmp_path, _STRAGGLER_CHILD, slow,
            env_extra={"RANK1_SLEEP_S": "0.25"}, port_off=0)
    rows0 = _rows(slow / "MH_telemetry_0.jsonl")
    fleet = [r for r in rows0 if r["kind"] == "fleet"]
    assert fleet, rows0
    # the gathered skew stats cover both hosts, and rank 1's host-side
    # share dwarfs rank 0's (the sleep lives in ITS input pipeline;
    # lockstep collectives equalize interval_s, which is exactly why the
    # aggregator folds host_s)
    last = fleet[-1]
    assert set(last["per_rank_step"]) == {"0", "1"}
    assert last["per_rank_host_s"]["1"] > last["per_rank_host_s"]["0"]
    stragglers = [r for r in rows0 if r["kind"] == "straggler"]
    assert len(stragglers) == 1, stragglers  # one-shot
    assert stragglers[0]["rank"] == 1
    # rank 1 writes no straggler row (rank-0 fold), but shares the fleet
    rows1 = _rows(slow / "MH_telemetry_1.jsonl")
    assert not [r for r in rows1 if r["kind"] == "straggler"]
    # the end-of-run report records the event and both ranks' last steps
    report = json.loads((slow / "MH_report.json").read_text())
    assert report["straggler_events"] and \
        report["straggler_events"][0]["rank"] == 1
    assert set(report["per_rank_last_seen"]) == {"0", "1"}

    healthy = tmp_path / "healthy"
    _launch(tmp_path, _STRAGGLER_CHILD, healthy,
            env_extra={"RANK1_SLEEP_S": "0"}, port_off=1)
    rows0 = _rows(healthy / "MH_telemetry_0.jsonl")
    assert [r for r in rows0 if r["kind"] == "fleet"]
    assert not [r for r in rows0 if r["kind"] == "straggler"]
    report = json.loads((healthy / "MH_report.json").read_text())
    assert report["straggler_events"] == []


def test_divergence_probe_catches_cross_process_perturbation(tmp_path):
    out = tmp_path / "div"
    _launch(tmp_path, _DIVERGENCE_CHILD, out, port_off=2)
    for rank in (0, 1):
        res = json.loads((out / f"div_{rank}.json").read_text())
        # clean replicas agree bitwise
        assert res["clean"]["replica_divergence"] == 0
        # device 5's perturbed copy disagrees with replica 0 — every
        # process sees the same (replicated) verdict in-graph, within ONE
        # probe, without any host-side cross-rank comparison
        assert res["desynced"]["replica_divergence"] == 1
