"""The quickest proof that tpudist still starts on the chip.

Drives the main paths once, on the TPU, through the entry points a user
calls — ``main.py`` (ResNet-50 trainer, profiler on), ``examples/
train_gpt2.py`` (GPT-2 124M trainer, Pallas kernels on) and ``examples/
serve_gpt2.py`` (the serving engine, contiguous; then the same prompts
through a paged engine) — at full model width, on random weights and data
made from a fixed seed, and checks what comes out by the repo's own means.

    python chip_smoke.py [--out DIR]            # one chip, three phases
    python chip_smoke.py --chips 4 [--out DIR]  # ONLY the four-chip phase

One process, which holds the chip itself and starts no child. There is no
CPU branch: without a TPU it exits non-zero before any phase. Every phase
prints one JSON line; any failing phase makes the exit code non-zero. The
last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. What the phases print themselves goes
to ``<out>/<phase>.log``.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import math
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# -- the runs, as a user would type them -------------------------------------
# sets small enough to repeat, so a falling loss is memorisation at work:
# 512 images / 128 = 4 steps x 4 epochs; 32 windows / 8 = 4 steps x 4 epochs
VISION_ARGV = [
    "--model", "resnet50", "--dataset", "synthetic", "--bf16",
    "--batch_size", "128", "--synthetic_size", "512", "--epochs", "4",
]
GPT2_124M = dict(vocab_size=50257, max_seq_len=1024, hidden_dim=768,
                 depth=12, num_heads=12)
LM_ARGV = [
    "--bf16", "--batch_size", "8", "--attn", "auto", "--fused", "all",
    "--telemetry", "--no_profiler", "--synthetic_tokens", str(32 * 1024),
    "--epochs", "4", "--warmup_steps", "2",
]
SERVE_ARGV = ["--bf16", "--requests", "4", "--max_new", "16", "--slots", "2"]
PAGED = dict(block_size=16, n_blocks=2 * 64 + 1)  # 2 slots x 1024 tokens
# four chips: 8 sequences per chip as above; the one-device side takes the
# same 32 as 4 microbatches of 8 (32 x 1024 logits do not fit one chip)
MC_PER_CHIP, MC_STEPS = 8, 6
# the tolerance __graft_entry__.dryrun_multichip holds DP agreement to
DP_REL_TOL = 2e-4


def _fail(msg: str) -> None:
    raise AssertionError(msg)


class CompileMeter:
    """Compile seconds and persistent-cache traffic, from JAX's own
    monitoring events: tracing, lowering, and backend compile-or-load.
    The events nest (an inner jit is traced inside the outer trace), so
    the seconds are the length of the union of their intervals."""

    _COMPILE = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.intervals: list[tuple[float, float]] = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event in self._COMPILE:
            end = time.perf_counter()  # the listener fires as the event ends
            self.intervals.append((end - seconds, end))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def compile_seconds(self, since: float) -> float:
        """Seconds since ``since`` (a ``perf_counter`` reading) covered by
        at least one compile event."""
        total, covered_to = 0.0, since
        for start, end in sorted(self.intervals):
            start = max(start, covered_to)
            if end > start:
                total += end - start
                covered_to = end
        return total


@contextlib.contextmanager
def dumped_ir(directory: str):
    """JAX writes every module it lowers in here as StableHLO text; the
    directory is removed on the way out (a step's text runs to megabytes)."""
    import jax

    jax.config.update("jax_dump_ir_to", directory)
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", None)
        shutil.rmtree(directory, ignore_errors=True)


def custom_calls_in(directory: str, module: str) -> int:
    """``tpu_custom_call`` sites in the dumped module(s) of jitted function
    ``module``: a Pallas kernel that compiled for the chip is one; a kernel
    that interpreted, or gave way to a reference, is plain HLO."""
    files = glob.glob(os.path.join(directory, f"*jit_{module}_compile.mlir"))
    if not files:
        _fail(f"no lowered module named jit_{module} under {directory}")
    n = 0
    for path in files:
        with open(path) as f:
            n += f.read().count("tpu_custom_call")
    return n


def rows_of(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def check_losses(losses) -> dict:
    losses = [float(x) for x in losses]
    if not losses or not all(math.isfinite(x) for x in losses):
        _fail(f"non-finite or missing losses: {losses}")
    if len(losses) < 6:
        _fail(f"only {len(losses)} steps ran")
    first, last = sum(losses[:3]) / 3, sum(losses[-3:]) / 3
    if not last < first:
        _fail(f"loss did not fall: first three {first:.4f}, last {last:.4f}")
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1]}


# -- phases ------------------------------------------------------------------


def phase_vision(out: str) -> dict:
    """main.py: ResNet-50 bf16, batch 128, synthetic CIFAR shapes, with the
    profiler left on (the reference's default: wait 2, warmup 2, active 6)."""
    import jax
    import main as trainer

    from tpudist import csrc

    job = "SmokeVision"
    _, losses = trainer.main(
        VISION_ARGV + ["--JobID", job, "--log_dir", out]
    )
    info = check_losses(losses)
    if info["steps"] < 12:
        _fail(f"{info['steps']} steps: the profiler window needs 12")

    batch = VISION_ARGV[VISION_ARGV.index("--batch_size") + 1]
    with open(os.path.join(out, f"{job}_{batch}_0.log")) as f:
        tsv = f.read().splitlines()
    if tsv[0] != "datetime\tg_step\tg_img\tloss_value\texamples_per_sec":
        _fail(f"TSV header: {tsv[0]!r}")
    data = [ln for ln in tsv[1:] if len(ln.split("\t")) == 5]
    if not data:
        _fail("TSV log has no data rows")
    if not any(ln.startswith("TrainTime\t") for ln in tsv):
        _fail("TSV log has no TrainTime footer")

    traces = glob.glob(os.path.join(
        out, f"log_{job}", "plugins", "profile", "*", "*.xplane.pb"
    ))
    if not traces:
        _fail("the profiler wrote no *.xplane.pb")
    events = 0
    for plane in jax.profiler.ProfileData.from_file(traces[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            events += sum(len(list(line.events)) for line in plane.lines)
    if not events:
        _fail(f"no TPU device plane with events in {traces[0]}")
    return {**info, "tsv_rows": len(data), "tpu_trace_events": events,
            "native_core": csrc.lib() is not None}


def phase_lm(out: str) -> dict:
    """examples/train_gpt2.py at its defaults (GPT-2 124M, seq 1024) with
    bf16, batch 8, --attn auto, --fused all and telemetry."""
    import jax
    import train_gpt2

    from tpudist.telemetry import flops

    job, ir = "SmokeLM", os.path.join(out, "ir_lm")
    with dumped_ir(ir):
        _, losses = train_gpt2.main(
            LM_ARGV + ["--JobID", job, "--log_dir", out]
        )
        kernels = custom_calls_in(ir, "step_fn")
    info = check_losses(losses)
    if not kernels:
        _fail("the lowered train step holds no tpu_custom_call: the Pallas "
              "kernels interpreted or gave way to a reference")
    sys.stdout.flush()  # this phase's own log, so far
    with open(os.path.join(out, "lm.log")) as f:
        if "attn: auto -> vmem" not in f.read():
            _fail("--attn auto did not resolve to vmem")

    rows = rows_of(os.path.join(out, f"{job}_telemetry_0.jsonl"))
    kind = jax.devices()[0].device_kind
    (fusion,) = [r for r in rows if r["kind"] == "fusion"]
    if not (fusion["ln"] and fusion["optimizer"]):
        _fail(f"fusion row does not name both kernels: {fusion}")
    (meta,) = [r for r in rows if r["kind"] == "run_meta"]
    if (meta["device_kind"] != kind
            or meta["peak_flops_per_chip"] != flops.device_peaks(kind)[0]):
        _fail(f"run_meta does not carry this chip's peak: {meta}")
    mfus = [r["mfu"] for r in rows if r["kind"] == "mfu"]
    if not mfus or not all(m is not None and 0.0 < m < 1.0 for m in mfus):
        _fail(f"MFU rows: {mfus}")
    return {**info, "tpu_custom_calls": kernels, "attn": "vmem",
            "device_kind": kind, "mfu_rows": mfus}


def phase_serve(out: str) -> dict:
    """examples/serve_gpt2.py at the 124M geometry (contiguous KV cache),
    then the same prompts through a paged engine whose decode tick runs
    the paged Pallas kernel; greedy, so the two streams must be equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import serve_gpt2

    from tpudist.models.gpt2 import GPT2
    from tpudist.serve import ServeEngine
    from tpudist.telemetry import TelemetrySink

    rng = np.random.Generator(np.random.PCG64(SEED))
    prompts = [
        rng.integers(0, GPT2_124M["vocab_size"], int(rng.integers(4, 64)))
        for _ in range(4)
    ]
    job = "SmokeServe"
    argv = SERVE_ARGV + ["--seed", str(SEED), "--JobID", job,
                         "--log_dir", out]
    for p in prompts:
        argv += ["--prompt", ",".join(str(int(t)) for t in p)]
    snap, contiguous = serve_gpt2.main(argv)
    if snap["completed"] != len(prompts):
        _fail(f"{snap['completed']} of {len(prompts)} requests completed")
    if [len(r) for r in contiguous] != [16] * len(prompts):
        _fail(f"stream lengths {[len(r) for r in contiguous]}, want 16 each")
    kinds = {r["kind"] for r in rows_of(
        os.path.join(out, f"{job}_serve_0.jsonl")
    )}
    if "serve_summary" not in kinds:
        _fail(f"no serve_summary row (kinds: {sorted(kinds)})")

    # the example has no paged flag: build that engine directly, on the
    # weights the example made (same seed, same init, same cast)
    model = GPT2(dtype=jnp.bfloat16, attn_impl="vmem", **GPT2_124M)
    params = model.init(
        jax.random.key(SEED), np.zeros((1, 8), np.int32), train=False
    )["params"]
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params,
    )
    ir = os.path.join(out, "ir_paged")
    with dumped_ir(ir), TelemetrySink(
        os.path.join(out, f"{job}Paged_serve_0.jsonl")
    ) as sink:
        engine = ServeEngine(model, params, max_slots=2, seed=SEED,
                             sink=sink, paged=True, **PAGED)
        rids = [engine.submit(p.astype(np.int32), 16) for p in prompts]
        engine.run()
        paged = [engine.result(r) for r in rids]
        paged_snap = engine.stats.snapshot()
        engine.close()
        kernels = custom_calls_in(ir, "step")
    if not kernels:
        _fail("the paged decode tick holds no tpu_custom_call")
    if paged_snap["completed"] != len(prompts):
        _fail(f"paged: {paged_snap['completed']} requests completed")
    if paged != contiguous:
        _fail(f"greedy streams differ: contiguous {contiguous} vs paged "
              f"{paged}")
    # no rate here: the engines' clocks include every first-use compile
    return {"requests": len(prompts), "tokens": snap["tokens"],
            "paged_tokens": paged_snap["tokens"],
            "paged_tpu_custom_calls": kernels}


def _gpt2_124m(mesh):
    """GPT-2 124M and its optimizer the way examples/train_gpt2.py builds
    them under ``--bf16 --attn auto --fused all`` (vmem attention, fused LN,
    fused AdamW with the bf16 compute copy). ``mesh=None``: no kernel wraps
    itself in a shard_map — the form for per-replica code."""
    import jax.numpy as jnp

    from tpudist.models.gpt2 import GPT2
    from tpudist.optim import make_optimizer, run_schedule

    model = GPT2(dtype=jnp.bfloat16, attn_impl="vmem", mesh=mesh,
                 **GPT2_124M)
    tx = make_optimizer(
        run_schedule(3e-4, total_steps=MC_STEPS, warmup_steps=2),
        optimizer="adam", weight_decay=0.1, clip_norm=1.0, fused=True,
        compute_dtype=jnp.bfloat16,
    )
    return model, tx


def _mc_tokens():
    import numpy as np

    rng = np.random.Generator(np.random.PCG64(SEED))
    return rng.integers(
        0, GPT2_124M["vocab_size"], 4 * MC_PER_CHIP * MC_STEPS * 1024
    ).astype(np.int32)


def _fit_gpt2(mesh, out: str, job: str, *, model_mesh, grad_accum: int = 1,
              **fit_kw):
    """``fit`` over MC_STEPS global batches of 4 x MC_PER_CHIP sequences."""
    from tpudist import mesh as mesh_lib
    from tpudist.data.lm import TokenWindowLoader
    from tpudist.train import fit, lm_loss

    model, tx = _gpt2_124m(model_mesh)
    batch = 4 * MC_PER_CHIP
    loader = TokenWindowLoader(
        _mc_tokens(), batch, 1024, vocab_size=GPT2_124M["vocab_size"]
    )
    dp = mesh_lib.data_parallel_size(mesh)
    return fit(
        model, tx, loader, epochs=1, mesh=mesh, job_id=job,
        batch_size=batch // dp // grad_accum, world_size=dp,
        loss_fn=lm_loss, input_key="tokens", label_key="tokens",
        grad_accum=grad_accum, fused="all", profile=False, log_dir=out,
        seed=SEED, **fit_kw,
    )


def phase_four_chips(out: str) -> dict:
    """One process, all four chips: the data-parallel GPT-2 step against
    the same seed and global batch on one device, then the explicit
    gradient reducer across real chips."""
    import jax

    from tpudist import create_mesh
    from tpudist.train import lm_loss, make_train_step, state_shardings_of

    mesh4 = create_mesh()
    if dict(mesh4.shape)["data"] != 4:
        _fail(f"create_mesh() gave {dict(mesh4.shape)}, want data=4")
    state4, losses4 = _fit_gpt2(mesh4, out, "SmokeMC4", model_mesh=mesh4)

    # placement — code that has only met one chip may put everything on
    # device 0 — and the program fit ran, asked of the compiler itself
    leaf = jax.tree_util.tree_leaves(state4.params)[0]
    param_devices = {s.device for s in leaf.addressable_shards}
    if len(param_devices) != 4 or not leaf.sharding.is_fully_replicated:
        _fail(f"params are not replicated on four chips: {leaf.sharding}")
    model, tx = _gpt2_124m(mesh4)
    step = make_train_step(
        model, tx, mesh4, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", fused="all",
        state_sharding=state_shardings_of(state4),
    )
    staged = step.stage(
        {"tokens": _mc_tokens()[:4 * MC_PER_CHIP * 1024].reshape(-1, 1024)}
    )
    shards = staged["tokens"].addressable_shards
    if (len(shards) != 4 or len({s.device for s in shards}) != 4
            or any(s.data.shape != (MC_PER_CHIP, 1024) for s in shards)):
        _fail("the staged batch is not four shards on four chips: "
              f"{[(s.device, s.data.shape) for s in shards]}")
    hlo = step.jitted.lower(state4, staged).compile().as_text()
    if "tpu_custom_call" not in hlo:
        _fail("the four-chip step holds no tpu_custom_call")
    all_reduces = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    if not all_reduces:
        _fail("the four-chip step holds no all-reduce")

    mesh1 = create_mesh(devices=jax.devices()[:1])
    _, losses1 = _fit_gpt2(mesh1, out, "SmokeMC1", model_mesh=mesh1,
                           grad_accum=4)
    if len(losses4) != MC_STEPS or len(losses1) != MC_STEPS:
        _fail(f"steps: {len(losses4)} on four chips, {len(losses1)} on one")
    if not all(math.isfinite(x) for x in (*losses4, *losses1)):
        _fail(f"non-finite loss: {losses4} / {losses1}")
    rel = [abs(a - b) / abs(b) for a, b in zip(losses4, losses1)]
    if max(rel) > DP_REL_TOL:
        _fail(f"four chips vs one device disagree: rel {rel} > {DP_REL_TOL} "
              f"({losses4} vs {losses1})")

    # the explicit reducer (fit(reduce="quantized"), README "Parallelism"):
    # its shard_map body is per-replica code, so the model takes no mesh
    _, losses_q = _fit_gpt2(mesh4, out, "SmokeMCQ", model_mesh=None,
                            reduce="quantized")
    if len(losses_q) != MC_STEPS or not all(
        math.isfinite(x) for x in losses_q
    ):
        _fail(f"explicit reducer losses: {losses_q}")
    return {"losses_4chip": [float(x) for x in losses4],
            "losses_1chip": [float(x) for x in losses1],
            "max_rel_delta": max(rel), "rel_tol": DP_REL_TOL,
            "batch_shards": len(shards), "param_devices": len(param_devices),
            "all_reduces": all_reduces,
            "losses_quantized_reducer": [float(x) for x in losses_q]}


# -- driver ------------------------------------------------------------------


def run_phase(name: str, fn, out: str, meter: CompileMeter, device) -> bool:
    """Run one phase with its own stdout in ``<out>/<name>.log``; print its
    JSON line. A failure is reported (traceback on stderr) and returned —
    the caller turns it into the exit code."""
    h0, m0 = meter.hits, meter.misses
    t0 = time.perf_counter()
    row: dict = {"phase": name, "ok": True}
    with open(os.path.join(out, f"{name}.log"), "w") as log:
        try:
            with contextlib.redirect_stdout(log):
                row.update(fn(out))
        except Exception as exc:
            traceback.print_exc()
            row.update(ok=False, error=f"{type(exc).__name__}: {exc}")
    wall = time.perf_counter() - t0
    compile_s = meter.compile_seconds(t0)
    row.update(
        seconds=round(wall, 3), compile_s=round(compile_s, 3),
        steady_s=round(wall - compile_s, 3),
        # the allocator's high-water mark since the process started
        peak_bytes_in_use=(device.memory_stats() or {}).get(
            "peak_bytes_in_use"
        ),
        cache_hits=meter.hits - h0, cache_misses=meter.misses - m0,
    )
    print(json.dumps(row), flush=True)
    return row["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                    help="directory for logs, telemetry and traces")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs ONLY the four-chip phase and its one-device "
                    "comparison")
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    report = {"platform": device.platform, "kind": device.device_kind,
              "count": jax.device_count()}
    if device.platform != "tpu" or report["count"] != args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX reports "
              f"{report}", file=sys.stderr)
        print(json.dumps({"ok": False, "device": report}))
        return 1

    sys.path.insert(0, os.path.join(REPO, "examples"))
    from tpudist.utils.cache import place_compile_cache

    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    cache_dir = place_compile_cache()
    meter = CompileMeter()
    print(json.dumps({"phase": "start", "out": out,
                      "compile_cache": cache_dir}), flush=True)
    phases = (
        [("four_chips", phase_four_chips)] if args.chips == 4 else
        [("vision", phase_vision), ("lm", phase_lm), ("serve", phase_serve)]
    )
    ok = True
    for name, fn in phases:
        ok = run_phase(name, fn, out, meter, device) and ok
    print(json.dumps({"ok": ok, "device": report}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
