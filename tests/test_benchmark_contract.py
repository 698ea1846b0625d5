"""The program and the benchmark that judges it agree.

``benchmarks/`` copies facts from the program — operation counts, the
chip's peaks, ``fit``'s parameters, span, phase and scope names — and tier-1 runs
none of ``benchmarks/tests``. Each case here holds one copy to its source,
and names what broke: a scope renamed in the program would otherwise show
as a ``null`` per-layer metric on the driver's machine, and a ``fit``
parameter renamed as a cell that cannot run. Reads ``benchmarks/``, edits
nothing there; CPU only.

The names themselves are declared in ``tpudist/telemetry/trace.py``
(``FIT_SPANS``, ``BRINGUP_SPANS``, ``STEP_SCOPES``); the modules that emit them spell them as
literals (most sit below ``telemetry``), so (d) and (f) look for the
literal where it is emitted.
"""

import ast
import functools
import importlib
import inspect
import json
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import spans  # noqa: E402
from tpudist.telemetry import flops  # noqa: E402
from tpudist.telemetry.trace import (  # noqa: E402
    BLOCK_SCOPES, BRINGUP_SPANS, FIT_SPANS, STEP_SCOPES, TRAIN_STEP,
)

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
TINY = REPO / "benchmarks" / "tests"


def _family(name):
    return importlib.import_module(f"benchmarks.families.{name}")


def _tiny(family):
    config = json.loads((TINY / "configs" / f"{family}-tiny.json").read_text())
    traffic = {"bert": "tiny_mlm", "sdar": "tiny_bd"}.get(family, "tiny_train")
    return config, json.loads(
        (TINY / "traffic" / f"{traffic}.json").read_text())


def _one_chip_mesh():
    from tpudist import mesh as mesh_lib

    return mesh_lib.create_mesh(devices=jax.devices()[:1])


# -- (a) one FLOP accounting: the family's copy against the program's --------


@pytest.mark.parametrize("name", ["gpt2-medium", "bert-large",
                                  "kanana-2-30b-a3b", "sdar-30b-a3b",
                                  "laguna-xs-2", "nemotron-3-nano-30b-a3b"])
def test_family_flops_per_token_is_the_programs_counter(name, monkeypatch):
    """``step_mfu_pct``'s numerator (``benchmarks/families/*.py``
    ``train_flops_per_token``, "copied from telemetry/flops.py") equals
    the program's own counter on the model the family builds, at the
    published size, for the traffic of the configuration's first cell."""
    from benchmarks.families import common

    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    cell = next(w for w in BENCH["workloads"] if w["config"] == name)
    config = json.loads((REPO / entry["file"]).read_text())
    traffic = json.loads(
        (REPO / "benchmarks" / "traffic" / f"{cell['traffic']}.json")
        .read_text())
    # shapes only: the model object is wanted, not a trace of 24 layers
    monkeypatch.setattr(common, "param_shapes", lambda model, sample: None)
    family = _family(config["family"])
    built = family.build(config, traffic, _one_chip_mesh())
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"], traffic["seq_len"]), jnp.int32)}
    program = flops.train_step_flops(built["model"], batch)
    tokens = family.tokens_per_step(traffic, 1)
    assert tokens == flops.tokens_per_step(built["model"], batch)
    assert family.train_flops_per_token(config, traffic) == pytest.approx(
        program / tokens, rel=1e-12)


# -- (b) one table of peaks ---------------------------------------------------


@pytest.mark.parametrize("column, key", [(0, "bf16_flops_per_s"),
                                         (1, "hbm_bytes_per_s")])
def test_benchmark_peaks_are_the_programs(column, key):
    table = json.loads((REPO / "benchmarks" / "peaks.json").read_text())
    assert set(table) == set(flops.DEVICE_PEAKS)
    for kind, row in flops.DEVICE_PEAKS.items():
        assert table[kind][key] == row[column], kind


# -- (c) what the harness passes, fit takes -----------------------------------


def _calls(path, func):
    """The calls of ``func`` in a file: ``(positional count, keywords)``,
    a ``**mapping`` left out."""
    tree = ast.parse((REPO / path).read_text())
    return [
        (len(node.args), [k.arg for k in node.keywords if k.arg])
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and getattr(node.func, "id", getattr(node.func, "attr", None)) == func
    ]


@pytest.mark.parametrize("family", ["gpt2", "bert", "zaya", "kanana", "sdar",
                                    "laguna", "nemotron_h"])
def test_every_argument_the_cell_passes_is_a_parameter_of_fit(family):
    """``benchmarks/cell.py`` calls ``fit(model, tx, loader, <its own
    keywords>, **built["fit"])``: every one of them is a parameter."""
    from tpudist.train import fit

    config, traffic = _tiny(family)
    built = _family(family).build(config, traffic, _one_chip_mesh())
    ((positional, keywords),) = _calls("benchmarks/cell.py", "fit")
    assert positional == 3 and "init_params" in keywords
    passed = set(keywords) | set(built["fit"])
    parameters = inspect.signature(fit).parameters
    assert not passed - set(parameters), sorted(passed - set(parameters))
    # the three positional ones are taken as such and not named again
    first = list(parameters.values())[:positional]
    assert all(p.kind is p.POSITIONAL_OR_KEYWORD for p in first)
    assert not passed & {p.name for p in first}


def test_the_traced_runs_telemetry_fields_exist():
    from tpudist.telemetry import TelemetryConfig

    ((_, keywords),) = _calls("benchmarks/cell.py", "TelemetryConfig")
    fields = set(inspect.signature(TelemetryConfig).parameters)
    assert keywords and not set(keywords) - fields


# -- (d) the names the benchmark quotes are the names the program declares ----


def _reader_spans(metric, monkeypatch):
    """The span names a ``layer_metrics`` reader sums."""
    seen = []
    monkeypatch.setattr(
        spans, "span_ms", lambda ctx, names, *a, **k: seen.extend(names))
    importlib.import_module(f"benchmarks.layer_metrics.{metric}").read({})
    return seen


@pytest.mark.parametrize("prefix", spans.PROGRAM_SPANS)
def test_program_span_prefixes_cover_the_declared_spans(prefix):
    """``spans.load`` keeps a host event by these prefixes: each finds a
    declared span, and no declared span is outside all of them."""
    assert any(name.startswith(prefix) for name in FIT_SPANS)
    assert all(name.startswith(spans.PROGRAM_SPANS) for name in FIT_SPANS)


@pytest.mark.parametrize(
    "metric", ["loop_host_ms", "input_stage_ms", "input_produce_ms"])
def test_host_span_readers_sum_declared_spans(metric, monkeypatch):
    """Each reader sums the spans the program declares for its metric:
    none that is not declared, and none of them left out."""
    names = _reader_spans(metric, monkeypatch)
    assert names and sorted(names) == sorted(
        n for n, m in FIT_SPANS.items() if m == metric)


def test_step_marker_is_the_declared_one():
    assert spans.thread_names(
        {("/host:CPU", 0, "python"): [(TRAIN_STEP, 0, 1, 1)]}
    ) == {("/host:CPU", 0, "python"): "main"}


@pytest.mark.parametrize("scope", sorted(spans.OPT_SCOPES))
def test_optimizer_scopes_are_declared(scope):
    assert STEP_SCOPES.get(scope) == "opt_ms"


def test_exchange_and_loss_head_scopes_are_declared():
    assert STEP_SCOPES.get(spans.EXCHANGE_SCOPE) == "bwd_ms"
    assert STEP_SCOPES.get("loss_head") == "fwd_ms"
    # the reader treats loss_head as one of the program's own scopes
    assert spans.scope_of(
        "jit(step_fn)/transpose(jvp(loss_head))/while/body/dot_general:"
    ) == "loss_head/while"
    # nothing is declared that the reader would fold into the model
    assert set(STEP_SCOPES) == spans.OPT_SCOPES | {
        spans.EXCHANGE_SCOPE, "loss_head"}


# the Kanana-2, SDAR, Laguna and Nemotron-H cells' readers quote block
# scopes: each is declared for that metric, and none declared for it is
# left out
@pytest.mark.parametrize("metric, attribute", [
    ("mla_proj_ms", "STAGES"), ("moe_shared_ms", "STAGE"),
    ("moe_topk_ms", "STAGES"), ("bd_proj_ms", "STAGES"),
    ("bd_moe_ms", "STAGES"), ("lg_proj_ms", "STAGES"),
    ("mamba_mix_ms", "STAGES"), ("ssd_roofline", "STAGE"),
])
def test_block_stage_readers_sum_declared_scopes(metric, attribute):
    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    quoted = getattr(reader, attribute)
    quoted = [quoted] if isinstance(quoted, str) else list(quoted)
    # ``moe_topk_ms`` and ``bd_moe_ms`` read the stages declared for
    # ``moe_ms``, each in its cell
    declared = "moe_ms" if metric in ("moe_topk_ms", "bd_moe_ms") else metric
    assert sorted(quoted) == sorted(
        s for s, m in BLOCK_SCOPES.items() if m == declared)


@pytest.mark.parametrize("family, scope", [
    ("zaya", "cca_attn"), ("kanana", "mla_attn"), ("sdar", "bd_attn"),
    ("laguna:sliding_attention", "swa_attn"),
    ("laguna:full_attention", "full_attn"),
])
def test_attention_kernel_patterns_follow_the_declared_scope(family, scope):
    """XLA names a Pallas call after its innermost scope: the family's
    ``ATTENTION_OPS`` (a family of two kinds of layer: each kind's, under
    ``KINDS``) finds ``<scope>.<k>`` for the scope the program declares for
    that roofline metric, and no other block scope."""
    name, _, kind = family.partition(":")
    module = _family(name)
    pattern = module.KINDS[kind].ATTENTION_OPS if kind \
        else module.ATTENTION_OPS
    assert BLOCK_SCOPES[scope].endswith("_attn_roofline")
    assert re.match(pattern, scope) and re.match(pattern, f"{scope}.17")
    assert not [s for s in BLOCK_SCOPES if s != scope
                and re.match(pattern, s)]


@functools.cache
def _span_literals():
    """First arguments of the program's ``span(...)`` calls and of its
    bring-up's ``bringup.enter(...)`` calls, by file."""
    found = {}
    for path in sorted((REPO / "tpudist").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not (isinstance(node, ast.Call) and node.args and (
                    getattr(node.func, "id", None) == "span"
                    or (getattr(node.func, "attr", None) == "enter" and
                        getattr(node.func.value, "id", None) == "bringup"))):
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and isinstance(first.value, str):
                found.setdefault(first.value, path.name)
            elif isinstance(first, ast.Name) and first.id == "TRAIN_STEP":
                found.setdefault(TRAIN_STEP, path.name)
    return found


@pytest.mark.parametrize("name", sorted(FIT_SPANS) + sorted(BRINGUP_SPANS))
def test_every_declared_span_is_emitted_under_its_name(name):
    """The call sites keep their literals: each declared span is the first
    argument of a ``span(...)`` call (a bring-up phase: of a
    ``bringup.enter(...)`` call) somewhere under ``tpudist/`` (that it
    shows once a step on the profiler's timeline, or once a run in the
    ``bringup`` row, is tests/test_telemetry_fit.py's)."""
    assert name in _span_literals()


def test_no_span_is_emitted_undeclared():
    assert not set(_span_literals()) - set(FIT_SPANS) - set(BRINGUP_SPANS)


@pytest.mark.parametrize("metric", ["fit_bringup_s", "init_state_s",
                                    "first_step_s"])
def test_bringup_readers_sum_declared_phases(metric):
    """Each reader of the ``bringup`` row's phases quotes the phases the
    program declares for its metric: none undeclared, none left out."""
    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    assert sorted(reader.SPANS) == sorted(
        n for n, metrics in BRINGUP_SPANS.items() if metric in metrics)


def test_the_two_sums_of_phases_cover_the_bringup_once():
    """``fit_bringup_s`` and ``first_step_s`` split the declared phases
    between them, so that with ``pre_fit_s`` and the printed remainder
    they add up to ``setup_s``."""
    for name, metrics in BRINGUP_SPANS.items():
        assert len({"fit_bringup_s", "first_step_s"} & set(metrics)) == 1, name
    declared = {m["name"] for m in BENCH["per_layer"]}
    assert {m for metrics in BRINGUP_SPANS.values() for m in metrics} \
        <= declared


@pytest.mark.parametrize("name", sorted(BRINGUP_SPANS))
def test_no_step_reader_picks_up_a_bringup_phase(name):
    """``spans.load`` keeps a host event by ``PROGRAM_SPANS``' prefixes
    and counts what it keeps once a step: a phase of the bring-up, should
    a profiler session ever cover it, is none of them."""
    assert name.startswith("bringup/")
    assert not name.startswith(spans.PROGRAM_SPANS)


# -- (f) the declared device scopes are in a lowered step's name stacks -------


def _paths(lowered):
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def scope_paths():
    """Name stacks by the recipe that emits each scope: the cells' recipe
    (the tiny GPT-2 configuration of ``benchmarks/tests`` through its
    family), the explicit reducer, the mixed-precision policy's cast."""
    from tpudist import amp, mesh as mesh_lib
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, lm_loss, make_train_step

    def lowered_step(model, tx, mesh, rows, seq, **kw):
        state = create_train_state(
            model, 0, jnp.zeros((1, seq), jnp.int32), tx, mesh=mesh)
        step = make_train_step(model, tx, mesh, **kw)
        if step.grad_reducer is not None:
            state = step.grad_reducer.attach_residual(state)
        return _paths(step.jitted.lower(
            state, step.stage({"tokens": np.zeros((rows, seq), np.int32)})))

    config, traffic = _tiny("gpt2")
    mesh = _one_chip_mesh()
    built = _family("gpt2").build(config, traffic, mesh)
    step_args = inspect.signature(make_train_step).parameters
    cell = lowered_step(
        built["model"], built["tx"], mesh, traffic["per_chip_batch"],
        traffic["seq_len"],
        **{k: v for k, v in built["fit"].items() if k in step_args})

    mesh = mesh_lib.create_mesh()
    model = GPT2(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=1,
                 num_heads=2, dropout=0.0)
    reducer = lowered_step(
        model, optax.adam(1e-3), mesh, 16, 16, loss_fn=lm_loss,
        input_key="tokens", label_key="tokens", reduce="bucketed")
    cast = _paths(jax.jit(amp.BF16_COMPUTE.cast_to_compute).lower(
        {"w": jnp.ones((2, 2))}))
    return {"optimizer": cell, "grad_clip": cell, "loss_head": cell,
            "grad_exchange": reducer, "cast": cast}


@pytest.mark.parametrize("scope", sorted(STEP_SCOPES))
def test_lowered_step_holds_the_declared_scope(scope, scope_paths):
    """...and the benchmark's reader puts its ops in the pass the
    declaration names (``loss_head``: forward and backward both)."""
    held = {p for p in scope_paths[scope]
            if scope in {spans._bare(c) for c in spans._components(p)}}
    assert held, f"no op of the lowered step is under {scope!r}"
    passes = {spans.pass_of(p) for p in held}
    want = STEP_SCOPES[scope].removesuffix("_ms")
    assert passes == ({"fwd", "bwd"} if scope == "loss_head" else {want})


@pytest.fixture(scope="module")
def kanana_block_paths():
    """Name stacks of the tiny Kanana-2 configuration's lowered step under
    its cell's recipe (per-block recomputation, fused norms, chunked CE)."""
    from tpudist.train import create_train_state, make_train_step

    config, traffic = _tiny("kanana")
    mesh = _one_chip_mesh()
    built = _family("kanana").build(config, traffic, mesh)
    seq, rows = traffic["seq_len"], traffic["per_chip_batch"]
    state = create_train_state(
        built["model"], 0, jnp.zeros((1, seq), jnp.int32), built["tx"],
        mesh=mesh)
    step_args = inspect.signature(make_train_step).parameters
    step = make_train_step(
        built["model"], built["tx"], mesh,
        **{k: v for k, v in built["fit"].items() if k in step_args})
    return _paths(step.jitted.lower(
        state, step.stage({"tokens": np.zeros((rows, seq), np.int32)})))


@pytest.mark.parametrize(
    "scope", sorted(s for s in BLOCK_SCOPES
                    if not s.startswith(("cca_", "attn_", "bd_", "lg_",
                                         "swa_", "full_", "mamba_", "ssd_",
                                         "gqa_"))))
def test_lowered_kanana_step_holds_the_block_scope_in_both_passes(
        scope, kanana_block_paths):
    """Each scope of the Kanana-2 block is in the lowered step's name
    stacks as a direct child of an expert block, in the forward and in the
    backward pass, where ``layer_metrics/moe_ms.py`` ``stage_of`` finds it
    whatever recomputation puts before the block."""
    from benchmarks.layer_metrics import moe_ms

    held = {p for p in kanana_block_paths
            if moe_ms.stage_of(p, "%fusion = f32[]") == scope
            and "/h_1/" in p}
    assert held, f"no op of the lowered step is under h_1/{scope}"
    assert {spans.pass_of(p) for p in held} >= {"fwd", "bwd"}


@pytest.fixture(scope="module")
def sdar_block_paths():
    """Name stacks of the tiny SDAR configuration's lowered step under its
    cell's recipe (per-block recomputation, fused norms, the block-diffusion
    forward over both copies)."""
    from tpudist.train import create_train_state, make_train_step

    config, traffic = _tiny("sdar")
    mesh = _one_chip_mesh()
    built = _family("sdar").build(config, traffic, mesh)
    seq, rows = traffic["seq_len"], traffic["per_chip_batch"]
    state = create_train_state(
        built["model"], 0, jnp.zeros((1, seq), jnp.int32), built["tx"],
        mesh=mesh)
    step_args = inspect.signature(make_train_step).parameters
    step = make_train_step(
        built["model"], built["tx"], mesh,
        **{k: v for k, v in built["fit"].items() if k in step_args})
    batch = {"tokens": np.zeros((rows, seq), np.int32),
             "clean": np.zeros((rows, seq), np.int32),
             "loss_weight": np.ones((rows, seq), np.float32)}
    return _paths(step.jitted.lower(state, step.stage(batch)))


@pytest.mark.parametrize(
    "scope", sorted(s for s, m in BLOCK_SCOPES.items()
                    if m.startswith("bd_") or m == "moe_ms"))
def test_lowered_sdar_step_holds_the_block_scope_in_both_passes(
        scope, sdar_block_paths):
    """Each scope of the SDAR block is in the lowered step's name stacks as
    a direct child of a block, in the forward and in the backward pass,
    where ``layer_metrics/moe_ms.py`` ``stage_of`` finds it whatever
    recomputation puts before the block; ``loss_head`` (``bd_head_ms``) is
    there beside them."""
    from benchmarks.layer_metrics import bd_head_ms, moe_ms

    held = {p for p in sdar_block_paths
            if moe_ms.stage_of(p, "%fusion = f32[]") == scope
            and "/h_1/" in p}
    assert held, f"no op of the lowered step is under h_1/{scope}"
    assert {spans.pass_of(p) for p in held} >= {"fwd", "bwd"}
    assert bd_head_ms.SCOPE in STEP_SCOPES
    assert any(spans.scope_of(p).startswith(bd_head_ms.SCOPE)
               for p in sdar_block_paths)


@pytest.fixture(scope="module")
def laguna_block_paths():
    """Name stacks of the tiny Laguna configuration's lowered step under
    its cell's recipe (per-block recomputation, fused norms, chunked CE):
    layer 0 full and dense, 1-3 sliding, 4 full."""
    from tpudist.train import create_train_state, make_train_step

    config, traffic = _tiny("laguna")
    mesh = _one_chip_mesh()
    built = _family("laguna").build(config, traffic, mesh)
    seq, rows = traffic["seq_len"], traffic["per_chip_batch"]
    state = create_train_state(
        built["model"], 0, jnp.zeros((1, seq), jnp.int32), built["tx"],
        mesh=mesh)
    step_args = inspect.signature(make_train_step).parameters
    step = make_train_step(
        built["model"], built["tx"], mesh,
        **{k: v for k, v in built["fit"].items() if k in step_args})
    return _paths(step.jitted.lower(
        state, step.stage({"tokens": np.zeros((rows, seq), np.int32)})))


@pytest.mark.parametrize(
    "scope", sorted(s for s, m in BLOCK_SCOPES.items()
                    if s.startswith("lg_") or m in (
                        "swa_attn_roofline", "full_attn_roofline", "moe_ms")))
def test_lowered_laguna_step_holds_the_block_scope_in_both_passes(
        scope, laguna_block_paths):
    """Each scope of the Laguna block is in the lowered step's name stacks
    as a direct child of a block, in the forward and in the backward pass,
    where ``layer_metrics/moe_ms.py`` ``stage_of`` finds it whatever
    recomputation puts before the block: in the full expert layer ``h_4``
    for ``full_attn``, in the sliding ``h_1`` for every other."""
    from benchmarks.layer_metrics import moe_ms

    block = "/h_4/" if scope == "full_attn" else "/h_1/"
    held = {p for p in laguna_block_paths
            if moe_ms.stage_of(p, "%fusion = f32[]") == scope and block in p}
    assert held, f"no op of the lowered step is under {block}{scope}"
    assert {spans.pass_of(p) for p in held} >= {"fwd", "bwd"}


@pytest.fixture(scope="module")
def nemotron_h_block_paths():
    """Name stacks of the tiny Nemotron-H configuration's lowered step under
    its cell's recipe (per-block recomputation that keeps the scan kernel's
    residuals, fused norms, chunked CE): layers ``MEMEM*E``."""
    from tpudist.train import create_train_state, make_train_step

    config, traffic = _tiny("nemotron_h")
    mesh = _one_chip_mesh()
    built = _family("nemotron_h").build(config, traffic, mesh)
    seq, rows = traffic["seq_len"], traffic["per_chip_batch"]
    state = create_train_state(
        built["model"], 0, jnp.zeros((1, seq), jnp.int32), built["tx"],
        mesh=mesh)
    step_args = inspect.signature(make_train_step).parameters
    step = make_train_step(
        built["model"], built["tx"], mesh,
        **{k: v for k, v in built["fit"].items() if k in step_args})
    return _paths(step.jitted.lower(
        state, step.stage({"tokens": np.zeros((rows, seq), np.int32)})))


@pytest.mark.parametrize(
    "scope", sorted(s for s, m in BLOCK_SCOPES.items()
                    if s.startswith(("mamba_", "ssd_", "gqa_"))
                    or m == "moe_ms"))
def test_lowered_nemotron_h_step_holds_the_block_scope_in_both_passes(
        scope, nemotron_h_block_paths):
    """Each scope of the Nemotron-H block is in the lowered step's name
    stacks as a direct child of a block of its kind, in the forward and in
    the backward pass, where ``layer_metrics/moe_ms.py`` ``stage_of`` finds
    it whatever recomputation puts before the block: the Mamba-2 mixer's
    (the scan's backward included) in ``h_2``, the attention's in ``h_5``,
    the expert layer's in ``h_6``."""
    from benchmarks.layer_metrics import moe_ms

    block = "/h_6/" if scope.startswith("moe_") \
        else "/h_5/" if scope.startswith("gqa_") else "/h_2/"
    held = {p for p in nemotron_h_block_paths
            if moe_ms.stage_of(p, "%fusion = f32[]") == scope and block in p}
    assert held, f"no op of the lowered step is under {block}{scope}"
    assert {spans.pass_of(p) for p in held} >= {"fwd", "bwd"}


def test_the_scan_counter_feeds_a_declared_metric():
    """``ssd_log_carry`` (``SSD_COUNTERS``) names the metric whose reader
    prints it, and the family reads it under that field name."""
    from benchmarks.families import nemotron_h
    from tpudist.telemetry.trace import SSD_COUNTERS

    declared = {m["name"] for m in BENCH["per_layer"]}
    assert set(SSD_COUNTERS.values()) <= declared
    row = {"kind": "moe", "step": 9, **{f"h_{i}/{name}": -88.0
                                        for name in SSD_COUNTERS
                                        for i in (0, 2)}}
    ctx = {"window": type("W", (), {"warmup_steps": 6}), "telemetry_rows": [row]}
    assert nemotron_h.ssd_counters(ctx) == {"ssd_log_carry": -88.0,
                                            "layers_steps": 2}


# -- (e) documents name files that exist --------------------------------------

# what a document may still name although it is gone: history lines of the
# records, spelled in pieces so that a grep for them does not find this file
_GONE = {"bench" + ".py", "BENCH" + "_SUMMARY.json", "VERDICT.md",
         "docs/" + "PERF.md", "tools/bench" + "_gate.py"}
_PATH = re.compile(r"^[\w.-]+(?:/[\w.-]+)*\.(?:py|md|cpp|toml|json|jsonl)$")


@functools.cache
def _tree():
    """The files of the checkout, what a run leaves behind set aside
    (hidden directories: caches, a builder's copy of the parent commit)."""
    files = []
    for folder, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        rel = pathlib.Path(folder).relative_to(REPO)
        files += [(rel / n).as_posix() for n in names]
    return files


def _in_tree(path):
    """``ops/attention.py`` stands for ``tpudist/ops/attention.py``: a
    path is there when a file of the tree ends with it."""
    return any(f == path or f.endswith("/" + path) for f in _tree())


def _quoted_paths(text):
    for quoted in re.findall(r"`([^`\n]+)`", text):
        # `train.py:843–2090`, `optim.py` `shard_state`, `fit(...)`
        token = re.split(r"[:\s]", quoted.strip(), maxsplit=1)[0]
        if not _PATH.match(token):
            continue
        stem = token.rsplit(".", 1)[0]
        if token.endswith((".json", ".jsonl")) and "/" not in token \
                and not (stem.isupper() and len(stem) > 3):
            continue  # `config.json`, `Smoke_report.json`, `X.jsonl`: outputs
        yield token


DOCUMENTS = ["README.md", "PERF.md"] + sorted(
    p.relative_to(REPO).as_posix() for p in (REPO / "docs").glob("*.md"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_in_the_tree(document):
    allowed = _GONE if document == "PERF.md" else set()  # a record keeps its past
    missing = sorted({p for p in _quoted_paths((REPO / document).read_text())
                      if not _in_tree(p) and p not in allowed})
    assert not missing, f"{document} names files that are not in the tree"


def test_no_source_names_a_document_or_tool_that_is_gone():
    """Docstrings and comments under the program, the examples and the
    tools cite documents (``docs/X.md``, ``X.md §n``) and tools
    (``tools/x.py``, ``examples/x.py``): each is in the tree."""
    cited = re.compile(
        r"(?<![\w/.-])((?:docs|tools|examples|benchmarks|tests)/[\w./-]+\.(?:py|md)"
        r"|[A-Z_]+\.md|[A-Z_]+\.json|bench\w*\.py)\b")
    sources = [REPO / "main.py", REPO / "chip_smoke.py"]
    for folder in ("tpudist", "examples", "tools"):
        sources += sorted((REPO / folder).rglob("*.py"))
    missing = sorted({
        f"{src.relative_to(REPO)}: {name}"
        for src in sources for name in cited.findall(src.read_text())
        if not _in_tree(name)})
    assert not missing
