"""Nemotron-H (tpudist.models.nemotron_h) and its chunked state-space scan
(tpudist.ops.ssd) against the plain reference (benchmarks/reference/
nemotron_h.py, whose Mamba-2 layer is the sequential recurrence a position
at a time), and the properties the cell rests on: the state carried across
chunks, the squared-ReLU shared expert counted once over the shares of an
expert-parallel layer, recomputation that keeps the scan kernel's forward
once a layer, the counter of what a state keeps, the trace contract.

CPU, tiny sizes, Pallas in interpret mode; weights drawn as the harness
draws them (N(0, 0.02); ``*scale`` leaves around one) and under Mamba-2's
published initialisation (``A ~ -U[1, 16]``, ``dt`` log-uniform on
``[1e-3, 1e-1]``), where a state crosses many chunks."""

import functools
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.families import nemotron_h as family
from benchmarks.reference import nemotron_h as reference
from tpudist import remat
from tpudist.models.lm_utils import chunked_lm_forward
from tpudist.ops import ssd

REPO = pathlib.Path(__file__).resolve().parent.parent
# the harness's tiny preset: the published pattern's first 7 layers
# (MEMEM*E), 4 Mamba heads of 8 in 2 groups of state 16, chunks of 8 over
# 32 positions; 4 of 8 experts held, top-2
CONFIG = json.loads(
    (REPO / "benchmarks/tests/configs/nemotron_h-tiny.json").read_text())
TOKENS = (2, 32)


# -- the scan against the recurrence ----------------------------------------


def scan_inputs(key, chunks: int, published: bool, *, b=2, chunk=8, h=4,
                p=8, g=2, n=16):
    """Inputs of one scan call over ``chunks`` chunks: ``A`` and ``dt`` as
    Mamba-2 initialises them (``published``) or as the harness's N(0, 0.02)
    leaves make them (``A = -exp(~0)``, ``dt = softplus(~0)``)."""
    k = jax.random.split(key, 6)
    s = chunks * chunk
    x = jax.random.normal(k[0], (b, s, h, p))
    B = 0.5 * jax.random.normal(k[1], (b, s, g, n))
    C = 0.5 * jax.random.normal(k[2], (b, s, g, n))
    if published:
        A = -jax.random.uniform(k[3], (h,), minval=1.0, maxval=16.0)
        dt = jnp.exp(jax.random.uniform(k[4], (b, s, h), minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
    else:
        A = -jnp.exp(0.02 * jax.random.normal(k[3], (h,)))
        dt = jax.nn.softplus(0.02 * jax.random.normal(k[4], (b, s, h)))
    D = jax.random.normal(k[5], (h,))
    return x, dt, A, B, C, D


def _probe(fn):
    """``sum(fn(...) * a fixed probe)``: a scalar whose gradient reaches
    every output element with its own weight."""
    def loss(*args):
        y = fn(*args)
        probe = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(
            y.shape)
        return jnp.sum(y * probe)
    return jax.jit(jax.value_and_grad(loss, argnums=range(6)))


@functools.cache
def _kernel_and_reference(chunk: int):
    scan = functools.partial(ssd.ssd_scan, chunk=chunk)
    return jax.jit(scan), _probe(scan), jax.jit(reference.recurrence), \
        _probe(reference.recurrence)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


# float32 against float32, the two sides summing in different orders (the
# chunk's products against a position at a time): 2e-5 of the largest
# element, output and every input's gradient; measured <= 1e-6
SCAN_TOL = 2e-5


@pytest.mark.parametrize("published", [True, False],
                         ids=["published_init", "harness_init"])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_ssd_scan_matches_the_sequential_recurrence(chunks, published):
    """The forward and backward kernels (interpret mode) against the
    recurrence a position at a time: ``y`` and the gradients of ``x``,
    ``dt``, ``A``, ``B``, ``C`` and ``D``. Under the published
    initialisation the state a chunk hands on is a large part of the
    state-space output of the next (checked against the same chunks each
    started from nought), so the state passed between chunks is what is
    compared."""
    args = scan_inputs(jax.random.key(chunks), chunks, published)
    fwd, grad, ref_fwd, ref_grad = _kernel_and_reference(8)
    with jax.default_matmul_precision("highest"):
        want = ref_fwd(*args)
        _, want_grads = ref_grad(*args)
        y = fwd(*args)
        _, grads = grad(*args)
        assert _rel(y, want) < SCAN_TOL
        for name, g, w in zip("x dt A B C D".split(), grads, want_grads):
            assert _rel(g, w) < SCAN_TOL, name
        if published and chunks > 1:
            # the chunks' outputs once more, each chunk started from nought
            x, dt, A, B, C, D = args
            cut = lambda v: v.reshape((-1, 8) + v.shape[2:])
            alone = ref_fwd(cut(x), cut(dt), A, cut(B), cut(C), D)
            carried = want - alone.reshape(want.shape)
            ssm = want - D[:, None] * x
            assert float(jnp.max(jnp.abs(carried[:, 8:]))) \
                > 0.2 * float(jnp.max(jnp.abs(ssm[:, 8:])))


def _vjp(fn, args, cot):
    y, pullback = jax.vjp(fn, *args)
    return pullback(cot.astype(y.dtype))


@functools.cache
def _backward_pair(chunk: int):
    """The kernels' gradients and JAX's gradient of :func:`ssd.ssd_chunked`
    (the XLA form of the same mathematics), and of the chunked form run on
    each chunk alone (no state handed between chunks: the control)."""
    def alone(x, dt, A, B, C, D):
        cut = lambda v: v.reshape((-1, chunk) + v.shape[2:])
        return ssd.ssd_chunked(cut(x), cut(dt), A, cut(B), cut(C), D,
                               chunk=chunk).reshape(x.shape)

    pair = (functools.partial(ssd.ssd_scan, chunk=chunk),
            functools.partial(ssd.ssd_chunked, chunk=chunk), alone)
    return [jax.jit(lambda cot, *a, fn=fn: _vjp(fn, a, cot)) for fn in pair]


def _rel_rms(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


# bfloat16 operands, float32 accumulation, on both sides: the two round
# different partial sums (the kernel's dx once, the chunked form's two
# terms apiece), so they agree to about one bfloat16 step (2^-8 = 3.9e-3)
# of RMS: measured 1.2e-3 - 4.0e-3 on six seeds. 1e-2; the state dropped
# between chunks misses it by 5x or more (measured >= 5.2e-2)
BF16_TOL = 1e-2


@pytest.mark.parametrize("published", [True, False],
                         ids=["published_init", "harness_init"])
def test_ssd_backward_kernel_matches_the_chunked_mathematics(published):
    """The backward kernel against JAX's gradient of the chunk-parallel
    XLA form, in the cell's layout: bfloat16 ``x``, ``B``, ``C``; 2 groups
    of 8 heads of 64 (4 tiles of two heads a group), 3 chunks; all six
    gradients, as the relative RMS of the gap. The same chunks computed
    each from nought are outside the tolerance, so the state's cotangent
    carried back across chunks is what is compared."""
    chunk = 16
    x, dt, A, B, C, D = scan_inputs(jax.random.key(7), 3, published, b=1,
                                    chunk=chunk, h=16, p=64, g=2, n=32)
    bf = jnp.bfloat16
    args = (x.astype(bf), dt, A, B.astype(bf), C.astype(bf), D)
    cot = jax.random.normal(jax.random.key(8), x.shape)
    kernel, chunked, alone = _backward_pair(chunk)
    got, want, control = (f(cot, *args) for f in (kernel, chunked, alone))
    for name, g, w, c in zip("x dt A B C D".split(), got, want, control):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rel_rms(g, w) < BF16_TOL, name
        if name != "D":  # the skip's gradient holds no state
            assert _rel_rms(c, w) > 5 * BF16_TOL, name


def test_chunk_count_is_the_kernel_grid():
    """``ssd.chunk_count`` is the last axis of the traced kernel's grid
    (batch rows, groups, chunks), and one ``pallas_call`` holds it."""
    args = scan_inputs(jax.random.key(0), 5, True)
    jaxpr = jax.make_jaxpr(functools.partial(ssd.ssd_scan, chunk=8))(*args)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    if not calls:  # the custom rule holds the call
        calls = [e for eqn in jaxpr.jaxpr.eqns
                 for sub in jax.core.jaxprs_in_params(eqn.params)
                 for e in sub.eqns if e.primitive.name == "pallas_call"]
    (call,) = calls
    assert tuple(call.params["grid_mapping"].grid) == (
        2, 2, ssd.chunk_count(40, 8))


def test_ssd_cost_is_the_familys_copy():
    """The kernel's cost at the cell's shape, the program's function and the
    benchmark's copy; forward 2 L² N a group and 2 L² P + 4 L N P a head,
    a chunk."""
    config = json.loads(
        (REPO / "benchmarks/configs/nemotron-3-nano-30b-a3b.json").read_text())
    traffic = {"per_chip_batch": 1, "seq_len": 8192}
    copy = family.ssd_cost(config, traffic)
    program = ssd.ssd_cost(batch=1, seq=8192, heads=64, head_dim=64,
                           groups=8, state=128, chunk=128, itemsize=2)
    assert {k: copy[k] for k in ("fwd", "bwd")} == program
    assert copy["chunks"] == 64 and copy["calls_per_step"] == 3
    L, n, p = 128, 128, 64
    assert program["fwd"]["flops"] == 64 * (8 * 2 * L * L * n
                                            + 64 * (2 * L * L * p
                                                    + 4 * L * n * p))


# -- the model against the reference ----------------------------------------


def tiny(**kw):
    """The preset through the family, as the harness builds it (float32,
    dense attention on the CPU), no recomputation unless asked."""
    from tpudist import mesh as mesh_lib

    recipe = dict(CONFIG["recipe"], remat_policy=kw.pop("remat_policy", None))
    model = family.build(
        dict(CONFIG, recipe=recipe),
        {"seq_len": TOKENS[1], "per_chip_batch": TOKENS[0]},
        mesh_lib.create_mesh(devices=jax.devices()[:1]))["model"]
    return model.clone(**kw) if kw else model


@pytest.fixture(scope="module")
def setup():
    """Tokens, and the tiny model's parameters two ways: the harness's
    draw and the model's own (published) initialisation."""
    tokens = jax.random.randint(jax.random.key(5), TOKENS, 0,
                                CONFIG["vocab_size"])
    init = jax.jit(tiny().init)
    harness = weights.generate(jax.eval_shape(
        init, jax.random.key(0), tokens)["params"], 2**31 + 11)
    published = init(jax.random.key(3), tokens)["params"]
    return tokens, {"harness_init": harness, "published_init": published}


@functools.cache
def _sown():
    """The tiny model's forward that returns what its blocks sow."""
    model = tiny()
    return jax.jit(lambda params, tokens: model.apply(
        {"params": params}, tokens, mutable=["moe_stats"])[1]["moe_stats"])


def flat(params):
    return dict(zip(weights.leaf_paths(params),
                    jax.tree_util.tree_leaves(params)))


def program_loss(model):
    forward = chunked_lm_forward(model, chunk=8)
    return lambda params, tokens: forward(params, {}, {"tokens": tokens})[0]


@functools.cache
def _program_grad():
    return jax.jit(jax.value_and_grad(program_loss(tiny())))


@functools.cache
def _reference(precision: str):
    loss_sum = reference.make_loss_sum(CONFIG, precision)

    def mean(p, tokens):
        total, count = loss_sum(p, {"tokens": tokens})
        return total / count

    return jax.jit(jax.value_and_grad(mean))


def reference_value_and_grad(params, tokens, precision="float32"):
    """The reference's mean loss and gradient, its recurrence kept every 8
    positions and its head in three stretches (both seams crossed)."""
    kept = reference.CARRY_BLOCK, reference.HEAD_STRETCH
    reference.CARRY_BLOCK, reference.HEAD_STRETCH = 8, 12
    try:
        return _reference(precision)(params, tokens)
    finally:
        reference.CARRY_BLOCK, reference.HEAD_STRETCH = kept


def _gaps(got: dict, want: dict) -> dict:
    """Each leaf's largest gap over its largest element."""
    assert set(got) == set(want)
    return {name: _rel(got[name], want[name]) for name in want}


# float32 against float32: the chunked scan against the recurrence, grouped
# products against masked dense experts, the chunked head against stretches
# of whole logits. Loss: 1e-6 relative; every leaf's gradient: 2e-4 of its
# largest element (measured <= 1e-5)
LOSS_TOL, GRAD_TOL = 1e-6, 2e-4


@pytest.mark.parametrize("init", ["harness_init", "published_init"])
def test_loss_and_every_leafs_gradient_match_the_reference(setup, init):
    """The whole stack — Mamba-2, attention and expert layers in the
    published order — under the benchmark's bias on the selection."""
    tokens, params = setup
    loss, grads = _program_grad()(params[init], tokens)
    want_loss, want = reference_value_and_grad(flat(params[init]), tokens)
    assert float(loss) == pytest.approx(float(want_loss), rel=LOSS_TOL)
    gaps = _gaps(flat(grads), want)
    assert max(gaps.values()) < GRAD_TOL, max(gaps, key=gaps.get)


def test_one_precision_lower_fails_the_tolerances(setup):
    """The comparison can tell: the reference with every matmul operand
    rounded to bfloat16 — one precision below the preset's float32 —
    against the float32 reference breaks the gradient tolerance."""
    tokens, params = setup
    f = flat(params["harness_init"])
    loss, want = reference_value_and_grad(f, tokens)
    low_loss, low = reference_value_and_grad(f, tokens, "bfloat16")
    assert max(_gaps(low, want).values()) > 10 * GRAD_TOL


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The model-configs guide's test of the cut, at the cell's ratio: the
    16 shares of one expert layer (``held=(i, 1)`` of 16 experts, top-2,
    sigmoid scores scaled 2.5) — each the program's ``dropless_moe`` with
    its squared-ReLU shared expert — add up, the shared expert counted
    once, to what the UNCUT reference gives for the whole layer."""
    from flax import linen as nn

    from tpudist.parallel.ep import Routing, dropless_moe

    u = jax.random.normal(jax.random.key(3), (2, 32, 32))
    keys = jax.random.split(jax.random.key(4), 5)
    whole = {
        "moe_router/kernel": jax.random.normal(keys[0], (32, 16)),
        "moe_experts/w_up": 0.3 * jax.random.normal(keys[1], (16, 32, 8)),
        "moe_experts/w_down": 0.3 * jax.random.normal(keys[2], (16, 8, 32)),
        "moe_shared/w_up/kernel": 0.3 * jax.random.normal(keys[3], (32, 12)),
        "moe_shared/w_down/kernel": 0.3 * jax.random.normal(keys[4], (12, 32)),
    }
    layer = lambda **kw: reference.expert_layer(
        u, whole, num_experts=16, top_k=2, first=0, count=16,
        routed_scale=2.5, **kw)
    with jax.default_matmul_precision("highest"):
        want = layer()
        shared = want - layer(shared=False)

    class Layer(nn.Module):
        held: tuple

        @nn.compact
        def __call__(self, u):
            return dropless_moe(
                self, u, ffn_dim=8, shared_dim=12, expert_act="relu2",
                routing=Routing(16, top_k=2, held=self.held,
                                scoring="sigmoid", routed_scale=2.5))[0]

    total = 0.0
    for first in range(16):
        share = {
            "moe_router": {"kernel": whole["moe_router/kernel"]},
            "moe_experts": {k: whole[f"moe_experts/{k}"][first:first + 1]
                            for k in ("w_up", "w_down")},
            "moe_shared": {k: {"kernel": whole[f"moe_shared/{k}/kernel"]}
                           for k in ("w_up", "w_down")},
        }
        with jax.default_matmul_precision("highest"):
            total = total + Layer((first, 1)).apply({"params": share}, u)
    # sixteen shares hold the shared expert sixteen times: count it once
    np.testing.assert_allclose(total - 15 * shared, want, atol=1e-4)
    assert float(jnp.abs(shared).max()) > 0.1  # it is in the sum


# -- recomputation, counters, the trace contract ----------------------------


@pytest.mark.parametrize("policy,per_layer", [("dots_saveable", 1),
                                              ("full", 2)])
def test_recomputation_keeps_the_scan_kernel_once_a_layer(setup, policy,
                                                          per_layer):
    """Under ``dots_saveable`` a Mamba-2 block's backward finds the
    kernel's output and states kept (``remat.KERNEL_RESIDUALS``) and the
    step holds one forward kernel a Mamba-2 layer; under ``full`` each
    block's backward launches it again. The backward kernel runs once a
    layer under both."""
    tokens, params = setup
    model = tiny(remat_policy=policy)
    traced = jax.jit(jax.grad(program_loss(model))).trace(
        params["harness_init"], tokens)
    mamba = CONFIG["hybrid_override_pattern"][:CONFIG["num_hidden_layers"]]
    assert remat.forward_kernels(traced.jaxpr, ("ssd.py",)) \
        == per_layer * mamba.count("M")
    assert remat.pallas_kernels(traced.jaxpr, ("ssd.py",), "_bwd_kernel") \
        == mamba.count("M")


def _scans_from(jaxpr, file: str) -> int:
    """``scan`` equations of a traced program (inside custom rules and
    loops too) that a line of ``file`` made."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and any(
                f.file_name.endswith(file)
                for f in eqn.source_info.traceback.frames):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans_from(getattr(sub, "jaxpr", sub), file)
    return n


def test_a_steps_backward_runs_one_scan_kernel_a_layer(setup):
    """Static counter of the backward: a step's gradient holds exactly one
    backward kernel of ``ops/ssd.py`` (``_bwd_kernel``) a Mamba-2 layer,
    beside one forward kernel, and no ``lax.scan`` of ``ops/ssd.py``: the
    state's cotangent is carried across the chunks inside the kernel."""
    tokens, params = setup
    model = tiny(remat_policy="dots_saveable")
    traced = jax.jit(jax.grad(program_loss(model))).trace(
        params["harness_init"], tokens)
    mamba = CONFIG["hybrid_override_pattern"][
        :CONFIG["num_hidden_layers"]].count("M")
    assert remat.pallas_kernels(traced.jaxpr, ("ssd.py",), "_bwd_kernel") \
        == mamba
    assert remat.forward_kernels(traced.jaxpr, ("ssd.py",)) == mamba
    assert _scans_from(traced.jaxpr.jaxpr, "ops/ssd.py") == 0


def test_block_scopes_keep_the_trace_contract(setup):
    """Every stage of a block is a direct child of ``h_<n>`` under the name
    ``tpudist/telemetry/trace.py`` promises the trace reader, in the layers
    of its kind only: the lowered step's op locations hold
    ``h_<n>/<scope>/``. The Mamba-2 layers sow ``ssd_log_carry``, the
    expert layers the dropless layer's counters."""
    from tpudist.telemetry.trace import (
        BLOCK_SCOPES, MOE_COUNTERS, SSD_COUNTERS,
    )

    tokens, params = setup
    model = tiny()
    text = jax.jit(jax.grad(program_loss(model))).lower(
        params["harness_init"], tokens).as_text(debug_info=True)
    owner = {"mamba_": "M", "ssd_": "M", "gqa_": "*", "moe_": "E"}
    kinds = CONFIG["hybrid_override_pattern"][:CONFIG["num_hidden_layers"]]
    mine = [s for s in BLOCK_SCOPES if s.startswith(tuple(owner))]
    assert len(mine) == 13
    for layer, kind in enumerate(kinds):
        for scope in mine:
            own = next(k for p, k in owner.items() if scope.startswith(p))
            assert (f"h_{layer}/{scope}/" in text) == (own == kind), \
                (layer, scope)
    sown = _sown()(params["harness_init"], tokens)
    for layer, kind in enumerate(kinds):
        got = set(sown.get(f"h_{layer}", {}))
        assert got == {"M": set(SSD_COUNTERS), "E": set(MOE_COUNTERS),
                       "*": set()}[kind], layer


@pytest.mark.parametrize("init", ["harness_init", "published_init"])
def test_ssd_log_carry_reads_what_a_state_keeps(setup, init):
    """``ssd_log_carry`` is ``chunk x mean(dt A)`` over heads and positions:
    under the harness's N(0, 0.02) leaves ``dt A ~ -softplus(0)``, so
    about ``-0.69 chunk`` (-88.7 at the cell's 128: nothing survives a
    chunk); under the published initialisation far less negative."""
    tokens, params = setup
    carry = [float(v["ssd_log_carry"][0])
             for v in _sown()(params[init], tokens).values()
             if "ssd_log_carry" in v]
    chunk = CONFIG["chunk_size"]
    assert len(carry) == 3
    if init == "harness_init":
        assert all(abs(c / (-math.log(2) * chunk) - 1) < 0.1 for c in carry)
    else:
        assert all(-0.5 * math.log(2) * chunk < c < 0 for c in carry)
