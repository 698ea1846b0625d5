"""Laguna (tpudist.models.laguna) against its plain reference
(benchmarks/reference/laguna.py), and the properties its layers rest on:
two kinds of attention layer in one stack, each with its own head count,
rotary embedding and mask (a window shorter than the sequence, YaRN at
positions past its original length), the gated output, the shared expert
counted once over the shares of an expert-parallel layer, recomputation,
the FLOP counter, the trace contract.

CPU, tiny sizes, weights drawn as the harness draws them (N(0, 0.02);
``*scale`` leaves around one)."""

import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.families import laguna as family
from benchmarks.reference import laguna as reference
from benchmarks.reference import kanana as kanana_reference
from tpudist.models.laguna import FULL, PERIOD, SLIDING, Laguna, laguna_xs_2
from tpudist.models.llama import Yarn, apply_rope
from tpudist.models.lm_utils import chunked_lm_forward

REPO = pathlib.Path(__file__).resolve().parent.parent
# the harness's tiny preset: full (dense), three sliding, full; window 8 of
# 32 positions; YaRN over 16 original positions; 4 of 8 experts held
CONFIG = json.loads(
    (REPO / "benchmarks/tests/configs/laguna-tiny.json").read_text())
TOKENS = (4, 32)


def tiny(**kw):
    """The preset through the family, as the harness builds it (float32,
    dense attention on the CPU), with the family's bias on the selection
    unless ``selection_bias`` says otherwise."""
    from tpudist import mesh as mesh_lib

    bias = kw.pop("selection_bias", "sequence_quantile")
    config = dict(CONFIG, recipe=dict(CONFIG["recipe"], remat_policy=None,
                                      selection_bias=bias))
    traffic = {"seq_len": TOKENS[1], "per_chip_batch": TOKENS[0]}
    model = family.build(config, traffic, mesh_lib.create_mesh(
        devices=jax.devices()[:1]))["model"]
    return model.clone(**kw) if kw else model


@pytest.fixture(scope="module")
def setup():
    tokens = jax.random.randint(jax.random.key(5), TOKENS, 0,
                                CONFIG["vocab_size"])
    shapes = jax.eval_shape(
        lambda: tiny().init(jax.random.key(0), tokens))["params"]
    params = weights.generate(shapes, 2**31 + 11)
    flat = dict(zip(weights.leaf_paths(params),
                    jax.tree_util.tree_leaves(params)))
    return tokens, params, flat


def program_loss(model):
    forward = chunked_lm_forward(model, chunk=8)
    return lambda params, tokens: forward(params, {}, {"tokens": tokens})[0]


def reference_value_and_grad(flat, tokens, config, precision="float32"):
    loss_sum = reference.make_loss_sum(config, precision)

    def mean(p):
        total, count = loss_sum(p, {"tokens": tokens})
        return total / count

    return jax.value_and_grad(mean)(flat)


def _gaps(got: dict, want: dict) -> dict:
    """Each leaf's largest gap over its largest element."""
    assert set(got) == set(want)
    return {name: float(jnp.max(jnp.abs(got[name] - want[name])))
            / (float(jnp.max(jnp.abs(want[name]))) + 1e-12) for name in want}


# float32 against float32: the two sides sum in different orders (the
# flash-free dense attention of the program against the reference's head at
# a time and blocks of rows, grouped products against masked dense experts,
# the chunked head against stretches of whole logits) and nothing else
# differs. Logits: 1e-5 of the largest; loss: 1e-6 relative; every leaf's
# gradient: 2e-4 of its largest element (the kanana family's test reads
# 1e-6 here: the margin is for other BLAS builds, not for a difference)
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 1e-5, 1e-6, 2e-4


@pytest.mark.parametrize("bias", [None, "sequence_quantile"])
def test_logits_loss_and_every_leafs_gradient_match_the_reference(
        setup, bias, monkeypatch):
    """Under the plain top-k and under the benchmark's bias on the
    selection; the reference's sliding layers in two blocks of rows and its
    head in three stretches, so that both seams are crossed."""
    tokens, params, flat = setup
    monkeypatch.setattr(reference, "HEAD_STRETCH", 12)
    monkeypatch.setattr(reference, "ROW_BLOCK", 16)
    model = tiny(selection_bias=bias)
    config = dict(CONFIG, recipe={"selection_bias": bias})
    logits = model.apply({"params": params}, tokens)
    with jax.default_matmul_precision("highest"):
        want_logits = reference.make_stack(config)(flat, tokens) \
            @ flat["lm_head"].T
    scale = float(jnp.max(jnp.abs(want_logits)))
    np.testing.assert_allclose(logits / scale, want_logits / scale,
                               atol=LOGITS_TOL)
    loss, grads = jax.value_and_grad(program_loss(model))(params, tokens)
    want_loss, want = reference_value_and_grad(flat, tokens, config)
    assert float(loss) == pytest.approx(float(want_loss), rel=LOSS_TOL)
    gaps = _gaps(dict(zip(weights.leaf_paths(grads),
                          jax.tree_util.tree_leaves(grads))), want)
    assert max(gaps.values()) < GRAD_TOL, max(gaps, key=gaps.get)


def test_one_precision_lower_fails_the_tolerances(setup):
    """The comparison can tell: the reference with every matmul operand
    rounded to bfloat16 — one precision below the preset's float32 —
    against the float32 reference breaks the gradient tolerance (and the
    program stays inside it, test above)."""
    tokens, _, flat = setup
    config = dict(CONFIG, recipe={"selection_bias": "sequence_quantile"})
    loss, want = reference_value_and_grad(flat, tokens, config)
    low_loss, low = reference_value_and_grad(flat, tokens, config, "bfloat16")
    gaps = _gaps(low, want)
    loss_gap = abs(float(low_loss) - float(loss)) / abs(float(loss))
    assert loss_gap > LOSS_TOL or max(gaps.values()) > GRAD_TOL
    assert max(gaps.values()) > 10 * GRAD_TOL


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The model-configs guide's test of the cut, at the cell's ratio: the
    16 shares of one expert layer (``held=(i, 1)`` of 16 experts, top-2,
    sigmoid scores scaled 2.5) — each the program's ``dropless_moe`` with
    its shared expert — add up, the shared expert counted once, to what the
    UNCUT reference gives for the whole layer (all 16 held)."""
    from flax import linen as nn

    from tpudist.parallel.ep import Routing, dropless_moe

    u = jax.random.normal(jax.random.key(3), (2, 32, 32))
    keys = jax.random.split(jax.random.key(4), 7)
    whole = {
        "moe_router/kernel": jax.random.normal(keys[0], (32, 16)),
        "moe_experts/w_gate": 0.3 * jax.random.normal(keys[1], (16, 32, 8)),
        "moe_experts/w_up": 0.3 * jax.random.normal(keys[2], (16, 32, 8)),
        "moe_experts/w_down": 0.3 * jax.random.normal(keys[3], (16, 8, 32)),
        "moe_shared/w_gate/kernel": 0.3 * jax.random.normal(keys[4], (32, 8)),
        "moe_shared/w_up/kernel": 0.3 * jax.random.normal(keys[5], (32, 8)),
        "moe_shared/w_down/kernel": 0.3 * jax.random.normal(keys[6], (8, 32)),
    }
    layer = lambda **kw: kanana_reference.expert_layer(
        u, whole, num_experts=16, top_k=2, first=0, count=16,
        routed_scale=2.5, **kw)
    want = layer()
    shared = want - layer(shared=False)

    class Layer(nn.Module):
        held: tuple

        @nn.compact
        def __call__(self, u):
            return dropless_moe(
                self, u, ffn_dim=8, shared_dim=8,
                routing=Routing(16, top_k=2, held=self.held,
                                scoring="sigmoid", routed_scale=2.5))[0]

    total = 0.0
    for first in range(16):
        share = {
            "moe_router": {"kernel": whole["moe_router/kernel"]},
            "moe_experts": {k: whole[f"moe_experts/{k}"][first:first + 1]
                            for k in ("w_gate", "w_up", "w_down")},
            "moe_shared": {k: {"kernel": whole[f"moe_shared/{k}/kernel"]}
                           for k in ("w_gate", "w_up", "w_down")},
        }
        total = total + Layer((first, 1)).apply({"params": share}, u)
    # sixteen shares hold the shared expert sixteen times: count it once
    np.testing.assert_allclose(total - 15 * shared, want, atol=5e-5)
    assert float(jnp.abs(shared).max()) > 0.1  # it is in the sum


def test_yarn_frequencies_are_the_formula():
    """Laguna-XS.2's full layers at the rotary width 64: ``low`` 5 and
    ``high`` 16, and ``inv_freq_i = f_i (1 - r_i) + (f_i / 64) r_i`` with
    ``f_i = 5e5^(-2i/64)`` and ``r_i = clip((i - 5) / 11, 0, 1)`` —
    written out here, in the program's ``Yarn`` and in the reference's own
    copy alike."""
    yarn = laguna_xs_2().yarn
    assert yarn.correction_range(64, 5e5) == (5, 16)
    i = np.arange(32)
    f = 5e5 ** (-2.0 * i / 64)
    r = np.clip((i - 5) / 11, 0, 1)
    want = f * (1 - r) + f / 64 * r
    np.testing.assert_allclose(yarn.inv_freq(64, 5e5), want, rtol=1e-6)
    np.testing.assert_array_equal(
        yarn.inv_freq(64, 5e5), reference.yarn_inv_freq(
            64, 5e5, factor=64, original=4096, beta_fast=64, beta_slow=1))
    # the pairs below low keep their frequency, those past high are 1/64
    got = yarn.inv_freq(64, 5e5)
    assert (got[:6] == f[:6].astype(np.float32)).all()
    np.testing.assert_allclose(got[16:], f[16:] / 64, rtol=1e-6)
    # with the ramp from beta_fast 64 / beta_slow 1 at 4,096 positions
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (5, 16)


def test_yarn_rotary_turns_half_a_head_and_scales_it():
    """``apply_rope(rotary_dim=, yarn=)`` past the original positions:
    channels beyond the rotary width pass untouched; each turned pair keeps
    its angle's rotation and grows by the attention factor — against the
    rotation written out by hand."""
    yarn = Yarn(4.0, 16, beta_fast=2.0, beta_slow=1.0, attention_factor=1.5)
    x = np.asarray(jax.random.normal(jax.random.key(0), (1, 40, 2, 32)))
    got = np.asarray(apply_rope(jnp.asarray(x), theta=100.0, rotary_dim=16,
                                yarn=yarn))
    np.testing.assert_array_equal(got[..., 16:], x[..., 16:])
    freq = yarn.inv_freq(16, 100.0)
    pos = np.arange(40)[:, None] * freq  # [S, 8]
    cos, sin = np.cos(pos)[None, :, None], np.sin(pos)[None, :, None]
    a, b = x[..., :8], x[..., 8:16]
    want = 1.5 * np.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    np.testing.assert_allclose(got[..., :16], want, atol=2e-5)
    # the reference's own rotation is the same function
    np.testing.assert_allclose(
        reference.rope_halves(jnp.asarray(x[..., :16]), freq, 1.5), want,
        atol=2e-5)


def test_flash_path_matches_the_dense_path(setup):
    """Both kinds through the flash kernels (interpret mode, padded to 128
    rows: the full layers causal, the sliding ones on the band's grid)
    against the dense path: loss and every gradient."""
    tokens, params, _ = setup
    dense = program_loss(tiny())
    flash = program_loss(tiny(attn_impl="flash"))
    want, want_grads = jax.value_and_grad(dense)(params, tokens)
    got, grads = jax.value_and_grad(flash)(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        np.testing.assert_allclose(a / scale, b / scale, atol=1e-4)


@pytest.mark.parametrize("how", ["full", "dots_saveable", "fused_ln"])
def test_recomputation_and_fused_norms_keep_loss_and_gradient(setup, how):
    tokens, params, _ = setup
    want, want_grads = jax.value_and_grad(program_loss(tiny()))(params, tokens)
    model = tiny(fused_ln=True) if how == "fused_ln" \
        else tiny(remat_policy=how)
    got, grads = jax.value_and_grad(program_loss(model))(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-7)


def test_preset_has_the_published_sizes():
    model = laguna_xs_2()
    assert (model.hidden_dim, model.depth, model.vocab_size) == (2048, 40, 100352)
    assert model.layer_types == PERIOD * 10 and PERIOD == (
        FULL, SLIDING, SLIDING, SLIDING)
    assert model.heads_per_layer == (48, 64, 64, 64) * 10
    assert (model.num_kv_heads, model.head_dim, model.window) == (8, 128, 512)
    assert (model.dense_layers, model.dense_ffn_dim, model.ffn_dim,
            model.shared_dim) == (1, 8192, 512, 512)
    routing = model.routing
    assert (routing.num_experts, routing.top_k, routing.scoring,
            routing.routed_scale) == (256, 8, "sigmoid", 2.5)
    assert (model.full_rope_theta, model.full_rotary_dim,
            model.sliding_rope_theta) == (5e5, 64, 1e4)
    assert model.yarn == Yarn(64.0, 4096, 64.0, 1.0, 1.4158883083359672)
    assert model.max_seq_len == 262144 and model.sows_moe_stats


def test_flops_counter_counts_the_pairs_each_layer_needs():
    """``laguna_train_flops``' attention term is the pairs counted one by
    one — the causal triangle in a full layer, the band in a sliding one —
    at 12 x head size a pair a head; the family's copy equals it."""
    from tpudist.telemetry import flops

    for seq, window in ((32, 8), (100, 7), (16, 64)):
        i, j = np.meshgrid(np.arange(seq), np.arange(seq), indexing="ij")
        band = int(((i - j >= 0) & (i - j < window)).sum())
        assert flops.window_pairs(seq, window) == band
        assert flops.window_pairs(seq, None) == int((i >= j).sum())
        assert family.window_pairs(seq, window) == band
    model = tiny()
    batch = {"tokens": jax.ShapeDtypeStruct(TOKENS, jnp.int32)}
    total = flops.train_step_flops(model, batch)
    d, dh, kv, heads = 32, 32, 2, (4, 6, 6, 6, 4)
    weights = 256 * d + sum(d * (h + 2 * kv) * dh + 2 * h * dh * d
                            for h in heads) + 3 * d * 48 \
        + 4 * (d * 8 + 3 * d * 16 + 2 * (4 / 8) * 3 * d * 16)
    # heads x pairs: the two full layers' triangle, the three sliding
    # layers' band
    pairs = 2 * 4 * (32 * 33 // 2) + 3 * 6 * flops.window_pairs(32, 8)
    rows, seq = TOKENS
    assert total == pytest.approx(
        6.0 * rows * seq * weights + 12.0 * dh * pairs * rows, rel=1e-12)
    traffic = {"seq_len": TOKENS[1], "per_chip_batch": TOKENS[0]}
    assert family.train_flops_per_token(CONFIG, traffic) == pytest.approx(
        total / (TOKENS[0] * TOKENS[1]), rel=1e-12)


def test_block_scopes_keep_the_trace_contract(setup):
    """Every stage of a block is a direct child of ``h_<n>`` under the
    name ``tpudist/telemetry/trace.py`` promises the trace reader: the
    lowered step's op locations hold ``h_<n>/<scope>/`` for each of them —
    ``full_attn`` in the full layers, ``swa_attn`` in the sliding ones, the
    expert layer's stages past the dense first layer."""
    from tpudist.telemetry.trace import BLOCK_SCOPES, MOE_COUNTERS

    tokens, params, _ = setup
    model = tiny()
    text = jax.jit(jax.grad(program_loss(model))).lower(
        params, tokens).as_text(debug_info=True)
    mine = [s for s, m in BLOCK_SCOPES.items()
            if s.startswith("lg_") or m in ("swa_attn_roofline",
                                            "full_attn_roofline")]
    assert sorted(mine) == ["full_attn", "lg_gate", "lg_out", "lg_qkv",
                            "lg_rope", "swa_attn"]
    for layer, kind in enumerate(CONFIG["layer_types"]):
        for scope in mine:
            own = {"full_attn": kind == FULL, "swa_attn": kind == SLIDING}
            assert (f"h_{layer}/{scope}/" in text) == own.get(scope, True), \
                (layer, scope)
        for scope in ("moe_router", "moe_dispatch", "moe_experts",
                      "moe_combine", "moe_shared"):
            assert (f"h_{layer}/{scope}/" in text) == (layer > 0), scope
    _, sown = model.apply({"params": params}, tokens, mutable=["moe_stats"])
    assert set(sown["moe_stats"]) == {"h_1", "h_2", "h_3", "h_4"}
    assert set(sown["moe_stats"]["h_1"]) == set(MOE_COUNTERS)


def test_head_counts_and_kinds_follow_the_layer_index(setup):
    """One block class: layer ``l``'s projections are ``H_l`` heads wide
    and its rotary and mask those of its kind; the head is untied."""
    _, params, _ = setup
    for layer, heads in enumerate(CONFIG["num_attention_heads_per_layer"]):
        block = params[f"h_{layer}"]
        assert block["lg_qkv"]["kernel"].shape == (32, (heads + 4) * 32)
        assert block["lg_gate"]["kernel"].shape == (32, heads * 32)
        assert block["lg_out"]["kernel"].shape == (heads * 32, 32)
        assert ("mlp_gate" in block) == (layer == 0)
    assert params["lm_head"].shape == params["embed"].shape
    with pytest.raises(ValueError, match="layer types"):
        # the published stack has 40 (nothing is built before the check)
        jax.eval_shape(lambda: Laguna(depth=41).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
