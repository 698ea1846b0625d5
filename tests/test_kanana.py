"""Kanana-2 (tpudist.models.kanana) against its plain reference
(benchmarks/reference/kanana.py), and the properties its layers rest on:
sigmoid scoring with a scale and a bias that moves the choice only, the
shared expert counted once over the shares of an expert-parallel layer,
rotary embedding on adjacent pairs, recomputation, the trace contract.

CPU, tiny sizes, weights drawn as the harness draws them (N(0, 0.02);
``*scale`` leaves around one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import kanana as reference
from tpudist.models.kanana import Kanana, kanana2_30b_a3b
from tpudist.models.llama import apply_rope
from tpudist.models.lm_utils import chunked_lm_forward
from tpudist.parallel.ep import Routing, select_experts

CONFIG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 32,
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "kv_lora_rank": 16, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_shared_experts": 2,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "num_experts_held": 4,
    "deployment": {"experts_held_first": 0}, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "norm_topk_prob": True, "n_group": 1,
    "q_lora_rank": None, "rope_scaling": None, "vocab_size": 96,
}


def tiny(dtype=jnp.float32, held=(0, 4), selection_bias=None, **kw):
    return Kanana(
        vocab_size=CONFIG["vocab_size"], max_seq_len=64, hidden_dim=32,
        depth=3, dense_layers=1, num_heads=4, nope_dim=8, rope_dim=4,
        v_dim=8, kv_rank=16, dense_ffn_dim=48, ffn_dim=16, shared_dim=32,
        routing=Routing(8, top_k=2, held=held, scoring="sigmoid",
                        routed_scale=2.448, selection_bias=selection_bias),
        dtype=dtype, **kw)


@pytest.fixture(scope="module")
def setup():
    tokens = jax.random.randint(jax.random.key(5), (4, 32), 0,
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


def reference_value_and_grad(flat, tokens, config=CONFIG):
    loss_sum = reference.make_loss_sum(config)

    def mean(p):
        total, count = loss_sum(p, {"tokens": tokens})
        return total / count

    return jax.value_and_grad(mean)(flat)


def quantile_bias(scores):
    return reference.sequence_quantile_bias(scores, 2)


@pytest.mark.parametrize("bias", [None, "sequence_quantile"])
def test_loss_and_every_leafs_gradient_match_the_reference(
        setup, bias, monkeypatch):
    """Float32 against float32: every leaf's gradient, element by element,
    under the plain top-k and under the benchmark's bias on the selection
    (the reference's own copy of the rule). 2e-4 of the leaf's largest
    element: the two sides sum in different orders (grouped product
    against masked dense experts, chunked head against stretches of whole
    logits, one contraction of 12 against 8 + 4) and nothing else
    differs."""
    tokens, params, flat = setup
    monkeypatch.setattr(reference, "HEAD_STRETCH", 12)  # three stretches
    model = tiny(selection_bias=quantile_bias if bias else None)
    loss, grads = jax.value_and_grad(program_loss(model))(params, tokens)
    config = dict(CONFIG, recipe={"selection_bias": bias})
    want_loss, want = reference_value_and_grad(flat, tokens, config)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = dict(zip(weights.leaf_paths(grads),
                   jax.tree_util.tree_leaves(grads)))
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        np.testing.assert_allclose(
            got[name] / scale, want[name] / scale, atol=2e-4, err_msg=name)


def test_sigmoid_gates_are_the_scaled_normalised_scores():
    """``w_i = scale * s_i / sum over the chosen of s``: the k gates of a
    token sum to the scale, each is its sigmoid's share, and the chosen
    are the k largest scores."""
    logits = jax.random.normal(jax.random.key(1), (2, 16, 8))
    routing = Routing(8, top_k=3, scoring="sigmoid", routed_scale=2.448)
    idx, gates = select_experts(logits, routing)
    scores = jax.nn.sigmoid(logits)
    np.testing.assert_array_equal(
        np.sort(idx, axis=-1), np.sort(np.argsort(-scores, axis=-1)[..., :3]))
    np.testing.assert_allclose(gates.sum(-1), 2.448, rtol=1e-6)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        gates, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # softmax scoring is untouched by the new field's default
    plain = Routing(8, top_k=3)
    _, soft = select_experts(logits, plain)
    np.testing.assert_allclose(soft.sum(-1), 1.0, rtol=1e-6)


def test_selection_bias_moves_the_choice_only():
    """A bias on the selection changes WHICH experts are chosen and
    nothing else: for the experts it leaves chosen, gates and their
    gradient are those of the same choice without it — the bias is added
    to the scores (not the logits), nowhere but in the top-k, and no
    gradient passes through it."""
    logits = jax.random.normal(jax.random.key(2), (1, 64, 8))
    bias = jnp.zeros((8,)).at[3].set(10.0)  # expert 3 into every top-2
    plain = Routing(8, top_k=2, scoring="sigmoid", routed_scale=2.448)
    biased = Routing(8, top_k=2, scoring="sigmoid", routed_scale=2.448,
                     selection_bias=lambda s: jnp.broadcast_to(bias, s.shape))
    idx, gates = select_experts(logits, biased)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    assert not bool(jnp.all(jnp.any(select_experts(logits, plain)[0] == 3,
                                    axis=-1)))
    # the gates are the UNBIASED scores of the chosen, normalised
    scores = jax.nn.sigmoid(logits)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        gates, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)

    # and their gradient is that of the same fixed choice, bias or none
    def fixed(z):
        s = jnp.take_along_axis(jax.nn.sigmoid(z), idx, axis=-1)
        return jnp.sum(jnp.sin(2.448 * s / s.sum(-1, keepdims=True)))

    got = jax.grad(lambda z: jnp.sum(jnp.sin(
        select_experts(z, biased)[1])))(logits)
    np.testing.assert_allclose(got, jax.grad(fixed)(logits), atol=1e-6)
    # under softmax the bias stays on the logits (ZAYA1's path)
    soft = Routing(8, top_k=1, selection_bias=lambda z: jnp.broadcast_to(
        bias, z.shape))
    assert bool(jnp.all(select_experts(logits, soft)[0] == 3))


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The model-configs guide's test of the cut: the 4 shares of one
    expert layer (``held=(2i, 2)`` of 8 experts) — each the program's
    ``dropless_moe`` with its shared expert — add up, the shared expert
    counted once, to what the UNCUT reference gives for the whole layer
    (all 8 experts held, its own top-k)."""
    from flax import linen as nn

    from tpudist.parallel.ep import dropless_moe

    u = jax.random.normal(jax.random.key(3), (2, 32, 32))
    keys = jax.random.split(jax.random.key(4), 7)
    whole = {
        "moe_router/kernel": jax.random.normal(keys[0], (32, 8)),
        "moe_experts/w_gate": 0.3 * jax.random.normal(keys[1], (8, 32, 16)),
        "moe_experts/w_up": 0.3 * jax.random.normal(keys[2], (8, 32, 16)),
        "moe_experts/w_down": 0.3 * jax.random.normal(keys[3], (8, 16, 32)),
        "moe_shared/w_gate/kernel": 0.3 * jax.random.normal(keys[4], (32, 32)),
        "moe_shared/w_up/kernel": 0.3 * jax.random.normal(keys[5], (32, 32)),
        "moe_shared/w_down/kernel": 0.3 * jax.random.normal(keys[6], (32, 32)),
    }
    want = reference.expert_layer(
        u, whole, num_experts=8, top_k=2, first=0, count=8,
        routed_scale=2.448)
    shared = want - reference.expert_layer(
        u, whole, num_experts=8, top_k=2, first=0, count=8,
        routed_scale=2.448, shared=False)

    class Layer(nn.Module):
        held: tuple

        @nn.compact
        def __call__(self, u):
            return dropless_moe(
                self, u, ffn_dim=16, shared_dim=32,
                routing=Routing(8, top_k=2, held=self.held,
                                scoring="sigmoid", routed_scale=2.448))[0]

    total = 0.0
    for first in range(0, 8, 2):
        share = {
            "moe_router": {"kernel": whole["moe_router/kernel"]},
            "moe_experts": {k: whole[f"moe_experts/{k}"][first:first + 2]
                            for k in ("w_gate", "w_up", "w_down")},
            "moe_shared": {k: {"kernel": whole[f"moe_shared/{k}/kernel"]}
                           for k in ("w_gate", "w_up", "w_down")},
        }
        total = total + Layer((first, 2)).apply({"params": share}, u)
    # four shares hold the shared expert four times: count it once
    np.testing.assert_allclose(total - 3 * shared, want, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 0.1  # it is in the sum


def test_adjacent_pair_rotary_is_a_plane_rotation_of_each_pair():
    """``interleaved``: channels ``(2i, 2i+1)`` of every head turn by
    ``pos * theta^(-2i/D)`` in place — against the rotation written out
    by hand — and the rotate-half default is as it was."""
    x = np.asarray(jax.random.normal(jax.random.key(0), (2, 6, 3, 8)))
    theta = 1e6
    want = np.empty_like(x)
    for pos in range(6):
        for i in range(4):
            a = pos * theta ** (-2 * i / 8)
            want[:, pos, :, 2 * i] = (x[:, pos, :, 2 * i] * np.cos(a)
                                      - x[:, pos, :, 2 * i + 1] * np.sin(a))
            want[:, pos, :, 2 * i + 1] = (x[:, pos, :, 2 * i] * np.sin(a)
                                          + x[:, pos, :, 2 * i + 1] * np.cos(a))
    got = apply_rope(jnp.asarray(x), theta=theta, interleaved=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        reference.rope_pairs(jnp.asarray(x), theta), want, atol=1e-5)
    # the same rotation as rotate-half on the de-interleaved channels
    halves = apply_rope(jnp.asarray(
        np.concatenate([x[..., 0::2], x[..., 1::2]], -1)), theta=theta)
    np.testing.assert_allclose(got[..., 0::2], halves[..., :4], atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], halves[..., 4:], atol=1e-5)


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


def test_auto_attention_at_short_sequences_takes_a_path_with_two_widths():
    """The VMEM kernel has one head size for q, k and v: it says so, and
    ``auto`` goes on to the dense path (the flash kernel from 2048 on)."""
    from tpudist.ops.attention import (
        dot_product_attention, multi_head_attention,
    )
    from tpudist.ops.vmem_attention import vmem_attention

    q = jax.random.normal(jax.random.key(0), (1, 128, 2, 12))
    v = jax.random.normal(jax.random.key(1), (1, 128, 2, 8))
    with pytest.raises(NotImplementedError, match="one head size"):
        vmem_attention(q, q, v, causal=True)
    np.testing.assert_allclose(
        multi_head_attention(q, q, v, causal=True, impl="auto"),
        dot_product_attention(q, q, v, causal=True), atol=1e-6)


def test_preset_has_the_published_sizes():
    model = kanana2_30b_a3b()
    assert (model.hidden_dim, model.depth, model.vocab_size) == (2048, 48, 128256)
    assert (model.num_heads, model.nope_dim, model.rope_dim, model.v_dim,
            model.kv_rank) == (32, 128, 64, 128, 512)
    assert (model.dense_layers, model.dense_ffn_dim, model.ffn_dim,
            model.shared_dim) == (1, 6144, 768, 1536)
    routing = model.routing
    assert (routing.num_experts, routing.top_k, routing.scoring,
            routing.routed_scale) == (128, 6, "sigmoid", 2.448)
    assert routing.held is None and model.sows_moe_stats
    assert model.max_seq_len == 32768 and model.rope_theta == 1e6


def test_head_is_untied_and_the_chunked_forward_finds_it(setup):
    tokens, params, _ = setup
    assert params["lm_head"].shape == params["embed"].shape
    grads = jax.grad(program_loss(tiny()))(params, tokens)
    # both tables get a gradient of their own: the head from every logit,
    # the embedding only from the rows looked up
    assert float(jnp.abs(grads["lm_head"]).min()) > 0
    unused = np.setdiff1d(np.arange(CONFIG["vocab_size"]), np.asarray(tokens))
    assert unused.size and not np.asarray(grads["embed"])[unused].any()


def test_block_scopes_keep_the_trace_contract(setup):
    """Every stage of a block is a direct child of ``h_<n>`` under the
    name ``tpudist/telemetry/trace.py`` promises the trace reader: the
    lowered step's op locations hold ``h_<n>/<scope>/`` for each of them
    (the dense block has MLA's, the expert blocks the expert layer's
    too)."""
    from tpudist.telemetry.trace import BLOCK_SCOPES, MOE_COUNTERS

    tokens, params, _ = setup
    model = tiny()
    text = jax.jit(jax.grad(program_loss(model))).lower(
        params, tokens).as_text(debug_info=True)
    # (``cca_*`` are the ZAYA1 block's, ``attn_*`` / ``bd_attn`` the SDAR
    # block's, ``lg_*`` / ``swa_attn`` / ``full_attn`` the Laguna block's,
    # ``mamba_*`` / ``ssd_scan`` / ``gqa_*`` the Nemotron-H block's:
    # test_zaya.py, test_sdar.py, test_laguna.py, test_nemotron_h.py)
    mine = [s for s in BLOCK_SCOPES
            if not s.startswith(("cca_", "attn_", "bd_", "lg_", "swa_",
                                 "full_", "mamba_", "ssd_", "gqa_"))]
    assert len(mine) == 11
    for scope in mine:
        assert f"h_1/{scope}/" in text, scope
        assert (f"h_0/{scope}/" in text) == scope.startswith("mla_"), scope
    _, sown = model.apply({"params": params}, tokens, mutable=["moe_stats"])
    assert set(sown["moe_stats"]) == {"h_1", "h_2"}
    assert set(sown["moe_stats"]["h_1"]) == set(MOE_COUNTERS)
