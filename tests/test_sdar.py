"""SDAR (tpudist.models.sdar) against its plain reference
(benchmarks/reference/sdar.py), and the properties block-diffusion training
rests on: what the mask over the noised and the clean copy MEANS, the
shares of an expert-parallel layer adding up to the whole layer, the
host-side corruption with its 1/t weights, recomputation, the trace
contract.

CPU, tiny sizes, weights drawn as the harness draws them (N(0, 0.02);
``*scale`` leaves around one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import sdar as reference
from tpudist.models.sdar import (
    Sdar, block_diffusion_forward, block_diffusion_transform, sdar_30b_a3b,
)
from tpudist.ops.attention import BlockMask
from tpudist.parallel.ep import Routing

BLOCK, LENGTH, VOCAB, MASK_ID = 4, 32, 96, 95
CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "num_experts_held": 4,
    "deployment": {"experts_held_first": 0}, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "norm_topk_prob": True, "rope_scaling": None,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "attention_bias": False,
    "use_sliding_window": False, "vocab_size": VOCAB, "block_length": BLOCK,
}


def tiny(dtype=jnp.float32, held=(0, 4), selection_bias=None, **kw):
    return Sdar(
        vocab_size=VOCAB, max_seq_len=128, hidden_dim=32, depth=2,
        num_heads=4, num_kv_heads=2, head_dim=8, ffn_dim=16,
        routing=Routing(8, top_k=2, held=held, selection_bias=selection_bias),
        block_length=BLOCK, dtype=dtype, **kw)


def corrupted(seed=8, rows=4):
    """A corrupted batch. Seed 8 masks no row's FIRST block whole: a
    first noised block that is all mask tokens sees nothing but itself,
    so its rows are the same function of the same input — router logits
    tied exactly — and which of them the selection bias's quantile takes
    is rounding, which the program and the reference round apart."""
    clean = np.asarray(jax.random.randint(
        jax.random.key(seed), (rows, LENGTH), 0, MASK_ID))
    out = block_diffusion_transform(MASK_ID, BLOCK, seed=seed)(
        {"tokens": clean})
    return {k: jnp.asarray(v) for k, v in out.items()}


@pytest.fixture(scope="module")
def setup():
    batch = corrupted()
    shapes = jax.eval_shape(
        lambda: tiny().init(jax.random.key(0), batch["tokens"]))["params"]
    params = weights.generate(shapes, 2**31 + 13)
    flat = dict(zip(weights.leaf_paths(params),
                    jax.tree_util.tree_leaves(params)))
    return batch, params, flat


def program_loss(model):
    forward = block_diffusion_forward(model, chunk=8)
    return lambda params, batch: forward(params, {}, batch)[0]


def quantile_bias(logits):
    return reference.sequence_quantile_bias(logits, 2)


@pytest.mark.parametrize("bias", [None, "sequence_quantile"])
def test_loss_and_every_leafs_gradient_match_the_reference(
        setup, bias, monkeypatch):
    """Float32 against float32: every leaf's gradient, element by element,
    under the plain top-k and under the benchmark's bias on the selection
    (the reference's own copy of the rule, over the 2 L rows of a batch
    row). 2e-4 of the leaf's largest element: the two sides sum in
    different orders (grouped product against masked dense experts, chunked
    head against stretches of whole logits, grouped heads against repeated
    ones) and nothing else differs."""
    batch, params, flat = setup
    monkeypatch.setattr(reference, "HEAD_STRETCH", 12)  # three stretches
    model = tiny(selection_bias=quantile_bias if bias else None)
    loss, grads = jax.value_and_grad(program_loss(model))(params, batch)
    loss_sum = reference.make_loss_sum(
        dict(CONFIG, recipe={"selection_bias": bias}))

    def mean(p):
        total, count = loss_sum(p, batch)
        return total / count

    want_loss, want = jax.value_and_grad(mean)(flat)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = dict(zip(weights.leaf_paths(grads),
                   jax.tree_util.tree_leaves(grads)))
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        np.testing.assert_allclose(
            got[name] / scale, want[name] / scale, atol=2e-4, err_msg=name)


def test_the_mask_is_the_references_index_arithmetic():
    """The program's description and the reference's boolean array are the
    same function of (row, key), for the two-copy mask and for the
    block-causal one; the two-copy mask allows ``L² + L b`` pairs."""
    for block in (1, 4, 8, 32):
        np.testing.assert_array_equal(
            BlockMask(block, LENGTH).dense(2 * LENGTH),
            reference.block_diffusion_mask(LENGTH, block))
        np.testing.assert_array_equal(
            BlockMask(block).dense(LENGTH),
            reference.block_causal_mask(LENGTH, block))
    allowed = np.asarray(BlockMask(BLOCK, LENGTH).dense(2 * LENGTH))
    assert allowed.sum() == LENGTH * LENGTH + LENGTH * BLOCK
    np.testing.assert_array_equal(
        BlockMask().dense(LENGTH), np.tril(np.ones((LENGTH, LENGTH), bool)))


def pair_logits(model, params, noised, clean):
    """Logits of all ``2 L`` rows of the training pass."""
    position = jnp.arange(LENGTH, dtype=jnp.float32)
    return model.apply(
        {"params": params}, jnp.concatenate([noised, clean], axis=1),
        positions=jnp.concatenate([position, position]),
        mask=BlockMask(BLOCK, LENGTH))


def moved(a, b):
    """Per position: did any logit move?"""
    return np.asarray(jnp.abs(a - b).max(axis=(0, 2)) > 1e-6)


@pytest.mark.parametrize("k", [0, 3, 7])
def test_a_clean_token_moves_only_the_noised_blocks_after_it(setup, k):
    """Changing a clean token of block k moves no noised logit of blocks
    <= k (a noised block sees the clean past strictly before it) and moves
    some of every block > k."""
    batch, params, _ = setup
    model = tiny()
    noised, clean = batch["tokens"], batch["clean"]
    other = clean.at[:, k * BLOCK + 1].set((clean[:, k * BLOCK + 1] + 1) % MASK_ID)
    change = moved(pair_logits(model, params, noised, clean)[:, :LENGTH],
                   pair_logits(model, params, noised, other)[:, :LENGTH])
    assert not change[:(k + 1) * BLOCK].any()
    blocks_after = change[(k + 1) * BLOCK:].reshape(-1, BLOCK)
    assert blocks_after.any(axis=1).all() or k == LENGTH // BLOCK - 1


@pytest.mark.parametrize("k", [0, 3, 7])
def test_a_noised_token_moves_its_own_noised_block_only(setup, k):
    """Changing a noised token of block k moves noised logits of block k —
    all of them: the block is bidirectional — and nothing else, in either
    half (no row sees another block's noised rows; the clean half sees no
    noised row at all)."""
    batch, params, _ = setup
    model = tiny()
    noised, clean = batch["tokens"], batch["clean"]
    other = noised.at[:, k * BLOCK + 2].set((noised[:, k * BLOCK + 2] + 1) % MASK_ID)
    change = moved(pair_logits(model, params, noised, clean),
                   pair_logits(model, params, other, clean))
    want = np.zeros(2 * LENGTH, bool)
    want[k * BLOCK:(k + 1) * BLOCK] = True
    np.testing.assert_array_equal(change, want)


def test_the_clean_half_is_the_block_causal_run(setup):
    """The clean rows of the training pass see clean rows only, up to
    their own block: their hidden state is what the model gives for the
    clean sequence alone under block-causal attention — the default call,
    what a served model prefills with."""
    batch, params, _ = setup
    model = tiny()
    position = jnp.arange(LENGTH, dtype=jnp.float32)
    pair = model.apply(
        {"params": params},
        jnp.concatenate([batch["tokens"], batch["clean"]], axis=1),
        return_hidden=True, positions=jnp.concatenate([position, position]),
        mask=BlockMask(BLOCK, LENGTH))
    alone = model.apply({"params": params}, batch["clean"], return_hidden=True)
    np.testing.assert_allclose(pair[:, LENGTH:], alone, atol=2e-6)
    # and block-causal is not causal: a row sees the rest of its own block
    causal = model.apply({"params": params}, batch["clean"],
                         return_hidden=True, mask=BlockMask())
    assert float(jnp.abs(causal - alone).max()) > 1e-3


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The model-configs guide's test of the cut: the 4 shares of one
    expert layer (``held=(2i, 2)`` of 8 experts) — each the program's
    ``dropless_moe`` as the block calls it — add up to what the UNCUT
    reference gives for the whole layer (all 8 experts held, its own
    top-k). No shared expert: nothing is counted twice."""
    from flax import linen as nn

    from tpudist.parallel.ep import dropless_moe

    u = jax.random.normal(jax.random.key(3), (2, 32, 32))
    keys = jax.random.split(jax.random.key(4), 4)
    whole = {
        "moe_router/kernel": jax.random.normal(keys[0], (32, 8)),
        "moe_experts/w_gate": 0.3 * jax.random.normal(keys[1], (8, 32, 16)),
        "moe_experts/w_up": 0.3 * jax.random.normal(keys[2], (8, 32, 16)),
        "moe_experts/w_down": 0.3 * jax.random.normal(keys[3], (8, 16, 32)),
    }
    want = reference.expert_layer(
        u, whole, num_experts=8, top_k=2, first=0, count=8)

    class Layer(nn.Module):
        held: tuple

        @nn.compact
        def __call__(self, u):
            return dropless_moe(
                self, u, ffn_dim=16,
                routing=Routing(8, top_k=2, held=self.held))[0]

    total = 0.0
    for first in range(0, 8, 2):
        share = {
            "moe_router": {"kernel": whole["moe_router/kernel"]},
            "moe_experts": {k: whole[f"moe_experts/{k}"][first:first + 2]
                            for k in ("w_gate", "w_up", "w_down")},
        }
        part = Layer((first, 2)).apply({"params": share}, u)
        assert float(jnp.abs(part).max()) > 0.01  # every share adds
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_softmax_top_k_gates_are_the_normalised_scores():
    from tpudist.parallel.ep import select_experts

    logits = jax.random.normal(jax.random.key(1), (2, 16, 8))
    idx, gates = select_experts(logits, Routing(8, top_k=3))
    scores = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_array_equal(
        np.sort(idx, axis=-1), np.sort(np.argsort(-scores, axis=-1)[..., :3]))
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        gates, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("block, t_min", [(4, 1e-3), (8, 0.25), (1, 0.5)])
def test_transform_draws_a_level_a_block_and_weighs_by_its_inverse(
        block, t_min):
    """Per block one ``t`` in ``[t_min, 1]``; the masked positions carry
    ``1/t`` of their block, the others 0; a masked position holds the mask
    id and its target is the clean token (never the mask id); the share
    masked follows ``E[t]``; same seed, same stream."""
    clean = np.random.default_rng(0).integers(0, MASK_ID, (64, 64))
    run = block_diffusion_transform(MASK_ID, block, t_min=t_min, seed=7)
    out = run({"tokens": clean})
    np.testing.assert_array_equal(out["clean"], clean)
    masked = out["loss_weight"] > 0
    np.testing.assert_array_equal(out["tokens"] == MASK_ID, masked)
    np.testing.assert_array_equal(out["tokens"][~masked], clean[~masked])
    assert out["loss_weight"].dtype == np.float32
    assert (out["clean"] != MASK_ID).all()
    per_block = out["loss_weight"].reshape(64, -1, block)
    for row in per_block.reshape(-1, block):
        levels = np.unique(row[row > 0])
        assert len(levels) <= 1  # one t a block
    weights_seen = out["loss_weight"][masked]
    assert weights_seen.min() >= 1.0 and weights_seen.max() <= 1.0 / t_min + 1e-3
    assert abs(masked.mean() - (1 + t_min) / 2) < 0.05
    # E[m / t] = 1 a position: the loss is an unbiased sum over positions
    assert abs(out["loss_weight"].mean() - 1.0) < 0.25
    again = block_diffusion_transform(MASK_ID, block, t_min=t_min, seed=7)
    np.testing.assert_array_equal(again({"tokens": clean})["tokens"],
                                  out["tokens"])
    assert (run({"tokens": clean})["tokens"] != out["tokens"]).any()


def test_transform_refuses_a_sequence_that_is_no_multiple_of_the_block():
    run = block_diffusion_transform(MASK_ID, 8)
    with pytest.raises(ValueError, match="multiple of block_length"):
        run({"tokens": np.zeros((2, 12), np.int32)})


def test_loss_is_the_weighted_sum_over_the_noised_rows(setup):
    """``(1 / (B L)) sum w_i CE_i`` from the full logits of the training
    pass: positions of weight 0 add nothing, and the clean half's logits
    are not in it."""
    import optax

    batch, params, _ = setup
    model = tiny()
    logits = pair_logits(model, params, batch["tokens"], batch["clean"])
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :LENGTH], batch["clean"])
    want = jnp.sum(ce * batch["loss_weight"]) / ce.size
    got = program_loss(model)(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("how", ["full", "dots_saveable", "fused_ln"])
def test_recomputation_and_fused_norms_keep_loss_and_gradient(setup, how):
    batch, params, _ = setup
    want, want_grads = jax.value_and_grad(program_loss(tiny()))(params, batch)
    model = tiny(fused_ln=True) if how == "fused_ln" \
        else tiny(remat_policy=how)
    got, grads = jax.value_and_grad(program_loss(model))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-7)


def test_the_flash_kernel_gives_the_dense_paths_loss_and_gradient():
    """``attn_impl`` ``flash`` (the kernel, interpreted here) against the
    dense masked path at 2 x 128 rows, the key/value heads repeated to the
    query heads before the kernel: same loss, same gradients."""
    clean = np.asarray(jax.random.randint(jax.random.key(2), (1, 128), 0,
                                          MASK_ID))
    batch = {k: jnp.asarray(v) for k, v in block_diffusion_transform(
        MASK_ID, BLOCK, seed=3)({"tokens": clean}).items()}
    shapes = jax.eval_shape(lambda: tiny().init(
        jax.random.key(0), batch["tokens"]))["params"]
    params = weights.generate(shapes, 2**31 + 14)
    want, want_grads = jax.value_and_grad(
        program_loss(tiny()))(params, batch)
    got, grads = jax.value_and_grad(
        program_loss(tiny(attn_impl="flash")))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


def test_forward_loss_has_the_hooks_fit_asks_for(setup):
    batch, params, _ = setup
    forward = block_diffusion_forward(tiny(), chunk=8)
    fused = forward.rebuild(tiny(fused_ln=True))
    assert fused.model.fused_ln and not forward.model.fused_ln
    loss, (stats, sown) = forward.with_moe_stats()(params, {}, batch)
    assert float(loss) == pytest.approx(float(forward(params, {}, batch)[0]))
    assert stats == {} and set(sown) == {"h_0", "h_1"}
    # the counters count ROWS: 2 L a sequence, k choices each
    rows = batch["tokens"].shape[0] * 2 * LENGTH * 2
    held = float(sown["h_0"]["tokens"][0].sum())
    assert float(sown["h_0"]["held_share"][0]) == pytest.approx(held / rows)


def test_preset_has_the_published_sizes():
    model = sdar_30b_a3b()
    assert (model.hidden_dim, model.depth, model.vocab_size) == (2048, 48, 151936)
    assert (model.num_heads, model.num_kv_heads, model.head_dim,
            model.ffn_dim) == (32, 4, 128, 768)
    routing = model.routing
    assert (routing.num_experts, routing.top_k, routing.scoring,
            routing.routed_scale) == (128, 8, "softmax", 1.0)
    assert routing.held is None and model.sows_moe_stats
    assert model.max_seq_len == 32768 and model.rope_theta == 1e6
    assert model.block_length == 4 and model.flops_counter == "sdar"


def test_flops_count_rows_twice_and_the_head_once():
    """6 x weights x 2 rows a trained token for the layers, 6 x the head
    once, attention at ``L² + L b`` pairs a head a sequence."""
    from tpudist.telemetry import flops

    model = tiny()
    batch = {"tokens": jax.ShapeDtypeStruct((4, LENGTH), jnp.int32)}
    attn = 32 * (4 + 2 * 2) * 8 + 4 * 8 * 32
    layer = attn + 32 * 8 + 2 * 0.5 * 3 * 32 * 16
    tokens = 4 * LENGTH
    want = 6.0 * tokens * (2 * 2 * layer + VOCAB * 32) \
        + 2 * 12.0 * tokens * (LENGTH + BLOCK) * 4 * 8
    assert flops.train_step_flops(model, batch) == want
    assert flops.tokens_per_step(model, batch) == tokens


def test_block_scopes_keep_the_trace_contract(setup):
    """Every stage of a block is a direct child of ``h_<n>`` under the
    name ``tpudist/telemetry/trace.py`` promises the trace reader: the
    lowered step's op locations hold ``h_<n>/<scope>/`` for each of them,
    and nothing but the attention call sits under ``bd_attn``."""
    from tpudist.telemetry.trace import BLOCK_SCOPES, MOE_COUNTERS

    batch, params, _ = setup
    model = tiny()
    text = jax.jit(jax.grad(program_loss(model))).lower(
        params, batch).as_text(debug_info=True)
    mine = [s for s in BLOCK_SCOPES
            if s.startswith(("attn_", "bd_")) or BLOCK_SCOPES[s] == "moe_ms"]
    assert len(mine) == 9
    for scope in mine:
        for layer in range(2):
            assert f"h_{layer}/{scope}/" in text, scope
    assert "h_0/moe_shared/" not in text
    _, sown = model.apply({"params": params}, batch["tokens"],
                          mutable=["moe_stats"])
    assert set(sown["moe_stats"]) == {"h_0", "h_1"}
    assert set(sown["moe_stats"]["h_0"]) == set(MOE_COUNTERS)
