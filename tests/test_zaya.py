"""ZAYA1 (tpudist.models.zaya) against its plain reference
(benchmarks/reference/zaya.py), and the properties its layer rests on:
causal convolutions and value shift, partial rotary embedding, and the
``(x, r)`` carry through recomputation and the chunked-CE forward.

CPU, tiny sizes, weights drawn as the harness draws them (N(0, 0.02);
``*scale`` leaves around one; biases off nought, so every path is live),
but for the router's last kernel (``setup``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare, weights
from benchmarks.reference import zaya as reference
from tpudist.models.lm_utils import chunked_lm_forward
from tpudist.models.llama import apply_rope
from tpudist.models.zaya import CcaMix, Zaya, shift_right, zaya1_8b
from tpudist.parallel.ep import Routing

CONFIG = {
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-5,
    "num_experts": 4, "num_experts_held": 2, "num_experts_per_tok": 1,
    "deployment": {"experts_held_first": 0}, "cca_time0": 2, "cca_time1": 2,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5e6}},
    "router_hidden_size": 8, "vocab_size": 96,
}


def tiny(dtype=jnp.float32, **kw):
    return Zaya(
        vocab_size=CONFIG["vocab_size"], max_seq_len=64, hidden_dim=32,
        depth=2, num_heads=4, num_kv_heads=2, head_dim=8, ffn_dim=16,
        routing=Routing(4, top_k=1, held=(0, 2), router="mlp",
                        router_width=8),
        dtype=dtype, **kw)


@pytest.fixture(scope="module")
def setup():
    tokens = jax.random.randint(jax.random.key(5), (4, 32), 0,
                                CONFIG["vocab_size"])
    shapes = jax.eval_shape(
        lambda: tiny().init(jax.random.key(0), tokens))["params"]
    params = weights.generate(shapes, 2**31 + 11)
    # as drawn, the router's last bias outweighs the token-dependent part
    # of its logits and every token of a layer takes one expert (PERF.md
    # §6, PR 28); these tests want held and absent experts and several
    # groups, so the router's kernels speak up
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 30.0 * x if [str(k.key) for k in path][1::2]
        == ["moe_router", "kernel"] else x, params)
    flat = dict(zip(weights.leaf_paths(params),
                    jax.tree_util.tree_leaves(params)))
    return tokens, params, flat


def program_loss(model):
    forward = chunked_lm_forward(model, chunk=8)
    return lambda params, tokens: forward(params, {}, {"tokens": tokens})[0]


def reference_value_and_grad(flat, tokens, precision="float32"):
    loss_sum = reference.make_loss_sum(CONFIG, precision)

    def mean(p):
        total, count = loss_sum(p, {"tokens": tokens})
        return total / count

    return jax.value_and_grad(mean)(flat)


def gaps(loss, grads, want_loss, want_grads):
    norm = lambda t: {k: float(jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))) for k, v in t.items()}
    leaf = compare.leaf_gaps(norm(grads), norm(want_grads))
    return {"loss": abs(float(loss) - float(want_loss)) / float(want_loss),
            "grad_worst": max(leaf.values()),
            "grad_median": float(np.median(list(leaf.values())))}


def test_loss_and_every_leafs_gradient_match_the_reference(setup):
    """Float32 against float32: every leaf's gradient, element by element.
    2e-4 of the leaf's largest element: the two sides sum in different
    orders (grouped product against masked dense experts, chunked head
    against whole logits) and nothing else differs."""
    tokens, params, flat = setup
    loss, grads = jax.value_and_grad(program_loss(tiny()))(params, tokens)
    want_loss, want = reference_value_and_grad(flat, tokens)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = dict(zip(weights.leaf_paths(grads),
                   jax.tree_util.tree_leaves(grads)))
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        np.testing.assert_allclose(
            got[name] / scale, want[name] / scale, atol=2e-4, err_msg=name)


# bf16 compute rounds every matmul operand to 8 bits of mantissa, fp8 to 3.
# Over three seeds at this size bf16 reads a loss gap of 1.6e-6..7.3e-6 and
# a median leaf-gradient gap of 5.8e-4..9.9e-4; the reference with fp8
# operands (the control one precision below) reads 5.9e-5..1.1e-4 and
# 4.7e-3..6.1e-3. Each limit lies between, about 2-3x from both sides.
BF16_LIMITS = {"loss": 2.5e-5, "grad_median": 2.2e-3}


def test_bf16_compute_passes_where_the_fp8_control_fails(setup):
    tokens, params, flat = setup
    want_loss, want = reference_value_and_grad(flat, tokens)
    loss, grads = jax.value_and_grad(
        program_loss(tiny(jnp.bfloat16)))(params, tokens)
    got = dict(zip(weights.leaf_paths(grads),
                   jax.tree_util.tree_leaves(grads)))
    bf16 = gaps(loss, got, want_loss, want)
    assert all(bf16[k] <= v for k, v in BF16_LIMITS.items()), bf16
    fp8 = gaps(*reference_value_and_grad(flat, tokens, "fp8"), want_loss, want)
    assert any(fp8[k] > v for k, v in BF16_LIMITS.items()), fp8


def test_convolutions_and_value_shift_are_causal():
    """Changing token t leaves every output before t as it was, and moves
    t (and, through the two taps, what follows)."""
    b, s, h, kv, dh, t = 2, 16, 4, 2, 8, 9
    keys = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(keys[0], (b, s, h, dh))
    k = jax.random.normal(keys[1], (b, s, kv, dh))
    va = jax.random.normal(keys[2], (b, s, kv, dh // 2))
    vb = jax.random.normal(keys[3], (b, s, kv, dh // 2))
    mix = CcaMix(rotary_dim=dh // 2)
    params = mix.init(keys[4], q, k, va, vb)
    before = mix.apply(params, q, k, va, vb)
    bump = lambda x: x.at[:, t].add(1.0)
    after = mix.apply(params, bump(q), bump(k), bump(va), bump(vb))
    for old, new in zip(before, after):
        np.testing.assert_array_equal(old[:, :t], new[:, :t])
        assert not np.allclose(old[:, t], new[:, t])
    # the value shift: the second half of a head is the token before's
    v = before[2]
    np.testing.assert_array_equal(v[:, 1:, :, dh // 2:], vb[:, :-1])
    np.testing.assert_array_equal(v[:, 0, :, dh // 2:], 0.0)
    np.testing.assert_array_equal(v[..., : dh // 2], va)
    np.testing.assert_array_equal(shift_right(q, 2)[:, 2:], q[:, :-2])


def test_partial_rotary_leaves_the_rest_of_the_head_untouched():
    x = jax.random.normal(jax.random.key(0), (2, 12, 3, 16))
    out = apply_rope(x, theta=5e6, rotary_dim=8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out[..., :8],
                               apply_rope(x[..., :8], theta=5e6))
    assert not np.allclose(out[:, 1:, :, :8], x[:, 1:, :, :8])
    # the default is the whole head: Llama's programs do not change
    np.testing.assert_array_equal(apply_rope(x), apply_rope(x, rotary_dim=16))
    with pytest.raises(ValueError, match="rotary_dim"):
        apply_rope(x, rotary_dim=7)


@pytest.mark.parametrize("how", ["full", "dots_saveable", "chunked"])
def test_the_carry_survives_recomputation_and_the_chunked_forward(setup, how):
    """``(x, r)`` through ``remat_policy`` and through
    ``chunked_lm_forward``: the same loss and the same gradient of the
    second layer's carry weight as the plain forward."""
    from tpudist.train import lm_loss

    tokens, params, _ = setup

    def plain(p):
        return lm_loss(tiny().apply({"params": p}, tokens), tokens)

    want, want_grads = jax.value_and_grad(plain)(params)
    if how == "chunked":
        loss_fn = lambda p: program_loss(tiny())(p, tokens)
    else:
        loss_fn = lambda p: lm_loss(
            tiny(remat_policy=how).apply({"params": p}, tokens), tokens)
    got, grads = jax.value_and_grad(loss_fn)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    carry = lambda g: g["h_1"]["moe_router"]["depth_scale"]
    assert float(jnp.linalg.norm(carry(want_grads))) > 0  # the carry is live
    np.testing.assert_allclose(carry(grads), carry(want_grads), rtol=1e-4,
                               atol=1e-9)


def test_preset_has_the_published_sizes():
    model = zaya1_8b()
    assert (model.hidden_dim, model.depth, model.vocab_size) == (2048, 40, 262272)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (8, 2, 128)
    assert model.routing.num_experts == 16 and model.routing.top_k == 1
    assert model.routing.held is None and model.sows_moe_stats


def test_router_counters_ride_the_chunked_step_without_health_metrics(setup):
    """A model that says it sows router counters (``sows_moe_stats``) gets
    them back with the step's metrics under the chunked-CE forward, health
    metrics or not; the loss is that of the forward without counters."""
    import optax

    from tpudist import mesh as mesh_lib
    from tpudist.telemetry import TelemetryConfig
    from tpudist.train import TrainState, lm_loss, make_train_step

    tokens, params, _ = setup
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
    model, tx = tiny(), optax.adam(1e-3)
    knobs = TelemetryConfig(health_metrics=False,
                            guard_nonfinite=False).step_kwargs()
    assert not knobs["telemetry"]
    forward_loss = chunked_lm_forward(model, 8)
    step = make_train_step(
        model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
        label_key="tokens", forward_loss=forward_loss, **knobs)
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=jax.tree_util.tree_map(jnp.copy, params),
                       batch_stats={}, opt_state=tx.init(params))
    counted = step(state, {"tokens": np.asarray(tokens)})[1]
    plain, _ = forward_loss(params, {}, {"tokens": tokens})
    assert float(counted["loss"]) == pytest.approx(float(plain), rel=1e-6)
    rows = tokens.size
    for layer in ("h_0", "h_1"):
        held = np.asarray(counted[f"moe/{layer}/tokens"])
        assert held.shape == (2,)
        share = float(counted[f"moe/{layer}/held_share"])
        assert held.sum() == pytest.approx(share * rows)
        assert float(counted[f"moe/{layer}/load_max_over_mean"]) >= 1.0


def test_block_scopes_keep_the_trace_contract(setup):
    """Every stage of a block is a direct child of ``h_<n>`` under the
    name ``tpudist/telemetry/trace.py`` promises the trace reader: the
    lowered step's op locations hold ``h_0/<scope>/`` for each of them."""
    from tpudist.telemetry.trace import BLOCK_SCOPES, MOE_COUNTERS

    tokens, params, _ = setup
    model = tiny()
    text = jax.jit(jax.grad(program_loss(model))).lower(
        params, tokens).as_text(debug_info=True)
    # (``mla_*`` and ``moe_shared`` are the Kanana-2 block's, ``attn_*``
    # and ``bd_attn`` the SDAR block's, ``lg_*``, ``swa_attn`` and
    # ``full_attn`` the Laguna block's, ``mamba_*``, ``ssd_scan`` and
    # ``gqa_*`` the Nemotron-H block's: test_kanana.py, test_sdar.py,
    # test_laguna.py, test_nemotron_h.py)
    mine = [s for s in BLOCK_SCOPES
            if not s.startswith(("mla_", "attn_", "bd_", "lg_", "swa_",
                                 "full_", "mamba_", "ssd_", "gqa_"))
            and s != "moe_shared"]
    assert len(mine) == 8
    for scope in mine:
        assert f"h_0/{scope}/" in text, scope
    _, sown = model.apply({"params": params}, tokens, mutable=["moe_stats"])
    assert set(sown["moe_stats"]["h_1"]) == set(MOE_COUNTERS)
