"""Fused residual-add+LayerNorm/RMSNorm kernel (tpudist/ops/layernorm.py)
vs the flax reference composition, interpret mode on CPU — the parity half
of the step-fusion layer. Covers the three public
compositions (plain / post-norm / pre-norm), both norm flavors, fp32+bf16,
edge shapes (non-lane-divisible hidden, non-tile row counts), gradients,
and the four model families' ``fused_ln`` knob (identical param trees,
forward/grad parity, scan layouts, untouched decode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from tpudist.ops.layernorm import FusedLayerNorm, fused_layernorm


def _data(shape, seed=0, dtype=jnp.float32):
    rng = np.random.Generator(np.random.PCG64(seed))
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _ref_ln(x, scale, bias, *, eps, dtype, rms):
    if rms:
        return nn.RMSNorm(epsilon=eps, dtype=dtype).apply(
            {"params": {"scale": scale}}, x
        )
    return nn.LayerNorm(epsilon=eps, dtype=dtype).apply(
        {"params": {"scale": scale, "bias": bias}}, x
    )


# ---- kernel-level parity ---------------------------------------------------


@pytest.mark.parametrize("d", [64, 80, 768])  # 80: non-lane-divisible
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rms", [False, True])
def test_forward_matches_flax(d, dtype, rms, kernel_parity):
    x = _data((3, 7, d), 1, dtype)  # 21 rows: not a tile multiple either
    y = _data((3, 7, d), 2, dtype)
    scale = _data((d,), 3)
    bias = _data((d,), 4)
    ref_r = x + y
    ref_n = _ref_ln(ref_r, scale, bias, eps=1e-5, dtype=dtype, rms=rms)
    n, r = fused_layernorm(
        x, scale, None if rms else bias, residual=y, eps=1e-5, rms=rms,
        out_dtype=dtype,
    )
    assert n.dtype == jnp.dtype(dtype) and r.dtype == x.dtype
    kernel_parity(n, ref_n)
    kernel_parity(r, ref_r)


def test_plain_and_post_norm_variants(kernel_parity):
    """No-residual (first/final LN) and post-norm (BERT) compositions."""
    x = _data((5, 96), 5)
    y = _data((5, 96), 6)
    scale, bias = _data((96,), 7), _data((96,), 8)
    kernel_parity(
        fused_layernorm(x, scale, bias, eps=1e-12),
        _ref_ln(x, scale, bias, eps=1e-12, dtype=jnp.float32, rms=False),
    )
    kernel_parity(
        fused_layernorm(x, scale, bias, residual=y, eps=1e-12,
                        return_residual=False),
        _ref_ln(x + y, scale, bias, eps=1e-12, dtype=jnp.float32, rms=False),
    )


@pytest.mark.parametrize("rms", [False, True])
@pytest.mark.parametrize("d", [80, 128])
def test_grads_match_flax(rms, d, kernel_parity):
    """Pre-norm composition with BOTH outputs consumed: dx/dy/dscale/dbias
    against autodiff through the flax composition."""
    x, y = _data((4, 5, d), 10), _data((4, 5, d), 11)
    scale, bias = _data((d,), 12), _data((d,), 13)
    w = _data((d, d), 14)

    def fused_loss(x, y, scale, bias):
        n, r = fused_layernorm(x, scale, None if rms else bias, residual=y,
                               eps=1e-5, rms=rms)
        return jnp.sum((n @ w) ** 2) + jnp.sum(jnp.sin(r))

    def ref_loss(x, y, scale, bias):
        r = x + y
        n = _ref_ln(r, scale, bias, eps=1e-5, dtype=jnp.float32, rms=rms)
        return jnp.sum((n @ w) ** 2) + jnp.sum(jnp.sin(r))

    gf = jax.grad(fused_loss, argnums=(0, 1, 2, 3))(x, y, scale, bias)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(x, y, scale, bias)
    for name, a, b in zip("x y scale bias".split(), gf, gr):
        if rms and name == "bias":
            continue  # rms has no bias param; the dummy's grad is unused
        kernel_parity(a, b, atol=5e-5, rtol=5e-5)


def test_post_norm_grads_no_residual_cotangent(kernel_parity):
    """return_residual=False (post-norm): only the normed value feeds the
    loss; grads still match the reference sum+LN composition."""
    x, y = _data((8, 48), 20), _data((8, 48), 21)
    scale, bias = _data((48,), 22), _data((48,), 23)

    def fused_loss(x, y):
        n = fused_layernorm(x, scale, bias, residual=y,
                            return_residual=False, eps=1e-6)
        return jnp.sum(n ** 3)

    def ref_loss(x, y):
        return jnp.sum(
            _ref_ln(x + y, scale, bias, eps=1e-6, dtype=jnp.float32,
                    rms=False) ** 3
        )

    gf = jax.grad(fused_loss, argnums=(0, 1))(x, y)
    gr = jax.grad(ref_loss, argnums=(0, 1))(x, y)
    kernel_parity(gf[0], gr[0], atol=5e-5, rtol=5e-5)
    kernel_parity(gf[1], gr[1], atol=5e-5, rtol=5e-5)


def test_validation_errors():
    x = _data((4, 32), 0)
    with pytest.raises(ValueError, match="scale shape"):
        fused_layernorm(x, _data((16,), 1))
    with pytest.raises(ValueError, match="residual shape"):
        fused_layernorm(x, _data((32,), 1), residual=_data((4, 16), 2))
    with pytest.raises(ValueError, match="return_residual"):
        fused_layernorm(x, _data((32,), 1), return_residual=True)


def test_module_params_match_flax_modules():
    """FusedLayerNorm declares the exact nn.LayerNorm / nn.RMSNorm param
    tree — the checkpoint-compat contract the fused_ln knob relies on."""
    x = _data((2, 32), 0)
    fused = FusedLayerNorm(epsilon=1e-5).init(jax.random.key(0), x)
    flax_ln = nn.LayerNorm(epsilon=1e-5).init(jax.random.key(0), x)
    assert jax.tree_util.tree_structure(fused) == jax.tree_util.tree_structure(flax_ln)
    fused_rms = FusedLayerNorm(rms=True).init(jax.random.key(0), x)
    flax_rms = nn.RMSNorm().init(jax.random.key(0), x)
    assert jax.tree_util.tree_structure(fused_rms) == jax.tree_util.tree_structure(flax_rms)


# ---- model-family knob -----------------------------------------------------


def _gpt2(**kw):
    from tpudist.models.gpt2 import GPT2

    return GPT2(vocab_size=97, max_seq_len=32, hidden_dim=48, depth=2,
                num_heads=4, **kw)


def _llama(**kw):
    from tpudist.models.llama import Llama

    return Llama(vocab_size=97, max_seq_len=32, hidden_dim=48, depth=2,
                 num_heads=4, num_kv_heads=2, **kw)


def _bert(**kw):
    from tpudist.models.bert import Bert

    return Bert(vocab_size=97, max_seq_len=32, hidden_dim=48, depth=2,
                num_heads=4, **kw)


def _vit(**kw):
    from tpudist.models.vit import ViT

    return ViT(num_classes=10, patch_size=4, hidden_dim=48, depth=2,
               num_heads=4, mlp_dim=96, **kw)


_TOKENS = jnp.asarray(
    np.random.Generator(np.random.PCG64(0)).integers(0, 97, (2, 16)),
    jnp.int32,
)
_IMAGES = _data((2, 16, 16, 3), 99)


@pytest.mark.parametrize("build,inp", [
    (_gpt2, _TOKENS), (_llama, _TOKENS), (_bert, _TOKENS), (_vit, _IMAGES),
], ids=["gpt2", "llama", "bert", "vit"])
def test_model_fused_ln_parity(build, inp, kernel_parity):
    """Same params, same tree, same function (to kernel tolerance) — the
    fused_ln knob across all four families, forward AND grads."""
    m0, m1 = build(), build(fused_ln=True)
    v0 = m0.init(jax.random.key(0), inp, train=False)
    v1 = m1.init(jax.random.key(0), inp, train=False)
    assert jax.tree_util.tree_structure(v0) == jax.tree_util.tree_structure(v1)
    o0 = m0.apply(v0, inp, train=False)
    o1 = m1.apply(v0, inp, train=False)
    kernel_parity(o1, o0, atol=5e-5, rtol=5e-5)

    g0 = jax.grad(lambda p: jnp.mean(
        m0.apply({"params": p}, inp, train=True) ** 2))(v0["params"])
    g1 = jax.grad(lambda p: jnp.mean(
        m1.apply({"params": p}, inp, train=True) ** 2))(v0["params"])
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        kernel_parity(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("build", [_gpt2, _llama, _bert],
                         ids=["gpt2", "llama", "bert"])
def test_model_fused_ln_scan_layout(build, kernel_parity):
    """fused_ln composes with scan_layers (the one-traced-block layout)."""
    m0 = build(scan_layers=True)
    m1 = build(scan_layers=True, fused_ln=True)
    v0 = m0.init(jax.random.key(0), _TOKENS, train=False)
    kernel_parity(
        m1.apply(v0, _TOKENS, train=False),
        m0.apply(v0, _TOKENS, train=False),
        atol=5e-5, rtol=5e-5,
    )


def test_fused_ln_decode_path_unchanged():
    """Decode keeps the reference composition: a fused_ln GPT-2 generates
    BIT-identically to the unfused one (the decode trace never touches the
    kernel — single-token norms are launch-bound, not bandwidth-bound)."""
    from tpudist.generate import generate

    m0, m1 = _gpt2(), _gpt2(fused_ln=True)
    v = m0.init(jax.random.key(0), _TOKENS, train=False)
    prompt = _TOKENS[:, :4]
    out0 = generate(m0, v["params"], prompt, max_new_tokens=6, temperature=0.0)
    out1 = generate(m1, v["params"], prompt, max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
