"""Flash-attention kernel vs the XLA oracle (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.attention import dot_product_attention
from tpudist.ops.flash_attention import flash_attention


def _qkv(b=2, s=256, h=4, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal, kernel_parity):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = dot_product_attention(q, k, v, causal=causal)
    kernel_parity(out, ref)


def test_forward_bf16(kernel_parity):
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    kernel_parity(out, ref)


def test_multiple_k_blocks_small_blocks():
    # exercises the online-softmax accumulation across 4 K blocks and 4 Q blocks
    q, k, v = _qkv(s=512, h=2)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla(causal):
    q, k, v = _qkv(b=1, s=128, h=2, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ragged_seq_pads_and_masks(causal):
    """200 % 128 != 0: the wrapper pads to 256 and masks the padded keys —
    output and grads match the XLA oracle on the unpadded shape."""
    q, k, v = _qkv(s=200)
    out = flash_attention(q, k, v, causal=causal)
    ref = dot_product_attention(q, k, v, causal=causal)
    assert out.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

    g_fl = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g_fl, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5, err_msg=name
        )


def test_explicit_kv_len_matches_sliced_keys():
    q, k, v = _qkv(s=256)
    ref = dot_product_attention(q, k[:, :130], v[:, :130], causal=False)
    out = flash_attention(q, k, v, kv_len=130)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_head_dim_padding():
    # head_dim 64 (GPT-2's) is zero-padded to the 128-lane tile internally
    q, k, v = _qkv(s=128, d=64)
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_pallas_bwd_matches_scan_bwd():
    """The opt-in Pallas FA-2 backward (interpret mode here) must produce
    the same dq/dk/dv as the default blockwise-scan backward."""
    from tpudist.ops.flash_attention import (
        _bwd_blockwise, _bwd_pallas, _flash_fwd,
    )

    rng = np.random.Generator(np.random.PCG64(9))
    B, S, H, D = 2, 256, 2, 128
    sm = 1.0 / np.sqrt(D)
    for causal in (False, True):
        q, k, v = (
            jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
            for _ in range(3)
        )
        o, lse = _flash_fwd(
            q, k, v, causal=causal, sm_scale=sm, block_q=128, block_k=128
        )
        g = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
        res = (q, k, v, o, lse)
        got = _bwd_pallas(
            res, g, causal=causal, sm_scale=sm, block_q=128, block_k=128,
            interpret=True,
        )
        want = _bwd_blockwise(res, g, causal=causal, sm_scale=sm, block_k=128)
        for a, b in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )


def test_pallas_bwd_kv_len_matches_scan_bwd():
    """kv_len masking through the Pallas dq/dkv kernels (interpret mode)
    agrees with the blockwise-scan backward on the same masked problem."""
    from tpudist.ops.flash_attention import (
        _bwd_blockwise, _bwd_pallas, _flash_fwd,
    )

    rng = np.random.Generator(np.random.PCG64(11))
    B, S, H, D = 1, 256, 2, 128
    sm = 1.0 / np.sqrt(D)
    kv_len = 140  # second K block partially masked, none fully retired
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        for _ in range(3)
    )
    o, lse = _flash_fwd(
        q, k, v, causal=False, sm_scale=sm, block_q=128, block_k=128,
        kv_len=kv_len,
    )
    g = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
    res = (q, k, v, o, lse)
    got = _bwd_pallas(
        res, g, causal=False, sm_scale=sm, block_q=128, block_k=128,
        kv_len=kv_len, interpret=True,
    )
    want = _bwd_blockwise(
        res, g, causal=False, sm_scale=sm, block_k=128, kv_len=kv_len
    )
    for name, a, b in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5, err_msg=name
        )
    # padded keys receive zero gradient
    assert np.abs(np.asarray(got[1][:, :, kv_len:])).max() == 0.0
    assert np.abs(np.asarray(got[2][:, :, kv_len:])).max() == 0.0


@pytest.mark.parametrize("seq_q,seq_k,head_dim,want", [
    (4096, 4096, 128, (512, 1024, True)),   # measured: PERF.md §6, PR 28
    (2048, 2048, 128, (512, 1024, True)),
    (2304, 2304, 128, (256, 256, True)),    # the largest that divide
    (4096, 4096, 64, (128, 128, False)),    # the scan backward's shapes
    (1024, 1024, 128, (128, 128, False)),
    (128, 4096, 128, (128, 128, False)),
    (8192, 8192, 128, (512, 1024, True)),   # MLA's call: values of 128
])
def test_default_blocks_follow_the_shape(seq_q, seq_k, head_dim, want):
    from tpudist.ops.flash_attention import default_blocks

    assert default_blocks(seq_q, seq_k, head_dim) == want
    block_q, block_k, _ = want
    assert seq_q % block_q == 0 and seq_k % block_k == 0


# -- keys of one width, values of another (latent attention) ------------------


def _mla_qkv(b, s, h, dk, dv, seed=3):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, dk)),
            jax.random.normal(ks[1], (b, s, h, dk)),
            jax.random.normal(ks[2], (b, s, h, dv)),
            jax.random.normal(ks[3], (b, s, h, dv)))


@pytest.mark.parametrize("heads,dk,dv", [(8, 24, 16), (2, 192, 128)])
@pytest.mark.parametrize("causal", [False, True])
def test_key_and_value_widths_differ_forward_and_scan_backward(
        heads, dk, dv, causal):
    """Keys of 24 on values of 16 (8 heads; each padded to the lane tile)
    and the MLA shape itself, keys of 192 on values of 128 (fed as they
    are): output ``[B, S, H, dv]`` and all three gradients against the XLA
    oracle, the scale ``1/sqrt(dk)``."""
    q, k, v, g = _mla_qkv(2, 256, heads, dk, dv)
    attn = lambda fn: jax.vjp(
        lambda q, k, v: fn(q, k, v, causal=causal), q, k, v)
    out, vjp = attn(lambda *a, **kw: flash_attention(
        *a, block_q=128, block_k=128, **kw))
    ref, ref_vjp = attn(dot_product_attention)
    assert out.shape == (2, 256, heads, dv)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), vjp(g), ref_vjp(g)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("heads,dk,dv", [(8, 24, 16), (2, 192, 128)])
def test_key_and_value_widths_differ_pallas_backward(heads, dk, dv):
    """The Pallas dq / dkv kernels (interpret mode here) at the two widths
    — the 24 / 16 case padded to 128 / 128 as the wrapper pads it, 192 /
    128 as they are — against the XLA oracle's gradients."""
    from tpudist.ops.flash_attention import (
        _bwd_pallas, _flash_fwd, _lane_pad,
    )

    q, k, v, g = _mla_qkv(1, 256, heads, dk, dv, seed=4)
    _, ref_vjp = jax.vjp(
        lambda q, k, v: dot_product_attention(q, k, v, causal=True), q, k, v)
    lay = lambda x: jnp.pad(
        x.transpose(0, 2, 1, 3), [(0, 0)] * 3 + [(0, _lane_pad(x.shape[3]))])
    assert lay(q).shape[3] == (192 if dk == 192 else 128)
    sm = 1.0 / np.sqrt(dk)
    o, lse = _flash_fwd(lay(q), lay(k), lay(v), causal=True, sm_scale=sm,
                        block_q=128, block_k=128)
    got = _bwd_pallas((lay(q), lay(k), lay(v), o, lse), lay(g), causal=True,
                      sm_scale=sm, block_q=128, block_k=128, interpret=True)
    for name, a, b, width in zip(("dq", "dk", "dv"), got, ref_vjp(g),
                                 (dk, dk, dv)):
        np.testing.assert_allclose(
            a.transpose(0, 2, 1, 3)[..., :width], b, atol=5e-5, rtol=5e-5,
            err_msg=name)


def test_equal_widths_reach_the_kernels_they_reached_before():
    """A call with equal widths of 128 (the ZAYA1 cell's) hands the
    kernels its operands as they come: no pad of a head, every block of
    every ``pallas_call`` 128 wide, the scale ``1/sqrt(128)`` — and a
    head of 64 is still padded to the lane tile, a key of 192 is not."""
    from tpudist.ops.flash_attention import _lane_pad

    assert [_lane_pad(w) for w in (16, 64, 128, 192, 200, 256)] == \
        [112, 64, 0, 0, 56, 0]
    q, k, v = _qkv(b=1, s=256, h=2, d=128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).sum(),
        (0, 1, 2)))(q, k, v)
    text = str(jaxpr)
    assert "pallas_call" in text and " pad[" not in text
    shapes = {tuple(v.aval.shape) for e in jaxpr.jaxpr.eqns
              for v in e.outvars if hasattr(v.aval, "shape")}
    assert (1, 2, 256, 128) in shapes and not any(
        s[-1] in (192, 256) for s in shapes if len(s) == 4)
