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
])
def test_default_blocks_follow_the_shape(seq_q, seq_k, head_dim, want):
    from tpudist.ops.flash_attention import default_blocks

    assert default_blocks(seq_q, seq_k, head_dim) == want
    block_q, block_k, _ = want
    assert seq_q % block_q == 0 and seq_k % block_k == 0
