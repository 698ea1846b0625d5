"""Flash-attention kernel vs the XLA oracle (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.attention import dot_product_attention
from tpudist.ops.flash_attention import CAUSAL, flash_attention


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
            q, k, v, mask=CAUSAL if causal else None, sm_scale=sm, block_q=128, block_k=128
        )
        g = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
        res = (q, k, v, o, lse)
        got = _bwd_pallas(
            res, g, mask=CAUSAL if causal else None, sm_scale=sm, block_q=128, block_k=128,
            interpret=True,
        )
        want = _bwd_blockwise(res, g, mask=CAUSAL if causal else None, sm_scale=sm, block_k=128)
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
        q, k, v, mask=None, sm_scale=sm, block_q=128, block_k=128,
        kv_len=kv_len,
    )
    g = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
    res = (q, k, v, o, lse)
    got = _bwd_pallas(
        res, g, mask=None, sm_scale=sm, block_q=128, block_k=128,
        kv_len=kv_len, interpret=True,
    )
    want = _bwd_blockwise(
        res, g, mask=None, sm_scale=sm, block_k=128, kv_len=kv_len
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
    o, lse = _flash_fwd(lay(q), lay(k), lay(v), mask=CAUSAL, sm_scale=sm,
                        block_q=128, block_k=128)
    got = _bwd_pallas((lay(q), lay(k), lay(v), o, lse), lay(g), mask=CAUSAL,
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


# -- the structured mask (BlockMask): causal, block-causal, block diffusion ---


def _masked_grads(fn, q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("length, block, block_q, block_k", [
    (128, 4, 128, 128),    # the cell's block length; one tile a copy
    (256, 4, 128, 128),    # two tiles a copy: the noised diagonal skips
    (256, 32, 128, 256),   # blocks wider than a tile is tall: 128 x 256
    (256, 128, 128, 128),  # a diffusion block the size of a tile
    (256, 256, 256, 128),  # one block: both copies bidirectional
    (128, 1, 128, 128),    # blocks of one token: the noised rows see
                           # themselves and the clean past
])
def test_block_diffusion_mask_matches_dense_masked_attention(
        length, block, block_q, block_k):
    """The masked kernel (interpret mode) over the ``2 L`` rows of a
    noised and a clean copy against dense attention under the same mask
    as a boolean array: output, dq, dk, dv — the scan backward."""
    from tpudist.ops.attention import BlockMask

    mask = BlockMask(block, length)
    q, k, v = _qkv(b=1, s=2 * length, h=2, d=32, seed=length + block)
    dense = lambda q, k, v: dot_product_attention(
        q, k, v, mask=mask.dense(2 * length)[None, None])
    flash = lambda q, k, v: flash_attention(
        q, k, v, mask=mask, block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), _masked_grads(flash, q, k, v),
                          _masked_grads(dense, q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("length, block, noised", [
    (256, 4, True), (256, 64, True), (512, 16, False), (256, 1, False),
])
def test_masked_pallas_backward_matches_the_scan_backward(
        length, block, noised):
    """Both backward paths under one mask description: the Pallas dq / dkv
    kernels (interpret mode) give the blockwise scan's dq, dk, dv — two
    copies, block-causal, and the causal instance."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import (
        _bwd_blockwise, _bwd_pallas, _flash_fwd,
    )

    rows = 2 * length if noised else length
    mask = BlockMask(block, length if noised else 0)
    rng = np.random.Generator(np.random.PCG64(block))
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, rows, 128)), jnp.float32)
               for _ in range(3))
    sm = 1.0 / np.sqrt(128)
    o, lse = _flash_fwd(q, k, v, mask=mask, sm_scale=sm, block_q=128,
                        block_k=256)
    g = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
    got = _bwd_pallas((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                      block_q=128, block_k=256, interpret=True)
    want = _bwd_blockwise((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                          block_k=256)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_block_diffusion_mask_with_grouped_heads_through_the_dispatcher():
    """GQA 8:1 as the SDAR block calls it: ``multi_head_attention`` repeats
    the one key/value head over its eight query heads before the kernel;
    ``flash`` under the mask equals the dense path, and dk / dv come back
    at the one head (the repeat's transpose sums the group)."""
    from tpudist.ops.attention import BlockMask, multi_head_attention

    mask = BlockMask(4, 128)
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 8, 32))
    k, v = (jax.random.normal(key, (1, 256, 1, 32)) for key in ks[1:])
    via = lambda impl: lambda q, k, v: multi_head_attention(
        q, k, v, mask=mask, impl=impl)
    np.testing.assert_allclose(via("flash")(q, k, v), via("xla")(q, k, v),
                               atol=2e-5, rtol=2e-5)
    got, want = (_masked_grads(via(impl), q, k, v) for impl in ("flash", "xla"))
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    # ``auto`` keeps a structured mask off the vmem kernel: dense below
    # 2048 rows (the flash kernel from there on)
    np.testing.assert_allclose(via("auto")(q, k, v), via("xla")(q, k, v),
                               atol=1e-6)
    with pytest.raises(ValueError, match="not both"):
        multi_head_attention(q, k, v, mask=mask, causal=True)


def _computed_tiles(mask, rows, block_q, block_k):
    """(q tile, k tile) pairs whose products the forward kernel RUNS,
    observed from outside: a NaN in one key tile's values reaches the
    output rows of exactly the query tiles that computed it (0 x NaN is
    NaN: a tile that is computed and masked whole still shows)."""
    q, k, v = _qkv(b=1, s=rows, h=1, d=32, seed=rows)
    computed = 0
    for tile in range(rows // block_k):
        poisoned = v.at[:, tile * block_k:(tile + 1) * block_k].set(jnp.nan)
        out = flash_attention(q, k, poisoned, mask=mask, block_q=block_q,
                              block_k=block_k)
        hit = np.isnan(np.asarray(out)).any(axis=(0, 2, 3))
        hit = hit.reshape(rows // block_q, block_q)
        assert (hit.all(axis=1) | ~hit.any(axis=1)).all()  # whole tiles
        computed += int(hit.any(axis=1).sum())
    return computed


@pytest.mark.parametrize("length, block, noised, block_q, block_k, share, window", [
    (512, 4, True, 128, 256, 16 / 32, 0),  # clean 6, noised to clean 6, diagonal 4
    (1024, 4, True, 256, 512, 16 / 32, 0),  # the cell's blocks at an eighth
    (256, 4, True, 128, 128, 8 / 16, 0),   # 3 + 3 + 2
    (256, 128, True, 128, 128, 6 / 16, 0),  # tile-sized blocks: 3 + 1 + 2
    (512, 64, False, 128, 128, 10 / 16, 0),  # block-causal
    (512, 1, False, 128, 128, 10 / 16, 0),   # the causal instance
    (512, 1, False, 128, 256, 6 / 8, 0),
    # sliding windows on the band's grid: the diagonal and the tile before
    (512, 1, False, 128, 128, 7 / 16, 128),
    (512, 1, False, 128, 256, 6 / 8, 200),
    (1024, 1, False, 256, 128, 16 / 32, 300),
])
def test_computed_tile_share_is_what_the_kernel_runs(
        length, block, noised, block_q, block_k, share, window):
    """The static counter of the tile skipping equals the kernel's own
    products, tile for tile."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import computed_tile_share

    rows = 2 * length if noised else length
    mask = BlockMask(block, length if noised else 0, window)
    assert computed_tile_share(mask, rows, block_q, block_k) == share
    tiles = (rows // block_q) * (rows // block_k)
    assert _computed_tiles(mask, rows, block_q, block_k) == share * tiles


def test_tile_share_at_the_cells_shape():
    """8,192 rows (two copies of 4,096) in blocks of 4 at the blocks the
    shape takes, 512 x 1024: 48 of 128 tiles — clean to clean 20, noised
    to clean 20, the noised diagonal 8 — against 72 for a causal call over
    the same rows and a need of ``(L² + L b) / (2 L)²``; no mask, all."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import (
        CAUSAL, computed_tile_share, default_blocks,
    )

    block_q, block_k, pallas_bwd = default_blocks(4096, 4096, 128)
    assert (block_q, block_k, pallas_bwd) == (512, 1024, True)
    mask = BlockMask(4, 4096)
    assert computed_tile_share(mask, 8192, block_q, block_k) == 48 / 128
    assert computed_tile_share(CAUSAL, 8192, block_q, block_k) == 72 / 128
    assert computed_tile_share(None, 8192, block_q, block_k) == 1.0
    need = (4096 ** 2 + 4096 * 4) / 8192 ** 2
    assert 0.25 < need < 0.2503 < 48 / 128


def test_causal_instance_is_the_old_path_to_the_digit():
    """``causal=True`` IS ``BlockMask()``: one object, the kernels traced
    from it compare ``q_pos >= k_pos`` and skip ``k0 <= q0 + block_q - 1``
    as before the mask was a description; the general formula at block 1
    in one copy is the same function, and both give the same bits."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import CAUSAL

    assert CAUSAL == BlockMask() and CAUSAL.causal
    pos = np.arange(64)
    np.testing.assert_array_equal(
        CAUSAL.allowed(pos[:, None], pos[None, :]), np.tril(np.ones((64, 64), bool)))
    q, k, v = _qkv(s=384, h=2, d=64, seed=21)
    old = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    new = flash_attention(q, k, v, mask=CAUSAL, block_q=128, block_k=128)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    # the traced forward kernel holds the comparison and no shift, divide
    # or second copy's offset
    text = str(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v))
    assert ":bool[128,128] = ge " in text
    assert "shift_right" not in text and ":bool[128,128] = lt" not in text


def test_mask_misuse_is_refused():
    from tpudist.ops.attention import BlockMask

    q, k, v = _qkv(b=1, s=256, h=1, d=32)
    with pytest.raises(ValueError, match="not both"):
        flash_attention(q, k, v, causal=True, mask=BlockMask(4))
    with pytest.raises(NotImplementedError, match="2 x that many rows"):
        flash_attention(q, k, v, mask=BlockMask(4, 64))  # not 128-aligned
    with pytest.raises(NotImplementedError, match="2 x that many rows"):
        flash_attention(q, k, v, mask=BlockMask(4, 256))  # rows != 2 x 256
    with pytest.raises(NotImplementedError, match="do not divide a copy"):
        flash_attention(q, k, v, mask=BlockMask(4, 128), block_q=256)
    with pytest.raises(ValueError, match="divide noised_len"):
        BlockMask(8, 12)
    # a window goes with one copy at block 1
    with pytest.raises(ValueError, match="causal mask only"):
        BlockMask(4, window=8)
    with pytest.raises(ValueError, match="causal mask only"):
        BlockMask(1, 128, window=8)
    with pytest.raises(ValueError, match=">= 0"):
        BlockMask(window=-1)
    assert not BlockMask(window=8).causal and BlockMask(window=0).causal


@pytest.mark.parametrize("block, noised_len, rows, window", [
    (4, 256, 512, 0), (128, 256, 512, 0), (256, 256, 512, 0),
    (1, 128, 256, 0), (32, 256, 512, 0), (64, 0, 512, 0), (3, 0, 384, 0),
    # sliding windows: under a tile, across tiles, one key, wider than S
    (1, 0, 512, 128), (1, 0, 512, 200), (1, 0, 384, 1), (1, 0, 768, 300),
    (1, 0, 512, 5000),
])
def test_tile_tests_are_the_dense_mask_tile_by_tile(block, noised_len, rows,
                                                    window):
    """``tile_live`` = some pair of the tile allowed, ``tile_full`` = every
    pair, ``tile_allowed`` = the tile's pairs themselves, each against the
    boolean array of ``allowed`` cut into tiles, for every tile that lies
    whole in one copy; a window's ``allowed`` against ``0 <= i - j < W``
    written out."""
    from tpudist.ops.attention import BlockMask

    mask = BlockMask(block, noised_len, window)
    dense = np.asarray(mask.dense(rows))
    if window:
        pos = np.arange(rows)
        np.testing.assert_array_equal(dense, (pos[:, None] >= pos[None, :])
                                      & (pos[:, None] - pos[None, :] < window))
    for block_q, block_k in ((128, 128), (128, 256), (256, 128)):
        if noised_len % block_q or noised_len % block_k or rows % block_k \
                or rows % block_q:
            continue
        nq, nk = rows // block_q, rows // block_k
        tiles = dense.reshape(nq, block_q, nk, block_k).transpose(0, 2, 1, 3)
        qi, ki = np.arange(nq)[:, None], np.arange(nk)[None, :]
        live = np.asarray(mask.tile_live(qi, ki, block_q, block_k))
        np.testing.assert_array_equal(live, tiles.any(axis=(2, 3)))
        np.testing.assert_array_equal(
            mask.tile_full(qi, ki, block_q, block_k), tiles.all(axis=(2, 3)))
        for a, b in zip(*np.nonzero(live)):
            np.testing.assert_array_equal(
                mask.tile_allowed(jnp.int32(a), jnp.int32(b), block_q,
                                  block_k), tiles[a, b])


def test_block_causal_mask_on_a_ragged_sequence():
    """One copy, blocks of 8, 200 rows: the wrapper pads to 256 and the
    kernels mask the padded keys beside the block mask (the single-branch
    path: ``kv_len`` and a mask other than the causal one together)."""
    from tpudist.ops.attention import BlockMask

    mask = BlockMask(8)
    q, k, v = _qkv(b=1, s=200, h=2, d=32, seed=31)
    dense = lambda q, k, v: dot_product_attention(
        q, k, v, mask=mask.dense(200)[None, None])
    flash = lambda q, k, v: flash_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(_masked_grads(flash, q, k, v),
                    _masked_grads(dense, q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# -- the live-step grid: a static list of the live tiles ----------------------


def _committed_sweeps(monkeypatch, mask, rows, block_q, block_k, kv_len=None):
    """What each of the three ``pallas_call``s is given, traced at these
    shapes (one batch row, one head; ``jax.eval_shape``: nothing runs):
    ``{kernel: (steps, names)}`` with ``steps`` its step list (or ``None``)
    and ``names[pos]`` the block index the call's committed index maps name
    for each of its in_specs at grid step ``(0, 0, *pos)`` — ``pos`` one
    index on the live-step grid, two on the square and the band's."""
    from tpudist.ops import flash_attention as fa

    seen = []
    real = fa._pallas

    def spy(kernel, steps, **kw):
        seen.append((steps, kw))
        return real(kernel, steps, **kw)

    monkeypatch.setattr(fa, "_pallas", spy)
    q = jax.ShapeDtypeStruct((1, 1, rows, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, 1, rows), jnp.float32)
    jax.eval_shape(lambda q: fa._flash_fwd(
        q, q, q, mask=mask, sm_scale=1.0, block_q=block_q, block_k=block_k,
        kv_len=kv_len), q)
    jax.eval_shape(lambda q, lse: fa._bwd_pallas(
        (q, q, q, q, lse), q, mask=mask, sm_scale=1.0, block_q=block_q,
        block_k=block_k, kv_len=kv_len, interpret=True), q, lse)
    sweeps = {}
    for name, (steps, kw) in zip(("fwd", "dkv", "dq"), seen):
        dims = kw["grid"][2:]
        tbl = () if steps is None else (steps.ravel(),)
        sweeps[name] = steps, np.array(
            [[int(spec.index_map(0, 0, *pos, *tbl)[2])
              for spec in kw["in_specs"]] for pos in np.ndindex(*dims)]
        ).reshape(*dims, len(kw["in_specs"]))
    return sweeps


def _live(mask, kv_len, rows, block_q, block_k):
    """[q tile, k tile]: has it an allowed pair (the mask's tile test; a
    ``kv_len`` retires whole K blocks beside it)?"""
    qi = np.arange(rows // block_q)[:, None]
    ki = np.arange(rows // block_k)[None, :]
    live = np.ones((qi.size, ki.size), bool)
    if mask is not None:
        live &= np.asarray(mask.tile_live(qi, ki, block_q, block_k))
    if kv_len is not None:
        live &= ki * block_k < kv_len
    return live


def _want_steps(tiles):
    """The live-step list a kernel should carry for its ``[outer, inner]``
    live tiles, written out sweep by sweep: each live tile once, in the
    square grid's order, flagged at its sweep's ends; a sweep with none
    one step, opening and closing it, on the block the step before
    named."""
    want = []
    for i, row in enumerate(tiles):
        held = np.flatnonzero(row)
        if not held.size:
            want.append((i, want[-1][1] if want else 0, 1, 1))
        for j in held:
            want.append((i, j, j == held[0], j == held[-1]))
    return np.array(want, np.int32)


# (mask, rows, block_q, block_k, kv_len): the three cells' shapes, then
# small ones — block diffusion, causal, block-causal, a ragged kv_len that
# retires whole K blocks, and no mask
_SWEEP_CASES = {
    "sdar_cell": (("bd", 4, 4096), 8192, 512, 1024, None),
    "kanana_cell": (("causal",), 8192, 512, 1024, None),
    "zaya_cell": (("causal",), 4096, 512, 1024, None),
    "bd_small": (("bd", 4, 512), 1024, 128, 256, None),
    "bd_tile_blocks": (("bd", 128, 256), 512, 128, 128, None),
    "causal_small": (("causal",), 512, 128, 256, None),
    "block_causal": (("bd", 64, 0), 512, 128, 128, None),
    "ragged_kv": (None, 512, 128, 128, 200),
    "block_causal_ragged": (("bd", 8, 0), 512, 128, 128, 300),
    "unmasked": (None, 1024, 128, 256, None),
}


def _mask_of(spec):
    from tpudist.ops.attention import BlockMask

    if spec is None:
        return None
    return CAUSAL if spec[0] == "causal" else BlockMask(*spec[1:])


@pytest.mark.parametrize("case", list(_SWEEP_CASES))
def test_committed_index_maps_fetch_once_a_live_tile(case, monkeypatch):
    """Every committed index map over the whole grid of the forward, dkv
    and dq kernels. A masked call runs on the live-step grid: every step
    names a live tile, each live tile exactly once, in the square grid's
    sweep order, the first / last flags at the sweep's ends, a sweep with
    no live tile one flagged step; the resident operands name the list's
    outer block, the streamed ones (K / V; Q / dO / lse / delta in dkv) its
    inner block, so a sweep changes its streamed index once a live tile.
    An unmasked call carries no list and runs on the square grid. Building
    the list stages no JAX op."""
    spec, rows, block_q, block_k, kv_len = _SWEEP_CASES[case]
    mask = _mask_of(spec)
    live = _live(mask, kv_len, rows, block_q, block_k)
    sweeps = _committed_sweeps(monkeypatch, mask, rows, block_q, block_k,
                               kv_len)
    streamed = {"fwd": (1, 2), "dkv": (0, 1, 2, 3), "dq": (0, 1)}
    for kernel, (steps, names) in sweeps.items():
        tiles = live.T if kernel == "dkv" else live  # [outer, inner]
        resident = [s for s in range(names.shape[-1])
                    if s not in streamed[kernel]]
        if tiles.all():  # the square grid, as before the list
            assert steps is None and names.shape[:2] == tiles.shape, kernel
            assert (names[:, :, resident]
                    == np.arange(tiles.shape[0])[:, None, None]).all()
            for s in streamed[kernel]:
                assert (names[:, :, s] == np.arange(tiles.shape[1])).all()
            continue
        np.testing.assert_array_equal(steps, _want_steps(tiles),
                                      err_msg=kernel)
        outer, inner, first, last = steps.T
        assert names.shape == (len(steps), len(streamed[kernel])
                               + len(resident)), kernel
        for s in resident:
            np.testing.assert_array_equal(names[:, s], outer)
        for s in streamed[kernel]:
            np.testing.assert_array_equal(names[:, s], inner)
        # each live tile named once; the other steps open and close a
        # sweep with no live tile, one a sweep
        at_live = tiles[outer, inner]
        assert at_live.sum() == tiles.sum() == len(
            {(o, i) for o, i in zip(outer[at_live], inner[at_live])})
        hollow = ~tiles.any(axis=1)
        assert (~at_live).sum() == hollow.sum()
        assert (first[~at_live] == 1).all() and (last[~at_live] == 1).all()
        assert set(outer[~at_live]) == set(np.flatnonzero(hollow))
        # a sweep's steps name distinct inner blocks: one fetch a tile
        same_sweep = outer[1:] == outer[:-1]
        assert (inner[1:][same_sweep] > inner[:-1][same_sweep]).all()
        assert first.sum() == last.sum() == tiles.shape[0]
    if mask is None and kv_len is None:
        assert all(steps is None for steps, _ in sweeps.values())
    # numpy alone, while a step is traced: no JAX op is staged or run (on
    # the chip each would compile, op by op, inside the step's trace)
    from tpudist.ops import flash_attention as fa

    for dkv in (False, True):
        n_outer, n_inner = live.T.shape if dkv else live.shape
        build = lambda: fa._step_list.__wrapped__(
            mask, kv_len, n_outer, n_inner, block_q, block_k, dkv)
        assert not jax.make_jaxpr(lambda: (build(), jnp.int32(0))[1])(
            ).jaxpr.eqns


@pytest.mark.parametrize("mask_spec, rows, blocks, share", [
    (("bd", 4, 4096), 8192, None, 0.375),    # sdar_30b_bd_train_s4096
    (("causal",), 8192, None, 0.5625),       # kanana2_30b_train_s8192
    (("causal",), 4096, None, 0.625),        # zaya1_8b_train_s4096
    (None, 8192, None, 1.0),
    (None, 4096, None, 1.0),
    (("bd", 4, 512), 1024, (128, 256), 16 / 32),
    (("bd", 64, 0), 512, (128, 128), 10 / 16),
    (("causal",), 512, (128, 256), 6 / 8),
    (None, 512, (128, 128), 1.0),
])
def test_fetched_tile_share_is_what_the_index_maps_fetch(
        mask_spec, rows, blocks, share, monkeypatch):
    """The counters at the blocks each cell's shape takes (``None``: 512 x
    1024) and at small ones, and each kernel's own share of steps whose
    streamed block changes index, read off its committed index maps in the
    order its grid runs them: forward, dq and dkv alike, one fetch a live
    tile (where the dead steps fetched, every step of a masked call did:
    1.0).
    The live-step grid runs a step a live tile, so ``grid_step_share`` is
    the same share (1.0 on the square grid); the tiles computed are
    unchanged."""
    from tpudist.ops.flash_attention import (
        computed_tile_share, default_blocks, fetched_tile_share,
        grid_step_share,
    )

    mask = _mask_of(mask_spec)
    if blocks is None:
        copy = mask.noised_len if mask and mask.noised_len else rows
        blocks = default_blocks(copy, copy, 128)[:2]
        assert blocks == (512, 1024)
    assert fetched_tile_share(mask, rows, *blocks) == share
    assert computed_tile_share(mask, rows, *blocks) == share
    assert grid_step_share(mask, rows, *blocks) == share
    tiles = (rows // blocks[0]) * (rows // blocks[1])
    for kernel, (_, names) in _committed_sweeps(
            monkeypatch, mask, rows, *blocks).items():
        names = names.reshape(-1, names.shape[-1])  # in the grid's order
        inner = names[:, {"fwd": 1, "dkv": 0, "dq": 0}[kernel]]
        outer = names[:, {"fwd": 0, "dkv": 4, "dq": 2}[kernel]]
        fetches = 1 + np.sum((inner[1:] != inner[:-1])
                             | (outer[1:] != outer[:-1]))
        assert fetches / tiles == len(names) / tiles == share, kernel


@pytest.mark.parametrize("block, block_q, block_k", [
    (4, 128, 128), (16, 128, 256),
])
def test_dead_runs_fetch_nothing_and_change_no_bit(block, block_q, block_k,
                                                   monkeypatch):
    """Two copies of 512 rows in blocks of ``block``: a dkv sweep with dead
    Q blocks before its first live one (a clean K block that only later
    rows see), a forward sweep with a dead K block between two live ones
    (a noised Q block's own block, then the clean past) — the live-step
    lists step over neither. The forward and the Pallas backward
    (interpret mode) on the live-step grid against the dense reference and
    the scan backward, and against the same kernels fed a list of EVERY
    tile of the square, whose dead steps the liveness test in the bodies
    skips: equal to the last bit."""
    from tpudist.ops import flash_attention as fa
    from tpudist.ops.attention import BlockMask

    length = 512
    mask = BlockMask(block, length)
    rows = 2 * length
    fwd_steps = fa._step_list(mask, None, rows // block_q, rows // block_k,
                              block_q, block_k, False)
    dkv_steps = fa._step_list(mask, None, rows // block_k, rows // block_q,
                              block_q, block_k, True)
    live = _live(mask, None, rows, block_q, block_k)
    first = [np.flatnonzero(r)[0] for r in live.T]
    assert max(first) > 0  # dkv: a dead run before the first live tile
    opens = dkv_steps[dkv_steps[:, 2] == 1]
    np.testing.assert_array_equal(opens[:, 1], first)  # ... stepped over
    gaps = [i for i, r in enumerate(live) if np.any(np.diff(
        np.flatnonzero(r)) > 1)]
    assert gaps  # forward: a dead run between two live tiles ...
    for i in gaps:  # ... stepped over
        np.testing.assert_array_equal(fwd_steps[fwd_steps[:, 0] == i, 1],
                                      np.flatnonzero(live[i]))

    rng = np.random.Generator(np.random.PCG64(block))
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, rows, 128)), jnp.float32)
               for _ in range(3))
    g = jnp.asarray(rng.normal(size=(1, 2, rows, 128)), jnp.float32)
    sm = 1.0 / np.sqrt(128)

    def kernels():
        o, lse = fa._flash_fwd(q, k, v, mask=mask, sm_scale=sm,
                               block_q=block_q, block_k=block_k)
        grads = fa._bwd_pallas((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                               block_q=block_q, block_k=block_k,
                               interpret=True)
        return [np.asarray(x) for x in (o, lse, *grads)]

    got = kernels()
    dense = mask.dense(rows)[None, None]
    ref = lambda q, k, v: dot_product_attention(
        *(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
        mask=dense).transpose(0, 2, 1, 3)
    o_ref, vjp = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(got[0], o_ref, atol=2e-5, rtol=2e-5)
    for a, b in zip(got[2:], vjp(g)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    scan = fa._bwd_blockwise((q, k, v, jnp.asarray(got[0]),
                              jnp.asarray(got[1])), g, mask=mask,
                             sm_scale=sm, block_k=block_k)
    for a, b in zip(got[2:], scan):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    square = lambda mask, kv_len, n_outer, n_inner, *_: fa._steps(
        np.ones((n_outer, n_inner), bool))
    monkeypatch.setattr(fa, "_step_list", square)
    for a, b in zip(got, kernels()):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["ragged_kv", "block_causal_ragged"])
def test_key_blocks_a_kv_len_retires_get_zero_gradients(case):
    """A ``kv_len`` that retires whole K blocks leaves their dkv sweeps
    without a live tile: each is one step that opens and closes its sweep,
    and its dk / dv come out zero — written, not left as the interpreter's
    NaN fill — while the other rows match the scan backward."""
    from tpudist.ops import flash_attention as fa

    spec, rows, block_q, block_k, kv_len = _SWEEP_CASES[case]
    mask = _mask_of(spec)
    live = _live(mask, kv_len, rows, block_q, block_k)
    retired = np.flatnonzero(~live.any(axis=0))  # K blocks
    assert retired.size and retired[0] * block_k >= kv_len
    dkv_steps = fa._step_list(mask, kv_len, rows // block_k, rows // block_q,
                              block_q, block_k, True)
    hollow = dkv_steps[np.isin(dkv_steps[:, 0], retired)]
    assert len(hollow) == retired.size and (hollow[:, 2:] == 1).all()

    rng = np.random.Generator(np.random.PCG64(kv_len))
    q, k, v, g = (jnp.asarray(rng.normal(size=(1, 2, rows, 128)), jnp.float32)
                  for _ in range(4))
    sm = 1.0 / np.sqrt(128)
    o, lse = fa._flash_fwd(q, k, v, mask=mask, sm_scale=sm, block_q=block_q,
                           block_k=block_k, kv_len=kv_len)
    got = fa._bwd_pallas((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         interpret=True)
    want = fa._bwd_blockwise((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                             block_k=block_k, kv_len=kv_len)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    for grad in got[1:]:
        tail = np.asarray(grad)[:, :, retired[0] * block_k:]
        assert tail.size and (tail == 0).all()


# -- the sliding window: BlockMask(window=W) and the band's grid --------------

# (rows, window, block_q, block_k): a window under one tile, one that
# straddles tiles, one wider than a tile, blocks taller than wide
_WINDOW_CASES = [(512, 128, 128, 128), (512, 200, 128, 256),
                 (1024, 300, 256, 128)]


@pytest.mark.parametrize("rows, window, block_q, block_k", _WINDOW_CASES)
def test_sliding_window_matches_dense_masked_attention(rows, window, block_q,
                                                       block_k):
    """The windowed kernel (interpret mode) against dense attention under
    the same mask as a boolean array: output, dq, dk, dv — the scan
    backward."""
    from tpudist.ops.attention import BlockMask

    mask = BlockMask(window=window)
    q, k, v = _qkv(b=1, s=rows, h=2, d=32, seed=rows + window)
    dense = lambda q, k, v: dot_product_attention(
        q, k, v, mask=mask.dense(rows)[None, None])
    flash = lambda q, k, v: flash_attention(
        q, k, v, mask=mask, block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), _masked_grads(flash, q, k, v),
                          _masked_grads(dense, q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("rows, window, block_q, block_k", _WINDOW_CASES)
def test_sliding_window_pallas_backward_matches_the_scan_backward(
        rows, window, block_q, block_k):
    """The Pallas dq / dkv kernels on the band's grid (interpret mode) give
    the blockwise scan's dq, dk, dv, and the forward on its band the dense
    output."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import (
        _bwd_blockwise, _bwd_pallas, _flash_fwd,
    )

    mask = BlockMask(window=window)
    rng = np.random.Generator(np.random.PCG64(window))
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, rows, 128)), jnp.float32)
               for _ in range(3))
    sm = 1.0 / np.sqrt(128)
    o, lse = _flash_fwd(q, k, v, mask=mask, sm_scale=sm, block_q=block_q,
                        block_k=block_k)
    want_o = dot_product_attention(
        *(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
        mask=mask.dense(rows)[None, None]).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
    g = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
    got = _bwd_pallas((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                      block_q=block_q, block_k=block_k, interpret=True)
    want = _bwd_blockwise((q, k, v, o, lse), g, mask=mask, sm_scale=sm,
                          block_k=block_k)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows, window, block_q, block_k", [
    (8192, 512, 512, 512), (8192, 512, 512, 1024), (8192, 512, 256, 512),
    *_WINDOW_CASES, (512, 1, 128, 128), (512, 512, 128, 128),
])
def test_band_grid_names_each_live_tile_once(rows, window, block_q, block_k,
                                             monkeypatch):
    """A windowed call's three grids span the band and no more: the inner
    axis is the widest sweep's live tiles; every sweep names its live
    blocks in order, one index change a live tile, then stays on its last
    (the steps past a narrower band fetch nothing); no step list rides
    along. ``grid_step_share`` is the grids' steps over the square's tiles
    and ``fetched_tile_share`` the live tiles over the same."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import (
        computed_tile_share, fetched_tile_share, grid_step_share,
    )

    mask = BlockMask(window=window)
    live = _live(mask, None, rows, block_q, block_k)
    sweeps = _committed_sweeps(monkeypatch, mask, rows, block_q, block_k)
    streamed = {"fwd": (1, 2), "dkv": (0, 1, 2, 3), "dq": (0, 1)}
    steps = 0
    for kernel, (listed, names) in sweeps.items():
        assert listed is None, kernel
        tiles = live.T if kernel == "dkv" else live  # [outer, inner]
        widest = int(tiles.sum(axis=1).max())
        assert names.shape[:2] == (tiles.shape[0], widest), kernel
        steps += names.shape[0] * names.shape[1]
        resident = [s for s in range(names.shape[2])
                    if s not in streamed[kernel]]
        assert (names[:, :, resident]
                == np.arange(tiles.shape[0])[:, None, None]).all(), kernel
        for i, row in enumerate(tiles):
            held = np.flatnonzero(row)
            assert held.size and (np.diff(held) == 1).all()  # contiguous
            for s in streamed[kernel]:
                want = np.concatenate([held, np.full(widest - held.size,
                                                     held[-1])])
                np.testing.assert_array_equal(names[i, :, s], want)
    square = 3 * live.size
    assert grid_step_share(mask, rows, block_q, block_k) == steps / square
    assert fetched_tile_share(mask, rows, block_q, block_k) \
        == computed_tile_share(mask, rows, block_q, block_k) \
        == live.sum() / live.size


def test_band_grid_at_the_cells_shape():
    """8,192 rows and a window of 512 at the blocks ``default_blocks``
    takes for it, 512 x 512: 31 of 256 tiles computed and fetched (the
    window needs 0.0606 of the square), two grid steps a sweep in each
    kernel — 0.125 of the square's, where the square grid ran 1.0 of them
    and 225 of a sweep's 256 steps were empty. At 512 x 1024: 23 of 128
    tiles, 88 of 384 steps."""
    from tpudist.ops.attention import BlockMask
    from tpudist.ops.flash_attention import (
        computed_tile_share, default_blocks, fetched_tile_share,
        grid_step_share,
    )

    mask = BlockMask(window=512)
    blocks = default_blocks(8192, 8192, 128, 512)
    assert blocks == (512, 512, True)
    assert default_blocks(8192, 8192, 128) == (512, 1024, True)  # as before
    assert computed_tile_share(mask, 8192, 512, 512) == 31 / 256
    assert fetched_tile_share(mask, 8192, 512, 512) == 31 / 256
    assert grid_step_share(mask, 8192, 512, 512) == 0.125
    assert computed_tile_share(mask, 8192, 512, 1024) == 23 / 128
    assert grid_step_share(mask, 8192, 512, 1024) == 88 / 384 <= 0.25
    need = (512 * 8192 - 512 * 511 / 2) / 8192 ** 2
    assert 0.0605 < need < 31 / 256
    # every other call: the square grid unmasked, else the live steps
    assert grid_step_share(None, 8192, 512, 1024) == 1.0
    for other in (CAUSAL, BlockMask(4), BlockMask(4, 4096)):
        assert grid_step_share(other, 8192, 512, 1024) \
            == computed_tile_share(other, 8192, 512, 1024) < 1.0
