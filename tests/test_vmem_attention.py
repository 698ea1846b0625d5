"""vmem attention (tpudist/ops/vmem_attention.py) vs the XLA oracle:
forward and gradients, aligned and ragged (ViT-shaped) sequences, causal
and bidirectional, and the multi_head_attention auto routing."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.attention import dot_product_attention, multi_head_attention
from tpudist.ops.vmem_attention import computed_tile_share, vmem_attention


def _qkv(b, s, h, d, seed=0, dtype=jnp.float32):
    rng = np.random.Generator(np.random.PCG64(seed))
    return tuple(
        jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal,s,dtype", [
    (False, 256, jnp.float32),
    (True, 128, jnp.float32),    # one query block: the whole tile, masked
    (True, 256, jnp.float32),    # from here on the causal path runs by blocks
    (True, 512, jnp.float32),
    (True, 1024, jnp.float32),
    (True, 1024, jnp.bfloat16),
])
def test_matches_oracle_aligned(causal, s, dtype, kernel_parity):
    q, k, v = _qkv(2, s, 2, 64, seed=1, dtype=dtype)
    out = vmem_attention(q, k, v, causal=causal)
    ref = dot_product_attention(q, k, v, causal=causal)
    kernel_parity(out, ref)


def test_matches_oracle_ragged_vit_shape():
    """S=197 (ViT-B/16): padded to 256 internally, padded keys masked."""
    q, k, v = _qkv(2, 197, 3, 64, seed=2)
    out = vmem_attention(q, k, v, causal=False)
    ref = dot_product_attention(q, k, v, causal=False)
    assert out.shape == ref.shape == (2, 197, 3, 64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_kv_len_masks_padded_keys():
    """Explicit kv_len ≡ slicing the keys: padded K/V rows are inert."""
    q, k, v = _qkv(1, 128, 2, 64, seed=3)
    ref = dot_product_attention(q, k[:, :100], v[:, :100], causal=False)
    out = vmem_attention(q, k, v, causal=False, kv_len=100)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal,s,dtype", [
    (True, 256, jnp.float32),
    (False, 197, jnp.float32),
    (True, 512, jnp.float32),
    (True, 1024, jnp.float32),
    (True, 1024, jnp.bfloat16),
    (True, 200, jnp.float32),   # padded to 256 and 640: the padding falls
    (True, 600, jnp.float32),   # inside the last block's diagonal tile
])
def test_grads_match_oracle(causal, s, dtype, kernel_parity):
    q, k, v = _qkv(1, s, 2, 64, seed=4, dtype=dtype)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g_vmem = jax.grad(
        functools.partial(loss, functools.partial(vmem_attention, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        functools.partial(
            loss, functools.partial(dot_product_attention, causal=causal)
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g_vmem, g_ref):
        if dtype == jnp.bfloat16:
            kernel_parity(a, b)
        else:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5,
                err_msg=name,
            )


def test_refuses_long_sequences():
    q, k, v = _qkv(1, 2048, 1, 64, seed=5)
    with pytest.raises(NotImplementedError, match="flash"):
        vmem_attention(q, k, v)


def test_auto_routes_vmem_then_flash():
    """auto: short S runs the vmem kernel; long S falls through to
    flash/XLA without error."""
    q, k, v = _qkv(1, 256, 2, 64, seed=6)
    out = multi_head_attention(q, k, v, causal=True, impl="auto")
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    # long S: must not raise (flash handles 128-aligned 2048)
    q2, k2, v2 = _qkv(1, 2048, 1, 64, seed=7)
    out2 = multi_head_attention(q2, k2, v2, causal=True, impl="auto")
    assert out2.shape == q2.shape


def test_multi_head_attention_kv_len_plumbed():
    """kv_len reaches the kernel through the dispatcher, and the dense path
    builds the equivalent mask — all impls agree with sliced-K oracle."""
    q, k, v = _qkv(1, 128, 2, 64, seed=8)
    ref = dot_product_attention(q, k[:, :90], v[:, :90], causal=False)
    for impl in ("xla", "vmem", "auto"):
        out = multi_head_attention(q, k, v, impl=impl, kv_len=90)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=impl,
        )
    with pytest.raises(ValueError, match="not both"):
        multi_head_attention(
            q, k, v, impl="xla", kv_len=90,
            mask=jnp.ones((1, 1, 1, 128), bool),
        )


def test_gpt2_model_vmem_matches_xla():
    """Model-level: the cells' attn_impl='vmem' GPT-2 computes the same
    function as the XLA oracle (same params, same tokens, same loss)."""
    import optax

    from tpudist import mesh as mesh_lib
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, lm_loss, make_train_step

    mesh = mesh_lib.create_mesh()
    rng = np.random.Generator(np.random.PCG64(9))
    tokens = rng.integers(0, 97, (8, 128)).astype(np.int32)
    losses = {}
    for impl in ("xla", "vmem"):
        model = GPT2(vocab_size=97, max_seq_len=128, hidden_dim=32, depth=2,
                     num_heads=4, attn_impl=impl)
        tx = optax.adam(1e-3)
        state = create_train_state(
            model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens",
        )
        _, metrics = step(state, {"tokens": tokens})
        losses[impl] = float(metrics["loss"])
    assert abs(losses["vmem"] - losses["xla"]) < 2e-5, losses


def test_vit_model_vmem_matches_xla():
    """ViT at its ragged S (4-pixel patches on 32x32 → 65 tokens) through
    the padded+masked kernel equals the XLA path."""
    from tpudist.models import vit_b16

    rng = np.random.Generator(np.random.PCG64(10))
    images = jnp.asarray(rng.random((2, 32, 32, 3)), jnp.float32)
    outs = {}
    for impl in ("xla", "vmem"):
        model = vit_b16(patch_size=4, depth=2, attn_impl=impl)
        variables = model.init(jax.random.key(0), images[:1], train=False)
        outs[impl] = np.asarray(
            model.apply(variables, images, train=False)
        )
    np.testing.assert_allclose(outs["vmem"], outs["xla"], rtol=2e-4, atol=2e-4)


def test_multi_head_attention_kv_len_flash_impl():
    """impl='flash' + kv_len stays on the kernel path (native in-kernel
    masking, no dense fallback) and matches the sliced-K oracle."""
    import warnings

    q, k, v = _qkv(1, 256, 2, 64, seed=11)
    ref = dot_product_attention(q, k[:, :130], v[:, :130], causal=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a fallback warning = test failure
        out = multi_head_attention(q, k, v, impl="flash", kv_len=130)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("h,h_kv,s", [
    (4, 2, 256), (6, 2, 256), (4, 1, 256),
    (8, 2, 512),  # dk/dv sum over the group AND over four query blocks
])
def test_gqa_matches_repeated_kv(h, h_kv, s):
    """Grouped K/V read natively (no repeat in HBM) equals the repeat-then-
    MHA oracle — forward and all grads, including the f32-accumulated
    dk/dv that sum each query group's contributions."""
    rng = np.random.Generator(np.random.PCG64(30 + h * 10 + h_kv))
    b, d = 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    rep = h // h_kv

    def oracle(q, k, v):
        return dot_product_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            causal=True,
        )

    out = vmem_attention(q, k, v, causal=True)
    ref = oracle(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_vmem = jax.grad(
        loss(lambda q, k, v: vmem_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for name, a, bb in zip("dq dk dv".split(), g_vmem, g_ref):
        assert a.shape == bb.shape, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=1e-4, atol=1e-4, err_msg=name
        )


def test_gqa_through_dispatcher_and_fallback():
    """multi_head_attention takes grouped K/V on every impl: vmem reads it
    natively; the dense fallback repeats internally."""
    rng = np.random.Generator(np.random.PCG64(33))
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
    ref = dot_product_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True
    )
    for impl in ("vmem", "auto", "xla"):
        out = multi_head_attention(q, k, v, causal=True, impl=impl)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=impl,
        )


@pytest.mark.parametrize("seq", [128, 512], ids=["one_block", "four_blocks"])
def test_mesh_shard_map_wrap_matches_unwrapped(seq):
    """multi_head_attention(mesh=...) runs the kernel per-shard inside
    shard_map (the multi-chip Pallas path: pallas_call has no GSPMD rule);
    the wrap must be loss-exact vs the unwrapped single-program path."""
    import optax

    from tpudist import mesh as mesh_lib
    from tpudist.models.gpt2 import GPT2
    from tpudist.train import create_train_state, lm_loss, make_train_step

    mesh = mesh_lib.create_mesh()
    rng = np.random.Generator(np.random.PCG64(40))
    tokens = rng.integers(0, 97, (8, seq)).astype(np.int32)
    losses = {}
    for wrapped in (False, True):
        model = GPT2(vocab_size=97, max_seq_len=seq, hidden_dim=32, depth=2,
                     num_heads=4, attn_impl="vmem",
                     mesh=mesh if wrapped else None)
        tx = optax.adam(1e-3)
        state = create_train_state(
            model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
        )
        step = make_train_step(
            model, tx, mesh, loss_fn=lm_loss, input_key="tokens",
            label_key="tokens",
        )
        _, metrics = step(state, {"tokens": tokens})
        losses[wrapped] = float(metrics["loss"])
    assert abs(losses[True] - losses[False]) < 2e-5, losses


def _score_elements(fn, *args):
    """Elements of q·kᵀ output the traced program computes: every
    ``dot_general`` whose result is not D wide, kernels' bodies included."""
    d = args[0].shape[-1]

    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                shape = eqn.outvars[0].aval.shape
                n += int(np.prod(shape)) if shape[-1] != d else 0
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += walk(sub)
        return n

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("s,causal,share", [
    (1024, True, 0.5625),  # the benchmark's GPT-2 cells: eight blocks of 128
    (512, True, 0.625),
    (600, True, 0.6),      # padded to 640: five blocks
    (200, True, 0.75),     # padded to 256: two blocks
    (128, True, 1.0),      # one block is the whole tile
    (1024, False, 1.0),
    (197, False, 1.0),
])
def test_computed_tile_share_is_what_the_kernel_traces(s, causal, share):
    """The counter of the causal mechanism: static per shape, and the
    forward kernel's own q·kᵀ products cover exactly that share of the
    padded square."""
    s_pad = s + (-s % 128)
    assert computed_tile_share(s_pad, causal) == share
    q, k, v = _qkv(1, s, 1, 64, seed=12)
    scores = _score_elements(
        functools.partial(vmem_attention, causal=causal), q, k, v
    )
    assert scores == share * s_pad * s_pad


@pytest.mark.parametrize("causal", [True, False], ids=["blocked", "whole"])
def test_layers_share_one_kernel_trace_a_direction(causal):
    """A model's layers call the kernel with the same shapes: the
    ``pallas_call`` of the second to the last layer is the first one's —
    the same kernel jaxpr, so nothing is traced again and JAX's lowering
    cache lowers it to Mosaic once a step — and each still sits directly
    under its own layer's scope, where a trace reader looks for it."""
    x = _qkv(1, 384, 3, 32, seed=13)[0]  # a shape no other test traces

    def stack(x):
        for layer in range(3):
            with jax.named_scope(f"h_{layer}"):
                x = x + vmem_attention(x, x, x, causal=causal)
        return x.sum()

    kernels = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append((id(eqn.params["jaxpr"]),
                                str(eqn.source_info.name_stack)))
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(stack))(x).jaxpr)
    assert len(kernels) == 6  # forward and backward of three layers
    assert len({body for body, _ in kernels}) == 2
    for layer in range(3):
        scopes = [scope for _, scope in kernels if f"h_{layer}" in scope]
        assert len(scopes) == 2, kernels
        assert not any("jit(" in scope or "_vmem" in scope for scope in scopes)
