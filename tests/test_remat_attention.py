"""Per-block recomputation and the attention kernels (``tpudist/remat.py``
``KERNEL_RESIDUALS``): under ``dots_saveable`` a block's backward finds the
kernel's output and log-sum-exp kept and does not launch the forward kernel
again; ``full`` and ``save_nothing`` keep nothing and launch it twice.

The static counter is ``remat.forward_attention_kernels`` over a traced
gradient. CPU, interpret mode: the vmem backward is a ``pallas_call`` there
too and the flash backward is the scan, so only the kernel's own name tells
a forward launch from a backward one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import remat
from tpudist.ops.flash_attention import flash_attention
from tpudist.ops.vmem_attention import vmem_attention

KERNELS = {"flash": flash_attention, "vmem": vmem_attention}
BLOCKS = 2
# forward kernels in the gradient of BLOCKS checkpointed blocks
FORWARD_KERNELS = {"none": BLOCKS, "dots_saveable": BLOCKS,
                   "full": 2 * BLOCKS, "save_nothing": 2 * BLOCKS}


def _loss(kernel, policy):
    """Two blocks of projection -> attention kernel -> output projection,
    each under ``remat.checkpoint(policy)`` as a model's blocks are."""
    attend = KERNELS[kernel]

    def block(x, w):
        b, s, _ = x.shape
        q, k, v = (jnp.dot(x, w[i]).reshape(b, s, 2, 64) for i in range(3))
        o = attend(q, k, v, causal=True)
        return x + jnp.tanh(jnp.dot(o.reshape(b, s, -1), w[3]))

    block = remat.checkpoint(block, policy)

    def loss(x, ws):
        for w in ws:
            x = block(x, w)
        return jnp.sum(x * x)

    return loss


@pytest.fixture(scope="module")
def operands():
    kx, kw = jax.random.split(jax.random.key(3))
    x = jax.random.normal(kx, (2, 128, 128), jnp.float32)
    ws = 0.05 * jax.random.normal(kw, (BLOCKS, 4, 128, 128), jnp.float32)
    return x, ws


@pytest.fixture(scope="module")
def plain_gradients(operands):
    return {kernel: jax.grad(_loss(kernel, "none"), argnums=(0, 1))(*operands)
            for kernel in KERNELS}


@pytest.mark.parametrize("policy", remat.POLICY_NAMES)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_forward_kernels_in_a_two_block_gradient(kernel, policy, operands):
    traced = jax.make_jaxpr(
        jax.grad(_loss(kernel, policy), argnums=(0, 1)))(*operands)
    assert (remat.forward_attention_kernels(traced)
            == FORWARD_KERNELS[policy])


@pytest.mark.parametrize("policy", ["dots_saveable", "full", "save_nothing"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_gradients_under_every_policy_are_the_plain_ones(
        kernel, policy, operands, plain_gradients):
    """The kept ``o`` is the value a second launch would have produced:
    equal to the digit, not to a tolerance."""
    got = jax.grad(_loss(kernel, policy), argnums=(0, 1))(*operands)
    for g, want in zip(got, plain_gradients[kernel]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


def test_the_counter_takes_no_other_kernel_for_an_attention_forward():
    """LayerNorm's kernel is a ``_fwd_kernel`` too: the file tells them
    apart; a program without attention counts nought."""
    from tpudist.ops.layernorm import fused_layernorm

    x = jnp.ones((4, 128), jnp.float32)
    scale = bias = jnp.ones((128,), jnp.float32)
    traced = jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(fused_layernorm(x, scale, bias))))(x)
    assert "pallas_call" in str(traced)
    assert remat.forward_attention_kernels(traced) == 0


def _tiny_model_gradient(family, impl, policy):
    from tpudist.models.lm_utils import chunked_lm_forward

    if family == "kanana":
        from test_kanana import tiny
    else:
        from test_zaya import tiny
    model = tiny(attn_impl=impl, remat_policy=policy)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens))["params"]
    forward = chunked_lm_forward(model, chunk=8)
    loss = lambda p, t: forward(p, {}, {"tokens": t})[0]
    return model.depth, jax.make_jaxpr(jax.grad(loss))(params, tokens)


@pytest.mark.parametrize("policy, launches", [
    ("dots_saveable", 1), ("full", 2), (None, 1)])
@pytest.mark.parametrize("family, impl", [
    ("kanana", "flash"), ("zaya", "flash"), ("zaya", "vmem")])
def test_expert_models_run_one_forward_kernel_a_layer(
        family, impl, policy, launches):
    """The two expert cells' recipes (``remat_policy: dots_saveable`` around
    the flash kernel) at the tiny sizes of their own test files: one
    forward launch a layer, as without recomputation; ``full`` shows the
    counter can read two."""
    depth, traced = _tiny_model_gradient(family, impl, policy)
    assert remat.forward_attention_kernels(traced) == launches * depth
