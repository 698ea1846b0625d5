"""Named rematerialization policies — the one activation-memory surface.

The framework used to expose remat as an all-or-nothing ``remat: bool`` on
``make_train_step``. At ~1B-param scale on 16 GB HBM that is too blunt: the
right trade is usually *selective* — keep the MXU outputs (cheap to store,
expensive to recompute) and recompute the elementwise tail, or checkpoint at
block boundaries only. This module names the useful points on that curve and
is consumed by every surface that remats:

- ``tpudist.train.make_train_step(remat=...)`` — whole-forward checkpoint
  under the named policy (legacy ``remat=True`` still works ≡ ``"full"``);
- the model zoo's ``remat_policy`` field (GPT-2, Llama) — per-BLOCK
  checkpoint, the memory-discipline workhorse: backward stores only the
  ``depth`` inter-block residual streams and recomputes inside one block at
  a time, so activation HBM drops from O(depth · internals) to
  O(depth · hidden + one block's internals);
- FSDP/ZeRO runs compose through the same two hooks (``parallel/fsdp.py``);
  remat is orthogonal to state sharding.

Policies, by descending aggressiveness (ascending activation HBM):

===============  ============================================================
``save_nothing`` save no intermediates (explicit
                 ``jax.checkpoint_policies.nothing_saveable``) — the floor
``full``         plain ``jax.checkpoint`` (its default is also
                 save-nothing; kept as the legacy ``remat=True`` spelling)
``dots_saveable``save MXU/dot outputs, recompute the elementwise tail —
                 usually the best FLOP/HBM trade on TPU, where recomputing
                 a matmul costs real roofline and recomputing a gelu is free.
                 The attention kernels' output and log-sum-exp are MXU
                 outputs too (``KERNEL_RESIDUALS``): kept, so a block's
                 backward does not launch the forward kernel again
``none``         no checkpointing — store everything (fastest, hungriest)
===============  ============================================================

Measured/contracted ordering of live activation bytes:
``save_nothing ≤ full ≤ dots_saveable ≤ none``
(asserted against XLA's compiled memory analysis in
``tests/test_sharded_optim.py``).

**Kernel residuals.** A Pallas attention kernel is a ``pallas_call`` inside
a ``jax.custom_vjp``: it holds no ``dot_general`` a policy could see, and a
block's backward would launch the forward kernel a second time only to get
the residuals ``o`` and ``lse`` back. So the forward rules of
``ops/flash_attention.py`` and ``ops/vmem_attention.py`` pass the two
through ``jax.ad_checkpoint.checkpoint_name`` under the names in
``KERNEL_RESIDUALS``, and ``dots_saveable`` keeps those names beside the
dots: ``B·H·S·Dv`` in the compute dtype + ``B·H·S`` float32 a layer, the
size of one projection output. ``full`` and ``save_nothing`` keep nothing;
outside a ``jax.checkpoint`` a name lowers to nothing. The static counter
that says it engaged is :func:`forward_attention_kernels`: ``depth`` in a
training step under ``dots_saveable`` or without recomputation, ``2·depth``
under ``full`` / ``save_nothing`` (``tests/test_remat_attention.py``).
The chunked state-space scan (``ops/ssd.py``) names its output and every
chunk's starting state alike (``ssd_out``, ``ssd_states``: ``B·H·S·P`` in
the compute dtype + ``B·chunks·H·N·P`` float32 a layer); its counter is
:func:`forward_kernels` of ``ssd.py``, and its backward kernel's is
:func:`pallas_kernels` of ``ssd.py`` and ``_bwd_kernel``: one a layer in a
training step, under every policy.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

# The attention kernels' forward rules name their output and its row
# log-sum-exp so (``checkpoint_name``), the state-space scan's
# (``ops/ssd.py``) its output and every chunk's starting state;
# ``dots_saveable`` keeps them all.
KERNEL_RESIDUALS = ("attn_out", "attn_lse", "ssd_out", "ssd_states")

# name -> jax.checkpoint policy callable (None = jax.checkpoint's default,
# which saves nothing). "none" is absent on purpose: it means "do not wrap".
_POLICIES: dict[str, Any] = {
    "full": None,
    "save_nothing": jax.checkpoint_policies.nothing_saveable,
    "dots_saveable": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_saveable,
        jax.checkpoint_policies.save_only_these_names(*KERNEL_RESIDUALS),
    ),
}

POLICY_NAMES = ("none", "full", "dots_saveable", "save_nothing")

_ATTENTION_KERNEL_FILES = ("flash_attention.py", "vmem_attention.py")


def pallas_kernels(jaxpr, files: tuple[str, ...], prefix: str) -> int:
    """Static counter: the ``pallas_call``s of a traced program (a
    ``ClosedJaxpr`` or ``Jaxpr``, e.g. ``jitted.trace(*args).jaxpr``) whose
    kernel function's name starts with ``prefix`` and whose file is one of
    ``files`` (under ``tpudist/ops/``)."""
    n = 0
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            kernel = eqn.params["jaxpr"].debug_info
            n += (kernel.func_name.startswith(prefix)
                  and kernel.func_filename.endswith(files))
            continue  # a kernel's body holds no kernel
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += pallas_kernels(sub, files, prefix)
    return n


def forward_kernels(jaxpr, files: tuple[str, ...]) -> int:
    """:func:`pallas_kernels` of the FORWARD kernels, ``_fwd_kernel*``."""
    return pallas_kernels(jaxpr, files, "_fwd_kernel")


def forward_attention_kernels(jaxpr) -> int:
    """:func:`forward_kernels` of ``ops/flash_attention.py`` and
    ``ops/vmem_attention.py``. A training step holds ``depth`` of them
    where the backward gets ``o`` and ``lse`` from what the policy kept,
    ``2·depth`` where each block's backward launches the forward again."""
    return forward_kernels(jaxpr, _ATTENTION_KERNEL_FILES)


def resolve(policy: str | bool | None | Callable):
    """Normalize a remat policy argument.

    Returns ``None`` for "no remat" (``False``/``None``/``"none"``), else a
    dict of kwargs for ``jax.checkpoint``/``nn.remat``. Accepts the legacy
    bool (``True`` ≡ ``"full"``), a policy name, or a raw
    ``jax.checkpoint_policies`` callable (the escape hatch for custom
    ``save_only_these_names`` policies).
    """
    if policy in (False, None, "none"):
        return None
    if policy is True:
        policy = "full"
    if callable(policy):
        return {"policy": policy}
    try:
        fn = _POLICIES[policy]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown remat policy {policy!r}; expected one of "
            f"{POLICY_NAMES}, a bool, or a jax.checkpoint_policies callable"
        ) from None
    return {} if fn is None else {"policy": fn}


def checkpoint(fn: Callable, policy: str | bool | None | Callable) -> Callable:
    """``jax.checkpoint(fn)`` under the named policy; ``fn`` unchanged for
    ``"none"``/``False``/``None``. The function-level hook
    (``make_train_step``'s whole-forward remat)."""
    kwargs = resolve(policy)
    if kwargs is None:
        return fn
    return jax.checkpoint(fn, **kwargs)


def remat_module(module_cls, policy: str | bool | None | Callable,
                 **nn_remat_kwargs):
    """``nn.remat(module_cls)`` under the named policy; the class unchanged
    for ``"none"``. The module-level hook (the model zoo's per-block
    ``remat_policy`` field)."""
    from flax import linen as nn

    kwargs = resolve(policy)
    if kwargs is None:
        return module_cls
    return nn.remat(module_cls, **kwargs, **nn_remat_kwargs)
