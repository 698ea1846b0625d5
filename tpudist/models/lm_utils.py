"""Family-neutral LM utilities shared by the decoder models (GPT-2, Llama).

No reference counterpart (the reference's model is a CNN,
/root/reference/main.py:40); these serve the LM leg of the BASELINE ladder
for any model exposing the ``return_hidden`` contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn


def stack_layers(params, depth: int, *, prefix: str, dest: str) -> dict:
    """Unrolled ``{prefix}{i}/...`` params → the ``scan_layers`` layout
    (``{dest}/block/...`` with a leading depth axis). One implementation
    for both decoder families (GPT-2: ``h_``/``hs``; Llama:
    ``layer_``/``layers``)."""
    plain = nn.meta.unbox(params)
    found = sorted(k for k in plain if k.startswith(prefix))
    if len(found) != depth:
        raise ValueError(
            f"params hold {len(found)} {prefix}* layers but depth={depth} "
            "was requested — refusing to silently truncate/misstack"
        )
    out = {k: v for k, v in plain.items() if not k.startswith(prefix)}
    out[dest] = {
        "block": jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *(plain[f"{prefix}{i}"] for i in range(depth)),
        )
    }
    return out


def unstack_layers(params, *, prefix: str, dest: str) -> dict:
    """Inverse of :func:`stack_layers` — back to the unrolled layout that
    decode/generation and the HF exporters use."""
    plain = nn.meta.unbox(params)
    block = plain[dest]["block"]
    depth = jax.tree_util.tree_leaves(block)[0].shape[0]
    out = {k: v for k, v in plain.items() if k != dest}
    for i in range(depth):
        out[f"{prefix}{i}"] = jax.tree_util.tree_map(lambda a: a[i], block)
    return out


def lm_head_weight(params):
    """The [V, D] output-projection weight of an LM, whichever family:
    GPT-2's tied ``wte``, Llama's untied ``lm_head`` (falling back to its
    ``embed`` when tied). Accepts boxed (fresh ``model.init``) and unboxed
    (train-state) params."""
    for key in ("lm_head", "wte", "embed"):
        if key in params:
            return nn.meta.unbox(params[key])
    raise ValueError(f"no LM head weight among params: {list(params)}")


def _chunked(h, targets, pos_weight, chunk: int):
    """[B, S, ...] → the scan's chunk-major [S/chunk, B, chunk, ...], the
    tail padded with positions of weight 0."""
    b, s, d = h.shape
    pad = -s % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    pos_weight = jnp.pad(
        jnp.broadcast_to(pos_weight, (b, s)).astype(jnp.float32),
        ((0, 0), (0, pad)),
    )
    nc = (s + pad) // chunk
    hs = h.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    ws = pos_weight.reshape(b, nc, chunk).transpose(1, 0, 2)
    return hs, ts, ws


def _chunk_ce(logits_fn, head_params, hc, tc, wc):
    """One chunk's weighted softmax-CE sum, and its logits."""
    import optax

    logits = logits_fn(head_params, hc)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, tc)
    return jnp.sum(ce * wc), logits


def _ce_sweep(logits_fn, head_params, hs, ts, ws, *, hits: bool = False):
    """The sweep nothing differentiates: one head GEMM a chunk."""

    def body(carry, xs):
        hc, tc, wc = xs
        ce_sum, logits = _chunk_ce(logits_fn, head_params, hc, tc, wc)
        hit_sum = carry[1]
        if hits:
            hit = jnp.argmax(logits, axis=-1) == tc
            hit_sum = hit_sum + jnp.sum(jnp.where(wc > 0, hit, False))
        return (carry[0] + ce_sum, hit_sum), None

    (total, hit_total), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hs, ts, ws),
    )
    return (total, hit_total) if hits else total


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ce_sum(logits_fn, head_params, hs, ts, ws):
    return _ce_sweep(logits_fn, head_params, hs, ts, ws)


def _ce_sum_fwd(logits_fn, head_params, hs, ts, ws):
    """The sweep under differentiation: each chunk's logits are made once
    and serve the loss AND its gradient (``dlogits``, then the ``dh`` and
    ``dW`` GEMMs), so nothing of a chunk outlives its iteration. The
    residuals are the gradients themselves, in the dtypes a transposed
    scan would give them (and a mesh exchange): the head parameters' summed
    in the carry, the hidden chunks' as the scan's output."""

    chunk_grad = jax.value_and_grad(
        functools.partial(_chunk_ce, logits_fn), (0, 1), has_aux=True
    )

    def body(carry, xs):
        total, acc = carry
        (ce_sum, _), (dparams, dhc) = chunk_grad(head_params, *xs)
        acc = jax.tree_util.tree_map(jnp.add, acc, dparams)
        return (total + ce_sum, acc), dhc

    zeros = jax.tree_util.tree_map(jnp.zeros_like, head_params)
    (total, dparams), dhs = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), (hs, ts, ws)
    )
    return total, (dparams, dhs)


def _ce_sum_bwd(logits_fn, grads, g):
    """Scale the stored gradients by the cotangent. A caller that folds its
    normaliser into the weights is differentiated at ``g`` = 1, where this
    rounds nothing a second time."""
    del logits_fn
    dparams, dhs = jax.tree_util.tree_map(
        lambda d: (g * d).astype(d.dtype), grads
    )
    # targets are integers and nothing differentiates the weights
    return dparams, dhs, None, None


_ce_sum.defvjp(_ce_sum_fwd, _ce_sum_bwd)


# one name for the head + loss in the device trace: jvp(loss_head) holds the
# sweep (under differentiation the head's gradient GEMMs too),
# transpose(jvp(loss_head)) what the cotangent still has to do; its fusions
# are the step's largest and carry no module path otherwise
@jax.named_scope("loss_head")
def chunked_head_reduce(
    logits_fn, head_params, h, targets, pos_weight, chunk: int, *,
    hits: bool = False,
):
    """Scan an arbitrary position-wise head over sequence chunks, so live
    logits are bounded by [B, chunk, V], differentiated or not.

    ``logits_fn``: (``head_params``, [B, chunk, D] hidden chunk) → [B,
    chunk, V] logits (any head: a tied-matmul, BERT's transform+decode,
    ...); it closes over no traced value — the head's parameters come in
    through ``head_params``, any pytree. ``h``: [B, S, D]; ``targets``: [B,
    S]; ``pos_weight``: [B, S] or broadcastable, float — a 0/1 mask, or a
    mask over the caller's normaliser (1 / positions) for a mean. Returns
    the weighted softmax-CE sum, plus the argmax-hit count over the
    positions of weight > 0 when ``hits`` (for accuracy-style eval; not
    for differentiation). The one home for the chunked-head skeleton —
    every chunked train loss and eval path rides it, so HBM behavior can't
    diverge between them.

    Under differentiation (a ``jax.custom_vjp``) the sweep takes each
    chunk's gradient while its logits are there: three passes of the
    head's GEMM a chunk (logits, ``dh``, ``dW``), no logits made a second
    time and none kept; the backward only scales the stored gradients by
    the cotangent. A training loss therefore puts its normaliser into
    ``pos_weight``, not behind the sum: the scale then rides in
    ``dlogits`` and each gradient is rounded to its dtype once. Reverse
    mode only, and ``pos_weight`` gets no gradient.
    """
    hs, ts, ws = _chunked(h, targets, pos_weight, chunk)
    if hits:
        return _ce_sweep(logits_fn, head_params, hs, ts, ws, hits=True)
    return _ce_sum(logits_fn, head_params, hs, ts, ws)


def tied_head_logits_fn(head_w, hc):
    """``logits_fn`` for :func:`chunked_head_reduce`: the weight-tied decode
    against a [V, D] table (GPT-2's ``wte``, Llama's head), which is the
    ``head_params``."""
    return jnp.einsum(
        "bcd,vd->bcv", hc, head_w.astype(hc.dtype),
        preferred_element_type=jnp.float32,
    )


def chunked_ce_sum(head_w, h, targets, pos_weight, chunk: int):
    """Weighted softmax-CE sum under the weight-tied head — the decoder
    families' instantiation of :func:`chunked_head_reduce` (training via
    :func:`chunked_lm_forward`, eval via :func:`tpudist.train.evaluate_lm`).
    """
    return chunked_head_reduce(
        tied_head_logits_fn, head_w, h, targets, pos_weight, chunk
    )


def chunked_lm_forward(model, chunk: int = 256, *, moe_stats: bool = False):
    """Fused next-token loss that never materializes the [B,S,V] logits.

    The plain path's fp32 logits are the HBM high-water mark at realistic
    shapes (B=32, S=1024, V=50257 → 6.6 GB) and cap the per-chip batch.
    This forward runs the blocks once, then ``lax.scan``s the weight-tied
    head + softmax-CE over sequence chunks, so live logits are bounded by
    [B, chunk, V]; under differentiation the same sweep takes each chunk's
    gradient while its logits are there (:func:`chunked_head_reduce`), so
    no logits are kept and none are made a second time.

    Works for any model with the ``return_hidden`` contract (GPT-2, Llama),
    including MoE variants: their sowed load-balance losses (the ``losses``
    collection, tpudist.parallel.ep) are collected from the blocks pass and
    added to the chunked CE — the aux loss survives the chunked path.
    Returns a ``forward_loss`` for :func:`tpudist.train.make_train_step`:
    ``(params, batch_stats, batch) -> (loss, batch_stats)``. Mean CE over
    all positions — identical math to ``lm_loss`` on full logits.

    ``moe_stats=True`` (what ``forward_loss.with_moe_stats()`` builds; the
    train step asks for it when telemetry wants the router counters)
    returns ``(loss, (batch_stats, sown))`` with the blocks pass's
    ``moe_stats`` collection.
    """
    if getattr(model, "dropout", 0.0):
        raise ValueError(
            "chunked_lm_forward does not support dropout (the fused path "
            "has no rng stream); use the default forward"
        )
    if getattr(model, "router_jitter", 0.0):
        raise ValueError(
            "chunked_lm_forward does not support router_jitter (the fused "
            "path has no rng stream); use the default forward"
        )
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    wants_aux = bool(getattr(model, "has_aux_loss", False))

    mutable = (["losses"] if wants_aux else []) + (
        ["moe_stats"] if moe_stats else []
    )

    def forward_loss(params, batch_stats, batch):
        tokens = batch["tokens"]
        aux, updates = 0.0, {}
        if mutable:
            hidden, updates = model.apply(
                {"params": params}, tokens, train=True, return_hidden=True,
                mutable=mutable,
            )
            aux = sum(
                jax.tree_util.tree_leaves(updates.get("losses", {})), 0.0
            )
        else:
            hidden = model.apply(
                {"params": params}, tokens, train=True, return_hidden=True
            )
        h = hidden[:, :-1]
        targets = tokens[:, 1:]
        b, s, _ = h.shape
        # the mean's 1 / (b s) rides in the weights (chunked_head_reduce)
        mean_ce = chunked_ce_sum(
            lm_head_weight(params), h, targets, 1.0 / (b * s), chunk
        )
        loss = mean_ce + aux
        if moe_stats:
            return loss, (batch_stats, updates.get("moe_stats", {}))
        return loss, batch_stats

    # the hook make_train_step(fused="ln") uses to re-close this loss over
    # its fused_ln model clone (the closure above captured `model`; a
    # cloned model would otherwise never reach the forward)
    forward_loss.rebuild = lambda m: chunked_lm_forward(
        m, chunk=chunk, moe_stats=moe_stats
    )
    forward_loss.with_moe_stats = lambda: chunked_lm_forward(
        model, chunk=chunk, moe_stats=True
    )
    forward_loss.model = model
    return forward_loss
