"""Speculative decoding: acceptance-rejection sampling over a draft
window, preserving the target distribution EXACTLY.

The engine's speculative tick (``tpudist.serve.engine``) runs, per live
slot: K cheap draft-model steps proposing tokens ``d_1..d_K``, then ONE
bulk target pass scoring the window ``[t_last, d_1..d_K]`` — K+1 rows of
target logits from a single weight sweep (the decode cost that matters
is HBM bytes per sequential pass). This module decides
what to EMIT from those two logit sets.

The acceptance identity (Leviathan et al. / Chen et al.): draft token
``d_i`` (sampled from the draft's warped distribution ``q_i``) is
accepted with probability ``min(1, p_i(d_i) / q_i(d_i))`` where ``p_i``
is the target's warped distribution at that position; at the FIRST
rejection the emitted token is drawn from the residual distribution
``norm(max(p_i - q_i, 0))``; if all K drafts are accepted a BONUS token
is drawn from ``p_{K+1}`` (the verify pass's last row — free, its logits
already exist). Marginally every emitted token is distributed exactly as
``p`` — speculation changes throughput, never the output distribution.

Both ``p`` and ``q`` here are the WARPED per-row distributions
(temperature → top_k → top_p) via :func:`tpudist.generate.per_row_log_probs`,
which shares its filter math with :func:`tpudist.generate.sample_logits_per_row`
— the distribution the draft was ACTUALLY sampled from, not the raw
softmax. Greedy rows (``temperature == 0``) need no special case: their
warped distribution is a point mass at the argmax, so the ratio test
accepts iff the draft matched the target argmax and the residual/bonus
is the target argmax itself — which is what makes greedy speculative
output token-identical to the non-speculative engine (pinned in
tests/test_serve_spec.py).

RNG discipline: the engine derives one key per (request, cursor) and
this module folds purpose salts into it — draft steps use salts
``0..K-1`` at the engine layer, acceptance uniforms and the residual
draw use the disjoint salts below. Cursors are strictly increasing and
replay-stable, so a preempted request re-draws the same stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpudist.generate import per_row_log_probs

# purpose salts folded into the engine's per-(request, cursor) key; the
# engine folds 0..K-1 for the K draft sampling steps, so these live far
# above any sane K
SALT_ACCEPT = 1 << 20
SALT_RESIDUAL = (1 << 20) + 1


def _rep(a, n: int):
    """Per-row sampling params ``[B]`` → per-verify-row ``[B * n]``
    (row-major, matching ``logits.reshape(b * n, v)``)."""
    return jnp.repeat(jnp.asarray(a), n, axis=0)


def speculative_accept(t_logits, d_logits, d_toks, n_spec, keys, *,
                       temperature, top_k, top_p):
    """Accept/reject a draft window against the target's verify logits.

    Args:
      t_logits: ``[B, K+1, V]`` target logits — row ``i`` is the target
        distribution at the position draft ``d_{i+1}`` was proposed for
        (row ``K`` scores the bonus position after a fully-accepted
        window).
      d_logits: ``[B, K, V]`` draft logits the proposals were sampled
        from (raw — warped here with the same per-row params).
      d_toks: ``[B, K]`` proposed draft tokens.
      n_spec: ``[B]`` int32 — per-row cap on how many drafts are ELIGIBLE
        (sequence-end / budget clamp from the engine; rows beyond it are
        treated as rejected without consuming randomness semantics).
      keys: ``[B]`` per-(request, cursor) rng keys.
      temperature / top_k / top_p: ``[B]`` per-row sampling params.

    Returns ``(emit [B, K+1] int32, n_emit [B] int32)``: the emitted
    tokens (accepted prefix + one correction/bonus token; positions past
    ``n_emit`` are zero-padded) with ``1 <= n_emit <= K+1``.
    """
    b, k1, v = t_logits.shape
    kk = k1 - 1
    n_spec = jnp.asarray(n_spec, jnp.int32)
    logp = per_row_log_probs(
        t_logits.reshape(b * k1, v),
        temperature=_rep(temperature, k1),
        top_k=_rep(top_k, k1),
        top_p=_rep(top_p, k1),
    ).reshape(b, k1, v)
    if kk:
        logq = per_row_log_probs(
            d_logits.reshape(b * kk, v),
            temperature=_rep(temperature, kk),
            top_k=_rep(top_k, kk),
            top_p=_rep(top_p, kk),
        ).reshape(b, kk, v)
    u_keys = jax.vmap(lambda key: jax.random.fold_in(key, SALT_ACCEPT))(keys)
    us = jax.vmap(lambda key: jax.random.uniform(key, (max(kk, 1),)))(u_keys)

    # sequential accept scan, unrolled (K is small and static): a draft is
    # kept iff every earlier draft was kept AND its own ratio test passes
    acc = jnp.ones(b, bool)
    n_acc = jnp.zeros(b, jnp.int32)
    for i in range(kk):
        d_i = d_toks[:, i][:, None]
        lp = jnp.take_along_axis(logp[:, i], d_i, axis=-1)[:, 0]
        lq = jnp.take_along_axis(logq[:, i], d_i, axis=-1)[:, 0]
        # min(1, p/q) as exp(min(0, lp - lq)); lp=-inf → ratio 0 (reject),
        # lq=-inf (can't arise from a q-sampled token; ties aside) → NaN
        # or ratio 1, and u < NaN rejects — both safe
        ratio = jnp.exp(jnp.clip(lp - lq, None, 0.0))
        ok = (us[:, i] < ratio) & (i < n_spec) & acc
        n_acc = n_acc + ok
        acc = acc & ok

    # first-rejection (or bonus) position m = n_acc: correction token from
    # the residual norm(max(p_m - q_m, 0)). Where no proposal existed
    # (m == n_spec: the bonus row, a sequence-end clamp, or K == 0) q is
    # zero and the residual is p_m itself — the plain target draw.
    m = n_acc
    logp_m = jnp.take_along_axis(logp, m[:, None, None], axis=1)[:, 0]
    p_m = jnp.exp(logp_m)  # [B, V]
    if kk:
        mi = jnp.minimum(m, kk - 1)[:, None, None]
        q_m = jnp.exp(jnp.take_along_axis(logq, mi, axis=1)[:, 0])
        q_m = jnp.where((m < n_spec)[:, None], q_m, 0.0)
    else:
        q_m = jnp.zeros_like(p_m)
    residual = jnp.maximum(p_m - q_m, 0.0)
    rsum = jnp.sum(residual, axis=-1, keepdims=True)
    # all-zero residual (p <= q pointwise — only float rounding can get
    # here, since exact p == q never rejects): fall back to p itself
    res = jnp.where(rsum > 0.0, residual / rsum, p_m)
    corr_keys = jax.vmap(
        lambda key: jax.random.fold_in(key, SALT_RESIDUAL)
    )(keys)
    corr = jax.vmap(jax.random.categorical)(corr_keys, jnp.log(res))

    cols = jnp.arange(k1)[None, :]
    if kk:
        d_pad = jnp.concatenate(
            [d_toks, jnp.zeros((b, 1), d_toks.dtype)], axis=1
        )
    else:
        d_pad = jnp.zeros((b, k1), jnp.int32)
    emit = jnp.where(cols < m[:, None], d_pad, 0)
    emit = jnp.where(cols == m[:, None], corr[:, None], emit)
    return emit.astype(jnp.int32), (m + 1).astype(jnp.int32)


def early_exit_draft(model, params, depth: int):
    """A draft that is the target's own SHALLOW PREFIX: same embeddings,
    first ``depth`` transformer blocks, and final norm/head, sharing the
    target's parameter arrays (zero extra weight HBM — the draft's only
    footprint is its KV cache). The natural stand-in before a distilled
    draft exists: early-exit logits correlate with the full model's, and
    the correlation (= acceptance rate) is MEASURED by the engine's
    telemetry, never assumed.

    Works for the unrolled GPT-2 (``h_{i}`` blocks, ``wte``/``wpe``/
    ``ln_f``) and Llama (``layer_{i}``, ``embed``/``norm``[/``lm_head``])
    families. Returns ``(draft_model, draft_params)``.
    """
    if not 1 <= depth < model.depth:
        raise ValueError(
            f"draft depth {depth} outside [1, {model.depth}) of the target"
        )
    draft = model.clone(depth=depth)
    if "wte" in params:  # GPT-2 family
        block, shared = "h_{}", ("wte", "wpe", "ln_f")
    elif "embed" in params:  # Llama family
        block, shared = "layer_{}", ("embed", "norm", "lm_head")
    else:
        raise ValueError(
            f"unrecognized param layout {sorted(params)[:4]}...; "
            "early_exit_draft knows the GPT-2 and Llama families"
        )
    if block.format(0) not in params:
        raise ValueError(
            f"early_exit_draft needs unrolled per-layer params (missing "
            f"{block.format(0)!r}); scanned/stacked layouts aren't "
            "sliceable by depth"
        )
    dp = {k: params[k] for k in shared if k in params}
    for i in range(depth):
        dp[block.format(i)] = params[block.format(i)]
    return draft, dp


def cache_bytes(model, rows: int, *, tensor_world: int = 1) -> int:
    """KV-cache bytes ``model.init_cache(rows)`` would allocate (4-D K/V
    buffers only, via ``eval_shape`` — nothing materializes). The number
    the equal-HBM A/B and SERVING.md's "cache sizing with a draft" use:
    a speculative engine pays this for its draft on TOP of the target
    pool, so at fixed HBM the draft cache comes out of the target's block
    budget (:func:`tpudist.serve.blocks.draft_equivalent_blocks`).

    ``tensor_world``: PER-CHIP bytes on a tensor-sharded engine
    (``ServeEngine(mesh=...)``) — the 4-D buffers shard exactly on the
    KV-head dim, so each chip holds ``1/T`` of every buffer (the engine's
    head-divisibility refusal guarantees the split is even)."""
    tree = jax.eval_shape(lambda: model.init_cache(rows))
    total = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if len(leaf.shape) == 4
    )
    return total // max(int(tensor_world), 1)
