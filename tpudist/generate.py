"""Autoregressive text generation with a KV cache.

No reference counterpart (the reference is a training-only CNN script); this
is the inference half every LM framework needs. TPU-first design: the whole
generation — prompt prefill and sampling — is ONE jit-compiled program.
Prefill is ONE bulk decode pass over the whole prompt (causal within the
chunk); sampling is a ``lax.scan`` of single-token decode steps. Both run
against a static-shaped head-major ``[B, H, max_seq_len, dh]`` KV cache
(:mod:`tpudist.ops.decode` — head-major so the fused decode kernel DMAs
each head's panel contiguously), so the cache never reallocates and the
compile count stays bounded: prompts are padded to power-of-two BUCKETS
(:func:`bucket_length`) with the true length a traced scalar, so repeated
calls with varying prompt lengths share a handful of compiled programs
instead of one per length.

The continuous-batching serving engine (:mod:`tpudist.serve`) builds on the
pieces here: :func:`zero_cache` allocates its slot pool,
:func:`sample_logits_per_row` is its vectorized per-slot sampler, and
:func:`eos_retire` is the ONE stop rule shared between :func:`generate`'s
in-scan masking and the engine's per-slot retirement.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def bucket_length(n: int, cap: int | None = None, *, minimum: int = 8) -> int:
    """Smallest power of two >= ``n`` (floored at ``minimum``), capped at
    ``cap`` — the shared prompt-padding rule of :func:`generate` and the
    serving prefiller (:mod:`tpudist.serve.prefill`). Bucketing is what
    keeps XLA's compile cache bounded under mixed-length traffic: every
    prompt length lands on one of ~log2(max_seq_len) shapes."""
    if n > (cap if cap is not None else n):
        raise ValueError(f"length {n} exceeds the bucket cap {cap}")
    b = minimum
    while b < n:
        b *= 2
    return min(b, cap) if cap is not None else b


def _nucleus_threshold_from_probs(sorted_desc, probs, top_p):
    """Nucleus (top-p) threshold over DESCENDING-sorted logits with their
    probabilities supplied by the caller (the per-row sampler's candidate
    subset carries full-vocab or filtered-subset probabilities depending
    on the row's filter mix): keep tokens whose EXCLUSIVE cumulative
    probability is < p (the most likely token always survives); the
    threshold is the last kept token's logit. ``top_p`` is a python float
    (scalar sampling) or a ``[B, 1]`` array (the per-row sampler)."""
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    keep = exclusive_cum < top_p
    # the docstring's guarantee, unconditionally: at top_p <= 0.0 (or
    # denormal-tiny p) the exclusive-cum test keeps NOTHING, the
    # threshold becomes +inf and categorical samples over all -inf
    # logits — undefined output. HF guards the same edge with
    # min_tokens_to_keep=1; position 0 of the descending sort IS the
    # most likely token, so force-keep it.
    keep = keep.at[..., 0].set(True)
    return jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )


def _nucleus_threshold(sorted_desc, top_p):
    """The scalar-path flavor: probabilities are the softmax of the
    (already filtered) sorted values themselves."""
    return _nucleus_threshold_from_probs(
        sorted_desc, jax.nn.softmax(sorted_desc, axis=-1), top_p
    )


def sample_logits(logits, rng, *, temperature: float = 1.0,
                  top_k: int | None = None, top_p: float | None = None):
    """One sampling step over ``[B, V]`` logits. ``temperature=0`` is
    greedy; ``top_k`` keeps the k most likely tokens (exactly k: on an
    exact tie at the k-th value the later tied ids are dropped, where a
    threshold formulation would keep them — see the inline note); ``top_p``
    keeps the smallest set of tokens whose probabilities sum to >= p
    (nucleus sampling). Filters compose in the HF order: temperature →
    top_k → top_p."""
    if temperature == 0.0:
        # top_k(1) indices, not jnp.argmax: same first-occurrence winner;
        # argmax lowers to a slower full-vocab reduction than the top-k
        # kernel (no ledger line: not measured in a cell)
        return jax.lax.top_k(logits, 1)[1][:, 0].astype(jnp.int32)
    logits = logits / temperature

    if top_k is not None:
        # sample IN THE TOP-K SUBSET: categorical over the k kept values
        # and map the winner back through the top-k indices. The
        # full-vocab formulation pays a [B, V] gumbel + reduction per
        # token; the subset pays it on [B, k]. Tie semantics: EXACTLY k ids are
        # candidates — ids tied with the k-th value beyond the k-th slot
        # are dropped (a `logits < kth` threshold, like HF's warper,
        # keeps every tied id). Tied ids carry equal probability, so this
        # only narrows which of the exchangeable tied ids can appear; for
        # float logits ties have measure zero.
        k = min(top_k, logits.shape[-1])  # clamp k > vocab, like HF/torch
        topk_vals, topk_idx = jax.lax.top_k(logits, k)  # [B, k], sorted
        if top_p is not None and top_p < 1.0:
            # composed filters: after the top-k cut only the k kept logits
            # carry probability mass, so the nucleus threshold over the
            # full filtered vocab equals the one over the (already sorted)
            # top-k values — no [B, V] sort
            topk_vals = jnp.where(
                topk_vals < _nucleus_threshold(topk_vals, top_p),
                -jnp.inf, topk_vals,
            )
        choice = jax.random.categorical(rng, topk_vals, axis=-1)
        return jnp.take_along_axis(
            topk_idx, choice[:, None], axis=-1
        )[:, 0].astype(jnp.int32)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        logits = jnp.where(
            logits < _nucleus_threshold(sorted_logits, top_p),
            -jnp.inf, logits,
        )
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# the per-row sampler resolves its filters inside a static top-K candidate
# subset (one lax.top_k, no [B, V] sort in the serving hot path — the same
# full-vocab-chain trap the scalar sampler's subset rework removed).
# Per-row top_k clamps to the cap; a nucleus that would
# extend past the cap truncates there — at serving temperatures the
# nucleus lives far inside 128 candidates.
PER_ROW_TOPK_CAP = 128


def _per_row_warp(logits, temperature, top_k, top_p):
    """The per-row filter resolution shared by :func:`sample_logits_per_row`
    and :func:`per_row_log_probs`: temperature scaling, the static
    top-``PER_ROW_TOPK_CAP`` candidate subset, and the composed top-k /
    nucleus cut expressed as ONE per-row value threshold. Factored out so
    the speculative-decoding acceptance ratio (:mod:`tpudist.serve.spec`)
    scores EXACTLY the distribution the sampler draws from — any drift
    between the two breaks the acceptance-rejection identity."""
    b, v = logits.shape
    temperature = jnp.asarray(temperature, jnp.float32)
    cap = min(PER_ROW_TOPK_CAP, v)
    k = jnp.clip(jnp.asarray(top_k, jnp.int32), 0, cap)
    p = jnp.asarray(top_p, jnp.float32)
    greedy = jax.lax.top_k(logits, 1)[1][:, 0].astype(jnp.int32)
    # greedy rows divide by 1.0 — their scaled values feed the (discarded)
    # sampled branch, and an inf/NaN there would be harmless but noisy
    scaled = logits / jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    k_active = k > 0
    p_active = p < 1.0
    top_vals, top_idx = jax.lax.top_k(scaled, cap)  # [B, cap], sorted desc
    rank = jnp.arange(cap)[None, :]
    # top-k as a per-row threshold: the k-th largest value (rank k-1)
    kth = jnp.take_along_axis(top_vals, jnp.maximum(k - 1, 0)[:, None], axis=-1)
    k_thresh = jnp.where(k_active[:, None], kth, -jnp.inf)
    # nucleus (HF order — over the top-k-FILTERED mass): k-active rows
    # renormalize over their k-subset; k-inactive rows use TRUE full-vocab
    # probabilities (one logsumexp pass, no sort) so the exclusive-cumsum
    # over the sorted candidates is exact for every candidate rank
    in_k = jnp.where(k_active[:, None], rank < k[:, None], True)
    masked_vals = jnp.where(in_k, top_vals, -jnp.inf)
    logz = jnp.where(
        k_active[:, None],
        jax.nn.logsumexp(masked_vals, axis=-1, keepdims=True),
        jax.nn.logsumexp(scaled, axis=-1, keepdims=True),
    )
    probs = jnp.exp(masked_vals - logz)
    p_thresh = _nucleus_threshold_from_probs(
        masked_vals, probs, jnp.minimum(p, 1.0)[:, None]
    )
    p_thresh = jnp.where(p_active[:, None], p_thresh, -jnp.inf)
    thresh = jnp.maximum(k_thresh, p_thresh)  # [B, 1]
    return (greedy, scaled, top_vals, top_idx, masked_vals, thresh,
            k_active, p_active, temperature)


def per_row_log_probs(logits, *, temperature, top_k, top_p):
    """Log-probabilities ``[B, V]`` of the WARPED per-row distribution
    :func:`sample_logits_per_row` draws from — the exact ``log p(token)``
    the speculative-decoding acceptance ratio needs for both the target
    and the draft side (:mod:`tpudist.serve.spec`). Filtered-out tokens
    are ``-inf``; kept tokens are renormalized over the kept set.

    Greedy rows (``temperature == 0``) are a point mass: ``0.0`` at the
    first-occurrence argmax, ``-inf`` elsewhere — the distribution the
    greedy branch of the sampler actually realizes.

    The kept set is expressed as the full-vocab threshold test
    ``scaled >= thresh`` rather than a candidate-subset membership list;
    the two coincide except on exact value ties at the cut boundary
    (measure zero for float logits — the same tie caveat the sampler
    documents)."""
    (greedy, scaled, _, _, _, thresh, k_active, p_active,
     temperature) = _per_row_warp(logits, temperature, top_k, top_p)
    filtered = (k_active | p_active)[:, None]
    keep = jnp.where(filtered, scaled >= thresh, True)
    masked = jnp.where(keep, scaled, -jnp.inf)
    logp = masked - jax.nn.logsumexp(masked, axis=-1, keepdims=True)
    v = logits.shape[-1]
    point = jnp.where(
        jnp.arange(v)[None, :] == greedy[:, None], 0.0, -jnp.inf
    )
    return jnp.where((temperature == 0.0)[:, None], point, logp)


def sample_logits_per_row(logits, keys, *, temperature, top_k, top_p):
    """Per-ROW sampling over ``[B, V]`` logits: ``temperature``/``top_k``/
    ``top_p`` are ``[B]`` arrays and ``keys`` is a ``[B]`` array of rng
    keys — one compiled program serves every mix of per-slot sampling
    params, which is what lets the serving engine keep requests with
    different decoding configs in ONE masked decode step
    (:mod:`tpudist.serve.engine`).

    Per-row semantics: ``temperature == 0`` is greedy (the same
    first-occurrence ``lax.top_k(·, 1)`` winner as :func:`sample_logits`,
    so a greedy slot is bit-identical to the static path); ``top_k <= 0``
    disables the top-k filter for that row; ``top_p >= 1`` disables
    nucleus. Filters compose in the HF order (temperature → top_k →
    top_p) and resolve inside a static top-``PER_ROW_TOPK_CAP`` candidate
    subset: per-row ``top_k`` clamps to the cap, and a ``top_p`` whose
    nucleus would extend past the cap keeps exactly the cap's candidates
    (vocab-size subsets are exact — the cap only binds at ``V > 128``).
    Tie semantics are THRESHOLD-based (every id tied with the k-th value
    is kept, like HF's warper; the scalar path keeps exactly k) — for
    float logits ties have measure zero. Sampling is gumbel-max with one
    ``[V]`` gumbel field per row from that row's key (each slot owns an
    rng stream independent of its neighbors — retiring or admitting a
    request cannot perturb another slot's draw); an unfiltered row's
    categorical runs over the full vocab, a filtered row's over its
    candidate subset through the same gumbel field."""
    b, v = logits.shape
    (greedy, scaled, top_vals, top_idx, masked_vals, thresh, k_active,
     p_active, temperature) = _per_row_warp(logits, temperature, top_k, top_p)
    # ONE [B, V] gumbel field serves both sampling flavors: unfiltered
    # rows argmax over the full vocab; filtered rows over their candidate
    # subset (the subset reads its gumbel values through top_idx, so a
    # candidate's noise is identical either way)
    gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (v,)))(keys)
    free_choice = jnp.argmax(scaled + gumbel, axis=-1)
    sub_gumbel = jnp.take_along_axis(gumbel, top_idx, axis=-1)
    sub_scores = jnp.where(
        masked_vals >= thresh, masked_vals + sub_gumbel, -jnp.inf
    )
    sub_choice = jnp.take_along_axis(
        top_idx, jnp.argmax(sub_scores, axis=-1)[:, None], axis=-1
    )[:, 0]
    sampled = jnp.where(
        k_active | p_active, sub_choice, free_choice
    ).astype(jnp.int32)
    return jnp.where(temperature == 0.0, greedy, sampled)


def eos_retire(tok, done, eos_id, pad_id=0):
    """The ONE stop rule shared by :func:`generate`'s in-scan masking and
    the serving engine's per-slot retirement (:mod:`tpudist.serve.engine`):
    rows already done emit ``pad_id``, and a row is done after it emits
    ``eos_id`` (the EOS token itself is still delivered). ``eos_id`` and
    ``pad_id`` may be scalars or per-row arrays — the engine passes per-
    request stop ids with ``-1`` meaning "no stop token" (token ids are
    non-negative, so ``-1`` never matches)."""
    tok = jnp.where(done, pad_id, tok)
    return tok, done | (tok == eos_id)


def _sample_scan(decode_step, cache, first_logits, rng, *, max_new_tokens,
                 temperature, top_k, top_p, eos_id=None, pad_id=0):
    """The shared sampling loop of both generation paths: scan
    ``max_new_tokens`` (sample from the previous position's logits, decode
    one step) iterations. The final carry's logits go unused — the last
    decode_step primes a position that is never sampled.

    ``eos_id``: rows that have emitted it produce ``pad_id`` from then on
    (the sequence stays static-shaped — the TPU way to "stop"; the cache
    keeps advancing, which is harmless since padded positions are never
    read back). The scan always runs ``max_new_tokens`` steps: a
    data-dependent early exit would force a ``while_loop`` that defeats
    the fixed-shape single compilation.

    Returns ``(tokens [B, max_new_tokens], lengths [B])`` — ``lengths``
    counts each row's real tokens (through its first EOS inclusive;
    ``max_new_tokens`` when it never stopped)."""

    def sample_step(carry, _):
        cache, last_logits, rng, done = carry
        rng, sub = jax.random.split(rng)
        tok = sample_logits(
            last_logits, sub, temperature=temperature, top_k=top_k,
            top_p=top_p,
        )
        alive = ~done  # this step emits a REAL token for still-alive rows
        if eos_id is not None:
            tok, done = eos_retire(tok, done, eos_id, pad_id)
        cache, next_logits = decode_step(cache, tok)
        return (cache, next_logits, rng, done), (tok, alive)

    done0 = jnp.zeros(first_logits.shape[0], bool)
    (cache, _, _, _), (toks, alive) = jax.lax.scan(
        sample_step, (cache, first_logits, rng, done0), None,
        length=max_new_tokens,
    )
    lengths = jnp.sum(alive, axis=0).astype(jnp.int32)
    return toks.T, lengths  # [B, max_new_tokens], [B]


def zero_cache(model, batch_size: int, **init_kwargs):
    """Freshly-zeroed decode cache for ``batch_size`` rows, with the
    shapes ``model.init(..., decode=True)`` would create — via
    ``eval_shape``, so the throwaway init never materializes a second copy
    of the params (``model.init`` would — a 2× HBM spike at 7B scale).
    The serving engine's slot pool is exactly this at
    ``batch_size=max_slots`` (:mod:`tpudist.serve.slots`)."""
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((batch_size, 1), jnp.int32),
            train=False, decode=True, **init_kwargs,
        )
    )["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )


def _reset_cursors(cache, true_len):
    """Rewind every scalar position counter (the per-block ``cache_index``
    and GPT-2's wpe cursor) to the TRUE prompt length after a
    bucket-padded prefill: the pad tail existed only for shape bucketing,
    and decode must continue at position ``true_len`` (the stale pad K/V
    above the cursor is overwritten step by step and never attended — the
    mask only admits slots <= cursor)."""
    t = jnp.asarray(true_len, jnp.int32)
    return jax.tree_util.tree_map(
        lambda leaf: t
        if jnp.ndim(leaf) == 0 and jnp.issubdtype(leaf.dtype, jnp.integer)
        else leaf,
        cache,
    )


def _fetch(out) -> np.ndarray:
    """Generated device tokens → host numpy, multi-process-safe."""
    if not out.is_fully_addressable:
        # multi-process with sharded/global params: the jit output may span
        # hosts, and np.asarray on a non-addressable array raises; every
        # process runs the same decode on the same prompt, so allgathering
        # the token ids (tiny) yields the identical [B, T] everywhere.
        # tiled=True is required for global non-addressable inputs and
        # returns the global [B, T] (no leading process dim)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(out, tiled=True))
    return np.asarray(out)


def generate(
    model,
    params,
    prompt,
    max_new_tokens: int,
    *,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    seed: int = 0,
    eos_id: int | None = None,
    pad_id: int = 0,
    return_lengths: bool = False,
) -> np.ndarray:
    """Continue ``prompt`` (``[B, P]`` int tokens) by ``max_new_tokens``.

    Works for any model with the decode contract (``decode=True`` +
    ``cache`` collection): GPT-2 and Llama. Returns ``[B, max_new_tokens]``
    int32. Greedy when ``temperature=0``, else temperature/top-k/top-p
    (nucleus) sampling. With ``eos_id``, rows that emit it produce
    ``pad_id`` thereafter (static shapes — the compiled program always
    runs ``max_new_tokens`` steps); ``return_lengths=True`` additionally
    returns a ``[B]`` int32 array of real lengths (through each row's
    first EOS inclusive) — the same per-row retirement rule the serving
    engine applies (:func:`eos_retire`).

    The prompt is padded to a power-of-two BUCKET (:func:`bucket_length`)
    with the true length passed as a traced scalar, so repeated calls
    with varying prompt lengths reuse one compiled program per bucket
    instead of compiling per length.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    if p + max_new_tokens > model.max_seq_len:
        raise ValueError(
            f"prompt {p} + {max_new_tokens} new tokens exceeds the model's "
            f"max_seq_len {model.max_seq_len} (the KV cache size)"
        )

    bucket = bucket_length(p, cap=model.max_seq_len)
    if bucket > p:
        # pad-token VALUES are irrelevant: prefill is causal within the
        # chunk, so real rows never attend the tail, and _reset_cursors
        # rewinds the write cursor so decode overwrites the tail's K/V
        prompt = jnp.pad(prompt, ((0, 0), (0, bucket - p)))
    cache = zero_cache(model, b)
    toks, lengths = _run(
        model, params, cache, prompt, jnp.asarray(p, jnp.int32),
        jax.random.key(seed),
        max_new_tokens=max_new_tokens, temperature=temperature, top_k=top_k,
        top_p=top_p, eos_id=eos_id, pad_id=pad_id,
    )
    if return_lengths:
        return _fetch(toks), _fetch(lengths)
    return _fetch(toks)


def generate_seq2seq(
    model,
    params,
    enc_tokens,
    max_new_tokens: int,
    *,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    seed: int = 0,
    start_id: int = 0,
    eos_id: int | None = None,
    pad_id: int = 0,
    return_lengths: bool = False,
) -> np.ndarray:
    """Seq2seq generation for encoder-decoder models (T5): encode
    ``enc_tokens`` ``[B, Se]`` once, then autoregressively decode
    ``max_new_tokens`` tokens from ``start_id`` against the decoder's KV
    cache — all (encode + prefill + sampling) as ONE jit-compiled program,
    the same single-compilation contract as :func:`generate`. Returns
    ``[B, max_new_tokens]`` int32; same sampling controls as
    :func:`sample_logits`.

    The model must support the ``encode_only``/``decode`` entry points
    (:class:`tpudist.models.t5.T5`); the cache buffer is
    ``model.max_decode_len`` slots (the start token takes one).
    ``eos_id`` (T5's natural stop: its EOS ends the span-target sequence)
    pads each row with ``pad_id`` after its first EOS;
    ``return_lengths=True`` adds the ``[B]`` real lengths.
    """
    enc_tokens = jnp.asarray(enc_tokens, jnp.int32)
    if max_new_tokens + 1 > model.max_decode_len:
        raise ValueError(
            f"start token + {max_new_tokens} new tokens exceeds the "
            f"model's max_decode_len {model.max_decode_len} (the decoder "
            "KV cache size)"
        )
    toks, lengths = _run_seq2seq(
        model, params, enc_tokens, jax.random.key(seed),
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, start_id=start_id, eos_id=eos_id,
        pad_id=pad_id,
    )
    if return_lengths:
        return _fetch(toks), _fetch(lengths)
    return _fetch(toks)


@partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k",
                     "top_p", "start_id", "eos_id", "pad_id"),
)
def _run_seq2seq(model, params, enc_tokens, rng, *, max_new_tokens,
                 temperature, top_k, top_p, start_id, eos_id, pad_id):
    b = enc_tokens.shape[0]
    enc = model.apply(
        {"params": params}, enc_tokens, train=False, encode_only=True
    )
    # the cache depends on the decoder side alone, so a length-1 dummy enc
    # keeps the throwaway init trace cheap
    cache = zero_cache(
        model, b, enc=jnp.zeros((b, 1, model.hidden_dim), enc.dtype)
    )

    def decode_step(cache, tok):
        logits, updates = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            train=False, decode=True, enc=enc, mutable=["cache"],
        )
        return updates["cache"], logits[:, -1]

    cache, logits = decode_step(
        cache, jnp.full((b,), start_id, jnp.int32)
    )
    return _sample_scan(
        decode_step, cache, logits, rng, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p, eos_id=eos_id,
        pad_id=pad_id,
    )


@partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k",
                     "top_p", "eos_id", "pad_id"),
)
def _run(model, params, cache, prompt, true_len, rng, *, max_new_tokens,
         temperature, top_k, top_p, eos_id, pad_id):
    """One compiled program for prefill + sampling. ``params``, the
    bucket-padded ``prompt``, and ``true_len`` are traced arguments (not
    closure constants), and jit caches on the static (model, bucket,
    length, sampling) config — repeated generate() calls with the same
    setup (any prompt length within the bucket) reuse the compilation."""

    def decode_chunk(cache, toks):
        """toks [B, s] → (updated cache, [B, s, V] logits)."""
        logits, updates = model.apply(
            {"params": params, "cache": cache}, toks,
            train=False, decode=True, mutable=["cache"],
        )
        return updates["cache"], logits

    def decode_step(cache, tok):
        cache, logits = decode_chunk(cache, tok[:, None])
        return cache, logits[:, -1]

    # BULK prefill: the whole (bucket-padded) prompt in ONE decode pass —
    # cached_kv's mask is causal within the chunk (slot t attendable by
    # row i iff t <= pos + i), so a P-token prompt costs one MXU-shaped
    # forward instead of a P-iteration scan of launch-bound single-token
    # steps (no served cell: not measured on the chip). The first sampled
    # position is the TRUE last prompt token's logits (a traced index — the pad tail feeds
    # nothing), and the cursors rewind to true_len so decode continues
    # exactly where the real prompt ended.
    cache, all_logits = decode_chunk(cache, prompt)
    logits = jax.lax.dynamic_index_in_dim(
        all_logits, true_len - 1, axis=1, keepdims=False
    )
    cache = _reset_cursors(cache, true_len)
    return _sample_scan(
        decode_step, cache, logits, rng, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p, eos_id=eos_id,
        pad_id=pad_id,
    )
