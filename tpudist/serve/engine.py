"""Continuous-batching inference engine.

The scheduler over the slot pool: a priority-laned request queue with
admission control, per-slot sampling/stop params, per-step streaming token
delivery, and latency-SLO telemetry. One scheduler **tick**
(:meth:`ServeEngine.step`) is:

1. **admit** — while a slot is free, the active count is under
   ``max_active``, and the budget holds (slot count on the contiguous
   pool; BLOCK budget on the paged pool): pop the most urgent queued
   request, run its bucketed chunked prefill (``tpudist.serve.prefill``
   — resumed past any prefix-cache hit), sample its FIRST token from the
   prefill logits (that emission is the request's TTFT), and map its
   prefix K/V into a slot;
2. **dispatch** — ONE compiled masked decode step over the FULL slot batch
   (``positions=`` per-slot cursors — plus per-slot block tables in paged
   mode — non-live slots ride along masked): write each fed token's K/V
   at its slot's cursor, sample each slot's next token with its own
   params and rng stream (:func:`tpudist.generate.sample_logits_per_row`),
   apply the shared stop rule (:func:`tpudist.generate.eos_retire`);
3. **process** — fetch the PREVIOUS tick's dispatched step, stream its
   tokens, and retire finished slots (stop token or budget), making room
   for the next admission — requests join and leave between decode steps
   with ZERO recompiles.

The decode loop is **one-step-delayed**, the same pipeline idiom as
``fit()``'s metric fetch: step ``k`` is dispatched
BEFORE step ``k-1``'s tokens are fetched, and each step's sampled tokens
feed the next step ON DEVICE (a carried ``[S]`` token array, overridden
per-slot at admission), so the device never idles waiting for a host
round-trip. The price is bounded and paid only on retirement: a
slot whose stop token is discovered one tick late burns at most ONE
masked zombie row-step (its write lands at its own cursor and the slot
is released before anything reads it), and the ``(request_id, slot
ownership)`` snapshot guard discards the zombie's output.

**Paged mode** (``paged=True``, docs/SERVING.md "Paged memory"): the KV
cache becomes a shared block pool with per-slot block tables
(:mod:`tpudist.serve.blocks`), so HBM holds Σ(actual lengths) instead of
``max_slots × max_seq_len`` and ``max_slots`` can rise to whatever the
byte budget actually supports under the traffic's length distribution.
Three scheduler behaviors only exist there:

- **block-budget admission**: a request admits when the pool can map its
  (post-prefix-hit) prompt plus ``watermark_blocks`` of decode headroom,
  evicting cold prefix-cache leaves first — slot count alone no longer
  measures capacity;
- **prefix cache**: completed prompt-prefix blocks are content-hashed and
  shared copy-on-write at block granularity, so requests repeating a
  system prompt skip its prefill (TTFT drops to ~one chunk) and share
  its bytes;
- **preempt-to-queue**: when the pool runs dry mid-decode (a slot's
  cursor needs a block and eviction finds none), the newest
  lowest-priority slot is evicted back to the FRONT of its lane — its
  blocks free NOW, its prompt+progress replay at re-admission (prefix
  cache usually making the replay cheap), and its token stream continues
  exactly where it stopped (the replayed request re-enters decode at the
  same cursor, rng stream, and sampling state — greedy output stays
  bit-identical through an eviction cycle, pinned by test).

**Speculative mode** (``draft_model=``, docs/SERVING.md §6): each tick a
cheap DRAFT model proposes ``spec_k`` tokens per live slot (K+1
single-token draft steps against a second, slot-pinned draft KV pool),
and the target scores the whole window ``[last, d_1..d_K]`` in ONE bulk
decode pass — the accepted prefix plus one correction/bonus token all
land in a single target weight sweep, so a slot emits up to ``spec_k+1``
tokens per tick at roughly one sequential-pass cost (fewer passes, not
faster passes). Acceptance-rejection sampling
(:mod:`tpudist.serve.spec`) preserves the target distribution EXACTLY —
greedy speculative output is token-identical to the non-speculative
engine, pinned by test. The cursor becomes DEVICE-carried (``[S]``
positions ride the step outputs, since only the device knows how many
tokens each sweep accepted); the host's view syncs at each delayed
fetch, lagging at most two sweeps — the paged block-mapping horizon
covers ``2·(spec_k+1)`` tokens of that lag. "Rollback" of rejected
draft K/V is pure cursor bookkeeping: stale entries above the cursor
are overwritten before the causal mask ever admits them.

**Priority lanes**: ``submit(priority=N)`` — admission always serves the
highest-priority non-empty lane, FIFO within a lane, UNLESS
``ttft_slo_s`` is set and a lower lane's head has waited past it (then
the oldest overdue head goes first — TTFT-deadline-driven aging, fed by
the same clock ``stats.py`` measures TTFT with, so starvation surfaces
in the ``serve`` rows exactly when the scheduler acts on it).

Why this wins over static batching: a static batch must assemble before
prefill (queue wait on the LAST arrival) and every row decodes until the
LONGEST request finishes (retired rows burn full decode steps). The
engine's decode batch stays full under mixed-length Poisson arrivals.
The benchmark has no served cell: the gap is not measured on the chip.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpudist.generate import eos_retire, sample_logits_per_row
from tpudist.serve.prefill import Prefiller
from tpudist.serve.slots import SlotPool
from tpudist.serve.stats import ServeStats

NO_EOS = -1  # token ids are non-negative, so -1 never matches


class QueueFull(RuntimeError):
    """Admission control: the request queue is at ``max_queue``. Callers
    shed load (or retry later) — unbounded queues just move the failure
    to an OOM or an SLO blowout."""


@dataclasses.dataclass(frozen=True)
class Request:
    request_id: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: int = NO_EOS
    priority: int = 0
    # a preempted request re-queues with the tokens it already emitted:
    # re-admission rebuilds its K/V (prompt + replay[:-1]) via prefill —
    # prefix-cache hits making most of that a gather — and feeds
    # replay[-1] as the next step's input, continuing the stream without
    # re-emitting anything
    replay_tokens: tuple | None = None


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token: ``index`` is its 0-based position in the
    request's generated sequence; ``done`` marks the request's last
    token (EOS or budget)."""

    request_id: int
    token: int
    index: int
    done: bool


@dataclasses.dataclass
class _Inflight:
    """A dispatched-but-unfetched decode step: the device token/stop
    futures plus the host-side snapshot of which slots were live and who
    owned them at dispatch time (ownership can change before the fetch —
    the processing guard keys on it)."""

    tok: jax.Array
    done: jax.Array
    live: np.ndarray   # [S] bool — rows fed for real at this dispatch
    rid: np.ndarray    # [S] int64 — owner snapshot


@dataclasses.dataclass
class _SpecInflight:
    """A dispatched-but-unfetched SPECULATIVE sweep: the device futures
    for the emitted window (``emit [S, K+1]`` / ``n_emit [S]``), the
    eligible-draft counts (``n_spec`` — acceptance-rate telemetry), the
    advanced cursors (``pos`` — the host's position sync), the eos flags,
    and the same ownership snapshot the plain pipeline keys its zombie
    guard on."""

    emit: jax.Array
    n_emit: jax.Array
    n_spec: jax.Array
    pos: jax.Array
    done: jax.Array
    live: np.ndarray   # [S] bool — rows fed for real at this dispatch
    rid: np.ndarray    # [S] int64 — owner snapshot


def _build_spec_step(model, params, draft_model, draft_params, base_key,
                     spec_k: int, paged: bool):
    """The one compiled SPECULATIVE step over the full slot batch:
    ``spec_k`` single-token draft proposals (plus one priming step so a
    fully-accepted window's K/V is complete), ONE bulk target verify pass
    over ``[last, d_1..d_K]``, acceptance-rejection
    (:func:`tpudist.serve.spec.speculative_accept`), and an in-graph
    first-EOS cut. Both caches are donated; the cursor and last-token
    lanes are device-carried outputs (only the device knows each row's
    acceptance count).

    Per-row clamps make one formula cover sequence end AND budget:
    ``limit = prompt_len + max_new_tokens`` rides in as a device input,
    ``allowed = limit - 1 - pos`` is how many tokens the row may still
    emit, and ``n_spec = clip(allowed - 1, 0, K)`` caps eligibility so
    ``n_emit <= allowed`` — the device NEVER overshoots a budget, which
    is what keeps the paged block-mapping horizon inside the worst case
    ``submit()`` already validated (no admission livelock). Draft/verify
    writes past the clamp land above the cursor (contiguous: the one-hot
    write self-clamps past ``max_seq_len``; paged: unmapped table
    entries redirect to the garbage block) and rows past ``n_spec`` are
    never consumed, so the overshoot is dead weight, not corruption.

    RNG: one key per (request, cursor) — ``fold(fold(base, rid), pos)``;
    draft step ``i`` folds salt ``i``, acceptance/residual use the
    disjoint salts in :mod:`tpudist.serve.spec`. ``pos`` is strictly
    increasing and replay-stable, so a preempted request re-draws the
    same stream; ``pos >= 1`` (prompts are non-empty) keeps the space
    disjoint from ``_first_token``'s token-index-0 keys."""
    from tpudist.serve.spec import speculative_accept

    K = int(spec_k)

    def body(cache, d_cache, prev_tok, override_tok, use_override, pos_in,
             override_pos, done, req_ids, temperature, top_k, top_p, eos,
             limit, block_tables=None):
        extra = {} if block_tables is None else {"block_tables": block_tables}
        tok0 = jnp.where(use_override, override_tok, prev_tok)
        pos = jnp.where(use_override, override_pos, pos_in).astype(jnp.int32)
        allowed = limit - 1 - pos          # tokens this row may still emit
        n_spec = jnp.clip(allowed - 1, 0, K).astype(jnp.int32)
        alive = (~done) & (allowed > 0)

        keys = jax.vmap(
            lambda r, p: jax.random.fold_in(jax.random.fold_in(base_key, r), p)
        )(req_ids, pos)

        # K draft proposals, each a masked single-token step at its own
        # per-row position (the draft pool rides the SAME slot/cursor
        # lanes as the target), sampled from the draft's WARPED
        # distribution — the distribution the acceptance ratio divides by
        cur, d_toks, d_logits = tok0, [], []
        for i in range(K):
            dl, dup = draft_model.apply(
                {"params": draft_params, "cache": d_cache}, cur[:, None],
                train=False, decode=True, mutable=["cache"],
                positions=pos + i,
            )
            d_cache = dup["cache"]
            ki = jax.vmap(lambda kk: jax.random.fold_in(kk, i))(keys)
            cur = sample_logits_per_row(
                dl[:, -1], ki, temperature=temperature, top_k=top_k,
                top_p=top_p,
            )
            d_toks.append(cur)
            d_logits.append(dl[:, -1])
        if K:
            # prime d_K's draft K/V (logits discarded — return_hidden
            # skips the head): after a FULLY accepted window the next
            # tick feeds the bonus token at pos+K+1, and the draft must
            # attend d_K at pos+K
            _, dup = draft_model.apply(
                {"params": draft_params, "cache": d_cache}, cur[:, None],
                train=False, decode=True, mutable=["cache"],
                positions=pos + K, return_hidden=True,
            )
            d_cache = dup["cache"]
        d_toks_a = jnp.stack(d_toks, axis=1)      # [S, K]
        d_logits_a = jnp.stack(d_logits, axis=1)  # [S, K, V]

        # ONE bulk target pass scores the whole window [tok0, d_1..d_K]:
        # K+1 rows of target logits from a single weight sweep, writing
        # every window token's K/V at its own per-row position in the
        # same pass (accepted tokens' K/V is already in place next tick;
        # rejected tokens' K/V sits above the cursor, dead)
        window = jnp.concatenate([tok0[:, None], d_toks_a], axis=1)
        t_logits, updates = model.apply(
            {"params": params, "cache": cache}, window,
            train=False, decode=True, mutable=["cache"], positions=pos,
            **extra,
        )
        cache = updates["cache"]
        emit, n_emit = speculative_accept(
            t_logits, d_logits_a, d_toks_a, n_spec, keys,
            temperature=temperature, top_k=top_k, top_p=top_p,
        )

        # in-graph first-EOS cut (the window analog of eos_retire): keep
        # through the first stop token, flag the row for retirement
        cols = jnp.arange(K + 1)[None, :]
        is_eos = (emit == eos[:, None]) & (eos >= 0)[:, None] & (
            cols < n_emit[:, None]
        )
        first_eos = jnp.min(
            jnp.where(is_eos, cols, K + 1), axis=1
        ).astype(jnp.int32)
        n_emit = jnp.minimum(n_emit, first_eos + 1)
        eos_hit = first_eos < n_emit
        n_emit = jnp.where(alive, n_emit, 0)
        n_spec = jnp.where(alive, n_spec, 0)
        emit = jnp.where(cols < n_emit[:, None], emit, 0)
        done_out = done | (alive & eos_hit)

        new_pos = pos + n_emit
        last = jnp.take_along_axis(
            emit, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
        )[:, 0]
        next_tok = jnp.where(n_emit > 0, last, tok0)
        return (cache, d_cache, new_pos, next_tok, emit, n_emit, n_spec,
                done_out)

    if paged:
        @partial(jax.jit, donate_argnums=(0, 1))
        def step(cache, d_cache, prev_tok, override_tok, use_override,
                 pos_in, override_pos, block_tables, done, req_ids,
                 temperature, top_k, top_p, eos, limit):
            return body(cache, d_cache, prev_tok, override_tok,
                        use_override, pos_in, override_pos, done, req_ids,
                        temperature, top_k, top_p, eos, limit,
                        block_tables=block_tables)

        return step

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(cache, d_cache, prev_tok, override_tok, use_override, pos_in,
             override_pos, done, req_ids, temperature, top_k, top_p, eos,
             limit):
        return body(cache, d_cache, prev_tok, override_tok, use_override,
                    pos_in, override_pos, done, req_ids, temperature,
                    top_k, top_p, eos, limit)

    return step


def _build_decode_step(model, params, base_key, paged: bool):
    """The one compiled decode step over the full slot batch: feed each
    slot's last token (the PREVIOUS step's on-device sample, or the
    admission override for slots that just joined) at its own position,
    sample each slot's next token with its own params from its own rng
    stream, apply the shared stop rule. Non-live slots arrive with
    ``done=True``: they emit the pad id and their (masked, later
    overwritten) cache writes are dead — in paged mode those ride-along
    writes land in the reserved garbage block their all-zero tables map.

    ``model``/``params``/``base_key`` are CLOSURE constants, not traced
    arguments (one compiled step per engine instance): with params as jit
    arguments, XLA re-canonicalizes the big weight layouts on EVERY call
    — the vocab-sized embedding table alone is read with two access
    patterns. The static ``generate()`` path keeps params traced because
    one call amortizes that over the whole in-graph scan; the engine
    calls once per token and cannot."""

    def body(cache, prev_tok, override_tok, use_override, pos, done,
             req_ids, tok_idx, temperature, top_k, top_p, eos,
             block_tables=None):
        tok = jnp.where(use_override, override_tok, prev_tok)
        extra = {} if block_tables is None else {"block_tables": block_tables}
        logits, updates = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            train=False, decode=True, mutable=["cache"], positions=pos,
            **extra,
        )
        # per-slot rng streams: (request id, token index) keys the draw,
        # so a slot's stream is independent of which other requests share
        # the batch — and survives a preempt/replay cycle unchanged
        keys = jax.vmap(
            lambda r, t: jax.random.fold_in(jax.random.fold_in(base_key, r), t)
        )(req_ids, tok_idx)
        nxt = sample_logits_per_row(
            logits[:, -1], keys, temperature=temperature, top_k=top_k,
            top_p=top_p,
        )
        nxt, done = eos_retire(nxt, done, eos, 0)
        return updates["cache"], nxt, done

    if paged:
        @partial(jax.jit, donate_argnums=(0,))
        def step(cache, prev_tok, override_tok, use_override, pos,
                 block_tables, done, req_ids, tok_idx, temperature, top_k,
                 top_p, eos):
            return body(cache, prev_tok, override_tok, use_override, pos,
                        done, req_ids, tok_idx, temperature, top_k, top_p,
                        eos, block_tables=block_tables)

        return step

    @partial(jax.jit, donate_argnums=(0,))
    def step(cache, prev_tok, override_tok, use_override, pos, done,
             req_ids, tok_idx, temperature, top_k, top_p, eos):
        return body(cache, prev_tok, override_tok, use_override, pos, done,
                    req_ids, tok_idx, temperature, top_k, top_p, eos)

    return step


def engine_param_shardings(model, params, mesh):
    """``NamedSharding`` tree for a serving param tree over ``mesh``, by
    the models' own Megatron ``nn.with_partitioning`` metadata (the same
    annotations the training side shards by —
    ``tpudist.train.state_shardings_from_meta``; unannotated leaves
    replicate). One deviation from the training path: a spec dim whose
    size the mesh axis does NOT divide is dropped to replicated for that
    dim — jax refuses uneven named placements at runtime (tpudist.memory's
    ceil-shard note), and GPT-2's 50257-row vocab table under ``tensor=2``
    is exactly that case. Replicating such a leaf is always correct under
    GSPMD (the matmuls still partition on the other operand); it just
    forgoes that leaf's byte saving.

    ``params`` may be concrete arrays or a ``jax.eval_shape`` tree — only
    leaf SHAPES are read, so a geometry's per-chip bytes
    (``tpudist.memory.per_device_bytes``) can be budgeted without
    materializing a weight."""
    import flax.linen as nn
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    specs = nn.get_partition_spec(jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32), train=False
        )["params"]
    ))
    # PartitionSpec is a tuple subclass: flatten with is_leaf, and align
    # leaves by flatten order (dict/FrozenDict both flatten key-sorted) so
    # the spec tree's container types need not match the params tree's
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)
    )
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if len(spec_leaves) != len(leaves):
        raise ValueError(
            f"params tree has {len(leaves)} leaves but the model's "
            f"partition-spec tree has {len(spec_leaves)} — params do not "
            "belong to this model architecture"
        )

    def fix(spec, leaf):
        dims = []
        for i, ax in enumerate(spec):
            if ax is None:
                dims.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            world = int(np.prod([mesh.shape[a] for a in axes]))
            dims.append(ax if leaf.shape[i] % world == 0 else None)
        return P(*dims)

    shardings = [
        NamedSharding(mesh, fix(spec, leaf))
        for spec, leaf in zip(spec_leaves, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def _shard_engine_params(model, params, mesh):
    """Place a serving param tree over ``mesh`` per
    :func:`engine_param_shardings`."""
    shardings = engine_param_shardings(model, params, mesh)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


@jax.jit
def _first_token(logits, base_key, request_id, temperature, top_k, top_p):
    """Sample a just-prefilled request's first token (token index 0 of its
    stream) from the prefill logits ``[V]``."""
    key = jax.random.fold_in(
        jax.random.fold_in(base_key, request_id), jnp.int32(0)
    )
    return sample_logits_per_row(
        logits[None], key[None], temperature=temperature[None],
        top_k=top_k[None], top_p=top_p[None],
    )[0]


class ServeEngine:
    """Continuous-batching engine over a model with the decode contract
    (GPT-2 / Llama: ``decode=True`` + ``cache`` collection + per-row
    ``positions``; paged mode additionally threads ``block_tables``).

    ``max_slots`` sizes the decode batch; ``max_active`` (default
    ``max_slots``) caps concurrently-decoding requests below it when
    prefill latency must be bounded; ``max_queue`` bounds admission
    (submit raises :class:`QueueFull` beyond it). ``sink`` (a
    :class:`tpudist.telemetry.TelemetrySink`) streams ``serve`` rows every
    ``stats_every`` ticks; ``on_token`` is the streaming callback, called
    with each :class:`TokenEvent` as it is emitted (one tick after its
    dispatch — the delayed-fetch pipeline).

    Paged-mode knobs (``paged=True``): ``block_size`` (must divide
    ``model.max_seq_len``), ``n_blocks`` (default: the contiguous pool's
    byte budget, ``max_slots × max_seq_len / block_size``, plus the
    garbage block — size it DOWN and raise ``max_slots`` to serve more
    concurrency from the same HBM; docs/SERVING.md "Paged memory" has the
    sizing math), ``prefix_cache`` (content-hash completed prompt-prefix
    blocks for sharing), ``watermark_blocks`` (admission headroom kept
    free for live slots' decode growth; default ``max_slots``).
    ``ttft_slo_s`` arms priority-lane aging (module docstring).

    ``compile_cache=dir`` routes the engine's compiled program inventory
    (the decode step + the per-bucket prefill programs) through
    :class:`tpudist.compile_cache.CompileCache`: construction AOT-compiles
    everything NOW (deploy-time, instead of lazily on first traffic) and
    a REDEPLOYED server with the same weights/geometry loads the
    serialized executables instead of re-tracing — engine cold-start is a
    recorded number (``compile_cache_info``), not a first-request tax.
    The key fingerprints the param VALUES (the programs close over the
    weights, so the serialized payload embeds them): one hashing pass
    over the params at construction, and a new checkpoint can never be
    served by a stale executable. Fail-soft like the training cache — a
    load or first-call failure falls back to the jit path permanently.

    ``retain_results=False`` drops a request's state (its accumulated
    token list) the moment it completes — the long-lived-server mode:
    consume tokens through ``on_token``/``events()``, and host memory
    stays bounded by the ACTIVE requests instead of growing with every
    request ever served. The default keeps results so the drain-style
    ``run()``/``result()`` batch API works."""

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_active: int | None = None, max_queue: int = 256,
                 prefill_chunk: int = 512, seed: int = 0, sink=None,
                 stats_every: int = 50, on_token=None,
                 retain_results: bool = True, clock=time.perf_counter,
                 paged: bool = False, block_size: int = 32,
                 n_blocks: int | None = None, prefix_cache: bool = True,
                 watermark_blocks: int | None = None,
                 ttft_slo_s: float | None = None, compile_cache=None,
                 draft_model=None, draft_params=None, spec_k: int = 4,
                 mesh=None, trace: bool = False,
                 metrics_port: int | None = None,
                 anatomy: bool = False):
        self.mesh = mesh
        self.tensor_world = 1
        self._kv_sharding = None
        self._rep_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from tpudist.mesh import TENSOR_AXIS

            if TENSOR_AXIS in mesh.axis_names:
                self.tensor_world = int(mesh.shape[TENSOR_AXIS])
            self._rep_sharding = NamedSharding(mesh, P())
        if self.tensor_world > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from tpudist.mesh import TENSOR_AXIS

            for name, m in (("model", model), ("draft_model", draft_model)):
                if m is None:
                    continue
                h = int(m.num_heads)
                h_kv = int(getattr(m, "num_kv_heads", None) or h)
                if h % self.tensor_world or h_kv % self.tensor_world:
                    raise ValueError(
                        f"{name}: num_heads={h} / num_kv_heads={h_kv} not "
                        f"divisible by tensor={self.tensor_world} — the KV "
                        "pool shards on the KV-head dim and the paged "
                        "kernel runs per-shard, so BOTH head counts must "
                        "divide the tensor world (GQA: the KV heads are "
                        "the binding constraint); pick a smaller tensor= "
                        "or serve unsharded (mesh=None)"
                    )
            # the models already thread mesh= (context-parallel attention
            # uses the same field); setting it here routes the paged
            # kernel through its shard_map wrap (ops/decode.py)
            if getattr(model, "mesh", None) is not mesh:
                model = model.clone(mesh=mesh)
            params = _shard_engine_params(model, params, mesh)
            if draft_model is not None and draft_params is not None:
                if getattr(draft_model, "mesh", None) is not mesh:
                    draft_model = draft_model.clone(mesh=mesh)
                draft_params = _shard_engine_params(
                    draft_model, draft_params, mesh
                )
            # the KV pools — contiguous [S, H_kv, max_len, dh], paged
            # [n_blocks, H_kv, block_size, dh], and the prefiller's
            # batch-1 rows — all shard on their KV-head dim (dim 1);
            # host-side tables/cursors stay replicated
            self._kv_sharding = NamedSharding(
                mesh, P(None, TENSOR_AXIS, None, None)
            )
        self.model = model
        self.params = params
        self.spec = draft_model is not None
        self.spec_k = int(spec_k)
        if self.spec:
            if draft_params is None:
                raise ValueError("draft_model given without draft_params")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if getattr(draft_model, "vocab_size", None) != model.vocab_size:
                raise ValueError(
                    f"draft vocab {getattr(draft_model, 'vocab_size', None)} "
                    f"!= target vocab {model.vocab_size} — the acceptance "
                    "ratio compares per-token distributions"
                )
            if draft_model.max_seq_len < model.max_seq_len:
                raise ValueError(
                    f"draft max_seq_len {draft_model.max_seq_len} < target's "
                    f"{model.max_seq_len}: the draft pool rides the target's "
                    "cursor lane and must cover the same positions"
                )
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.max_active = max_slots if max_active is None else max_active
        if not 1 <= self.max_active <= max_slots:
            raise ValueError(
                f"max_active {self.max_active} outside [1, {max_slots}]"
            )
        self.max_queue = max_queue
        self.paged = bool(paged)
        if self.paged:
            from tpudist.serve.blocks import PagedSlotPool

            if n_blocks is None:
                # equal-HBM default: the contiguous pool's bytes, paged
                # (+1 for the reserved garbage block). Sizing n_blocks
                # DOWN while raising max_slots is the point of the layout.
                n_blocks = max_slots * (model.max_seq_len // block_size) + 1
            self.pool = PagedSlotPool(
                model, max_slots, n_blocks=n_blocks, block_size=block_size,
                prefix_cache=prefix_cache, kv_sharding=self._kv_sharding,
            )
            self.watermark = (
                max_slots if watermark_blocks is None else int(watermark_blocks)
            )
        else:
            self.pool = SlotPool(
                model, max_slots, kv_sharding=self._kv_sharding
            )
            self.watermark = 0
        self.prefiller = Prefiller(
            model, params, chunk=prefill_chunk,
            kv_sharding=self._kv_sharding,
        )
        self.on_token = on_token
        self.ttft_slo_s = ttft_slo_s
        self.stats = ServeStats(
            slots=max_slots, sink=sink, every=stats_every, clock=clock,
            paged=self.paged, tensor_world=self.tensor_world,
        )
        # per-request lifecycle spans (tpudist.telemetry.trace.ServeTracer,
        # docs/OBSERVABILITY.md §8): every hook reuses the EXACT clock
        # reading the stats call returned, so span-derived TTFT/TPOT are
        # bit-equal to the SLO samples. Off (the default) constructs
        # nothing and the streams stay byte-identical.
        self.tracer = None
        if trace:
            if sink is None:
                raise ValueError("trace=True needs a sink= to write spans to")
            from tpudist.telemetry.trace import ServeTracer

            self.tracer = ServeTracer(sink)
        # live Prometheus endpoint: a scrape-time snapshot() reader — the
        # request hot path pays nothing for it (no pushes, no device work)
        self.exporter = None
        self.metrics_port: int | None = None
        if metrics_port is not None:
            from tpudist.telemetry.trace import MetricsExporter

            self.exporter = MetricsExporter(metrics_port)
            self.exporter.add_collector(self._metrics_snapshot)
            self.metrics_port = self.exporter.port
        self._base_key = jax.random.key(seed)
        if self.spec:
            # second, slot-pinned KV pool for the draft (contiguous even
            # under a paged target — the draft cache is small enough to
            # pay its full rectangle; equal-HBM comparisons account for
            # it via blocks.draft_equivalent_blocks) plus a HEADLESS
            # draft prefiller: the draft's first proposal conditions on
            # the target-sampled first token, so its prompt-end logits
            # are never read
            self._draft_pool = SlotPool(
                draft_model, max_slots, kv_sharding=self._kv_sharding
            )
            self._draft_prefiller = Prefiller(
                draft_model, draft_params, chunk=prefill_chunk, head=False,
                kv_sharding=self._kv_sharding,
            )
            self._decode_fn = _build_spec_step(
                model, params, draft_model, draft_params, self._base_key,
                self.spec_k, self.paged,
            )
        else:
            self._decode_fn = _build_decode_step(
                model, params, self._base_key, self.paged
            )
        self._lanes: dict[int, collections.deque[Request]] = {}
        self._t_submit: dict[int, float] = {}
        self.retain_results = retain_results
        self._results: dict[int, list[int]] = {}
        self._counts: dict[int, int] = {}  # emitted per LIVE request
        self._live_toks: dict[int, list[int]] = {}  # emitted values (replay)
        self._next_id = 0
        self._step = 0
        s = max_slots
        # per-slot request state (host side; shipped as tiny arrays each
        # tick). A slot's row is meaningful iff pool.active[slot].
        self._req = np.full(s, -1, np.int64)
        self._dispatched = np.zeros(s, np.int32)  # tokens dispatched so far
        self._budget = np.zeros(s, np.int32)
        self._temp = np.zeros(s, np.float32)
        self._topk = np.zeros(s, np.int32)
        self._topp = np.ones(s, np.float32)
        self._eos = np.full(s, NO_EOS, np.int32)
        self._slot_prio = np.zeros(s, np.int32)
        self._admit_seq = np.zeros(s, np.int64)  # victim choice: newest first
        self._seq = 0
        self._slot_req: dict[int, Request] = {}  # original request per slot
        # the device-carried token feedback (each step's samples feed the
        # next step without a host round-trip) and the admission overrides
        # that splice a new request's first token into its slot's lane
        self._prev_tok = self._dev(jnp.zeros(s, jnp.int32))
        self._override: dict[int, int] = {}
        # speculative device-carried cursor lane + per-slot emission limit
        # (prompt_len + max_new — the spec step's one clamp covering both
        # sequence end and budget); host positions sync at each fetch
        self._pos_dev = self._dev(jnp.zeros(s, jnp.int32))
        self._limit = np.zeros(s, np.int32)
        self._inflight: _Inflight | None = None
        self._drained_events: list[TokenEvent] = []
        self._decode_aot: dict | None = None
        self.compile_cache_info: dict | None = None
        if compile_cache is not None:
            self._setup_compile_cache(compile_cache, seed=seed)
            if sink is not None:
                # the per-program outcomes, a compiler refusal's full
                # message among them: the jit path it falls back to must
                # not hide why
                sink.write("compile_cache", **self.compile_cache_info)
        # program anatomy at bring-up (docs/OBSERVABILITY.md §9): one
        # `anatomy` row per serving program — XLA's own FLOPs/bytes for a
        # decode tick and a prefill body chunk. The AOT executables above
        # yield cost AND static memory for free; without a compile cache
        # each program pays one lowering (no compile). Off (the default)
        # runs nothing and the streams stay byte-identical.
        self.anatomy_info: list[dict] | None = None
        if anatomy:
            if sink is None:
                raise ValueError("anatomy=True needs a sink= to write to")
            self.anatomy_info = self.program_anatomy()
            for row in self.anatomy_info:
                sink.write("anatomy", **row)

    # -- submission --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               eos_id: int | None = None, priority: int = 0) -> int:
        """Enqueue a request; returns its id. Sampling params are
        PER-REQUEST (``temperature=0`` greedy, ``top_k<=0`` / ``top_p>=1``
        off — :func:`tpudist.generate.sample_logits_per_row` semantics);
        ``priority`` picks the lane (higher = served first, subject to
        ``ttft_slo_s`` aging). Raises :class:`QueueFull` past
        ``max_queue`` and ``ValueError`` when the request cannot fit the
        KV budget (per-slot window, and in paged mode the block pool)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            # reject HERE like every other bad request: deferred to the
            # prefiller it would abort the whole drain mid-flight
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.model.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + {max_new_tokens} new tokens exceeds "
                f"max_seq_len {self.model.max_seq_len} (the per-slot KV size)"
            )
        if self.paged:
            worst = self.pool.blocks_for(prompt.size + max_new_tokens)
            if worst > self.pool.blocks.n_usable:
                raise ValueError(
                    f"request needs up to {worst} blocks but the pool has "
                    f"{self.pool.blocks.n_usable}; raise n_blocks"
                )
        if self.queue_depth >= self.max_queue:
            raise QueueFull(
                f"request queue at max_queue={self.max_queue}; shed load"
            )
        rid = self._next_id
        self._next_id += 1
        req = Request(
            rid, prompt, int(max_new_tokens), float(temperature),
            int(top_k or 0), float(1.0 if top_p is None else top_p),
            NO_EOS if eos_id is None else int(eos_id), int(priority),
        )
        self._lanes.setdefault(req.priority, collections.deque()).append(req)
        self._counts[rid] = 0
        if self.paged:
            self._live_toks[rid] = []
        if self.retain_results:
            self._results[rid] = []
        self._t_submit[rid] = self.stats.on_submit(rid)
        if self.tracer is not None:
            self.tracer.on_submit(rid, self._t_submit[rid], lane=req.priority)
        return rid

    # -- scheduler ---------------------------------------------------------

    @property
    def pending(self) -> bool:
        return (self.queue_depth > 0 or self.pool.n_active > 0
                or self._inflight is not None)

    @property
    def queue_depth(self) -> int:
        return sum(len(d) for d in self._lanes.values())

    def step(self) -> list[TokenEvent]:
        """One scheduler tick: admit, dispatch, process. Returns the
        tokens emitted this tick (also delivered to ``on_token``) — a
        dispatched token surfaces on the NEXT tick's process phase."""
        t_tick0 = None if self.tracer is None else self.stats._clock()
        events = self._admit()
        self._drained_events = []
        new_inflight = self._dispatch()
        # a preemption inside _dispatch force-fetched the in-flight step
        # (its retirements can free blocks) — surface those tokens now
        events.extend(self._drained_events)
        if self._inflight is not None:
            events.extend(self._process(self._inflight))
        self._inflight = new_inflight
        self._step += 1
        self.stats.on_tick(
            self._step, queue_depth=self.queue_depth,
            active=self.pool.n_active,
            pool_occupancy=(
                self.pool.blocks.occupancy if self.paged else None
            ),
        )
        if self.tracer is not None:
            self.tracer.on_tick(
                self._step, t_tick0, self.stats._clock(),
                active=self.pool.n_active, queue_depth=self.queue_depth,
                emitted=len(events),
            )
        if self.on_token is not None:
            for e in events:
                self.on_token(e)
        return events

    def run(self) -> dict[int, list[int]]:
        """Drain queue and slots to completion; returns
        ``{request_id: tokens}`` and writes the ``serve_summary`` row.
        (With ``retain_results=False`` the dict only holds still-live
        requests — i.e. nothing after a full drain; stream via
        ``on_token``/``events()`` in that mode.)"""
        while self.pending:
            self.step()
        self.stats.write_summary(self._step)
        return {r: list(t) for r, t in self._results.items()}

    def events(self):
        """Generator of :class:`TokenEvent` until the engine drains —
        the streaming consumption shape (``for ev in engine.events():``)."""
        while self.pending:
            yield from self.step()
        self.stats.write_summary(self._step)

    def result(self, request_id: int) -> list[int]:
        """Tokens accumulated for a request (``KeyError`` once a completed
        request's state was dropped under ``retain_results=False``)."""
        return list(self._results[request_id])

    def reset_stats(self) -> None:
        """Fresh SLO accounting on a warm engine (same sink/cadence/clock)
        — benches warm the compiled programs with a throwaway workload on
        ONE engine instance (the decode step and prefill programs are
        per-instance closures), then reset before the timed run."""
        s = self.stats
        self.stats = ServeStats(
            slots=self.pool.max_slots, sink=s.sink, every=s.every,
            clock=s._clock, paged=self.paged,
            tensor_world=self.tensor_world,
        )

    def close(self) -> None:
        """Release the engine's host-side services (today: the live
        metrics endpoint's server thread). Safe to call twice; a no-op
        when ``metrics_port`` was never given."""
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None

    # -- internals ---------------------------------------------------------

    def _occ(self) -> float | None:
        """Block-pool occupancy at a scheduler transition (None on a
        contiguous engine) — the pressure tag span rows carry."""
        return self.pool.blocks.occupancy if self.paged else None

    def _metrics_snapshot(self) -> dict:
        """The live-metrics collector: host-side SLO state at scrape time
        (``ServeStats.snapshot()`` plus the queue/slot live readings).
        Runs on the exporter's HTTP thread — reads only host scalars, so
        a scrape can never block or perturb the serving loop."""
        snap = {f"serve_{k}": v for k, v in self.stats.snapshot().items()}
        snap["serve_queue_depth"] = self.queue_depth
        snap["serve_active"] = self.pool.n_active
        snap["serve_preemptions_total"] = snap.pop("serve_preemptions", 0)
        return snap

    def _dev(self, x):
        """Host lane → device argument. On a mesh engine the lane commits
        to the REPLICATED placement: the compiled step's weights and KV
        live mesh-sharded, and the AOT executables validate argument
        shardings, so an uncommitted single-device array would either
        force a reshard per tick or fail warm-start validation outright.
        Off-mesh this is a plain ``jnp.asarray`` (the call sites keep
        their ``.copy()`` snapshots — the XLA:CPU aliasing discipline is
        unchanged)."""
        if self._rep_sharding is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), self._rep_sharding)

    def _emit(self, rid: int, token: int, done: bool) -> TokenEvent:
        ev = TokenEvent(rid, token, self._counts[rid], done)
        self._counts[rid] += 1
        if self.paged:
            # replay record for preempt-to-queue — paged-only machinery;
            # a contiguous streaming server should not pay double host
            # memory per live token for a list nothing ever reads
            self._live_toks[rid].append(token)
        if self.retain_results:
            self._results[rid].append(token)
        return ev

    def _finish(self, rid: int) -> None:
        """Request complete: close out its SLO accounting and (in
        streaming mode) drop its per-request state — host memory stays
        bounded by live requests, not requests ever served."""
        n_tokens = self._counts.pop(rid)
        t_done = self.stats.on_done(rid, n_tokens)
        if self.tracer is not None:
            self.tracer.on_done(
                rid, t_done, n_tokens, pool_occupancy=self._occ()
            )
        self._live_toks.pop(rid, None)
        self._t_submit.pop(rid, None)
        if not self.retain_results:
            self._results.pop(rid, None)

    def _peek_next(self) -> tuple[int, Request] | None:
        """The lane/request admission would serve next: highest-priority
        non-empty lane's head, unless ``ttft_slo_s`` aging promotes an
        overdue lower lane's head (oldest overdue first)."""
        heads = [(lane, dq[0]) for lane, dq in self._lanes.items() if dq]
        if not heads:
            return None
        if self.ttft_slo_s is not None:
            now = self.stats._clock()
            overdue = [
                (lane, r) for lane, r in heads
                if now - self._t_submit.get(r.request_id, now)
                > self.ttft_slo_s
            ]
            if overdue:
                return min(
                    overdue,
                    key=lambda lr: self._t_submit.get(
                        lr[1].request_id, float("inf")
                    ),
                )
        return max(heads, key=lambda lr: lr[0])

    def _admit(self) -> list[TokenEvent]:
        events: list[TokenEvent] = []
        while self.pool.n_free > 0 and self.pool.n_active < self.max_active:
            picked = self._peek_next()
            if picked is None:
                break
            lane, req = picked
            replay = req.replay_tokens
            # the K/V the slot must hold before its first dispatch: the
            # prompt for a fresh request; prompt + all-but-the-last
            # emitted token for a replay (the last one is the next step's
            # INPUT, exactly the steady-state shape)
            if replay is not None:
                kv_tokens = np.concatenate(
                    [req.prompt, np.asarray(replay[:-1], np.int32)]
                )
            else:
                kv_tokens = req.prompt
            hit_blocks: list[int] = []
            lookup_blocks = 0
            if self.paged:
                bs = self.pool.block_size
                worst = self.pool.blocks_for(len(kv_tokens))
                # a fresh request must re-run its LAST prompt token (its
                # logits are the first sample); a replay needs no logits,
                # so its whole K/V may come from the cache
                limit = (len(kv_tokens) if replay is not None
                         else len(kv_tokens) - 1)
                max_hits = (
                    0 if self.pool.prefix is None
                    else max(min(limit, len(kv_tokens)), 0) // bs
                )
                # the watermark is decode headroom against the OTHER live
                # slots' growth; on an idle pool there is nothing to
                # thrash against, and insisting on it would make a
                # request whose need_new + watermark exceeds the pool
                # permanently unadmittable (head-of-line livelock) even
                # though submit() verified it fits
                wm = self.watermark if self.pool.n_active else 0
                if self.pool.free_after_evict() < worst - max_hits + wm:
                    # even a FULL prefix hit cannot fit: stop admitting
                    # before paying the prompt hash + pin work this tick
                    # (FIFO head-of-line — the request stays queued,
                    # decode drains the pool; a blocked tick costs one
                    # evictability scan, not O(prompt) hashing)
                    break
                if self.pool.prefix is not None:
                    hit_blocks = self.pool.prefix.lookup(kv_tokens, limit)
                    lookup_blocks = max_hits
                    # PIN the hits until insert takes its own refs: the
                    # eviction below frees cache-only (refcount-1) leaves,
                    # and the matched blocks are exactly that until the
                    # slot maps them — without the pin a budget eviction
                    # could free the blocks this admission is about to use
                    for blk in hit_blocks:
                        self.pool.blocks.incref(int(blk))
                budget = worst - len(hit_blocks) + wm
                if self.pool.free_after_evict() < budget:
                    # the actual hits fell short of the optimistic
                    # pre-check (and the pins just excluded them from the
                    # evictable count): release and stay queued
                    for blk in hit_blocks:
                        self.pool.blocks.decref(int(blk))
                    break
                if self.pool.blocks.n_free < budget:
                    self.pool.evict_prefix(budget - self.pool.blocks.n_free)
            self._lanes[lane].popleft()
            # admission commit: the queue-wait sample closes here (the
            # prefill dispatch follows immediately); a replay re-admission
            # doesn't re-sample, it closes its preempted span instead
            t_adm = self.stats.on_prefill_start(req.request_id)
            if self.tracer is not None:
                if replay is None:
                    self.tracer.on_admit(
                        req.request_id, t_adm, pool_occupancy=self._occ()
                    )
                else:
                    self.tracer.on_resume(
                        req.request_id, t_adm, pool_occupancy=self._occ()
                    )
            if self.paged and self.pool.prefix is not None:
                # record the prefix outcome only for COMMITTED admissions:
                # a budget-blocked head retries the lookup every tick, and
                # counting those attempts would let one stuck request
                # inflate prefix_hit_rate with phantom lookups
                self.stats.on_prefix(len(hit_blocks), lookup_blocks)
            n_hit_tokens = len(hit_blocks) * (
                self.pool.block_size if self.paged else 0
            )
            if self.paged and hit_blocks:
                if n_hit_tokens < len(kv_tokens):
                    row_cache, last_logits = self.prefiller.resume(
                        self.pool.gather_row(hit_blocks), kv_tokens,
                        n_hit_tokens,
                    )
                else:
                    # full-hit replay: every block is shared and insert
                    # scatters nothing — skip the whole-window gather too
                    row_cache, last_logits = None, None
            else:
                row_cache, last_logits = self.prefiller(kv_tokens)
            if replay is None:
                tok = int(_first_token(
                    last_logits, self._base_key,
                    jnp.asarray(req.request_id, jnp.int32),
                    jnp.asarray(req.temperature, jnp.float32),
                    jnp.asarray(req.top_k, jnp.int32),
                    jnp.asarray(req.top_p, jnp.float32),
                ))
                t_first = self.stats.on_first_token(req.request_id)
                if self.tracer is not None:
                    self.tracer.on_first_token(
                        req.request_id, t_first,
                        prefix_hit=(len(hit_blocks) if self.paged else None),
                        prefix_lookup=(lookup_blocks if self.paged else None),
                    )
                done = tok == req.eos_id or req.max_new_tokens == 1
                events.append(self._emit(req.request_id, tok, done))
                if done:
                    # one-token request (or instant EOS): never occupies
                    # a slot — release the prefix pins insert would have
                    # taken over, or the hit blocks' refcounts stay
                    # elevated forever (unevictable, never freed)
                    for blk in hit_blocks:
                        self.pool.blocks.decref(int(blk))
                    self._finish(req.request_id)
                    continue
                override, n_disp = tok, 1
            else:
                # re-admission after preemption: everything through
                # replay[-1] was already emitted; feed it back and resume
                # the stream at the same cursor/rng position
                override, n_disp = int(replay[-1]), len(replay)
            # the pool write composes with an in-flight decode step: the
            # pool's cache is already the dispatched step's output future,
            # and the scatter simply queues behind it on the device stream
            if self.paged:
                slot = self.pool.insert(
                    row_cache, len(kv_tokens), prompt=kv_tokens,
                    hit_blocks=hit_blocks,
                )
                for blk in hit_blocks:  # insert holds its own refs now
                    self.pool.blocks.decref(int(blk))
            else:
                slot = self.pool.insert(row_cache, len(kv_tokens))
            if self.tracer is not None:
                self.tracer.set_slot(req.request_id, slot)
            if self.spec:
                # the draft's K/V for the same window, pinned to the SAME
                # slot (shared cursor lane). Always a real prefill — the
                # draft has no paged pool or prefix cache to resume from,
                # and headless chunks on a narrow model are cheap
                d_row, _ = self._draft_prefiller(kv_tokens)
                self._draft_pool.write_row(d_row, slot)
                self._limit[slot] = len(req.prompt) + req.max_new_tokens
            self._req[slot] = req.request_id
            self._dispatched[slot] = n_disp
            self._budget[slot] = req.max_new_tokens
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
            self._eos[slot] = req.eos_id
            self._slot_prio[slot] = req.priority
            self._seq += 1
            self._admit_seq[slot] = self._seq
            self._slot_req[slot] = req
            self._override[slot] = override
        return events

    def _choose_victim(self) -> int | None:
        """The slot preemption evicts when the pool runs dry: lowest
        priority first, newest admission within a priority (LIFO — the
        request that has invested least, and whose re-queue at the front
        of its lane costs the least reordering)."""
        cands = np.nonzero(self.pool.active)[0]
        if cands.size == 0:
            return None
        return int(min(
            cands,
            key=lambda s: (self._slot_prio[s], -self._admit_seq[s]),
        ))

    def _preempt(self, victim: int) -> None:
        """Evict a live slot back to its lane's FRONT: its blocks free
        now, its request replays at re-admission (the in-flight step was
        already drained by the caller, so the emitted-token record is
        complete and the stream resumes exactly where it stopped)."""
        rid = int(self._req[victim])
        orig = self._slot_req[victim]
        req = dataclasses.replace(
            orig, replay_tokens=tuple(self._live_toks.get(rid, ()))
        )
        self._lanes.setdefault(req.priority, collections.deque()).appendleft(
            req
        )
        self._override.pop(victim, None)
        self._slot_req.pop(victim, None)
        self.pool.release(victim)
        self._req[victim] = -1
        t_pre = self.stats.on_preempt(rid)
        if self.tracer is not None:
            self.tracer.on_preempt(rid, t_pre, pool_occupancy=self._occ())

    def _ensure_blocks(self, live: np.ndarray) -> np.ndarray:
        """Paged pre-dispatch pass: every live slot whose cursor crossed a
        block boundary must map a fresh block before the step runs. When
        the pool is dry the escalation ladder is: (1) force-fetch the
        in-flight step — its retirements may free blocks (one extra host
        sync, only on the pressure path); (2) evict a cold prefix-cache
        leaf; (3) preempt the newest lowest-priority slot to the queue.
        The loop terminates because every preemption removes a slot from
        ``live`` — in the worst case the requesting slot preempts
        itself."""
        for slot in np.nonzero(live)[0]:
            while live[slot] and not self.pool.ensure_next(slot):
                if self._inflight is not None:
                    self._drained_events.extend(
                        self._process(self._inflight)
                    )
                    self._inflight = None
                    live &= self.pool.active & (
                        self._dispatched < self._budget
                    )
                    continue
                if self.pool.evict_prefix(1):
                    continue
                victim = self._choose_victim()
                if victim is None:  # no active slots left to free
                    live[slot] = False
                    break
                self._preempt(victim)
                live[victim] = False
        return live

    def _ensure_blocks_spec(self, live: np.ndarray) -> np.ndarray:
        """Paged pre-dispatch pass, speculative flavor: one sweep writes
        up to ``spec_k + 1`` positions past a cursor the host only knows
        ONE FETCH LATE (the in-flight sweep may have advanced it another
        ``spec_k + 1``), so each live slot maps a whole window — host
        cursor + ``2·(spec_k+1)`` tokens, capped at the slot's emission
        limit, which ``submit()`` already validated fits the pool — via
        :meth:`tpudist.serve.blocks.PagedSlotPool.ensure_to`. Dry-pool
        escalation is the same ladder as the plain path: force-fetch the
        in-flight sweep (retirements free blocks AND tighten the horizon,
        since the host cursor catches up), evict a cold prefix leaf,
        preempt the newest lowest-priority slot."""
        horizon = 2 * (self.spec_k + 1)
        for slot in np.nonzero(live)[0]:
            while live[slot]:
                need = min(
                    int(self.pool.positions[slot]) + horizon,
                    int(self._limit[slot]),
                )
                if self.pool.ensure_to(slot, need):
                    break
                if self._inflight is not None:
                    self._drained_events.extend(
                        self._process(self._inflight)
                    )
                    self._inflight = None
                    live &= self.pool.active
                    continue
                if self.pool.evict_prefix(1):
                    continue
                victim = self._choose_victim()
                if victim is None:  # no active slots left to free
                    live[slot] = False
                    break
                self._preempt(victim)
                live[victim] = False
        return live

    def _dispatch_spec(self) -> _SpecInflight | None:
        """The speculative analog of :meth:`_dispatch`: live rows are
        simply the occupied slots — budget gating moved ON DEVICE (the
        step's ``limit`` clamp emits zero once a row is exhausted, so an
        over-dispatched zombie sweep is dead weight, and the host retires
        the slot at the fetch that consumes its budget). The cursor lane
        is device-carried (``_pos_dev`` chains through the step outputs);
        admission overrides splice a fresh slot's cursor in exactly like
        its first token."""
        live = self.pool.active.copy()
        if self.paged and live.any():
            live = self._ensure_blocks_spec(live)
        if not live.any():
            return None
        s = self.pool.max_slots
        override_tok = np.zeros(s, np.int32)
        override_pos = np.zeros(s, np.int32)
        use_override = np.zeros(s, bool)
        for slot, tok in self._override.items():
            override_tok[slot] = tok
            override_pos[slot] = self.pool.positions[slot]
            use_override[slot] = True
        self._override.clear()
        # same snapshot discipline as _dispatch: every host array copies
        # before becoming a device argument (XLA:CPU zero-copy aliasing)
        args = [
            self.pool.cache, self._draft_pool.cache, self._prev_tok,
            self._dev(override_tok), self._dev(use_override),
            self._pos_dev, self._dev(override_pos),
        ]
        if self.paged:
            args.append(self._dev(self.pool.tables.copy()))
        args += [
            self._dev(~live), self._dev(self._req.astype(np.int32)),
            self._dev(self._temp.copy()), self._dev(self._topk.copy()),
            self._dev(self._topp.copy()), self._dev(self._eos.copy()),
            self._dev(self._limit.copy()),
        ]
        (self.pool.cache, self._draft_pool.cache, new_pos, next_tok, emit,
         n_emit, n_spec, done_dev) = self._call_decode(*args)
        self._pos_dev = new_pos
        self._prev_tok = next_tok
        return _SpecInflight(
            emit, n_emit, n_spec, new_pos, done_dev, live, self._req.copy()
        )

    def _process_spec(self, prev: _SpecInflight) -> list[TokenEvent]:
        """Fetch a speculative sweep (the one host sync per tick): stream
        each owned row's emitted window IN ORDER (every token its own
        :class:`TokenEvent` — the consumer-visible contract is unchanged,
        there are just up to ``spec_k + 1`` per slot per tick), sync the
        host cursor from the device's, and retire on the in-graph EOS
        flag or the budget landing exactly on the window's last token
        (the device clamp guarantees no mid-window overshoot)."""
        emit = np.asarray(prev.emit)
        n_emit = np.asarray(prev.n_emit)
        n_spec = np.asarray(prev.n_spec)
        pos = np.asarray(prev.pos)
        done = np.asarray(prev.done)
        events: list[TokenEvent] = []
        drafted = accepted = 0
        for slot in np.nonzero(prev.live)[0]:
            rid = int(prev.rid[slot])
            if self._req[slot] != rid or rid not in self._counts:
                continue  # zombie sweep: ownership guard, as in _process
            self.pool.positions[slot] = int(pos[slot])
            m = int(n_emit[slot])
            if m == 0:
                continue
            # accepted = emitted minus the one correction/bonus token the
            # target pass supplies anyway; drafted = ELIGIBLE proposals
            # (the device's n_spec clamp), so a budget-clamped window
            # doesn't read as rejection
            drafted += int(n_spec[slot])
            accepted += m - 1
            if self.tracer is not None:
                self.tracer.on_spec(rid, int(n_spec[slot]), m - 1)
            for j in range(m):
                n = self._counts[rid]
                finished = (
                    (bool(done[slot]) and j == m - 1)
                    or n + 1 >= int(self._budget[slot])
                )
                events.append(self._emit(rid, int(emit[slot, j]), finished))
                if finished:
                    self._finish(rid)
                    self.pool.release(slot)
                    self._req[slot] = -1
                    self._slot_req.pop(slot, None)
                    break
        self.stats.on_decode_step(int(prev.live.sum()), len(events))
        self.stats.on_spec(drafted, accepted)
        return events

    def _dispatch(self) -> _Inflight | _SpecInflight | None:
        """Dispatch the next decode step without waiting on the previous
        one's results. Live rows = occupied slots with budget left; a slot
        whose stop token sits in the unfetched step rides one extra masked
        zombie row (discarded at process time by the ownership guard)."""
        if self.spec:
            return self._dispatch_spec()
        live = self.pool.active & (self._dispatched < self._budget)
        if self.paged and live.any():
            live = self._ensure_blocks(live)
        if not live.any():
            return None
        override_tok = np.zeros(self.pool.max_slots, np.int32)
        use_override = np.zeros(self.pool.max_slots, bool)
        for slot, tok in self._override.items():
            override_tok[slot] = tok
            use_override[slot] = True
        self._override.clear()
        # every host array is SNAPSHOTTED (.copy()/astype) before it
        # becomes a device argument: XLA:CPU's device_put zero-copy
        # ALIASES aligned numpy buffers, and under async dispatch the
        # step may read them only after this tick's host-side bookkeeping
        # (advance/admission) has already mutated them in place —
        # reproduced as per-process-deterministic corrupted token
        # streams, pinned by test_serve_paged's aliasing regression
        # test. The copies are tiny ([S]-scalar lanes and the [S, MB]
        # table) next to the decode step itself.
        args = [
            self.pool.cache, self._prev_tok, self._dev(override_tok),
            self._dev(use_override), self._dev(self.pool.positions.copy()),
        ]
        if self.paged:
            args.append(self._dev(self.pool.tables.copy()))
        args += [
            self._dev(~live), self._dev(self._req.astype(np.int32)),
            self._dev(self._dispatched.copy()), self._dev(self._temp.copy()),
            self._dev(self._topk.copy()), self._dev(self._topp.copy()),
            self._dev(self._eos.copy()),
        ]
        self.pool.cache, tok_dev, done_dev = self._call_decode(*args)
        self._prev_tok = tok_dev
        for slot in np.nonzero(live)[0]:
            self.pool.advance(slot)
            self._dispatched[slot] += 1
        return _Inflight(tok_dev, done_dev, live, self._req.copy())

    def _process(self, prev) -> list[TokenEvent]:
        """Fetch a dispatched step's tokens (the ONE host sync per tick,
        one step behind the device) and stream/retire."""
        if isinstance(prev, _SpecInflight):
            return self._process_spec(prev)
        tok = np.asarray(prev.tok)
        done = np.asarray(prev.done)
        events: list[TokenEvent] = []
        for slot in np.nonzero(prev.live)[0]:
            rid = int(prev.rid[slot])
            # ownership guard: a zombie row (its request retired between
            # this step's dispatch and its fetch) is discarded — the slot
            # may already belong to a newly admitted request. The slot
            # check alone suffices (a completing request's slot resets to
            # -1 in the same _process pass, before the one step that can
            # still reference it is fetched); the _counts membership is a
            # second, O(live)-memory line of defense
            if self._req[slot] != rid or rid not in self._counts:
                continue
            n = self._counts[rid]
            finished = bool(done[slot]) or n + 1 >= int(self._budget[slot])
            events.append(self._emit(rid, int(tok[slot]), finished))
            if finished:
                self._finish(rid)
                self.pool.release(slot)
                self._req[slot] = -1
                self._slot_req.pop(slot, None)
        self.stats.on_decode_step(int(prev.live.sum()), len(events))
        return events

    # -- deploy-time compile cache (warm start) ----------------------------

    def _call_decode(self, *args):
        """Dispatch through the cached AOT executable when one loaded;
        any failure (geometry the fingerprint couldn't see) permanently
        falls back to the jit path — the cache may cost a trace, never a
        wrong step. The fallback boundary is PRE-dispatch: an input
        mismatch raises at the executable's argument validation, before
        donation invalidates the cache buffers, so re-invoking the jit
        path on the same args is safe. A fault AFTER dispatch (device
        OOM mid-step) leaves the donated cache deleted and the retry
        dies on it — correct, since the cache contents are undefined at
        that point and no fallback could serve them."""
        if self._decode_aot is not None and self._decode_aot["exe"] is not None:
            try:
                return self._decode_aot["exe"](*args)
            except Exception:
                self._decode_aot["exe"] = None
        return self._decode_fn(*args)

    def _fingerprint(self, seed: int) -> str:
        """Content hash of everything the engine's executables bake in:
        model identity/config, engine geometry, jax versions, backend —
        and the PARAM VALUES, because the programs close over the weights
        (the serialized payload embeds them; a redeployed server with a
        new checkpoint must miss, or it would silently serve the old
        weights). One hashing pass over the params at construction — the
        deploy-time cost of the warm start."""
        from tpudist.compile_cache import SCHEMA, model_identity

        h = hashlib.sha256()
        cfg = {
            "schema": SCHEMA,
            "model": model_identity(self.model),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "max_slots": self.pool.max_slots,
            "max_seq_len": self.model.max_seq_len,
            "paged": self.paged,
            "block_size": getattr(self.pool, "block_size", 0),
            "n_blocks": (
                self.pool.blocks.n_blocks if self.paged else 0
            ),
            "chunk": self.prefiller.chunk,
            "minimum": self.prefiller.minimum,
            "seed": seed,
            # speculative geometry: the step program bakes in K and the
            # draft architecture, and closes over the draft weights too
            "spec_k": self.spec_k if self.spec else 0,
            "draft": model_identity(self.draft_model) if self.spec else None,
            # mesh topology: the executables bake in the device assignment
            # and every argument's sharding — a cache dir shared across
            # topologies must miss cheaply here, not fail (or worse,
            # validate) a wrong-geometry executable at first call
            "mesh": None if self.mesh is None else {
                "axes": [str(a) for a in self.mesh.axis_names],
                "shape": [
                    int(self.mesh.shape[a]) for a in self.mesh.axis_names
                ],
            },
            "tensor_world": self.tensor_world,
        }
        h.update(json.dumps(cfg, sort_keys=True).encode())
        trees = [("", self.params)]
        if self.spec:
            trees.append(("draft/", self.draft_params))
        for prefix, tree in trees:
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            for path, leaf in flat:
                arr = np.asarray(jax.device_get(leaf))
                h.update((prefix + jax.tree_util.keystr(path)).encode())
                h.update(str(arr.dtype).encode())
                h.update(arr.tobytes())
        return h.hexdigest()[:24]

    def _sds(self, x):
        """Shape/dtype (and, on a mesh engine, COMMITTED sharding) struct
        of one example argument: the lowered executable must see each
        argument's real placement (replicated lanes, KV-sharded pools) or
        first-call validation rejects the real args. Shared by the AOT
        compile-cache lowers and program introspection."""
        sh = getattr(x, "sharding", None)
        if self.mesh is not None and sh is not None:
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype)

    def _i32(self, *shape):
        return self._dev(jnp.zeros(shape, jnp.int32))

    def _decode_example_args(self) -> list:
        """Example argument list of ONE decode tick — exactly the shapes,
        dtypes, and committed placements `_decode_fn` is fed every step
        (mesh engine: each lane commits replicated via the same _dev
        discipline the per-tick dispatch uses). One definition feeds both
        the AOT compile-cache lower and :meth:`program_anatomy`, so the
        cached program and the introspected one can never drift."""
        s = self.pool.max_slots
        i32 = self._i32
        zeros_b = lambda: self._dev(jnp.zeros(s, bool))
        zeros_f = lambda: self._dev(jnp.zeros(s, jnp.float32))
        ones_f = lambda: self._dev(jnp.ones(s, jnp.float32))
        if self.spec:
            args = [
                self.pool.cache, self._draft_pool.cache, i32(s), i32(s),
                zeros_b(), i32(s), i32(s),
            ]
            if self.paged:
                args.append(i32(s, self.pool.max_blocks))
            args += [
                zeros_b(), i32(s), zeros_f(),
                i32(s), ones_f(), i32(s), i32(s),
            ]
            return args
        args = [self.pool.cache, i32(s), i32(s), zeros_b(), i32(s)]
        if self.paged:
            args.append(i32(s, self.pool.max_blocks))
        args += [
            zeros_b(), i32(s), i32(s), zeros_f(),
            i32(s), ones_f(), i32(s),
        ]
        return args

    def _prefill_row_example(self, prefiller):
        """The batch-1 KV-row example tree a prefill program is lowered
        against: ``_cache_shapes`` is already a ShapeDtypeStruct tree (no
        device allocation just to describe shapes); on a mesh engine it is
        re-structed with the KV sharding the prefiller's fresh caches
        actually carry."""
        row_ex = prefiller._cache_shapes
        if self._kv_sharding is not None:
            row_ex = jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct(
                    t.shape, t.dtype,
                    sharding=(
                        self._kv_sharding if len(t.shape) == 4
                        else self._rep_sharding
                    ),
                ),
                row_ex,
            )
        return row_ex

    def program_anatomy(self) -> list[dict]:
        """XLA's own account of the serving programs (docs/OBSERVABILITY
        .md §9): one info dict per program — the decode tick and a prefill
        body chunk — with XLA-counted FLOPs/bytes and, when the program
        came through the AOT compile cache, the static HBM breakdown too
        (a merely-lowered program yields costs only; lowering is cheap, no
        compile). Per-program fail-soft: an un-analyzable config
        contributes nothing rather than failing engine bring-up."""
        from tpudist.telemetry.anatomy import analyze_program

        rows: list[dict] = []
        try:
            exe = (self._decode_aot or {}).get("exe")
            lowered = None
            if exe is None:
                lowered = self._decode_fn.lower(*jax.tree_util.tree_map(
                    self._sds, self._decode_example_args()
                ))
            info = analyze_program(
                "serve_spec_decode" if self.spec else "serve_decode",
                compiled=exe, lowered=lowered,
            )
            if info is not None:
                info["slots"] = int(self.pool.max_slots)
                info["paged"] = self.paged
                rows.append(info)
        except Exception:
            pass
        try:
            chunk = self.prefiller.chunk
            exe = self.prefiller._aot.get(("body", chunk))
            lowered = None
            if exe is None:
                example = (self._prefill_row_example(self.prefiller),
                           self._i32(1, chunk))
                lowered = self.prefiller._chunk_body.lower(
                    *jax.tree_util.tree_map(self._sds, example)
                )
            info = analyze_program("serve_prefill_body", compiled=exe,
                                   lowered=lowered)
            if info is not None:
                info["chunk"] = int(chunk)
                rows.append(info)
        except Exception:
            pass
        return rows

    def _setup_compile_cache(self, directory, *, seed: int) -> None:
        """Deploy-time program inventory through the AOT executable cache:
        the decode step plus every power-of-two prefill bucket's body/
        final program, compiled NOW (cold) or deserialized (warm). Rare
        shapes outside the inventory (a capped non-power-of-two final
        bucket near the cache end) simply take the jit path."""
        from tpudist.compile_cache import CompileCache

        t0 = time.perf_counter()
        cc = CompileCache(directory)
        fp = self._fingerprint(seed)
        info: dict = {"hits": 0, "misses": 0, "programs": {}, "bytes": 0}

        def fetch(name, jitted, *example):
            key = f"{fp}-{name}"
            exe = cc.load(key)
            if exe is not None:
                info["hits"] += 1
                info["programs"][name] = "hit"
                return exe
            try:
                exe = jitted.lower(
                    *jax.tree_util.tree_map(self._sds, example)
                ).compile()
                nbytes = cc.store(key, exe, {"program": name})
                if nbytes and cc.load(key) is None:
                    # XLA:CPU wart (same family as tests/conftest.py's
                    # persistent-cache notes): an executable whose compile
                    # was satisfied from JAX's OWN persistent compilation
                    # cache serializes to a payload missing its fused-
                    # kernel symbols — it can never deserialize. Drop the
                    # dead entry so warm starts don't re-fail on it; the
                    # live executable still serves this process.
                    cc.path_for(key).unlink(missing_ok=True)
                    cc.path_for(key).with_suffix(".json").unlink(
                        missing_ok=True
                    )
                    info["programs"][name] = "unserializable"
                else:
                    info["bytes"] += nbytes
                    info["misses"] += 1
                    info["programs"][name] = "miss"
                return exe
            except Exception as exc:  # exotic config: jit path serves it
                info["programs"][name] = f"error:{type(exc).__name__}: {exc}"
                return None

        decode_args = self._decode_example_args()
        self._decode_aot = {"exe": fetch(
            "spec" if self.spec else "decode", self._decode_fn, *decode_args
        )}
        # _cache_shapes is already a ShapeDtypeStruct tree and _sds() maps
        # it through unchanged — no device-side batch-1 cache allocation
        # just to describe shapes (mesh engine: re-struct with the KV
        # sharding the prefiller's fresh caches actually carry)
        row_ex = self._prefill_row_example(self.prefiller)
        buckets, b = [], self.prefiller.minimum
        while b <= self.prefiller.chunk:
            buckets.append(b)
            b *= 2
        aot = {}
        for b in buckets:
            exe = fetch(f"pf{b}", self.prefiller._chunk_final,
                        row_ex, self._i32(1, b))
            if exe is not None:
                aot[("final", b)] = exe
        # body chunks are always exactly `chunk` long (only the final
        # chunk is partial), so one body program covers them
        exe = fetch(f"pb{self.prefiller.chunk}", self.prefiller._chunk_body,
                    row_ex, self._i32(1, self.prefiller.chunk))
        if exe is not None:
            aot[("body", self.prefiller.chunk)] = exe
        self.prefiller.attach_aot(aot)
        if self.spec:
            # the HEADLESS draft prefiller runs every chunk — including
            # the bucketed final one — through its body program, so it
            # needs a body executable at every bucket, not just `chunk`
            dpf = self._draft_prefiller
            d_row_ex = self._prefill_row_example(dpf)
            d_aot = {}
            for b in {*buckets, dpf.chunk}:
                exe = fetch(f"dpb{b}", dpf._chunk_body, d_row_ex,
                            self._i32(1, b))
                if exe is not None:
                    d_aot[("body", b)] = exe
            dpf.attach_aot(d_aot)
        info["build_s"] = round(time.perf_counter() - t0, 6)
        self.compile_cache_info = info
