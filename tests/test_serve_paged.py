"""Paged KV cache, prefix cache, and paged decode attention
(tpudist.serve.blocks + ServeEngine(paged=True), docs/SERVING.md "Paged
memory"): greedy paged-engine output must be BIT-identical to the
contiguous engine — and hence to static ``generate()`` — under staggered
arrivals, slot pressure, mixed lengths + eos (GPT-2 and Llama GQA/RoPE),
copy-on-write prefix sharing, and a preempt-to-queue eviction cycle. Plus
the block-pool lifecycle invariants (refcount torture), the paged Pallas
kernel's parity against the gather-then-dense oracle, block-budget
admission, priority lanes, pool telemetry on the serve rows, and the
serving warm start through the AOT compile cache."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.generate import generate
from tpudist.models.gpt2 import GPT2
from tpudist.models.llama import Llama
from tpudist.ops.decode import paged_decode_attention
from tpudist.serve import BlockPool, PagedSlotPool, PrefixCache, ServeEngine
from tpudist.serve.blocks import GARBAGE_BLOCK


def _gpt2(max_seq_len=64):
    return GPT2(vocab_size=64, max_seq_len=max_seq_len, hidden_dim=32,
                depth=2, num_heads=4)


def _llama(max_seq_len=64, kv=2):
    return Llama(vocab_size=64, max_seq_len=max_seq_len, hidden_dim=32,
                 depth=2, num_heads=4, num_kv_heads=kv, ffn_dim=64)


def _params(model, seed=0):
    return model.init(
        jax.random.key(seed), np.zeros((1, 8), np.int32), train=False
    )["params"]


def _prompts(lens, vocab=64, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    return [rng.integers(0, vocab, (p,)).astype(np.int32) for p in lens]


def _pool_clean(engine):
    """After a full drain every block the slots held is back on the free
    list; only prefix-cache references may remain, and each of those is
    exactly one reference."""
    pool = engine.pool.blocks
    held = np.nonzero(pool.refcount > 0)[0]
    cached = (set() if engine.pool.prefix is None else
              {e.block for e in engine.pool.prefix._entries.values()})
    assert set(held.tolist()) == cached
    assert all(pool.refcount[b] == 1 for b in cached)


# ---------------------------------------------------------------------------
# equivalence: the acceptance-criterion tests


def test_paged_greedy_matches_static_under_slot_pressure():
    """GPT-2, staggered arrivals, 2 slots for 4 requests: paged greedy
    streams equal the static batch rows bit-for-bit (the same scenario
    test_serve pins for the contiguous engine)."""
    model = _gpt2()
    prompts = np.stack(_prompts([6, 6, 6, 6], seed=1))
    params = _params(model, 1)
    static = generate(model, params, prompts, 10, temperature=0.0)

    eng = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                      block_size=8, watermark_blocks=2)
    rids = [eng.submit(prompts[i], 10) for i in range(2)]
    for _ in range(3):
        eng.step()
    rids += [eng.submit(prompts[i], 10) for i in (2, 3)]
    out = eng.run()
    for i in range(4):
        np.testing.assert_array_equal(out[rids[i]], static[i])
    _pool_clean(eng)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_paged_greedy_mixed_lengths_eos_sweep(family):
    """Mixed prompt lengths crossing block boundaries + per-request stop
    tokens, on both decode families (Llama = GQA + per-row RoPE): every
    paged stream equals the per-request static oracle truncated at its
    returned length."""
    model = _gpt2() if family == "gpt2" else _llama()
    params = _params(model, 2)
    prompts = _prompts([3, 6, 5, 9, 12, 17], seed=3)
    eos = 7
    oracle = {}
    for i, pr in enumerate(prompts):
        toks, lens = generate(model, params, pr[None], 12, temperature=0.0,
                              eos_id=eos, return_lengths=True)
        oracle[i] = toks[0, : lens[0]].tolist()

    eng = ServeEngine(model, params, max_slots=3, seed=0, paged=True,
                      block_size=8, watermark_blocks=2)
    rids = [eng.submit(prompts[i], 12, eos_id=eos) for i in range(3)]
    for _ in range(2):
        eng.step()
    rids += [eng.submit(prompts[i], 12, eos_id=eos) for i in (3, 4, 5)]
    out = eng.run()
    for i in range(6):
        assert out[rids[i]] == oracle[i], (family, i)
    _pool_clean(eng)


def test_paged_eviction_cycle_bit_identical():
    """A pool sized so mid-decode growth runs it dry: the engine must
    preempt a slot to the queue (blocks free NOW) and re-admit it later —
    and every request's greedy stream STILL equals the static oracle
    bit-for-bit through the eviction/replay cycle."""
    model = _gpt2()
    params = _params(model, 1)
    prompts = _prompts([6, 6, 6], seed=5)
    static = {
        i: generate(model, params, p[None], 12, temperature=0.0)[0].tolist()
        for i, p in enumerate(prompts)
    }
    # 3 slots but only 7 usable blocks of 8: three requests at ~18 tokens
    # each need 9 blocks — the third forces a preemption mid-decode
    eng = ServeEngine(model, params, max_slots=3, seed=0, paged=True,
                      block_size=8, n_blocks=8, watermark_blocks=0,
                      prefix_cache=False)
    rids = [eng.submit(p, 12) for p in prompts]
    out = eng.run()
    for i in range(3):
        assert out[rids[i]] == static[i], i
    assert eng.stats.preemptions > 0  # the cycle actually happened
    assert eng.pool.blocks.n_free == eng.pool.blocks.n_usable
    _pool_clean(eng)


def test_cow_divergence_matches_cold_runs():
    """Two requests sharing a 24-token system prompt then diverging: the
    second (cache-hit) admission's tokens are bit-identical to a cold
    run, the prefix cache actually hit, and the shared blocks are mapped
    (not copied) by both physical tables."""
    model = _gpt2()
    params = _params(model, 1)
    system = _prompts([24], seed=9)[0]
    tails = _prompts([4, 7], seed=11)
    full = [np.concatenate([system, t]) for t in tails]
    cold = {
        i: generate(model, params, p[None], 8, temperature=0.0)[0].tolist()
        for i, p in enumerate(full)
    }

    eng = ServeEngine(model, params, max_slots=4, seed=0, paged=True,
                      block_size=8, watermark_blocks=2)
    r0 = eng.submit(full[0], 8)
    out0 = eng.run()
    # the three full system-prompt blocks are now cached (refcount 1)
    assert len(eng.pool.prefix) == 3
    eng.step()  # idle tick: no admissions pending
    r1 = eng.submit(full[1], 8)
    # admit WITHOUT stepping to inspect sharing before retirement
    eng._admit()
    slot = int(np.nonzero(eng.pool.active)[0][0])
    cached_blocks = {e.block for e in eng.pool.prefix._entries.values()}
    mapped = set(eng.pool.tables[slot][: int(eng.pool.fill[slot])].tolist())
    assert len(cached_blocks & mapped) == 3  # shared, not re-written
    out1 = eng.run()
    assert out0[r0] == cold[0]
    assert out1[r1] == cold[1]
    assert eng.stats.prefix_hit_rate is not None
    assert eng.stats.prefix_hit_rate > 0
    _pool_clean(eng)


def test_engine_rerun_deterministic_across_instances():
    """Regression for the XLA:CPU host-buffer aliasing wart: device_put
    zero-copy ALIASES aligned numpy arguments, and under async dispatch
    the decode step could read positions/cursor lanes AFTER the host
    already mutated them in place — corrupting streams per-process-
    deterministically (~80% of processes before _dispatch snapshotted its
    host arrays; this exact scenario reproduced it)."""
    model = _gpt2()
    params = _params(model, 1)
    pr = _prompts([5], seed=105)[0]
    oracle = generate(model, params, pr[None], 10, temperature=0.0)[0].tolist()
    for paged in (False, True):
        for _ in range(2):
            kw = dict(paged=True, block_size=8, watermark_blocks=2) \
                if paged else {}
            eng = ServeEngine(model, params, max_slots=2, seed=0, **kw)
            r = eng.submit(pr, 10)
            assert eng.run()[r] == oracle, paged


# ---------------------------------------------------------------------------
# block pool + prefix cache lifecycle


def test_block_pool_refcount_rules():
    pool = BlockPool(6)
    assert pool.n_usable == 5
    b = pool.alloc()
    assert b != GARBAGE_BLOCK and pool.refcount[b] == 1
    pool.incref(b)
    pool.decref(b)
    assert pool.n_free == 4  # still held once
    pool.decref(b)
    assert pool.n_free == 5  # returned exactly at zero
    with pytest.raises(RuntimeError, match="double free"):
        pool.decref(b)
    with pytest.raises(RuntimeError, match="unallocated"):
        pool.incref(b)
    # exhaustion probes None, never raises
    got = [pool.alloc() for _ in range(6)]
    assert got[-1] is None and all(g is not None for g in got[:-1])


def test_block_pool_garbage_block_reserved():
    pool = BlockPool(4)
    assert GARBAGE_BLOCK not in [pool.alloc() for _ in range(3)]
    with pytest.raises(RuntimeError):
        pool.decref(GARBAGE_BLOCK)


def test_refcount_torture_interleaved_admit_retire_evict():
    """Fragmentation/refcount torture: randomized interleaved admissions
    (shared prefixes), retirements, prefix evictions, and mid-decode
    block growth across many cycles — afterwards, zero leaked and zero
    double-freed blocks, and every remaining reference is a prefix-cache
    entry at refcount exactly 1 (slot references all returned)."""
    model = _gpt2(max_seq_len=64)
    params = _params(model, 0)
    eng = ServeEngine(model, params, max_slots=4, seed=0, paged=True,
                      block_size=8, n_blocks=24, watermark_blocks=1)
    rng = np.random.Generator(np.random.PCG64(42))
    shared = _prompts([16], seed=77)[0]
    live = []
    for cycle in range(60):
        roll = rng.random()
        if roll < 0.5 and len(live) < 10:
            plen = int(rng.integers(3, 20))
            if rng.random() < 0.5:
                pr = np.concatenate(
                    [shared, rng.integers(0, 64, (plen,)).astype(np.int32)]
                )
            else:
                pr = rng.integers(0, 64, (plen,)).astype(np.int32)
            budget = int(rng.integers(1, 12))
            try:
                live.append(eng.submit(pr, budget))
            except ValueError:
                pass  # request can never fit this pool: fine
        elif roll < 0.8:
            eng.step()
        else:
            eng.pool.evict_prefix(int(rng.integers(1, 3)))
        # invariant at every point: free + referenced = usable
        pool = eng.pool.blocks
        assert pool.n_free + int((pool.refcount > 0).sum()) == pool.n_usable
    eng.run()
    _pool_clean(eng)
    # the cache's own refs die at refcount 0 too
    eng.pool.evict_prefix(len(eng.pool.prefix or ()) or 1)
    if eng.pool.prefix is not None:
        eng.pool.prefix.evict(10_000)
        assert eng.pool.blocks.n_free == eng.pool.blocks.n_usable


def test_prefix_cache_chain_hash_and_lru_leaf_eviction():
    pool = BlockPool(12)
    cache = PrefixCache(pool, block_size=4)
    toks = np.arange(12, dtype=np.int32)
    blocks = [pool.alloc() for _ in range(3)]
    cache.insert(toks, blocks, 0)
    assert len(cache) == 3
    # chained: a matching prefix hits in order; a diverging block-1 chain
    # breaks the walk after block 0
    assert cache.lookup(toks, 12) == blocks
    fork = toks.copy()
    fork[5] = 63
    assert cache.lookup(fork, 12) == blocks[:1]
    # while a "slot" (our alloc refs) maps the blocks, NOTHING evicts
    assert cache.evict(3) == 0
    for b in blocks:  # the slot releases: cache-only refs remain
        pool.decref(b)
    # eviction takes LRU LEAVES only: the chain tail goes first, a
    # mid-chain block is never freed while its child lives
    assert cache.evict(1) == 1
    assert cache.lookup(toks, 12) == blocks[:2]
    assert pool.refcount[blocks[2]] == 0
    assert pool.refcount[blocks[1]] == 1
    # a slot re-mapping a block pins it (and its ancestors) again
    pool.incref(blocks[1])
    assert cache.evict(2) == 0
    assert cache.lookup(toks, 12) == blocks[:2]
    pool.decref(blocks[1])
    assert cache.evict(2) == 2  # tail-first down the chain
    assert cache.lookup(toks, 12) == []
    assert pool.n_free == pool.n_usable


def test_prefix_lookup_caps_at_limit():
    pool = BlockPool(12)
    cache = PrefixCache(pool, block_size=4)
    toks = np.arange(8, dtype=np.int32)
    blocks = [pool.alloc() for _ in range(2)]
    cache.insert(toks, blocks, 0)
    # a 8-token prompt may only consume 7 tokens of cache (the last
    # prompt token must re-run for its logits): one full block, not two
    assert cache.lookup(toks, 7) == blocks[:1]
    assert cache.lookup(toks, 8) == blocks


# ---------------------------------------------------------------------------
# paged slot pool + admission


def test_paged_pool_utilization_reports_block_occupancy():
    """The satellite bug fix: under paged admission `utilization` must be
    BLOCK occupancy, not active/max_slots — one long request in 1 of 4
    slots can hold most of the pool's bytes."""
    model = _gpt2()
    pool = PagedSlotPool(model, 4, n_blocks=9, block_size=8,
                         prefix_cache=False)
    row = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32),
            train=False, decode=True)["cache"]),
    )
    pool.insert(row, 48)  # 6 of 8 usable blocks, one slot of four
    assert pool.n_active == 1
    assert pool.utilization == pytest.approx(6 / 8)   # byte truth
    assert pool.n_active / pool.max_slots == 0.25     # the old reading


def test_paged_pool_validation():
    model = _gpt2()
    with pytest.raises(ValueError, match="block_size"):
        PagedSlotPool(model, 2, n_blocks=8, block_size=7)
    with pytest.raises(ValueError, match="n_blocks"):
        PagedSlotPool(model, 2, n_blocks=1, block_size=8)


def test_submit_rejects_never_fitting_request():
    model = _gpt2()
    params = _params(model, 0)
    eng = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                      block_size=8, n_blocks=4)  # 3 usable blocks = 24 toks
    with pytest.raises(ValueError, match="raise n_blocks"):
        eng.submit(_prompts([20])[0], 10)


def test_block_budget_admission_stalls_then_drains():
    """Admission is block-budget, not slot-count: with slots free but the
    pool near-full, the queued request waits; decode retirements free
    blocks and it admits on a later tick — no deadlock, full drain."""
    model = _gpt2()
    params = _params(model, 0)
    eng = ServeEngine(model, params, max_slots=4, seed=0, paged=True,
                      block_size=8, n_blocks=6, watermark_blocks=1,
                      prefix_cache=False)
    a = eng.submit(_prompts([10], seed=1)[0], 6)   # 2 blocks + growth
    b = eng.submit(_prompts([10], seed=2)[0], 6)
    eng.step()
    # pool: 5 usable, slot a holds 2; b needs 2 + watermark 1 → admitted;
    # a third long prompt cannot admit until someone retires
    c = eng.submit(_prompts([16], seed=3)[0], 4)
    depths = []
    while eng.pending:
        eng.step()
        depths.append(eng.queue_depth)
    assert max(depths[:1] + [0]) <= 1  # c queued at first
    out_lens = {r: len(eng.result(r)) for r in (a, b, c)}
    assert out_lens == {a: 6, b: 6, c: 4}
    _pool_clean(eng)


def test_one_token_admission_releases_prefix_pins():
    """Regression: an admission that completes at its first sample
    (max_new_tokens=1 / instant EOS) never takes a slot — it must still
    release the refcount pins admission placed on its prefix-cache hits,
    or the hit blocks stay elevated forever (unevictable, never freed:
    the pool shrinks monotonically under one-token traffic)."""
    model = _gpt2()
    params = _params(model, 1)
    system = _prompts([16], seed=9)[0]  # two full 8-token blocks
    eng = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                      block_size=8)
    # seed the prefix cache with the system prompt's blocks
    first = eng.submit(np.concatenate([system, _prompts([4], seed=1)[0]]), 4)
    eng.run()
    assert len(eng.result(first)) == 4
    # a burst of one-token requests, every one hitting the cached prefix
    for s in range(5):
        rid = eng.submit(
            np.concatenate([system, _prompts([4], seed=20 + s)[0]]), 1
        )
        eng.run()
        assert len(eng.result(rid)) == 1
    assert eng.stats.prefix_hit_rate > 0  # the hits actually happened
    _pool_clean(eng)
    # and the cached blocks remain evictable: a full eviction drains the
    # pool back to empty
    eng.pool.evict_prefix(eng.pool.blocks.n_usable)
    assert eng.pool.blocks.n_free == eng.pool.blocks.n_usable


def test_idle_pool_waives_watermark():
    """Regression: a request whose need_new + watermark exceeds the pool
    must still admit when the pool is IDLE (nothing decoding, nothing to
    thrash against) — otherwise it sits at the head of its lane forever
    and run() livelocks even though submit() verified it fits."""
    model = _gpt2()
    params = _params(model, 0)
    # 7 usable blocks; request needs 3 (prompt 10 + 6 new = 16 tokens);
    # watermark 6 makes need_new + watermark = 9 > 7 on an empty pool
    eng = ServeEngine(model, params, max_slots=4, seed=0, paged=True,
                      block_size=8, n_blocks=8, watermark_blocks=6,
                      prefix_cache=False)
    rid = eng.submit(_prompts([10], seed=2)[0], 6)
    out = eng.run()  # must terminate
    assert len(out[rid]) == 6
    _pool_clean(eng)


def test_full_hit_replay_resumes_without_prefill():
    """A replay re-admission whose ENTIRE K/V (prompt + replay[:-1], a
    block multiple) is prefix-cached runs no prefill and no scatter —
    the slot maps the shared blocks directly — and the resumed stream
    still matches the static oracle's suffix. Pins the row_cache=None
    fast path in _admit."""
    from tpudist.serve.engine import Request

    model = _gpt2()
    params = _params(model, 1)
    prompt = _prompts([16], seed=11)[0]  # 2 full 8-token blocks
    static = generate(model, params, prompt[None], 12,
                      temperature=0.0)[0].tolist()

    eng = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                      block_size=8)
    # seed the cache with the exact 24-token kv the replay will need
    warm = eng.submit(np.concatenate([prompt, np.asarray(static[:8],
                                                         np.int32)]), 2)
    eng.run()
    assert len(eng.result(warm)) == 2
    # inject a preempted-shape request: 9 tokens already emitted, so
    # kv = prompt + static[:8] = 24 tokens = 3 blocks, all cached
    rid = eng._next_id
    eng._next_id += 1
    req = Request(rid, prompt, 12, replay_tokens=tuple(static[:9]))
    eng._lanes.setdefault(0, __import__("collections").deque()).append(req)
    eng._counts[rid] = 9
    eng._live_toks[rid] = list(static[:9])
    eng._results[rid] = list(static[:9])
    eng.stats.on_submit(rid)
    eng._t_submit[rid] = eng.stats._clock()
    out = eng.run()
    assert out[rid] == static, "replay suffix diverged"
    _pool_clean(eng)


def test_paged_kernel_engine_greedy_matches_static():
    """The whole engine through the paged Pallas KERNEL path (any
    non-"xla" attn_impl dispatches it; interpret mode on CPU): greedy
    streams still equal the static xla-model oracle bit-for-bit."""
    kmodel = GPT2(vocab_size=64, max_seq_len=64, hidden_dim=32, depth=2,
                  num_heads=4, attn_impl="fused")
    params = _params(_gpt2(), 1)
    prompts = _prompts([5, 9, 12], seed=6)
    static = {
        i: generate(_gpt2(), params, p[None], 8, temperature=0.0)[0].tolist()
        for i, p in enumerate(prompts)
    }
    eng = ServeEngine(kmodel, params, max_slots=3, seed=0, paged=True,
                      block_size=8, watermark_blocks=2)
    rids = [eng.submit(p, 8) for p in prompts]
    out = eng.run()
    for i in range(3):
        assert out[rids[i]] == static[i], i
    _pool_clean(eng)


def test_priority_lanes_and_ttft_aging():
    """Higher lanes admit first; with ttft_slo_s set, an overdue lower-
    lane head jumps the queue (deadline-driven aging)."""
    model = _gpt2()
    params = _params(model, 0)
    t = [0.0]
    clock = lambda: t[0]
    eng = ServeEngine(model, params, max_slots=1, seed=0, clock=clock)
    pr = _prompts([4])[0]
    lo = eng.submit(pr, 3, priority=0)
    hi = eng.submit(pr, 3, priority=5)
    assert eng._peek_next()[1].request_id == hi
    eng.run()

    eng2 = ServeEngine(model, params, max_slots=1, seed=0, clock=clock,
                       ttft_slo_s=1.0)
    lo = eng2.submit(pr, 3, priority=0)
    t[0] += 5.0  # lo is now overdue
    hi = eng2.submit(pr, 3, priority=5)
    assert eng2._peek_next()[1].request_id == lo
    eng2.run()


# ---------------------------------------------------------------------------
# paged write + kernel


def _paged_fixture(seed, b, h, h_kv, dh, bs, n_blocks, mb, max_pos):
    rng = np.random.Generator(np.random.PCG64(seed))
    q = rng.standard_normal((b, 1, h, dh)).astype(np.float32)
    k_pool = rng.standard_normal((n_blocks, h_kv, bs, dh)).astype(np.float32)
    v_pool = rng.standard_normal((n_blocks, h_kv, bs, dh)).astype(np.float32)
    # distinct physical blocks per row, deliberately non-contiguous
    perm = rng.permutation(n_blocks - 1)[: b * mb] + 1
    tables = perm.reshape(b, mb).astype(np.int32)
    pos = rng.integers(0, max_pos, (b,)).astype(np.int32)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(pos))


@pytest.mark.parametrize("gqa", [1, 2])
def test_paged_kernel_matches_dense_oracle(kernel_parity, gqa):
    """The paged Pallas kernel (interpret mode on CPU) against the
    gather-then-dense oracle across rows whose cursors sit at block
    starts, block ends, and mid-block — including GQA head grouping."""
    h = 4
    q, k, v, bt, pos = _paged_fixture(
        0, b=5, h=h, h_kv=h // gqa, dh=16, bs=8, n_blocks=64, mb=4,
        max_pos=31,
    )
    # pin the edge cursors explicitly: first slot of a block, last slot
    pos = pos.at[0].set(0).at[1].set(7).at[2].set(8).at[3].set(31)
    got = paged_decode_attention(q, k, v, bt, pos, impl="paged")
    want = paged_decode_attention(q, k, v, bt, pos, impl="xla")
    kernel_parity(got, want)


def test_paged_kernel_large_batch_ok(kernel_parity):
    """No FUSED_MAX_BATCH-style ceiling: the paged kernel's grid scales
    with batch (the dense path's crossover was about gather bytes the
    paged walk never reads)."""
    q, k, v, bt, pos = _paged_fixture(
        1, b=24, h=4, h_kv=2, dh=16, bs=8, n_blocks=128, mb=4, max_pos=31
    )
    got = paged_decode_attention(q, k, v, bt, pos, impl="paged")
    want = paged_decode_attention(q, k, v, bt, pos, impl="xla")
    kernel_parity(got, want)


def test_paged_write_lands_in_mapped_block():
    """cached_kv's paged branch writes each row's K/V at
    (table[pos // bs], pos % bs) in the shared pool and nowhere else —
    pinned through the model decode step by comparing a paged engine
    slot's gathered window against the contiguous engine's slot rows
    after identical traffic."""
    model = _gpt2()
    params = _params(model, 3)
    pr = _prompts([9], seed=4)[0]
    cont = ServeEngine(model, params, max_slots=2, seed=0)
    paged = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                        block_size=8, watermark_blocks=2)
    rc, rp = cont.submit(pr, 6), paged.submit(pr, 6)
    for _ in range(3):
        cont.step()
        paged.step()
    n = int(cont.pool.positions[0])
    assert n == int(paged.pool.positions[0])
    fill = int(paged.pool.fill[0])
    row = paged.pool.gather_row(
        [int(x) for x in paged.pool.tables[0][:fill]]
    )
    for lc, lp in zip(jax.tree_util.tree_leaves(cont.pool.cache),
                      jax.tree_util.tree_leaves(row)):
        if getattr(lc, "ndim", 0) == 4:
            np.testing.assert_array_equal(
                np.asarray(lc)[0, :, :n], np.asarray(lp)[0, :, :n]
            )
    cont.run(), paged.run()


# ---------------------------------------------------------------------------
# telemetry + warm start


def test_serve_rows_carry_pool_fields(tmp_path):
    from tpudist.telemetry import TelemetrySink

    model = _gpt2()
    params = _params(model, 0)
    path = tmp_path / "serve.jsonl"
    sink = TelemetrySink(str(path))
    eng = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                      block_size=8, sink=sink, stats_every=2)
    system = _prompts([16], seed=6)[0]
    for t in _prompts([3, 5], seed=8):
        eng.submit(np.concatenate([system, t]), 4)
    eng.run()
    sink.close()
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    serve = [r for r in rows if r["kind"] == "serve"]
    summary = [r for r in rows if r["kind"] == "serve_summary"]
    assert serve and summary
    for r in serve + summary:
        assert "pool_occupancy" in r
        assert "prefix_hit_rate" in r
        assert "preemptions" in r
    assert summary[-1]["pool_occupancy"] is not None
    assert summary[-1]["prefix_hit_rate"] is not None
    assert summary[-1]["preemptions"] == 0
    # contiguous rows keep the fields (null occupancy/hit rate): one
    # schema, docs/OBSERVABILITY.md §1
    path2 = tmp_path / "serve2.jsonl"
    sink2 = TelemetrySink(str(path2))
    eng2 = ServeEngine(model, params, max_slots=2, seed=0, sink=sink2,
                       stats_every=2)
    eng2.submit(_prompts([4])[0], 4)
    eng2.run()
    sink2.close()
    rows2 = [json.loads(l) for l in path2.read_text().splitlines()]
    s2 = [r for r in rows2 if r["kind"] == "serve_summary"][-1]
    assert s2["pool_occupancy"] is None
    assert s2["prefix_hit_rate"] is None


def test_compile_cache_warm_start(tmp_path):
    """ServeEngine(compile_cache=dir): cold construction AOT-compiles and
    stores the decode + per-bucket prefill programs; a second engine with
    the same weights/geometry loads every one (hits == cold misses > 0)
    and produces bit-identical output."""
    model = _gpt2()
    params = _params(model, 1)
    pr = _prompts([5, 9], seed=7)
    outs = {}
    infos = {}
    for tag in ("cold", "warm"):
        eng = ServeEngine(model, params, max_slots=2, seed=0, paged=True,
                          block_size=8, compile_cache=str(tmp_path))
        infos[tag] = eng.compile_cache_info
        rids = [eng.submit(p, 6) for p in pr]
        out = eng.run()
        outs[tag] = [out[r] for r in rids]
    assert infos["cold"]["misses"] > 0 and infos["cold"]["hits"] == 0
    assert infos["warm"]["hits"] == infos["cold"]["misses"]
    assert infos["warm"]["misses"] == 0
    assert outs["cold"] == outs["warm"]


def test_compile_cache_misses_on_new_weights(tmp_path):
    """The fingerprint covers param VALUES: an engine over different
    weights must not load the stale executables (they embed the old
    params as closure constants)."""
    model = _gpt2()
    eng1 = ServeEngine(model, _params(model, 1), max_slots=2, seed=0,
                       compile_cache=str(tmp_path))
    assert eng1.compile_cache_info["misses"] > 0
    eng2 = ServeEngine(model, _params(model, 2), max_slots=2, seed=0,
                       compile_cache=str(tmp_path))
    assert eng2.compile_cache_info["hits"] == 0
    assert eng2.compile_cache_info["misses"] > 0
