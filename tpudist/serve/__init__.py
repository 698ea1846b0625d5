"""Serving subsystem: continuous-batching inference over the decode path.

The training side (PRs 3–8) made the framework fast and resilient; this
package opens the INFERENCE workload the north star names ("serves heavy
traffic from millions of users"). The static ``tpudist.generate`` path —
one jit program, batch-at-once — cannot admit, stream, or retire requests
independently; under real mixed-length arrivals its batch assembly and
longest-row decode dominate latency and waste throughput. The engine here
keeps the decode batch full instead:

- :mod:`tpudist.serve.slots` — slot-pooled KV cache: one pre-allocated
  ``[max_slots, ...]`` cache with per-slot cursors/masks; requests join
  and leave between decode steps with zero recompiles.
- :mod:`tpudist.serve.blocks` — PAGED KV cache (``ServeEngine(paged=True)``):
  a shared refcounted block pool with per-slot block tables and a
  content-hashed prefix cache, so HBM holds Σ(actual lengths) instead of
  ``max_slots × max_seq_len`` and shared system prompts pay prefill once
  (docs/SERVING.md "Paged memory").
- :mod:`tpudist.serve.prefill` — chunked prefill compiled at a small set
  of power-of-two bucket lengths, writing prefix K/V into a free slot
  (resumable past a prefix-cache hit).
- :mod:`tpudist.serve.engine` — the scheduler: priority-laned admission
  control (block-budget accounting + preempt-to-queue in paged mode),
  per-slot sampling/stop params, one compiled masked decode step over the
  full slot batch, per-step streaming delivery, optional deploy-time AOT
  program cache (``compile_cache=``).
- :mod:`tpudist.serve.spec` — speculative decoding
  (``ServeEngine(draft_model=...)``): a cheap draft proposes ``spec_k``
  tokens per slot per tick, the target verifies the whole window in ONE
  bulk pass, and acceptance-rejection sampling preserves the target
  distribution exactly — greedy output stays token-identical to the
  non-speculative engine (docs/SERVING.md §6).
- :mod:`tpudist.serve.stats` — TTFT/TPOT percentiles, queue depth, slot
  utilization, block-pool occupancy / prefix hit rate / preemptions,
  speculative acceptance rate, tokens/s as ``serve`` JSONL rows through
  the telemetry sink (docs/OBSERVABILITY.md; architecture in
  docs/SERVING.md).

Quick start::

    from tpudist.serve import ServeEngine
    engine = ServeEngine(model, params, max_slots=8,
                         on_token=lambda ev: print(ev.request_id, ev.token))
    engine.submit(prompt_ids, max_new_tokens=64, temperature=0.7, top_k=50)
    results = engine.run()   # or: for ev in engine.events(): ...
"""

from tpudist.serve.blocks import BlockPool, PagedSlotPool, PrefixCache
from tpudist.serve.engine import (
    NO_EOS,
    QueueFull,
    Request,
    ServeEngine,
    TokenEvent,
)
from tpudist.serve.prefill import Prefiller
from tpudist.serve.slots import SlotPool, write_slot
from tpudist.serve.spec import (
    cache_bytes,
    early_exit_draft,
    speculative_accept,
)
from tpudist.serve.stats import ServeStats

__all__ = [
    "ServeEngine",
    "Request",
    "TokenEvent",
    "QueueFull",
    "NO_EOS",
    "Prefiller",
    "SlotPool",
    "write_slot",
    "BlockPool",
    "PagedSlotPool",
    "PrefixCache",
    "ServeStats",
    "speculative_accept",
    "early_exit_draft",
    "cache_bytes",
]
