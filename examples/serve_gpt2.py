"""Continuous-batching GPT-2 serving (tpudist.serve, docs/SERVING.md).

Streams mixed-length requests through the slot-pooled engine: FIFO
admission, bucketed chunked prefill, one compiled masked decode step over
the slot batch, per-request sampling params, per-token streaming, and
``serve`` telemetry rows (TTFT/TPOT percentiles, queue depth, slot
utilization) next to the run.

    # random weights at the GPT-2 124M geometry (the flags' defaults)
    python examples/serve_gpt2.py --bf16 --requests 8 --slots 4

    # tiny random-weight smoke run (any machine, seconds on CPU)
    python examples/serve_gpt2.py --small --requests 8 --slots 4

    # real GPT-2 124M weights from a local HF checkpoint
    python examples/serve_gpt2.py --init_hf /path/to/gpt2 \
        --prompt "464,3290,373" --prompt "15496,995" --max_new 64 \
        --temperature 0.8 --top_k 50

``--prompt`` takes comma-separated token ids (the repo ships no
tokenizer); without any, mixed-length random prompts exercise the
scheduler.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--init_hf", default=None, type=str,
                   help="LOCAL HF GPT-2 checkpoint dir/file to serve "
                   "(tpudist.interop conversion); default: random params")
    p.add_argument("--vocab_size", default=None, type=int,
                   help="default: 50257, or 256 with --small")
    p.add_argument("--seq_len", default=1024, type=int)
    p.add_argument("--hidden_dim", default=768, type=int)
    p.add_argument("--depth", default=12, type=int)
    p.add_argument("--num_heads", default=12, type=int)
    p.add_argument("--small", action="store_true",
                   help="tiny random geometry (128 wide, 2 deep, byte "
                   "vocab) for a seconds-scale smoke run")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--prompt", action="append", default=None,
                   help="comma-separated token ids; repeatable (one per "
                   "request)")
    p.add_argument("--requests", default=8, type=int,
                   help="synthetic request count when no --prompt is given")
    p.add_argument("--max_new", default=32, type=int)
    p.add_argument("--slots", default=4, type=int,
                   help="KV slot-pool size = the decode batch")
    p.add_argument("--max_queue", default=256, type=int)
    p.add_argument("--temperature", default=0.0, type=float)
    p.add_argument("--top_k", default=0, type=int)
    p.add_argument("--top_p", default=1.0, type=float)
    p.add_argument("--eos_id", default=None, type=int)
    p.add_argument("--spec_draft", default=0, type=int,
                   help="speculative decoding: early-exit draft DEPTH "
                   "(the target's first N blocks, zero extra weight HBM; "
                   "0 = off). The draft proposes --spec_k tokens per slot "
                   "per tick and the target verifies the window in one "
                   "bulk pass — greedy output stays token-identical "
                   "(docs/SERVING.md §6)")
    p.add_argument("--spec_k", default=4, type=int,
                   help="with --spec_draft: proposals per slot per tick")
    p.add_argument("--tensor", default=1, type=int,
                   help="tensor-parallel world: shard the engine (weights "
                   "by their Megatron metadata, KV pools on the KV-head "
                   "dim) over the mesh's 'tensor' axis; num_heads must "
                   "divide it (docs/SERVING.md §7). 1 = single chip")
    p.add_argument("--trace", action="store_true",
                   help="per-request lifecycle span rows on the serve "
                   "telemetry stream (queued/prefill/decode/preempted "
                   "phases per request); stitch into a Perfetto timeline "
                   "with tools/tracelens.py (docs/OBSERVABILITY.md §8)")
    p.add_argument("--metrics_port", default=None, type=int,
                   help="live Prometheus text endpoint on "
                   "http://0.0.0.0:<port>/metrics (0 = ephemeral port)")
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--log_dir", default=".", type=str)
    p.add_argument("--JobID", default="Serve", type=str)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpudist.models.gpt2 import GPT2
    from tpudist.serve import ServeEngine
    from tpudist.telemetry import TelemetrySink
    from tpudist.utils.cache import place_compile_cache

    place_compile_cache()
    small = args.small
    vocab = args.vocab_size or (256 if small else 50257)
    model = GPT2(
        vocab_size=vocab, max_seq_len=args.seq_len,
        hidden_dim=128 if small else args.hidden_dim,
        depth=2 if small else args.depth,
        num_heads=4 if small else args.num_heads,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    if args.init_hf:
        from tpudist.interop import load_hf_params

        params = load_hf_params(
            args.init_hf, arch="gpt2", depth=model.depth,
            num_heads=model.num_heads,
        )
    else:
        params = model.init(
            jax.random.key(args.seed), np.zeros((1, 8), np.int32),
            train=False,
        )["params"]
    if args.bf16:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params,
        )

    rng = np.random.Generator(np.random.PCG64(args.seed))
    if args.prompt:
        prompts = [
            np.asarray([int(t) for t in s.split(",")], np.int32)
            for s in args.prompt
        ]
    else:
        prompts = [
            rng.integers(0, vocab, (int(rng.integers(4, 64)),)).astype(np.int32)
            for _ in range(args.requests)
        ]

    sink = TelemetrySink(
        os.path.join(args.log_dir, f"{args.JobID}_serve_0.jsonl")
    )
    spec_kw = {}
    if args.spec_draft:
        from tpudist.serve import early_exit_draft

        draft_model, draft_params = early_exit_draft(
            model, params, args.spec_draft
        )
        spec_kw = dict(draft_model=draft_model, draft_params=draft_params,
                       spec_k=args.spec_k)
    mesh_kw = {}
    if args.tensor > 1:
        from tpudist import mesh as mesh_lib

        # the engine refuses loudly when num_heads (or a GQA model's KV
        # heads) doesn't divide the tensor world — surface that before
        # any weights move
        mesh_kw = {"mesh": mesh_lib.create_mesh(
            mesh_lib.MeshConfig(tensor=args.tensor)
        )}
    engine = ServeEngine(
        model, params, max_slots=args.slots, max_queue=args.max_queue,
        seed=args.seed, sink=sink, stats_every=10, trace=args.trace,
        metrics_port=args.metrics_port, **spec_kw, **mesh_kw,
    )
    if engine.metrics_port is not None:
        print(f"metrics: http://0.0.0.0:{engine.metrics_port}/metrics")
    rids = [
        engine.submit(
            pr, args.max_new, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, eos_id=args.eos_id,
        )
        for pr in prompts
    ]
    # streaming consumption: tokens print as slots produce them,
    # interleaved across requests — the continuous-batching shape
    for ev in engine.events():
        print(f"  r{ev.request_id} +{ev.token}" + (" [done]" if ev.done else ""))
    for r in rids:
        print(f"request {r}: {len(engine.result(r))} tokens -> "
              f"{engine.result(r)}")
    results = [engine.result(r) for r in rids]
    snap = engine.stats.snapshot()
    engine.close()
    sink.close()
    from tpudist.serve.stats import fmt_s

    print(
        f"\nserved {snap['completed']} requests, {snap['tokens']} tokens in "
        f"{snap['wall_s']:.2f}s ({snap['tokens_per_sec']:.1f} tok/s)\n"
        f"TTFT p50/p95 {fmt_s(snap['ttft_p50'])}/{fmt_s(snap['ttft_p95'])}s, "
        f"TPOT p50/p95 {fmt_s(snap['tpot_p50'], 1e3, 1)}/"
        f"{fmt_s(snap['tpot_p95'], 1e3, 1)}ms, "
        f"slot utilization {fmt_s(snap['slot_utilization'], digits=2)}\n"
        + (
            f"speculative: {snap['spec_accepted']}/{snap['spec_drafted']} "
            "drafts accepted (rate "
            f"{fmt_s(snap['spec_acceptance_rate'], digits=2)})\n"
            if args.spec_draft else ""
        )
        + f"serve telemetry: {sink.path}"
    )
    return snap, results


if __name__ == "__main__":
    main()
