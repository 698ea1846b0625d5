"""T5 span-corruption pretraining — the encoder-decoder counterpart of
train_gpt2.py / train_bert.py.

Same data format (flat token stream, ``.bin``/``.npy`` memmap), same
observability contract (TSV metrics, windowed profiler, TrainTime), same
multi-host launch (``python -m tpudist.launch ... examples/train_t5.py``).
The model vocabulary is the corpus vocabulary plus a reserved block at the
top for the span sentinels and EOS (tpudist.models.t5's fixed-count
corruption), and each gathered window is corrupted on the host
(span_corrupt_transform) into static-shape (encoder, decoder, targets)
triples — no padding, no masks.

No reference counterpart (SURVEY.md §2.12 — the reference has one model);
this is capability surface beyond the baseline ladder.

    # byte-level corpus, t5-small-ish geometry, bf16:
    python examples/train_t5.py --tokens corpus.bin --vocab_size 256 \
        --bf16 --batch_size 16 --JobID T5 --eval
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable as a plain script from anywhere: put the repo root (one level up)
# on sys.path when tpudist isn't pip-installed
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--local_rank", type=int,
                   default=int(os.environ.get("LOCAL_RANK", 0)))
    p.add_argument("--tokens", required=True,
                   help=".bin (raw little-endian) or .npy flat token stream")
    p.add_argument("--val_tokens", default=None)
    p.add_argument("--token_dtype", default="uint16")
    p.add_argument("--vocab_size", default=256, type=int,
                   help="CORPUS vocabulary; the model reserves sentinel/EOS "
                   "ids in a block ABOVE it")
    p.add_argument("--seq_len", default=512, type=int,
                   help="window length BEFORE corruption")
    p.add_argument("--density", default=0.15, type=float,
                   help="fraction of each window corrupted")
    p.add_argument("--mean_span", default=3.0, type=float)
    p.add_argument("--batch_size", default=16, type=int,
                   help="per data-parallel replica (reference semantics)")
    p.add_argument("--hidden_dim", default=512, type=int)
    p.add_argument("--ffn_dim", default=1024, type=int)
    p.add_argument("--enc_depth", default=8, type=int)
    p.add_argument("--dec_depth", default=8, type=int)
    p.add_argument("--num_heads", default=6, type=int)
    p.add_argument("--epochs", default=1, type=int)
    p.add_argument("--total_steps", default=0, type=int)
    p.add_argument("--lr", default=1e-3, type=float)
    p.add_argument("--warmup_steps", default=0, type=int)
    p.add_argument("--optimizer", default="adam")
    p.add_argument("--weight_decay", default=0.0, type=float)
    p.add_argument("--clip_norm", default=None, type=float)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--amp", action="store_true",
                   help="bf16 policy + non-finite update guard (tpudist.amp)")
    p.add_argument("--grad_accum", default=1, type=int)
    p.add_argument("--tensor", default=1, type=int,
                   help="Megatron TP degree over the 'tensor' mesh axis")
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--JobID", default="T5_0", type=str)
    p.add_argument("--log_dir", default=".", type=str)
    p.add_argument("--no_profiler", action="store_true")
    p.add_argument("--checkpoint_dir", default=None, type=str)
    p.add_argument("--checkpoint_every", default=0, type=int)
    p.add_argument("--no_resume", action="store_true")
    p.add_argument("--eval", action="store_true",
                   help="span-denoising loss + in-span token accuracy on "
                   "the held-out stream (or the train stream in order)")
    p.add_argument("--generate", action="store_true",
                   help="after training, greedily DENOISE one held-out "
                   "window with the KV-cache decoder "
                   "(tpudist.generate.generate_seq2seq) and report the "
                   "generated vs true span targets")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import time

    import jax.numpy as jnp
    import numpy as np

    from tpudist import init_from_env
    from tpudist import mesh as mesh_lib
    from tpudist.data.lm import TokenWindowLoader, load_token_stream
    from tpudist.models.t5 import (
        T5, seq2seq_forward, span_corrupt_transform, span_corruption_plan,
    )
    from tpudist.optim import make_optimizer, run_schedule
    from tpudist.train import fit

    ctx = init_from_env()
    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=-1, tensor=args.tensor)
    )
    dtype = jnp.bfloat16 if (args.bf16 or args.amp) else jnp.float32

    # the sentinel/EOS block sits above the corpus vocab: spans sentinels
    # plus one EOS id (span_corruption_plan fixes `spans` per seq_len)
    _, spans, enc_len, dec_len = span_corruption_plan(
        args.seq_len, density=args.density, mean_span=args.mean_span
    )
    model_vocab = args.vocab_size + spans + 1
    model = T5(
        vocab_size=model_vocab, hidden_dim=args.hidden_dim,
        ffn_dim=args.ffn_dim, enc_depth=args.enc_depth,
        dec_depth=args.dec_depth, num_heads=args.num_heads, dtype=dtype,
        # generation (--generate) decodes the span targets: start token +
        # dec_len slots in the decoder KV cache
        max_decode_len=dec_len + 1,
    )

    local_replicas = max(
        mesh_lib.data_parallel_size(mesh) // ctx.process_count, 1
    )
    per_process_batch = args.batch_size * local_replicas * args.grad_accum
    corruption = span_corrupt_transform(
        model_vocab, density=args.density, mean_span=args.mean_span,
        seed=args.seed + ctx.process_index,
    )
    loader = TokenWindowLoader(
        args.tokens, per_process_batch, args.seq_len,
        dtype=np.dtype(args.token_dtype), vocab_size=args.vocab_size,
        num_replicas=ctx.process_count, rank=ctx.process_index,
        transform=corruption,
    )

    steps_per_epoch = len(loader)
    total = args.total_steps or args.epochs * steps_per_epoch
    tx = make_optimizer(
        run_schedule(args.lr, total_steps=total,
                     warmup_steps=args.warmup_steps),
        optimizer=args.optimizer,
        weight_decay=args.weight_decay, clip_norm=args.clip_norm,
        skip_nonfinite_updates=args.amp,
    )

    dp = mesh_lib.data_parallel_size(mesh)
    t0 = time.time()
    state, losses = fit(
        model, tx, loader,
        epochs=args.epochs, mesh=mesh, seed=args.seed,
        job_id=args.JobID, batch_size=args.batch_size,
        world_size=dp, global_rank=ctx.process_index,
        input_key="enc_tokens", label_key="targets",
        forward_loss=seq2seq_forward(model),
        grad_accum=args.grad_accum,
        profile=not args.no_profiler, log_dir=args.log_dir,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=not args.no_resume,
        # two-stream init: fit's probe only covers batch[input_key]
        init_input=(
            jnp.zeros((dp, enc_len), jnp.int32),
            jnp.zeros((dp, dec_len), jnp.int32),
        ),
    )
    wall = time.time() - t0
    if losses and ctx.process_index == 0:
        seqs = len(losses) * args.batch_size * dp * args.grad_accum
        print(
            f"tokens/sec: {seqs * args.seq_len / wall:.1f} "
            f"(global, incl. compile) steps={len(losses)} "
            f"final_loss={losses[-1]:.4f}"
        )

    if args.eval:
        import jax

        source = load_token_stream(
            args.val_tokens or args.tokens, dtype=np.dtype(args.token_dtype)
        )
        val_corruption = span_corrupt_transform(
            model_vocab, density=args.density, mean_span=args.mean_span,
            seed=args.seed + 10_000,
        )
        val_loader = TokenWindowLoader(
            source, args.batch_size, args.seq_len,
            vocab_size=args.vocab_size, shuffle=False, drop_remainder=True,
            num_replicas=ctx.process_count, rank=ctx.process_index,
            transform=val_corruption,
        )

        @jax.jit
        def score(params, enc, dec, tgt, row_mask):
            import optax

            logits = model.apply({"params": params}, enc, dec, train=False)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            hit = jnp.argmax(logits, axis=-1) == tgt
            rows = row_mask[:, None]
            return (
                jnp.sum(jnp.where(rows, ce, 0.0)),
                jnp.sum(jnp.where(rows, hit, False)),
                jnp.sum(row_mask) * tgt.shape[1],
            )

        # globally-accounted, like tpudist.train.evaluate/evaluate_lm: each
        # process's (disjoint, rank-sharded) rows are staged as ONE global
        # batch-sharded array padded to the mesh's replica multiple (the
        # pad rows masked out of every sum), so the in-graph sums are
        # global sums and every process sees the same totals — a rank-0
        # print of its local sums would report 1/world of the set on a
        # real multi-host run, and jitting mesh-global params with
        # process-local host arrays can fail outright there. Lockstep
        # holds: drop_remainder=True plus the sampler's stride gives every
        # process the same batch count.
        dp = mesh_lib.data_parallel_size(mesh)
        total_ce, total_hit, total_n = 0.0, 0, 0
        for batch in val_loader:
            arrs = {k: np.asarray(batch[k])
                    for k in ("enc_tokens", "dec_tokens", "targets")}
            n = arrs["targets"].shape[0]
            pad = -n % (dp // ctx.process_count or 1)
            if pad:
                arrs = {
                    k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                    for k, v in arrs.items()
                }
            row_mask = np.arange(n + pad) < n
            dev = mesh_lib.shard_batch(arrs, mesh)
            mask_dev = mesh_lib.put_sharded(
                row_mask, mesh_lib.batch_sharding(mesh, extra_dims=0)
            )
            ce, hit, cnt = score(
                state.params, dev["enc_tokens"], dev["dec_tokens"],
                dev["targets"], mask_dev,
            )
            total_ce += float(ce)
            total_hit += int(hit)
            total_n += int(cnt)
        if ctx.process_index == 0 and total_n:
            print(
                f"span_loss: {total_ce / total_n:.4f} "
                f"span_accuracy: {total_hit / total_n:.4f}"
            )

    if args.generate:
        from tpudist.generate import generate_seq2seq

        # greedily denoise one held-out window with the KV-cache decoder:
        # the generated sequence should reproduce the span targets
        source = load_token_stream(
            args.val_tokens or args.tokens, dtype=np.dtype(args.token_dtype)
        )
        if len(source) < args.seq_len:
            # a short val stream would corrupt to a different dec_len than
            # the model's cache was sized for — refuse with the reason
            # instead of a downstream shape error
            raise SystemExit(
                f"--generate needs a stream of >= --seq_len "
                f"({args.seq_len}) tokens to build one window; "
                f"{args.val_tokens or args.tokens} holds {len(source)}"
            )
        gen_corruption = span_corrupt_transform(
            model_vocab, density=args.density, mean_span=args.mean_span,
            seed=args.seed + 20_000,
        )
        window = np.asarray(source[: args.seq_len], np.int32)[None]
        demo = gen_corruption({"tokens": window})
        out = generate_seq2seq(
            model, state.params, demo["enc_tokens"], dec_len,
            temperature=0.0,
        )
        tgt = demo["targets"][0]
        match = float((out[0] == tgt).mean())
        if ctx.process_index == 0:
            print(f"generated span tokens: {out[0].tolist()}")
            print(f"true span targets:     {tgt.tolist()}")
            print(f"generation_span_match: {match:.4f}")
    return state, losses


if __name__ == "__main__":
    main()
