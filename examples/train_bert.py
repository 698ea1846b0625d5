"""BERT masked-LM pretraining — the encoder counterpart of train_gpt2.py.

Same data format (flat token stream, ``.bin``/``.npy`` memmap), same
observability contract (TSV metrics, windowed profiler, TrainTime), same
multi-host launch (``python -m tpudist.launch ... examples/train_bert.py``).
The model vocabulary is the corpus vocabulary plus one reserved [MASK] id
appended at the top (``--mask_id`` overrides when the tokenizer already has
one), and each gathered window gets BERT's 80/10/10 corruption on the host
(tpudist.models.bert.mlm_transform).

No reference counterpart (SURVEY.md §2.12 — the reference has one model);
this is capability surface beyond the baseline ladder.

    # byte-level corpus, bert-base geometry, bf16:
    python examples/train_bert.py --tokens corpus.bin --vocab_size 256 \
        --bf16 --batch_size 32 --JobID MLM --eval
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
    p.add_argument("--vocab_size", default=30522, type=int,
                   help="CORPUS vocabulary; the model reserves one extra "
                   "[MASK] id above it unless --mask_id is given")
    p.add_argument("--mask_id", default=None, type=int)
    p.add_argument("--seq_len", default=512, type=int)
    p.add_argument("--batch_size", default=32, type=int,
                   help="per data-parallel replica (reference semantics)")
    p.add_argument("--hidden_dim", default=768, type=int)
    p.add_argument("--depth", default=12, type=int)
    p.add_argument("--num_heads", default=12, type=int)
    p.add_argument("--mask_rate", default=0.15, type=float)
    p.add_argument("--epochs", default=1, type=int)
    p.add_argument("--total_steps", default=0, type=int)
    p.add_argument("--lr", default=1e-4, type=float)
    p.add_argument("--warmup_steps", default=0, type=int)
    p.add_argument("--optimizer", default="adam")
    p.add_argument("--weight_decay", default=0.0, type=float)
    p.add_argument("--clip_norm", default=None, type=float)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--grad_accum", default=1, type=int)
    p.add_argument("--chunked_ce", default=0, type=int,
                   help="scan the MLM head over sequence chunks of this "
                   "size (bounds the [B,S,V] logits)")
    p.add_argument("--tensor", default=1, type=int,
                   help="Megatron TP degree over the 'tensor' mesh axis")
    p.add_argument("--cp", default=1, type=int,
                   help="context-parallel degree over the 'seq' mesh axis "
                   "(pair with --attn ring/ulysses/ulysses_flash)")
    p.add_argument("--attn", default="xla",
                   choices=["xla", "flash", "ring", "ulysses", "ulysses_flash"])
    p.add_argument("--scan_layers", action="store_true",
                   help="nn.scan the depth (one traced layer; params stack "
                   "[depth, ...])")
    p.add_argument("--remat_layers", action="store_true",
                   help="checkpoint each scanned layer (requires "
                   "--scan_layers)")
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--JobID", default="Bert0", type=str)
    p.add_argument("--log_dir", default=".", type=str)
    p.add_argument("--no_profiler", action="store_true")
    p.add_argument("--checkpoint_dir", default=None, type=str)
    p.add_argument("--checkpoint_every", default=0, type=int)
    p.add_argument("--no_resume", action="store_true")
    p.add_argument("--eval", action="store_true",
                   help="masked-prediction loss + accuracy on the held-out "
                   "stream (or the train stream in order)")
    p.add_argument("--init_hf", default=None, type=str,
                   help="warm-start from a LOCAL HF BertForMaskedLM "
                   "checkpoint dir (tpudist.interop); sizes must match the "
                   "model flags, and --mask_id should name the tokenizer's "
                   "[MASK] id (BERT-base: 103)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudist import init_from_env
    from tpudist import mesh as mesh_lib
    from tpudist.data.lm import TokenWindowLoader, load_token_stream
    from tpudist.models.bert import Bert, mlm_forward, mlm_transform
    from tpudist.optim import make_optimizer, run_schedule
    from tpudist.train import fit

    ctx = init_from_env()
    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(data=-1, tensor=args.tensor, seq=args.cp)
    )
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    if args.cp > 1 and args.attn not in ("ring", "ulysses", "ulysses_flash"):
        raise SystemExit(
            "--cp needs a sequence-parallel attention: "
            "--attn ring|ulysses|ulysses_flash"
        )

    if args.mask_id is None:
        mask_id, model_vocab = args.vocab_size, args.vocab_size + 1
    else:
        if not 0 <= args.mask_id < args.vocab_size:
            raise SystemExit(
                f"--mask_id {args.mask_id} outside [0, {args.vocab_size})"
            )
        mask_id, model_vocab = args.mask_id, args.vocab_size

    if args.remat_layers and not args.scan_layers:
        raise SystemExit("--remat_layers requires --scan_layers")
    if args.scan_layers and args.init_hf:
        raise SystemExit(
            "--init_hf uses the unrolled layout; convert with "
            "tpudist.models.lm_utils.stack_layers or drop --scan_layers"
        )
    model = Bert(
        vocab_size=model_vocab, max_seq_len=args.seq_len,
        hidden_dim=args.hidden_dim, depth=args.depth,
        num_heads=args.num_heads, dtype=dtype,
        attn_impl=args.attn, mesh=mesh,
        scan_layers=args.scan_layers, remat_layers=args.remat_layers,
    )

    local_replicas = max(
        mesh_lib.data_parallel_size(mesh) // ctx.process_count, 1
    )
    per_process_batch = args.batch_size * local_replicas * args.grad_accum
    corruption = mlm_transform(
        model_vocab, mask_id, mask_rate=args.mask_rate,
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
    )

    init_params = None
    if args.init_hf:
        from tpudist.interop import load_hf_params

        if args.mask_id is None:
            raise SystemExit(
                "--init_hf needs --mask_id (the pretrained tokenizer's "
                "[MASK] id; the +1 reserved-id vocab wouldn't match the "
                "checkpoint)"
            )
        init_params = load_hf_params(
            args.init_hf, arch="bert", depth=args.depth,
            num_heads=args.num_heads,
        )

    batch_spec = None
    if args.cp > 1:
        from jax.sharding import PartitionSpec as P

        bd = (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)
        # every [B, S] key rides sequence-sharded; grad accumulation adds
        # the leading (replicated) microbatch dim
        spec = (
            P(bd, mesh_lib.SEQUENCE_AXIS)
            if args.grad_accum == 1
            else P(None, bd, mesh_lib.SEQUENCE_AXIS)
        )
        batch_spec = {"tokens": spec, "targets": spec, "mlm_mask": spec}

    dp_size = mesh_lib.data_parallel_size(mesh)
    t0 = time.time()
    state, losses = fit(
        model, tx, loader,
        epochs=args.epochs, mesh=mesh, seed=args.seed,
        job_id=args.JobID, batch_size=args.batch_size,
        world_size=dp_size, global_rank=ctx.process_index,
        input_key="tokens", label_key="targets",
        forward_loss=mlm_forward(model, chunk=args.chunked_ce or None),
        grad_accum=args.grad_accum, batch_spec=batch_spec,
        profile=not args.no_profiler, log_dir=args.log_dir,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=not args.no_resume,
        init_params=init_params,
    )
    wall = time.time() - t0
    if losses and ctx.process_index == 0:
        seqs = len(losses) * args.batch_size * dp_size * args.grad_accum
        print(
            f"tokens/sec: {seqs * args.seq_len / wall:.1f} "
            f"(global, incl. compile) steps={len(losses)} "
            f"final_loss={losses[-1]:.4f}"
        )

    if args.eval:
        source = (
            load_token_stream(
                args.val_tokens, dtype=np.dtype(args.token_dtype)
            )
            if args.val_tokens
            else load_token_stream(args.tokens, dtype=np.dtype(args.token_dtype))
        )
        metrics = evaluate_mlm(
            model, state, source, args, mesh, corruption=mlm_transform(
                model_vocab, mask_id, mask_rate=args.mask_rate,
                seed=args.seed + 10_000,
            ),
        )
        if ctx.process_index == 0:
            print(
                f"mlm_loss: {metrics['loss']:.4f} "
                f"masked_accuracy: {metrics['accuracy']:.4f}"
            )
    return state, losses


def evaluate_mlm(model, state, source, args, mesh, *, corruption):
    """Masked-prediction CE + top-1 accuracy over a token stream, every
    process scoring its own shard (the shard-safe global-mask accounting of
    tpudist.train.evaluate). Rides the same chunked head as training
    (``--chunked_ce``), so eval never re-creates the [B,S,V] logits peak
    the training path avoided."""
    import jax
    import jax.numpy as jnp

    from tpudist.data.lm import TokenWindowLoader
    from tpudist.models.bert import (
        MlmHead, mlm_head_logits_fn, mlm_head_params,
    )
    from tpudist.models.lm_utils import chunked_head_reduce
    from tpudist.train import _padded_batches

    loader = TokenWindowLoader(
        source, args.batch_size, args.seq_len,
        vocab_size=args.vocab_size, shuffle=False, drop_remainder=False,
        num_replicas=jax.process_count(), rank=jax.process_index(),
        transform=corruption,
    )
    head = MlmHead(dtype=model.dtype)
    chunk = args.chunked_ce or args.seq_len  # one chunk == the full head

    @jax.jit
    def score(params, batch, row_mask):
        hidden = model.apply(
            {"params": params}, batch["tokens"], train=False,
            return_hidden=True,
        )
        pos = (batch["mlm_mask"] & row_mask[:, None]).astype(jnp.float32)
        ce_sum, hit_sum = chunked_head_reduce(
            mlm_head_logits_fn(head), mlm_head_params(params), hidden,
            batch["targets"], pos, chunk, hits=True,
        )
        return ce_sum, hit_sum, jnp.sum(pos)

    total_ce, total_hit, total_pos = 0.0, 0, 0.0
    for batch, row_mask, _ in _padded_batches(loader, mesh, "tokens"):
        ce, hit, pos = score(state.params, batch, row_mask)
        total_ce += float(ce)
        total_hit += int(hit)
        total_pos += float(pos)
    denom = max(total_pos, 1.0)
    return {"loss": total_ce / denom, "accuracy": total_hit / denom}


if __name__ == "__main__":
    main()
