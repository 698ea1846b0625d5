"""GPT-2 language-model training — BASELINE.json config 5 (GPT-2 124M,
DP + gradient accumulation, tokens/sec) and the showcase for the framework's
parallelism axes beyond the reference's DP (SURVEY.md §2.12).

The data/metrics contract matches the reference's trainer
(/root/reference/main.py:86-117) with sequences standing in for images: the
per-rank TSV log keeps the exact header/fields (examples_per_sec counts
sequences), and a final tokens/sec summary is printed for the baseline table.

Launch (single host):

    python examples/train_gpt2.py --batch_size 8 --grad_accum 4 --JobID LM

Parallelism knobs compose on the named mesh:

    --fsdp 4               params+Adam scattered over 'fsdp' (ZeRO-3-style;
                           composes with --tensor/--pipe under a
                           ParallelPlan — tpudist.parallel.plan)
    --tensor 4             Megatron TP over 'tensor'
    --pipe 4 --num_micro 8 microbatch pipelining over 'pipe' (stacked
                           blocks; --pipe_schedule gpipe|1f1b)
    --cp 4 --attn ring     ring-attention context parallelism over 'seq'
    --experts 8            MoE blocks (every other for gpt2, every for
                           llama/Mixtral-style), experts over 'expert'

Multi-host works exactly like main.py: ``python -m tpudist.launch ...``.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

# runnable as a plain script from anywhere: put the repo root (one level up)
# on sys.path when tpudist isn't pip-installed
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--local_rank", type=int, default=int(os.environ.get("LOCAL_RANK", 0)))
    p.add_argument("--batch_size", default=8, type=int,
                   help="per-replica sequences per step (reference semantics)")
    p.add_argument("--JobID", default="GPT2", type=str)
    p.add_argument("--epochs", default=1, type=int)
    p.add_argument("--lr", default=3e-4, type=float)
    p.add_argument("--warmup_steps", default=100, type=int)
    p.add_argument("--total_steps", default=0, type=int,
                   help="schedule horizon; 0 = epochs x steps_per_epoch")
    p.add_argument("--optimizer", default="adam",
                   choices=["adam", "sgd", "lamb", "lion", "muon"])
    p.add_argument("--weight_decay", default=0.1, type=float)
    p.add_argument("--clip_norm", default=1.0, type=float)
    p.add_argument("--grad_accum", default=1, type=int)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--amp", action="store_true",
                   help="mixed precision end-to-end (tpudist.amp): bf16 "
                   "compute policy (implies --bf16) + non-finite update "
                   "guard on the optimizer")
    p.add_argument("--dropout", default=0.0, type=float,
                   help="embedding+residual dropout rate (GPT-2 paper: 0.1)")
    p.add_argument("--remat", default=None, nargs="?", const="full",
                   choices=["none", "full", "dots_saveable", "save_nothing"],
                   help="whole-forward jax.checkpoint under a named policy "
                   "(tpudist.remat; bare --remat = full, the legacy "
                   "behavior)")
    p.add_argument("--remat_policy", default=None,
                   choices=["none", "full", "dots_saveable", "save_nothing"],
                   help="per-BLOCK checkpoint policy on the transformer "
                   "blocks (the deep-model memory lever; works unrolled "
                   "and with --scan_layers)")
    p.add_argument("--shard_opt_state", action="store_true",
                   help="ZeRO-1 cross-replica optimizer-state sharding "
                   "(tpudist.optim.shard_state): Adam mirrors live "
                   "~1/world_size per chip; with --remat_policy this is "
                   "the ~1B-on-16GB recipe (docs/LM_TRAINING.md)")
    p.add_argument("--fused", default="none",
                   choices=["none", "auto", "ln", "optimizer", "all"],
                   help="step-fusion layer: 'ln' = the "
                   "Pallas fused residual-add+LayerNorm kernel in every "
                   "block, 'optimizer' = the one-pass fused-AdamW update "
                   "(+ bf16 compute-copy forward under --bf16; requires "
                   "--optimizer adam), 'all' both, 'auto' whatever the "
                   "model/optimizer support")
    p.add_argument("--chunked_ce", default=0, type=int,
                   help="sequence-chunked weight-tied CE (chunk size); the "
                   "[B,S,V] logits never materialize — raises the max batch/"
                   "seq_len per chip (dense models only)")
    # model family + size
    p.add_argument("--arch", default="gpt2",
                   choices=["gpt2", "llama", "zaya", "kanana", "sdar",
                            "laguna"],
                   help="decoder family: GPT-2 (learned positions, GELU MLP, "
                   "tied head), Llama (RoPE, RMSNorm, SwiGLU, GQA), ZAYA1 "
                   "(compressed convolutional attention, top-1 experts "
                   "routed by an MLP router, no token dropped; --experts, "
                   "--held, --head_dim, --router_width, --ffn_dim) or "
                   "Kanana-2 (latent attention, a leading dense layer, "
                   "sigmoid top-k experts beside shared ones, untied head; "
                   "--experts, --moe_top_k, --held, --head_dim, --rope_dim, "
                   "--kv_rank, --ffn_dim, --dense_ffn_dim, --shared_experts) "
                   "or SDAR (grouped-query attention with q/k norms, softmax "
                   "top-k experts, untied head, trained by diffusion over "
                   "blocks: the noised and the clean copy of a sequence "
                   "through one stack; --block_length, --t_min, --experts, "
                   "--moe_top_k, --held, --num_kv_heads, --head_dim, "
                   "--ffn_dim; the mask token is one id past --vocab_size) "
                   "or Laguna (Laguna-XS.2's geometry: a full-attention "
                   "layer, then three sliding-window ones, in periods — YaRN "
                   "on half of each full head, a gated output in all —, a "
                   "leading dense layer, sigmoid top-8 of 256 experts beside "
                   "a shared one, untied head; --hidden_dim, --depth, "
                   "--vocab_size, --seq_len and --held cut it, and "
                   "--experts, --head_dim, --ffn_dim, --dense_ffn_dim where "
                   "non-zero)")
    p.add_argument("--hidden_dim", default=768, type=int)
    p.add_argument("--depth", default=12, type=int)
    p.add_argument("--num_heads", default=12, type=int)
    p.add_argument("--num_kv_heads", default=0, type=int,
                   help="llama GQA K/V heads (0 = MHA)")
    p.add_argument("--ffn_dim", default=0, type=int,
                   help="llama SwiGLU width (0 = 8/3*hidden rounded to 256)")
    p.add_argument("--head_dim", default=0, type=int,
                   help="zaya: size of a latent attention head; kanana: of "
                   "a value head and of a key's part without position; "
                   "sdar: of a head (0 = hidden_dim / num_heads); laguna: of "
                   "a head (0 = 128)")
    p.add_argument("--rope_dim", default=0, type=int,
                   help="kanana: rotary channels of a key beside its "
                   "--head_dim others (0 = head_dim / 2)")
    p.add_argument("--kv_rank", default=0, type=int,
                   help="kanana: width of the key/value latent (0 = "
                   "hidden_dim / 4)")
    p.add_argument("--dense_ffn_dim", default=0, type=int,
                   help="kanana, laguna: SwiGLU width of the leading dense "
                   "layer (0 = kanana: 3 * hidden_dim; laguna: 8192)")
    p.add_argument("--shared_experts", default=2, type=int,
                   help="kanana: shared experts of width --ffn_dim beside "
                   "the routed ones")
    p.add_argument("--block_length", default=4, type=int,
                   help="sdar: tokens of a diffusion block (--seq_len is a "
                   "multiple of it)")
    p.add_argument("--t_min", default=1e-3, type=float,
                   help="sdar: least noise level of a block, t ~ U[t_min, 1] "
                   "(a masked token's loss weighs 1/t)")
    p.add_argument("--router_width", default=256, type=int,
                   help="zaya: width of the MLP router and of its carry")
    p.add_argument("--held", default="", type=str,
                   help="zaya, kanana, sdar, laguna: 'first,count' — the contiguous experts this "
                   "run holds (one shard's share of an expert-parallel "
                   "layer: the router scores all --experts, tokens of the "
                   "others contribute nothing here); empty = all")
    p.add_argument("--rope_theta", default=10000.0, type=float)
    p.add_argument("--tie_embeddings", action="store_true",
                   help="llama: tie the LM head to the embedding")
    p.add_argument("--scan_layers", action="store_true",
                   help="nn.scan the depth (one traced layer, params stacked "
                   "[depth,...]) — compile time O(1) in depth; dense "
                   "training only")
    p.add_argument("--remat_layers", action="store_true",
                   help="with --scan_layers: checkpoint each layer (store "
                   "boundaries, recompute inside) — the deep-model memory "
                   "lever")
    p.add_argument("--vocab_size", default=50257, type=int)
    p.add_argument("--seq_len", default=1024, type=int)
    # data: a flat token file (.npy, or nanoGPT-style raw .bin) or synthetic
    p.add_argument("--tokens", default=None, type=str,
                   help="flat token file (.npy, or raw .bin read as "
                   "--token_dtype); memory-mapped, never materialized")
    p.add_argument("--token_dtype", default="uint16", type=str,
                   help="dtype of a raw .bin token file (uint16 fits GPT-2's "
                   "50257-entry vocab)")
    p.add_argument("--synthetic_tokens", default=2_000_000, type=int)
    # parallelism (sizes of the mesh axes; data gets the rest)
    p.add_argument("--fsdp", default=1, type=int,
                   help="'fsdp' mesh axis size: every leaf the Megatron/"
                   "pipe metadata leaves replicated (Adam mirrors "
                   "included) is scattered over it and the batch splits "
                   "over data x fsdp — the composed run goes through a "
                   "ParallelPlan (tpudist.parallel.plan)")
    p.add_argument("--tensor", default=1, type=int)
    p.add_argument("--pipe", default=1, type=int)
    p.add_argument("--num_micro", default=8, type=int)
    p.add_argument("--pipe_schedule", default="gpipe",
                   choices=["gpipe", "1f1b"],
                   help="microbatch schedule for --pipe (tpudist.parallel"
                   ".pp): gpipe = reverse-mode through the forward scan; "
                   "1f1b = explicit one-forward-one-backward backward "
                   "ring — same math, stage internals recomputed instead "
                   "of stored (the deep-pipeline activation lever)")
    p.add_argument("--cp", default=1, type=int, help="'seq' (context) axis size")
    p.add_argument("--experts", default=0, type=int, help="MoE experts (0=dense)")
    p.add_argument("--expert_axis", default=0, type=int,
                   help="'expert' mesh axis size (0 → min(experts, devices))")
    p.add_argument("--moe_every", default=0, type=int,
                   help="MoE block cadence: every Nth block is sparse "
                   "(0 = family default: 2 for gpt2, 1/Mixtral for llama)")
    p.add_argument("--moe_top_k", default=2, type=int,
                   help="experts each token is routed to")
    p.add_argument("--capacity_factor", default=1.25, type=float,
                   help="per-expert slot headroom over the balanced load "
                   "(tokens over capacity are dropped to the residual)")
    p.add_argument("--moe_dispatch", default="einsum",
                   choices=["einsum", "index"],
                   help="expert dispatch impl (tpudist.parallel.ep): "
                   "'einsum' = the one-hot oracle, 'index' = slot-index "
                   "gather/scatter + the explicit expert-axis all-to-all "
                   "on a real --expert_axis mesh")
    p.add_argument("--router_z_loss", default=0.0, type=float,
                   help="router z-loss weight (fp32 logit-norm regularizer; "
                   "0 = off, byte-identical trajectory)")
    p.add_argument("--router_jitter", default=0.0, type=float,
                   help="multiplicative router input noise, train only "
                   "(0 = off)")
    p.add_argument("--attn", default="auto",
                   choices=["auto", "xla", "vmem", "flash", "ring", "ulysses",
                            "ulysses_flash"],
                   help="auto picks by context length: the whole-sequence "
                   "VMEM kernel up to 1k, the blockwise flash kernel from "
                   "2k, dense XLA between; XLA is the dense-mask oracle")
    p.add_argument("--init_hf", default=None, type=str,
                   help="warm-start from a LOCAL HF checkpoint dir/file "
                   "(*.safetensors or pytorch_model*.bin) converted via "
                   "tpudist.interop; sizes must match the model flags")
    p.add_argument("--generate", default=0, type=int,
                   help="after training, KV-cache-generate this many tokens "
                   "from the start of the stream (greedy unless --temperature)")
    p.add_argument("--temperature", default=0.0, type=float)
    p.add_argument("--top_k", default=None, type=int)
    p.add_argument("--top_p", default=None, type=float,
                   help="nucleus sampling: keep the smallest token set "
                   "with cumulative probability >= p")
    p.add_argument("--eval", action="store_true",
                   help="after training, report next-token loss + perplexity "
                   "over --val_tokens (or the training stream if unset)")
    p.add_argument("--val_tokens", default=None, type=str,
                   help="held-out token file (.npy/.bin) for --eval")
    p.add_argument("--no_profiler", action="store_true")
    p.add_argument("--telemetry", action="store_true",
                   help="observability subsystem (docs/OBSERVABILITY.md): "
                   "in-step grad/param/update norms + non-finite update "
                   "guard, NaN/divergence sentry with on-demand trace "
                   "capture, step-time breakdown, MFU rows — JSONL stream "
                   "next to the reference TSV")
    p.add_argument("--log_dir", default=".", type=str)
    p.add_argument("--checkpoint_dir", default=None, type=str)
    p.add_argument("--checkpoint_every", default=0, type=int)
    p.add_argument("--no_resume", action="store_true")
    p.add_argument("--elastic", action="store_true",
                   help="resume a checkpoint written at a different world "
                   "size: ZeRO-1 shards reshard onto the live mesh "
                   "(docs/MULTIHOST.md 'Resuming on a different world "
                   "size')")
    p.add_argument("--compile_cache", default=None, type=str,
                   help="AOT executable cache dir (tpudist.compile_cache) "
                   "— a relaunched run deserializes its compiled step "
                   "instead of re-tracing")
    return p.parse_args(argv)


def token_source(args):
    """The flat token stream: a read-only memmap of ``--tokens`` (web-scale
    corpora never materialize) or a synthetic in-memory stand-in."""
    import numpy as np

    from tpudist.data.lm import load_token_stream

    if args.tokens:
        # vocab-range checking happens per gathered batch inside
        # TokenWindowLoader (scanning max() over a multi-billion-token
        # memmap up front would read the whole file)
        return load_token_stream(args.tokens, dtype=np.dtype(args.token_dtype))
    rng = np.random.Generator(np.random.PCG64(0))
    return rng.integers(0, args.vocab_size, args.synthetic_tokens).astype(np.int32)


def main(argv=None):
    args = parse_args(argv)

    import jax

    attn_requested = args.attn  # the user's words, pre-resolution
    if args.attn == "auto":
        # multi_head_attention(impl="auto") would route per-call; resolving
        # here keeps the choice visible in the run's config echo. Matches
        # attention.py's rule (vmem ≤ 1024, dense XLA in the
        # 1025–2047 window, flash from 2048). Off-TPU the Pallas kernels
        # only run in interpret emulation, so CPU runs stay on XLA; inside
        # --pipe the kernels don't compose with the GPipe shard_map
        # (build_model's guard), so auto resolves to XLA there too.
        # (sdar runs both copies of a sequence, 2 x seq_len rows, under a
        # block mask that the flash kernel takes and the vmem kernel not)
        sdar = args.arch == "sdar"
        rows = args.seq_len * (2 if sdar else 1)
        if args.pipe > 1 or jax.default_backend() != "tpu":
            args.attn = "xla"
        elif rows <= 1024 and not sdar:
            args.attn = "vmem"
        elif rows < 2048:
            args.attn = "xla"
        else:
            args.attn = "flash"
    print(f"attn: {attn_requested} -> {args.attn}")
    import jax.numpy as jnp

    from tpudist import init_from_env
    from tpudist import mesh as mesh_lib
    from tpudist.models.gpt2 import GPT2, PipelinedGPT2
    from tpudist.optim import make_optimizer, run_schedule
    from tpudist.train import fit, lm_loss

    cp_attn = args.attn in ("ring", "ulysses", "ulysses_flash")
    if args.generate and cp_attn:
        raise SystemExit(
            f"--attn {args.attn} has no decode path; --generate needs the "
            "xla/flash model"
        )
    if (args.eval or args.generate) and (args.cp > 1 or args.pipe > 1):
        # fail fast, BEFORE the (possibly hours-long) training run: cp
        # eval/decode would need the plain forward, pipe eval batches padded
        # to num_micro — neither is what evaluate_lm/generate does
        raise SystemExit(
            "--eval/--generate support the non-cp, non-pipe paths; rerun "
            "them separately without --cp/--pipe"
        )
    if args.experts and args.init_hf:
        # HF checkpoints are dense; an MoE model's per-block moe/router
        # subtrees have no source weights — fail fast, not mid-warm-start
        raise SystemExit("--init_hf converts dense checkpoints only")
    if args.generate and args.generate >= args.seq_len:
        raise SystemExit(
            f"--generate {args.generate} must be < --seq_len {args.seq_len} "
            "(the KV cache is seq_len slots)"
        )

    ctx = init_from_env()
    n_dev = jax.device_count()
    if args.expert_axis:
        expert_axis = args.expert_axis
    elif args.experts and args.arch not in ("zaya", "kanana", "sdar",
                                            "laguna"):
        # (the dropless layer runs one shard's experts, no exchange)
        # largest axis that divides both the expert count (weights shard
        # evenly) and the devices left over from the other model axes
        avail = max(n_dev // (args.tensor * args.pipe * args.cp), 1)
        expert_axis = max(
            d for d in range(1, min(args.experts, avail) + 1)
            if args.experts % d == 0 and avail % d == 0
        )
    else:
        expert_axis = 1
    if args.fsdp > 1 and args.cp > 1:
        raise SystemExit(
            "--fsdp does not compose with --cp yet (the context-parallel "
            "batch_spec owns the batch layout); drop one"
        )
    mesh = mesh_lib.create_mesh(
        mesh_lib.MeshConfig(
            data=-1, fsdp=args.fsdp, tensor=args.tensor, pipe=args.pipe,
            seq=args.cp, expert=max(expert_axis, 1),
        )
    )
    # the composed-parallelism resolver (tpudist.parallel.plan): engaged
    # when the fsdp axis is real — tensor/pipe-only runs keep the
    # metadata path they always used (identical placements)
    plan = None
    if args.fsdp > 1:
        from tpudist.parallel.plan import ParallelPlan

        plan = ParallelPlan(mesh)
    dtype = jnp.bfloat16 if (args.bf16 or args.amp) else jnp.float32

    def build_model():
        """Model per the CLI flags."""
        if args.pipe > 1:
            # --pipe composes with data AND tensor parallelism (the pipeline
            # shard_map is manual over 'pipe' only; Megatron tensor shardings
            # ride the stacked params under GSPMD — tpudist.parallel.pp);
            # MoE/context-parallel/kernel attention are not pipelined. An
            # EXPLICIT kernel request errors; --attn auto quietly resolves
            # to the supported XLA path inside the pipeline.
            if args.experts or attn_requested not in ("xla", "auto"):
                raise SystemExit(
                    "--pipe composes with --tensor and data parallelism and "
                    "runs XLA attention; MoE/context-parallel/kernel "
                    "attention are not pipelined"
                )
            if args.dropout:
                raise SystemExit("--dropout is not supported with --pipe")
            if args.arch != "gpt2":
                raise SystemExit("--pipe supports the gpt2 arch only")
            if args.scan_layers or args.remat_layers:
                raise SystemExit(
                    "--scan_layers/--remat_layers are not supported with --pipe "
                    "(the pipeline already stacks blocks over the 'pipe' axis)"
                )
            if args.remat_policy:
                raise SystemExit(
                    "--remat_policy is not supported with --pipe (checkpoint "
                    "the whole forward with --remat instead)"
                )
            return PipelinedGPT2(
                mesh, num_micro=args.num_micro, vocab_size=args.vocab_size,
                max_seq_len=args.seq_len, hidden_dim=args.hidden_dim,
                depth=args.depth, num_heads=args.num_heads, dtype=dtype,
                attn_impl=args.attn, schedule=args.pipe_schedule,
            )
        if args.arch == "zaya":
            from tpudist.models.zaya import Zaya
            from tpudist.parallel.ep import Routing

            if args.dropout or args.scan_layers or args.generate or args.init_hf:
                raise SystemExit(
                    "zaya trains unrolled, without dropout; --generate and "
                    "--init_hf have no path for it yet"
                )
            held = tuple(int(n) for n in args.held.split(",")) if args.held else None
            return Zaya(
                vocab_size=args.vocab_size, max_seq_len=args.seq_len,
                hidden_dim=args.hidden_dim, depth=args.depth,
                num_heads=args.num_heads,
                num_kv_heads=args.num_kv_heads or args.num_heads,
                head_dim=args.head_dim or args.hidden_dim // args.num_heads,
                ffn_dim=args.ffn_dim or args.hidden_dim,
                routing=Routing(
                    args.experts or 16, top_k=args.moe_top_k, held=held,
                    router="mlp", router_width=args.router_width,
                ),
                rope_theta=args.rope_theta, remat_policy=args.remat_policy,
                dtype=dtype, attn_impl=args.attn, mesh=mesh,
            )
        if args.arch == "kanana":
            from tpudist.models.kanana import Kanana
            from tpudist.parallel.ep import Routing

            if args.dropout or args.scan_layers or args.generate or args.init_hf:
                raise SystemExit(
                    "kanana trains unrolled, without dropout; --generate "
                    "and --init_hf have no path for it yet"
                )
            held = tuple(int(n) for n in args.held.split(",")) if args.held else None
            head_dim = args.head_dim or args.hidden_dim // args.num_heads
            ffn_dim = args.ffn_dim or args.hidden_dim // 2
            return Kanana(
                vocab_size=args.vocab_size, max_seq_len=args.seq_len,
                hidden_dim=args.hidden_dim, depth=args.depth,
                num_heads=args.num_heads, nope_dim=head_dim,
                rope_dim=args.rope_dim or head_dim // 2, v_dim=head_dim,
                kv_rank=args.kv_rank or args.hidden_dim // 4,
                dense_ffn_dim=args.dense_ffn_dim or 3 * args.hidden_dim,
                ffn_dim=ffn_dim, shared_dim=args.shared_experts * ffn_dim,
                routing=Routing(
                    args.experts or 128, top_k=args.moe_top_k, held=held,
                    scoring="sigmoid", routed_scale=2.448,
                ),
                rope_theta=args.rope_theta, remat_policy=args.remat_policy,
                dtype=dtype, attn_impl=args.attn, mesh=mesh,
            )
        if args.arch == "sdar":
            from tpudist.models.sdar import Sdar
            from tpudist.parallel.ep import Routing

            if (args.dropout or args.scan_layers or args.generate
                    or args.init_hf or args.eval or args.pipe > 1):
                raise SystemExit(
                    "sdar trains unrolled, without dropout; --generate, "
                    "--eval and --init_hf have no path for it yet"
                )
            held = tuple(int(n) for n in args.held.split(",")) if args.held else None
            return Sdar(
                # one row past the corpus's ids: the mask token
                vocab_size=args.vocab_size + 1, max_seq_len=args.seq_len,
                hidden_dim=args.hidden_dim, depth=args.depth,
                num_heads=args.num_heads,
                num_kv_heads=args.num_kv_heads or args.num_heads,
                head_dim=args.head_dim or args.hidden_dim // args.num_heads,
                ffn_dim=args.ffn_dim or args.hidden_dim // 2,
                routing=Routing(args.experts or 128, top_k=args.moe_top_k,
                                held=held),
                block_length=args.block_length,
                rope_theta=args.rope_theta, remat_policy=args.remat_policy,
                dtype=dtype, attn_impl=args.attn, mesh=mesh,
            )
        if args.arch == "laguna":
            from tpudist.models.laguna import PERIOD, laguna_xs_2

            if args.dropout or args.scan_layers or args.generate or args.init_hf:
                raise SystemExit(
                    "laguna trains unrolled, without dropout; --generate "
                    "and --init_hf have no path for it yet"
                )
            # the published geometry, cut by the generic size flags
            base = laguna_xs_2()
            routing = dataclasses.replace(
                base.routing,
                num_experts=args.experts or base.routing.num_experts,
                held=(tuple(int(n) for n in args.held.split(","))
                      if args.held else None),
            )
            head_dim = args.head_dim or base.head_dim
            ffn_dim = args.ffn_dim or base.ffn_dim
            periods = -(-args.depth // len(PERIOD))
            return base.clone(
                vocab_size=args.vocab_size, max_seq_len=args.seq_len,
                hidden_dim=args.hidden_dim, depth=args.depth,
                layer_types=PERIOD * periods,
                heads_per_layer=base.heads_per_layer[:len(PERIOD)] * periods,
                head_dim=head_dim, full_rotary_dim=head_dim // 2,
                ffn_dim=ffn_dim, shared_dim=ffn_dim,
                dense_ffn_dim=args.dense_ffn_dim or base.dense_ffn_dim,
                routing=routing, remat_policy=args.remat_policy,
                dtype=dtype, attn_impl=args.attn, mesh=mesh,
            )
        if args.arch == "llama":
            from tpudist.models.llama import Llama

            if args.dropout:
                raise SystemExit("llama has no dropout (matching the family)")
            if args.scan_layers and (args.generate or args.init_hf or args.experts):
                raise SystemExit(
                    "--scan_layers uses the stacked dense layout; --generate/"
                    "--init_hf/--experts need the unrolled model"
                )
            return Llama(
                vocab_size=args.vocab_size, max_seq_len=args.seq_len,
                hidden_dim=args.hidden_dim, depth=args.depth,
                num_heads=args.num_heads,
                num_kv_heads=args.num_kv_heads or None,
                ffn_dim=args.ffn_dim or None, rope_theta=args.rope_theta,
                tie_embeddings=args.tie_embeddings,
                scan_layers=args.scan_layers, remat_layers=args.remat_layers,
                remat_policy=args.remat_policy,
                num_experts=args.experts,  # Mixtral-style SwiGLU experts
                moe_every=args.moe_every or 1, moe_top_k=args.moe_top_k,
                capacity_factor=args.capacity_factor,
                moe_dispatch=args.moe_dispatch,
                router_z_loss=args.router_z_loss,
                router_jitter=args.router_jitter,
                dtype=dtype, attn_impl=args.attn, mesh=mesh,
            )
        if args.scan_layers and (args.experts or args.generate or args.init_hf):
            raise SystemExit(
                "--scan_layers supports dense training only (no --experts/"
                "--generate/--init_hf: those need the unrolled layout)"
            )
        return GPT2(
            vocab_size=args.vocab_size, max_seq_len=args.seq_len,
            hidden_dim=args.hidden_dim, depth=args.depth,
            num_heads=args.num_heads, dtype=dtype, attn_impl=args.attn,
            num_experts=args.experts, moe_every=args.moe_every or 2,
            moe_top_k=args.moe_top_k, capacity_factor=args.capacity_factor,
            moe_dispatch=args.moe_dispatch,
            router_z_loss=args.router_z_loss,
            router_jitter=args.router_jitter,
            mesh=mesh, dropout=args.dropout,
            scan_layers=args.scan_layers, remat_layers=args.remat_layers,
            remat_policy=args.remat_policy,
        )

    model = build_model()

    from tpudist.data.lm import TokenWindowLoader

    # --batch_size is per data-parallel replica (reference semantics); model-
    # parallel axes (tensor/pipe/seq/expert) don't multiply the batch
    local_replicas = max(
        mesh_lib.data_parallel_size(mesh) // ctx.process_count, 1
    )
    per_process_batch = args.batch_size * local_replicas * args.grad_accum
    corruption = None
    if args.arch == "sdar":
        from tpudist.models.sdar import block_diffusion_transform

        # per-block noise on the host, as train_bert.py's mlm_transform
        corruption = block_diffusion_transform(
            args.vocab_size, args.block_length, t_min=args.t_min,
            seed=ctx.process_index,
        )
    loader = TokenWindowLoader(
        token_source(args), per_process_batch, args.seq_len,
        vocab_size=args.vocab_size, transform=corruption,
        num_replicas=ctx.process_count, rank=ctx.process_index,
    )

    steps_per_epoch = len(loader)
    total = args.total_steps or args.epochs * steps_per_epoch
    # --fused optimizer/all/auto builds the one-pass fused-AdamW update
    # (auto only when the optimizer is adam — it implements the
    # adam/adamw update); under --bf16 it also keeps the bf16 compute
    # copy the fused step's forward reads
    fuse_opt = args.fused in ("optimizer", "all") or (
        args.fused == "auto" and args.optimizer == "adam"
    )
    tx = make_optimizer(
        run_schedule(args.lr, total_steps=total,
                     warmup_steps=args.warmup_steps),
        optimizer=args.optimizer,
        weight_decay=args.weight_decay, clip_norm=args.clip_norm,
        skip_nonfinite_updates=args.amp,
        fused=fuse_opt,
        compute_dtype=dtype if dtype != jnp.float32 else None,
    )

    forward_loss = None
    if args.arch == "sdar":
        from tpudist.models.sdar import block_diffusion_forward

        # the objective is the forward's own: both copies through the stack
        # once, the head (always chunked) over the noised rows only
        forward_loss = block_diffusion_forward(
            model, chunk=args.chunked_ce or 256)
    elif args.chunked_ce:
        from tpudist.models.gpt2 import chunked_lm_forward

        if args.pipe > 1:
            raise SystemExit("--chunked_ce does not compose with --pipe")
        # MoE composes: the chunked scan carries the sowed aux loss
        # (lm_utils applies with 'losses' mutable); router jitter is the
        # one knob it can't serve (no rng stream on the fused path)
        forward_loss = chunked_lm_forward(model, chunk=args.chunked_ce)

    batch_spec = None
    if args.cp > 1:
        from jax.sharding import PartitionSpec as P

        shape = (
            P((mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS), mesh_lib.SEQUENCE_AXIS)
            if args.grad_accum == 1
            else P(None, (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS),
                   mesh_lib.SEQUENCE_AXIS)
        )
        batch_spec = {"tokens": shape}

    init_params = None
    if args.init_hf:
        from tpudist.interop import load_hf_params

        init_params = load_hf_params(
            args.init_hf, arch=args.arch, depth=args.depth,
            num_heads=args.num_heads, num_kv_heads=args.num_kv_heads or None,
        )
        if args.pipe > 1:
            # re-layout the unrolled HF params into the pipelined stacked
            # form (pure re-indexing — same function, now layer-over-stage)
            from flax import linen as nn

            from tpudist.models.gpt2 import stack_gpt2_params

            init_params = nn.meta.unbox(
                stack_gpt2_params(init_params, args.depth)["params"]
            )

    import time

    # throughput accounting counts data-parallel replicas (the reference's
    # world = one replica per GPU); model-parallel axes don't multiply it
    dp_size = mesh_lib.data_parallel_size(mesh)

    t0 = time.time()
    state, losses = fit(
        model, tx, loader,
        epochs=args.epochs, mesh=mesh, plan=plan,
        job_id=args.JobID, batch_size=args.batch_size,
        world_size=dp_size, global_rank=ctx.process_index,
        loss_fn=lm_loss, input_key="tokens", label_key="tokens",
        grad_accum=args.grad_accum, remat=args.remat,
        shard_opt_state=args.shard_opt_state,
        fused=None if args.fused == "none" else args.fused,
        batch_spec=batch_spec, forward_loss=forward_loss,
        profile=not args.no_profiler, log_dir=args.log_dir,
        telemetry=args.telemetry,
        checkpoint_dir=args.checkpoint_dir,
        elastic=args.elastic,
        compile_cache=args.compile_cache,
        checkpoint_every=args.checkpoint_every,
        resume=not args.no_resume,
        init_params=init_params,
    )
    wall = time.time() - t0
    n_steps = len(losses)
    if n_steps and ctx.process_index == 0:
        seqs = n_steps * args.batch_size * dp_size * args.grad_accum
        print(
            f"tokens/sec: {seqs * args.seq_len / wall:.1f} "
            f"(global, incl. compile) steps={n_steps} final_loss={losses[-1]:.4f}"
        )
    if args.amp and ctx.process_index == 0:
        from tpudist.amp import skipped_steps

        skipped = skipped_steps(state.opt_state)
        if skipped:
            print(f"amp: skipped {skipped} non-finite update step(s)")

    if args.generate:
        # EVERY process runs the (collective) jitted decode — params are
        # global arrays; the prompt is identical everywhere (same stream),
        # so outputs agree and only rank 0 prints
        import numpy as np

        from tpudist.generate import generate

        prompt_len = max(1, min(32, args.seq_len - args.generate))
        prompt = np.asarray(token_source(args)[:prompt_len], np.int32)[None]
        out = generate(
            model, state.params, prompt, args.generate,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p,
        )[0]
        if ctx.process_index == 0:
            print(f"generated tokens: {out.tolist()}")
            if args.vocab_size <= 256:
                # byte-level vocab decodes straight back to text
                text = bytes(int(t) % 256 for t in out).decode("utf-8", "replace")
                print(f"generated text: {text!r}")

    if args.eval:
        from tpudist.train import evaluate_lm
        # held-out stream if provided; otherwise the training stream in
        # order (smoke-level perplexity, like the reference's val loader
        # being the train-distribution set, /root/reference/main.py:56-63)
        if args.val_tokens:
            import numpy as np

            from tpudist.data.lm import load_token_stream

            source = load_token_stream(
                args.val_tokens, dtype=np.dtype(args.token_dtype)
            )
        else:
            source = token_source(args)

        # sharded like the train loader so N hosts split the eval work
        # instead of each scoring the full set (the sampler's pad-to-
        # divisible may re-count at most process_count-1 head windows)
        val_loader = TokenWindowLoader(
            source, args.batch_size * local_replicas, args.seq_len,
            vocab_size=args.vocab_size, shuffle=False, drop_remainder=False,
            num_replicas=ctx.process_count, rank=ctx.process_index,
        )
        # same chunked head as training: without it, --eval would re-create
        # the [B,S,V] logits peak that --chunked_ce exists to avoid
        metrics = evaluate_lm(
            model, state, val_loader, mesh, chunk=args.chunked_ce or None
        )
        if ctx.process_index == 0:
            print(
                f"val_loss: {metrics['loss']:.4f} "
                f"perplexity: {metrics['perplexity']:.2f}"
            )
    return state, losses


if __name__ == "__main__":
    main()
