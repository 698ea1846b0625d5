"""Parity entrypoint — the reference's ``main.py`` re-expressed TPU-native.

Same CLI contract as /root/reference/main.py:23-28 (``--local_rank``,
``--batch_size`` default 128, ``--JobID`` default "Job0"), same defaults
(``epochs=2``, ``lr=0.001``, main.py:31-32 — promoted to flags), same
training program (2 epochs of Adam on a ResNet over CIFAR-100 with
global-batch loss/BN, rank-0 TSV logging every 5 steps, console prints
every 10 batches, windowed profiler traces in ``./log_{JobID}``, terminal
``TrainTime`` row) — but the whole per-step pipeline is one pjit-compiled
SPMD program on the TPU mesh instead of eager CUDA ops + NCCL callbacks.

Launch exactly like the reference (README.md:12-35), with
``python -m tpudist.launch`` standing in for ``torch.distributed.launch``:

    # single host (all local TPU chips)
    python main.py --batch_size 128 --JobID Job0

    # multi-host (per host; master = node A)
    python -m tpudist.launch --nnode=2 --node_rank=0 --master_addr=A main.py ...
    python -m tpudist.launch --nnode=2 --node_rank=1 --master_addr=A main.py ...
"""

from __future__ import annotations

import argparse
import os


def parse_args(argv=None):
    parser = argparse.ArgumentParser()
    # flag names/defaults match /root/reference/main.py:23-28
    parser.add_argument("--local_rank", type=int, default=int(os.environ.get("LOCAL_RANK", 0)),
                        help="local process id on this host (launcher-injected)")
    parser.add_argument("--batch_size", default=128, type=int,
                        help="per-replica batch size (reference semantics: per-GPU)")
    parser.add_argument("--JobID", default="Job0", type=str, help="JOB ID")
    # hardcoded in the reference (main.py:31-32); promoted to flags with the
    # same defaults
    parser.add_argument("--epochs", default=2, type=int)
    parser.add_argument("--lr", default=0.001, type=float)
    parser.add_argument("--schedule", default="constant",
                        choices=["constant", "cosine"],
                        help="constant = reference parity (fixed lr, "
                        "main.py:32); cosine adds linear warmup + cosine "
                        "decay over the full run")
    parser.add_argument("--warmup_steps", default=0, type=int,
                        help="warmup steps for --schedule cosine")
    # capability knobs beyond the reference CLI
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet18", "resnet34", "resnet50", "resnet101", "resnet152", "vit_b16", "gpt2"])
    parser.add_argument("--dataset", default="cifar100",
                        choices=["cifar10", "cifar100", "synthetic", "imagenet",
                                 "digits"],
                        help="digits = sklearn's bundled real handwritten-"
                        "digit images (for egress-free convergence runs, "
                        "tpudist/data/digits.py)")
    parser.add_argument("--data_root", default="dataset", type=str,
                        help="CIFAR cache dir, or for --dataset imagenet an "
                        "image-folder tree with train/ and val/ class subdirs")
    parser.add_argument("--synthetic_size", default=2048, type=int)
    parser.add_argument("--image_size", default=224, type=int,
                        help="crop size for --dataset imagenet")
    parser.add_argument("--workers", default=None, type=int,
                        help="decode threads for --dataset imagenet")
    parser.add_argument("--packed", default=None, type=str,
                        help="pre-decoded pack prefix for --dataset imagenet "
                        "(tpudist.data.packed; build once with `python -m "
                        "tpudist.data.packed --root .../train --out X`) — "
                        "streams pixels from a uint8 memmap at memcpy speed "
                        "instead of re-decoding JPEGs every epoch; composes "
                        "with --device_cache (pack staged to HBM, index-only "
                        "steps)")
    parser.add_argument("--packed_val", default=None, type=str,
                        help="pack prefix for the val split (with --eval); "
                        "defaults to the image-folder val/ tree")
    parser.add_argument("--cache_shard_rows", default=0, type=int,
                        help="with --packed --device_cache: rotate the HBM "
                        "cache in shards of this many rows (for packs "
                        "larger than HBM; shard k+1 stages while shard k "
                        "trains — tpudist.data.device_cache."
                        "RotatingDeviceCache). 0 = fully resident")
    parser.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    parser.add_argument("--amp", action="store_true",
                        help="mixed precision END-TO-END (tpudist.amp): the "
                        "bf16 compute policy (implies --bf16) plus the "
                        "non-finite update guard — a gradient spike skips "
                        "one optimizer step (counted) instead of poisoning "
                        "params and Adam moments")
    parser.add_argument("--stem", default="conv7",
                        choices=["conv7", "space_to_depth"],
                        help="ResNet stem; space_to_depth is the MLPerf TPU "
                        "stem (same function class, ~2.5%% faster on v5e)")
    parser.add_argument("--optimizer", default="adam",
                        choices=["adam", "sgd", "lamb", "lion", "muon"],
                        help="reference default: Adam(lr=1e-3), main.py:80")
    parser.add_argument("--weight_decay", default=0.0, type=float,
                        help="decoupled (AdamW) weight decay, 1-D params excluded")
    parser.add_argument("--clip_norm", default=None, type=float,
                        help="global gradient-norm clip")
    def _smoothing_eps(v):
        v = float(v)
        if not 0.0 <= v < 1.0:
            raise argparse.ArgumentTypeError(
                f"label smoothing must be in [0, 1), got {v}"
            )
        return v

    parser.add_argument("--label_smoothing", default=0.0, type=_smoothing_eps,
                        help="smoothed-CE epsilon in [0,1) (ImageNet recipe: "
                        "0.1); 0 = the reference's plain CE (main.py:79)")
    parser.add_argument("--grad_accum", default=1, type=int)
    parser.add_argument("--fused", default="none",
                        choices=["none", "auto", "ln", "optimizer", "all"],
                        help="step-fusion layer: 'ln' = "
                        "Pallas fused residual-add+LayerNorm in the "
                        "transformer blocks (vit_b16), 'optimizer' = the "
                        "one-pass fused-AdamW update (requires --optimizer "
                        "adam; under --bf16 the forward reads its bf16 "
                        "compute copy), "
                        "'all' both, 'auto' whatever model/optimizer "
                        "support")
    parser.add_argument("--reduce", default="none",
                        choices=("none", "bucketed", "quantized", "auto"),
                        help="gradient-reduction path (tpudist.parallel.dp)"
                        ": none = implicit XLA psum (optimal on ICI); "
                        "bucketed = explicit fp32 bucketed all-reduce; "
                        "quantized = int8-on-the-wire with per-bucket "
                        "scales + error feedback (the DCN-bound lever); "
                        "auto = quantized on a "
                        "multi-slice attach, none otherwise")
    parser.add_argument("--fsdp", default=1, type=int,
                        help="'fsdp' mesh axis size (tpudist.parallel.plan)"
                        ": params + Adam mirrors scattered over it, batch "
                        "split over data x fsdp (ZeRO semantics — sharded "
                        "state, DP gradients); >1 runs the whole loop "
                        "under a ParallelPlan")
    parser.add_argument("--augment", action="store_true",
                        help="train augmentation (crop+flip+normalize); "
                        "reference default is ToTensor only. Host-side for "
                        "host loaders; IN-GRAPH (step-keyed crop+flip) with "
                        "--device_cache or --packed")
    parser.add_argument("--device_cache", action="store_true",
                        help="stage the uint8 dataset to HBM once before "
                        "compile and ship only sampler indices per step "
                        "(tpudist/data/device_cache.py) — removes pixels "
                        "from the step's H2D path; incompatible with "
                        "--augment (host-side) and --dataset imagenet "
                        "(streaming)")
    parser.add_argument("--telemetry", action="store_true",
                        help="observability subsystem (tpudist.telemetry): "
                        "in-step health metrics + non-finite update guard, "
                        "NaN/divergence sentry with profiler flight "
                        "recorder, step-time breakdown, MFU rows — a "
                        "per-process JSONL stream next to the TSV "
                        "(docs/OBSERVABILITY.md)")
    parser.add_argument("--health", action="store_true",
                        help="run-health layer on top of --telemetry "
                        "(implied): cross-process straggler aggregation, "
                        "in-graph replica-divergence probe, hang watchdog "
                        "with crash forensics, and a {JobID}_report.json "
                        "end-of-run report (docs/OBSERVABILITY.md §7, "
                        "docs/MULTIHOST.md)")
    parser.add_argument("--trace", action="store_true",
                        help="structured span rows on the telemetry stream "
                        "(tpudist.telemetry.trace; implies --telemetry): "
                        "per-step spans with data-wait/dispatch/device "
                        "breakdown, checkpoint saves, probe/repair/reshard "
                        "markers — and per-request lifecycle spans under "
                        "--serve. Stitch into a Perfetto timeline with "
                        "tools/tracelens.py (docs/OBSERVABILITY.md §8)")
    parser.add_argument("--metrics_port", default=None, type=int,
                        help="live Prometheus text endpoint on "
                        "http://0.0.0.0:<port>/metrics (0 = ephemeral "
                        "port): host-side counters only, no extra device "
                        "syncs (docs/OBSERVABILITY.md §8)")
    parser.add_argument("--hang_timeout", default=300.0, type=float,
                        help="with --health: seconds without a completed "
                        "step before the watchdog dumps thread stacks and "
                        "writes the crash report (keep it above the "
                        "attach's compile time; 0 disables the watchdog)")
    parser.add_argument("--divergence_every", default=200, type=int,
                        help="with --health: steps between replica-"
                        "checksum divergence probes (0 disables the probe)")
    parser.add_argument("--hang_action", default="report",
                        choices=["report", "exit"],
                        help="with --health: what the hang watchdog does "
                        "after writing its crash forensics — 'report' "
                        "(non-fatal, the pre-resilience behavior) or "
                        "'exit' (terminate with the restartable code 76 "
                        "so tpudist.launch relaunches from the last "
                        "checkpoint; docs/MULTIHOST.md)")
    parser.add_argument("--chaos", default=None, type=str,
                        help="fault injection for recovery drills "
                        "(tpudist.resilience.chaos): '<kind>[:<n>]"
                        "@<step>[@<generation>|@*]' with kind in crash/"
                        "hang/sigterm/corrupt/bitflip/nanburst, comma-"
                        "separable — e.g. 'sigterm@50' rehearses a "
                        "preemption, 'bitflip@50' an SDC, "
                        "'bitflip@10,nanburst:3@30' composes an SDC with "
                        "a later spike in one drill")
    parser.add_argument("--repair", action="store_true",
                        help="self-healing loop (tpudist.resilience."
                        "repair, docs/MULTIHOST.md): detector verdicts "
                        "(replica divergence, non-finite skip streaks, "
                        "sustained loss spikes) roll state back to the "
                        "last-known-good ANCHORED checkpoint, skip "
                        "--skip_window batches past the trigger, and "
                        "continue in-process; repeat triggers exit 77 "
                        "for a supervised relaunch, a rolling budget "
                        "circuit-breaks deterministic poison. Needs "
                        "--checkpoint_dir + a save cadence; implies "
                        "--telemetry (combine with --health for the "
                        "SDC/divergence trigger)")
    parser.add_argument("--skip_window", default=8, type=int,
                        help="with --repair: batches skipped past a "
                        "trigger on rollback (the presumed-offending "
                        "data window)")
    parser.add_argument("--keep_last", default=0, type=int,
                        help="checkpoint retention: keep only the newest "
                        "N step dirs (health-anchored steps exempt — "
                        "they are the repair rollback target); 0 keeps "
                        "the legacy orbax max_to_keep=3 behavior")
    parser.add_argument("--serve", action="store_true",
                        help="continuous-batching serving demo "
                        "(tpudist.serve, docs/SERVING.md): a byte-vocab "
                        "GPT-2 with random params streams mixed-length "
                        "synthetic requests through the slot-pooled "
                        "engine, writing serve telemetry rows to "
                        "{log_dir}/{JobID}_serve_0.jsonl and printing the "
                        "TTFT/TPOT/throughput summary")
    parser.add_argument("--serve_requests", default=8, type=int,
                        help="with --serve: number of demo requests")
    parser.add_argument("--serve_slots", default=4, type=int,
                        help="with --serve: KV slot-pool size (the decode "
                        "batch)")
    parser.add_argument("--spec_draft", default=0, type=int,
                        help="with --serve: speculative decoding via an "
                        "early-exit draft of this DEPTH (the target's "
                        "first N blocks sharing its weights, "
                        "tpudist.serve.spec.early_exit_draft; 0 = off). "
                        "Each tick the draft proposes --spec_k tokens per "
                        "slot and the target verifies the window in one "
                        "bulk pass; greedy output is token-identical to "
                        "the non-speculative engine (docs/SERVING.md §6)")
    parser.add_argument("--serve_experts", default=0, type=int,
                        help="with --serve: make every other demo-model "
                        "block a routed top-2 MoE of this many experts "
                        "(tpudist.parallel.ep; 0 = dense). Decode routes "
                        "per generated token; greedy output is identical "
                        "across dispatch impls")
    parser.add_argument("--serve_moe_dispatch", default="einsum",
                        choices=["einsum", "index"],
                        help="with --serve_experts: expert dispatch impl "
                        "(tpudist.parallel.ep)")
    parser.add_argument("--spec_k", default=4, type=int,
                        help="with --spec_draft: draft tokens proposed per "
                        "slot per tick (a slot emits up to spec_k+1 "
                        "tokens per verified sweep)")
    parser.add_argument("--tensor", default=1, type=int,
                        help="with --serve: tensor-parallel world — the "
                        "engine runs sharded over the mesh's 'tensor' "
                        "axis (weights by their Megatron metadata, KV "
                        "pools on the KV-head dim; docs/SERVING.md §7). "
                        "num_heads must divide it; 1 = single chip")
    parser.add_argument("--no_profiler", action="store_true")
    parser.add_argument("--log_dir", default=".", type=str)
    parser.add_argument("--checkpoint_dir", default=None, type=str,
                        help="enable async checkpoint/resume (extension; the "
                        "reference has no persistence, SURVEY.md §5)")
    parser.add_argument("--checkpoint_every", default=0, type=int,
                        help="steps between checkpoints (0 = end of run only)")
    parser.add_argument("--checkpoint_every_s", default=0.0, type=float,
                        help="WALL-CLOCK seconds between checkpoints, "
                        "alongside --checkpoint_every (a save triggers "
                        "when either is due; any save resets this clock, "
                        "the step knob stays step-aligned) — the knob "
                        "that bounds preemption loss to 'at most M "
                        "minutes of work' on runs with variable step "
                        "times (0 = off)")
    parser.add_argument("--no_resume", action="store_true")
    parser.add_argument("--elastic", action="store_true",
                        help="allow a resume whose checkpoint was written "
                        "at a DIFFERENT world size: ZeRO-1 optimizer "
                        "shards reshard onto the live mesh, the "
                        "error-feedback residual restarts zeroed, and the "
                        "step counter/sampler cursor remap to the same "
                        "data position (tpudist.resilience.elastic, "
                        "docs/MULTIHOST.md 'Resuming on a different "
                        "world size')")
    parser.add_argument("--compile_cache", default=None, type=str,
                        help="directory of serialized AOT step "
                        "executables (tpudist.compile_cache): a "
                        "relaunched generation deserializes its compiled "
                        "step — overlapped with the checkpoint restore — "
                        "instead of re-tracing; misses compile at "
                        "bring-up and store for the next life")
    parser.add_argument("--eval", action="store_true",
                        help="run the top-1 eval pass after training — the "
                        "reference's dormant eval loop "
                        "(/root/reference/main.py:119-130), alive")
    return parser.parse_args(argv)


def _serve_demo(args):
    """The --serve demo: the continuous-batching engine end to end on a
    small randomly-initialized byte-vocab GPT-2 — admission, slot reuse,
    per-request sampling params, streaming delivery, and the serve
    telemetry rows, all observable in seconds on CPU (the real-model
    entrypoint is examples/serve_gpt2.py)."""
    import numpy as np

    import jax

    from tpudist.models.gpt2 import GPT2
    from tpudist.serve import ServeEngine
    from tpudist.telemetry import TelemetrySink

    moe_kw = {}
    if args.serve_experts:
        # sparse demo model: every other block routed top-2 MoE; the
        # decode step routes each generated token (capacity auto-sizes
        # to the one-token step, so nothing drops at decode)
        moe_kw = dict(num_experts=args.serve_experts, moe_every=2,
                      moe_dispatch=args.serve_moe_dispatch)
    model = GPT2(vocab_size=256, max_seq_len=256, hidden_dim=128, depth=2,
                 num_heads=4, **moe_kw)
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32), train=False
    )["params"]
    sink = TelemetrySink(
        os.path.join(args.log_dir, f"{args.JobID}_serve_0.jsonl")
    )
    streamed: dict[int, int] = {}

    def on_token(ev):
        streamed[ev.request_id] = streamed.get(ev.request_id, 0) + 1
        if ev.done:
            print(f"request {ev.request_id}: {streamed[ev.request_id]} "
                  "tokens (done)")

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

        # head-divisibility is validated by the engine with a loud
        # ValueError before any weights move
        mesh_kw = {"mesh": mesh_lib.create_mesh(
            mesh_lib.MeshConfig(tensor=args.tensor)
        )}
    engine = ServeEngine(model, params, max_slots=args.serve_slots,
                         sink=sink, stats_every=10, on_token=on_token,
                         trace=args.trace, metrics_port=args.metrics_port,
                         **spec_kw, **mesh_kw)
    if engine.metrics_port is not None:
        print(f"metrics: http://0.0.0.0:{engine.metrics_port}/metrics")
    rng = np.random.Generator(np.random.PCG64(0))
    for i in range(args.serve_requests):
        engine.submit(
            rng.integers(0, 256, (int(rng.integers(4, 48)),)),
            int(rng.integers(8, 48)),
            # alternate greedy and sampled requests: per-slot params share
            # one compiled decode step
            temperature=0.0 if i % 2 == 0 else 0.8,
            top_k=0 if i % 2 == 0 else 50,
        )
    engine.run()
    engine.close()
    sink.close()
    snap = engine.stats.snapshot()
    from tpudist.serve.stats import fmt_s

    print(
        f"served {snap['completed']} requests, {snap['tokens']} tokens in "
        f"{snap['wall_s']:.2f}s ({snap['tokens_per_sec']:.1f} tok/s); "
        f"TTFT p50/p95 {fmt_s(snap['ttft_p50'])}/{fmt_s(snap['ttft_p95'])}s, "
        f"TPOT p50 {fmt_s(snap['tpot_p50'], 1e3, 1)}ms, slot utilization "
        f"{fmt_s(snap['slot_utilization'], digits=2)}"
    )
    if args.spec_draft:
        print(
            f"speculative: {snap['spec_accepted']}/{snap['spec_drafted']} "
            "drafts accepted (rate "
            f"{fmt_s(snap['spec_acceptance_rate'], digits=2)})"
        )
    print(f"serve telemetry: {sink.path}")
    return snap


def main(argv=None):
    args = parse_args(argv)
    if args.serve:
        return _serve_demo(args)

    import jax
    import jax.numpy as jnp

    from tpudist import init_from_env, create_mesh
    from tpudist.data.cifar import load_cifar, synthetic_cifar, to_tensor
    from tpudist.data.loader import DataLoader
    from tpudist.data.sampler import DistributedSampler
    from tpudist.models import (
        resnet18, resnet34, resnet50, resnet101, resnet152, vit_b16,
    )
    from tpudist.train import fit

    ctx = init_from_env()
    plan = None
    if args.fsdp > 1:
        from tpudist.parallel.plan import ParallelPlan

        plan = ParallelPlan.build(data=-1, fsdp=args.fsdp)
        mesh = plan.mesh
    else:
        mesh = create_mesh()

    # --amp = the named policy (fp32 master params, bf16 compute) + the
    # overflow guard on the optimizer below; --bf16 alone = dtype only
    from tpudist.amp import policy_for

    dtype = policy_for(args.bf16 or args.amp).compute_dtype
    # reference keeps the stock 1000-way head even on CIFAR (main.py:40)
    resnets = {"resnet18": resnet18, "resnet34": resnet34, "resnet50": resnet50,
               "resnet101": resnet101, "resnet152": resnet152}
    small = args.dataset != "imagenet"  # 32x32 CIFAR vs 224x224 folder images
    if args.model in resnets:
        model = resnets[args.model](dtype=dtype, stem=args.stem)
    elif args.model == "vit_b16":
        # 4-pixel patches keep 32x32 inputs at 64 tokens; ImageNet crops use
        # the standard 16-pixel patches
        model = vit_b16(dtype=dtype, patch_size=4 if small else 16)
    else:
        raise SystemExit("gpt2 training uses examples/train_gpt2.py (token data)")

    # reference semantics: --batch_size is per-replica (per-GPU, main.py:25);
    # this process's loader yields batch_size × local replicas, and the mesh
    # assembles the global batch of batch_size × world_size
    per_process_batch = args.batch_size * jax.local_device_count()
    input_transform = None  # set by the --device_cache path
    if args.cache_shard_rows and not (
        args.dataset == "imagenet" and args.packed and args.device_cache
    ):
        # guard EVERY dataset path: the rotation only backs the packed HBM
        # cache, and silently ignoring the flag would run a path with a
        # completely different memory/throughput profile
        raise SystemExit(
            "--cache_shard_rows rotates the packed HBM cache and needs "
            "--dataset imagenet --packed <prefix> --device_cache"
        )

    if args.dataset == "imagenet" and args.packed:
        # pre-decoded pack (tpudist.data.packed): pixels stream from a uint8
        # memmap at memcpy speed — the fix for decode-bound hosts;
        # normalization runs in-graph either way (uint8 H2D, 4x less traffic)
        from tpudist.data.packed import load_packed
        from tpudist.data.transforms import (
            IMAGENET_MEAN, IMAGENET_STD, device_normalize,
        )

        packed = load_packed(args.packed)
        train_classes = packed["classes"]
        pdata = {"image": packed["image"], "label": packed["label"]}
        norm = device_normalize(IMAGENET_MEAN, IMAGENET_STD, dtype=dtype)
        if args.augment:
            # packed pixels are the deterministic eval decode; --augment
            # restores train-time variety IN-GRAPH (reflect-pad crop +
            # flip, step-keyed) — weaker than streaming RandomResizedCrop
            # but fresh every epoch at zero host cost
            from tpudist.data.transforms import (
                device_compose, device_random_crop_flip,
            )

            norm = device_compose(
                device_random_crop_flip(pad=max(args.image_size // 28, 4)),
                norm,
            )
        if args.device_cache and args.cache_shard_rows:
            from tpudist.data.device_cache import RotatingDeviceCache

            # pack larger than HBM: double-buffered shard rotation with a
            # windowed shuffle. The rotation is its OWN sampler (its
            # (seed, epoch) plan replaces the DistributedSampler's global
            # permutation), so no sampler is built here.
            loader = RotatingDeviceCache(
                pdata, per_process_batch, mesh=mesh,
                shard_rows=args.cache_shard_rows,
            )
            input_transform = loader.input_transform(norm)
        elif args.device_cache:
            from tpudist.data.device_cache import DeviceCachedLoader

            # staged pre-compile (same contract as the CIFAR path below)
            loader = DeviceCachedLoader(
                pdata, per_process_batch, mesh=mesh,
                sampler=DistributedSampler(
                    len(pdata["label"]), num_replicas=ctx.process_count,
                    rank=ctx.process_index,
                ),
            )
            input_transform = loader.input_transform(norm)
        else:
            loader = DataLoader(
                pdata, per_process_batch,
                sampler=DistributedSampler(
                    len(pdata["label"]), num_replicas=ctx.process_count,
                    rank=ctx.process_index,
                ),
                transform=None,
            )
            input_transform = norm
    elif args.dataset == "imagenet":
        # streaming image-folder pipeline (BASELINE configs 2/3): decode-on-
        # demand with the standard train augmentation; --augment is implied
        from tpudist.data.imagenet import ImageFolderLoader

        loader = ImageFolderLoader(
            os.path.join(args.data_root, "train"), per_process_batch,
            train=True, image_size=args.image_size,
            num_replicas=ctx.process_count, rank=ctx.process_index,
            workers=args.workers,
        )
        train_classes = loader.classes
    else:
        # --- dataset (reference: CIFAR-100 + ToTensor only, main.py:42-51);
        # the model head deliberately stays 1000-way regardless of the
        # dataset's class count — the reference does not adapt it (main.py:40)
        if args.dataset == "synthetic":
            data = synthetic_cifar(args.synthetic_size, num_classes=100)
        elif args.dataset == "digits":
            from tpudist.data.digits import load_digits_dataset

            data = load_digits_dataset(train=True)
        else:
            data = load_cifar(args.data_root, dataset=args.dataset, train=True)
        sampler = DistributedSampler(
            len(data["label"]), num_replicas=ctx.process_count,
            rank=ctx.process_index,
        )
        if args.device_cache:
            from tpudist.data.device_cache import DeviceCachedLoader

            # staged HERE, at bring-up, before create_train_state
            # compiles anything: no jitted work competes with the one-time
            # H2D of the set
            loader = DeviceCachedLoader(
                data, per_process_batch, mesh=mesh, sampler=sampler
            )
            if args.augment:
                # the host augmentation's in-graph twin (crop+flip then
                # the dataset-stats normalize), applied after the HBM
                # gather — augmented device-cached training
                from tpudist.data.transforms import (
                    _STATS, device_compose, device_normalize,
                    device_random_crop_flip,
                )

                mean, std = _STATS[args.dataset]
                input_transform = loader.input_transform(
                    device_compose(
                        device_random_crop_flip(),
                        device_normalize(mean, std, dtype=dtype),
                    )
                )
            else:
                # in-graph ToTensor (uint8 → [0,1] float), the reference's
                # transform (main.py:46) moved into the compiled step
                input_transform = loader.input_transform(
                    lambda x: x.astype(dtype) / 255.0
                )
        elif args.augment:
            from tpudist.data.transforms import standard_cifar_augment

            transform = standard_cifar_augment(
                seed=ctx.process_index, dataset=args.dataset
            )
            loader = DataLoader(
                data, per_process_batch, sampler=sampler, transform=transform
            )
        else:
            # reference parity (main.py:46: ToTensor only)
            loader = DataLoader(
                data, per_process_batch, sampler=sampler, transform=to_tensor
            )

    from tpudist.optim import make_optimizer

    # defaults reproduce the reference's Adam(lr=1e-3) (main.py:80) exactly
    if args.schedule == "cosine":
        from tpudist.optim import run_schedule

        lr = run_schedule(
            args.lr, total_steps=args.epochs * len(loader),
            warmup_steps=args.warmup_steps,
        )
    else:
        lr = args.lr
    fuse_opt = args.fused in ("optimizer", "all") or (
        args.fused == "auto" and args.optimizer == "adam"
    )
    tx = make_optimizer(
        lr, optimizer=args.optimizer,
        weight_decay=args.weight_decay, clip_norm=args.clip_norm,
        skip_nonfinite_updates=args.amp,
        fused=fuse_opt,
        # the compute copy only pays when the model computes in a narrower
        # dtype than the fp32 masters
        compute_dtype=dtype if dtype != jnp.float32 else None,
    )
    if args.label_smoothing:
        from tpudist.train import smoothed_cross_entropy

        loss_fn = smoothed_cross_entropy(args.label_smoothing)
    else:
        from tpudist.train import cross_entropy_loss as loss_fn
    telemetry = args.telemetry
    if args.health:
        from tpudist.telemetry.health import health_config

        telemetry = health_config(
            divergence_every=args.divergence_every,
            hang_timeout_s=args.hang_timeout or None,
            hang_action=args.hang_action,
        )
    if args.trace:
        import dataclasses

        from tpudist.telemetry import TelemetryConfig

        # --trace implies --telemetry: spans ride the JSONL sink
        telemetry = dataclasses.replace(
            telemetry if not isinstance(telemetry, bool)
            else TelemetryConfig(),
            trace=True,
        )
    state, losses = fit(
        model, tx, loader,
        epochs=args.epochs, mesh=mesh, plan=plan,
        loss_fn=loss_fn,
        job_id=args.JobID,
        batch_size=args.batch_size,
        world_size=ctx.world_size,
        global_rank=ctx.process_index,
        grad_accum=args.grad_accum,
        reduce=args.reduce,
        fused=None if args.fused == "none" else args.fused,
        input_transform=input_transform,
        profile=not args.no_profiler,
        log_dir=args.log_dir,
        telemetry=telemetry,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        checkpoint_every_s=args.checkpoint_every_s or None,
        keep_last=args.keep_last or None,
        resume=not args.no_resume,
        elastic=args.elastic,
        compile_cache=args.compile_cache,
        repair=(
            {"skip_window": args.skip_window} if args.repair else None
        ),
        chaos=args.chaos,
        metrics_port=args.metrics_port,
    )

    if args.amp and ctx.process_index == 0:
        from tpudist.amp import skipped_steps

        skipped = skipped_steps(state.opt_state)
        if skipped:
            print(f"amp: skipped {skipped} non-finite update step(s)")

    if args.eval:
        from tpudist.train import evaluate

        # the reference's val loader is unsharded (every rank sees the full
        # set, /root/reference/main.py:56-63); same here, and only rank 0
        # reports — matching the commented-out accuracy print (main.py:129)
        eval_input_transform = None
        if args.dataset == "imagenet" and args.packed_val:
            from tpudist.data.packed import load_packed
            from tpudist.data.transforms import (
                IMAGENET_MEAN, IMAGENET_STD, device_normalize,
            )

            vdata = load_packed(args.packed_val)
            if vdata["classes"] != train_classes:
                # same label-stability contract as the streaming val path
                # below: a val pack built without --classes_from (or from a
                # tree missing a class dir) would silently shift labels
                raise SystemExit(
                    "--packed_val class list does not match the training "
                    "classes — rebuild it with `python -m "
                    "tpudist.data.packed --classes_from <train pack>`"
                )
            val_loader = DataLoader(
                {"image": vdata["image"], "label": vdata["label"]},
                per_process_batch, transform=None, drop_remainder=False,
            )
            # same in-graph normalize the training step used
            eval_input_transform = device_normalize(
                IMAGENET_MEAN, IMAGENET_STD, dtype=dtype
            )
        elif args.dataset == "imagenet":
            from tpudist.data.imagenet import ImageFolderLoader

            val_loader = ImageFolderLoader(
                os.path.join(args.data_root, "val"), per_process_batch,
                train=False, image_size=args.image_size,
                workers=args.workers, drop_remainder=False,
                # train's class list keys the labels: a val tree missing a
                # class dir can't silently shift every later label
                classes=train_classes,
            )
        else:
            if args.dataset == "synthetic":
                val = synthetic_cifar(args.synthetic_size // 4 or 1, num_classes=100)
            elif args.dataset == "digits":
                from tpudist.data.digits import load_digits_dataset

                val = load_digits_dataset(train=False)
            else:
                val = load_cifar(args.data_root, dataset=args.dataset, train=False)
            # drop_remainder=False + evaluate's pad-and-mask scores the FULL
            # val set (the reference's loop covers every sample too)
            eval_batch = min(per_process_batch, len(val["label"]))
            if args.augment:
                # eval must see the training distribution: normalized (same
                # stats as the train transform), but no crop/flip
                from tpudist.data.transforms import standard_cifar_eval

                eval_transform = standard_cifar_eval(dataset=args.dataset)
            else:
                eval_transform = to_tensor
            val_loader = DataLoader(
                val, eval_batch, transform=eval_transform, drop_remainder=False
            )
        acc = evaluate(
            model, state, val_loader, mesh,
            input_transform=eval_input_transform,
        )
        if ctx.process_index == 0:
            print(f"Accuracy: {acc:.4f}")
    return state, losses


if __name__ == "__main__":
    main()
