"""Analytic per-model training-step FLOPs counters — the MFU numerators.

MFU (model FLOPs utilization) is the headline efficiency metric of
"Scalable Training of Language Models using JAX pjit and TPUv4"
(arXiv:2204.06514): analytic model FLOPs per step divided by step time and
chip peak. This module is the program's home for the analytic counters:
``fit()``'s telemetry MFU rows and ``examples/mfu_probe.py``'s GEMM tables
read them here. The benchmark's ``step_mfu_pct`` uses copies kept with it
(``benchmarks/families/*.py``, ``benchmarks/peaks.json``);
tests/test_benchmark_contract.py holds the copies to these.

Accounting convention (PERF.md §3): weight GEMMs count forward + dgrad + wgrad
(``6 · tokens · matmul_params``); attention counts 6 matmuls per layer
(QKᵀ and AV, forward + two backward passes: ``12 · tokens · seq · hidden``
with the causal factor folded into the convention, not halved); embedding
lookups, norms, and elementwise work are excluded (sub-1% at these
shapes). These are MODEL FLOPs — recompute from remat does NOT count,
which is what makes the metric comparable across memory policies.

Dispatch is duck-typed: a model advertises its counter family via a
``flops_counter`` property (``"gpt2"``/``"llama"``/``"gpt2_moe"``/
``"llama_moe"``/``"t5"``/``"bert"``/``"vit"``/``"resnet"``);
:func:`train_step_flops` reads the model's own
geometry fields and the batch's shapes. Models without the attribute (or
geometries without a counter, e.g. a non-50-layer ResNet) return ``None``
— no MFU row is ever fabricated from a guessed numerator.
"""

from __future__ import annotations

import math
from typing import Any, Mapping

# Published per-chip peaks keyed by jax's ``device_kind``:
# (bf16 FLOP/s, HBM bytes/s, source) — the one table the MFU denominator
# and any roofline read. A device that is not here is an error, not a
# default: a utilisation against another chip's peak is a wrong number.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}


def device_peaks(device_kind: str | None = None) -> tuple[float, float, str]:
    """``(bf16 FLOP/s, HBM bytes/s, source)`` of ``device_kind`` (default:
    the kind of ``jax.devices()[0]``); raises on a kind with no row."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add a row "
            "(with its source) to tpudist.telemetry.flops.DEVICE_PEAKS, or "
            "pass the peak explicitly"
        ) from None


def mfu(flops_per_step: float, step_seconds: float, *,
        peak: float | None = None, n_chips: int = 1) -> float:
    """Fraction of aggregate peak the step achieved; 0.0 on a degenerate
    (non-positive) step time rather than a ZeroDivisionError — the same
    coarse-clock guard as ``MetricsLogger.log_step``. ``peak`` is the
    per-chip FLOP/s (``None`` → :func:`device_peaks` of the running chip).

    ``n_chips`` must be the FULL chip count of the mesh the program spans
    (:func:`mesh_chips`), model axes included: the numerator is total
    MODEL FLOPs for the global batch, so dividing by every chip is
    correct whether each chip holds the whole model (pure DP) or
    ``1/(tensor·pipe)`` of it (a composed plan) — per-chip work is
    ``total/chips`` either way. Counting only the data replicas (the
    whole-model-per-chip assumption) would overstate MFU by exactly
    ``tensor·pipe`` on a composed mesh."""
    if step_seconds <= 0.0:
        return 0.0
    if peak is None:
        peak = device_peaks()[0]
    return flops_per_step / step_seconds / (peak * max(n_chips, 1))


def mesh_chips(mesh) -> int:
    """The MFU denominator's chip count for ``mesh``: every device the
    compiled program spans — data, fsdp, pipe, and tensor axes alike, and
    ONLY those (a sub-mesh must not divide by chips it never used).
    ``fit()``'s telemetry and ``ParallelPlan.n_chips`` route through this
    one function, so a composed-plan MFU row has one denominator."""
    return int(mesh.size)


# -- decoder / encoder LM counters (per GLOBAL step: pass global tokens) ----


def gpt2_train_flops(tokens: float, *, hidden: int, depth: int, vocab: int,
                     seq: int) -> float:
    """GPT-2 geometry: 12·H² weight-GEMM params per block (qkv 3H² + out H²
    + mlp 4H²+4H²), weight-tied head V·H."""
    weight_matmul_params = depth * 12 * hidden * hidden + vocab * hidden
    return 6.0 * tokens * weight_matmul_params + depth * 12.0 * tokens * seq * hidden


def llama_train_flops(tokens: float, *, hidden: int, depth: int, ffn_dim: int,
                      vocab: int, seq: int, num_heads: int,
                      num_kv_heads: int) -> float:
    """Llama geometry: GQA qkv (2H² q+o, 2·H·kv_heads·dh k+v), SwiGLU MLP
    (3·H·ffn), un-tied head V·H."""
    dh = hidden // num_heads
    layer_p = (2 * hidden * hidden + 2 * hidden * (num_kv_heads * dh)
               + 3 * hidden * ffn_dim)
    return (6.0 * tokens * (depth * layer_p + vocab * hidden)
            + depth * 12.0 * tokens * seq * hidden)


def gpt2_moe_train_flops(tokens: float, *, hidden: int, depth: int,
                         vocab: int, seq: int, num_experts: int,
                         moe_every: int, top_k: int,
                         moe_ffn_dim: int | None = None) -> float:
    """Sparse GPT-2 (tpudist.parallel.ep): ACTIVE-param accounting — each
    token pays its dense blocks (12·H²), plus per MoE block the attention
    4·H², the fp32 router GEMM H·E, and ``top_k`` gelu expert FFNs of
    2·H·ffn params each. Capacity drops are NOT subtracted (the dispatch
    einsums/gathers still move full-capacity slots, and an MFU that rose
    when the router dropped tokens would reward imbalance); ``moe_every``
    follows the models' placement rule (every moe_every-th block,
    ``depth // moe_every`` MoE blocks total)."""
    ffn = moe_ffn_dim or 4 * hidden
    n_moe = depth // moe_every
    moe_layer_p = (4 * hidden * hidden + hidden * num_experts
                   + top_k * 2 * hidden * ffn)
    weight_matmul_params = ((depth - n_moe) * 12 * hidden * hidden
                            + n_moe * moe_layer_p + vocab * hidden)
    return (6.0 * tokens * weight_matmul_params
            + depth * 12.0 * tokens * seq * hidden)


def llama_moe_train_flops(tokens: float, *, hidden: int, depth: int,
                          ffn_dim: int, vocab: int, seq: int, num_heads: int,
                          num_kv_heads: int, num_experts: int,
                          moe_every: int, top_k: int) -> float:
    """Sparse Llama (Mixtral-style): GQA attention as the dense counter,
    per MoE block the router H·E plus ``top_k`` active SwiGLU experts
    (3·H·ffn each) instead of the dense MLP. Same active-param convention
    as :func:`gpt2_moe_train_flops`."""
    dh = hidden // num_heads
    attn_p = 2 * hidden * hidden + 2 * hidden * (num_kv_heads * dh)
    n_moe = depth // moe_every
    dense_layer_p = attn_p + 3 * hidden * ffn_dim
    moe_layer_p = (attn_p + hidden * num_experts
                   + top_k * 3 * hidden * ffn_dim)
    return (6.0 * tokens * ((depth - n_moe) * dense_layer_p
                            + n_moe * moe_layer_p + vocab * hidden)
            + depth * 12.0 * tokens * seq * hidden)


def kanana_train_flops(tokens: float, *, hidden: int, depth: int,
                       dense_layers: int, vocab: int, seq: int,
                       num_heads: int, nope_dim: int, rope_dim: int,
                       v_dim: int, kv_rank: int, dense_ffn_dim: int,
                       ffn_dim: int, shared_dim: int, num_experts: int,
                       top_k: int, held_share: float) -> float:
    """Kanana-2 / DeepSeek-V3 geometry (tpudist.models.kanana): MLA's four
    projections in every layer (q, the key/value latent down and up, out),
    a dense SwiGLU in the leading layers; in the others the fp32 router
    GEMM H·E, the shared expert (3·H·shared) and ``top_k`` routed SwiGLU
    experts at the share of the experts this shard HOLDS (a choice whose
    expert lives elsewhere computes nothing here); un-tied head V·H.
    Attention 6·S·heads·(key width + value width) a layer: keys are
    ``nope + rope`` wide, values ``v_dim`` (causal half not taken off)."""
    dk = nope_dim + rope_dim
    mla_p = (hidden * num_heads * dk + hidden * (kv_rank + rope_dim)
             + kv_rank * num_heads * (nope_dim + v_dim)
             + num_heads * v_dim * hidden)
    expert_layer_p = (hidden * num_experts + 3 * hidden * shared_dim
                      + top_k * held_share * 3 * hidden * ffn_dim)
    weight_matmul_params = (depth * mla_p
                            + dense_layers * 3 * hidden * dense_ffn_dim
                            + (depth - dense_layers) * expert_layer_p
                            + vocab * hidden)
    return (6.0 * tokens * weight_matmul_params
            + depth * 6.0 * tokens * seq * num_heads * (dk + v_dim))


def sdar_train_flops(tokens: float, *, hidden: int, depth: int, vocab: int,
                     seq: int, num_heads: int, num_kv_heads: int,
                     head_dim: int, ffn_dim: int, num_experts: int,
                     top_k: int, held_share: float,
                     block_length: int) -> float:
    """SDAR geometry trained by diffusion over blocks
    (tpudist.models.sdar): ``tokens`` are the TRAINED tokens, ``seq`` the
    clean length ``L`` — the stack runs ``2 L`` rows a sequence (the noised
    and the clean copy), so every layer's weights count twice a trained
    token: the fused q/k/v and the output projection, the fp32 router GEMM
    H·E and ``top_k`` routed SwiGLU experts at the share of the experts
    this shard HOLDS; the un-tied head V·H runs over the noised rows only,
    once a token. Attention at the pairs the mask NEEDS, ``L² + L·b`` a
    head a sequence (a noised block sees itself and the clean past, the
    clean copy is block-causal): QK^T and PV, three passes."""
    attn_p = (hidden * (num_heads + 2 * num_kv_heads) * head_dim
              + num_heads * head_dim * hidden)
    layer_p = (attn_p + hidden * num_experts
               + top_k * held_share * 3 * hidden * ffn_dim)
    return (6.0 * tokens * (2 * depth * layer_p + vocab * hidden)
            + depth * 12.0 * tokens * (seq + block_length)
            * num_heads * head_dim)


def window_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs a head of one sequence attends to: the causal
    triangle ``S (S + 1) / 2``, or under a sliding window ``sum_i min(i +
    1, W)``."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def laguna_train_flops(tokens: float, *, hidden: int, depth: int,
                       dense_layers: int, vocab: int, seq: int,
                       heads_per_layer, windows, num_kv_heads: int,
                       head_dim: int, dense_ffn_dim: int, ffn_dim: int,
                       shared_dim: int, num_experts: int, top_k: int,
                       held_share: float) -> float:
    """Laguna geometry (tpudist.models.laguna): layer ``l`` has
    ``heads_per_layer[l]`` query heads and attends causally (``windows[l]``
    ``None``) or in a sliding window. Weights a token, 6x: the fused q/k/v,
    the output gate and the output projection of every layer, a dense
    SwiGLU in the leading layers, in the others the fp32 router GEMM H·E,
    the shared expert (3·H·shared) and ``top_k`` routed SwiGLU experts at
    the share of the experts this shard HOLDS; un-tied head V·H. Attention
    at the pairs each layer NEEDS (:func:`window_pairs`: the causal
    triangle, or the window's band): QK^T and PV, three passes, ``12 ·
    head_dim`` a pair a head."""
    weights = vocab * hidden
    attention = 0.0
    for layer in range(depth):
        h = heads_per_layer[layer]
        weights += (hidden * (h + 2 * num_kv_heads) * head_dim
                    + 2 * h * head_dim * hidden)
        if layer < dense_layers:
            weights += 3 * hidden * dense_ffn_dim
        else:
            weights += (hidden * num_experts + 3 * hidden * shared_dim
                        + top_k * held_share * 3 * hidden * ffn_dim)
        attention += h * window_pairs(seq, windows[layer])
    return (6.0 * tokens * weights
            + 12.0 * head_dim * attention * tokens / seq)


def bert_train_flops(tokens: float, *, hidden: int, depth: int, vocab: int,
                     seq: int) -> float:
    """BERT MLM: 12·H² encoder blocks + the MLM head's H² transform and
    tied V·H projection."""
    return (6.0 * tokens * (depth * 12 * hidden * hidden + hidden * hidden
                            + vocab * hidden)
            + depth * 12.0 * tokens * seq * hidden)


def vit_train_flops(tokens: float, *, hidden: int, depth: int,
                    seq: int) -> float:
    """ViT encoder blocks only (12·H² per block); the patch embed and
    classifier head are sub-1% at ImageNet shapes and excluded."""
    return (6.0 * tokens * depth * 12 * hidden * hidden
            + depth * 12.0 * tokens * seq * hidden)


def t5_train_flops(enc_tokens: float, dec_tokens: float, *, hidden: int,
                   ffn_dim: int, enc_depth: int, dec_depth: int, vocab: int,
                   enc_len: int, dec_len: int) -> float:
    """T5 v1.1 geometry: self-attn 4H² + gated-GELU MLP 3·H·ffn per block,
    decoder cross-attn q/o on dec tokens and k/v on enc tokens, un-tied
    head. Bit-identical to the bench_t5 hand model it replaced."""
    h, ffn = hidden, ffn_dim
    te, td = enc_tokens, dec_tokens
    attn_p, mlp_p = 4 * h * h, 3 * h * ffn
    gemm = 3.0 * 2.0 * (
        te * enc_depth * (attn_p + mlp_p)
        + td * dec_depth * (attn_p + mlp_p)
        + dec_depth * (2 * h * h * td + 2 * h * h * te)
        + td * vocab * h
    )
    attn = 6.0 * 2.0 * (
        te * enc_len * h * enc_depth
        + td * dec_len * h * dec_depth
        + td * enc_len * h * dec_depth
    )
    return gemm + attn


# ResNet-50 at 224×224: ~4.1 GFLOPs forward per image (the standard
# multiply+add count); backward ≈ 2× forward, same as the transformer
# convention above. Other ResNet geometries return None (no counter) —
# a guessed constant is worse than an absent row.
RESNET50_FWD_FLOPS_224 = 4.1e9
_RESNET50_STAGES = (3, 4, 6, 3)


def resnet_train_flops(images: float, *, stage_sizes, image_size: int = 224,
                       bottleneck: bool = True) -> float | None:
    if not bottleneck or tuple(stage_sizes) != _RESNET50_STAGES:
        return None
    scale = (image_size / 224.0) ** 2
    return 3.0 * RESNET50_FWD_FLOPS_224 * scale * images


# -- the dispatcher ----------------------------------------------------------


def _rows(shape, trailing: int) -> int:
    """Flat example count of a batch leaf: product of all dims before the
    ``trailing`` content dims — handles both the loader's flat [B, ...] and
    the grad-accum staged [accum, micro, ...] layouts."""
    lead = shape[: len(shape) - trailing]
    return int(math.prod(lead)) if lead else 1


def train_step_flops(model: Any, batch: Mapping[str, Any], *,
                     input_key: str = "tokens") -> float | None:
    """Analytic model FLOPs of ONE training step of ``model`` on ``batch``
    (shapes only — works on host arrays, staged ``jax.Array``s, or
    ``jax.eval_shape`` results). Returns ``None`` when the model doesn't
    advertise a counter (``flops_counter``), the batch is missing the
    expected keys (e.g. an index-only DeviceCachedLoader batch), or the
    geometry has no counter — callers must treat ``None`` as "no MFU row",
    never as zero.
    """
    family = getattr(model, "flops_counter", None)
    if family is None:
        return None
    try:
        if family == "t5":
            enc, dec = batch["enc_tokens"].shape, batch["dec_tokens"].shape
            return t5_train_flops(
                _rows(enc, 1) * enc[-1], _rows(dec, 1) * dec[-1],
                hidden=model.hidden_dim, ffn_dim=model.ffn_dim,
                enc_depth=model.enc_depth, dec_depth=model.dec_depth,
                vocab=model.vocab_size, enc_len=enc[-1], dec_len=dec[-1],
            )
        shape = batch[input_key].shape
    except (KeyError, AttributeError):
        return None
    if family == "gpt2":
        seq = shape[-1]
        return gpt2_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, vocab=model.vocab_size, seq=seq,
        )
    if family == "gpt2_moe":
        seq = shape[-1]
        return gpt2_moe_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, vocab=model.vocab_size, seq=seq,
            num_experts=model.num_experts, moe_every=model.moe_every,
            top_k=model.moe_top_k,
        )
    if family == "llama_moe":
        seq = shape[-1]
        from tpudist.models.llama import default_ffn_dim

        ffn = model.ffn_dim or default_ffn_dim(model.hidden_dim)
        return llama_moe_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, ffn_dim=ffn, vocab=model.vocab_size, seq=seq,
            num_heads=model.num_heads,
            num_kv_heads=model.num_kv_heads or model.num_heads,
            num_experts=model.num_experts, moe_every=model.moe_every,
            top_k=model.moe_top_k,
        )
    if family == "llama":
        seq = shape[-1]
        from tpudist.models.llama import default_ffn_dim

        ffn = model.ffn_dim or default_ffn_dim(model.hidden_dim)
        return llama_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, ffn_dim=ffn, vocab=model.vocab_size, seq=seq,
            num_heads=model.num_heads,
            num_kv_heads=model.num_kv_heads or model.num_heads,
        )
    if family == "kanana":
        seq = shape[-1]
        routing = model.routing
        return kanana_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, dense_layers=model.dense_layers,
            vocab=model.vocab_size, seq=seq, num_heads=model.num_heads,
            nope_dim=model.nope_dim, rope_dim=model.rope_dim,
            v_dim=model.v_dim, kv_rank=model.kv_rank,
            dense_ffn_dim=model.dense_ffn_dim, ffn_dim=model.ffn_dim,
            shared_dim=model.shared_dim, num_experts=routing.num_experts,
            top_k=routing.top_k,
            held_share=routing.held_range[1] / routing.num_experts,
        )
    if family == "sdar":
        seq = shape[-1]
        routing = model.routing
        return sdar_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, vocab=model.vocab_size, seq=seq,
            num_heads=model.num_heads, num_kv_heads=model.num_kv_heads,
            head_dim=model.head_dim, ffn_dim=model.ffn_dim,
            num_experts=routing.num_experts, top_k=routing.top_k,
            held_share=routing.held_range[1] / routing.num_experts,
            block_length=model.block_length,
        )
    if family == "laguna":
        seq = shape[-1]
        routing = model.routing
        from tpudist.models.laguna import SLIDING

        return laguna_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, dense_layers=model.dense_layers,
            vocab=model.vocab_size, seq=seq,
            heads_per_layer=model.heads_per_layer,
            windows=[model.window if kind == SLIDING else None
                     for kind in model.layer_types],
            num_kv_heads=model.num_kv_heads, head_dim=model.head_dim,
            dense_ffn_dim=model.dense_ffn_dim, ffn_dim=model.ffn_dim,
            shared_dim=model.shared_dim, num_experts=routing.num_experts,
            top_k=routing.top_k,
            held_share=routing.held_range[1] / routing.num_experts,
        )
    if family == "bert":
        seq = shape[-1]
        return bert_train_flops(
            _rows(shape, 1) * seq, hidden=model.hidden_dim,
            depth=model.depth, vocab=model.vocab_size, seq=seq,
        )
    if family == "vit":
        patches = (shape[-3] // model.patch_size) * (shape[-2] // model.patch_size)
        seq = patches + 1  # the CLS token
        return vit_train_flops(
            _rows(shape, 3) * seq, hidden=model.hidden_dim,
            depth=model.depth, seq=seq,
        )
    if family == "resnet":
        block_cls = getattr(model, "block_cls", None)
        return resnet_train_flops(
            _rows(shape, 3), stage_sizes=model.stage_sizes,
            image_size=shape[-3],
            bottleneck=getattr(block_cls, "__name__", "") == "BottleneckBlock",
        )
    return None


def tokens_per_step(model: Any, batch: Mapping[str, Any], *,
                    input_key: str = "tokens") -> int | None:
    """The throughput denominator matching :func:`train_step_flops`'s
    numerator: total tokens (LMs; enc+dec for T5) or images (vision) per
    step, or ``None`` for the same cases the counter returns ``None``."""
    family = getattr(model, "flops_counter", None)
    if family is None:
        return None
    try:
        if family == "t5":
            enc, dec = batch["enc_tokens"].shape, batch["dec_tokens"].shape
            return _rows(enc, 1) * enc[-1] + _rows(dec, 1) * dec[-1]
        shape = batch[input_key].shape
    except (KeyError, AttributeError):
        return None
    if family in ("gpt2", "llama", "bert", "gpt2_moe", "llama_moe",
                  "kanana", "sdar", "laguna"):
        return _rows(shape, 1) * shape[-1]
    if family in ("vit", "resnet"):
        return _rows(shape, 3)
    return None


def gpt2_step_shapes(tokens: int, hidden: int, vocab: int = 50257,
                     ce_chunk_rows: int = 4096) -> list[tuple[str, int, int, int]]:
    """The GEMM shapes of one GPT-2 block + tied head, forward and the two
    backward passes (dgrad/wgrad) per GEMM, at ``tokens`` rows — the
    per-GEMM table behind ``examples/mfu_probe.py``."""
    t, d = tokens, hidden
    fwd = [
        ("qkv", t, d, 3 * d),
        ("attn_out", t, d, d),
        ("mlp_fc", t, d, 4 * d),
        ("mlp_proj", t, 4 * d, d),
        ("lm_head(chunk)", ce_chunk_rows, d, vocab),
    ]
    shapes = []
    for name, m, k, n in fwd:
        shapes.append((f"{name} fwd", m, k, n))
        shapes.append((f"{name} dgrad", m, n, k))
        shapes.append((f"{name} wgrad", k, m, n))
    return shapes
