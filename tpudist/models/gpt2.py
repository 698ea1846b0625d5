"""GPT-2 decoder in Flax — BASELINE.json config 5 (GPT-2 124M, DP + grad
accumulation, tokens/sec).

No reference counterpart (SURVEY.md §2.12); built for the LM leg of the
baseline ladder. TPU-first: causal attention through tpudist.ops (XLA or
Pallas flash path), bf16 compute with fp32 params, weight-tied LM head as a
single MXU matmul against the embedding table.

Tensor parallelism is expressed as Megatron-style param partitioning
metadata over the ``tensor`` mesh axis (``nn.with_partitioning``): qkv and
mlp_fc are column-parallel (heads / ffn dim sharded), out and mlp_proj are
row-parallel, and the embedding table is vocab-sharded. GSPMD inserts the
pair of all-reduces per block from these shardings — there is no hand-written
collective. On a mesh with ``tensor=1`` the metadata is inert.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.mesh import PIPELINE_AXIS, TENSOR_AXIS
from tpudist.ops.attention import multi_head_attention
from tpudist.parallel.pp import pipeline_apply
from tpudist.parallel.tp import partitioned as _partitioned


class Block(nn.Module):
    num_heads: int
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    # tp=False drops the tensor-axis partitioning metadata — required when
    # the block runs inside a shard_map manual-mesh context (the pipelined
    # model), where flax's eval_shape re-run of boxed initializers would
    # apply sharding constraints that cannot be resolved
    tp: bool = True
    # num_experts > 0 swaps the dense MLP for a mixture-of-experts FFN
    # (tpudist.parallel.ep) routed top-k with expert-sharded weights
    num_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # expert dispatch implementation (tpudist.parallel.ep): "einsum" (the
    # one-hot oracle) or "index" (slot-index gather/scatter + explicit
    # expert-axis all-to-all on a real expert mesh axis)
    moe_dispatch: str = "einsum"
    # router hardening knobs (off by default, byte-inert when 0.0)
    router_z_loss: float = 0.0
    router_jitter: float = 0.0
    mesh: Any = None
    # residual dropout (GPT-2 uses 0.1); needs a 'dropout' rng when > 0 and
    # train=True — tpudist.train supplies a per-step key automatically
    dropout: float = 0.0
    # fused_ln=True swaps both LayerNorms for the Pallas fused
    # residual-add+LN kernel (tpudist.ops.layernorm — identical param
    # names/shapes, so checkpoints and the unfused-built TrainState drive
    # it unchanged). The decode path keeps the reference composition (a
    # single-token norm is launch-bound, not bandwidth-bound).
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True, decode: bool = False,
                 max_len: int = 0, positions=None, block_tables=None):
        b, s, d = x.shape
        h = self.num_heads
        drop = lambda y: (
            nn.Dropout(self.dropout, deterministic=not train)(y)
            if self.dropout else y
        )
        dense_init = nn.initializers.lecun_normal()
        partitioned = _partitioned if self.tp else (lambda init, *axes: init)
        fused = self.fused_ln and not decode
        if fused:
            from tpudist.ops.layernorm import FusedLayerNorm

            ln = lambda name: FusedLayerNorm(
                epsilon=1e-5, dtype=self.dtype, mesh=self.mesh, name=name
            )
        else:
            ln = lambda name: nn.LayerNorm(
                epsilon=1e-5, dtype=self.dtype, name=name
            )
        y = ln("ln_1")(x)
        # column-parallel: head dim sharded over 'tensor'
        qkv = nn.DenseGeneral(
            (3, h, d // h), dtype=self.dtype, name="qkv",
            kernel_init=partitioned(dense_init, None, None, TENSOR_AXIS, None),
            bias_init=partitioned(nn.initializers.zeros_init(), None, TENSOR_AXIS, None),
        )(y)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if decode:
            # autoregressive KV-cache attention (tpudist.ops.decode): the
            # context-parallel impls don't apply to single-token steps
            if self.attn_impl in ("ring", "ulysses", "ulysses_flash"):
                raise ValueError(
                    f"attn_impl={self.attn_impl!r} has no decode path; "
                    "generate with the xla/flash model"
                )
            from tpudist.ops.decode import (
                cached_kv, decode_attention, paged_decode_attention,
            )

            keys, values, mask, pos = cached_kv(
                self, k, v, max_len, positions=positions,
                block_tables=block_tables,
            )
            if block_tables is not None:
                # paged decode (tpudist.serve.blocks): keys/values are the
                # SHARED block pool and `mask` the per-row block tables;
                # the paged kernel walks each row's table up to its cursor
                attn = paged_decode_attention(
                    q, keys, values, mask, pos,
                    impl="xla" if self.attn_impl == "xla" else "paged",
                    mesh=self.mesh,
                )
            else:
                # one fused Pallas launch per layer per token unless the
                # caller pinned the dense oracle (attn_impl="xla"): one
                # launch a layer in place of the ~6-kernel attention chain
                attn = decode_attention(
                    q, keys, values, mask, pos,
                    impl="xla" if self.attn_impl == "xla" else "fused",
                )
        elif self.attn_impl in ("ring", "ulysses", "ulysses_flash"):
            # context-parallel attention over the 'seq' mesh axis
            # (tpudist.parallel.cp); activations arrive sequence-sharded and
            # the shard_map keeps them that way — requires ``mesh``
            if self.mesh is None:
                raise ValueError(
                    f"attn_impl={self.attn_impl!r} needs the model's mesh= "
                    "field set (the shard_map runs over its 'seq' axis)"
                )
            from tpudist.parallel.cp import ring_attention, ulysses_attention

            if self.attn_impl == "ring":
                attn = ring_attention(q, k, v, self.mesh, causal=True)
            else:
                attn_fn = None
                if self.attn_impl == "ulysses_flash":
                    # full-sequence attention per head group via the best
                    # Pallas kernel for the shape (vmem ≤1024 / blockwise
                    # flash ≥2048) — the long-context composition
                    # (all_to_all re-shard + fused-kernel softmax)
                    from tpudist.ops.attention import kernel_attention

                    attn_fn = kernel_attention
                attn = ulysses_attention(
                    q, k, v, self.mesh, causal=True, attn_fn=attn_fn
                )
        else:
            attn = multi_head_attention(
                q, k, v, causal=True, impl=self.attn_impl,
                # multi-chip Pallas runs need the per-shard shard_map wrap,
                # inside which the kernel keeps this block's name
                mesh=self.mesh, name=self.name,
            )
        # row-parallel: contraction dim sharded; GSPMD all-reduces the output
        y = nn.DenseGeneral(
            d, axis=(-2, -1), dtype=self.dtype, name="out",
            kernel_init=partitioned(dense_init, TENSOR_AXIS, None, None),
        )(attn)
        if fused:
            # one kernel sweep: residual add + LN (+ the compute-dtype
            # cast); both the normed value and the updated residual
            # stream come back from the same HBM pass
            y, x = ln("ln_2")(drop(y), residual=x)
        else:
            x = x + drop(y)
            y = ln("ln_2")(x)
        if self.num_experts > 0:
            from tpudist.parallel.ep import MoEMlp

            y = MoEMlp(
                num_experts=self.num_experts, top_k=self.moe_top_k,
                capacity_factor=self.capacity_factor,
                dispatch_impl=self.moe_dispatch,
                router_z_loss=self.router_z_loss,
                router_jitter=self.router_jitter, dtype=self.dtype,
                mesh=self.mesh, name="moe",
            )(y, deterministic=not train)
        else:
            y = nn.Dense(
                4 * d, dtype=self.dtype, name="mlp_fc",
                kernel_init=partitioned(dense_init, None, TENSOR_AXIS),
                bias_init=partitioned(nn.initializers.zeros_init(), TENSOR_AXIS),
            )(y)
            y = nn.gelu(y)
            y = nn.Dense(
                d, dtype=self.dtype, name="mlp_proj",
                kernel_init=partitioned(dense_init, TENSOR_AXIS, None),
            )(y)
        return x + drop(y)


class _CarryBlock(nn.Module):
    """:class:`Block` with the (carry, xs) -> (carry, ys) signature
    ``nn.scan`` maps over (``train`` rides as a field; dropout rngs are
    split per layer by the scan)."""

    num_heads: int
    train: bool = True
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Any = None
    dropout: float = 0.0
    fused_ln: bool = False

    @nn.compact
    def __call__(self, x, _):
        x = Block(
            self.num_heads, dtype=self.dtype, attn_impl=self.attn_impl,
            mesh=self.mesh, dropout=self.dropout, fused_ln=self.fused_ln,
            name="block",
        )(x, train=self.train)
        return x, None


class GPT2(nn.Module):
    vocab_size: int = 50257
    max_seq_len: int = 1024
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    # num_experts > 0 makes every ``moe_every``-th block an MoE block
    # (tpudist.parallel.ep); aux load-balance losses are sowed into the
    # ``losses`` collection, which tpudist.train adds to the task loss
    num_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # dispatch impl + router hardening, threaded into every MoE block
    # (see Block / tpudist.parallel.ep.MoEMlp)
    moe_dispatch: str = "einsum"
    router_z_loss: float = 0.0
    router_jitter: float = 0.0
    mesh: Any = None
    dropout: float = 0.0  # embedding + residual dropout (GPT-2 paper: 0.1)
    # scan_layers=True runs the depth as ONE nn.scan'd block (params stacked
    # [depth, ...], one traced layer at any depth — see the Llama field of
    # the same name). Dense blocks only; decode/MoE use the unrolled layout.
    scan_layers: bool = False
    # remat_layers=True checkpoints each scanned layer (store layer
    # boundaries, recompute inside) — requires scan_layers; legacy sugar
    # for remat_policy="full"
    remat_layers: bool = False
    # per-BLOCK rematerialization policy (tpudist.remat names: "full",
    # "dots_saveable", "save_nothing"; None/"none" off). Works in BOTH
    # layouts — scanned (policy on the scanned body) and unrolled (each
    # h_{i} checkpointed, param names unchanged) — so deep models trade
    # recompute for activation HBM without switching layouts. Ignored on
    # the decode path (the KV-cache step has no backward).
    remat_policy: str | None = None
    # fused_ln=True runs every LayerNorm (ln_1/ln_2/ln_f) through the
    # Pallas fused residual-add+LN kernel (tpudist.ops.layernorm), which
    # the benchmark's cells run (PERF.md §4). Same param tree as the
    # flax modules; decode keeps the reference composition. Usually set
    # via make_train_step(fused="ln"|"all"), which clones the model.
    fused_ln: bool = False

    @property
    def has_aux_loss(self) -> bool:
        return self.num_experts > 0

    @property
    def flops_counter(self) -> str | None:
        """Analytic-FLOPs family tag (tpudist.telemetry.flops) — the MFU
        numerator dispatch. MoE geometries get their own counter
        ("gpt2_moe": active-param accounting — routed experts count
        ``top_k`` FFNs per MoE block plus the router GEMM), so MFU rows
        stay real for sparse models."""
        return "gpt2_moe" if self.num_experts > 0 else "gpt2"

    def init_cache(self, batch_size: int):
        """Zeroed decode KV cache for ``batch_size`` rows — the serving
        engine's slot-pool allocation hook (``tpudist.serve.slots``); built
        via ``eval_shape`` so no params materialize."""
        from tpudist.generate import zero_cache

        return zero_cache(self, batch_size)

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False,
                 decode: bool = False, positions=None, block_tables=None):
        b, s = tokens.shape
        wte = self.param(
            "wte",
            _partitioned(nn.initializers.normal(0.02), TENSOR_AXIS, None),
            (self.vocab_size, self.hidden_dim), jnp.float32,
        )
        wpe = self.param(
            "wpe", nn.initializers.normal(0.01), (self.max_seq_len, self.hidden_dim), jnp.float32
        )
        if decode and positions is not None:
            # slot-pooled decode (tpudist.serve): each row reads its wpe
            # entry at its OWN per-slot cursor; the scalar counter below is
            # neither read nor advanced (the engine owns per-slot lengths),
            # but stays declared so the cache tree matches the scalar path
            self.variable("cache", "position", lambda: jnp.zeros((), jnp.int32))
            positions = jnp.asarray(positions, jnp.int32)
            # per-ENTRY overrun: row b's chunk entry i sits at pos_b + i.
            # s > 1 is the speculative verify chunk (tpudist.serve.spec),
            # whose tail may legitimately poke past the table on a
            # near-end row — those entries NaN-poison individually (their
            # K/V writes self-clamp in cached_kv and the engine's
            # acceptance cap never consumes their logits), while an
            # eagerly-detected FULLY-overrun row still fails loudly.
            row_pos = positions[:, None] + jnp.arange(s)[None, :]  # [B, s]
            overrun = row_pos + 1 > self.max_seq_len
            # probe OVERRUN for tracer-ness, not positions: under jit a
            # closed-over concrete positions array still yields a traced
            # comparison (constants lift to tracers inside the trace)
            if not isinstance(overrun, jax.core.Tracer) and bool(
                jnp.any(overrun[:, 0])
            ):
                raise ValueError(
                    f"per-slot decode past max_seq_len {self.max_seq_len} "
                    f"(positions {positions}); the KV cache and wpe table "
                    "end there"
                )
            pos = jnp.take(
                wpe, jnp.minimum(row_pos, self.max_seq_len - 1), axis=0
            )  # [B, s, d]
            pos = jnp.where(overrun[:, :, None], jnp.nan, pos)
        elif decode:
            # learned positions follow the cache cursor, not [0, s); the
            # init trace only creates the counter (no advance)
            initialized = self.has_variable("cache", "position")
            pos_var = self.variable(
                "cache", "position", lambda: jnp.zeros((), jnp.int32)
            )
            # overrun guard, same contract as T5's decode path: past
            # max_seq_len the wpe dynamic_slice (and the KV caches'
            # update) would clamp silently; fail loudly eagerly, NaN-
            # poison the step under jit (generate() bounds-checks at
            # entry, so the guarded path never pays it)
            cursor = pos_var.value
            overrun = cursor + s > self.max_seq_len
            if not isinstance(cursor, jax.core.Tracer) and bool(overrun):
                raise ValueError(
                    f"incremental decode past max_seq_len "
                    f"{self.max_seq_len} (cursor {int(cursor)} + chunk "
                    f"{s}); the KV cache and wpe table end there"
                )
            pos = jax.lax.dynamic_slice(wpe, (cursor, 0),
                                        (s, self.hidden_dim))
            pos = jnp.where(overrun, jnp.nan, pos)
            if initialized:
                pos_var.value = cursor + s
        else:
            pos = wpe[:s]
        x = wte[tokens].astype(self.dtype) + pos.astype(self.dtype)
        if self.dropout:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        from tpudist.remat import remat_module

        block_policy = self.remat_policy or (
            "full" if self.remat_layers else None
        )
        if self.scan_layers:
            if decode:
                raise ValueError(
                    "scan_layers has no decode path (the KV cache needs "
                    "per-layer variables); generate with scan_layers=False"
                )
            if self.num_experts:
                raise ValueError("scan_layers supports dense blocks only")
            body = remat_module(_CarryBlock, block_policy)
            scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=self.depth,
                metadata_params={nn.PARTITION_NAME: None},
            )(
                num_heads=self.num_heads, train=train, dtype=self.dtype,
                attn_impl=self.attn_impl, mesh=self.mesh,
                dropout=self.dropout, fused_ln=self.fused_ln, name="hs",
            )
            x, _ = scanned(x, None)
        elif self.remat_layers:
            raise ValueError("remat_layers requires scan_layers=True "
                             "(set remat_policy to checkpoint the unrolled "
                             "blocks, or make_train_step(remat=...) for a "
                             "whole-forward checkpoint)")
        else:
            # per-block checkpoint in the unrolled layout too: h_{i} param
            # names unchanged (nn.remat is name-transparent), train/decode/
            # max_len static (they steer python-level structure)
            block_cls = (
                remat_module(Block, block_policy, static_argnums=(2, 3, 4))
                if not decode else Block
            )
            for i in range(self.depth):
                moe_here = self.num_experts > 0 and (i % self.moe_every == self.moe_every - 1)
                x = block_cls(
                    self.num_heads, dtype=self.dtype, attn_impl=self.attn_impl,
                    num_experts=self.num_experts if moe_here else 0,
                    moe_top_k=self.moe_top_k, capacity_factor=self.capacity_factor,
                    moe_dispatch=self.moe_dispatch,
                    router_z_loss=self.router_z_loss,
                    router_jitter=self.router_jitter,
                    mesh=self.mesh, dropout=self.dropout,
                    fused_ln=self.fused_ln, name=f"h_{i}",
                )(x, train, decode, self.max_seq_len,
                  # only the (remat-free) decode path threads per-slot
                  # positions/block tables; the remat wrapper's
                  # static_argnums contract stays untouched
                  **({"positions": positions,
                      "block_tables": block_tables} if decode else {}))
        if self.fused_ln and not decode:
            from tpudist.ops.layernorm import FusedLayerNorm

            x = FusedLayerNorm(
                epsilon=1e-5, dtype=self.dtype, mesh=self.mesh, name="ln_f"
            )(x)
        else:
            x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="ln_f")(x)
        if return_hidden:
            # the chunked-CE path (chunked_lm_forward) applies the tied head
            # per sequence chunk so the [B,S,V] f32 logits never materialize
            return x
        # weight-tied LM head
        logits = jnp.einsum(
            "bsd,vd->bsv", x, wte.astype(self.dtype), preferred_element_type=jnp.float32
        )
        return logits


def gpt2_124m(**kw) -> GPT2:
    return GPT2(**kw)


def gpt2_medium(**kw) -> GPT2:
    """GPT-2 medium (355M): 24 layers, 1024 hidden, 16 heads."""
    kw.setdefault("hidden_dim", 1024)
    kw.setdefault("depth", 24)
    kw.setdefault("num_heads", 16)
    return GPT2(**kw)


def gpt2_large(**kw) -> GPT2:
    """GPT-2 large (774M): 36 layers, 1280 hidden, 20 heads."""
    kw.setdefault("hidden_dim", 1280)
    kw.setdefault("depth", 36)
    kw.setdefault("num_heads", 20)
    return GPT2(**kw)


# family-neutral home; re-exported here for the established import path
from tpudist.models.lm_utils import chunked_lm_forward  # noqa: E402,F401


def stack_gpt2_params(variables, depth: int):
    """Convert a plain (unrolled) :class:`GPT2` param tree into
    :class:`PipelinedGPT2`'s stacked layout.

    The per-layer subtrees ``h_0 .. h_{depth-1}`` are stacked leaf-for-leaf
    into ``blocks`` with a new leading ``[depth]`` dim boxed over ``pipe``;
    boxed leaves keep their tensor-axis names shifted past the layer dim
    (Megatron TP-within-stage), and ``wte``/``wpe``/``ln_f`` pass through
    with their boxes. Because this is a pure re-layout, a
    ``PipelinedGPT2`` holding the converted params computes the *identical
    function* as the source model — the property the PP agreement
    certification relies on, and what enables warm-starting the pipelined
    model from an unrolled checkpoint (``examples/train_gpt2.py`` routes
    ``--init_hf --pipe`` through this conversion). Accepts boxed or
    unboxed trees, and a full ``{"params": ...}`` variables dict or a bare
    param tree.
    """
    p = variables["params"] if "params" in variables else variables

    def is_box(x):
        return isinstance(x, nn.Partitioned)

    def stack(*leaves):
        if is_box(leaves[0]):
            vals = [leaf.value for leaf in leaves]
            names = leaves[0].names
        else:
            vals = list(leaves)
            names = (None,) * jnp.ndim(leaves[0])
        return nn.Partitioned(jnp.stack(vals), names=(PIPELINE_AXIS, *names))

    blocks = jax.tree_util.tree_map(
        stack, *[p[f"h_{i}"] for i in range(depth)], is_leaf=is_box
    )
    return {
        "params": {
            "wte": p["wte"],
            "wpe": p["wpe"],
            "blocks": blocks,
            "ln_f": p["ln_f"],
        }
    }


class PipelinedGPT2:
    """GPT-2 with its blocks stacked ``[depth, ...]`` and run through GPipe
    microbatch pipelining over the ``pipe`` mesh axis
    (``tpudist.parallel.pp``).

    Duck-types the flax ``init``/``apply`` surface that
    ``tpudist.train.create_train_state``/``make_train_step`` drive, so the
    ordinary compiled train step works unchanged: ``init`` boxes the stacked
    block params with ``nn.Partitioned(('pipe', ...))`` metadata, which
    ``create_train_state`` turns into layer-over-stage placement (and
    matching Adam-moment shardings); ``apply`` embeds, pipelines the blocks,
    and runs the stage-replicated final LayerNorm + weight-tied head.

    ``init`` is *init-by-conversion*: it initializes the plain unrolled
    :class:`GPT2` twin with the caller's rng and re-stacks its params
    (:func:`stack_gpt2_params`), so the same seed yields the same function
    as the plain model — making PP certifiable against the DP reference
    (and every Adam update identical, since the stacked layout is a pure
    re-indexing of the same leaves). The blocks' Megatron ``tensor``
    shardings survive the conversion, and the pipeline's ``shard_map`` is
    manual over ``pipe`` only, so PP×TP (and ×DP) composes under GSPMD —
    see ``tpudist.parallel.pp``.

    Embedding/head stay outside the pipeline (computed replicated over
    ``pipe``) — standard for shallow heads; the depth is where the memory is.

    ``schedule`` selects the microbatch schedule (``tpudist.parallel.pp``):
    ``"gpipe"`` (default) or ``"1f1b"`` — same function and gradients,
    different backward memory profile (1F1B banks stage inputs and
    recomputes internals in its interleaved backward ring).
    """

    def __init__(
        self,
        mesh,
        *,
        num_micro: int,
        vocab_size: int = 50257,
        max_seq_len: int = 1024,
        hidden_dim: int = 768,
        depth: int = 12,
        num_heads: int = 12,
        dtype: Any = jnp.float32,
        attn_impl: str = "xla",
        schedule: str = "gpipe",
    ):
        if depth % mesh.shape[PIPELINE_AXIS]:
            raise ValueError(
                f"depth {depth} not divisible by pipe={mesh.shape[PIPELINE_AXIS]}"
            )
        if attn_impl != "xla":
            # pallas_call inside the pipe-manual shard_map region trips the
            # varying-manual-axes checks in the kernels' interpret/backward
            # scans — refuse loudly rather than fail with a cryptic trace
            raise ValueError(
                f"attn_impl={attn_impl!r} does not compose with the GPipe "
                "schedule yet; the pipelined model runs XLA attention "
                "(attn_impl='xla')"
            )
        from tpudist.parallel.pp import SCHEDULES

        if schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {schedule!r}"
            )
        self.mesh = mesh
        self.num_micro = num_micro
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.hidden_dim = hidden_dim
        self.depth = depth
        self.num_heads = num_heads
        self.dtype = dtype
        self.schedule = schedule
        # the unrolled twin: the source of init (same seed -> same function)
        self.unrolled = GPT2(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            hidden_dim=hidden_dim, depth=depth, num_heads=num_heads,
            dtype=dtype, attn_impl=attn_impl,
        )
        # partitioning metadata on the apply-side Block is irrelevant (its
        # initializers never run — params arrive pre-boxed from the
        # conversion), so tp=False keeps the module free of boxing logic
        self.block = Block(num_heads, dtype=dtype, attn_impl=attn_impl, tp=False)

    @property
    def flops_counter(self) -> str:
        """Same analytic family as the unrolled twin (it IS the same
        function): pipelining is an execution schedule, and the MFU
        numerator must not vanish just because the depth moved onto the
        ``pipe`` axis — telemetry divides by the mesh's FULL chip count
        (``tpudist.telemetry.flops``)."""
        return "gpt2"

    def init(self, rng, tokens, train: bool = False):
        return stack_gpt2_params(
            self.unrolled.init(rng, tokens, train=train), self.depth
        )

    def apply(self, variables, tokens, train: bool = True):
        p = variables["params"]
        s = tokens.shape[1]
        x = p["wte"][tokens].astype(self.dtype) + p["wpe"][:s].astype(self.dtype)

        def block_fn(bp, h):
            return self.block.apply({"params": bp}, h)

        x = pipeline_apply(
            block_fn, p["blocks"], x, self.mesh, num_micro=self.num_micro,
            schedule=self.schedule,
        )
        # same module (and epsilon) as plain GPT2's ln_f
        x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype).apply({"params": p["ln_f"]}, x)
        return jnp.einsum(
            "bsd,vd->bsv", x, p["wte"].astype(self.dtype),
            preferred_element_type=jnp.float32,
        )
