"""Expert parallelism — Mixture-of-Experts with GShard-style routing over
the ``expert`` mesh axis, with two dispatch implementations.

No reference counterpart (SURVEY.md §2.12: the reference's only strategy is
DDP, /root/reference/main.py:83); built so the framework scales parameter
count past dense models. TPU-native design:

- **Static shapes everywhere.** Each expert has a fixed ``capacity`` slot
  count and tokens beyond capacity are dropped (their contribution is zero;
  transformer residuals carry them through unchanged). Routing itself is
  shared (:func:`top_k_routing`: argmax/cumsum slot assignment with the
  GShard priority rule); what differs is how tokens reach their slots:

  - ``dispatch_impl="einsum"`` — the GShard/Switch one-hot formulation:
    dense ``[t, E, C]`` dispatch/combine tensors contracted on the MXU.
    O(t·E·C) FLOPs and bytes, but every op is an einsum; this is the
    bit-checked oracle the index path is certified against.
  - ``dispatch_impl="index"`` — slot-index gather/scatter: each kept
    (token, choice) computes its flat slot id ``e·C + pos``; a scatter of
    token ids builds the slot→token map, one ``take`` gathers tokens into
    ``[E, C, d]`` slots, and the combine is a gather from the expert
    outputs whose backward is the scatter-add. O(t·k) index work instead
    of O(t·E·C) — the dense one-hots never materialize.

- **Expert placement = sharding metadata.** Stacked expert FFN weights
  ``[E, d, ff]`` carry ``nn.with_partitioning(..., ('expert', ...))``. On
  the einsum path the dispatched activations are sharding-constrained to
  ``P('expert')`` and GSPMD derives the token all-to-all. On the index
  path with a real (>1) ``expert`` axis the collective is EXPLICIT: a
  ``shard_map`` over the mesh in which each expert shard gathers only its
  own experts' slots from its (expert-replicated) local tokens, runs its
  local FFNs, and one ``all_gather`` over ``expert`` ships the slot
  OUTPUTS back — wire bytes equal dispatched-token bytes
  (``G·E·C·d``·dtype per direction), not whatever GSPMD derives from the
  one-hot einsums.
- **Load balance is a differentiable aux loss** (Switch-style
  ``E · Σ_e f_e·P_e``), sowed into the ``losses`` collection; the train
  step (tpudist.train) adds any sowed losses to the task loss. Optional
  router hardening: ``router_z_loss`` (penalizes ``logsumexp(logits)²``,
  keeping the fp32 router's logits from drifting to magnitudes where
  softmax saturates) and ``router_jitter`` (multiplicative uniform input
  noise, train-only) — both off by default and byte-inert when off.
- **Router observability**: per-expert load fractions, the dropped-token
  rate, and the unscaled aux value are sowed into the ``moe_stats``
  collection; the train step forwards them to telemetry when it runs with
  ``telemetry=True`` (docs/OBSERVABILITY.md §1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpudist.mesh import DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, TENSOR_AXIS


def expert_capacity(
    num_tokens: int, num_experts: int, *, top_k: int, capacity_factor: float
) -> int:
    """Per-expert slot count: ``ceil(top_k · T / E) · capacity_factor``,
    rounded up — the static buffer size every expert processes."""
    import math

    base = (top_k * num_tokens + num_experts - 1) // num_experts
    return max(1, math.ceil(base * capacity_factor))


def top_k_routing(probs: jax.Array, top_k: int, capacity: int):
    """Router probabilities → per-(token, choice) routing decisions.

    ``probs``: ``[T, E]`` softmax router output. Returns
    ``(idx, gates, pos, keep, aux_loss)`` with ``idx`` ``[T, k]`` int32
    expert choices, ``gates`` ``[T, k]`` the (renormalized) gate weights,
    ``pos`` ``[T, k]`` int32 slot positions within the chosen expert,
    ``keep`` ``[T, k]`` bool capacity survival, and the Switch-style
    load-balance ``aux_loss`` (1.0 at perfect balance).

    This is the ONE routing implementation both dispatch paths consume:
    slot assignment order is token order (int32 cumsum over the token dim
    — a float cumsum in low-precision dtypes would collide positions),
    with all k-th choices placed after all (k-1)-th choices (the GShard
    priority rule, so a token's secondary expert never evicts another's
    primary). Top-1 (Switch) keeps the raw gate — renormalizing a single
    gate to ~1 would zero the router's task-loss gradient; top-k≥2
    renormalizes the kept gates to sum to 1 (GShard).
    """
    T, E = probs.shape
    gates, idxs, masks = [], [], []
    p = probs
    for _ in range(top_k):
        idx = jnp.argmax(p, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=probs.dtype)  # [T, E]
        gates.append(jnp.sum(p * m, axis=-1))  # [T]
        idxs.append(idx.astype(jnp.int32))
        masks.append(m)
        p = p * (1.0 - m)

    # aux loss from primary assignments: E · Σ_e (token fraction)·(mean prob)
    f = jnp.mean(masks[0], axis=0)
    pr = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(f * pr)

    if top_k > 1:
        denom = sum(gates) + 1e-9
        gates = [g / denom for g in gates]

    poss, keeps = [], []
    counts = jnp.zeros((E,), jnp.int32)  # slots consumed by earlier choices
    for m in masks:
        mi = m.astype(jnp.int32)
        pos = jnp.cumsum(mi, axis=0) - mi + counts  # [T, E]
        pos_t = jnp.sum(pos * mi, axis=-1)  # [T]
        keep = (pos_t < capacity) & (jnp.sum(mi, axis=-1) > 0)
        poss.append(pos_t)
        keeps.append(keep)
        counts = counts + jnp.sum(mi, axis=0)
    return (
        jnp.stack(idxs, axis=-1),
        jnp.stack(gates, axis=-1),
        jnp.stack(poss, axis=-1),
        jnp.stack(keeps, axis=-1),
        aux_loss,
    )


def _one_hot_dispatch(idx, gates, pos, keep, num_experts: int, capacity: int,
                      dtype):
    """Routing decisions → dense one-hot ``(dispatch, combine)`` tensors
    (``[..., E, C]``), the GShard einsum formulation. Sequential adds in
    choice order — the exact op order of the original oracle."""
    shape = idx.shape[:-1] + (num_experts, capacity)
    dispatch = jnp.zeros(shape, dtype)
    combine = jnp.zeros(shape, dtype)
    for j in range(idx.shape[-1]):
        m = jax.nn.one_hot(idx[..., j], num_experts, dtype=dtype)
        slot = jax.nn.one_hot(pos[..., j], capacity, dtype=dtype)
        d = m[..., :, None] * slot[..., None, :] * keep[..., j, None, None]
        dispatch = dispatch + d
        combine = combine + d * gates[..., j, None, None]
    return dispatch, combine


def top_k_dispatch(probs: jax.Array, top_k: int, capacity: int):
    """Router probabilities → (dispatch, combine, aux_loss) — the einsum
    oracle's dense form.

    ``dispatch``: ``[T, E, C]`` 0/1 — token t occupies slot c of expert e.
    ``combine``: ``dispatch`` weighted by the token's (renormalized) gate.
    ``aux_loss``: Switch-style load-balance loss, 1.0 at perfect balance.

    Built from :func:`top_k_routing` (one routing implementation for both
    dispatch paths); numerics are unchanged from the original fused loop.
    """
    idx, gates, pos, keep, aux_loss = top_k_routing(probs, top_k, capacity)
    E = probs.shape[-1]
    dispatch, combine = _one_hot_dispatch(
        idx, gates, pos, keep, E, capacity, probs.dtype
    )
    return dispatch, combine, aux_loss


def _flat_dest(idx, pos, keep, capacity: int, num_experts: int):
    """Per-(token, choice) flat slot id ``e·C + pos``; dropped choices
    point at the one-past-the-end garbage slot ``E·C``."""
    return jnp.where(keep, idx * capacity + pos, num_experts * capacity)


def _index_dispatch(tokens, dest, num_experts: int, capacity: int):
    """Tokens → ``[E, C, d]`` slots via slot-index scatter/gather.

    ``tokens``: ``[t, d]``; ``dest``: ``[t, k]`` flat slot ids
    (:func:`_flat_dest`). A scatter of token ids builds the slot→token
    map (kept destinations are unique by construction — one token per
    slot — so the scatter is order-independent and deterministic; all
    dropped pairs collide harmlessly on the garbage slot), then ONE
    gather materializes the slots. Empty slots read the appended zero row
    — the same zeros the einsum dispatch produces. The gather's backward
    is a scatter-add into the token gradients.
    """
    t, d = tokens.shape
    k = dest.shape[-1]
    n_slots = num_experts * capacity
    token_ids = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[:, None], (t, k)
    )
    # index t (one past the tokens) marks "empty": it reads the zero row
    slot_token = jnp.full((n_slots + 1,), t, jnp.int32)
    slot_token = slot_token.at[dest.reshape(-1)].set(token_ids.reshape(-1))
    tokens_pad = jnp.concatenate(
        [tokens, jnp.zeros((1, d), tokens.dtype)], axis=0
    )
    slots = jnp.take(tokens_pad, slot_token[:n_slots], axis=0)
    return slots.reshape(num_experts, capacity, d)


def _index_combine(out, dest, gates, keep, dtype):
    """Expert outputs → per-token mix via gather.

    ``out``: ``[E, C, d]``; ``dest``/``gates``/``keep``: ``[t, k]``.
    ``y[t] = Σ_j gate_j·keep_j·out[dest_j]`` — dropped choices gather the
    appended zero row. Sequential adds in choice order; the gate weights
    are cast exactly like the einsum path's combine tensor
    (``dtype(gate·keep)``). Dispatch and the expert outputs match the
    oracle BIT-exactly (tests/test_moe.py asserts it on the composed
    layer); this final mix matches to ≤1 ulp — the oracle's contraction
    accumulates with FMA (one rounding per term), this explicit
    multiply-add rounds the product first — which greedy decode and the
    train-loss trajectory absorb (both pinned by tests)."""
    E, C, d = out.shape
    out_pad = jnp.concatenate(
        [out.reshape(E * C, d), jnp.zeros((1, d), out.dtype)], axis=0
    )
    w = (gates * keep.astype(gates.dtype)).astype(dtype)  # [t, k]
    y = jnp.zeros((dest.shape[0], d), dtype)
    for j in range(dest.shape[-1]):
        y = y + w[:, j, None] * jnp.take(out_pad, dest[:, j], axis=0)
    return y


class MoEMlp(nn.Module):
    """Mixture-of-experts FFN (drop-in for a transformer's dense MLP).

    ``x: [batch, seq, d] → [batch, seq, d]``; top-``top_k`` routing into
    ``num_experts`` FFNs of width ``mlp_ratio·d`` (or ``ffn_dim``); expert
    weights are expert-sharded (and FFN-dim tensor-sharded) via
    partitioning metadata. Sows the scaled load-balance loss into the
    ``losses`` collection and router stats into ``moe_stats``.

    Routing is **grouped** (GShard): tokens are split into ``num_groups``
    independent dispatch groups (default: one per batch row, so groups ride
    the existing ``data`` sharding) and capacity is per-group. On the
    einsum path this keeps the dispatch/combine one-hots at
    O(group_size²·E⁻¹) instead of O(T²·E⁻¹); the index path never builds
    them at all.

    ``dispatch_impl`` selects the dispatch formulation (module docstring):
    ``"einsum"`` (default, the oracle) or ``"index"``. With a real (>1)
    ``expert`` mesh axis the index path runs inside an explicit
    ``shard_map``: local dispatch + local expert FFNs + ONE ``all_gather``
    of the slot outputs over ``expert`` (wire bytes = dispatched-token
    bytes); the per-block ``tensor`` reduction stays a ``psum``, and the
    batch axes stay data-manual — gradients under ``jax.grad`` transpose
    the ``all_gather`` into the matching ``psum_scatter``.
    """

    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    mlp_ratio: int = 4
    ffn_dim: int | None = None  # overrides mlp_ratio·d when set
    # "gelu": GPT-2-style single-FFN experts; "swiglu": Mixtral-style
    # gated experts (silu(x·w_gate)·(x·w_up))·w_down
    expert_act: str = "gelu"
    aux_loss_weight: float = 0.01
    num_groups: int = 0  # 0 → one group per batch row
    # "einsum" (one-hot oracle) | "index" (slot-index gather/scatter +
    # explicit expert all-to-all on a real expert axis)
    dispatch_impl: str = "einsum"
    # router z-loss weight (ST-MoE): penalizes mean(logsumexp(logits)²),
    # sowed into ``losses`` scaled. 0.0 = off (byte-inert).
    router_z_loss: float = 0.0
    # multiplicative uniform router-input jitter in [1-j, 1+j], train-only
    # (needs a 'dropout' rng and deterministic=False). 0.0 = off.
    router_jitter: float = 0.0
    dtype: Any = jnp.float32
    mesh: Any = None  # when set, activations get explicit expert shardings

    @nn.compact
    def __call__(self, x, deterministic: bool | None = None):
        b, s, d = x.shape
        E = self.num_experts
        ff = self.ffn_dim or self.mlp_ratio * d
        G = self.num_groups or b
        T = b * s
        if T % G:
            raise ValueError(f"{T} tokens not divisible into {G} groups")
        if self.dispatch_impl not in ("einsum", "index"):
            raise ValueError(
                f"dispatch_impl must be 'einsum' or 'index', got "
                f"{self.dispatch_impl!r}"
            )
        t = T // G
        tokens = x.reshape(G, t, d)

        # router in fp32 — cheap, and argmax ties/probs stay stable in bf16 runs
        wr = self.param(
            "router", nn.initializers.lecun_normal(), (d, E), jnp.float32
        )
        rin = tokens.astype(jnp.float32)
        if (self.router_jitter > 0.0 and deterministic is False
                and not self.is_initializing()):
            if not self.has_rng("dropout"):
                raise ValueError(
                    "router_jitter > 0 needs a 'dropout' rng stream at "
                    "train time (tpudist.train supplies one per step); "
                    "pass rngs={'dropout': key} or set router_jitter=0"
                )
            j = self.router_jitter
            rin = rin * jax.random.uniform(
                self.make_rng("dropout"), rin.shape, jnp.float32,
                1.0 - j, 1.0 + j,
            )
        logits = jnp.einsum("gtd,de->gte", rin, wr)
        probs = jax.nn.softmax(logits)
        if self.router_z_loss > 0.0:
            z = jax.nn.logsumexp(logits, axis=-1)  # [G, t]
            self.sow(
                "losses", "moe_router_z_loss",
                self.router_z_loss * jnp.mean(z * z),
                reduce_fn=lambda a, b: a + b,
                init_fn=lambda: jnp.zeros((), jnp.float32),
            )
        capacity = expert_capacity(
            t, E, top_k=self.top_k, capacity_factor=self.capacity_factor
        )
        idx, gates, pos, keep, aux = jax.vmap(
            lambda p: top_k_routing(p, self.top_k, capacity)
        )(probs)
        self.sow(
            "losses", "moe_aux_loss", self.aux_loss_weight * jnp.mean(aux),
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        # router observability (docs/OBSERVABILITY.md §1): dispatched load
        # fraction per expert, dropped-choice rate, unscaled aux. Dead
        # code (DCE'd) unless the caller makes 'moe_stats' mutable.
        kept = keep.astype(jnp.float32)
        # fraction of routed (token, choice) pairs landing on each expert:
        # Σ_e load_e = 1 − dropped, perfectly balanced = 1/E per expert
        load = jnp.mean(
            jax.nn.one_hot(idx, E, dtype=jnp.float32) * kept[..., None],
            axis=(0, 1, 2),
        )
        self.sow("moe_stats", "load", load)
        self.sow("moe_stats", "dropped", 1.0 - jnp.mean(kept))
        self.sow("moe_stats", "aux", jnp.mean(aux))

        def ew(name, shape, spec):
            return self.param(
                name,
                nn.with_partitioning(nn.initializers.lecun_normal(), spec),
                shape, jnp.float32,
            )

        col = (EXPERT_AXIS, None, TENSOR_AXIS)
        row = (EXPERT_AXIS, TENSOR_AXIS, None)
        if self.expert_act == "swiglu":
            ws = (ew("w_gate", (E, d, ff), col), ew("w_up", (E, d, ff), col),
                  ew("w_down", (E, ff, d), row))
            specs = (col, col, row)
        elif self.expert_act == "gelu":
            ws = (ew("w1", (E, d, ff), col), ew("w2", (E, ff, d), row))
            specs = (col, row)
        else:
            raise ValueError(f"unknown expert_act {self.expert_act!r}")

        ep_world = (
            int(dict(self.mesh.shape).get(EXPERT_AXIS, 1))
            if self.mesh is not None else 1
        )
        # the manual lowering splits the group dim over (data, fsdp); a
        # trace whose batch can't split — single-row decode, init probes —
        # takes the local formulation below and lets GSPMD place it (the
        # dispatch/FFN math is identical, so outputs don't change)
        dp_world = (
            int(dict(self.mesh.shape).get(DATA_AXIS, 1))
            * int(dict(self.mesh.shape).get(FSDP_AXIS, 1))
            if self.mesh is not None else 1
        )
        if (self.dispatch_impl == "index" and ep_world > 1
                and tokens.shape[0] % dp_world == 0):
            y = self._sharded_index_forward(
                tokens, idx, gates, pos, keep, ws, specs, capacity, ep_world
            )
        elif self.dispatch_impl == "index":
            dest = _flat_dest(idx, pos, keep, capacity, E)
            slots = jax.vmap(
                lambda tk, de: _index_dispatch(
                    tk.astype(self.dtype), de, E, capacity
                )
            )(tokens, dest)
            out = self._expert_ffn(slots, ws)
            y = jax.vmap(
                lambda o, de, g, k: _index_combine(o, de, g, k, self.dtype)
            )(out, dest, gates, keep)
        else:
            dispatch, combine = _one_hot_dispatch(
                idx, gates, pos, keep, E, capacity, probs.dtype
            )
            # tokens (data-sharded groups) → expert slots: GSPMD turns the
            # sharding jump into the all-to-all
            slots = jnp.einsum(
                "gtec,gtd->gecd", dispatch.astype(self.dtype),
                tokens.astype(self.dtype),
            )
            slots = self._constrain(slots)
            out = self._constrain(self._expert_ffn(slots, ws))
            # expert slots → tokens (the reverse all-to-all), gate-weighted
            y = jnp.einsum(
                "gtec,gecd->gtd", combine.astype(self.dtype), out
            )
        return y.reshape(b, s, d)

    def _expert_ffn(self, slots, ws):
        """Per-expert FFN over ``[..., E_local, C, d]`` slots; ``ws`` are
        the (possibly locally-sharded) stacked expert weights."""
        if self.expert_act == "swiglu":
            wg, wu, wd = ws
            h = nn.silu(
                jnp.einsum("...ecd,edf->...ecf", slots, wg.astype(self.dtype))
            ) * jnp.einsum("...ecd,edf->...ecf", slots, wu.astype(self.dtype))
            return jnp.einsum("...ecf,efd->...ecd", h, wd.astype(self.dtype))
        w1, w2 = ws
        h = jnp.einsum("...ecd,edf->...ecf", slots, w1.astype(self.dtype))
        h = nn.gelu(h)
        return jnp.einsum("...ecf,efd->...ecd", h, w2.astype(self.dtype))

    def _sharded_index_forward(self, tokens, idx, gates, pos, keep, ws,
                               specs, capacity: int, ep_world: int):
        """The explicit expert all-to-all: index dispatch under a manual
        ``shard_map`` over the WHOLE mesh.

        Tokens ride their existing ``(data, fsdp)`` batch sharding and are
        REPLICATED over ``expert`` (that axis shards only weights), so
        dispatch needs no send at all: each expert shard scatters/gathers
        its OWN experts' slots from its local token copy and runs its
        local FFNs. The one collective is the ``all_gather`` of the slot
        OUTPUTS over ``expert`` — ``G·E·C·d`` dtype bytes, exactly the
        dispatched-token volume — after which the combine is a local
        gather. Row-parallel ``tensor`` partial sums stay a ``psum``,
        matching the metadata the einsum path hands GSPMD."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        E = self.num_experts
        if E % ep_world:
            raise ValueError(
                f"num_experts={E} not divisible by the mesh's "
                f"expert={ep_world} axis"
            )
        e_loc = E // ep_world
        tp_world = int(dict(self.mesh.shape).get(TENSOR_AXIS, 1))
        batch = P((DATA_AXIS, FSDP_AXIS), None, None)
        w_specs = tuple(P(*spec) for spec in specs)

        def fwd(tk, idx, gates, pos, keep, *ws_loc):
            ei = jax.lax.axis_index(EXPERT_AXIS)
            lo = ei * e_loc
            # choices landing on THIS shard's experts, re-based locally;
            # everything else collides on the local garbage slot
            mine = keep & (idx >= lo) & (idx < lo + e_loc)
            dest_l = jnp.where(
                mine, (idx - lo) * capacity + pos, e_loc * capacity
            )
            slots = jax.vmap(
                lambda tkg, de: _index_dispatch(
                    tkg.astype(self.dtype), de, e_loc, capacity
                )
            )(tk, dest_l)  # [G_loc, e_loc, C, d]
            out = self._expert_ffn(slots, ws_loc)
            if tp_world > 1:
                # row-parallel partial sums over the ffn shards
                out = jax.lax.psum(out, TENSOR_AXIS)
            # THE all-to-all's return leg: every shard needs every
            # expert's outputs for its local tokens
            outs = jax.lax.all_gather(
                out, EXPERT_AXIS, axis=1, tiled=True
            )  # [G_loc, E, C, d]
            dest = _flat_dest(idx, pos, keep, capacity, E)
            return jax.vmap(
                lambda o, de, g, k: _index_combine(o, de, g, k, self.dtype)
            )(outs, dest, gates, keep)

        routed = P((DATA_AXIS, FSDP_AXIS), None, None)
        return shard_map(
            fwd,
            mesh=self.mesh,
            in_specs=(batch, routed, routed, routed, routed, *w_specs),
            out_specs=batch,
            check_vma=False,
        )(tokens, idx, gates, pos, keep, *ws)

    def _constrain(self, slots):
        if self.mesh is None:
            return slots
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            slots,
            NamedSharding(
                self.mesh, P((DATA_AXIS, FSDP_AXIS), EXPERT_AXIS, None, None)
            ),
        )


# -- dropless routing over held experts ---------------------------------------
#
# What a present-day expert model asks (ROADMAP Reach, mechanism 1): no
# ``capacity`` and no dropped token, tokens sorted by expert and ONE grouped
# product over the experts this shard holds, and a layer that is told which
# experts those are. The capacity layer above stays as it is for the
# GPT-2/Llama fields that configure it.


@dataclasses.dataclass(frozen=True)
class Routing:
    """How an expert layer routes — the one description a model hands
    :func:`dropless_moe` (no per-model copies of these fields).

    ``held`` is ``(first, count)``: the contiguous experts THIS shard
    computes; ``None`` holds all. The router always scores all
    ``num_experts``; a token whose expert is not held contributes nothing
    here (its part of the result lives on the shard that holds the expert).
    So about ``count / num_experts`` of a shard's (token, choice) rows are
    live, on every shard of every such deployment, and the layer sizes its
    row chunks from that ratio (:func:`row_chunks`): rows of experts not
    held are sorted last and the chunks they fill do no row-wide work.
    ``scoring`` turns the router's logits into scores, float32:
    ``softmax`` over all experts, or ``sigmoid`` of each (DeepSeek-V3's
    family: the chosen scores are then normalised to sum 1 over the k
    chosen and scaled by ``routed_scale``). ``router`` names the router module:
    ``"linear"`` (one matrix) or ``"mlp"`` (:class:`MlpRouter`, a small MLP
    of width ``router_width`` whose state is carried from layer to layer).
    The selection is the top-k of the scores, with no auxiliary loss.
    ``selection_bias`` is for models that balance their experts' loads by
    a bias on the SELECTION (ZAYA1, DeepSeek-V3): a function of what the
    selection ranks, ``[B, S, E]`` — the router's logits under ``softmax``
    (which keeps their order), the scores under ``sigmoid`` — that returns
    what is added to them before the top-k and nowhere else: the gates
    stay the unbiased scores and no gradient passes through it. Those models carry
    the bias from step to step outside the gradient; this trainer carries
    no router state yet (ROADMAP Reach 1), so the caller says how the
    bias is set. ``None``: the plain top-k."""

    num_experts: int
    top_k: int = 1
    held: tuple[int, int] | None = None
    scoring: str = "softmax"
    router: str = "linear"
    router_width: int = 256
    selection_bias: Callable[[jax.Array], jax.Array] | None = None
    routed_scale: float = 1.0

    def __post_init__(self):
        first, count = self.held_range
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"held={self.held} lies outside 0..{self.num_experts}"
            )
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} of {self.num_experts}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        if self.router not in ("linear", "mlp"):
            raise ValueError(f"unknown router {self.router!r}")

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held if self.held is not None else (0, self.num_experts)


class MlpRouter(nn.Module):
    """Router as a small MLP with a state carried through the layer stack:
    ``r = u·W_down + b``; ``r += depth_scale ⊙ r_prev``; logits =
    ``W_3 gelu(W_2 gelu(W_1 RMSNorm(r)))``, each with its bias. All
    float32. Returns ``(logits [T, E], r [T, width])``; ``r`` goes on to the
    next layer's router (``r_prev``; nought before the first)."""

    num_experts: int
    width: int = 256
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, u, r_prev):
        # float32 in full: a TPU's default matmul precision rounds float32
        # operands to bf16, and the selection is an argmax over near ties
        f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
        r = nn.Dense(self.width, name="down", **f32)(u.astype(jnp.float32))
        depth_scale = self.param(
            "depth_scale", nn.initializers.ones_init(), (self.width,),
            jnp.float32,
        )
        r = r + depth_scale * r_prev.astype(jnp.float32)
        h = nn.RMSNorm(epsilon=self.norm_eps, name="norm",
                       dtype=jnp.float32, param_dtype=jnp.float32)(r)
        h = nn.gelu(nn.Dense(self.width, name="fc1", **f32)(h))
        h = nn.gelu(nn.Dense(self.width, name="fc2", **f32)(h))
        logits = nn.Dense(self.num_experts, name="out", **f32)(h)
        return logits, r


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` of the rows; its backward is
    the gather by ``inverse`` instead of the scatter-add a plain ``take``
    transposes to."""
    return jnp.take(x, perm, axis=0)


def _permute_rows_fwd(x, perm, inverse):
    return jnp.take(x, perm, axis=0), (perm, inverse)


def _permute_rows_bwd(res, g):
    perm, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_choices(x, order, inverse, k):
    """``x[order // k]``: the token of each of the ``T·k`` (token, choice)
    rows in sorted order (row ``t·k + c`` unsorted is token ``t``'s choice
    ``c``). Its backward un-sorts the rows' gradients by ``inverse`` and
    sums a token's ``k``: a gather and a sum where a plain ``take``
    transposes to a scatter-add over ``T·k`` rows."""
    return jnp.take(x, order // k, axis=0)


def _rows_of_choices_fwd(x, order, inverse, k):
    return jnp.take(x, order // k, axis=0), (inverse,)


def _rows_of_choices_bwd(k, res, g):
    (inverse,) = res
    rows, d = g.shape
    by_token = jnp.take(g, inverse, axis=0).reshape(rows // k, k, d)
    return (jnp.sum(by_token, axis=1, dtype=jnp.float32).astype(g.dtype),
            None, None)


_rows_of_choices.defvjp(_rows_of_choices_fwd, _rows_of_choices_bwd)


# -- the sorted rows in chunks: row-wide work over the live ones only ---------
#
# A shard that holds ``count`` of ``num_experts`` experts sees about
# ``count / num_experts`` of its ``T·k`` sorted rows live (routed to a held
# expert); the rest sort last and feed nothing. The rows are cut into
# ``n_chunks`` equal chunks of a static size and every row-wide stage runs
# chunk by chunk over the chunks that hold a live row: chunk 0 straight,
# the others in a loop whose trip count the device reads from ``n_live``.
# No shape depends on the routing and no row is dropped: an uneven router
# pays for one chunk more. Between the stages the rows travel as a pair
# ``(chunk 0, the other chunks)``: chunk 0 goes from one stage's kernels to
# the next's as it is; the rest is written only in a step that needs it.

_ROW_TILE = 16  # rows of a bf16 tile: every chunk starts on a tile's edge

# The rules below sit in a ``jax.jit(inline=True)``: a model's expert layers
# call them with the same shapes, in the forward, the recomputed forward
# and the backward of each of the four traces ``fit`` makes of a step, so
# all but the first inline a cached jaxpr instead of tracing the loops and
# the ``jax.vjp``s inside them again (untraced-once, a four-layer step
# traced a second longer, and ``setup_s`` rose by 6 s: PR 35). Inlined, the
# equations stay directly under the caller's stage scope.
_traced_once = functools.partial(jax.jit, inline=True)


def row_chunks(rows: int, count: int, num_experts: int) -> tuple[int, int]:
    """``(chunk_rows, n_chunks)`` for ``rows`` sorted (token, choice) rows
    on a shard that holds ``count`` of ``num_experts`` experts: chunks of
    about twice the expected live rows, ``2 · rows · count / num_experts``,
    that divide ``rows`` into whole row tiles. One chunk — the layer
    without any control flow — where half the experts or more are held."""
    for n in range(max(1, num_experts // (2 * count)), 0, -1):
        if rows % (n * _ROW_TILE) == 0:
            return rows // n, n
    return rows, 1


# ``jax.lax.ragged_dot`` lowers on the TPU to the compiler's grouped-product
# kernel, whose tile on each width is the largest power of two up to 512
# that divides it: 1,856 and 2,688 (Nemotron-H) are cut 128 wide, and a
# grid step of 512 x 128 x 128 costs as much in its fixed overhead as in
# MXU work (the products ran at 5.6% of their roofline). A width that
# rounds up to a multiple of ``_WIDTH_TILE`` for at most an eighth more is
# run at that width, zero-padded; the parameters keep their shapes.
_WIDTH_TILE = 256


def grouped_widths(d: int, ff: int) -> tuple[int, int]:
    """``(d_pad, ff_pad)``: the widths :class:`GroupedExperts` runs its
    grouped products at for a model width ``d`` and an expert width
    ``ff`` — each rounded up to the next multiple of 256 where that adds
    at most an eighth of it, else as it is. Noughts in the added columns
    of ``w_up`` / ``w_gate`` give ``relu²(0) = silu(0)·0 = 0``, the added
    rows of ``w_down`` add nothing: the layer's function is unchanged."""
    def wide(width):
        up = -(-width // _WIDTH_TILE) * _WIDTH_TILE
        return up if 8 * (up - width) <= width else width

    return wide(d), wide(ff)


def _cols(x, n: int):
    """Rows ``x`` with ``n`` columns: noughts added after the last, or the
    first ``n`` kept (no operation where it has ``n``)."""
    d = x.shape[-1]
    if d < n:
        return jnp.pad(x, ((0, 0), (0, n - d)))
    return x[:, :n] if d > n else x


def _live_chunks(n_live, chunk_rows: int):
    """How many chunks run: those whose first row is live, and chunk 0."""
    return jnp.maximum(1, (n_live + chunk_rows - 1) // chunk_rows)


def _split(x, chunk_rows: int):
    """Sorted rows as the ``(chunk 0, the other chunks)`` pair."""
    return x[:chunk_rows], x[chunk_rows:]


def _live_mask(c, chunk_rows: int, n_live):
    return c * chunk_rows + jnp.arange(chunk_rows, dtype=jnp.int32) < n_live


def _over_live_chunks(fn, n_live, *chunked, first=None):
    """``fn(c, *chunk) -> (row results, sums)`` over the chunks that hold
    a live row. ``chunked`` are ``(chunk 0, the other chunks)`` pairs; the
    row results come back as such pairs (nought in the chunks that did not
    run), the sums added up over the chunks that ran. ``first``: chunk 0's
    results, where the caller has them already."""
    heads, rests = zip(*chunked)
    chunk_rows = heads[0].shape[0]
    rows, sums = fn(0, *heads) if first is None else first
    rest_rows = tuple(
        jnp.zeros((rests[0].shape[0],) + r.shape[1:], r.dtype) for r in rows
    )

    def body(c, carry):
        rest_rows, sums = carry
        at = (c - 1) * chunk_rows
        chunk, more = fn(c, *(
            jax.lax.dynamic_slice_in_dim(r, at, chunk_rows) for r in rests
        ))
        rest_rows = tuple(
            jax.lax.dynamic_update_slice_in_dim(buf, r, at, 0)
            for buf, r in zip(rest_rows, chunk)
        )
        return rest_rows, tuple(s + m for s, m in zip(sums, more))

    rest_rows, sums = jax.lax.fori_loop(
        1, _live_chunks(n_live, chunk_rows), body, (rest_rows, sums)
    )
    return tuple(zip(rows, rest_rows)), sums


@functools.partial(_traced_once, static_argnames="k")
def _sum_live_rows(x, inverse, n_live, k: int, w=None):
    """``Σ_c w[t·k + c] · x[inverse[t·k + c]]`` for every token ``t``,
    float32 ``[T, d]``, a row at or past ``n_live`` counting nought and
    never read. ``x`` is a ``(chunk 0, the other chunks)`` pair: ONE
    ``T·k``-row gather from chunk 0, and one more for every further chunk
    that holds a live row."""
    head, rest = x
    chunk_rows = head.shape[0]
    # choice-major, ``[k, T]``: a token's sum then runs over the LEADING
    # axis of the gathered ``[k, T, d]`` — no relayout of a ``T·k``-row
    # buffer whose ``k`` is no multiple of the 8-row tile
    by_choice = lambda v: v.reshape(-1, k).T

    def part(c, rows):
        at = inverse - c * chunk_rows
        at = jnp.where((at >= 0) & (at < chunk_rows) & (inverse < n_live),
                       at, chunk_rows)
        got = jnp.take(rows, by_choice(at), axis=0, mode="fill",
                       fill_value=0)
        if w is not None:
            got = got * by_choice(w)[..., None]
        return jnp.sum(got, axis=0, dtype=jnp.float32)

    return jax.lax.fori_loop(
        1, _live_chunks(n_live, chunk_rows),
        lambda c, total: total + part(c, jax.lax.dynamic_slice_in_dim(
            rest, (c - 1) * chunk_rows, chunk_rows)),
        part(0, head),
    )


@functools.partial(_traced_once, static_argnames=("k", "chunk_rows"))
def _gather_live_rows(x, order, n_live, k: int, chunk_rows: int):
    def gather(c, at):
        rows = jnp.take(x, at // k, axis=0)
        live = _live_mask(c, chunk_rows, n_live)[:, None]
        return (jnp.where(live, rows, 0),), ()

    (xs,), _ = _over_live_chunks(gather, n_live, _split(order, chunk_rows))
    return xs


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _live_rows_of_choices(x, order, inverse, n_live, k, chunk_rows):
    """:func:`_rows_of_choices` over the live chunks: ``x[order // k]`` for
    the rows before ``n_live``, nought past them, as a ``(chunk 0, the
    other chunks)`` pair. Its backward is :func:`_sum_live_rows` of the
    rows' gradients: the un-sort and a token's sum, the live rows only."""
    return _gather_live_rows(x, order, n_live, k, chunk_rows)


def _live_rows_of_choices_fwd(x, order, inverse, n_live, k, chunk_rows):
    xs = _gather_live_rows(x, order, n_live, k, chunk_rows)
    return xs, (inverse, n_live)


def _live_rows_of_choices_bwd(k, chunk_rows, res, g):
    inverse, n_live = res
    return (_sum_live_rows(g, inverse, n_live, k).astype(g[0].dtype),
            None, None, None)


_live_rows_of_choices.defvjp(_live_rows_of_choices_fwd,
                             _live_rows_of_choices_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _mix_live_rows(out, w, order, inverse, n_live, k):
    """The combine over the live rows: ``y[t] = Σ_c w[t·k + c] ·
    out[inverse[t·k + c]]`` (:func:`_sum_live_rows`; ``out`` a ``(chunk 0,
    the other chunks)`` pair, ``w`` nought where a choice is not held).
    Its backward runs in SORTED order over the live chunks — the rows'
    gradient ``w[order[j]] · dy[order[j] // k]`` and the gates'
    ``<out[j], dy[order[j] // k]>`` — and un-sorts the gates' scalars
    only."""
    return _sum_live_rows(out, inverse, n_live, k, w).astype(w.dtype)


def _mix_live_rows_fwd(out, w, order, inverse, n_live, k):
    return (_mix_live_rows(out, w, order, inverse, n_live, k),
            (out, w, order, inverse, n_live))


@functools.partial(_traced_once, static_argnames="k")
def _mix_live_rows_bwd(k, res, dy):
    out, w, order, inverse, n_live = res
    chunk_rows = out[0].shape[0]

    def grads(c, out_c, at):
        _, vjp = jax.vjp(lambda o, g: o * g[:, None], out_c, jnp.take(w, at))
        d_out, d_w = vjp(jnp.take(dy, at // k, axis=0))
        live = _live_mask(c, chunk_rows, n_live)
        return (jnp.where(live[:, None], d_out, 0),
                jnp.where(live, d_w, 0)), ()

    (d_out, d_w), _ = _over_live_chunks(
        grads, n_live, out, _split(order, chunk_rows)
    )
    return (d_out, jnp.take(jnp.concatenate(d_w), inverse),
            None, None, None)


_mix_live_rows.defvjp(_mix_live_rows_fwd, _mix_live_rows_bwd)


def select_experts(logits, routing: Routing):
    """Router logits ``[B, S, E]`` (float32) → ``(idx [B, S, k] int32,
    gates [B, S, k] float32)``. The gates are the scores of the chosen
    experts: raw for top-1 (normalising a single gate to 1 would cut the
    router off from the loss, as :func:`top_k_routing` says), normalised
    to sum 1 for k ≥ 2, times ``routing.routed_scale``.
    ``routing.selection_bias`` moves the choice only."""
    logits = logits.astype(jnp.float32)
    if routing.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        ranked, eps = scores, 1e-20
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        # the softmax keeps the logits' order, so a bias on the logits
        # reorders the selection and leaves the gates below as they were
        ranked, eps = logits, 1e-9
    if routing.selection_bias is None:
        _, idx = jax.lax.top_k(scores, routing.top_k)
    else:
        raw = jax.lax.stop_gradient(ranked)
        _, idx = jax.lax.top_k(raw + routing.selection_bias(raw),
                               routing.top_k)
    # the chosen experts' scores by a mask over the E columns: a
    # gather here transposes to a scatter-add over [tokens, E], the mask
    # stays elementwise work in both passes
    chosen = jax.nn.one_hot(idx, routing.num_experts, dtype=scores.dtype)
    gates = jnp.sum(scores[..., None, :] * chosen, axis=-1)
    if routing.top_k > 1:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + eps)
    if routing.routed_scale != 1.0:
        gates = routing.routed_scale * gates
    return idx.astype(jnp.int32), gates


def dropless_moe(owner: nn.Module, u, r_prev=None, *, routing: Routing,
                 ffn_dim: int, shared_dim: int = 0, dtype=jnp.float32,
                 mesh=None, norm_eps: float = 1e-5,
                 expert_act: str = "swiglu"):
    """Dropless expert FFN over the experts ``routing.held``: SiLU-gated
    (``expert_act="swiglu"``) or two matrices around a squared ReLU
    (``"relu2"``: ``relu(x·w_up)²·w_down``), the shared expert alike.

    Call it inside ``owner``'s compact method: the router
    (``moe_router``) and the stacked expert weights (``moe_experts``,
    ``[held, d, ff]``) become ``owner``'s children and the four stages run
    under ``moe_router`` / ``moe_dispatch`` / ``moe_experts`` /
    ``moe_combine``, so that a device trace separates them by the first
    two components of an op's name (``h_N/moe_experts``; the contract in
    ``tpudist/telemetry/trace.py``).

    ``u``: ``[B, S, d]`` normed input (float32 for the router; the experts
    compute in ``dtype``). ``r_prev``: the MLP router's carried state
    ``[B, S, width]`` or ``None``. Returns ``(y [B, S, d], r)``.

    ``shared_dim`` > 0 adds a shared expert (``moe_shared``, one FFN of
    that width and of the same ``expert_act`` over EVERY token, ungated)
    to the result: every
    shard of an expert-parallel layer computes it alike, so the shards'
    results add up to the whole layer's with it counted once.

    No capacity, no dropped token: the ``T·k`` (token, choice) rows are
    stably sorted by local expert id, rows whose expert is not held sort
    past the last group, and one grouped product (``jax.lax.ragged_dot``)
    a weight runs over the held groups — those rows are neither computed
    nor stood in for. All shapes are static, and still only the live rows
    are worked on: the sorted rows are cut into :func:`row_chunks`'
    ``n_chunks`` equal chunks, the gathers, products, masks and the
    backward of each stage run chunk by chunk, chunk 0 always and a
    further chunk only while its first row is before ``n_live =
    sum(sizes)`` (a loop INSIDE each stage's scope whose trip count the
    device reads; nothing is dropped when the router is uneven, the step
    pays for one chunk more). What stays ``T·k`` rows wide is one gather
    in the combine and its mirror in the dispatch's backward, from the
    live rows. With one chunk (half the experts or more held) the layer is
    the plain one: no loop, no branch. The backward is the same grouped
    product transposed; the un-sort is a gather both ways.
    Counters (``moe_stats``, sown on ``owner``): ``tokens`` (rows routed
    to each held expert), ``held_share`` (share of rows whose expert is
    held), ``load_max_over_mean`` (over the held experts),
    ``row_share_computed`` (rows of the chunks that ran ÷ ``T·k``:
    ``1 / n_chunks`` on an even step).
    """
    if mesh is not None and int(dict(mesh.shape).get(EXPERT_AXIS, 1)) > 1:
        raise NotImplementedError(
            "dropless_moe runs one shard's experts without the exchange; "
            "on an 'expert' mesh axis > 1 give each shard its Routing.held "
            "under a shard_map (not in this layer yet)"
        )
    b, s, d = u.shape
    T = b * s
    k = routing.top_k
    first, count = routing.held_range
    chunk_rows, n_chunks = row_chunks(T * k, count, routing.num_experts)
    tokens = u.reshape(T, d)
    if routing.router == "mlp":
        if r_prev is None:
            r_prev = jnp.zeros((b, s, routing.router_width), jnp.float32)
        logits, r = MlpRouter(
            routing.num_experts, routing.router_width, norm_eps,
            name="moe_router",
        )(tokens, r_prev.reshape(T, -1))
        r = r.reshape(b, s, -1)
    else:
        logits = nn.Dense(
            routing.num_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            name="moe_router",
        )(tokens.astype(jnp.float32))
        r = r_prev
    with jax.named_scope("moe_router"):
        idx, gates = select_experts(
            logits.reshape(b, s, routing.num_experts), routing
        )

    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(T * k) - first
        held = (local >= 0) & (local < count)
        # rows of absent experts take the key ``count``: past every group
        key = jnp.where(held, local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32)
        )
        sizes = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32,
        )
        n_live = jnp.sum(sizes)
        tokens = tokens.astype(dtype)
        if n_chunks == 1:
            live = (jnp.arange(T * k) < n_live)[:, None]
            # top-1 sorts the tokens themselves (a gather both ways); with
            # k choices a token has k rows and its gradient is their sum
            xs = (_permute_rows(tokens, order, inverse) if k == 1
                  else _rows_of_choices(tokens, order, inverse, k))
            # the rows past the last group feed nothing: nought in, and
            # (below) nought out, whatever the grouped product leaves there
            xs = jnp.where(live, xs, 0)
        else:
            xs = _live_rows_of_choices(tokens, order, inverse, n_live, k,
                                       chunk_rows)

    out = GroupedExperts(count, ffn_dim, dtype, act=expert_act,
                         name="moe_experts")(xs, sizes)

    with jax.named_scope("moe_combine"):
        w = (gates.reshape(T * k) * held).astype(dtype)
        if n_chunks == 1:
            out = jnp.where(live, out, 0)
            y = _permute_rows(out, inverse, order) * w[:, None]
            if k > 1:
                y = jnp.sum(y.reshape(T, k, d), axis=1)
        else:
            y = _mix_live_rows(out, w, order, inverse, n_live, k)
    if shared_dim:
        y = y + SharedExpert(shared_dim, dtype, act=expert_act,
                             name="moe_shared")(tokens)

    load = sizes.astype(jnp.float32)
    owner.sow("moe_stats", "tokens", load)
    owner.sow("moe_stats", "held_share", jnp.sum(load) / (T * k))
    owner.sow(
        "moe_stats", "load_max_over_mean",
        jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0),
    )
    # one chunk always runs whole: a constant, so that the plain layer's
    # step gains no operation for it
    owner.sow(
        "moe_stats", "row_share_computed",
        _live_chunks(n_live, chunk_rows).astype(jnp.float32) / n_chunks
        if n_chunks > 1 else jnp.float32(1.0),
    )
    return y.reshape(b, s, d), r


def relu2(a):
    """The squared ReLU of a non-gated expert (``mlp_hidden_act: relu2``)."""
    return jnp.square(jax.nn.relu(a))


class SharedExpert(nn.Module):
    """The expert every token passes: ``(silu(x·w_gate) ⊙ x·w_up)·w_down``
    (``act="swiglu"``) or ``relu(x·w_up)²·w_down`` (``"relu2"``), no bias
    (``n_shared_experts`` of a DeepSeek-V3-family layer are one FFN of
    their summed width)."""

    ffn_dim: int
    dtype: Any = jnp.float32
    act: str = "swiglu"

    @nn.compact
    def __call__(self, x):
        dense = lambda name, width: nn.Dense(
            width, use_bias=False, dtype=self.dtype, name=name
        )
        if self.act == "relu2":
            return dense("w_down", x.shape[-1])(
                relu2(dense("w_up", self.ffn_dim)(x)))
        if self.act != "swiglu":
            raise ValueError(f"unknown expert_act {self.act!r}")
        h = nn.silu(dense("w_gate", self.ffn_dim)(x)) \
            * dense("w_up", self.ffn_dim)(x)
        return dense("w_down", x.shape[-1])(h)


class GroupedExperts(nn.Module):
    """The held experts' FFNs over rows sorted by expert:
    ``(silu(x·w_gate[e]) ⊙ x·w_up[e])·w_down[e]`` for the rows of group
    ``e``, as three grouped products (``jax.lax.ragged_dot``), or with
    ``act="relu2"`` ``relu(x·w_up[e])²·w_down[e]``, two. Rows past
    ``sum(sizes)`` belong to no group; what the product leaves there is
    not defined (the TPU lowering leaves values) and the caller masks it.
    ``xs`` is the sorted rows, or the ``(chunk 0, the other chunks)`` pair
    :func:`dropless_moe` cuts them into: then the products run over the
    chunks that hold a live row and the result is such a pair too. The
    products run at :func:`grouped_widths`: the weights are zero-padded
    as they are cast, and a chunk's rows as it is worked on."""

    count: int
    ffn_dim: int
    dtype: Any = jnp.float32
    act: str = "swiglu"

    @nn.compact
    def __call__(self, xs, sizes):
        chunked = isinstance(xs, tuple)
        d = (xs[0] if chunked else xs).shape[-1]
        ff = self.ffn_dim
        d_pad, ff_pad = grouped_widths(d, ff)

        def w(name, shape, wide):
            p = self.param(
                name, nn.initializers.lecun_normal(batch_axis=(0,)),
                (self.count, *shape), jnp.float32,
            ).astype(self.dtype)
            if shape == wide:
                return p
            return jnp.pad(p, [(0, 0)] + [(0, n - m)
                                          for m, n in zip(shape, wide)])

        into, out_of = ((d, ff), (d_pad, ff_pad)), ((ff, d), (ff_pad, d_pad))
        if self.act == "relu2":
            wu, wd = w("w_up", *into), w("w_down", *out_of)
            if chunked:
                return _relu2_ffn_live(xs, wu, wd, sizes)
            return _cols(jax.lax.ragged_dot(
                relu2(jax.lax.ragged_dot(_cols(xs, d_pad), wu, sizes)), wd,
                sizes), d)
        if self.act != "swiglu":
            raise ValueError(f"unknown expert_act {self.act!r}")
        wg, wu = w("w_gate", *into), w("w_up", *into)
        wd = w("w_down", *out_of)
        if chunked:
            return _gated_ffn_live(xs, wg, wu, wd, sizes)
        x = _cols(xs, d_pad)
        h = nn.silu(jax.lax.ragged_dot(x, wg, sizes)) \
            * jax.lax.ragged_dot(x, wu, sizes)
        return _cols(jax.lax.ragged_dot(h, wd, sizes), d)


def _chunk_sizes(sizes, c, chunk_rows: int):
    """The rows of each group that lie in chunk ``c`` of the sorted rows."""
    ends = jnp.cumsum(sizes)
    lo = c * chunk_rows
    return jnp.clip(ends, lo, lo + chunk_rows) \
        - jnp.clip(ends - sizes, lo, lo + chunk_rows)


@jax.custom_vjp
def _gated_ffn_live(xs, wg, wu, wd, sizes):
    """:class:`GroupedExperts`' three grouped products over the chunks that
    hold a live row, each chunk with its own part of the group sizes;
    ``xs`` and the result are ``(chunk 0, the other chunks)`` pairs. The
    backward is chunked alike, from chunk 0's kept ``x·w_gate`` and
    ``x·w_up`` (a further chunk computes its two again); a chunk's rows
    past the last group get no gradient. Weights wider than the rows
    (:func:`grouped_widths`) take each chunk's rows padded with noughts,
    and its results and row gradients cut back to the rows' width."""
    return _gated_ffn_live_fwd(xs, wg, wu, wd, sizes)[0]


@_traced_once
def _gated_ffn_live_fwd(xs, wg, wu, wd, sizes):
    chunk_rows = xs[0].shape[0]

    def ffn(c, x):
        sz = _chunk_sizes(sizes, c, chunk_rows)
        wide = _cols(x, wu.shape[1])
        a = jax.lax.ragged_dot(wide, wg, sz)
        b = jax.lax.ragged_dot(wide, wu, sz)
        return _cols(jax.lax.ragged_dot(nn.silu(a) * b, wd, sz),
                     x.shape[-1]), a, b

    out, a, b = ffn(0, xs[0])
    (out,), _ = _over_live_chunks(
        lambda c, x: ((ffn(c, x)[0],), ()), jnp.sum(sizes), xs,
        first=((out,), ()),
    )
    return out, (xs, a, b, wg, wu, wd, sizes)


@_traced_once
def _gated_ffn_live_bwd(res, d_out):
    xs, a0, b0, wg, wu, wd, sizes = res
    chunk_rows = xs[0].shape[0]
    n_live = jnp.sum(sizes)

    def grads(c, x, d_out, a=None, b=None):
        sz = _chunk_sizes(sizes, c, chunk_rows)
        product = lambda rows, w: jax.lax.ragged_dot(rows, w, sz)
        d, x = x.shape[-1], _cols(x, wu.shape[1])
        if a is None:
            a, b = product(x, wg), product(x, wu)
        h, gate_vjp = jax.vjp(lambda a, b: nn.silu(a) * b, a, b)
        d_h, d_wd = jax.vjp(product, h, wd)[1](_cols(d_out, wd.shape[-1]))
        d_a, d_b = gate_vjp(d_h)
        d_xa, d_wg = jax.vjp(product, x, wg)[1](d_a)
        d_xb, d_wu = jax.vjp(product, x, wu)[1](d_b)
        live = _live_mask(c, chunk_rows, n_live)[:, None]
        return (jnp.where(live, _cols(d_xa + d_xb, d), 0),), \
            (d_wg, d_wu, d_wd)

    (d_xs,), d_ws = _over_live_chunks(
        grads, n_live, xs, d_out, first=grads(0, xs[0], d_out[0], a0, b0)
    )
    return (d_xs, *d_ws, None)


_gated_ffn_live.defvjp(_gated_ffn_live_fwd, _gated_ffn_live_bwd)


@jax.custom_vjp
def _relu2_ffn_live(xs, wu, wd, sizes):
    """:func:`_gated_ffn_live` for ``act="relu2"``: the two grouped
    products over the chunks that hold a live row, chunk 0's ``x·w_up``
    kept for the backward (a further chunk computes it again)."""
    return _relu2_ffn_live_fwd(xs, wu, wd, sizes)[0]


@_traced_once
def _relu2_ffn_live_fwd(xs, wu, wd, sizes):
    chunk_rows = xs[0].shape[0]

    def ffn(c, x):
        sz = _chunk_sizes(sizes, c, chunk_rows)
        a = jax.lax.ragged_dot(_cols(x, wu.shape[1]), wu, sz)
        return _cols(jax.lax.ragged_dot(relu2(a), wd, sz), x.shape[-1]), a

    out, a = ffn(0, xs[0])
    (out,), _ = _over_live_chunks(
        lambda c, x: ((ffn(c, x)[0],), ()), jnp.sum(sizes), xs,
        first=((out,), ()),
    )
    return out, (xs, a, wu, wd, sizes)


@_traced_once
def _relu2_ffn_live_bwd(res, d_out):
    xs, a0, wu, wd, sizes = res
    chunk_rows = xs[0].shape[0]
    n_live = jnp.sum(sizes)

    def grads(c, x, d_out, a=None):
        sz = _chunk_sizes(sizes, c, chunk_rows)
        product = lambda rows, w: jax.lax.ragged_dot(rows, w, sz)
        d, x = x.shape[-1], _cols(x, wu.shape[1])
        if a is None:
            a = product(x, wu)
        h, act_vjp = jax.vjp(relu2, a)
        d_h, d_wd = jax.vjp(product, h, wd)[1](_cols(d_out, wd.shape[-1]))
        (d_a,) = act_vjp(d_h)
        d_x, d_wu = jax.vjp(product, x, wu)[1](d_a)
        live = _live_mask(c, chunk_rows, n_live)[:, None]
        return (jnp.where(live, _cols(d_x, d), 0),), (d_wu, d_wd)

    (d_xs,), d_ws = _over_live_chunks(
        grads, n_live, xs, d_out, first=grads(0, xs[0], d_out[0], a0)
    )
    return (d_xs, *d_ws, None)


_relu2_ffn_live.defvjp(_relu2_ffn_live_fwd, _relu2_ffn_live_bwd)
