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
                 mesh=None, norm_eps: float = 1e-5):
    """Dropless expert FFN (SiLU-gated) over the experts ``routing.held``.

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

    ``shared_dim`` > 0 adds a shared expert (``moe_shared``, one SiLU-gated
    FFN of that width over EVERY token, ungated) to the result: every
    shard of an expert-parallel layer computes it alike, so the shards'
    results add up to the whole layer's with it counted once.

    No capacity, no dropped token: the ``T·k`` (token, choice) rows are
    stably sorted by local expert id, rows whose expert is not held sort
    past the last group, and one grouped product (``jax.lax.ragged_dot``)
    a weight runs over the held groups — those rows are neither computed
    nor stood in for. All shapes are static. The backward is the same
    grouped product transposed; the un-sort is a gather both ways.
    Counters (``moe_stats``, sown on ``owner``): ``tokens`` (rows routed
    to each held expert), ``held_share`` (share of rows whose expert is
    held), ``load_max_over_mean`` (over the held experts).
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
        live = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]
        tokens = tokens.astype(dtype)
        # top-1 sorts the tokens themselves (a gather both ways); with k
        # choices a token has k rows and its gradient is their sum
        xs = (_permute_rows(tokens, order, inverse) if k == 1
              else _rows_of_choices(tokens, order, inverse, k))
        # the rows past the last group feed nothing: nought in, and (below)
        # nought out, whatever the grouped product leaves there
        xs = jnp.where(live, xs, 0)

    out = GroupedExperts(count, ffn_dim, dtype, name="moe_experts")(
        xs, sizes
    )

    with jax.named_scope("moe_combine"):
        out = jnp.where(live, out, 0)
        w = (gates.reshape(T * k) * held).astype(dtype)
        y = _permute_rows(out, inverse, order) * w[:, None]
        if k > 1:
            y = jnp.sum(y.reshape(T, k, d), axis=1)
    if shared_dim:
        y = y + SharedExpert(shared_dim, dtype, name="moe_shared")(tokens)

    load = sizes.astype(jnp.float32)
    owner.sow("moe_stats", "tokens", load)
    owner.sow("moe_stats", "held_share", jnp.sum(load) / (T * k))
    owner.sow(
        "moe_stats", "load_max_over_mean",
        jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0),
    )
    return y.reshape(b, s, d), r


class SharedExpert(nn.Module):
    """The expert every token passes: ``(silu(x·w_gate) ⊙ x·w_up)·w_down``,
    no bias (``n_shared_experts`` of a DeepSeek-V3-family layer are one FFN
    of their summed width)."""

    ffn_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda name, width: nn.Dense(
            width, use_bias=False, dtype=self.dtype, name=name
        )
        h = nn.silu(dense("w_gate", self.ffn_dim)(x)) \
            * dense("w_up", self.ffn_dim)(x)
        return dense("w_down", x.shape[-1])(h)


class GroupedExperts(nn.Module):
    """The held experts' SiLU-gated FFNs over rows sorted by expert:
    ``(silu(x·w_gate[e]) ⊙ x·w_up[e])·w_down[e]`` for the rows of group
    ``e``, as three grouped products (``jax.lax.ragged_dot``). Rows past
    ``sum(sizes)`` belong to no group; what the product leaves there is
    not defined (the TPU lowering leaves values) and the caller masks it."""

    count: int
    ffn_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xs, sizes):
        d = xs.shape[-1]
        w = lambda name, shape: self.param(
            name, nn.initializers.lecun_normal(batch_axis=(0,)), shape,
            jnp.float32,
        ).astype(self.dtype)
        wg = w("w_gate", (self.count, d, self.ffn_dim))
        wu = w("w_up", (self.count, d, self.ffn_dim))
        wd = w("w_down", (self.count, self.ffn_dim, d))
        h = nn.silu(jax.lax.ragged_dot(xs, wg, sizes)) \
            * jax.lax.ragged_dot(xs, wu, sizes)
        return jax.lax.ragged_dot(h, wd, sizes)
