"""Chunked state-space scan (SSD, the state-space duality of Mamba-2,
arXiv:2405.21060) as a Pallas TPU forward kernel and a Pallas TPU backward
kernel.

No counterpart in the system this repo was modelled on. The recurrence it
computes, per head ``h`` of ``P`` channels in group ``g(h) = h // (H / G)``
(the group's ``B`` and ``C`` are ``N`` wide), over positions ``t``::

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t^T x_t        (state [N, P])
    y_t = C_t h_t + D x_t

Cut into chunks of ``L`` positions, with ``cs_t`` the cumulative sum of
``dt A`` inside a chunk and ``H_c`` the state a chunk starts from::

    y_t = sum_{s <= t in chunk} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
          + exp(cs_t) C_t H_c + D x_t
    H_{c+1} = exp(cs_L) H_c + sum_s exp(cs_L - cs_s) dt_s B_s^T x_s

**Forward** (:func:`_fwd_kernel`): one grid step a (batch row, group,
chunk), the chunk axis sequential; the group's heads are one block of
``H / G · P`` lanes, taken a 128-lane tile (two heads of 64) at a time; the
state of the tile's heads lives in VMEM scratch, float32, ``[N, 128]``.
Per chunk it forms ``C B^T`` once for the group, then per head the masked
decay matrix and the in-chunk product on the MXU, adds the carried state's
``C H_c`` and the ``D`` skip, and moves the state on. It writes ``y`` and
every chunk's starting state: the two residuals (``tpudist/remat.py``
``KERNEL_RESIDUALS``: ``ssd_out``, ``ssd_states``).

**Backward** (:func:`_bwd_kernel`): the forward's mirror, on the same grid
with the chunks walked from the last. The cotangent of the state a chunk
hands on, ``G``, lives in VMEM scratch (float32, in the forward's tiles)
and starts from nought; each step reads the chunk's saved starting state
``H_c`` (it rebuilds nothing), forms ``C B^T`` once for the group and per
head the masked decay matrix, and gives ``dx = M^T dy + (B ⊙ W) G + D dy``
(``M`` the in-chunk product's matrix, ``W_s = dt_s exp(cs_L - cs_s)``),
``dB`` and ``dC`` (through ``C B^T``, ``W`` and ``exp(cs_t) C H_c``), each
position's cotangent of ``dt`` (its direct terms) and of ``cs``, the
chunk's share of ``dD``, and moves ``G`` back: ``G <- exp(cs_L) G + sum_t
exp(cs_t) C_t^T dy_t``. XLA keeps the per-position work: the in-chunk
reverse cumulative sum of ``cs``'s cotangent, and from it ``dt``'s and
``A``'s.

Decays, exponentials, the state and its cotangent are float32; matmul
operands are in the compute dtype (``x``'s). Cumulative sums are taken by
XLA in float32 and handed to the kernels in the layouts they read (a
column per head, a row per head, exact bfloat16 parts). :func:`ssd_chunked`
is the same mathematics in XLA alone (the microbenchmark's comparison, and
the tests'); :func:`ssd_cost` the work a call needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops import backend
from tpudist.remat import KERNEL_RESIDUALS

F32 = jnp.float32
LANES = 128
# the forward rule names its two outputs so; ``dots_saveable`` keeps both
RESIDUALS = KERNEL_RESIDUALS[2:]


def chunk_count(seq: int, chunk: int) -> int:
    """Chunks of a sequence: the kernel grid's last axis."""
    if seq % chunk:
        raise ValueError(f"sequence {seq} is not a multiple of chunk {chunk}")
    return seq // chunk


def _heads_per_tile(heads_per_group: int, head_dim: int) -> int:
    """Heads that share one 128-lane tile of ``x`` (2 of 64), at most a
    group's."""
    n = max(1, min(heads_per_group, LANES // head_dim))
    while heads_per_group % n:
        n -= 1
    return n


def _fwd_kernel(x_ref, c_ref, bt_ref, csc_ref, csr_ref, dtr_ref, d_ref,
                y_ref, st_ref, state, *, head_dim: int, per_tile: int):
    """One (batch row, group, chunk). ``x_ref`` ``[L, hpg·P]``, ``c_ref``
    ``[L, N]``, ``bt_ref`` ``[N, L]`` (``B`` transposed), ``csc_ref``
    ``[L, hpg]`` and ``csr_ref`` / ``dtr_ref`` ``[hpg, L]`` float32,
    ``d_ref`` ``[1, hpg]``; writes ``y_ref`` ``[L, hpg·P]`` and the chunk's
    starting state ``st_ref`` ``[tiles, N, per_tile·P]``."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    L = x_ref.shape[0]
    hpg = csr_ref.shape[0]
    width = per_tile * head_dim
    cdt = x_ref.dtype
    c, bt = c_ref[...], bt_ref[...]
    cb = jnp.dot(c, bt, preferred_element_type=F32)          # [L, L]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    csc, csr, dtr, d = csc_ref[...], csr_ref[...], dtr_ref[...], d_ref[...]
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, hpg), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (hpg, 1), 0)
    last_lane = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1) == L - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    # a head's column / row of a per-head table, exactly (a masked sum)
    column = lambda v, j: jnp.sum(jnp.where(head_lane == j, v, 0.0), axis=1,
                                  keepdims=True)
    row = lambda v, j: jnp.sum(jnp.where(head_row == j, v, 0.0), axis=0,
                               keepdims=True)
    for tile in range(hpg // per_tile):
        lanes = slice(tile * width, (tile + 1) * width)
        xt = x_ref[:, lanes]
        h = state[tile]                                      # [N, width]
        st_ref[tile] = h
        y = jnp.zeros((L, width), F32)
        update = jnp.zeros(h.shape, F32)
        into = jnp.zeros((L, width), F32)   # exp(cs_t), each head's lanes
        keep = jnp.zeros((1, width), F32)   # exp(cs_L), each head's lanes
        skip = jnp.zeros((1, width), F32)   # D
        for i in range(per_tile):
            j = tile * per_tile + i
            mine = (lane >= i * head_dim) & (lane < (i + 1) * head_dim)
            cs_t, cs_s, dt_s = column(csc, j), row(csr, j), row(dtr, j)
            decay = jnp.exp(jnp.where(causal, cs_t - cs_s, -jnp.inf))
            xi = jnp.where(mine, xt, 0).astype(cdt)
            y += jnp.dot((cb * decay * dt_s).astype(cdt), xi,
                         preferred_element_type=F32)
            last = jnp.sum(jnp.where(last_lane, cs_s, 0.0), axis=1,
                           keepdims=True)                    # [1, 1]
            weight = dt_s * jnp.exp(last - cs_s)             # [1, L]
            update += jnp.dot((bt * weight).astype(cdt), xi,
                              preferred_element_type=F32)
            into = jnp.where(mine, jnp.exp(cs_t), into)
            keep = jnp.where(mine, jnp.exp(last), keep)
            skip = jnp.where(mine, column(d, j), skip)
        y += into * jnp.dot(c, h.astype(cdt), preferred_element_type=F32)
        y += skip * xt.astype(F32)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        state[tile] = keep * h + update


def _chunk_cumsum(dt, A, chunk: int):
    """``cs``: the cumulative sum of ``dt A`` inside each chunk, float32,
    ``[b, S, H]``."""
    b, s, h = dt.shape
    a = dt.astype(F32) * A.astype(F32)
    return jnp.cumsum(a.reshape(b, s // chunk, chunk, h), axis=2).reshape(
        b, s, h)


def _fwd(x, dt, A, B, C, D, chunk: int):
    """``(y [b, S, H, P], states)``: the kernel's output and every chunk's
    starting state as it writes it, ``[b, chunks, G, tiles, N, per_tile·P]``
    float32."""
    b, s, heads, p = x.shape
    groups, n = B.shape[2:]
    hpg = heads // groups
    nc = chunk_count(s, chunk)
    per_tile = _heads_per_tile(hpg, p)
    tiles = hpg // per_tile
    cs = _chunk_cumsum(dt, A, chunk).reshape(b, s, groups, hpg)
    rows = lambda v: v.transpose(0, 2, 3, 1)                 # [b, G, hpg, S]
    csc = cs.transpose(0, 2, 1, 3)                           # [b, G, S, hpg]
    dtr = rows(dt.astype(F32).reshape(b, s, groups, hpg))
    x2 = x.reshape(b, s, heads * p)
    c2 = C.astype(x.dtype).reshape(b, s, groups * n)
    bt = B.astype(x.dtype).reshape(b, s, groups * n).transpose(0, 2, 1)
    d3 = D.astype(F32).reshape(groups, 1, hpg)
    kernel = functools.partial(_fwd_kernel, head_dim=p, per_tile=per_tile)
    y, states = pl.pallas_call(
        kernel,
        grid=(b, groups, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, hpg * p), lambda i, g, c: (i, c, g)),
            pl.BlockSpec((None, chunk, n), lambda i, g, c: (i, c, g)),
            pl.BlockSpec((None, n, chunk), lambda i, g, c: (i, g, c)),
            pl.BlockSpec((None, None, chunk, hpg),
                         lambda i, g, c: (i, g, c, 0)),
            pl.BlockSpec((None, None, hpg, chunk),
                         lambda i, g, c: (i, g, 0, c)),
            pl.BlockSpec((None, None, hpg, chunk),
                         lambda i, g, c: (i, g, 0, c)),
            pl.BlockSpec((None, 1, hpg), lambda i, g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, hpg * p), lambda i, g, c: (i, c, g)),
            pl.BlockSpec((None, None, None, tiles, n, per_tile * p),
                         lambda i, g, c: (i, c, g, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * p), x.dtype),
            jax.ShapeDtypeStruct((b, nc, groups, tiles, n, per_tile * p),
                                 F32),
        ],
        scratch_shapes=[pltpu.VMEM((tiles, n, per_tile * p), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret(),
    )(x2, c2, bt, csc, rows(cs), dtr, d3)
    return y.reshape(b, s, heads, p), states


def _split3(v):
    """``v`` (float32) as three bfloat16 parts that sum to it exactly."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(F32)).astype(jnp.bfloat16)


def _split2(v):
    """``v`` (float32) as two bfloat16 parts that sum to it within 2^-16."""
    hi = v.astype(jnp.bfloat16)
    return hi, (v - hi.astype(F32)).astype(jnp.bfloat16)


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, csr_ref, dtr_ref, parts_ref,
                dl_ref, st_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref,
                dd_ref, grad, *, head_dim: int, per_tile: int):
    """One (batch row, group, chunk), the chunks from the last. ``x_ref``
    and ``dy_ref`` ``[L, hpg·P]``, ``b_ref`` and ``c_ref`` ``[L, N]``,
    ``csr_ref`` / ``dtr_ref`` ``[hpg, L]`` float32, ``parts_ref`` ``[L,
    6·hpg]`` the same two as columns in exact bfloat16 parts
    (:func:`_split3`), ``dl_ref`` ``[1, hpg·P]`` ``D`` on each head's
    lanes, ``st_ref`` the chunk's starting state as the forward wrote it.
    Writes ``dx``, ``dB`` and ``dC`` ``[L, N]``, the cotangents of ``dt``
    (its direct terms, ``ddt_ref``) and of ``cs`` (``dcs_ref``), a row per
    head ``[hpg, L]``, float32, and ``dd_ref`` ``[1, hpg]``, the chunk's
    share of ``dD``. ``grad`` ``[tiles, N, per_tile·P]`` holds the
    cotangent of the state the chunk hands on, and leaves the cotangent of
    the one it starts from.

    The in-chunk matrices are held transposed, ``[s, t]`` (source,
    target): ``M^T = (B C^T)^T ⊙ exp(cs_t - cs_s) ⊙ dt_s``, so that ``dx =
    M^T dy`` and ``dM^T = x dy^T`` are plain products. A head's ``cs_s``
    and ``dt_s`` across the lanes, and each head's sum over its lanes, are
    products with 0/1 selections on the MXU (bfloat16 parts, float32 sums)
    where the vector unit would broadcast and reduce across lanes. ``G``
    enters its products as two parts of the compute dtype (:func:`_split2`),
    which keeps ``dt``'s and ``A``'s gradients as close to the float32
    recurrence as the chunk-parallel XLA form kept them."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        grad[...] = jnp.zeros_like(grad)

    L = x_ref.shape[0]
    hpg = csr_ref.shape[0]
    width = per_tile * head_dim
    cdt = x_ref.dtype
    bf = jnp.bfloat16
    nt = (((1,), (1,)), ((), ()))           # a b^T
    tn = (((0,), (0,)), ((), ()))           # a^T b
    b, c = b_ref[...], c_ref[...]
    cbt = jax.lax.dot_general(b, c, nt, preferred_element_type=F32)  # [s, t]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 0))
    csr, dtr, parts = csr_ref[...], dtr_ref[...], parts_ref[...]
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, hpg), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (hpg, 1), 0)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    # column r of parts_ref: part r // hpg (cs 0-2, dt 3-5) of head r % hpg
    part = jax.lax.broadcasted_iota(jnp.int32, (6 * hpg, 1), 0)
    part_of_cs, part_head = part < 3 * hpg, part % hpg

    def spread(of_lane, n):
        """``[L, 2n]``: lane ``k < n`` holds ``cs_s`` of head
        ``of_lane[k]``, lane ``n + k`` its ``dt_s``, exactly."""
        sel = ((part_head == jnp.concatenate([of_lane, of_lane], axis=1))
               & (part_of_cs == (jax.lax.broadcasted_iota(
                   jnp.int32, (1, 2 * n), 1) < n)))
        return jnp.dot(parts, sel.astype(bf), preferred_element_type=F32)

    dcbt = jnp.zeros((L, L), F32)
    db = jnp.zeros(b.shape, F32)
    dc = jnp.zeros(c.shape, F32)
    lane_dt = jnp.zeros((hpg, L), F32)  # d dt_s through W_s
    lane_cs = jnp.zeros((hpg, L), F32)  # d cs through C H_c and W_s
    in_cs = jnp.zeros((hpg, L), F32)    # d cs_t through M
    q_cols = jnp.zeros((L, hpg), F32)   # d dt_s through M
    dd = jnp.zeros((1, hpg), F32)
    for tile in range(hpg // per_tile):
        lanes = slice(tile * width, (tile + 1) * width)
        xt, dyt = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = xt.astype(F32), dyt.astype(F32)
        h = st_ref[tile]                                     # H_c [N, width]
        g = grad[tile]                                       # of H_{c+1}
        of_lane = jnp.full((1, width), tile * per_tile, jnp.int32)
        for i in range(1, per_tile):
            of_lane += (lane >= i * head_dim).astype(jnp.int32)
        per_lane = spread(of_lane, width)
        cs_l, dt_l = per_lane[:, :width], per_lane[:, width:]
        into = jnp.exp(cs_l)                                 # exp(cs_t)
        keep = jnp.exp(cs_l[L - 1:, :])                      # exp(cs_L)
        decay = jnp.exp(cs_l[L - 1:, :] - cs_l)              # exp(cs_L - cs_s)
        weight = dt_l * decay                                # W_s
        hc = h.astype(cdt)
        # G's products in two parts of the compute dtype (~16 bits)
        gs = [g.astype(cdt)] if cdt == F32 else _split2(g)
        ch = jnp.dot(c, hc, preferred_element_type=F32)      # C H_c
        v = jnp.zeros((L, width), F32)                       # B G
        ydy = (into * dyf).astype(cdt)
        wx = (weight * xf).astype(cdt)
        dc += jax.lax.dot_general(ydy, hc, nt, preferred_element_type=F32)
        for u in gs:
            v += jnp.dot(b, u, preferred_element_type=F32)
            db += jax.lax.dot_general(wx, u, nt, preferred_element_type=F32)
        dx = weight * v + dl_ref[:, lanes] * dyf
        own = jnp.sum(dyf * xf, axis=0, keepdims=True)       # dD, by lane
        for i in range(per_tile):
            j = tile * per_tile + i
            mine = (lane >= i * head_dim) & (lane < (i + 1) * head_dim)
            cs_t = jnp.sum(jnp.where(head_row == j, csr, 0.0), axis=0,
                           keepdims=True)                    # [1, L]
            both = spread(jnp.full((1, L), j, jnp.int32), L)
            cs_s, dt_s = both[:, :L], both[:, L:]            # [L, L]
            et = jnp.exp(jnp.where(causal, cs_t - cs_s, -jnp.inf))
            mt = cbt * et * dt_s                             # M^T
            dx += jnp.dot(mt.astype(cdt), jnp.where(mine, dyt, 0).astype(cdt),
                          preferred_element_type=F32)
            r = jax.lax.dot_general(jnp.where(mine, xt, 0).astype(cdt), dyt,
                                    nt, preferred_element_type=F32) * et
            dcbt += r * dt_s                # dM^T ⊙ E^T ⊙ dt_s
            q = r * cbt                     # dM^T ⊙ M^T / dt_s
            # cs_t gains the column sums of dM^T ⊙ M^T and cs_s loses its
            # row sums: both from one matrix, so that they cancel
            in_cs = jnp.where(head_row == j, jnp.sum(
                q * dt_s, axis=0, keepdims=True), in_cs)
            q_cols = jnp.where(head_lane == j, jnp.sum(
                q, axis=1, keepdims=True), q_cols)
            dd = jnp.where(head_lane == j, jnp.sum(jnp.where(
                mine, own, 0.0), axis=1, keepdims=True), dd)
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        onward = v * xf                     # dW_s, by lane
        tail = (keep * jnp.sum(g * h, axis=0, keepdims=True)
                + jnp.sum(onward * weight, axis=0, keepdims=True))  # d cs_L
        # each head's sum over its lanes, [hpg, L], of the two parts
        to_head = (head_row == of_lane).astype(bf)
        to_head = jnp.concatenate([to_head, to_head], axis=1)
        head_sums = lambda u: jax.lax.dot_general(
            to_head, jnp.concatenate(_split2(u), axis=1), nt,
            preferred_element_type=F32)
        lane_dt += head_sums(onward * decay)
        lane_cs += head_sums(into * dyf * ch - onward * weight
                             + jnp.where(last_row, tail, 0.0))
        grad[tile] = keep * g + jax.lax.dot_general(
            c, ydy, tn, preferred_element_type=F32)
    dcb16 = dcbt.astype(cdt)
    dc += jax.lax.dot_general(dcb16, b, tn, preferred_element_type=F32)
    db += jnp.dot(dcb16, c, preferred_element_type=F32)
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    q_rows = q_cols.T
    ddt_ref[...] = lane_dt + q_rows
    dcs_ref[...] = lane_cs + in_cs - dtr * q_rows
    dd_ref[...] = dd


# -- the chunk-parallel mathematics, one group: the XLA comparison's.
#    ``x [b, nc, L, hpg, P]``, ``dt [b, nc, L, hpg]``, ``A``, ``D``
#    ``[hpg]``, ``B``, ``C`` ``[b, nc, L, N]``, ``st [b, nc, hpg, N, P]``
#    the state each chunk starts from -----------------------------------------


def _in_chunk(x, dt, A, B, C, D, st):
    """``y`` of every chunk from the state it starts from, float32."""
    cdt = x.dtype
    cs = jnp.cumsum(dt * A, axis=2)                          # [b, nc, L, hpg]
    L = x.shape[2]
    causal = jnp.tril(jnp.ones((L, L), bool))[:, :, None]
    cb = jnp.einsum("bcln,bcsn->bcls", C, B, preferred_element_type=F32)
    gap = cs[:, :, :, None, :] - cs[:, :, None, :, :]        # [b,nc,t,s,hpg]
    decay = jnp.exp(jnp.where(causal, gap, -jnp.inf))
    m = cb[..., None] * decay * dt[:, :, None, :, :]
    y = jnp.einsum("bctsh,bcshp->bcthp", m.astype(cdt), x,
                   preferred_element_type=F32)
    y += jnp.exp(cs)[..., None] * jnp.einsum(
        "bctn,bchnp->bcthp", C, st.astype(cdt), preferred_element_type=F32)
    return y + D[:, None] * x.astype(F32)


def _carry(x, dt, A, B):
    """Each chunk's contribution to the next chunk's state ``[b, nc, hpg,
    N, P]`` and its decay ``exp(cs_L)`` ``[b, nc, hpg]``, float32."""
    cs = jnp.cumsum(dt * A, axis=2)
    last = cs[:, :, -1]
    weight = dt * jnp.exp(last[:, :, None] - cs)             # [b, nc, L, hpg]
    bw = (B[:, :, :, None, :] * weight[..., None]).astype(x.dtype)
    contrib = jnp.einsum("bclhn,bclhp->bchnp", bw, x,
                         preferred_element_type=F32)
    return contrib, jnp.exp(last)


def _by_group(x, dt, B, C, chunk: int, groups: int):
    """``[b, S, ...]`` operands as group-major chunks: ``x [G, b, nc, L,
    hpg, P]``, ``dt [G, b, nc, L, hpg]``, ``B``, ``C`` ``[G, b, nc, L, N]``."""
    b, s, heads, p = x.shape
    nc, hpg = s // chunk, heads // groups
    return (jnp.moveaxis(x.reshape(b, nc, chunk, groups, hpg, p), 3, 0),
            jnp.moveaxis(dt.reshape(b, nc, chunk, groups, hpg), 3, 0),
            jnp.moveaxis(B.reshape(b, nc, chunk, groups, -1), 3, 0),
            jnp.moveaxis(C.reshape(b, nc, chunk, groups, -1), 3, 0))


def _states(contrib, decay):
    """The state each chunk starts from: nought, then ``H_{c+1} =
    exp(cs_L) H_c + contrib_c`` — a recurrence over the chunks."""
    def step(h, inp):
        add, keep = inp
        return keep[..., None, None] * h + add, h

    _, st = jax.lax.scan(step, jnp.zeros_like(contrib[:, 0]),
                         (jnp.moveaxis(contrib, 1, 0),
                          jnp.moveaxis(decay, 1, 0)))
    return jnp.moveaxis(st, 0, 1)


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = 128):
    """:func:`ssd_scan`'s mathematics in XLA contractions alone (all
    groups at once; differentiable by JAX): the comparison the
    microbenchmark and the tests set beside the kernels."""
    b, s, heads, p = x.shape
    groups = B.shape[2]
    hpg = heads // groups
    dt, A, D = dt.astype(F32), A.astype(F32), D.astype(F32)
    xg, dtg, bg, cg = _by_group(x, dt, B.astype(x.dtype), C.astype(x.dtype),
                                chunk, groups)
    per = lambda v: v.reshape(groups, hpg)
    contrib, decay = jax.vmap(_carry)(xg, dtg, per(A), bg)
    st = jax.vmap(_states)(contrib, decay)
    y = jax.vmap(_in_chunk)(xg, dtg, per(A), bg, cg, per(D), st)
    return jnp.moveaxis(y, 0, 3).reshape(b, s, heads, p).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    return _fwd(x, dt, A, B, C, D, chunk)[0]


def _ssd_vjp_fwd(x, dt, A, B, C, D, chunk):
    """Forward rule: ``y`` and the chunks' starting states carry the names
    of ``RESIDUALS``, so that a ``dots_saveable`` checkpoint keeps them and
    a block's backward does not launch the kernel again."""
    y, states = map(checkpoint_name, _fwd(x, dt, A, B, C, D, chunk),
                    RESIDUALS)
    return y, (x, dt, A, B, C, D, states)


def _ssd_bwd(chunk, res, gy):
    """Backward rule: the kernel's reverse sweep, then the per-position sums
    it leaves — the in-chunk reverse cumulative sum of ``cs``'s cotangent,
    and from it ``dt``'s and ``A``'s."""
    x, dt, A, B, C, D, states = res
    b, s, heads, p = x.shape
    groups, n = B.shape[2:]
    hpg = heads // groups
    nc = chunk_count(s, chunk)
    per_tile = _heads_per_tile(hpg, p)
    tiles = hpg // per_tile
    cs = _chunk_cumsum(dt, A, chunk).reshape(b, s, groups, hpg)
    dt32 = dt.astype(F32)
    dtg = dt32.reshape(b, s, groups, hpg)
    rows = lambda v: v.transpose(0, 2, 3, 1)                 # [b, G, hpg, S]
    to = lambda v: v.astype(x.dtype).reshape(b, s, groups * n)
    kernel = functools.partial(_bwd_kernel, head_dim=p, per_tile=per_tile)
    back = lambda c: nc - 1 - c                              # reverse sweep
    lanes = pl.BlockSpec((None, chunk, hpg * p),
                         lambda i, g, c: (i, back(c), g))
    state = pl.BlockSpec((None, chunk, n), lambda i, g, c: (i, back(c), g))
    row = pl.BlockSpec((None, None, hpg, chunk),
                       lambda i, g, c: (i, g, 0, back(c)))
    # cs and dt as columns of exact bfloat16 parts, [b, G, S, 6·hpg]
    parts = jnp.concatenate(_split3(cs) + _split3(dtg),
                            axis=-1).transpose(0, 2, 1, 3)
    dx, dB, dC, ddt, dcs, dD = pl.pallas_call(
        kernel,
        grid=(b, groups, nc),
        in_specs=[
            lanes, lanes, state, state, row, row,
            pl.BlockSpec((None, None, chunk, 6 * hpg),
                         lambda i, g, c: (i, g, back(c), 0)),
            pl.BlockSpec((None, 1, hpg * p), lambda i, g, c: (g, 0, 0)),
            pl.BlockSpec((None, None, None, tiles, n, per_tile * p),
                         lambda i, g, c: (i, back(c), g, 0, 0, 0)),
        ],
        out_specs=[
            lanes, state, state, row, row,
            pl.BlockSpec((None, None, None, 1, hpg),
                         lambda i, g, c: (i, g, back(c), 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * p), x.dtype),
            jax.ShapeDtypeStruct((b, s, groups * n), B.dtype),
            jax.ShapeDtypeStruct((b, s, groups * n), C.dtype),
            jax.ShapeDtypeStruct((b, groups, hpg, s), F32),
            jax.ShapeDtypeStruct((b, groups, hpg, s), F32),
            jax.ShapeDtypeStruct((b, groups, nc, 1, hpg), F32),
        ],
        scratch_shapes=[pltpu.VMEM((tiles, n, per_tile * p), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret(),
    )(x.reshape(b, s, heads * p), gy.reshape(b, s, heads * p), to(B), to(C),
      rows(cs), rows(dtg), parts,
      jnp.repeat(D.astype(F32), p).reshape(groups, 1, hpg * p), states)
    heads_of = lambda v: v.transpose(0, 3, 1, 2).reshape(b, s, heads)
    dcs = heads_of(dcs)
    # cs_t sums dt_u A over u <= t in the chunk
    da = jax.lax.cumsum(dcs.reshape(b, nc, chunk, heads), axis=2,
                        reverse=True).reshape(b, s, heads)
    ddt = heads_of(ddt) + A.astype(F32) * da
    return (dx.reshape(x.shape),
            ddt.astype(dt.dtype),
            jnp.sum(dt32 * da, axis=(0, 1)).astype(A.dtype),
            dB.reshape(B.shape),
            dC.reshape(C.shape),
            jnp.sum(dD, axis=(0, 2, 3)).reshape(heads).astype(D.dtype))


_ssd.defvjp(_ssd_vjp_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128):
    """The chunked SSD scan. ``x`` ``[b, S, H, P]`` (its dtype is the
    compute dtype), ``dt`` ``[b, S, H]`` (after the softplus), ``A`` and
    ``D`` ``[H]``, ``B`` and ``C`` ``[b, S, G, N]`` with ``H`` a multiple
    of ``G``; ``S`` a multiple of ``chunk``. Returns ``y [b, S, H, P]`` in
    ``x``'s dtype, the state starting from nought."""
    b, s, heads, p = x.shape
    if B.shape != C.shape or B.shape[:2] != (b, s) or heads % B.shape[2]:
        raise ValueError(f"x {x.shape} with B {B.shape}, C {C.shape}")
    chunk_count(s, chunk)
    return _ssd(x, dt, A, B, C, D, chunk)


def ssd_cost(*, batch: int, seq: int, heads: int, head_dim: int,
             groups: int, state: int, chunk: int, itemsize: int) -> dict:
    """Operations and HBM bytes one :func:`ssd_scan` call NEEDS on one
    chip, forward and backward apart, at the chunked algorithm's shapes.
    Forward, a chunk: ``C B^T`` once a group (``2 L² N``), and a head's
    in-chunk product (``2 L² P``), carried-state read-out ``C H`` and
    state update ``B^T x`` (``2 L N P`` each). Backward: two products for
    each of those (a gradient to each operand). Bytes: forward reads ``x``,
    ``B``, ``C`` in the compute type and writes ``y`` and every chunk's
    float32 state; backward reads ``x``, ``B``, ``C``, the states and
    ``dy`` and writes ``dx``, ``dB``, ``dC``; the per-position scalars
    (``dt`` and its sums) are left out."""
    nc = chunk_count(seq, chunk)
    L, n, p = chunk, state, head_dim
    fwd = batch * nc * (groups * 2 * L * L * n
                        + heads * (2 * L * L * p + 4 * L * n * p))
    x_bytes = batch * seq * heads * p * itemsize
    bc_bytes = 2 * batch * seq * groups * n * itemsize
    st_bytes = batch * nc * heads * n * p * 4
    return {
        "fwd": {"flops": float(fwd),
                "bytes": float(2 * x_bytes + bc_bytes + st_bytes)},
        "bwd": {"flops": float(2 * fwd),
                "bytes": float(3 * x_bytes + 2 * bc_bytes + st_bytes)},
    }
