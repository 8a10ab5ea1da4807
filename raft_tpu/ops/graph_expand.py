"""Gather-free CAGRA frontier expansion: streamed edge-tile scoring.

The graph hop's one HBM-bound op used to be a random row gather
(``cagra._gather_score``): each of the ``m·width`` expanded parents pulls
``degree`` scattered 128-256 B dataset rows, and the roofline measures
that access pattern at ~61 GB/s against ~640 GB/s streamed (BENCH_r05).
GGNN (Groh et al., arXiv:1912.01059) removes the same tax on GPU by
co-locating neighbor data with graph edges; this kernel is the TPU form:

* ``cagra.prepare_traversal`` packs, for every node, its ``degree``
  neighbors' *quantized* vectors into one contiguous ``(n, deg_p,
  dim_p)`` HBM array (int8 per-row-scaled by default, bf16 optional), so
  expanding a parent reads ONE contiguous tile (deg64×dim128 int8 =
  8 KB) instead of 64 random lines.
* Scalar-prefetched parent ids drive double-buffer-friendly async DMAs:
  the store stays in HBM (``pl.ANY``), and each grid step issues ``P``
  per-parent tile copies (plus their per-edge scale/norm rows) that are
  all in flight together before the step computes — the ivf_scan manual
  -DMA pattern, with enough concurrent 8 KB transfers to hide latency.
* Each grid step carries ``P_q`` queries and their ``P = P_q·width``
  parents: a one-hot matmul routes every parent its own query row, the
  tile is scored as a broadcast multiply + lane reduce (~2 flops per
  streamed byte — the VPU is nowhere near binding next to the DMA
  rate), the bitset-filter penalty and the pad-edge mask are applied
  in-kernel, and a per-parent top-``k'`` (value, edge position) is
  emitted — shrinking the host-side merge width from ``width·degree``
  to ``width·k'``.

The returned values are traversal scores in min-space (squared L2 or
-IP) at storage precision; CAGRA's exact f32 re-score of the final top-k
keeps returned distances exact regardless.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import round_up_to

__all__ = ["graph_expand", "edge_tile_widen", "score_dim", "lane_rows"]

_INT_BIG = 2**30


def lane_rows(x: jax.Array) -> jax.Array:
    """Pad the minor (edge) axis of a per-node row array — aux
    ``(n, 2, deg_p)``, graph/penalty ``(n, 1, deg_p)`` — to the 128-lane
    tile. Mosaic refuses a per-node DMA slice whose minor extent is not
    a lane multiple (deg64 rows were refused on v5e), so the kernels
    stream lane-padded rows and read their first ``deg_p`` lanes.
    A no-op on rows that are already aligned (``prepare_traversal``
    stores them so; only ad-hoc callers pay the copy)."""
    pad = round_up_to(x.shape[-1], 128) - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _pick_pq(width: int) -> int:
    """Queries per grid step: keep P = P_q·width parents per step near 16
    without exceeding it, and never below one query. P trades grid-step
    count against per-step DMA fan-in; per-parent DMA count is
    P-invariant, so the step count only has to amortize the grid
    bookkeeping while keeping ~2·P copies in flight to hide latency."""
    return max(1, min(8, 16 // max(width, 1)))


def edge_tile_widen(V, q_rows, mode: str, cb_ref=None, cbscl_ref=None):
    """Edge tile (P, deg_p, W) in storage form → per-edge f32 query
    cross-products ``(P, deg_p)``. The ONE scoring expression both the
    per-hop kernel here and the fused megakernel (ops/cagra_fused.py)
    call, so the engines stay bit-identical by construction across every
    storage rung:

    * ``dense`` — int8/bf16 rows widened through f32 in-register (Mosaic
      has no byte→bf16 cast — the ivf_scan idiom); f32 multiplies keep
      parity with the gather path's f32-highest einsum.
    * ``int4`` — nibble-packed rows (ops/quant.py split-half layout):
      lane-axis shift+mask into (low, high) planes and a split
      broadcast-mul/lane-reduce against the query's column halves.
    * ``pq`` — PQ codes decoded in-VMEM by a one-hot GEMM against the
      subspace-major decode table (``ops.quant.pq_decode_table``); the
      int8 table mode (the fp8-LUT role) accumulates exactly in int32
      and rescales per output column. The one-hot builds from plain
      per-subspace equality compares (NOT ``pltpu.repeat``, whose
      interpret semantics diverge from the tiling its other user
      assumes), and only major axes are ever reshaped — the
      (P·deg_p, pqb) flatten never touches the minor dim.
    """
    P, deg_p = V.shape[0], V.shape[1]
    if mode == "int4":
        from .quant import int4_nibbles

        half = V.shape[2]
        low, high = int4_nibbles(V.astype(jnp.int32))
        return jnp.sum(q_rows[:, None, :half] * low
                       + q_rows[:, None, half:] * high, axis=2)
    if mode == "pq":
        dim_p = cb_ref.shape[1]
        pq_dim = V.shape[2]
        book = cb_ref.shape[0] // pq_dim
        codes2 = V.reshape(P * deg_p, pq_dim).astype(jnp.int32)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (P * deg_p, book), 1)
        oh = jnp.concatenate(
            [codes2[:, s:s + 1] == iota_b for s in range(pq_dim)],
            axis=1).astype(cb_ref.dtype)                 # (P·deg_p, pqb)
        if cb_ref.dtype == jnp.int8:
            dec = jax.lax.dot_general(
                oh, cb_ref[:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
            dec = dec * cbscl_ref[:]                     # (1, dim_p)
        else:
            dec = jax.lax.dot_general(
                oh, cb_ref[:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        Vw = dec.reshape(P, deg_p, dim_p)
        return jnp.sum(q_rows[:, None, :] * Vw, axis=2)
    Vw = (V.astype(jnp.int32).astype(jnp.float32)
          if V.dtype in (jnp.int8, jnp.uint8) else V.astype(jnp.float32))
    return jnp.sum(q_rows[:, None, :] * Vw, axis=2)


def _kernel(pids_ref, q_ref, vecs_hbm, aux_hbm, *rest, P: int, P_q: int,
            width: int, deg_p: int, degree: int, k_out: int, kp: int,
            metric: str, with_pen: bool, mode: str):
    if mode == "pq":
        cb_ref, cbscl_ref, *rest = rest
    else:
        cb_ref = cbscl_ref = None
    if with_pen:
        pen_hbm, ov_ref, oi_ref, vtile, atile, ptile, sem = rest
    else:
        pen_hbm = ptile = None
        ov_ref, oi_ref, vtile, atile, sem = rest
    g = pl.program_id(0)

    # start every parent's copies before waiting on any: P tile DMAs
    # (plus the small aux/pen rows) in flight together hide the HBM
    # latency the way the grid pipeline does for fused_knn's tiles
    copies = []
    for j in range(P):
        pid = pids_ref[g * P + j]
        c = pltpu.make_async_copy(vecs_hbm.at[pid], vtile.at[j],
                                  sem.at[0, j])
        c.start()
        copies.append(c)
        c = pltpu.make_async_copy(aux_hbm.at[pid], atile.at[j],
                                  sem.at[1, j])
        c.start()
        copies.append(c)
        if with_pen:
            c = pltpu.make_async_copy(pen_hbm.at[pid], ptile.at[j],
                                      sem.at[2, j])
            c.start()
            copies.append(c)

    q = q_ref[:]                                     # (P_q, dim_p) f32
    for c in copies:
        c.wait()
    V = vtile[:]                                     # (P, deg_p, dim_p)
    # aux/pen rows are lane-padded (see lane_rows): read the deg_p edges
    scales = atile[:, 0, :deg_p]                     # (P, deg_p)
    vnorm = atile[:, 1, :deg_p]                      # ||dequant v||²

    # route each parent its own query row with a one-hot matmul — parent
    # j of the step belongs to query j // width — then score per parent
    # as an elementwise product + lane reduce. (A (P_q, P·deg_p) cross
    # product would need a minor-dim reshape at deg_p<128 granularity to
    # reach the per-parent (P, deg_p) extraction layout — a relayout
    # Mosaic handles far less reliably than these broadcast/reduce
    # forms; the VPU math is ~2 flops per streamed byte, nowhere near
    # binding next to the per-parent DMA issue rate.)
    prow = jax.lax.broadcasted_iota(jnp.int32, (P, P_q), 0) // width
    qcol = jax.lax.broadcasted_iota(jnp.int32, (P, P_q), 1)
    route = (prow == qcol).astype(jnp.float32)       # (P, P_q) one-hot
    qpar = jax.lax.dot_general(route, q, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    # storage-rung widen/decode + broadcast-mul/lane-reduce scoring —
    # shared with the fused megakernel (see edge_tile_widen)
    cross = edge_tile_widen(V, qpar, mode, cb_ref, cbscl_ref)  # (P, deg_p)
    cross = cross * scales                           # q·(s·v) = s·(q·v)
    if metric == "l2":
        qn_p = jnp.sum(qpar * qpar, axis=1, keepdims=True)   # (P, 1)
        dist = jnp.maximum(qn_p + vnorm - 2.0 * cross, 0.0)
    else:                                            # "ip": min-space -dot
        dist = -cross
    if with_pen:
        dist = dist + ptile[:, 0, :deg_p]
    col = jax.lax.broadcasted_iota(jnp.int32, (P, deg_p), 1)
    dist = jnp.where(col < degree, dist, jnp.inf)    # pad edges out

    lane = jax.lax.broadcasted_iota(jnp.int32, (P, kp), 1)

    def extract(t, state):
        c, nv, ni = state
        best = jnp.min(c, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(c <= best, col, _INT_BIG), axis=1,
                      keepdims=True)
        at = col == pos
        bid = jnp.where(jnp.isfinite(best), pos, -1)
        nv = jnp.where(lane == t, best, nv)
        ni = jnp.where(lane == t, bid, ni)
        return jnp.where(at, jnp.inf, c), nv, ni

    state = (dist, jnp.full((P, kp), jnp.inf, jnp.float32),
             jnp.full((P, kp), -1, jnp.int32))
    if k_out <= 16:
        for t in range(k_out):
            state = extract(t, state)
    else:
        state = jax.lax.fori_loop(0, k_out, extract, state)
    ov_ref[:] = state[1]
    oi_ref[:] = state[2]


@functools.partial(
    jax.jit,
    static_argnames=("k_out", "metric", "width", "degree", "P_q",
                     "interpret", "with_pen", "mode"))
def _expand_padded(pids, q, vecs, aux, pen, cbm, cbscl, k_out: int,
                   metric: str, width: int, degree: int, P_q: int,
                   interpret: bool, with_pen: bool, mode: str):
    m_pad, dim_p = q.shape
    n, deg_p, store_w = vecs.shape
    lane_w = aux.shape[2]
    P = P_q * width
    kp = round_up_to(k_out, 128)
    grid = (m_pad // P_q,)

    kern = functools.partial(_kernel, P=P, P_q=P_q, width=width,
                             deg_p=deg_p, degree=degree, k_out=k_out,
                             kp=kp, metric=metric, with_pen=with_pen,
                             mode=mode)
    in_specs = [
        pl.BlockSpec((P_q, dim_p), lambda g, p: (g, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),       # edge store stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),       # aux (scales, norms)
    ]
    args = [q, vecs, aux]
    if mode == "pq":
        # the decode matrix (and its int8 per-row rescale) live whole in
        # VMEM — a few hundred KB at pq8·book256·d128
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        args.append(cbm)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        args.append(cbscl)
    if with_pen:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(pen)
    scratch = [
        pltpu.VMEM((P, deg_p, store_w), vecs.dtype),
        pltpu.VMEM((P, 2, lane_w), jnp.float32),
    ]
    if with_pen:
        scratch.append(pltpu.VMEM((P, 1, lane_w), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((3, P)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((P, kp), lambda g, p: (g, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, kp), lambda g, p: (g, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=scratch,
    )
    vals, epos = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m_pad * width, kp), jnp.float32),
            jax.ShapeDtypeStruct((m_pad * width, kp), jnp.int32),
        ],
        interpret=interpret,
    )(pids, *args)
    return vals, epos


def score_dim(vecs: jax.Array, mode: str, cbm=None) -> int:
    """Query width the kernel scores at for a storage mode: the store
    minor dim ("dense"), twice the packed byte width ("int4" — the
    (low, high) split), or the decode matrix's row space ("pq")."""
    if mode == "int4":
        return 2 * vecs.shape[2]
    if mode == "pq":
        return cbm.shape[1]       # decode-table columns = embedded dims
    return vecs.shape[2]


def graph_expand(
    parents: jax.Array,          # (m, width) int32 parent node ids
    queries: jax.Array,          # (m, dim) f32
    vecs: jax.Array,             # (n, deg_p, W) int8 | bf16 | u8 edge store
    aux: jax.Array,              # (n, 2, ≥deg_p) f32: [scales, dequant norms]
    k_out: int,
    metric: str = "l2",
    degree: Optional[int] = None,
    pen: Optional[jax.Array] = None,   # (n, ≥deg_p) f32: +inf excludes edge
    interpret: Optional[bool] = None,
    mode: str = "dense",
    cbm: Optional[jax.Array] = None,     # pq: (pq_dim*book, dim_p)
    #                                      subspace-major decode table
    cb_scale: Optional[jax.Array] = None,  # pq int8 CB: (1, dim_p) rescale
) -> Tuple[jax.Array, jax.Array]:
    """Score every parent's neighbor tile, return per-parent top-``k_out``.

    Returns ``(vals (m, width, k_out) f32, epos (m, width, k_out) int32)``
    best-first in min-space ("l2": squared L2 at storage precision;
    "ip": -dot). ``epos`` are EDGE positions into the parent's graph row
    (callers map them to global ids via ``graph[parent][epos]``); empty
    slots are ``(+inf, -1)``. ``degree``: real edge count (≤ ``deg_p``;
    pad edges are masked in-kernel). ``pen``: optional per-edge additive
    penalty in the same edge-major layout as the store (bitset filters).
    ``mode``: storage rung of ``vecs`` — "dense" (int8/bf16 rows),
    "int4" (nibble-packed, W = half the scored dim), or "pq" (W = codes
    per row; ``cbm`` is the ``(pq_dim*book, dim_p)`` SUBSPACE-MAJOR
    decode table from ``ops.quant.pq_decode_table`` — NOT
    ``ivf_pq_scan.make_cb_matrix``'s transposed layout — with
    ``cb_scale`` its int8-mode per-column rescale).
    """
    m, width = parents.shape
    n, deg_p, _ = vecs.shape
    dim_p = score_dim(vecs, mode, cbm)
    degree = deg_p if degree is None else degree
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    P_q = _pick_pq(width)
    m_pad = round_up_to(m, P_q)

    q = jnp.asarray(queries, jnp.float32)
    q = jnp.pad(q, ((0, m_pad - m), (0, dim_p - q.shape[1])))
    pids = jnp.clip(jnp.asarray(parents, jnp.int32), 0, n - 1)
    pids = jnp.pad(pids, ((0, m_pad - m), (0, 0))).reshape(-1)
    # None rides through jit as an empty pytree; the kernel only takes a
    # pen operand when with_pen
    pen3 = (lane_rows(pen.reshape(n, 1, pen.shape[-1]))
            if pen is not None else None)

    vals, epos = _expand_padded(pids, q, vecs, lane_rows(aux), pen3, cbm,
                                cb_scale,
                                k_out, metric, width, degree, P_q,
                                interpret, pen is not None, mode)
    vals = vals.reshape(m_pad, width, -1)[:m, :, :k_out]
    epos = epos.reshape(m_pad, width, -1)[:m, :, :k_out]
    return vals, epos
