"""One-dispatch CAGRA traversal: the multi-hop frontier megakernel.

``cagra._search_jit``'s hop loop is a ``lax.while_loop`` whose body
launches a fresh kernel per hop (the Pallas frontier expansion of
``ops/graph_expand.py``) and round-trips the itopk buffer through HBM
between launches. At serving batch sizes the per-launch fixed cost
bounds p99, not the kernel math. The reference CAGRA (Ootomo et al.,
2023; RAFT's persistent single-launch search mode) wins precisely by
keeping the whole traversal resident on-device in one launch.

This module is that launch, TPU form: ONE ``pallas_call`` whose grid is
``(query_blocks, max_iter)`` — the hop dimension is a *grid axis*, not a
host loop. The frontier (itopk distances/ids/explored flags) lives in
VMEM scratch and persists across the sequential hop steps; each step

* picks the top ``search_width`` unexplored parents with the same
  masked-min extraction ``select_k`` ties imply (lowest column first),
* DMAs each parent's contiguous edge tile + aux row + graph row (and
  the bitset-penalty row when filtering) from the HBM edge store — the
  ``graph_expand`` scalar-addressed streamed-tile machinery, with all
  per-parent copies in flight together,
* scores tiles with ``graph_expand``'s exact arithmetic (bit-identical
  values), extracts each parent's top-``k'`` in (value, edge-position)
  order, dedups against the buffer and earlier candidates,
* and folds candidates into the itopk buffer with the in-VMEM
  (value, position)-lexicographic k-pass fold from ``ops/ring_topk.py``
  (``_vmem_fold``), explored flags riding as a fold payload.

Every step mirrors the ``engine="edge"`` hop's math and tie order, so
the traversal is BIT-IDENTICAL to the edge engine (the total order
(distance, concat position) makes the sequential per-parent fold equal
to the one-shot ``select_k`` over the full concatenation — the ring
merge's associativity argument). tests/test_cagra_fused.py pins it in
interpret mode; on hardware the ``cagra.fused_search`` breaker demotes
to the edge/gather path on any kernel failure.

Parent ids are data-dependent (read from the VMEM frontier), so the
per-parent DMA addresses come from in-kernel scalar extraction rather
than scalar prefetch — the one structural difference from
``graph_expand``. Like the ring kernel, this kernel has only been
shape-traced and interpret-tested off-TPU; first hardware session:
``pytest tests/test_cagra_fused.py`` on the pod before trusting the
race.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import round_up_to
from .graph_expand import _pick_pq, lane_rows

__all__ = ["fused_traverse", "fused_capable", "one_dispatch_stats",
           "FUSED_SITE"]

# the breaker site every fused dispatch runs under (ops/guarded.py):
# a megakernel failure demotes to the edge engine (itself guarded onto
# the XLA gather path) — one log line, never the request
FUSED_SITE = "cagra.fused_search"

_INT_BIG = 2**30
# conservative VMEM ceiling for the resident working set (v5e has
# ~16 MB/core; leave headroom for the fold temporaries Mosaic keeps live)
_VMEM_CAP_BYTES = 8 << 20


def _kernel(q_ref, bd0_ref, bi0_ref, vecs_hbm, aux_hbm, gph_hbm, *rest,
            P_q: int, width: int, deg_p: int, degree: int, itopk: int,
            itopk_p: int, kprime: int, kp: int, n_hops: int, n: int,
            metric: str, with_pen: bool, mode: str):
    from .ring_topk import _vmem_fold

    if with_pen:
        pen_hbm, obd_ref, obi_ref, bufd, bufi, bufe, vtile, atile, \
            gtile, ptile, sem = rest
    else:
        pen_hbm = ptile = None
        obd_ref, obi_ref, bufd, bufi, bufe, vtile, atile, gtile, sem = rest
    P = P_q * width
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        bufd[:] = bd0_ref[:]
        bufi[:] = bi0_ref[:]
        bufe[:] = jnp.zeros((P_q, itopk_p), jnp.int32)

    lane_it = jax.lax.broadcasted_iota(jnp.int32, (P_q, itopk_p), 1)
    bd = bufd[:]
    bi = bufi[:]
    be = bufe[:]

    # ---- pick the top `width` unexplored parents (pickup_next_parents):
    # sequential masked-min extraction == select_k's lowest-column tie
    # order, marking each pick explored as the XLA body does
    vald = jnp.where(be == 1, jnp.inf, bd)
    pids, poks = [], []
    for _w in range(width):
        best = jnp.min(vald, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(vald <= best, lane_it, _INT_BIG), axis=1,
                      keepdims=True)
        at = lane_it == pos
        pids.append(jnp.min(jnp.where(at, bi, _INT_BIG), axis=1,
                            keepdims=True))
        poks.append(jnp.isfinite(best))
        be = jnp.where(at, 1, be)
        vald = jnp.where(at, jnp.inf, vald)
    bufe[:] = be

    # ---- per-parent streamed DMAs, all in flight together (the
    # graph_expand pattern; addresses are in-kernel scalars here).
    # Tile row j = w*P_q + q is query q's w-th parent — width-major, so
    # each w-block of P_q tiles aligns 1:1 with the query rows and the
    # scoring below needs no routing matmul.
    copies = []
    for j in range(P):
        w, qr = j // P_q, j % P_q
        # select in vector form: Mosaic extracts only 32-bit scalars
        pid = jnp.where(poks[w], pids[w], 0)[qr, 0]
        pid = jnp.clip(pid, 0, n - 1)
        for src, dst, s in ((vecs_hbm, vtile, 0), (aux_hbm, atile, 1),
                            (gph_hbm, gtile, 2)):
            c = pltpu.make_async_copy(src.at[pid], dst.at[j], sem.at[s, j])
            c.start()
            copies.append(c)
        if with_pen:
            c = pltpu.make_async_copy(pen_hbm.at[pid], ptile.at[j],
                                      sem.at[3, j])
            c.start()
            copies.append(c)

    q = q_ref[:]                                     # (P_q, dim_p) f32
    qn = jnp.sum(q * q, axis=1, keepdims=True)       # (P_q, 1)
    for c in copies:
        c.wait()

    col = jax.lax.broadcasted_iota(jnp.int32, (P_q, deg_p), 1)
    rank = jax.lax.broadcasted_iota(jnp.int32, (P_q, kp), 1)

    # ---- score + per-parent top-k' per width slot (graph_expand's
    # arithmetic and extraction verbatim, so values/ties are
    # bit-identical to the edge engine's kernel)
    cvals, cids, coks = [], [], []
    for w in range(width):
        V = vtile[w * P_q:(w + 1) * P_q]             # (P_q, deg_p, W)
        # aux/graph/pen rows are lane-padded (graph_expand.lane_rows)
        scales = atile[w * P_q:(w + 1) * P_q, 0, :deg_p]   # (P_q, deg_p)
        vnorm = atile[w * P_q:(w + 1) * P_q, 1, :deg_p]
        # storage-rung widen + scoring SHARED with graph_expand (the
        # bit-parity contract: both engines evaluate the identical
        # expression — int4's split nibble reduce included)
        from .graph_expand import edge_tile_widen

        cross = edge_tile_widen(V, q, mode)           # (P_q, deg_p)
        cross = cross * scales
        if metric == "l2":
            dist = jnp.maximum(qn + vnorm - 2.0 * cross, 0.0)
        else:                                         # "ip": min-space -dot
            dist = -cross
        if with_pen:
            dist = dist + ptile[w * P_q:(w + 1) * P_q, 0, :deg_p]
        dist = jnp.where(col < degree, dist, jnp.inf)
        gids = gtile[w * P_q:(w + 1) * P_q, 0, :deg_p]

        def extract(t, state):
            c, nv, ni = state
            best = jnp.min(c, axis=1, keepdims=True)
            pos = jnp.min(jnp.where(c <= best, col, _INT_BIG), axis=1,
                          keepdims=True)
            at = col == pos
            gid = jnp.min(jnp.where(at, gids, _INT_BIG), axis=1,
                          keepdims=True)
            gid = jnp.where(jnp.isfinite(best), gid, -1)
            nv = jnp.where(rank == t, best, nv)
            ni = jnp.where(rank == t, gid, ni)
            return jnp.where(at, jnp.inf, c), nv, ni

        state = (dist, jnp.full((P_q, kp), jnp.inf, jnp.float32),
                 jnp.full((P_q, kp), -1, jnp.int32))
        if kprime <= 16:
            for t in range(kprime):
                state = extract(t, state)
        else:
            state = jax.lax.fori_loop(0, kprime, extract, state)
        cvals.append(state[1])
        cids.append(state[2])
        # an empty slot (inf value) mirrors pepos<0; parent gating is
        # applied after dedup exactly as the host-side edge path does
        coks.append(poks[w] & jnp.isfinite(state[1]))

    # ---- dedup (the _dup_mask semantics): a candidate equal to any
    # buffer entry or to an EARLIER candidate in (parent, rank) concat
    # order is masked to +inf — ids kept as-is; masked entries can never
    # be selected (every buffer entry outranks them by position)
    t_a = jax.lax.broadcasted_iota(jnp.int32, (P_q, kp, kp), 1)
    t_b = jax.lax.broadcasted_iota(jnp.int32, (P_q, kp, kp), 2)
    for w in range(width):
        dup = jnp.any(cids[w][:, :, None] == bi[:, None, :], axis=2)
        for wp in range(w):
            dup = dup | jnp.any(cids[w][:, :, None] == cids[wp][:, None, :],
                                axis=2)
        dup = dup | jnp.any(
            (cids[w][:, :, None] == cids[w][:, None, :]) & (t_b < t_a),
            axis=2)
        cvals[w] = jnp.where(coks[w] & ~dup, cvals[w], jnp.inf)

    # ---- merge: sequential per-parent folds with ORIGINAL concat
    # positions as the tie key == one select_k over the full (buffer ++
    # candidates) concatenation (total-order top-k is associative); the
    # explored plane rides as a fold payload
    run_d = bd
    run_p = jnp.where(lane_it < itopk, lane_it, _INT_BIG)
    run_g = bi
    run_e = bufe[:]
    zeros_e = jnp.zeros((P_q, kp), jnp.int32)
    for w in range(width):
        blk_p = jnp.where(rank < kprime, itopk + w * kprime + rank,
                          _INT_BIG)
        run_d, run_p, run_g, run_e = _vmem_fold(
            jnp.concatenate([run_d, cvals[w]], axis=1),
            jnp.concatenate([run_p, blk_p], axis=1),
            jnp.concatenate([run_g, cids[w]], axis=1),
            itopk, itopk_p,
            extra=(jnp.concatenate([run_e, zeros_e], axis=1),))
    bufd[:] = run_d
    bufi[:] = run_g
    bufe[:] = run_e

    @pl.when(h == n_hops - 1)
    def _out():
        obd_ref[:] = bufd[:]
        obi_ref[:] = bufi[:]


@functools.partial(
    jax.jit,
    static_argnames=("itopk", "width", "max_iter", "kprime", "degree",
                     "metric", "P_q", "interpret", "with_pen", "mode"))
def _fused_padded(q, bd0, bi0, vecs, aux, gph, pen, itopk: int, width: int,
                  max_iter: int, kprime: int, degree: int, metric: str,
                  P_q: int, interpret: bool, with_pen: bool,
                  mode: str = "dense"):
    m_pad, dim_p = q.shape
    n, deg_p, store_w = vecs.shape
    lane_w = aux.shape[2]
    P = P_q * width
    itopk_p = round_up_to(itopk, 128)
    kp = round_up_to(kprime, 128)
    grid = (m_pad // P_q, max_iter)

    kern = functools.partial(_kernel, P_q=P_q, width=width, deg_p=deg_p,
                             degree=degree, itopk=itopk, itopk_p=itopk_p,
                             kprime=kprime, kp=kp, n_hops=max_iter, n=n,
                             metric=metric, with_pen=with_pen, mode=mode)
    blk = lambda shape: pl.BlockSpec(shape, lambda i, h: (i, 0),
                                     memory_space=pltpu.VMEM)
    in_specs = [
        blk((P_q, dim_p)),                       # queries
        blk((P_q, itopk_p)),                     # seed-initialized buf_d
        blk((P_q, itopk_p)),                     # seed-initialized buf_i
        pl.BlockSpec(memory_space=pl.ANY),       # edge store stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),       # aux (scales, norms)
        pl.BlockSpec(memory_space=pl.ANY),       # graph rows (n, 1, deg_p)
    ]
    args = [q, bd0, bi0, vecs, aux, gph]
    if with_pen:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(pen)
    scratch = [
        pltpu.VMEM((P_q, itopk_p), jnp.float32),   # frontier: distances
        pltpu.VMEM((P_q, itopk_p), jnp.int32),     # frontier: ids
        pltpu.VMEM((P_q, itopk_p), jnp.int32),     # frontier: explored
        pltpu.VMEM((P, deg_p, store_w), vecs.dtype),
        pltpu.VMEM((P, 2, lane_w), jnp.float32),
        pltpu.VMEM((P, 1, lane_w), jnp.int32),
    ]
    if with_pen:
        scratch.append(pltpu.VMEM((P, 1, lane_w), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((4, P)))

    out_d, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[blk((P_q, itopk_p)), blk((P_q, itopk_p))],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, itopk_p), jnp.float32),
            jax.ShapeDtypeStruct((m_pad, itopk_p), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    return out_d, out_i


def fused_traverse(
    queries: jax.Array,          # (m, dim) f32
    buf_d: jax.Array,            # (m, itopk) f32 seed-initialized buffer
    buf_i: jax.Array,            # (m, itopk) int32 seed-initialized ids
    vecs: jax.Array,             # (n, deg_p, dim_p) int8 | bf16 edge store
    aux: jax.Array,              # (n, 2, ≥deg_p) f32 [scales, dequant norms]
    gph: jax.Array,              # (n, ≥deg_p) int32 padded graph rows
    pen: Optional[jax.Array] = None,   # (n, ≥deg_p) f32 edge penalties
    *,
    itopk: int,
    width: int,
    max_iter: int,
    kprime: int,
    degree: int,
    metric: str = "l2",
    interpret: Optional[bool] = None,
    mode: str = "dense",
) -> Tuple[jax.Array, jax.Array]:
    """Run the whole multi-hop traversal in one kernel launch.

    Takes the seed-initialized itopk buffer (``cagra._search_jit``'s
    shared seeding preamble) and returns the converged ``(buf_d, buf_i)``
    — bit-identical to ``max_iter`` iterations of the edge-engine hop
    body (the fixed grid runs every hop; a converged frontier yields no
    finite parents, so extra hops are exact no-ops on the buffer, which
    is also why early exit costs nothing but the idle steps). ``mode``:
    the edge store's rung — "dense" (int8/bf16 rows) or "int4"
    (nibble-packed; the shared ``graph_expand.edge_tile_widen`` keeps
    both engines' arithmetic identical). PQ stores serve the edge
    engine — the megakernel carries no in-kernel LUT decode."""
    from .graph_expand import score_dim

    m = queries.shape[0]
    n, deg_p, _ = vecs.shape
    dim_p = score_dim(vecs, mode)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    P_q = _pick_pq(width)
    m_pad = round_up_to(m, P_q)
    itopk_p = round_up_to(itopk, 128)
    q = jnp.asarray(queries, jnp.float32)
    q = jnp.pad(q, ((0, m_pad - m), (0, dim_p - q.shape[1])))
    bd = jnp.pad(buf_d.astype(jnp.float32),
                 ((0, m_pad - m), (0, itopk_p - itopk)),
                 constant_values=jnp.inf)
    bi = jnp.pad(buf_i.astype(jnp.int32),
                 ((0, m_pad - m), (0, itopk_p - itopk)),
                 constant_values=-1)
    gph3 = lane_rows(gph.reshape(n, 1, gph.shape[-1]))
    pen3 = (lane_rows(pen.reshape(n, 1, pen.shape[-1]))
            if pen is not None else None)
    od, oi = _fused_padded(q, bd, bi, vecs, lane_rows(aux), gph3, pen3,
                           itopk, width,
                           int(max_iter), kprime, degree, metric, P_q,
                           bool(interpret), pen is not None, mode)
    return od[:m, :itopk], oi[:m, :itopk]


def fused_capable(itopk: int, width: int, deg_p: int, dim_p: int,
                  store_dtype, max_iter: int) -> bool:
    """Whether the megakernel's resident working set fits the VMEM
    budget: edge tiles for P parents + the frontier planes + the fold's
    live concat temporaries (docs/perf.md has the itopk×width×dim
    math). Shapes past the cap should serve the edge engine instead —
    tune_search skips the fused lane for them."""
    if max_iter < 1:
        return False
    P_q = _pick_pq(width)
    P = P_q * width
    itopk_p = round_up_to(itopk, 128)
    kp = round_up_to(min(deg_p, max(itopk, 1)), 128)
    esize = jnp.dtype(store_dtype).itemsize
    tiles = (P * deg_p * dim_p * esize
             + P * 3 * round_up_to(deg_p, 128) * 4)   # lane-padded rows
    frontier = 3 * P_q * itopk_p * 4
    # fold temporaries: ~4 planes of the (itopk_p + kp)-wide concat plus
    # the (P_q, kp, itopk_p) dedup compare, live at once
    fold = 4 * P_q * (itopk_p + kp) * 4 + P_q * kp * itopk_p
    return tiles + frontier + fold <= _VMEM_CAP_BYTES


def one_dispatch_stats(fn, *args) -> dict:
    """Trace ``fn(*args)`` and report its device-loop / kernel-launch
    structure: ``while_loops`` counts device-side loops OUTSIDE Pallas
    kernel bodies (each iteration of one is a separate kernel-launch
    round trip on device), ``pallas_calls`` counts kernel launch sites.
    ``one_dispatch`` is True when no such loop remains — the whole
    search then lowers to one straight-line XLA executable, dispatched
    once per call (the bench serving lane and the one-dispatch test
    read this).

    Since ISSUE 14 this is the thin public alias of the generalized
    serving audit (:func:`raft_tpu.analysis.hotpath_audit.jaxpr_stats`),
    which additionally reports host-callback primitives — one walker,
    one definition of "a dispatch"."""
    from ..analysis.hotpath_audit import jaxpr_stats

    return jaxpr_stats(fn, *args)
