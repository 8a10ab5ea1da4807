"""Fused pairwise-distance + running top-k Pallas kernel.

The TPU analog of RAFT's fused brute-force path: the tiled distance GEMM
(detail/knn_brute_force.cuh:61) with the per-tile select and cross-tile
merge (matrix/detail/select_warpsort.cuh:35) collapsed into one kernel.
The distance block for each (query-tile, dataset-tile) pair is computed on
the MXU; a running k-best (value, index) buffer lives in VMEM scratch and
is updated in-place as the kernel walks the dataset tiles, so no
(m, n) distance matrix — and no full per-tile sort — ever exists.

Selection is TWO-LEVEL (the extraction economics of select_radix.cuh's
candidate-pruning pass, fused against the GEMM tile while it is still in
VMEM):

* level 1 — a VPU block-min partial reduce collapses the (tm, tn)
  distance tile to ``nc`` group minima per query row (``nc`` ≈ 2k,
  lane-aligned): one bandwidth-bound pass, O(tm·tn), instead of the
  former k-pass min-extraction's O(k·tm·tn);
* level 2 — only group minima that beat the running k-th value (the
  threshold filter) are merged into the k-best scratch, a k-pass extract
  over a (kp + nc)-wide row — O(k·(kp+nc)), independent of tile width.

A group can hold more than one of the tile's true top-k, so the reduce +
merge repeats for a bounded number of rounds (each round retires every
group's current minimum); a final exact fallback — the full-width k-pass
over whatever still beats the threshold — makes the kernel exact on any
input, including all-tied rows. Every round and the fallback are gated on
``any(remaining <= running k-th)``: in steady state (corpus scan past the
first few tiles) the gates collapse and a tile costs its GEMM plus one
block-min pass, nothing else.

Extraction breaks ties by (value, smallest global column) — exactly
``lax.top_k``'s order — so the fused engine is bit-identical in both
index set and order to the GEMM+top_k reference engine.

The corpus stays RESIDENT in HBM in its storage dtype — f32, bf16 (half
the stream traffic) or int8/uint8 (quarter traffic; int8 carries per-row
dequant scales folded into the dot) — and tiles stream HBM→VMEM through
the Pallas grid pipeline, which double-buffers the async tile copies
against the MXU work. At 1M×128 bf16 that is ~256 MB of corpus reads per
query batch: bandwidth-bound at the measured ~650 GB/s stream rate, with
the former compute+spill select cost gone from the steady state.

Masking (bitset sample filters, padded rows, shard validity) is folded
into an additive penalty row: +inf for excluded dataset rows, 0 otherwise
— one broadcast add, no per-metric special cases (all four expanded
metrics ride the same kernel; sqrt-L2 post-processes outside).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import round_up_to

__all__ = ["fused_knn"]

_INT_BIG = 2**30  # sentinel column id, larger than any real lane index

# extra block-min/merge rounds before the exact fallback: round 1 seeds
# the buffer from the group minima, round 2 catches groups that held two
# of the tile's top-k; anything rarer is the fallback's job
_ROUNDS = 2


def _pick_tiles(dim_p: int, k: int, itemsize: int = 4) -> Tuple[int, int]:
    """(query-tile, dataset-tile) sizes under v5e's 16 MB scoped VMEM.

    Defaults target v5e-class VMEM; override with
    ``RAFT_TPU_FUSED_TILES=tm,tn`` when sweeping other generations.
    Engine-level dispatch is where measurement lives: ops.autotune times
    this whole kernel against the matmul/scan engines per shape class
    (brute_force.tune_search), so a tile config only matters on hardware
    where the fused kernel wins that race. Shrink with dim so the
    (tm, tn) distance block plus tiles stay inside VMEM, and with k since
    the merge working set grows with kp. Byte-dtype corpora (``itemsize``
    < 4) stream wider dataset tiles: the double-buffered tile pair costs
    2·tn·dim_p·itemsize, so halving the element size funds a wider tn
    (fewer grid revisits per corpus pass) at the same VMEM budget.
    """
    import os

    env = os.environ.get("RAFT_TPU_FUSED_TILES")
    if env:
        parts = env.split(",")
        if len(parts) != 2:
            raise ValueError(
                f"RAFT_TPU_FUSED_TILES must be 'tm,tn', got {env!r}")
        tm, tn = (int(v) for v in parts)
        # snap to TPU tiling multiples (sublane 8 / lane 128)
        tm = max(8, (tm // 8) * 8)
        tn = max(128, (tn // 128) * 128)
        return tm, tn
    # tm=256: the (tm, tn) distance tile and its reduce temporaries
    # dominate scoped VMEM — tm=512 needed 19.0 MB (f32, d128) and
    # 21.1 MB (d512) against v5e's 16 MB scoped limit
    tm, tn = 256, (1024 if dim_p <= 256 else 512)
    if itemsize <= 2 and dim_p <= 512:
        tn *= 2
    if k > 128:
        # kp=256 doubles the (tm, kp) merge state: tm=256 at k=129 needs
        # more than v5e's scoped VMEM (a v5e compile refused it)
        tm = 128
    return tm, tn


def _extract_smallest(c, ci, k: int, kp: int):
    """k smallest of rows of ``c`` with global ids ``ci`` → (tm, kp) val/id.

    Iterative min-extraction with the tie-break on (value, smallest id) —
    not smallest *position* — so the result order matches ``lax.top_k``
    over the globally-indexed row regardless of how candidates were
    concatenated. Exactly one id is retired per pass (ids are unique
    except the -1 sentinel, which only accompanies +inf slots).
    """
    tm = c.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tm, kp), 1)

    def extract(t, state):
        c, nv, ni = state
        best = jnp.min(c, axis=1, keepdims=True)
        at_min = c <= best
        bid = jnp.min(jnp.where(at_min, ci, _INT_BIG), axis=1, keepdims=True)
        at = at_min & (ci == bid)
        # rows with no remaining finite candidate: emit the -1 sentinel,
        # not a (real, excluded/duplicate) id
        bid = jnp.where(jnp.isfinite(best), bid, -1)
        nv = jnp.where(lane == t, best, nv)
        ni = jnp.where(lane == t, bid, ni)
        return jnp.where(at, jnp.inf, c), nv, ni

    state = (c, jnp.full((tm, kp), jnp.inf, jnp.float32),
             jnp.full((tm, kp), -1, jnp.int32))
    if k <= 16:
        for t in range(k):
            state = extract(t, state)
    else:
        state = jax.lax.fori_loop(0, k, extract, state)
    return state[1], state[2]


def _kernel(q_ref, d_ref, dn_ref, pen_ref, *rest, k: int, kp: int, tn: int,
            nc: int, metric: str, n_dtiles: int, precision: str,
            with_scales: bool, int4: bool = False):
    if with_scales:
        sc_ref, ov_ref, oi_ref, sv_ref, si_ref = rest
    else:
        sc_ref = None
        ov_ref, oi_ref, sv_ref, si_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        sv_ref[:] = jnp.full_like(sv_ref, jnp.inf)
        si_ref[:] = jnp.full_like(si_ref, -1)

    q = q_ref[:]                                   # (tm, dim_p) f32
    d = d_ref[:]                                   # (tn, dim_p) stored dtype
    tm = q.shape[0]
    if int4:
        # nibble-packed corpus (ops/quant.py split-half layout): byte j
        # holds components j (low nibble) and j+half (high). Unpacking
        # is a lane-axis shift+mask — never a minor-axis reshape — and
        # the dot splits into two half-width GEMMs against the query's
        # (low, high) column halves. HBM stream traffic: 1/8 of f32.
        from .quant import int4_nibbles

        half = d.shape[1]
        low, high = int4_nibbles(d.astype(jnp.int32))
        kw = dict(preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision(precision))
        dot = (jax.lax.dot_general(q[:, :half], low,
                                   (((1,), (1,)), ((), ())), **kw)
               + jax.lax.dot_general(q[:, half:], high,
                                     (((1,), (1,)), ((), ())), **kw))
    elif d.dtype == jnp.bfloat16:
        # bf16 corpus mode: rows stream from HBM at half the f32 traffic;
        # the product accumulates in f32 (precision knob is moot — the
        # stored operand is already bf16)
        dot = jax.lax.dot_general(q.astype(jnp.bfloat16), d,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    elif d.dtype in (jnp.int8, jnp.uint8):
        # byte corpus mode: quarter HBM traffic; the f32 convert happens
        # in VMEM after the stream, and the math matches the GEMM
        # engine's fused-convert path bit for bit
        dot = jax.lax.dot_general(
            q, d.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision(precision))
    else:
        dot = jax.lax.dot_general(
            q, d, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision(precision))  # (tm, tn)
    if sc_ref is not None:
        dot = dot * sc_ref[:]          # int8 per-row scales: q·(s·v)=s·(q·v)
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        dist = jnp.maximum(qn + dn_ref[:] - 2.0 * dot, 0.0)
    elif metric == "cos":                          # dn holds sqrt row norms
        qn = jnp.sqrt(jnp.sum(q * q, axis=1, keepdims=True))
        dist = 1.0 - dot / jnp.maximum(qn * dn_ref[:], 1e-30)
    else:                                          # "ip": min-select on -dot
        dist = -dot
    dist = dist + pen_ref[:]                       # +inf on masked/padded rows

    col = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1) + j * tn

    def merge(cv, ci):
        nv, ni = _extract_smallest(
            jnp.concatenate([sv_ref[:], cv], axis=1),
            jnp.concatenate([si_ref[:], ci], axis=1), k, kp)
        sv_ref[:] = nv
        si_ref[:] = ni

    # ``<=`` (not ``<``) everywhere a threshold gates work: an element
    # EQUAL to the running k-th but with a smaller column must still
    # displace it for exact lax.top_k tie order
    thresh = sv_ref[:, k - 1 : k]                  # (tm, 1)
    tile_min = jnp.min(dist, axis=1, keepdims=True)

    @pl.when(jnp.any(tile_min <= thresh))
    def _():
        if nc >= tn:
            # tile no wider than the candidate budget: merge it directly
            merge(dist, col)
            return

        # STRIDED groups — group g holds columns {g, g+nc, g+2nc, ...} —
        # so the reduce runs over the middle axis and the lane axis stays
        # nc (≥128) wide, the layout Mosaic reduces at full VPU rate
        bw = tn // nc                              # chunks per group
        tcol = jax.lax.broadcasted_iota(jnp.int32, (tm, bw, nc), 1)
        gcol = jax.lax.broadcasted_iota(jnp.int32, (tm, nc), 1) + j * tn

        def round_body(dmask):
            """One block-min reduce + gated merge; retires each group's
            current minimum so the next round sees fresh candidates."""
            th = sv_ref[:, k - 1 : k]
            d3 = dmask.reshape(tm, bw, nc)
            gmin = jnp.min(d3, axis=1)                         # (tm, nc)
            # chunk attaining the min; smallest chunk index on ties ==
            # smallest global column within the group
            gchunk = jnp.min(
                jnp.where(d3 <= gmin[:, None, :], tcol, _INT_BIG),
                axis=1)                                        # (tm, nc)
            keep = gmin <= th

            @pl.when(jnp.any(keep))
            def _():
                merge(jnp.where(keep, gmin, jnp.inf), gchunk * nc + gcol)

            retired = (tcol == gchunk[:, None, :]) & keep[:, None, :]
            return jnp.where(retired, jnp.inf, d3).reshape(tm, tn)

        dmask = dist
        for _r in range(min(_ROUNDS, k)):
            dmask = round_body(dmask)

        # exact fallback: rows where >_ROUNDS of the tile's top-k shared a
        # group (or heavy value ties) still have pending candidates — the
        # full-width k-pass retires them. Steady state never reaches here.
        @pl.when(jnp.any(jnp.min(dmask, axis=1, keepdims=True)
                         <= sv_ref[:, k - 1 : k]))
        def _():
            tv, ti = _extract_smallest(dmask, col, k, kp)
            merge(tv, ti)

    @pl.when(j == n_dtiles - 1)
    def _():
        ov_ref[:] = sv_ref[:]
        oi_ref[:] = si_ref[:]


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "interpret", "precision",
                                    "tiles", "int4"))
def _fused_knn_padded(q, d, dn, pen, sc, k: int, metric: str,
                      interpret: bool, precision: str,
                      tiles: Tuple[int, int], int4: bool = False):
    m_pad, dim_p = q.shape
    n_pad = d.shape[0]
    d_w = d.shape[1]               # packed byte width (= dim_p/2 for int4)
    tm, tn = tiles
    tm = min(tm, m_pad)
    tn = min(tn, n_pad)
    kp = round_up_to(k, 128)
    # candidate budget per row after the level-1 reduce: ≥2k, lane-aligned,
    # and a divisor of tn so groups tile the row exactly
    nc = min(tn, max(128, round_up_to(2 * k, 128)))
    while tn % nc:
        nc += 128
    grid = (m_pad // tm, n_pad // tn)

    kern = functools.partial(_kernel, k=k, kp=kp, tn=tn, nc=nc,
                             metric=metric, n_dtiles=grid[1],
                             precision=precision, with_scales=sc is not None,
                             int4=int4)
    flops = 2 * m_pad * n_pad * dim_p
    row_spec = pl.BlockSpec((1, tn), lambda i, j: (0, j),
                            memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((tm, dim_p), lambda i, j: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((tn, d_w), lambda i, j: (j, 0),
                     memory_space=pltpu.VMEM),
        row_spec,
        row_spec,
    ]
    args = [q, d, dn, pen]
    if sc is not None:
        in_specs.append(row_spec)
        args.append(sc)
    vals, idxs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, kp), jnp.float32),
            jax.ShapeDtypeStruct((m_pad, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tm, kp), jnp.float32),
            pltpu.VMEM((tm, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=int(q.size * 4 + d.size * d.dtype.itemsize
                               + dn.size * 4),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*args)
    return vals[:, :k], idxs[:, :k]


def fused_knn(
    queries: jax.Array,
    dataset: jax.Array,
    k: int,
    metric: str = "l2",
    data_norms: Optional[jax.Array] = None,
    penalty: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    precision: str = "highest",
    scales: Optional[jax.Array] = None,
    int4_dim: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """k nearest rows of ``dataset`` for each query, fused on-TPU.

    metric: "l2" (squared L2), "cos" (1 - cosine, using precomputed or
    derived row norms), "ip" (inner product; returns min-ordered -dot,
    caller negates). ``dataset`` may be stored f32, bf16 (half the HBM
    stream traffic), or int8/uint8 (quarter traffic; int8 requires
    ``scales``, the per-row dequant factors). ``data_norms``: optional
    (n,) squared L2 row norms of the *dequantized* rows (reused from the
    index for "l2"/"cos"; derived here when absent).
    ``penalty``: optional (n,) f32 additive row penalty (+inf to exclude).
    ``precision``: MXU precision for the distance GEMM — "highest"
    (3-pass bf16, ~f32-accurate; the exact-search default) or "default"
    (single-pass bf16 multiplies, ~3x the MXU throughput, distance error
    ~1e-3 relative — fine as an ANN candidate generator).
    ``int4_dim``: when set, ``dataset`` is a nibble-packed int4 corpus
    (``(n, half_p)`` int8, ops/quant.py split-half layout) for a logical
    row width of ``int4_dim``; unpacking happens in-kernel (lane-axis
    shift+mask) so the HBM stream is 1/8 of f32. ``scales`` required.
    Pre-aligned inputs (rows a tile multiple, dim a 128 multiple — see
    ``brute_force.prepare_fused``) pass through without the trace-time
    pad copy, keeping the corpus genuinely HBM-resident across calls.
    Returns (values (m, k), indices (m, k)) sorted best-first; excluded /
    out-of-range slots have value +inf and index -1.
    """
    from ..core.errors import expects

    q = jnp.asarray(queries, jnp.float32)
    d = jnp.asarray(dataset)
    int4 = int4_dim is not None
    if not int4 and d.dtype not in (jnp.bfloat16, jnp.int8, jnp.uint8):
        d = d.astype(jnp.float32)   # low-precision modes stay as stored
    if (int4 or d.dtype == jnp.int8) and scales is None:
        # without the per-row dequant factors the raw quantized dot mixes
        # value spaces with the dequantized norms — plausibly-shaped,
        # silently wrong neighbors; fail the contract loudly instead
        expects(False, "int8/int4 datasets require per-row dequant scales "
                       "(see ops.quant.quantize_rows)")
    m, dim = q.shape
    n = d.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    if int4:
        # the packed corpus fixes the geometry: the query widens to the
        # (low, high) double-half width the split dot contracts against
        expects(d.shape[1] * 2 >= dim,
                "int4 corpus width %d cannot hold dim %d", d.shape[1], dim)
        dim_p = 2 * d.shape[1]
        tm, tn = _pick_tiles(dim_p, k, 1)
    else:
        dim_p = round_up_to(dim, 128)
        tm, tn = _pick_tiles(dim_p, k, d.dtype.itemsize)
    m_pad = round_up_to(m, min(tm, round_up_to(m, 8)))
    n_pad = round_up_to(n, min(tn, round_up_to(n, 128)))
    if (m_pad, dim_p) != (m, dim):
        q = jnp.pad(q, ((0, m_pad - m), (0, dim_p - dim)))
    # the dataset pad keys on the DATASET's own shape (a prepare_fused
    # corpus arrives already (n_pad, dim_p) while queries are unpadded —
    # comparing against the query dim would re-pad it every call)
    d_w = d.shape[1] if int4 else dim_p
    if (n_pad, d_w) != d.shape:
        d = jnp.pad(d, ((0, n_pad - n), (0, d_w - d.shape[1])))

    if metric in ("l2", "cos"):
        if data_norms is None:
            if int4:
                from .quant import int4_nibbles

                low, high = int4_nibbles(d.astype(jnp.int32))
                dn = jnp.sum(low * low + high * high, axis=1)
            else:
                dn = jnp.sum(d.astype(jnp.float32) ** 2, axis=1)
            if scales is not None:
                dn = dn * jnp.pad(jnp.asarray(scales, jnp.float32),
                                  (0, n_pad - n)) ** 2
        else:
            dn = jnp.pad(jnp.asarray(data_norms, jnp.float32),
                         (0, n_pad - n))
        if metric == "cos":   # kernel divides by the norm, not its square
            dn = jnp.sqrt(dn)
    else:
        dn = jnp.zeros((n_pad,), jnp.float32)

    pen = jnp.zeros((n,), jnp.float32) if penalty is None else (
        jnp.asarray(penalty, jnp.float32))
    pen = jnp.pad(pen, (0, n_pad - n), constant_values=jnp.inf)

    sc = None
    if scales is not None:
        sc = jnp.pad(jnp.asarray(scales, jnp.float32),
                     (0, n_pad - n)).reshape(1, -1)

    vals, idxs = _fused_knn_padded(q, d, dn.reshape(1, -1),
                                   pen.reshape(1, -1), sc, k, metric,
                                   interpret, precision, (tm, tn),
                                   int4=int4)
    return vals[:m], idxs[:m]
