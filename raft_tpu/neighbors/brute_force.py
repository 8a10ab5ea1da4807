"""Exact brute-force kNN: analog of ``raft::neighbors::brute_force``.

Reference: raft/neighbors/brute_force-inl.cuh with the tiled engine in
detail/knn_brute_force.cuh:61 (`tiled_brute_force_knn`: row×col tiles of
pairwise distance GEMM + per-tile select_k + cross-tile merge) and the
multi-shard merge in detail/knn_merge_parts.cuh:172.

TPU design: one `lax.scan` over dataset tiles. Each step computes a
(n_queries, tile) distance block — the cross term on the MXU for expanded
metrics — takes the tile's top-k, and merges it into the running top-k
(concat + re-select, the `knn_merge_parts` trick applied streamingly).
XLA double-buffers the HBM tile reads against compute, which is exactly the
role the reference's stream-pool round-robin plays (knn_brute_force.cuh:476);
no NxM distance matrix ever exists in HBM.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import deadline, interop, tracing
from ..core.bitset import Bitset
from ..core.errors import expects
from ..core.serialize import load_arrays, save_arrays
from ..ops.guarded import guarded_call
from ..distance.distance_types import DistanceType, canonical_metric, is_min_close
from ..distance.pairwise import _ELEMENTWISE, _elementwise_tile, _haversine
from ..matrix.select_k import select_k
from ..utils import hdot, in_jax_trace, round_up_to, run_query_chunks

__all__ = ["Index", "build", "search", "knn", "knn_merge_parts", "save",
           "load", "tune_search", "make_searcher", "prepare_fused",
           "health", "quantization_error", "health_sample_rows",
           "int8_scale_report"]

# v2: store_dtype meta + uint16-framed bf16 datasets + int8 scales; v1
# files (plain f32) remain readable
_SERIAL_VERSION = 2


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """Brute-force index: the dataset plus precomputed row norms
    (brute_force_types.hpp:50 stores exactly these).

    ``dataset`` may be stored low-precision (the per-dtype dataset modes of
    detail/ivf_flat_interleaved_scan-inl.cuh:99-584 applied to brute
    force): bf16 halves, int8 quarters and int4 (nibble-packed, see
    ops/quant.py) eighths the HBM scan traffic. ``scales`` holds per-row
    dequant factors for int8/int4 (row ≈ scale * quantized_vec);
    ``norms`` are always exact f32 norms of the *stored* representation.
    ``logical_dim`` is set ONLY for int4 stores, whose packed byte width
    is not the row width.
    """

    dataset: jax.Array          # (n, d) f32 | bf16 | int8 | uint8
    norms: Optional[jax.Array]  # (n,) squared L2 norms, for expanded metrics
    metric: DistanceType
    metric_arg: float = 2.0
    scales: Optional[jax.Array] = None   # (n,) f32, int8/int4 modes only
    logical_dim: Optional[int] = None    # int4 mode: the unpacked row width

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return (self.logical_dim if self.logical_dim is not None
                else self.dataset.shape[1])

    @property
    def store_dtype(self):
        return self.dataset.dtype

    @property
    def store_name(self) -> str:
        """Storage-rung tag ("float32" | "bfloat16" | "int8" | "uint8" |
        "int4") — what autotune keys and health reports should use; the
        physical ``store_dtype`` of an int4 store is int8."""
        return ("int4" if self.logical_dim is not None
                else str(jnp.dtype(self.dataset.dtype)))

    def tree_flatten(self):
        # the fused engine's tile-aligned corpus cache (prepare_fused)
        # travels WITH the index so jitted engines can take the index as
        # an ARGUMENT and still skip the per-call pad copy (closure-baking
        # the dataset would make every compile request corpus-sized;
        # cagra's _score_* caches set the precedent)
        fp = getattr(self, "_fused_pad", None)
        pad_leaves = tuple(fp[1:]) if fp is not None else (None,) * 4
        return ((self.dataset, self.norms, self.scales) + pad_leaves,
                (self.metric, self.metric_arg, self.logical_dim,
                 fp[0] if fp is not None else None))

    @classmethod
    def tree_unflatten(cls, aux, children):
        out = cls(children[0], children[1], aux[0], aux[1], children[2],
                  aux[2])
        if len(aux) > 3 and aux[3] is not None:
            out._fused_pad = (aux[3],) + tuple(children[3:])
        return out


# the per-row storage coding lives in ops/quant.py (the ladder's shared
# home — cagra/ivf_flat/mutable import these THROUGH this module, so the
# historical names keep working); semantics are byte-identical to the
# former local definitions
from ..ops.quant import (dequantize_rows, int8_scale_report,  # noqa: E402
                         quantize_rows)


@tracing.annotate("raft_tpu::brute_force::build")
def build(dataset: jax.Array, metric="sqeuclidean", metric_arg: float = 2.0,
          dtype=jnp.float32) -> Index:
    """Build = store dataset + precompute norms (no training).

    ``dtype``: storage dtype — float32 (exact), bfloat16 (half the HBM
    scan traffic, ~1e-3 relative distance error), int8 (quarter
    traffic, per-row symmetric quantization; the ANN-candidate mode),
    uint8 (quarter traffic, exact — byte-valued corpora like SIFT/DEEP
    only; scaled float data belongs in int8) or ``"int4"`` (eighth
    traffic: nibble-packed rows, per-row scales, in-kernel unpack on
    the fused engine — expanded metrics only; pair with
    ``refine.refine`` for exact final distances).
    """
    dataset = jnp.asarray(dataset, jnp.float32)
    expects(dataset.ndim == 2, "dataset must be (n, d)")
    mt = canonical_metric(metric)
    int4 = isinstance(dtype, str) and dtype in ("int4", "i4")
    if int4:
        expects(mt in _PALLAS_METRICS,
                "int4 storage supports L2/cosine/IP metrics, got %s",
                mt.name)
    stored, scales = quantize_rows(dataset, dtype)
    norms = None
    if mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
              DistanceType.CosineExpanded):
        from ..ops.quant import dequantize_int4

        deq = (dequantize_int4(stored, scales, dataset.shape[1]) if int4
               else dequantize_rows(stored, scales))
        norms = jnp.sum(deq * deq, axis=1)
    return Index(stored, norms, mt, metric_arg, scales,
                 dataset.shape[1] if int4 else None)


def health_sample_rows(n: int, sample: int):
    """Deterministic evenly-spread row sample for the health reports
    (numpy int array; empty for an empty index — a mid-streaming-build
    index with 0 rows must report, not raise): no RNG, so two snapshots
    of the same index agree."""
    import numpy as np

    if n <= 0:
        return np.zeros((0,), np.int64)
    take = max(1, min(int(sample), int(n)))
    return np.unique(np.linspace(0, n - 1, take).astype(np.int64))


def quantization_error(original, dequantized) -> dict:
    """Measured reconstruction error of a quantized copy vs its f32
    original (sampled rows): relative Frobenius RMSE + worst absolute
    component error — the health-report form shared by every family that
    keeps both representations."""
    import numpy as np

    o = np.asarray(original, np.float32)
    dq = np.asarray(dequantized, np.float32)
    err = o - dq
    denom = max(float(np.sqrt((o * o).mean())), 1e-30)
    return {"rel_rmse": round(float(np.sqrt((err * err).mean())) / denom, 6),
            "max_abs_err": round(float(np.abs(err).max()), 6)}


def health(index: Index, sample: int = 256) -> dict:
    """Index health report (docs/observability.md "Quality"): geometry,
    storage width, and — for int8/int4 stores — sampled per-row scale
    stats (see :func:`int8_scale_report`)."""
    import numpy as np

    report = {
        "family": "brute_force", "n": int(index.size),
        "dim": int(index.dim), "metric": index.metric.name,
        "store_dtype": index.store_name,
        "fused_cache": getattr(index, "_fused_pad", None) is not None,
    }
    dt = jnp.dtype(index.store_dtype)
    if index.logical_dim is not None:
        rows = health_sample_rows(index.size, sample)
        if rows.size:
            # same scale-step summary as int8, under the rung's own key
            report["quant"] = {
                "int4": int8_scale_report(index.scales[rows])["int8"]}
    elif dt == jnp.int8 and index.scales is not None:
        rows = health_sample_rows(index.size, sample)
        if rows.size:
            report["quant"] = int8_scale_report(index.scales[rows])
    elif dt == jnp.bfloat16:
        report["quant"] = {"bfloat16": {"rel_step": 2.0 ** -8}}
    elif dt == jnp.uint8:
        report["quant"] = {"uint8": {"exact": True}}
    return report


def _tile_distances(q, q_norm, tile, tile_norm, mt, metric_arg):
    """Distance block (n_queries, tile_rows) for one dataset tile."""
    if mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
        d = jnp.maximum(q_norm[:, None] + tile_norm[None, :] - 2.0 * hdot(q, tile.T), 0.0)
        return jnp.sqrt(d) if mt is DistanceType.L2SqrtExpanded else d
    if mt is DistanceType.CosineExpanded:
        qn = jnp.sqrt(jnp.maximum(q_norm, 1e-30))
        tn = jnp.sqrt(jnp.maximum(tile_norm, 1e-30))
        return 1.0 - hdot(q, tile.T) / (qn[:, None] * tn[None, :])
    if mt is DistanceType.InnerProduct:
        return hdot(q, tile.T)
    if mt is DistanceType.Haversine:
        return _haversine(q, tile)
    if mt in (DistanceType.CorrelationExpanded, DistanceType.HellingerExpanded,
              DistanceType.RusselRaoExpanded):
        from ..distance.pairwise import _EXPANDED
        return _EXPANDED[mt](q, tile)
    expects(mt in _ELEMENTWISE, "metric %s unsupported by brute force", mt.name)
    return _elementwise_tile(q, tile, mt, metric_arg)


_PALLAS_METRICS = {
    DistanceType.L2Expanded: "l2",
    DistanceType.L2SqrtExpanded: "l2",
    DistanceType.CosineExpanded: "cos",
    DistanceType.InnerProduct: "ip",
}


def fused_capable(metric) -> bool:
    """Whether the streaming fused kernel can serve ``metric`` — the
    public predicate callers (e.g. the CAGRA graph build's engine
    choice) consult instead of reading ``_PALLAS_METRICS``."""
    from ..distance.distance_types import canonical_metric

    return canonical_metric(metric) in _PALLAS_METRICS


def _penalty_row(index: Index, filter, valid_rows):
    """(n,) additive min-space penalty: +inf on excluded rows, else 0."""
    if filter is None and valid_rows is None:
        return None
    n = index.size
    pen = jnp.zeros((n,), jnp.float32)
    if filter is not None:
        pen = jnp.where(filter.to_mask(), pen, jnp.inf)
    if valid_rows is not None:
        pen = jnp.where(jnp.arange(n) < valid_rows, pen, jnp.inf)
    return pen


def _wide_select_k(s: jax.Array, k: int):
    """Exact per-row top-k over very wide rows via chunked select_k.

    select_k's KPASS engine caps at 4096 columns (its scoped-VMEM row
    block — 8192-wide blocks compile-OOM on v5e inside larger
    programs); wider rows select per 4096-chunk first, then select
    over the surviving nc·k candidates. Exact, including top_k's lowest-index tie-break:
    per-chunk selection keeps every chunk's own full top-k, and both
    levels break ties by ascending index."""
    from ..matrix.select_k import select_k

    m, n = s.shape
    c = 4096
    if n <= c or k * 4 > c:
        # narrow rows need no chunking; huge k makes chunking both
        # pointless (nc*k ~ n survivors) and ill-formed (the per-chunk
        # select needs k <= chunk width) — lax.top_k handles any k <= n
        return select_k(s, k, select_min=True)
    n_pad = round_up_to(n, c)
    nc = n_pad // c
    sp = jnp.pad(s, ((0, 0), (0, n_pad - n)), constant_values=jnp.inf)
    cv, ci = select_k(sp.reshape(m * nc, c), k, select_min=True)
    base = (jnp.arange(nc, dtype=jnp.int32) * c)[None, :, None]
    cand_v = cv.reshape(m, nc * k)
    cand_i = (ci.reshape(m, nc, k) + base).reshape(m, nc * k)
    v, j = select_k(cand_v, k, select_min=True)
    return v, jnp.take_along_axis(cand_i, j, axis=1)


def _blockmin_topk(s: jax.Array, k: int, blk: int = 32):
    """Exact top-k of a wide distance block via 32-column block minima.

    The binding cost of a naive top_k over (m, n≈500k) is XLA's sort
    (~9 ms per 8k columns, measured); a k-pass extraction is O(k·m·n)
    VPU work — both lose at corpus width. This two-level scheme reads
    the block once for a 32-way min reduce (bandwidth-bound), selects
    the k best BLOCKS per row (n/32-wide select on the KPASS engine),
    and re-reads only the k winning blocks' raw columns (m·k·32 values).

    Exactness: every true top-k element lives in one of the k
    smallest-min blocks — if its block were outside, the k selected
    blocks each contain an element no larger, displacing it (ties
    resolve by ascending block index at level 1 and ascending column at
    level 2, matching top_k's lowest-index-first order).
    Reference role: select_radix.cuh's candidate-pruning pass."""
    from ..matrix.select_k import select_k

    m, n = s.shape
    n_pad = round_up_to(n, blk)
    if k > n_pad // blk:
        # more winners than blocks: the pruning level cannot hold them;
        # plain select (top_k handles any k <= n)
        return select_k(s, k, select_min=True)
    sp = (s if n_pad == n else
          jnp.pad(s, ((0, 0), (0, n_pad - n)), constant_values=jnp.inf))
    s3 = sp.reshape(m, n_pad // blk, blk)
    bm = s3.min(axis=2)                              # (m, B)
    _, bidx = _wide_select_k(bm, k)                  # (m, k) block ids
    # ascending block order, so level-2's lowest-POSITION tie-break is
    # the lowest global COLUMN — exactly top_k's order on ties
    bidx = jnp.sort(bidx, axis=1)
    cand = jnp.take_along_axis(s3, bidx[:, :, None], axis=1)  # (m, k, blk)
    cand_cols = (bidx[:, :, None] * blk
                 + jnp.arange(blk, dtype=jnp.int32)[None, None, :])
    v, j = _wide_select_k(cand.reshape(m, k * blk), k)
    idx = jnp.take_along_axis(cand_cols.reshape(m, k * blk), j, axis=1)
    return v, idx


def _chunked_queries(one, q, chunk: int, k: int):
    """Run the per-chunk engine ``one`` over fixed-size query chunks via
    ``lax.map`` (a single chunk dispatches directly, no map wrapper),
    padding the tail chunk and slicing the pad rows back off. Shared by
    the matmul and fused engines so their chunking semantics cannot
    drift."""
    m = q.shape[0]
    m_pad = round_up_to(m, chunk)
    qp = jnp.pad(q, ((0, m_pad - m), (0, 0)))
    if m_pad == chunk:
        vals, idxs = one(qp)
    else:
        vals, idxs = jax.lax.map(one, qp.reshape(m_pad // chunk, chunk, -1))
        vals = vals.reshape(m_pad, k)
        idxs = idxs.reshape(m_pad, k)
    return vals[:m], idxs[:m]


def _search_matmul(index: Index, q, k, filter, valid_rows, precision,
                   workspace_mb: Optional[int] = None):
    """One-shot GEMM + top_k engine, query-chunked to a workspace budget.

    On backends where XLA's fused GEMM→top_k pipeline outruns the Pallas
    kernel (dispatch-dominated regimes; measured via ops.autotune), this is
    the fastest exact path. Expanded metrics only — the distance block for
    a query chunk is one MXU GEMM plus row/col norm terms.

    ``workspace_mb`` overrides the RAFT_TPU_MATMUL_WORKSPACE_MB budget
    for this call (bigger chunks amortize per-chunk top_k fixed costs).
    """
    import os

    mt = index.metric
    n, m = index.size, q.shape[0]
    prec = jax.lax.Precision(precision)
    pen = _penalty_row(index, filter, valid_rows)

    budget = (workspace_mb if workspace_mb is not None else int(
        os.environ.get("RAFT_TPU_MATMUL_WORKSPACE_MB", "1024"))) << 20
    chunk = int(max(8, min(m, budget // max(n * 4, 1))))
    dn = index.norms
    dns = None if dn is None else (
        jnp.sqrt(jnp.maximum(dn, 1e-30)) if mt is DistanceType.CosineExpanded
        else dn)

    ds = index.dataset

    def one(qc):
        if index.logical_dim is not None:
            # int4 resident fallback: the same split-half nibble dot the
            # fused kernel runs (two half-width GEMMs — identical
            # operand grouping, so values match the kernel's), composed
            # in XLA
            from ..ops.quant import int4_nibbles

            half = ds.shape[1]
            low, high = int4_nibbles(ds.astype(jnp.int32))
            qp = jnp.pad(qc, ((0, 0), (0, 2 * half - qc.shape[1])))
            dot = (jax.lax.dot_general(
                       qp[:, :half], low, (((1,), (1,)), ((), ())),
                       preferred_element_type=jnp.float32, precision=prec)
                   + jax.lax.dot_general(
                       qp[:, half:], high, (((1,), (1,)), ((), ())),
                       preferred_element_type=jnp.float32, precision=prec))
        else:
            if ds.dtype == jnp.bfloat16:
                lhs = qc.astype(jnp.bfloat16)
                rhs = ds
            elif ds.dtype in (jnp.int8, jnp.uint8):
                # XLA fuses the convert into the GEMM: byte rows stream
                # from HBM at 1/4 the f32 traffic; int8 scales fold in
                # after
                lhs, rhs = qc, ds.astype(jnp.float32)
            else:
                lhs, rhs = qc, ds
            dot = jax.lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=prec)
        if index.scales is not None:     # q·(s·v) = s·(q·v)
            dot = dot * index.scales[None, :]
        if mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
            qn = jnp.sum(qc * qc, axis=1, keepdims=True)
            s = jnp.maximum(qn + dns[None, :] - 2.0 * dot, 0.0)
        elif mt is DistanceType.CosineExpanded:
            qn = jnp.sqrt(jnp.maximum(jnp.sum(qc * qc, axis=1, keepdims=True),
                                      1e-30))
            s = 1.0 - dot / (qn * dns[None, :])
        else:                                   # InnerProduct: min-space -dot
            s = -dot
        if pen is not None:
            s = s + pen[None, :]
        if n >= 8192:
            # wide rows: block-min two-level select (see _blockmin_topk)
            return _blockmin_topk(s, k)
        negv, idx = jax.lax.top_k(-s, k)
        return -negv, idx

    vals, idxs = _chunked_queries(one, q, chunk, k)
    idxs = jnp.where(jnp.isfinite(vals), idxs, -1)
    if mt is DistanceType.L2SqrtExpanded:
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    elif mt is DistanceType.InnerProduct:
        vals = jnp.where(jnp.isfinite(vals), -vals, -jnp.inf)
    return vals, idxs


def _tune_key(index: Index, m: int, k: int) -> str:
    """Autotune bucket for the engine race. The store dtype is part of
    the key: the crossovers move with HBM traffic (a bf16 corpus streams
    at half the bytes, int8 at a quarter), so a winner measured for one
    storage mode must not steer another's dispatch."""
    from ..ops import autotune

    return autotune.shape_bucket("bf_search", n=index.size, m=m,
                                 d=index.dim, k=k,
                                 store=index.store_name)


def _fused_align_key(index: Index):
    """(tn, dim_p) the fused engine derives for this index — the ONE
    place the alignment contract between ``prepare_fused`` and
    ``fused_knn``'s internal padding is computed, so the two sites
    cannot silently desynchronize (tn depends only on dim/itemsize, not
    k: ``_pick_tiles`` varies tm with k, never tn)."""
    from ..ops.fused_knn import _pick_tiles

    if index.logical_dim is not None:
        # int4: the packed byte width IS the corpus minor dim (already
        # sublane-pair aligned by quantize_int4); tiles are sized for
        # the double-half query width the split dot contracts against
        d_w = index.dataset.shape[1]
        return _pick_tiles(2 * d_w, 1, 1)[1], d_w
    dtype = index.store_dtype
    itemsize = (jnp.dtype(dtype).itemsize
                if dtype in (jnp.bfloat16, jnp.int8, jnp.uint8) else 4)
    dim_p = round_up_to(index.dim, 128)
    return _pick_tiles(dim_p, 1, itemsize)[1], dim_p


def prepare_fused(index: Index) -> None:
    """Eagerly build the fused engine's tile-aligned corpus copy and
    attach it to the index (rows padded to the dataset-tile multiple,
    dim to the 128 lane width, plus a base +inf penalty on pad rows).
    The fused kernel then reads the corpus RESIDENT in HBM across calls
    instead of re-padding (a full corpus copy) per dispatch. No-op when
    the cache already matches the current tile geometry; realigns after
    a ``RAFT_TPU_FUSED_TILES`` change. Called automatically on eager
    fused dispatch and by ``tune_search``; jit users should call it once
    before tracing — caches are never written under a trace (storing
    tracers corrupts them), so an unprepared index pays the pad inside
    every jitted call."""
    if in_jax_trace():
        # enforce, not just document: a tracer stored in the cache would
        # poison every later eager dispatch (UnexpectedTracerError →
        # guard demotion) and the key-match early return would keep it
        return
    d = index.dataset
    if d.dtype not in (jnp.bfloat16, jnp.int8, jnp.uint8):
        d = d.astype(jnp.float32)
    n, dim = d.shape
    key = _fused_align_key(index)
    tn, dim_p = key
    n_pad = round_up_to(n, min(tn, round_up_to(n, 128)))
    cache = getattr(index, "_fused_pad", None)
    if cache is not None and cache[0] == key:
        return
    with tracing.range("raft_tpu::brute_force::prepare"):
        d_pad = jnp.pad(d, ((0, n_pad - n), (0, dim_p - dim)))
        base_pen = jnp.pad(jnp.zeros((n,), jnp.float32), (0, n_pad - n),
                           constant_values=jnp.inf)
        norms_pad = (None if index.norms is None
                     else jnp.pad(jnp.asarray(index.norms, jnp.float32),
                                  (0, n_pad - n)))
        scales_pad = (None if index.scales is None
                      else jnp.pad(jnp.asarray(index.scales, jnp.float32),
                                   (0, n_pad - n)))
    index._fused_pad = (key, d_pad, norms_pad, base_pen, scales_pad)


def tune_search(index: Index, queries, k: int, reps: int = 5,
                suspect_floor_s: float = 0.0):
    """Measure the search engines on-device for this shape class and cache
    the winner (consulted by ``algo="auto"``). Returns (winner, timings).

    Call eagerly (not under jit) — e.g. once at serving start, or from the
    bench harness before measuring.
    """
    from ..ops import autotune

    q = jnp.asarray(queries, jnp.float32)
    key = _tune_key(index, q.shape[0], k)
    # the index rides as a jit ARGUMENT: closure-baking it would trace
    # the dataset into the HLO as a constant (a corpus-sized compile
    # request)
    def _engine(algo):
        return autotune.JitArgFn(
            jax.jit(lambda qq, idx: search(idx, qq, k, algo=algo)), index)

    cands = {"matmul": _engine("matmul"), "scan": _engine("scan")}
    if index.metric in _PALLAS_METRICS and jax.default_backend() == "tpu":
        # the fused engine races at EVERY corpus size: the old 128k cap
        # guarded its O(k·m·n) per-tile extraction (a >20x loss at 500k,
        # r4), but the two-level block-min select reduced the steady-state
        # per-tile cost to one GEMM + one O(tm·tn) reduce, so the corpus
        # scan is bandwidth-bound (~n·d·itemsize bytes per batch) and the
        # race — not a constant — decides the crossover per shape bucket.
        # Only non-TPU backends sit out (the kernel exists there solely
        # as the interpret-mode test twin).
        prepare_fused(index)
        cands["pallas"] = _engine("pallas")
    # value_read: engine choice must not be steered by a backend that
    # lies about readiness (observed: block_until_ready returning in
    # ~1 ms for TFLOP-scale batches) — each rep closes with a host read
    winner, timings = autotune.tune_best(key, cands, q, reps=reps,
                                         force=True,
                                         suspect_floor_s=suspect_floor_s,
                                         value_read=True)
    if winner != "pallas":
        # the tile-aligned corpus copy is ~a corpus of extra HBM; keep it
        # only for the engine that won the race
        index.__dict__.pop("_fused_pad", None)
    return winner, timings


def _search_pallas(index: Index, q, k, filter, valid_rows, precision):
    """Fused Pallas distance+top-k path (the perf path on TPU)."""
    import os

    from ..ops import fused_knn

    mt = index.metric
    pen = _penalty_row(index, filter, valid_rows)
    ds, dn, sc = index.dataset, index.norms, index.scales
    if not in_jax_trace():
        # no-op on a matching key; builds or REALIGNS the cache after a
        # RAFT_TPU_FUSED_TILES change (fused dispatch was already chosen
        # here, so the corpus copy is earning its HBM)
        prepare_fused(index)
    cache = getattr(index, "_fused_pad", None)
    if cache is not None and cache[0] != _fused_align_key(index):
        cache = None   # stale geometry under a trace: inline pad instead
    if cache is not None:
        # tile-aligned corpus resident in HBM: no per-call pad copy
        _, ds, dn, base_pen, sc = cache
        pen = base_pen if pen is None else base_pen + jnp.pad(
            pen, (0, ds.shape[0] - index.size))

    # chunk queries to the fused engine's own budget: the kernel's VMEM
    # working set is per-tile (independent of m), so the chunk exists to
    # bound the (m, kp) output/accumulator footprint and the grid of a
    # single dispatch (graph builds push m to corpus scale). Each chunk
    # re-streams the corpus, so the default stays large — a 10k serving
    # batch is one dispatch.
    chunk = int(os.environ.get("RAFT_TPU_FUSED_QUERY_CHUNK", "16384"))
    m = q.shape[0]

    def one(qc):
        return fused_knn(qc, ds, k, metric=_PALLAS_METRICS[mt],
                         data_norms=dn, penalty=pen,
                         precision=precision, scales=sc,
                         int4_dim=index.logical_dim)

    with tracing.range("raft_tpu::brute_force::fused"):
        if m > chunk > 0:
            vals, idxs = _chunked_queries(one, q, chunk, k)
        else:
            vals, idxs = one(q)
    if mt is DistanceType.L2SqrtExpanded:
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    elif mt is DistanceType.InnerProduct:
        # kernel min-selects -dot; report the raw inner products
        vals = jnp.where(jnp.isfinite(vals), -vals, -jnp.inf)
    return vals, idxs


def _served(engine: str, out):
    """Count one eager dispatch of ``engine`` in the serving metrics'
    ``brute_force.search.<engine>`` counter and pass ``out`` through: the
    record of which engine ``algo="auto"`` chose. Traced calls count
    nothing (a trace is not a search)."""
    if not in_jax_trace():
        from ..serve import metrics as serve_metrics

        serve_metrics.counter(f"brute_force.search.{engine}").inc()
    return out


@interop.auto_convert_output
@tracing.annotate("raft_tpu::brute_force::search")
def search(
    index: Index,
    queries: jax.Array,
    k: int,
    tile_size: int = 8192,
    filter: Optional[Bitset] = None,  # noqa: A002 - mirrors reference name
    valid_rows: Optional[jax.Array] = None,
    algo: str = "auto",
    precision: str = "highest",
    workspace_mb: Optional[int] = None,
    res=None,
    query_chunk: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """k nearest neighbors of each query → (distances (m, k), indices (m, k)).

    ``filter``: optional sample bitset; cleared bits are excluded
    (the reference's bitset_filter applied to brute force).
    ``valid_rows``: optional traced scalar; rows at index >= valid_rows are
    excluded. Used by the sharded path where the per-shard row count is only
    known inside shard_map (padding shards).
    ``algo``: "pallas" (fused distance+top-k kernel: the VMEM-resident
    running-k path with the two-level block-min select, role of
    detail/knn_brute_force.cuh:61 + select_warpsort; streams every
    storage dtype — f32/bf16/int8/uint8 — in its stored width),
    "matmul" (one-shot GEMM + top_k, query-chunked to a workspace budget),
    "scan" (composed-XLA streaming fallback, any metric), or "auto"
    (consults the ops.autotune measurement cache — populate it with
    ``tune_search`` — falling back to matmul/scan by metric; see
    ops/autotune.py for why dispatch is measured, not hard-coded). Each
    eager dispatch adds one to the serving metrics' counter
    ``brute_force.search.<engine>`` of the engine that served it
    (``pallas``, ``matmul``, ``scan`` or the filter's ``crossover``,
    whose search of the compacted survivors counts its own engine too).
    ``precision``: MXU precision for the distance GEMM ("highest"/"default").
    ``workspace_mb``: matmul-engine distance-block budget override (else
    RAFT_TPU_MATMUL_WORKSPACE_MB, default 1024).
    ``res``/``query_chunk``: when a Resources carries a Deadline (or an
    explicit ``query_chunk`` is given), queries run in host-level chunks
    with a cancellation/deadline checkpoint between dispatches —
    ``DeadlineExceeded`` carries the completed chunks' partial results.
    """
    q = jnp.asarray(queries, jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim,
            "queries must be (m, %d), got %s", index.dim, q.shape)
    n = index.size
    expects(0 < k <= n, "k=%d out of range for index of size %d", k, n)
    if query_chunk <= 0 and deadline.carried(res) is not None:
        query_chunk = max(1, min(q.shape[0], 4096))
    # a carried deadline always takes the chunked path: even a single
    # chunk needs its pre-dispatch checkpoint (an already-expired budget
    # must raise, not dispatch)
    if query_chunk > 0 and (query_chunk < q.shape[0]
                            or deadline.carried(res) is not None):
        return run_query_chunks(
            lambda qc, _s0: search(index, qc, k, tile_size, filter,
                                   valid_rows, algo, precision,
                                   workspace_mb),
            q, query_chunk, res)
    mt = index.metric
    select_min = is_min_close(mt)
    expanded = mt in _PALLAS_METRICS

    if (filter is not None and valid_rows is None
            and index.logical_dim is None and not in_jax_trace()):
        # selectivity-adaptive crossover (ops/filter_policy.py): at
        # extreme selectivity a full scan pays the whole corpus's HBM
        # traffic to penalize almost every row — gather the survivors
        # and search the compacted set instead (exact either way; int4
        # stores skip it: nibble-packed rows don't row-gather).
        from ..ops import filter_policy

        fd = (None if filter_policy.adaptive_off()
              else filter_policy.decide_graph(filter, n, index.dim, k,
                                              family="brute_force"))
        if fd is not None and fd.use_brute:
            return filter_policy.crossover(
                fd, "brute_force",
                lambda: _served("crossover",
                                filter_policy.survivor_brute_dense(
                                    index.dataset, mt, q, k, filter,
                                    index.scales, index.metric_arg)),
                lambda: search(index, q, k, tile_size, filter, valid_rows,
                               algo, precision, workspace_mb))

    if algo == "auto":
        from ..ops import autotune

        hit = autotune.lookup(_tune_key(index, q.shape[0], k))
        if hit in ("pallas", "matmul", "scan") and (
                expanded or hit == "scan"):
            algo = hit
        elif not expanded:
            algo = "scan"
        else:
            # untuned heuristic: the fused engine owns corpus scale on
            # TPU — it pays corpus reads only (~n·d·itemsize bytes per
            # batch) where the GEMM engine materializes the (m, n)
            # distance block through HBM plus a select pass — but auto
            # only routes there when a prepare_fused cache is ALREADY
            # attached: an untuned read-only query must not double the
            # index's HBM footprint as a side effect, and trace-built
            # indexes (shard_map shard-locals) could never cache at all.
            # tune_search/make_searcher(algo='pallas') are the opt-ins;
            # the measured race then owns the bucket.
            if (jax.default_backend() == "tpu" and n >= (32 << 10)
                    and getattr(index, "_fused_pad", None) is not None):
                algo = "pallas"
            else:
                algo = "matmul"
    if algo == "pallas":
        expects(mt in _PALLAS_METRICS,
                "algo='pallas' supports L2/cosine/IP, got %s", mt.name)
        # guarded: a fused-kernel failure demotes this site to the exact
        # GEMM engine (ops/guarded.py)
        return guarded_call(
            "brute_force.fused",
            lambda: _served("pallas", _search_pallas(
                index, q, k, filter, valid_rows, precision)),
            lambda: _served("matmul", _search_matmul(
                index, q, k, filter, valid_rows, precision, workspace_mb)))
    if algo == "matmul":
        expects(expanded,
                "algo='matmul' supports L2/cosine/IP, got %s", mt.name)
        return _served("matmul", _search_matmul(
            index, q, k, filter, valid_rows, precision, workspace_mb))

    tile = min(tile_size, round_up_to(n, 128))
    n_pad = round_up_to(n, tile)
    data = jnp.pad(index.dataset, ((0, n_pad - n), (0, 0)))
    norms = index.norms
    if norms is None:
        norms = jnp.zeros((n,), jnp.float32)
    norms_p = jnp.pad(norms, (0, n_pad - n))
    n_tiles = n_pad // tile
    data_t = data.reshape(n_tiles, tile, data.shape[1])
    norms_t = norms_p.reshape(n_tiles, tile)
    scales_t = None
    if index.scales is not None:
        scales_t = jnp.pad(index.scales, (0, n_pad - n)).reshape(
            n_tiles, tile)

    q_norm = jnp.sum(q * q, axis=1)
    bad = jnp.inf if select_min else -jnp.inf
    col = jnp.arange(tile, dtype=jnp.int32)
    mask_bits = filter.to_mask() if filter is not None else None
    if mask_bits is not None:
        mask_t = jnp.pad(mask_bits, (0, n_pad - n)).reshape(n_tiles, tile)
    kt = min(k, tile)

    def step(carry, inp):
        best_val, best_idx = carry  # (m, k), (m, k)
        tmask = tile_scale = None
        if mask_bits is not None and scales_t is not None:
            tile_data, tile_norm, base, tmask, tile_scale = inp
        elif mask_bits is not None:
            tile_data, tile_norm, base, tmask = inp
        elif scales_t is not None:
            tile_data, tile_norm, base, tile_scale = inp
        else:
            tile_data, tile_norm, base = inp
        if index.logical_dim is not None:
            from ..ops.quant import dequantize_int4

            tile_data = dequantize_int4(tile_data, tile_scale, index.dim)
        else:
            tile_data = dequantize_rows(tile_data, tile_scale)
        d = _tile_distances(q, q_norm, tile_data, tile_norm, mt, index.metric_arg)
        limit = n if valid_rows is None else jnp.minimum(valid_rows, n)
        valid = (base + col) < limit
        if tmask is not None:
            valid = valid & tmask
        d = jnp.where(valid[None, :], d, bad)
        t_val, t_loc = select_k(d, kt, select_min=select_min)
        t_idx = t_loc + base
        merged_val = jnp.concatenate([best_val, t_val], axis=1)
        merged_idx = jnp.concatenate([best_idx, t_idx], axis=1)
        new_val, loc = select_k(merged_val, k, select_min=select_min)
        new_idx = jnp.take_along_axis(merged_idx, loc, axis=1)
        return (new_val, new_idx), None

    init = (jnp.full((q.shape[0], k), bad, jnp.float32),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    bases = jnp.arange(n_tiles, dtype=jnp.int32) * tile
    xs = [data_t, norms_t, bases]
    if mask_bits is not None:
        xs.append(mask_t)
    if scales_t is not None:
        xs.append(scales_t)
    (val, idx), _ = jax.lax.scan(step, init, tuple(xs))
    return _served("scan", (val, idx))


@interop.auto_convert_output
@tracing.annotate("raft_tpu::brute_force::knn")
def knn(dataset, queries, k, metric="sqeuclidean", metric_arg: float = 2.0,
        tile_size: int = 8192):
    """One-shot build+search (the reference's free-function ``knn``)."""
    return search(build(dataset, metric, metric_arg), queries, k, tile_size)


def knn_merge_parts(
    part_distances: jax.Array,
    part_indices: jax.Array,
    select_min: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard top-k results: (p, m, k) → (m, k).

    Analog of detail/knn_merge_parts.cuh:172, used by the sharded (MNMG)
    search path where each shard holds globally-valid indices.
    """
    p, m, k = part_distances.shape
    d = jnp.transpose(part_distances, (1, 0, 2)).reshape(m, p * k)
    i = jnp.transpose(part_indices, (1, 0, 2)).reshape(m, p * k)
    val, loc = select_k(d, k, select_min=select_min)
    return val, jnp.take_along_axis(i, loc, axis=1)


def save(index: Index, path) -> None:
    """Serialize (analog of brute_force_serialize.cuh). bf16 datasets are
    framed as uint16 (npy has no bfloat16) with the dtype recorded in the
    header."""
    import numpy as np

    ds = index.dataset
    meta = {"metric": index.metric.value,
            "metric_arg": float(index.metric_arg),
            "store_dtype": index.store_name}
    if index.logical_dim is not None:
        meta["logical_dim"] = int(index.logical_dim)
    if ds.dtype == jnp.bfloat16:
        ds = np.asarray(jax.device_get(ds)).view(np.uint16)
    arrays = {"dataset": ds}
    if index.norms is not None:
        arrays["norms"] = index.norms
    if index.scales is not None:
        arrays["scales"] = index.scales
    save_arrays(path, "brute_force", _SERIAL_VERSION, meta, arrays)


def load(path) -> Index:
    import ml_dtypes
    import numpy as np

    _, version, meta, arrays = load_arrays(path, "brute_force")
    expects(version in (1, 2), "unsupported serialization version %d", version)
    ds = np.asarray(arrays["dataset"])
    if meta.get("store_dtype") == "bfloat16":
        ds = ds.view(ml_dtypes.bfloat16)
    return Index(
        jnp.asarray(ds),
        jnp.asarray(arrays["norms"]) if "norms" in arrays else None,
        DistanceType(meta["metric"]),
        meta["metric_arg"],
        jnp.asarray(arrays["scales"]) if "scales" in arrays else None,
        meta.get("logical_dim"),
    )


def make_searcher(index: Index, params=None, **opts):
    """Stable batchable signature for the serving runtime
    (:mod:`raft_tpu.serve`): returns ``fn(queries, k, res=None) ->
    (distances, indices)`` with every engine choice frozen at closure
    build time, so repeated bucketed-shape calls hit the same cached
    executables. ``params`` exists for signature parity across the index
    families (brute force has no SearchParams and rejects one); ``opts``
    forwards to :func:`search` (``algo``, ``precision``, ``filter``,
    ``query_chunk``, ...)."""
    expects(params is None, "brute_force has no SearchParams; pass engine "
            "options as keywords")
    if opts.get("algo") == "pallas":
        # serving closures dispatch eagerly: align the corpus for the
        # fused engine once at closure build, not on the first request.
        # "auto" defers to the first eager dispatch (absorbed by serve
        # warmup) so an index whose race winner is matmul never holds
        # the extra corpus copy.
        prepare_fused(index)

    def _fn(queries, k, res=None):
        return search(index, queries, k, res=res, **opts)

    return _fn
