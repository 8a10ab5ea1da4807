"""IVF-Flat index: analog of ``raft::neighbors::ivf_flat``.

Reference: raft/neighbors/ivf_flat_types.hpp:131 (index = per-cluster
inverted lists of raw vectors), detail/ivf_flat_build.cuh:123-343
(build/extend: kmeans_balanced coarse quantizer + grouped-interleaved list
layout) and detail/ivf_flat_search-inl.cuh:38-255 (coarse GEMM + select_k,
then a fused per-list scan+topk kernel).

TPU design: lists live as *contiguous row ranges of one dense row-sorted
array* (cluster-sorted dataset + offsets) — the TPU analog of the
reference's interleaved group-of-32 layout (ivf_flat_build.cuh:87-158),
whose purpose (coalesced full-width loads) XLA gets for free from dense
rows. Search is two MXU stages: (1) coarse = queries×centroids GEMM +
select_k → n_probes lists; (2) candidate rows of the probed lists are
gathered per query chunk and scored with a batched GEMV + masked select_k.
The probe budget is the sum of the n_probes largest list sizes, so shapes
stay static under jit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import interop, tracing
from ..core.bitset import Bitset
from ..core.errors import expects
from ..core.resources import workspace_chunk_bytes
from ..core.serialize import load_arrays, save_arrays
from ..ops.guarded import guarded_call
from ..cluster import kmeans_balanced
from ..distance.distance_types import DistanceType, canonical_metric, is_min_close
from ..matrix.select_k import select_k
from ..utils import cdiv, hdot, in_jax_trace, run_query_chunks

__all__ = ["IndexParams", "SearchParams", "Index", "build",
           "build_from_batches", "extend", "search", "prepare_scan",
           "prepare_host_stream", "reconstruct", "save", "load",
           "make_searcher", "health"]

# v2: store_dtype meta + uint16-framed bf16 rows + int8 scales; v1 files
# (dense f32) remain readable
_SERIAL_VERSION = 2


@dataclasses.dataclass
class IndexParams:
    """Mirror of ivf_flat::index_params (ivf_flat_types.hpp).

    ``list_growth``: per-list capacity slack factor. 1.0 packs lists
    (aligned) densely; >1 reserves slack so ``extend`` is an O(batch)
    in-place device scatter until a list overflows (the reference grows
    lists via conservative_memory_allocation, ivf_flat_types.hpp)."""

    n_lists: int = 1024
    metric: DistanceType | str = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    add_data_on_build: bool = True
    seed: int = 0
    list_growth: float = 1.0
    # dataset storage dtype: float32 | bfloat16 (half the scan HBM
    # traffic) | int8 (quarter, per-row scales) | uint8 (quarter, exact
    # for byte corpora like SIFT/DEEP) — role of the per-dtype
    # loadAndComputeDist variants (ivf_flat_interleaved_scan-inl.cuh:99)
    dtype: str = "float32"


@dataclasses.dataclass
class SearchParams:
    """Mirror of ivf_flat::search_params."""

    n_probes: int = 20


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """Cluster-sorted IVF-Flat index.

    ``data``: (cap_total, d) rows sorted by list, with per-list capacity
    slack (rows in [offset+size, offset+cap) are unread padding);
    ``source_ids``: (cap_total,) original ids (-1 on slack);
    ``list_offsets``: (n_lists+1,) capacity offsets (host numpy — static
    under jit); ``list_sizes_arr``: (n_lists,) true sizes; ``centers``:
    (n_lists, d).
    """

    data: jax.Array                # (cap_total, d) f32 | bf16 | int8 | uint8
    data_norms: jax.Array          # (cap_total,) exact f32 (of stored rep)
    source_ids: jax.Array
    centers: jax.Array
    center_norms: jax.Array
    list_offsets: np.ndarray       # host-side, static
    metric: DistanceType
    conservative_memory: bool = False
    list_sizes_arr: Optional[np.ndarray] = None  # None → dense (old files)
    list_growth: float = 1.0
    scales: Optional[jax.Array] = None  # (cap_total,) f32, int8 mode only

    @property
    def size(self) -> int:
        """Number of indexed vectors (excludes capacity slack)."""
        return int(self.list_sizes.sum())

    @property
    def dim(self) -> int:
        return self.data.shape[1]

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def list_sizes(self) -> np.ndarray:
        if self.list_sizes_arr is not None:
            return self.list_sizes_arr
        return np.diff(self.list_offsets)

    def tree_flatten(self):
        # the pallas scan-prep cache travels WITH the index so a jitted
        # function can take the index as an ARGUMENT (closure-baked index
        # arrays would become index-sized HLO constants)
        cache = getattr(self, "_scan_pad", None)
        cache_leaves = None if cache is None else tuple(cache[1:])
        leaves = (self.data, self.data_norms, self.source_ids,
                  self.centers, self.center_norms, self.scales,
                  cache_leaves)
        aux = (tuple(self.list_offsets.tolist()), self.metric,
               self.conservative_memory,
               None if self.list_sizes_arr is None
               else tuple(self.list_sizes_arr.tolist()),
               self.list_growth,
               None if cache is None else cache[0])
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        offsets, metric, conservative, sizes, growth, cache_lmax = aux
        out = cls(*leaves[:5], np.asarray(offsets, np.int64), metric,
                  conservative,
                  None if sizes is None else np.asarray(sizes, np.int64),
                  growth, leaves[5])
        if cache_lmax is not None and leaves[6] is not None:
            out._scan_pad = (cache_lmax, *leaves[6])
        return out


@tracing.annotate("raft_tpu::ivf_flat::build")
def build(dataset, params: IndexParams | None = None) -> Index:
    """Train the coarse quantizer on a subsample and fill the lists
    (detail/ivf_flat_build.cuh:123).

    Device-resident end to end: the dataset never round-trips through the
    host (only O(n_lists) list sizes do) — the TPU analog of the
    reference's bounded-batch device build (ivf_pq_build.cuh:1550).
    """
    p = params or IndexParams()
    dataset = jnp.asarray(dataset, jnp.float32)
    n, d = dataset.shape
    mt = canonical_metric(p.metric)
    expects(mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                   DistanceType.InnerProduct, DistanceType.CosineExpanded),
            "ivf_flat supports L2/IP/cosine metrics, got %s", mt.name)
    expects(p.n_lists <= n, "n_lists %d > n %d", p.n_lists, n)

    # trainset subsample (ivf_flat_build.cuh uses a strided subsample)
    n_train = max(p.n_lists, int(n * p.kmeans_trainset_fraction))
    stride = max(1, n // n_train)
    trainset = dataset[::stride]

    bparams = kmeans_balanced.BalancedKMeansParams(
        n_iters=p.kmeans_n_iters, seed=p.seed)
    centers = kmeans_balanced.fit(trainset, p.n_lists, bparams)

    store_t = jnp.dtype(p.dtype)
    index = Index(
        jnp.zeros((0, d), store_t), jnp.zeros((0,), jnp.float32),
        jnp.zeros((0,), jnp.int32), centers,
        jnp.sum(centers * centers, axis=1),
        np.zeros(p.n_lists + 1, np.int64), mt,
        list_sizes_arr=np.zeros(p.n_lists, np.int64),
        list_growth=p.list_growth,
        scales=jnp.zeros((0,), jnp.float32) if store_t == jnp.int8 else None)
    if p.add_data_on_build:
        index = extend(index, dataset)
    return index


@tracing.annotate("raft_tpu::ivf_flat::build_from_batches")
def build_from_batches(batches, params: IndexParams | None = None,
                       trainset=None) -> Index:
    """Streaming build for corpora larger than host/device-transfer
    budgets (role of the reference's bounded-batch extend loop,
    detail/ivf_pq_build.cuh:1550, scaled to DEEP-1B-class inputs).

    ``batches``: iterable of (b, d) row blocks (e.g.
    ``bench.datasets.iter_fbin``); host memory stays O(batch). The coarse
    quantizer trains on ``trainset`` when given, else on the first batch.
    Capacity slack (``params.list_growth``, bumped to >=1.2 here) keeps
    subsequent extends O(batch) in-place scatters.
    """
    from ._list_layout import streaming_build

    return streaming_build(batches, params or IndexParams(), build, extend,
                           dataclasses.replace, trainset)


@tracing.annotate("raft_tpu::ivf_flat::extend")
def extend(index: Index, new_vectors, new_ids=None) -> Index:
    """Add vectors to an existing index (detail/ivf_flat_build.cuh:extend).

    O(batch) device scatter while lists have capacity slack; a list
    overflow triggers a device-side repack with ``list_growth`` slack
    (no host copies of the dataset either way).

    .. note:: For *online* mutation prefer the crash-safe tier,
       :class:`raft_tpu.neighbors.mutable.MutableIndex` — it adds
       durability (WAL'd upserts), deletes (tombstones) and a
       background merge, and its parity test pins
       ``upsert + merge == build`` on the concatenated corpus
       (docs/mutation.md). ``extend`` remains the right call inside
       bulk streaming builds (``build_from_batches``), where the WAL
       would only be overhead.
    """
    from ._list_layout import scatter_build, scatter_extend
    from .brute_force import dequantize_rows, quantize_rows

    new_vectors = jnp.asarray(new_vectors, jnp.float32)
    expects(new_vectors.shape[1] == index.dim, "dim mismatch")
    n_new = new_vectors.shape[0]
    if new_ids is None:
        base = int(index.source_ids.max()) + 1 if index.size else 0
        new_ids = jnp.arange(base, base + n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)
    labels, _ = kmeans_balanced.predict(new_vectors, index.centers)

    stored, new_scales = quantize_rows(new_vectors, index.data.dtype)
    deq = dequantize_rows(stored, new_scales)
    norms = jnp.sum(deq * deq, axis=1)   # exact norms of the stored rep

    new_arrays = [stored, norms, new_ids]
    old_arrays = [index.data, index.data_norms, index.source_ids]
    fills = [0, 0.0, -1]
    if new_scales is not None:
        new_arrays.append(new_scales)
        old_arrays.append(index.scales)
        fills.append(1.0)
    if index.size == 0:
        out, offsets, sizes = scatter_build(
            labels, new_arrays, fills, index.n_lists, index.list_growth)
    else:
        out, offsets, sizes = scatter_extend(
            labels, new_arrays, old_arrays, fills,
            index.list_offsets, index.list_sizes, index.list_growth)
    scales = out[3] if new_scales is not None else None
    return Index(out[0], out[1], out[2], index.centers, index.center_norms,
                 offsets, index.metric, index.conservative_memory,
                 sizes, index.list_growth, scales)


def _probe_budget(list_sizes: np.ndarray, n_probes: int) -> int:
    """Static upper bound on candidate rows: sum of the n_probes largest
    lists (rounded up for alignment)."""
    top = np.sort(list_sizes)[::-1][:n_probes]
    return max(8, int(top.sum()))


def _candidate_rows(probed_lists, offsets_j, sizes_j, max_rows):
    """(m, n_probes) probed list ids → (m, max_rows) row ids + validity +
    the probe rank covering each slot.

    For each query, the rows of its probed lists are laid out back-to-back;
    slot s maps to probe j = searchsorted(cum_sizes, s) and row
    offsets[list_j] + (s - cum_sizes[j-1]).
    """
    sizes = sizes_j[probed_lists]                       # (m, p)
    cum = jnp.cumsum(sizes, axis=1)                     # (m, p)
    total = cum[:, -1]
    slots = jnp.arange(max_rows, dtype=jnp.int32)       # (S,)
    # probe covering each slot: number of cum entries <= slot
    probe_of = jnp.sum(cum[:, None, :] <= slots[None, :, None], axis=2)  # (m, S)
    probe_of = jnp.minimum(probe_of, sizes.shape[1] - 1)
    prev_cum = jnp.where(probe_of > 0,
                         jnp.take_along_axis(cum, jnp.maximum(probe_of - 1, 0),
                                             axis=1), 0)
    within = slots[None, :] - prev_cum
    list_of = jnp.take_along_axis(probed_lists, probe_of, axis=1)
    rows = offsets_j[list_of] + within
    valid = slots[None, :] < total[:, None]
    rows = jnp.where(valid, rows, 0)
    return rows, valid, probe_of


_PALLAS_METRICS = {
    DistanceType.L2Expanded: "l2",
    DistanceType.L2SqrtExpanded: "l2",
    DistanceType.CosineExpanded: "cos",
    DistanceType.InnerProduct: "ip",
}


def _scan_penalty(index, mask_bits, lmax: int):
    """Sample filter → in-kernel penalty row in sorted row order, padded to
    the scan DMA window (built once per search call, not per query chunk)."""
    from ..ops.ivf_scan import scan_window

    if mask_bits is None:
        return None
    return jnp.pad(jnp.where(mask_bits[index.source_ids], 0.0, jnp.inf),
                   (0, scan_window(lmax)))


def prepare_scan(index: Index) -> None:
    """Eagerly build the pallas scan's aligned-DMA padded copy and attach
    it to the index (a full-dataset pad pass). Called automatically on the
    first *eager* search; jit users should call it once before tracing —
    caches are never written under a trace (storing tracers corrupts
    them), so an unprepared index pays the pad inside every jitted call."""
    lmax = int(index.list_sizes.max())
    cache = getattr(index, "_scan_pad", None)
    if cache is None or cache[0] != lmax:
        from ..ops.ivf_scan import pad_for_scan

        index._scan_pad = (lmax,
                           *pad_for_scan(index.data, index.data_norms,
                                         lmax, index.scales))


def _search_pallas(index, q, k, n_probes, offsets_j, sizes_j, precision,
                   pen_p=None, survivors=None):
    """Fused query-grouped list scan (the TPU perf path; ops/ivf_scan.py)."""
    from ..ops.ivf_scan import _ivf_flat_scan_jit, coarse_probe, pad_for_scan

    mt = index.metric
    with tracing.range("raft_tpu::ivf_flat::coarse"):
        probed = coarse_probe(q, index.centers, n_probes,
                              metric=_PALLAS_METRICS[mt],
                              center_norms=index.center_norms,
                              precision=precision, survivors=survivors)
    lmax = int(index.list_sizes.max())
    # the aligned-DMA padding copies the dataset: cached once per index,
    # but NEVER stored from inside a trace (leaked tracers)
    cache = getattr(index, "_scan_pad", None)
    if cache is None or cache[0] != lmax:
        if in_jax_trace():
            # traced: compute inline, never store (leaked tracers)
            cache = (lmax, *pad_for_scan(index.data, index.data_norms,
                                         lmax, index.scales))
        else:
            prepare_scan(index)
            cache = index._scan_pad
    interpret = jax.default_backend() != "tpu"
    with tracing.range("raft_tpu::ivf_flat::scan"):
        vals, rows = _ivf_flat_scan_jit(cache[1], cache[2], pen_p, cache[3],
                                        probed, offsets_j, sizes_j, q, k,
                                        lmax, _PALLAS_METRICS[mt], interpret,
                                        precision)
        ids = jnp.where(rows >= 0,
                        jnp.take(index.source_ids, jnp.maximum(rows, 0)), -1)
    if mt is DistanceType.L2SqrtExpanded:
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    elif mt is DistanceType.InnerProduct:
        vals = jnp.where(jnp.isfinite(vals), -vals, -jnp.inf)
    return vals, ids



@interop.auto_convert_output
@tracing.annotate("raft_tpu::ivf_flat::search")
def search(
    index: Index,
    queries,
    k: int,
    params: SearchParams | None = None,
    filter: Optional[Bitset] = None,  # noqa: A002
    query_chunk: int = 0,
    algo: str = "auto",
    precision: str = "highest",
    res=None,
) -> Tuple[jax.Array, jax.Array]:
    """Probe the n_probes nearest lists per query and return exact top-k over
    their members → (distances (m, k), indices (m, k)) with original ids.

    ``algo``: "pallas" (fused query-grouped list scan — the TPU perf path,
    role of the interleaved-scan kernel; ``filter`` rides in-kernel as a
    penalty row), "xla" (gather-based composed-XLA path), "auto" (pallas
    on TPU).

    A host-streamed index (:func:`prepare_host_stream`) serves its
    resident lists through the same engines and double-buffers the
    probed COLD lists' rows from host RAM per batch; host streaming is
    eager-only (host arrays cannot ride a jit trace).
    """
    p = params or SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim, "bad query shape %s", q.shape)
    tier = getattr(index, "_host_tier", None)
    if tier is not None and not getattr(_hot_local, "skip", False):
        # loud, not silent: a traced search of a host-streamed index
        # would skip every cold list and return systematically partial
        # results
        expects(not in_jax_trace(),
                "host-streamed indexes search eagerly (host arrays "
                "cannot ride a jit trace) — drop the outer jit or "
                "search before prepare_host_stream")
        return _search_host_stream(index, tier, q, k, p, filter,
                                   query_chunk, algo, precision, res)
    expects(index.size > 0, "index is empty")
    n_probes = min(p.n_probes, index.n_lists)
    mt = index.metric

    offsets_j = jnp.asarray(index.list_offsets[:-1], jnp.int32)
    sizes_np = index.list_sizes
    sizes_j = jnp.asarray(sizes_np, jnp.int32)
    mask_bits = filter.to_mask() if filter is not None else None

    # selectivity-adaptive policy (ops/filter_policy.py): measure per-list
    # survivor counts once, prune zero-survivor lists (their scan size
    # zeroes → sentinel rows, no DMA), widen the probe set to restore the
    # survivor-weighted candidate mass, and at extreme selectivity cross
    # over to an exact brute-force pass on the compacted survivors. The
    # widen/crossover half needs host values, so a traced search keeps
    # only the free device-side prune.
    surv_dev = None
    if filter is not None:
        from ..ops import filter_policy

        if (in_jax_trace() or getattr(_hot_local, "skip", False)
                or filter_policy.adaptive_off()):
            # traced, the resident half of a host-streamed search (which
            # keeps its own machinery), or a suspended internal filter
            # (mutable tombstones): free prune only
            surv_dev = filter_policy.list_survivors(index, filter)
        else:
            fd = filter_policy.decide_ivf(index, filter, n_probes, k,
                                          "ivf_flat")
            if fd.use_brute:
                return filter_policy.crossover(
                    fd, "ivf_flat",
                    lambda: filter_policy.survivor_brute_ivf(
                        index, reconstruct, q, k, filter),
                    lambda: search(index, q, k, p, filter, query_chunk,
                                   algo, precision, res))
            n_probes = fd.n_probes
            surv_dev = fd.surv_dev
        sizes_j = jnp.where(surv_dev > 0, sizes_j, 0)

    # every storage dtype rides the pallas scan: f32/bf16 natively,
    # int8 via per-row scales applied to the dot in-kernel, uint8 exact
    # (byte values are representable in bf16; role of the per-dtype
    # loadAndComputeDist variants, ivf_flat_interleaved_scan-inl.cuh:99)
    use_pallas = (algo == "pallas" or
                  (algo == "auto" and mt in _PALLAS_METRICS and
                   jax.default_backend() == "tpu"))
    if use_pallas:
        expects(mt in _PALLAS_METRICS, "metric %s unsupported by pallas",
                mt.name)
        pen_p = _scan_penalty(index, mask_bits,
                              int(index.list_sizes.max()))
        dim_pad = -(-index.dim // 128) * 128
        if query_chunk <= 0:
            # bound the (pairs × dim) query blocks to ~256 MB
            per_q = n_probes * dim_pad * 4
            query_chunk = max(1, min(q.shape[0],
                                     workspace_chunk_bytes(res) // max(per_q, 1)))
        fb_state: dict = {}   # built lazily: the fallback almost never runs

        def _xla_fallback(qc):
            # the gather path's per-query footprint (max_rows * dim * 4)
            # is orders of magnitude above the kernel's — re-chunk to ITS
            # workspace budget or the containment path itself OOMs
            if not fb_state:
                fb_state["max_rows"] = _probe_budget(sizes_np, n_probes)
                per_q = fb_state["max_rows"] * index.dim * 4
                fb_state["chunk"] = max(
                    1, workspace_chunk_bytes(res) // max(per_q, 1))
            return run_query_chunks(
                lambda qs, _s0: _search_chunk(index, qs, k, n_probes,
                                              fb_state["max_rows"],
                                              offsets_j, sizes_j, mask_bits,
                                              mt, surv_dev),
                qc, fb_state["chunk"])

        # guarded: a scan-kernel failure demotes this site to the exact
        # XLA gather path (ops/guarded.py)
        return run_query_chunks(
            lambda qc, _s0: guarded_call(
                "ivf_flat.scan",
                lambda: _search_pallas(index, qc, k, n_probes, offsets_j,
                                       sizes_j, precision, pen_p, surv_dev),
                lambda: _xla_fallback(qc)),
            q, query_chunk, res)

    max_rows = _probe_budget(sizes_np, n_probes)
    if query_chunk <= 0:
        # bound gathered candidates to ~256 MB
        per_q = max_rows * index.dim * 4
        query_chunk = max(1, min(q.shape[0], workspace_chunk_bytes(res) // max(per_q, 1)))

    return run_query_chunks(
        lambda qc, _s0: _search_chunk(index, qc, k, n_probes, max_rows,
                                      offsets_j, sizes_j, mask_bits, mt,
                                      surv_dev),
        q, query_chunk, res)


def search_arrays(data, data_norms, source_ids, centers, center_norms,
                  offsets_j, sizes_j, qc, k, n_probes, max_rows, mt,
                  mask_bits=None, scales=None, survivors=None,
                  int4_dim=None):
    """Pure-array IVF-Flat search core — everything traced, so it runs under
    jit, vmap and shard_map alike (the multi-chip path stacks per-shard
    arrays and calls this per shard). ``data`` may be stored low-precision
    (bf16/int8 + per-row ``scales``, or nibble-packed int4 when
    ``int4_dim`` names the logical width); gathers dequantize on the
    fly."""
    from .brute_force import dequantize_rows

    from ..ops.ivf_scan import coarse_probe

    select_min = is_min_close(mt)
    # stage 1: coarse probe selection (ivf_flat_search-inl.cuh:38) —
    # shared with the pallas path so both engines probe identical lists
    cmetric = ("ip" if mt is DistanceType.InnerProduct
               else "cos" if mt is DistanceType.CosineExpanded else "l2")
    probed = coarse_probe(qc, centers, n_probes, metric=cmetric,
                          center_norms=center_norms, survivors=survivors)

    # stage 2: gather candidates and score (the fused-scan analog)
    rows, valid, _ = _candidate_rows(probed, offsets_j, sizes_j, max_rows)
    if int4_dim is not None:
        from ..ops.quant import dequantize_int4

        cand = dequantize_int4(data[rows], scales[rows], int4_dim)
    else:
        cand = dequantize_rows(data[rows],
                               None if scales is None else scales[rows])
    if mt is DistanceType.InnerProduct:
        dist = jnp.einsum("msd,md->ms", cand, qc, precision="highest")
    elif mt is DistanceType.CosineExpanded:
        ip = jnp.einsum("msd,md->ms", cand, qc, precision="highest")
        qn = jnp.sqrt(jnp.maximum(jnp.sum(qc * qc, axis=1, keepdims=True), 1e-30))
        cn = jnp.sqrt(jnp.maximum(data_norms[rows], 1e-30))
        dist = 1.0 - ip / (qn * cn)
    else:
        ip = jnp.einsum("msd,md->ms", cand, qc, precision="highest")
        q2 = jnp.sum(qc * qc, axis=1, keepdims=True)
        dist = jnp.maximum(q2 + data_norms[rows] - 2.0 * ip, 0.0)
        if mt is DistanceType.L2SqrtExpanded:
            dist = jnp.sqrt(dist)

    if mask_bits is not None:
        valid = valid & mask_bits[source_ids[rows]]
    bad = jnp.inf if select_min else -jnp.inf
    dist = jnp.where(valid, dist, bad)
    kk = min(k, max_rows)
    vals, locs = select_k(dist, kk, select_min=select_min)
    ids = jnp.take_along_axis(source_ids[rows], locs, axis=1)
    ids = jnp.where(jnp.isfinite(vals) if select_min else vals > -jnp.inf,
                    ids, -1)
    if kk < k:  # pad (tiny indexes)
        pad = k - kk
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=bad)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    return vals, ids


def _search_chunk(index, qc, k, n_probes, max_rows, offsets_j, sizes_j,
                  mask_bits, mt, survivors=None):
    return search_arrays(index.data, index.data_norms, index.source_ids,
                         index.centers, index.center_norms, offsets_j,
                         sizes_j, qc, k, n_probes, max_rows, mt, mask_bits,
                         index.scales, survivors)


_hot_local = __import__("threading").local()   # re-entry guard: the hot
# half of a host-streamed search runs the ordinary resident path


def prepare_host_stream(index: Index, budget_gb: Optional[float] = None,
                        sample_queries=None, n_probes: int = 20,
                        chunk_mb: int = 64, hot_mask=None) -> None:
    """Move cold lists past the HBM budget into a host-RAM tier
    (docs/perf.md "Storage ladder", the beyond-HBM rung): the device
    keeps the hottest lists — ranked by measured probe frequency over
    ``sample_queries`` (list size standing in without a sample) — and
    every search double-buffers the probed cold lists' rows from host
    numpy over PCIe, scanning them with the SAME kernel as the resident
    lists and merging via ``knn_merge_parts``.

    ``budget_gb`` defaults to ``RAFT_TPU_HBM_BUDGET_GB``. A corpus that
    already fits is a no-op (no tier, nothing changes). Idempotent.
    Mutates the index in place (resident arrays shrink to the hot
    lists); ``index._host_tier`` carries the cold chunks and stats.
    Host-streamed search is EAGER-only — serving dispatch already is.

    ``hot_mask`` (bool, ``(n_lists,)``) bypasses the local budget plan
    with an externally-planned hot set — the fleet layer plans
    hot/cold ONCE from fleet-wide probe counts and hands each shard its
    slice, so per-shard planners never disagree about what is hot.
    """
    from ..ops.ivf_scan import scan_window
    from ..utils import round_up_to
    from . import host_stream as hs

    if getattr(index, "_host_tier", None) is not None:
        return
    sizes = index.list_sizes
    itemsize = jnp.dtype(index.data.dtype).itemsize
    row_bytes = (index.dim * itemsize + 8
                 + (4 if index.scales is not None else 0))
    if hot_mask is not None:
        hot = np.asarray(hot_mask, bool)
        expects(hot.shape == (index.n_lists,),
                f"hot_mask shape {hot.shape} != ({index.n_lists},)")
        if bool(hot.all()):
            return   # externally planned: everything stays resident
    else:
        budget = hs.budget_bytes(budget_gb)
        expects(budget > 0, "prepare_host_stream needs budget_gb or "
                "RAFT_TPU_HBM_BUDGET_GB")
        if int(sizes.sum()) * row_bytes <= budget:
            return   # everything fits: stay fully resident
        freq = None
        if sample_queries is not None:
            from ..ops.ivf_scan import coarse_probe

            cmetric = ("ip" if index.metric is DistanceType.InnerProduct
                       else "cos" if index.metric is DistanceType.CosineExpanded
                       else "l2")
            probed = np.asarray(coarse_probe(
                jnp.asarray(sample_queries, jnp.float32), index.centers,
                min(n_probes, index.n_lists), metric=cmetric,
                center_norms=index.center_norms))
            freq = hs.probe_frequency(probed, index.n_lists)
        hot = hs.plan_hot_cold(sizes, row_bytes, budget, freq)

    dim_pad = round_up_to(index.dim, 128)
    # cold chunks carry their rows SCAN-READY: dim padded to the lane
    # tile and `scan_window` tail rows for the kernel's aligned DMA —
    # a streamed chunk is never re-padded on device
    data_np = np.asarray(jax.device_get(index.data))
    if data_np.dtype == np.uint16:   # defensive: never expected
        raise AssertionError("unexpected raw-framed dataset")
    arrays = {
        "data": np.pad(np.asarray(data_np),
                       ((0, 0), (0, dim_pad - index.dim))),
        "norms": np.asarray(index.data_norms, np.float32),
        "ids": np.asarray(index.source_ids, np.int32),
    }
    fills = {"ids": -1}
    if index.scales is not None:
        arrays["scales"] = np.asarray(index.scales, np.float32)
        fills["scales"] = 1.0
    chunk_rows = max(1, int(float(chunk_mb) * (1 << 20)) // max(row_bytes, 1))
    cold_lmax = int(sizes[~hot].max()) if (~hot).any() else 0
    tier, hot_arrays, hot_offsets, hot_sizes = hs.build_tier(
        arrays, index.list_offsets, sizes, hot, chunk_rows,
        pad_tail=scan_window(cold_lmax), fills=fills)

    index.data = jnp.asarray(
        hot_arrays["data"][:, :index.dim].astype(data_np.dtype))
    index.data_norms = jnp.asarray(hot_arrays["norms"])
    index.source_ids = jnp.asarray(hot_arrays["ids"])
    if index.scales is not None:
        index.scales = jnp.asarray(hot_arrays["scales"])
    index.list_offsets = hot_offsets
    index.list_sizes_arr = hot_sizes
    index.__dict__.pop("_scan_pad", None)   # stale resident-scan cache
    index._host_tier = tier


@dataclasses.dataclass
class _ColdScanArgs:
    """Static scan geometry shared by every chunk of one tier (one jit
    executable serves all chunks)."""

    k: int
    lmax: int
    metric: str
    precision: str
    # logical row width when the chunk's rows are nibble-packed int4
    # (fleet quant-ladder tiers); None for f32/bf16/int8 storage
    int4_dim: Optional[int] = None


def _cold_chunk_scan_flat(index, dev, probed_local, qc, args, mask_bits):
    """Scan one streamed cold chunk with the SAME kernel as the resident
    lists (ops/ivf_scan.py) — per-list results are bit-identical to the
    fully-resident scan's."""
    from ..ops.ivf_scan import _ivf_flat_scan_jit

    ids = dev["ids"]
    pen_p = None
    if mask_bits is not None:
        pen_p = jnp.where((ids >= 0)
                          & jnp.take(mask_bits, jnp.maximum(ids, 0)),
                          0.0, jnp.inf).astype(jnp.float32)
    interpret = jax.default_backend() != "tpu"
    vals, rows = _ivf_flat_scan_jit(
        dev["data"], dev["norms"], pen_p, dev.get("scales"),
        jnp.asarray(probed_local), dev["offsets"].astype(jnp.int32),
        dev["sizes"].astype(jnp.int32), qc, args.k, args.lmax,
        args.metric, interpret, args.precision)
    out_i = jnp.where(rows >= 0, jnp.take(ids, jnp.maximum(rows, 0)), -1)
    return vals, out_i


def _cold_chunk_xla_flat(index, dev, probed_local, qc, args, mask_bits):
    """Guarded fallback: XLA rescore of the same streamed chunk (the
    search_arrays math on block-local lists) — correct, not
    arithmetic-identical to the kernel."""
    n_probes = probed_local.shape[1]
    max_rows = args.lmax * min(n_probes, dev["offsets"].shape[0])
    rows, valid, _ = _candidate_rows(
        jnp.asarray(probed_local), dev["offsets"].astype(jnp.int32),
        dev["sizes"].astype(jnp.int32), max_rows)
    from .brute_force import dequantize_rows

    sc = dev.get("scales")
    if args.int4_dim is not None:
        from ..ops.quant import dequantize_int4

        cand = dequantize_int4(dev["data"][rows], sc[rows], args.int4_dim)
    else:
        cand = dequantize_rows(dev["data"][rows],
                               None if sc is None else sc[rows])[..., :index.dim]
    mt = index.metric
    ip = jnp.einsum("msd,md->ms", cand, qc, precision="highest")
    if mt is DistanceType.InnerProduct:
        dist = -ip
    elif mt is DistanceType.CosineExpanded:
        qn = jnp.sqrt(jnp.maximum(
            jnp.sum(qc * qc, axis=1, keepdims=True), 1e-30))
        cn = jnp.sqrt(jnp.maximum(dev["norms"][rows], 1e-30))
        dist = 1.0 - ip / (qn * cn)
    else:
        q2 = jnp.sum(qc * qc, axis=1, keepdims=True)
        dist = jnp.maximum(q2 + dev["norms"][rows] - 2.0 * ip, 0.0)
    ids = dev["ids"][rows]
    valid = valid & (ids >= 0)
    if mask_bits is not None:
        valid = valid & jnp.take(mask_bits, jnp.maximum(ids, 0))
    dist = jnp.where(valid, dist, jnp.inf)
    kk = min(args.k, max_rows)
    vals, locs = select_k(dist, kk, select_min=True)
    out_i = jnp.where(jnp.isfinite(vals),
                      jnp.take_along_axis(ids, locs, axis=1), -1)
    if kk < args.k:
        vals = jnp.pad(vals, ((0, 0), (0, args.k - kk)),
                       constant_values=jnp.inf)
        out_i = jnp.pad(out_i, ((0, 0), (0, args.k - kk)),
                        constant_values=-1)
    return vals, out_i


def _postprocess(mt, vals):
    if mt is DistanceType.L2SqrtExpanded:
        return jnp.sqrt(jnp.maximum(vals, 0.0))
    if mt is DistanceType.InnerProduct:
        return jnp.where(jnp.isfinite(vals), -vals, -jnp.inf)
    return vals


def _search_host_stream(index, tier, q, k, p, filter, query_chunk, algo,
                        precision, res):
    """Resident half through the ordinary engines + probed cold lists
    streamed from the host tier, merged exactly like shard results
    (knn_merge_parts)."""
    from ..ops.ivf_scan import coarse_probe

    mt = index.metric
    select_min = is_min_close(mt)
    n_probes = min(p.n_probes, index.n_lists)
    mask_bits = filter.to_mask() if filter is not None else None
    cmetric = ("ip" if mt is DistanceType.InnerProduct
               else "cos" if mt is DistanceType.CosineExpanded else "l2")
    args = _ColdScanArgs(k, tier.lmax, _PALLAS_METRICS.get(mt, "l2"),
                         precision)
    if query_chunk <= 0:
        per_q = n_probes * (-(-index.dim // 128) * 128) * 4
        query_chunk = max(1, min(q.shape[0],
                                 workspace_chunk_bytes(res) // max(per_q, 1)))

    def one(qc, _s0):
        bad = jnp.inf if select_min else -jnp.inf
        if index.size > 0:
            _hot_local.skip = True
            try:
                hot_d, hot_i = search(index, qc, min(k, max(index.size, 1)),
                                      SearchParams(n_probes), filter,
                                      0, algo, precision)
            finally:
                _hot_local.skip = False
            if hot_d.shape[1] < k:
                pad = k - hot_d.shape[1]
                hot_d = jnp.pad(hot_d, ((0, 0), (0, pad)),
                                constant_values=bad)
                hot_i = jnp.pad(hot_i, ((0, 0), (0, pad)),
                                constant_values=-1)
        else:
            hot_d = jnp.full((qc.shape[0], k), bad, jnp.float32)
            hot_i = jnp.full((qc.shape[0], k), -1, jnp.int32)
        # the hot half just probed the same centers inside its own
        # fused executable; re-deriving the (m, p) ids here costs one
        # small GEMM + a host copy and keeps the resident executables
        # byte-identical to the tier-less path (threading probes out of
        # them would fork every compiled signature)
        probed = np.asarray(coarse_probe(
            qc, index.centers, n_probes, metric=cmetric,
            center_norms=index.center_norms, precision=precision))

        def run(ci, dev, probed_local):
            return guarded_call(
                "ivf.host_stream",
                lambda: _cold_chunk_scan_flat(index, dev, probed_local,
                                              qc, args, mask_bits),
                lambda: _cold_chunk_xla_flat(index, dev, probed_local,
                                             qc, args, mask_bits))

        cold = tier.stream(probed, run)
        if not cold:
            return hot_d, hot_i
        parts_d = [hot_d] + [_postprocess(mt, cd) for cd, _ in cold]
        parts_i = [hot_i] + [ci_ for _, ci_ in cold]
        from .brute_force import knn_merge_parts

        return knn_merge_parts(jnp.stack(parts_d), jnp.stack(parts_i),
                               select_min)

    return run_query_chunks(one, q, query_chunk, res)


def reconstruct(index: Index, row_ids) -> jax.Array:
    """Decode stored rows back to f32 input-space vectors by physical row
    id (role of the reference's ivf_flat helpers unpack/reconstruct list
    data, ivf_flat_helpers.cuh / ivf_flat_codepacker.hpp). Exact for f32
    storage; dequantized (per-row scale) for bf16/int8 storage. Physical
    row ids are what ``search`` returns before the source-id remap — i.e.
    positions in the cluster-sorted ``index.data``; use ``source_ids`` to
    map back to original ids.

    Range/slack validation runs eagerly only: under a jax trace invalid
    ids follow gather clamp semantics (no error) — validate before
    jitting."""
    from .brute_force import dequantize_rows

    row_ids = jnp.asarray(row_ids, jnp.int32)
    if not in_jax_trace():
        rid = np.asarray(row_ids)
        cap = index.data.shape[0]
        expects(rid.size == 0 or (rid.min() >= 0 and rid.max() < cap),
                "row_ids out of range [0, %d)", cap)
        # device-side gather, O(len(row_ids)) host transfer
        src = np.asarray(index.source_ids[row_ids]) if rid.size else rid
        expects((src >= 0).all(),
                "row_ids hit capacity-slack rows (source_id -1)")
    rows = index.data[row_ids]
    scales = None if index.scales is None else index.scales[row_ids]
    return dequantize_rows(rows, scales)


def save(index: Index, path) -> None:
    """Serialize (analog of ivf_flat_serialize.cuh). Capacity slack is
    stripped: the file holds densely-packed valid rows (v1 layout), so
    files are slack-free and old readers stay compatible. bf16 rows are
    framed as uint16 (npy has no bfloat16) with the dtype in the header.

    Host-streamed indexes refuse to serialize: the device arrays hold
    only the HOT lists, so a silent save would permanently drop every
    cold row — save before :func:`prepare_host_stream` (the tier is
    derived state; rebuild it after load)."""
    from ._list_layout import gather_dense

    expects(getattr(index, "_host_tier", None) is None,
            "cannot save a host-streamed index (cold lists live in the "
            "host tier, not the device arrays); save before "
            "prepare_host_stream and re-prepare after load")

    sizes = index.list_sizes
    arrays = [index.data, index.source_ids]
    if index.scales is not None:
        arrays.append(index.scales)
    if index.list_sizes_arr is not None:
        arrays, _ = gather_dense(arrays, index.list_offsets, sizes)
    data, ids = arrays[0], arrays[1]
    dense_offsets = np.zeros(index.n_lists + 1, np.int64)
    np.cumsum(sizes, out=dense_offsets[1:])
    if data.dtype == jnp.bfloat16:
        data = np.asarray(jax.device_get(data)).view(np.uint16)
    out = {
        "data": data,
        "source_ids": ids,
        "centers": index.centers,
        "list_offsets": dense_offsets,
    }
    if index.scales is not None:
        out["scales"] = arrays[2]
    save_arrays(
        path, "ivf_flat", _SERIAL_VERSION,
        {"metric": index.metric.value, "n_lists": index.n_lists,
         "store_dtype": str(index.data.dtype)},
        out)


def load(path) -> Index:
    import ml_dtypes

    from .brute_force import dequantize_rows

    _, version, meta, arrs = load_arrays(path, "ivf_flat")
    expects(version in (1, 2), "unsupported version %d", version)
    data_np = np.asarray(arrs["data"])
    if meta.get("store_dtype") == "bfloat16":
        data_np = data_np.view(ml_dtypes.bfloat16)
    data = jnp.asarray(data_np)
    scales = jnp.asarray(arrs["scales"]) if "scales" in arrs else None
    deq = dequantize_rows(data, scales)
    centers = jnp.asarray(arrs["centers"])
    offsets = np.asarray(arrs["list_offsets"], np.int64)
    return Index(
        data, jnp.sum(deq * deq, axis=1), jnp.asarray(arrs["source_ids"]),
        centers, jnp.sum(centers * centers, axis=1), offsets,
        DistanceType(meta["metric"]),
        list_sizes_arr=np.diff(offsets), scales=scales)


def health(index: Index, sample: int = 256) -> dict:
    """Index health report (docs/observability.md "Quality"): list-size
    skew (the probe-budget and recall-concentration signal) + storage
    width. int8 stores report sampled per-row scale stats over real rows
    (slack rows carry no data) — the quantization step bound, since the
    f32 originals are not retained."""
    from ._list_layout import list_skew
    from .brute_force import health_sample_rows, int8_scale_report

    report = {
        "family": "ivf_flat", "n": int(index.size), "dim": int(index.dim),
        "metric": index.metric.name,
        "store_dtype": str(jnp.dtype(index.data.dtype)),
        "lists": list_skew(index.list_sizes),
    }
    dt = jnp.dtype(index.data.dtype)
    if dt == jnp.int8 and index.scales is not None:
        rows = health_sample_rows(index.data.shape[0], sample)
        sid = np.asarray(index.source_ids[rows])
        sc = np.asarray(index.scales[rows], np.float64)[sid >= 0]
        if sc.size:
            report["quant"] = int8_scale_report(sc)
    elif dt == jnp.bfloat16:
        report["quant"] = {"bfloat16": {"rel_step": 2.0 ** -8}}
    elif dt == jnp.uint8:
        report["quant"] = {"uint8": {"exact": True}}
    return report


def make_searcher(index: Index, params: SearchParams | None = None, *,
                  degrade=None, **opts):
    """Stable batchable signature for the serving runtime
    (:mod:`raft_tpu.serve`): returns ``fn(queries, k, res=None) ->
    (distances, indices)`` with the probe policy and engine choice frozen
    at closure build time, so repeated bucketed-shape calls hit the same
    cached executables. ``opts`` forwards to :func:`search` (``algo``,
    ``filter``, ``precision``, ``query_chunk``, ...). ``degrade``: a
    :class:`~raft_tpu.serve.degrade.BrownoutController` — under brownout
    its current level overrides ``n_probes`` per call (same shape
    buckets, one compile per visited level; docs/robustness.md)."""
    base = params or SearchParams()

    def _fn(queries, k, res=None):
        p = base if degrade is None else degrade.params(base)
        return search(index, queries, k, p, res=res, **opts)

    return _fn
