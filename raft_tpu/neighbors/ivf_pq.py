"""IVF-PQ index: analog of ``raft::neighbors::ivf_pq``.

Reference: raft/neighbors/ivf_pq_types.hpp:43,110-146,264 (params: pq_bits,
pq_dim, codebook_gen PER_SUBSPACE|PER_CLUSTER, force_random_rotation; index
holds rotation matrix, coarse centers, codebooks, packed code lists),
detail/ivf_pq_build.cuh:1729 (build: kmeans_balanced coarse quantizer →
rotation matrix → train_per_subset/train_per_cluster codebooks → extend
packs codes) and detail/ivf_pq_search.cuh:731 (search: coarse GEMM +
select_k → rotate queries → per-(query,probe) LUT + packed-code scan).

TPU design differences from the CUDA reference:

* **Everything lives in rotated space.** The rotation is orthogonal, so L2
  and inner-product are preserved; we rotate the dataset once at build and
  the queries once at search, and then coarse selection, residuals, and
  codebooks never leave rotated coordinates (the reference rotates queries
  but keeps separate "extended" centers — ivf_pq_search.cuh:69-170 — to
  fold norms into one GEMM; XLA fuses that for free).
* **Lists are contiguous row ranges** of one dense cluster-sorted code
  matrix (codes: (n, pq_dim) uint8) — same layout as our IVF-Flat — instead
  of the reference's bit-packed interleaved groups (ivf_pq_codepacking.cuh):
  a byte per sub-quantizer keeps gathers vectorizable; pq_bits < 8 still
  shrinks the *codebook*, and a packed serialization keeps files small.
* **The LUT-in-shared-memory kernel** (ivf_pq_compute_similarity-inl.cuh:271)
  becomes one einsum building all (query, probe) LUTs at once + a flat
  take_along_axis contraction — both XLA-friendly; VMEM plays the role of
  the LUT smem automatically.
* Codebook training vmaps a fixed-iteration Lloyd over subspaces (or over
  clusters for PER_CLUSTER), replacing the reference's per-subspace stream
  parallelism (ivf_pq_build.cuh:392,469).
"""
from __future__ import annotations

import dataclasses
import enum
from functools import partial
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
from ..distance.distance_types import DistanceType, canonical_metric
from ..matrix.select_k import select_k
from ..utils import cdiv, hdot, in_jax_trace, run_query_chunks
from .ivf_flat import _candidate_rows, _probe_budget

__all__ = ["CodebookGen", "IndexParams", "SearchParams", "Index", "build",
           "build_from_batches", "extend", "search", "prepare_scan",
           "prepare_host_stream", "save", "load", "pack_codes",
           "unpack_codes", "reconstruct", "make_searcher", "health"]

_SERIAL_VERSION = 1

# auto-dispatch downgrade reasons already logged (once per process)
_GATHER_FALLBACK_LOGGED: set = set()


class CodebookGen(enum.Enum):
    """ivf_pq_types.hpp:43 codebook_gen."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


@dataclasses.dataclass
class IndexParams:
    """Mirror of ivf_pq::index_params (ivf_pq_types.hpp:110)."""

    n_lists: int = 1024
    metric: DistanceType | str = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8                   # 4..8
    pq_dim: int = 0                    # 0 → dim/4 rounded to a multiple of 8
    codebook_kind: CodebookGen = CodebookGen.PER_SUBSPACE
    force_random_rotation: bool = False
    add_data_on_build: bool = True
    seed: int = 0
    # per-list capacity slack factor: >1 makes extend an O(batch) in-place
    # device scatter until a list overflows (see neighbors/_list_layout.py)
    list_growth: float = 1.0


@dataclasses.dataclass
class SearchParams:
    """Mirror of ivf_pq::search_params (ivf_pq_types.hpp:146).

    The reference's lut_dtype/internal_distance_dtype knobs select smem LUT
    precision; here `lut_dtype` selects the scan compute dtype:
    ``jnp.float32`` exact, ``jnp.bfloat16`` (default, the fp16-LUT role),
    or ``jnp.int8`` / ``"int8"`` (the fp8-LUT role: per-subspace
    symmetrically-quantized codebook, int8 MXU decode at double rate —
    pair with refine for full recall).

    There is deliberately no ``internal_distance_dtype`` knob: the MXU
    accumulates every LUT mode in f32/int32 natively, so the reference's
    fp16-internal-distance speed/accuracy trade (ivf_pq_types.hpp:110-146)
    costs nothing to skip on TPU — internal distances are always full
    precision here."""

    n_probes: int = 20
    lut_dtype: jnp.dtype | str = jnp.bfloat16


def _lut_mode(lut_dtype) -> str:
    """SearchParams.lut_dtype → kernel mode string. Unknown names raise —
    a typo must not silently downgrade precision."""
    if isinstance(lut_dtype, str):
        s = lut_dtype.lower()
        if s in ("int8", "i8", "fp8"):
            return "int8"
        if s in ("f32", "float32", "fp32"):
            return "f32"
        expects(s in ("bf16", "bfloat16", "fp16", "f16"),
                "unknown lut_dtype %r (use float32 / bfloat16 / int8)",
                lut_dtype)
        return "bf16"
    dt = jnp.dtype(lut_dtype)
    if dt == jnp.int8:
        return "int8"
    if dt == jnp.float32:
        return "f32"
    expects(dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)),
            "unknown lut_dtype %r (use float32 / bfloat16 / int8)",
            lut_dtype)
    return "bf16"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """Rotated-space IVF-PQ index.

    ``codes``: (n, pq_dim) uint8 cluster-sorted; ``centers_rot``:
    (n_lists, rot_dim); ``codebooks``: (pq_dim, 2^bits, pq_len) for
    PER_SUBSPACE or (n_lists, 2^bits, pq_len) for PER_CLUSTER;
    ``rotation``: (rot_dim, dim) with orthonormal columns.
    """

    codes: jax.Array
    source_ids: jax.Array
    centers_rot: jax.Array
    codebooks: jax.Array
    rotation: jax.Array
    list_offsets: np.ndarray        # host-side, static (capacity offsets)
    metric: DistanceType
    pq_bits: int
    codebook_kind: CodebookGen
    list_sizes_arr: Optional[np.ndarray] = None  # None → dense (old files)
    list_growth: float = 1.0

    @property
    def size(self) -> int:
        """Number of indexed vectors (excludes capacity slack)."""
        return int(self.list_sizes.sum())

    @property
    def dim(self) -> int:
        return self.rotation.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.codes.shape[1]

    @property
    def pq_len(self) -> int:
        return self.codebooks.shape[2]

    @property
    def pq_book_size(self) -> int:
        return 1 << self.pq_bits

    @property
    def n_lists(self) -> int:
        return self.centers_rot.shape[0]

    @property
    def list_sizes(self) -> np.ndarray:
        if self.list_sizes_arr is not None:
            return self.list_sizes_arr
        return np.diff(self.list_offsets)

    def tree_flatten(self):
        # the pallas scan-prep cache travels WITH the index: a jitted
        # function taking the index as an argument (the
        # constants-as-parameters pattern — closure-baked index arrays
        # would be index-sized HLO constants) keeps the
        # prepared arrays instead of re-deriving them inside the trace
        cache = getattr(self, "_scan_cache", None)
        cache_leaves = (None if cache is None else
                        (cache["codes_p"], cache["norms_p"], cache["cbm"]))
        cache_aux = (None if cache is None else
                     (cache["n"], cache["lmax"]))
        leaves = (self.codes, self.source_ids, self.centers_rot,
                  self.codebooks, self.rotation, cache_leaves)
        aux = (tuple(self.list_offsets.tolist()), self.metric, self.pq_bits,
               self.codebook_kind,
               None if self.list_sizes_arr is None
               else tuple(self.list_sizes_arr.tolist()),
               self.list_growth, cache_aux)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        offsets, metric, pq_bits, kind, sizes, growth, cache_aux = aux
        *core, cache_leaves = leaves
        out = cls(*core, np.asarray(offsets, np.int64), metric, pq_bits,
                  kind,
                  None if sizes is None else np.asarray(sizes, np.int64),
                  growth)
        if cache_aux is not None and cache_leaves is not None:
            out._scan_cache = {
                "n": cache_aux[0], "lmax": cache_aux[1],
                "codes_p": cache_leaves[0], "norms_p": cache_leaves[1],
                "cbm": cache_leaves[2]}
        return out


def _default_pq_dim(dim: int) -> int:
    """ivf_pq_types.hpp: pq_dim=0 → dim/4 rounded for alignment."""
    pq = max(1, dim // 4)
    if pq > 8:
        pq = (pq // 8) * 8
    return pq


def make_rotation_matrix(key, rot_dim: int, dim: int,
                         force_random: bool) -> jax.Array:
    """(rot_dim, dim) with orthonormal columns (ivf_pq_build.cuh:119).

    Identity when rot_dim == dim and no rotation is forced; otherwise the Q
    factor of a gaussian (the reference uses RSVD of a gaussian for the same
    effect; like the reference, rot_dim != dim always randomizes).
    """
    if not force_random and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    # rot_dim != dim always gets a random rotation (ivf_pq_types.hpp:87-90):
    # a zero-padded identity would leave the tail subspace mostly zeros,
    # wasting its codebook
    g = jax.random.normal(key, (rot_dim, rot_dim), jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q[:, :dim]


@partial(jax.jit, static_argnums=(1, 2))
def _kmeans_fixed(x, k, iters, key):
    """Fixed-iteration Lloyd for codebook training — vmappable.

    ``x``: (T, d) with possible repeated/padded rows; init = random distinct
    subsample; empty clusters keep their previous center.
    """
    n, d = x.shape
    perm = jax.random.permutation(key, n)[:k]
    centers0 = x[perm]

    def step(centers, _):
        d2 = (jnp.sum(x * x, axis=1, keepdims=True)
              - 2.0 * hdot(x, centers.T)
              + jnp.sum(centers * centers, axis=1)[None, :])
        labels = jnp.argmin(d2, axis=1)
        sums = jax.ops.segment_sum(x, labels, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), labels,
                                   num_segments=k)
        new = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts, 1)[:, None],
                        centers)
        return new, None

    centers, _ = jax.lax.scan(step, centers0, None, length=iters)
    return centers


# the reference's index_params::max_train_points_per_pq_code default
_MAX_TRAIN_POINTS_PER_PQ_CODE = 256


def _train_per_subspace(resid_slices, book_size, iters, key):
    """(pq_dim, T, pq_len) residual slices → (pq_dim, book, pq_len)
    codebooks (ivf_pq_build.cuh:392 train_per_subset).

    Like the reference, each subspace trains on at most
    ``_MAX_TRAIN_POINTS_PER_PQ_CODE`` points per code (a strided
    subsample), one subspace at a time: the vmapped (pq_dim, T, book)
    distance block was 15 GB at 1M × pq64 × book256 and exhausted a
    v5e's HBM."""
    t = resid_slices.shape[1]
    cap = _MAX_TRAIN_POINTS_PER_PQ_CODE * book_size
    if t > cap:
        resid_slices = resid_slices[:, ::-(-t // cap)]
    keys = jax.random.split(key, resid_slices.shape[0])
    return jax.lax.map(
        lambda xs: _kmeans_fixed(xs[0], book_size, iters, xs[1]),
        (resid_slices, keys))


def _train_per_cluster(resid_rot, labels, n_lists, pq_len, book_size, iters,
                       key, samples_per_list=2048):
    """Per-cluster codebooks over pooled subspace slices
    (ivf_pq_build.cuh:469 train_per_cluster).

    Each cluster trains on min(count*pq_dim, samples) of its residual
    sub-vectors; clusters are padded to a common sample count by sampling
    rows with replacement, so one vmap covers all lists.
    """
    n = resid_rot.shape[0]
    pq_dim = resid_rot.shape[1] // pq_len
    slices = resid_rot.reshape(n, pq_dim, pq_len)
    key_rows, key_fit = jax.random.split(key)

    # per-list row sampling (host: one cluster-sort pass, then slice)
    labels_np = np.asarray(labels)
    order = np.argsort(labels_np, kind="stable")
    counts = np.bincount(labels_np, minlength=n_lists)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rows = np.zeros((n_lists, samples_per_list), np.int32)
    rng = np.random.default_rng(int(jax.random.randint(key_rows, (), 0, 1 << 30)))
    for l in range(n_lists):
        members = order[starts[l] : starts[l] + counts[l]]
        if len(members) == 0:
            members = np.array([0], np.int64)
        rows[l] = rng.choice(members, size=samples_per_list, replace=True)
    rows_j = jnp.asarray(rows)

    # (n_lists, samples, pq_dim, pq_len) → pool subspaces into the sample axis
    pool = slices[rows_j].reshape(n_lists, samples_per_list * pq_dim, pq_len)
    keys = jax.random.split(key_fit, n_lists)
    return jax.vmap(_kmeans_fixed, in_axes=(0, None, None, 0))(
        pool, book_size, iters, keys)


@partial(jax.jit, static_argnums=(3,))
def _encode(resid_rot, codebooks, labels, kind_per_cluster: bool):
    """Residuals → (n, pq_dim) uint8 codes: per-subspace argmin."""
    n = resid_rot.shape[0]
    if kind_per_cluster:
        pq_len = codebooks.shape[2]
        pq_dim = resid_rot.shape[1] // pq_len
        slices = resid_rot.reshape(n, pq_dim, pq_len)
        books = codebooks[labels]                    # (n, book, pq_len)
        d2 = (jnp.sum(slices * slices, axis=2)[:, :, None]
              - 2.0 * jnp.einsum("nsl,nbl->nsb", slices, books, precision="highest")
              + jnp.sum(books * books, axis=2)[:, None, :])
        return jnp.argmin(d2, axis=2).astype(jnp.uint8)
    pq_dim, _, pq_len = codebooks.shape
    slices = jnp.transpose(resid_rot.reshape(n, pq_dim, pq_len), (1, 0, 2))

    def one(xs):
        x, cb = xs                                   # (n, pq_len), (book, pq_len)
        return jnp.argmin(jnp.sum(cb * cb, axis=1)[None, :]
                          - 2.0 * hdot(x, cb.T), axis=1)

    # one 2-D argmin per subspace, as the codebook training does: the
    # batched (n, pq_dim, book) einsum + argmin agreed with the CPU on
    # 25% of codes on a v5e (recall 0.07), while training matched it
    return jnp.transpose(jax.lax.map(one, (slices, codebooks))).astype(
        jnp.uint8)


@tracing.annotate("raft_tpu::ivf_pq::build")
def build(dataset, params: IndexParams | None = None) -> Index:
    """Train coarse quantizer + rotation + codebooks, then pack the dataset
    (detail/ivf_pq_build.cuh:1729)."""
    p = params or IndexParams()
    dataset = jnp.asarray(dataset, jnp.float32)   # device-resident build
    n, dim = dataset.shape
    mt = canonical_metric(p.metric)
    expects(mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                   DistanceType.InnerProduct),
            "ivf_pq supports L2/IP metrics, got %s", mt.name)
    expects(4 <= p.pq_bits <= 8, "pq_bits must be in [4,8], got %d", p.pq_bits)
    expects(p.n_lists <= n, "n_lists %d > n %d", p.n_lists, n)
    pq_dim = p.pq_dim or _default_pq_dim(dim)
    pq_len = cdiv(dim, pq_dim)
    rot_dim = pq_dim * pq_len
    book_size = 1 << p.pq_bits
    key = jax.random.key(p.seed)
    k_rot, k_book = jax.random.split(key)

    # coarse quantizer on a subsample (ivf_pq_build.cuh:1760-1830)
    n_train = max(p.n_lists, min(n, int(n * p.kmeans_trainset_fraction)))
    stride = max(1, n // n_train)
    trainset = dataset[::stride]
    bparams = kmeans_balanced.BalancedKMeansParams(
        n_iters=p.kmeans_n_iters, seed=p.seed)
    centers = kmeans_balanced.fit(trainset, p.n_lists, bparams)

    rotation = make_rotation_matrix(k_rot, rot_dim, dim,
                                    p.force_random_rotation)
    centers_rot = hdot(centers, rotation.T)

    # codebooks on rotated trainset residuals (ivf_pq_build.cuh:1855-1873)
    train_rot = hdot(trainset, rotation.T)
    t_labels, _ = kmeans_balanced.predict(trainset, centers)
    t_resid = train_rot - centers_rot[t_labels]
    if p.codebook_kind is CodebookGen.PER_SUBSPACE:
        slices = jnp.transpose(
            t_resid.reshape(-1, pq_dim, pq_len), (1, 0, 2))
        codebooks = _train_per_subspace(slices, book_size, p.kmeans_n_iters,
                                        k_book)
    else:
        codebooks = _train_per_cluster(t_resid, t_labels, p.n_lists, pq_len,
                                       book_size, p.kmeans_n_iters, k_book)

    index = Index(
        jnp.zeros((0, pq_dim), jnp.uint8), jnp.zeros((0,), jnp.int32),
        centers_rot, codebooks, rotation,
        np.zeros(p.n_lists + 1, np.int64), mt, p.pq_bits, p.codebook_kind,
        list_sizes_arr=np.zeros(p.n_lists, np.int64),
        list_growth=p.list_growth)
    if p.add_data_on_build:
        index = extend(index, dataset)
    return index


@tracing.annotate("raft_tpu::ivf_pq::build_from_batches")
def build_from_batches(batches, params: IndexParams | None = None,
                       trainset=None) -> Index:
    """Streaming build for memory-scale corpora (DEEP-1B north star;
    detail/ivf_pq_build.cuh:1550 bounded-batch role): quantizers train on
    ``trainset`` (or the first batch), then every batch is assigned,
    encoded and scattered on device — host memory stays O(batch).
    Capacity slack (>=1.2) keeps the merges O(batch) in-place."""
    from ._list_layout import streaming_build

    return streaming_build(batches, params or IndexParams(), build, extend,
                           dataclasses.replace, trainset)


@tracing.annotate("raft_tpu::ivf_pq::extend")
def extend(index: Index, new_vectors, new_ids=None,
           batch_size: int = 1 << 17) -> Index:
    """Assign, encode and merge new vectors (ivf_pq_build.cuh:1550).

    Device-resident: encoding runs in bounded device batches (host memory
    stays O(batch)), and the merge is an O(batch) in-place scatter while
    lists have capacity slack (``IndexParams.list_growth``), else a
    device-side repack.

    .. note:: For *online* mutation prefer the crash-safe tier,
       :class:`raft_tpu.neighbors.mutable.MutableIndex` — durability
       (WAL'd upserts), deletes (tombstones), background merge
       (docs/mutation.md). ``extend`` remains the right call inside
       bulk streaming builds (``build_from_batches``).
    """
    from ._list_layout import scatter_build, scatter_extend

    new_vectors = jnp.asarray(new_vectors, jnp.float32)
    expects(new_vectors.shape[1] == index.dim, "dim mismatch")
    n_new = new_vectors.shape[0]
    if new_ids is None:
        base = int(index.source_ids.max()) + 1 if index.size else 0
        new_ids = jnp.arange(base, base + n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    per_cluster = index.codebook_kind is CodebookGen.PER_CLUSTER
    # the per-subspace argmin inside _encode materializes a
    # (batch, pq_dim, book) f32 tensor — bound it to the shared HBM
    # budget, but never above a batch the caller explicitly lowered
    from ..ops.ivf_pq_scan import pq_chunk_rows

    batch_size = min(batch_size,
                     pq_chunk_rows(index.pq_dim, index.codebooks.shape[-2]))
    labels_parts, codes_parts = [], []
    for b0 in range(0, n_new, batch_size):
        xb = new_vectors[b0 : b0 + batch_size]
        xb_rot = hdot(xb, index.rotation.T)
        # nearest rotated center == nearest center (orthogonal rotation)
        d2 = (jnp.sum(xb_rot * xb_rot, axis=1, keepdims=True)
              - 2.0 * hdot(xb_rot, index.centers_rot.T)
              + jnp.sum(index.centers_rot * index.centers_rot, axis=1)[None, :])
        lb = jnp.argmin(d2, axis=1)
        resid = xb_rot - index.centers_rot[lb]
        codes_parts.append(_encode(resid, index.codebooks, lb, per_cluster))
        labels_parts.append(lb.astype(jnp.int32))
    labels = (labels_parts[0] if len(labels_parts) == 1
              else jnp.concatenate(labels_parts))
    new_codes = (codes_parts[0] if len(codes_parts) == 1
                 else jnp.concatenate(codes_parts))

    fills = (0, -1)
    if index.size == 0:
        (codes, ids), offsets, sizes = scatter_build(
            labels, (new_codes, new_ids), fills, index.n_lists,
            index.list_growth)
    else:
        (codes, ids), offsets, sizes = scatter_extend(
            labels, (new_codes, new_ids),
            (index.codes, index.source_ids), fills,
            index.list_offsets, index.list_sizes, index.list_growth)
    return Index(codes, ids, index.centers_rot, index.codebooks,
                 index.rotation, offsets, index.metric, index.pq_bits,
                 index.codebook_kind, sizes, index.list_growth)


def _scan_penalty(index, mask_bits, lmax: int):
    """Sample filter → in-kernel penalty row in sorted row order, padded to
    the scan DMA window (built once per search call, not per query chunk)."""
    from ..ops.ivf_scan import scan_window

    if mask_bits is None:
        return None
    return jnp.pad(jnp.where(mask_bits[index.source_ids], 0.0, jnp.inf),
                   (0, scan_window(lmax)))


def _scan_prep(index: Index, lmax: int) -> dict:
    """Row norms + CB matrix + aligned-DMA padding for the pallas scan —
    full passes over the compressed dataset."""
    from ..ops.ivf_pq_scan import (decoded_row_norms, make_cb_matrix,
                                   pad_codes_for_scan)

    rn = decoded_row_norms(index.codes, index.centers_rot,
                           index.codebooks, index.list_offsets)
    codes_p, norms_p = pad_codes_for_scan(index.codes, rn, lmax,
                                          index.pq_dim)
    return {"n": index.size, "lmax": lmax, "codes_p": codes_p,
            "norms_p": norms_p, "cbm": make_cb_matrix(index.codebooks)}


def prepare_scan(index: Index) -> None:
    """Eagerly attach the pallas scan's per-index prep (see
    ivf_flat.prepare_scan for the caching contract: never written under a
    trace; jit users call this once before tracing)."""
    lmax = int(index.list_sizes.max())
    cache = getattr(index, "_scan_cache", None)
    if cache is None or cache["n"] != index.size or cache["lmax"] != lmax:
        index._scan_cache = _scan_prep(index, lmax)


def _search_pallas(index: Index, q, k, n_probes, lut_dtype, precision,
                   pen_p=None, survivors=None):
    """Fused query-grouped PQ scan (ops/ivf_pq_scan.py) — the TPU perf
    path (expanded-form LUT + one-hot GEMM scoring)."""
    from ..ops.ivf_pq_scan import _ivf_pq_scan_jit
    from ..ops.ivf_scan import coarse_probe

    mt = index.metric
    lmax = int(index.list_sizes.max())
    cache = getattr(index, "_scan_cache", None)
    if cache is None or cache["n"] != index.size or cache["lmax"] != lmax:
        if in_jax_trace():
            cache = _scan_prep(index, lmax)   # traced: compute inline
        else:
            prepare_scan(index)
            cache = index._scan_cache

    coarse_metric = "ip" if mt is DistanceType.InnerProduct else "l2"
    with tracing.range("raft_tpu::ivf_pq::coarse"):
        q_rot = hdot(q, index.rotation.T)
        probed = coarse_probe(q_rot, index.centers_rot, n_probes,
                              metric=coarse_metric, precision=precision,
                              survivors=survivors)
    interpret = jax.default_backend() != "tpu"
    with tracing.range("raft_tpu::ivf_pq::scan"):
        sizes_j = jnp.asarray(index.list_sizes, jnp.int32)
        if survivors is not None:
            # zero-survivor lists scan as empty: sentinel rows only, no DMA
            sizes_j = jnp.where(survivors > 0, sizes_j, 0)
        vals, rows = _ivf_pq_scan_jit(
            cache["codes_p"], cache["norms_p"], pen_p, index.centers_rot,
            cache["cbm"], probed,
            jnp.asarray(index.list_offsets[:-1], jnp.int32),
            sizes_j, q_rot, k, lmax,
            index.pq_dim, index.pq_book_size,
            "ip" if mt is DistanceType.InnerProduct else "l2",
            _lut_mode(lut_dtype), interpret, precision)
        ids = jnp.where(rows >= 0,
                        jnp.take(index.source_ids, jnp.maximum(rows, 0)), -1)
    if mt is DistanceType.L2SqrtExpanded:
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    elif mt is DistanceType.InnerProduct:
        vals = jnp.where(jnp.isfinite(vals), -vals, -jnp.inf)
    return vals, ids



@interop.auto_convert_output
@tracing.annotate("raft_tpu::ivf_pq::search")
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
    """LUT-based approximate top-k (detail/ivf_pq_search.cuh:731).

    ``algo``: "pallas" (fused query-grouped PQ scan — the TPU perf path;
    PER_SUBSPACE codebooks; ``filter`` rides in-kernel as a penalty row),
    "xla" (gather path, any config), "auto" (pallas on TPU when eligible).
    """
    p = params or SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim, "bad query shape %s",
            tuple(q.shape))
    tier = getattr(index, "_host_tier", None)
    if tier is not None and not getattr(_hot_local, "skip", False):
        # loud, not silent: a traced search would skip every cold list
        expects(not in_jax_trace(),
                "host-streamed indexes search eagerly (host arrays "
                "cannot ride a jit trace) — drop the outer jit or "
                "search before prepare_host_stream")
        return _search_host_stream(index, tier, q, k, p, filter,
                                   query_chunk, algo, precision, res)
    expects(index.size > 0, "index is empty")
    n_probes = min(p.n_probes, index.n_lists)

    # selectivity-adaptive policy (ops/filter_policy.py): same contract
    # as ivf_flat.search — prune zero-survivor lists, widen the probe
    # set to the survivor-weighted mass target, cross over to the exact
    # compacted brute pass (decode + back-rotate the survivors) at
    # extreme selectivity. Traced searches keep only the device prune.
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
                                          "ivf_pq")
            if fd.use_brute:
                return filter_policy.crossover(
                    fd, "ivf_pq",
                    lambda: filter_policy.survivor_brute_ivf(
                        index, reconstruct, q, k, filter),
                    lambda: search(index, q, k, p, filter, query_chunk,
                                   algo, precision, res))
            n_probes = fd.n_probes
            surv_dev = fd.surv_dev

    # wide PQ shapes need the bf16/int8 LUT modes in the kernel (an f32
    # one-hot block would bust VMEM); an explicit f32-LUT request there
    # keeps the exact gather path rather than silently downgrading
    wide_needs_bf16 = (index.pq_dim * index.pq_book_size >= 8192 and
                       _lut_mode(p.lut_dtype) == "f32")
    use_pallas = (algo == "pallas" or
                  (algo == "auto" and
                   index.codebook_kind is CodebookGen.PER_SUBSPACE and
                   not wide_needs_bf16 and
                   jax.default_backend() == "tpu"))
    if (algo == "auto" and not use_pallas
            and jax.default_backend() == "tpu"):
        # make the kernel→gather downgrade visible — once per reason, not
        # per call; fires at trace time too (jitted callers like the
        # bench harnesses only ever execute this body while tracing)
        why = ("PER_CLUSTER codebooks"
               if index.codebook_kind is CodebookGen.PER_CLUSTER
               else "f32 LUT with wide PQ "
                    "(set SearchParams.lut_dtype=bfloat16)")
        if why not in _GATHER_FALLBACK_LOGGED:
            _GATHER_FALLBACK_LOGGED.add(why)
            from ..core.logging import logger

            logger.info("ivf_pq auto: XLA gather path (%s); the pallas "
                        "scan kernel does not cover this config", why)
    mask_bits = filter.to_mask() if filter is not None else None
    if use_pallas:
        expects(index.codebook_kind is CodebookGen.PER_SUBSPACE,
                "algo='pallas' needs PER_SUBSPACE codebooks")
        expects(not wide_needs_bf16,
                "algo='pallas' with pq_dim*2^pq_bits >= 8192 requires the "
                "bf16 LUT mode (SearchParams.lut_dtype=jnp.bfloat16)")
        pen_p = _scan_penalty(index, mask_bits,
                              int(index.list_sizes.max()))
        if query_chunk <= 0:
            per_q = n_probes * index.rot_dim * 4 * 2
            query_chunk = max(1, min(q.shape[0],
                                     workspace_chunk_bytes(res) // max(per_q, 1)))
        fb_state: dict = {}   # built lazily: the fallback almost never runs

        def _xla_fallback(qc):
            # the gather/LUT path's per-query footprint dwarfs the
            # kernel's — re-chunk to ITS workspace budget or the
            # containment path itself OOMs
            if not fb_state:
                sizes_np = index.list_sizes
                fb_state["max_rows"] = _probe_budget(sizes_np, n_probes)
                fb_state["offsets_j"] = jnp.asarray(
                    index.list_offsets[:-1], jnp.int32)
                sizes_j = jnp.asarray(sizes_np, jnp.int32)
                if surv_dev is not None:
                    sizes_j = jnp.where(surv_dev > 0, sizes_j, 0)
                fb_state["sizes_j"] = sizes_j
                per_q = fb_state["max_rows"] * index.pq_dim * 8 + \
                    n_probes * index.pq_dim * index.pq_book_size * 4
                fb_state["chunk"] = max(
                    1, workspace_chunk_bytes(res) // max(per_q, 1))
            return run_query_chunks(
                lambda qs, _s0: _search_chunk(index, qs, k, n_probes,
                                              fb_state["max_rows"],
                                              fb_state["offsets_j"],
                                              fb_state["sizes_j"],
                                              mask_bits, p.lut_dtype,
                                              surv_dev),
                qc, fb_state["chunk"])

        # guarded: a PQ-scan kernel failure demotes this site to the
        # exact XLA gather/LUT path (ops/guarded.py)
        return run_query_chunks(
            lambda qc, _s0: guarded_call(
                "ivf_pq.scan",
                lambda: _search_pallas(index, qc, k, n_probes, p.lut_dtype,
                                       precision, pen_p, surv_dev),
                lambda: _xla_fallback(qc)),
            q, query_chunk, res)

    sizes_np = index.list_sizes
    max_rows = _probe_budget(sizes_np, n_probes)
    if query_chunk <= 0:
        # candidates gather (S × pq_dim) + LUT (p × pq_dim × book) per query
        per_q = max_rows * index.pq_dim * 8 + \
            n_probes * index.pq_dim * index.pq_book_size * 4
        query_chunk = max(1, min(q.shape[0], workspace_chunk_bytes(res) // max(per_q, 1)))

    offsets_j = jnp.asarray(index.list_offsets[:-1], jnp.int32)
    sizes_j = jnp.asarray(sizes_np, jnp.int32)
    if surv_dev is not None:
        sizes_j = jnp.where(surv_dev > 0, sizes_j, 0)

    return run_query_chunks(
        lambda qc, _s0: _search_chunk(index, qc, k, n_probes, max_rows,
                                      offsets_j, sizes_j, mask_bits,
                                      p.lut_dtype, surv_dev),
        q, query_chunk, res)


def _search_chunk(index, qc, k, n_probes, max_rows, offsets_j, sizes_j,
                  mask_bits, lut_dtype, survivors=None):
    mt = index.metric
    m = qc.shape[0]
    pq_dim, book = index.pq_dim, index.pq_book_size
    pq_len = index.pq_len
    q_rot = qc @ index.rotation.T                       # (m, rot_dim)

    # stage 1: coarse probe selection (select_clusters, ivf_pq_search.cuh:69)
    cross = hdot(q_rot, index.centers_rot.T)
    if mt is DistanceType.InnerProduct:
        coarse = -cross
    else:
        c2 = jnp.sum(index.centers_rot * index.centers_rot, axis=1)
        coarse = c2[None, :] - 2.0 * cross              # + q² is rank-constant
    if survivors is not None:
        # filter-pruned lists never win a probe slot (ops/filter_policy.py)
        coarse = jnp.where(survivors[None, :] > 0, coarse, jnp.inf)
    _, probed = select_k(coarse, n_probes, select_min=True)   # (m, p)

    # stage 2: per-(query, probe) LUTs (the smem LUT analog)
    centers_p = index.centers_rot[probed]               # (m, p, rot_dim)
    if mt is DistanceType.InnerProduct:
        qs = q_rot.reshape(m, pq_dim, pq_len)
        if index.codebook_kind is CodebookGen.PER_SUBSPACE:
            lut = -jnp.einsum("msl,sbl->msb", qs, index.codebooks, precision="highest")
            lut = jnp.broadcast_to(lut[:, None], (m, n_probes, pq_dim, book))
        else:
            books = index.codebooks[probed]             # (m, p, book, pq_len)
            lut = -jnp.einsum("msl,mpbl->mpsb", qs, books, precision="highest")
        const = -jnp.einsum("mr,mpr->mp", q_rot, centers_p, precision="highest")
    else:
        resid = q_rot[:, None, :] - centers_p           # (m, p, rot_dim)
        rs = resid.reshape(m, n_probes, pq_dim, pq_len)
        if index.codebook_kind is CodebookGen.PER_SUBSPACE:
            cb2 = jnp.sum(index.codebooks * index.codebooks, axis=2)  # (s, b)
            lut = (jnp.sum(rs * rs, axis=3)[..., None]
                   - 2.0 * jnp.einsum("mpsl,sbl->mpsb", rs, index.codebooks, precision="highest")
                   + cb2[None, None])
        else:
            books = index.codebooks[probed]             # (m, p, book, pq_len)
            cb2 = jnp.sum(books * books, axis=3)        # (m, p, b)
            lut = (jnp.sum(rs * rs, axis=3)[..., None]
                   - 2.0 * jnp.einsum("mpsl,mpbl->mpsb", rs, books, precision="highest")
                   + cb2[:, :, None, :])
        const = jnp.zeros((m, n_probes), jnp.float32)
    # the gather path has no int8 formulation (scores are gathered, not
    # GEMMed); int8 requests ride its bf16 LUT instead
    mode = _lut_mode(lut_dtype)
    lut = lut.astype(jnp.float32 if mode == "f32" else jnp.bfloat16)

    # stage 3: score packed codes via one flat gather per subspace
    rows, valid, probe_of = _candidate_rows(probed, offsets_j, sizes_j,
                                            max_rows)
    codes_c = index.codes[rows].astype(jnp.int32)       # (m, S, pq_dim)
    sub_ids = jnp.arange(pq_dim, dtype=jnp.int32)
    flat = lut.reshape(m, n_probes * pq_dim * book)
    idx = (probe_of[:, :, None] * (pq_dim * book)
           + sub_ids[None, None, :] * book + codes_c)   # (m, S, pq_dim)
    vals = jnp.take_along_axis(flat, idx.reshape(m, -1), axis=1)
    dist = vals.reshape(m, max_rows, pq_dim).sum(axis=2).astype(jnp.float32)
    dist = dist + jnp.take_along_axis(const, probe_of, axis=1)
    if mt is DistanceType.L2SqrtExpanded:
        dist = jnp.sqrt(jnp.maximum(dist, 0.0))

    if mask_bits is not None:
        valid = valid & mask_bits[index.source_ids[rows]]
    dist = jnp.where(valid, dist, jnp.inf)
    kk = min(k, max_rows)
    out_d, locs = select_k(dist, kk, select_min=True)
    out_i = jnp.take_along_axis(index.source_ids[rows], locs, axis=1)
    out_i = jnp.where(jnp.isfinite(out_d), out_i, -1)
    if mt is DistanceType.InnerProduct:
        out_d = -out_d                                  # report true IP
    if kk < k:
        pad = k - kk
        bad = -jnp.inf if mt is DistanceType.InnerProduct else jnp.inf
        out_d = jnp.pad(out_d, ((0, 0), (0, pad)), constant_values=bad)
        out_i = jnp.pad(out_i, ((0, 0), (0, pad)), constant_values=-1)
    return out_d, out_i


_hot_local = __import__("threading").local()   # re-entry guard (the hot
# half of a host-streamed search runs the ordinary resident path)


def prepare_host_stream(index: Index, budget_gb: Optional[float] = None,
                        sample_queries=None, n_probes: int = 20,
                        chunk_mb: float = 64, hot_mask=None) -> None:
    """Move cold PQ lists past the HBM budget into a host-RAM tier —
    same contract as :func:`ivf_flat.prepare_host_stream` (probe-
    frequency pinning, fixed-shape double-buffered chunks, eager-only
    search; ``RAFT_TPU_HBM_BUDGET_GB`` default budget). PQ codes are
    16-32x smaller than raw rows, so this rung matters for indexes whose
    *code* store outgrows HBM (the DEEP-1B shape) or that share a device
    with raw-row indexes. Chunk rows carry codes (scan-padded), decoded
    row norms, source ids and the row's chunk-local list label.

    ``hot_mask`` (bool, ``(n_lists,)``) bypasses the local budget plan
    with an externally-planned hot set — same contract as the ivf_flat
    variant (the fleet layer plans once, fleet-wide)."""
    from ..ops.ivf_pq_scan import decoded_row_norms
    from ..ops.ivf_scan import scan_window
    from ..utils import round_up_to
    from . import host_stream as hs

    if getattr(index, "_host_tier", None) is not None:
        return
    sizes = index.list_sizes
    row_bytes = index.pq_dim + 12
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
            return
        freq = None
        if sample_queries is not None:
            from ..ops.ivf_scan import coarse_probe

            q_rot = hdot(jnp.asarray(sample_queries, jnp.float32),
                         index.rotation.T)
            probed = np.asarray(coarse_probe(
                q_rot, index.centers_rot, min(n_probes, index.n_lists),
                metric="ip" if index.metric is DistanceType.InnerProduct
                else "l2"))
            freq = hs.probe_frequency(probed, index.n_lists)
        hot = hs.plan_hot_cold(sizes, row_bytes, budget, freq)

    rn = decoded_row_norms(index.codes, index.centers_rot,
                           index.codebooks, index.list_offsets)
    code_pad = round_up_to(index.pq_dim, 128)
    labels = np.repeat(np.arange(index.n_lists),
                       np.diff(index.list_offsets)).astype(np.int32)
    arrays = {
        "codes": np.pad(np.asarray(index.codes, np.uint8),
                        ((0, 0), (0, code_pad - index.pq_dim))),
        "norms": np.asarray(rn, np.float32),
        "ids": np.asarray(index.source_ids, np.int32),
        "labels": labels,
    }
    chunk_rows = max(1, int(float(chunk_mb) * (1 << 20))
                     // max(row_bytes, 1))
    cold_lmax = int(sizes[~hot].max()) if (~hot).any() else 0
    tier, hot_arrays, hot_offsets, hot_sizes = hs.build_tier(
        arrays, index.list_offsets, sizes, hot, chunk_rows,
        pad_tail=scan_window(cold_lmax), fills={"ids": -1})
    # chunk-local labels (build_tier copied GLOBAL list ids' rows; remap
    # each chunk's label rows to chunk-local slots for the XLA fallback)
    cent = np.asarray(index.centers_rot, np.float32)
    for ci, ch in enumerate(tier.chunks):
        lab = ch.arrays["labels"]
        ch.arrays["labels"] = np.where(
            tier.chunk_of[np.clip(lab, 0, index.n_lists - 1)] == ci,
            tier.local_of[np.clip(lab, 0, index.n_lists - 1)],
            0).astype(np.int32)
        loc_cent = np.zeros((tier.chunk_lists, cent.shape[1]), np.float32)
        loc_cent[:len(ch.lists)] = cent[ch.lists]
        tier.extras[ci]["centers"] = loc_cent

    index.codes = jnp.asarray(
        hot_arrays["codes"][:, :index.pq_dim].astype(np.uint8))
    index.source_ids = jnp.asarray(hot_arrays["ids"])
    index.list_offsets = hot_offsets
    index.list_sizes_arr = hot_sizes
    index.__dict__.pop("_scan_cache", None)
    index._host_tier = tier


def _cold_chunk_scan_pq(index, dev, probed_local, qc, k, lut_dtype,
                        precision, mask_bits):
    """Scan one streamed cold chunk with the SAME PQ kernel (and LUT
    mode) as the resident lists (ops/ivf_pq_scan.py): chunk-local
    rotated centers + the index's codebook matrix."""
    from ..ops.ivf_pq_scan import _ivf_pq_scan_jit

    cache = getattr(index, "_scan_cache", None)
    cbm = cache["cbm"] if cache is not None else \
        getattr(index, "_cold_cbm", None)
    if cbm is None:
        from ..ops.ivf_pq_scan import make_cb_matrix

        cbm = make_cb_matrix(index.codebooks)
        if not in_jax_trace():
            index._cold_cbm = cbm
    ids = dev["ids"]
    pen_p = None
    if mask_bits is not None:
        pen_p = jnp.where((ids >= 0)
                          & jnp.take(mask_bits, jnp.maximum(ids, 0)),
                          0.0, jnp.inf).astype(jnp.float32)
    q_rot = hdot(qc, index.rotation.T)
    interpret = jax.default_backend() != "tpu"
    mt = index.metric
    vals, rows = _ivf_pq_scan_jit(
        dev["codes"], dev["norms"], pen_p, dev["centers"], cbm,
        jnp.asarray(probed_local), dev["offsets"].astype(jnp.int32),
        dev["sizes"].astype(jnp.int32), q_rot, k,
        index._host_tier.lmax, index.pq_dim, index.pq_book_size,
        "ip" if mt is DistanceType.InnerProduct else "l2",
        _lut_mode(lut_dtype), interpret, precision)
    out_i = jnp.where(rows >= 0, jnp.take(ids, jnp.maximum(rows, 0)), -1)
    return vals, out_i


def _cold_chunk_xla_pq(index, dev, probed_local, qc, k, mask_bits):
    """Guarded fallback: exact rescore of the streamed chunk's candidate
    rows via decode + GEMM in rotated space — correct, not
    arithmetic-identical to the kernel's LUT path."""
    tier = index._host_tier
    n_probes = probed_local.shape[1]
    offs = dev["offsets"].astype(jnp.int32)
    szs = dev["sizes"].astype(jnp.int32)
    max_rows = tier.lmax * min(n_probes, offs.shape[0])
    rows, valid, _ = _candidate_rows(jnp.asarray(probed_local), offs, szs,
                                     max_rows)
    codes = dev["codes"][rows][..., :index.pq_dim].astype(jnp.int32)
    decoded = index.codebooks[
        jnp.arange(index.pq_dim)[None, None, :], codes]   # (m,S,s,len)
    y = (dev["centers"][dev["labels"][rows]]
         + decoded.reshape(codes.shape[0], codes.shape[1], -1))
    q_rot = hdot(qc, index.rotation.T)
    ip = jnp.einsum("msd,md->ms", y, q_rot, precision="highest")
    mt = index.metric
    if mt is DistanceType.InnerProduct:
        dist = -ip
    else:
        q2 = jnp.sum(q_rot * q_rot, axis=1, keepdims=True)
        dist = jnp.maximum(q2 + dev["norms"][rows] - 2.0 * ip, 0.0)
    ids = dev["ids"][rows]
    valid = valid & (ids >= 0)
    if mask_bits is not None:
        valid = valid & jnp.take(mask_bits, jnp.maximum(ids, 0))
    dist = jnp.where(valid, dist, jnp.inf)
    kk = min(k, max_rows)
    vals, locs = select_k(dist, kk, select_min=True)
    out_i = jnp.where(jnp.isfinite(vals),
                      jnp.take_along_axis(ids, locs, axis=1), -1)
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)),
                       constant_values=jnp.inf)
        out_i = jnp.pad(out_i, ((0, 0), (0, k - kk)), constant_values=-1)
    return vals, out_i


def _search_host_stream(index, tier, q, k, p, filter, query_chunk, algo,
                        precision, res):
    """Resident half through the ordinary PQ engines + probed cold lists
    streamed from the host tier, merged like shard results."""
    from ..ops.ivf_scan import coarse_probe
    from .brute_force import knn_merge_parts

    mt = index.metric
    select_min = mt is not DistanceType.InnerProduct
    n_probes = min(p.n_probes, index.n_lists)
    mask_bits = filter.to_mask() if filter is not None else None
    if query_chunk <= 0:
        per_q = n_probes * index.rot_dim * 4 * 2
        query_chunk = max(1, min(q.shape[0],
                                 workspace_chunk_bytes(res) // max(per_q, 1)))

    def _post(vals):
        if mt is DistanceType.L2SqrtExpanded:
            return jnp.sqrt(jnp.maximum(vals, 0.0))
        if mt is DistanceType.InnerProduct:
            return jnp.where(jnp.isfinite(vals), -vals, -jnp.inf)
        return vals

    def one(qc, _s0):
        bad = jnp.inf if select_min else -jnp.inf
        if index.size > 0:
            _hot_local.skip = True
            try:
                hot_d, hot_i = search(index, qc, k, p, filter, 0, algo,
                                      precision)
            finally:
                _hot_local.skip = False
        else:
            hot_d = jnp.full((qc.shape[0], k), bad, jnp.float32)
            hot_i = jnp.full((qc.shape[0], k), -1, jnp.int32)
        # duplicate of the hot half's in-executable coarse probe — see
        # ivf_flat._search_host_stream: one small GEMM buys unchanged
        # resident executables
        q_rot = hdot(qc, index.rotation.T)
        probed = np.asarray(coarse_probe(
            q_rot, index.centers_rot, n_probes,
            metric="ip" if mt is DistanceType.InnerProduct else "l2",
            precision=precision))

        def run(ci, dev, probed_local):
            return guarded_call(
                "ivf.host_stream",
                lambda: _cold_chunk_scan_pq(index, dev, probed_local, qc,
                                            k, p.lut_dtype, precision,
                                            mask_bits),
                lambda: _cold_chunk_xla_pq(index, dev, probed_local, qc,
                                           k, mask_bits))

        cold = tier.stream(probed, run)
        if not cold:
            return hot_d, hot_i
        parts_d = [hot_d] + [_post(cd) for cd, _ in cold]
        parts_i = [hot_i] + [ci_ for _, ci_ in cold]
        return knn_merge_parts(jnp.stack(parts_d), jnp.stack(parts_i),
                               select_min)

    return run_query_chunks(one, q, query_chunk, res)


def reconstruct(index: Index, row_ids) -> jax.Array:
    """Decode rows back to (approximate) input-space vectors
    (ivf_pq helpers reconstruct_list_data, detail/ivf_pq_build.cuh)."""
    row_ids = jnp.asarray(row_ids, jnp.int32)
    # physical row → list id via *capacity* spans (slack-aware)
    labels = jnp.asarray(
        np.repeat(np.arange(index.n_lists),
                  np.diff(index.list_offsets)))[row_ids]
    codes = index.codes[row_ids].astype(jnp.int32)      # (r, pq_dim)
    if index.codebook_kind is CodebookGen.PER_CLUSTER:
        books = index.codebooks[labels]                 # (r, book, pq_len)
        decoded = jnp.take_along_axis(
            books, codes[:, :, None], axis=1)           # (r, pq_dim, pq_len)
    else:
        decoded = index.codebooks[
            jnp.arange(index.pq_dim)[None, :], codes]   # (r, pq_dim, pq_len)
    y_rot = index.centers_rot[labels] + decoded.reshape(len(row_ids), -1)
    return y_rot @ index.rotation                       # back-project


def pack_codes(codes: np.ndarray, pq_bits: int) -> np.ndarray:
    """Bit-pack (n, pq_dim) byte codes → (n, ceil(pq_dim*bits/8)) for
    storage (analog of ivf_pq_codepacking.cuh)."""
    codes = np.asarray(codes, np.uint8)
    n, pq_dim = codes.shape
    bits = np.unpackbits(codes[:, :, None], axis=2, count=8)[:, :, 8 - pq_bits:]
    flat = bits.reshape(n, pq_dim * pq_bits)
    out_bytes = cdiv(pq_dim * pq_bits, 8) * 8
    flat = np.pad(flat, ((0, 0), (0, out_bytes - flat.shape[1])))
    return np.packbits(flat, axis=1)


def unpack_codes(packed: np.ndarray, pq_dim: int, pq_bits: int) -> np.ndarray:
    """Inverse of :func:`pack_codes`."""
    packed = np.asarray(packed, np.uint8)
    n = packed.shape[0]
    flat = np.unpackbits(packed, axis=1)[:, : pq_dim * pq_bits]
    bits = flat.reshape(n, pq_dim, pq_bits)
    weights = (1 << np.arange(pq_bits - 1, -1, -1)).astype(np.uint32)
    return (bits * weights).sum(axis=2).astype(np.uint8)


def save(index: Index, path) -> None:
    """Serialize (analog of detail/ivf_pq_serialize.cuh). Capacity slack is
    stripped: files hold densely-packed valid rows only. Host-streamed
    indexes refuse to serialize (the device arrays hold only the hot
    lists — a silent save would drop every cold row); save before
    :func:`prepare_host_stream`."""
    from ._list_layout import gather_dense

    expects(getattr(index, "_host_tier", None) is None,
            "cannot save a host-streamed index (cold lists live in the "
            "host tier, not the device arrays); save before "
            "prepare_host_stream and re-prepare after load")

    sizes = index.list_sizes
    if index.list_sizes_arr is not None:
        (codes, ids), _ = gather_dense(
            (index.codes, index.source_ids), index.list_offsets, sizes)
    else:
        codes, ids = index.codes, index.source_ids
    dense_offsets = np.zeros(index.n_lists + 1, np.int64)
    np.cumsum(sizes, out=dense_offsets[1:])
    save_arrays(
        path, "ivf_pq", _SERIAL_VERSION,
        {"metric": index.metric.value, "pq_bits": index.pq_bits,
         "codebook_kind": index.codebook_kind.value,
         "pq_dim": index.pq_dim},
        {
            "codes": pack_codes(np.asarray(codes), index.pq_bits),
            "source_ids": ids,
            "centers_rot": index.centers_rot,
            "codebooks": index.codebooks,
            "rotation": index.rotation,
            "list_offsets": dense_offsets,
        })


def load(path) -> Index:
    _, version, meta, arrs = load_arrays(path, "ivf_pq")
    expects(version == _SERIAL_VERSION, "unsupported version %d", version)
    codes = unpack_codes(arrs["codes"], meta["pq_dim"], meta["pq_bits"])
    offsets = np.asarray(arrs["list_offsets"], np.int64)
    return Index(
        jnp.asarray(codes), jnp.asarray(arrs["source_ids"]),
        jnp.asarray(arrs["centers_rot"]), jnp.asarray(arrs["codebooks"]),
        jnp.asarray(arrs["rotation"]), offsets,
        DistanceType(meta["metric"]), meta["pq_bits"],
        CodebookGen(meta["codebook_kind"]),
        list_sizes_arr=np.diff(offsets))


def health(index: Index, sample: int = 256) -> dict:
    """Index health report (docs/observability.md "Quality"): list-size
    skew, PQ geometry, and sampled **codeword utilization** — the
    PQ-specific quality signal available without the f32 originals: a
    subspace using a small fraction of its 2^bits codewords has
    collapsed codebook training (all residuals near one centroid), which
    caps the resolution — and therefore the recall — of every list scan.
    """
    from ._list_layout import list_skew
    from .brute_force import health_sample_rows

    report = {
        "family": "ivf_pq", "n": int(index.size), "dim": int(index.dim),
        "metric": index.metric.name,
        "lists": list_skew(index.list_sizes),
        "pq": {"pq_dim": int(index.pq_dim), "pq_bits": int(index.pq_bits),
               "book_size": int(index.pq_book_size),
               "rot_dim": int(index.rot_dim),
               "codebook_kind": index.codebook_kind.name,
               "compression": round(
                   index.dim * 4.0 / max(index.pq_dim, 1), 1)},
    }
    cap = int(index.codes.shape[0])
    if cap:
        rows = health_sample_rows(cap, sample)
        sid = np.asarray(index.source_ids[rows])
        codes = np.asarray(index.codes[rows])[sid >= 0]
        if codes.size:
            used = np.array([np.unique(codes[:, s]).size
                             for s in range(codes.shape[1])], np.float64)
            # utilization saturates at the sample size on tiny samples —
            # report the bound so the number stays interpretable
            denom = min(index.pq_book_size, codes.shape[0])
            report["pq"]["codeword_utilization"] = {
                "mean": round(float(used.mean() / denom), 4),
                "min": round(float(used.min() / denom), 4),
                "sampled_rows": int(codes.shape[0])}
    return report


def make_searcher(index: Index, params: SearchParams | None = None, *,
                  degrade=None, **opts):
    """Stable batchable signature for the serving runtime
    (:mod:`raft_tpu.serve`): returns ``fn(queries, k, res=None) ->
    (distances, indices)`` with the probe/LUT policy frozen at closure
    build time, so repeated bucketed-shape calls hit the same cached
    executables. ``opts`` forwards to :func:`search` (``algo``,
    ``filter``, ``precision``, ``query_chunk``, ...). ``degrade``: a
    :class:`~raft_tpu.serve.degrade.BrownoutController` — under brownout
    its current level overrides ``n_probes`` per call
    (docs/robustness.md)."""
    base = params or SearchParams()

    def _fn(queries, k, res=None):
        p = base if degrade is None else degrade.params(base)
        return search(index, queries, k, p, res=res, **opts)

    return _fn
