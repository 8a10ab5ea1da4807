"""CAGRA graph-based ANN: analog of ``raft::neighbors::cagra``.

Reference: raft/neighbors/cagra_types.hpp:66-113,134 (params: intermediate/
graph degree, build_algo IVF_PQ|NN_DESCENT; index = dataset + fixed-degree
graph), detail/cagra/cagra_build.cuh:43-343 (build_knn_graph via ivf_pq
search + refine, then optimize), detail/cagra/graph_core.cuh:128-191
(kern_prune detour counting + reverse-edge merge) and
detail/cagra/search_single_cta_kernel-inl.cuh:51-200 (persistent per-query
loop: pickup parents → fetch neighbors → hashmap dedup → distances →
bitonic merge into itopk).

TPU design differences:

* **Search is one jitted ``lax.while_loop`` over a batched frontier**: all
  queries advance in lockstep; per iteration the top ``search_width``
  unexplored itopk entries are expanded, their graph neighbors deduped
  *against the itopk buffer itself* (a (cand × itopk) equality mask — the
  vectorizable stand-in for the reference's per-CTA visited hashmap),
  scored with one gather+einsum, and bitonic-merged by a single
  ``select_k`` over the concatenated buffer. The three CUDA strategies
  (SINGLE_CTA/MULTI_CTA/MULTI_KERNEL, factory.cuh:31-91) collapse into
  this one program — XLA handles the batch/occupancy tradeoffs.
* **Graph optimize** keeps the reference's detour-count rule but computes
  all nodes' neighbor-pair adjacency in batched searchsorted membership
  probes instead of a per-edge kernel; the reverse-edge grouping runs on
  device too (stable sort by target + segment positions — see
  ``_rev_group_jit``).
* Graph build has two TPU-native fast paths (see ``build_knn_graph``):
  an *exact* all-pairs sweep through the streaming fused
  distance+select kernel (corpus HBM-resident in storage width, no
  per-batch full-width top_k) up to ``RAFT_TPU_CAGRA_BRUTE_N`` rows,
  and batched NN-descent (``ops/nn_descent.py``, O(rounds·n·C·d))
  above it. The reference's IVF-PQ+refine candidate pass remains as
  the structured fallback, and ``IndexParams.build_algo`` NN_DESCENT
  routes through the batched builder.
"""
from __future__ import annotations

import dataclasses
import enum
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import deadline, interop, tracing
from ..core.bitset import Bitset
from ..core.errors import expects
from ..core.serialize import load_arrays, save_arrays
from ..distance.distance_types import DistanceType, canonical_metric
from ..matrix.select_k import select_k
from ..ops.guarded import guarded_call
from ..utils import round_up_to, run_query_chunks
from . import ivf_pq as ivf_pq_mod
from . import refine as refine_mod

__all__ = ["BuildAlgo", "IndexParams", "SearchParams", "Index", "build",
           "build_knn_graph", "optimize", "search", "save", "load",
           "prepare_search", "prepare_traversal", "tune_search",
           "make_searcher", "health", "ENGINES"]

_SERIAL_VERSION = 2   # v2 adds optional seed_nodes

# the concrete traversal engines (SearchParams.engine / search(engine=)
# besides "auto"). THE registry the engine drift guard reads
# (tests/test_quality.py): every member must appear in the tune_search
# race and be warmable through serve/warmup.py's ladder, so a new
# engine cannot ship without a measured race lane and a pre-compile
# path — a first-request compile stall is exactly the regression the
# serving warmup exists to prevent.
ENGINES = ("gather", "edge", "fused")


class BuildAlgo(enum.Enum):
    """cagra_types.hpp graph_build_algo."""

    IVF_PQ = 0
    NN_DESCENT = 1


@dataclasses.dataclass
class IndexParams:
    """Mirror of cagra::index_params (cagra_types.hpp:66)."""

    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    build_algo: BuildAlgo = BuildAlgo.IVF_PQ
    metric: DistanceType | str = DistanceType.L2Expanded
    nn_descent_niter: int = 20
    seed: int = 0
    # candidate pass for the BuildAlgo.IVF_PQ route: "auto" substitutes
    # the exact fused all-pairs sweep below the brute cutover and
    # batched NN-descent above it (see build_knn_graph);
    # "brute"/"nn_descent"/"ivf_pq" force a specific pass
    knn_graph_algo: str = "auto"
    # shared traversal seed set: nearest dataset rows to this many
    # balanced-kmeans centroids, stored in the index. All queries score
    # the same rows, so seeding is one dense MXU GEMM instead of a
    # per-query random gather — starting the walk near a covering set
    # cuts hops at equal recall (measured at 100k×128: 39.9k QPS @ 0.975
    # in 6 hops vs 31.8k @ 0.948 in 10 hops random-seeded). -1 → auto
    # (max(128, min(2048, n // 64))); 0 disables (reference behavior:
    # random-only seeding, search_plan.cuh rand_xor_mask).
    seed_nodes: int = -1


@dataclasses.dataclass
class SearchParams:
    """Mirror of cagra::search_params (cagra_types.hpp:113).

    ``candidate_dtype``: dtype for candidate scoring during traversal —
    bf16 halves the gather bandwidth of the hot loop, int8 (per-row
    scaled) quarters it (the returned top-k is always re-scored exactly
    in f32); "float32" scores exactly throughout. ``seed``: RNG seed for
    the random seed-node init (rand_xor_mask's role, search_plan.cuh)."""

    itopk_size: int = 64
    search_width: int = 1          # parents expanded per iteration
    max_iterations: int = 0        # 0 → auto
    min_iterations: int = 0        # traverse at least this many hops
    num_random_samplings: int = 1  # random seed nodes multiplier
    candidate_dtype: str = "bfloat16"   # "bfloat16" | "float32" | "int8"
    seed: int = 0x5EED
    # the reference's SINGLE_CTA/MULTI_CTA/MULTI_KERNEL strategies
    # (factory.cuh:31-91) collapse into one batched-frontier program on
    # TPU; "auto"/"single_cta"/"multi_cta"/"multi_kernel" are all accepted
    # and run the same plan (XLA owns the occupancy tradeoffs)
    algo: str = "auto"
    # hop engine: "edge" streams each parent's contiguous neighbor tile
    # from the edge-resident candidate store (prepare_traversal) through
    # the Pallas frontier-expansion kernel; "fused" folds the WHOLE hop
    # loop into one megakernel launch (ops/cagra_fused.py — frontier in
    # VMEM, bit-identical to "edge", kills the per-hop dispatch floor);
    # "gather" is the composed-XLA random-row-gather path; "auto"
    # consults the ops.autotune race cache (tune_search populates it)
    # and otherwise picks "edge" only when a store is already attached
    # on TPU — a read-only query never grows the index's HBM footprint
    # as a side effect, and the megakernel only dispatches off a
    # measured race verdict
    engine: str = "auto"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """Dataset + fixed-degree neighbor graph (cagra_types.hpp:134).

    ``seed_nodes``: optional (s,) *sorted unique* row ids of a shared
    covering seed set (see IndexParams.seed_nodes; the search-time
    collision probe relies on sortedness); None → random-only seeding."""

    dataset: jax.Array        # (n, dim) float32
    graph: jax.Array          # (n, degree) int32
    metric: DistanceType
    seed_nodes: Optional[jax.Array] = None   # (s,) int32

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]

    def tree_flatten(self):
        # traversal-dtype caches travel WITH the index so jitted
        # functions can take it as an ARGUMENT (closure-baking the
        # dataset + bf16 copy as HLO constants would make every compile
        # request index-sized); the edge-resident candidate
        # store (prepare_traversal) rides the same way, its static meta
        # tuple in aux_data so executables re-key on geometry changes
        es = getattr(self, "_edge_store", None)
        cbs = es[4] if es is not None and len(es) > 4 else None
        leaves = (self.dataset, self.graph, self.seed_nodes,
                  getattr(self, "_score_bf16", None),
                  getattr(self, "_score_i8", None),
                  es[1] if es is not None else None,
                  es[2] if es is not None else None,
                  es[3] if es is not None else None,
                  cbs[0] if cbs is not None else None,
                  cbs[1] if cbs is not None else None)
        return leaves, (self.metric, es[0] if es is not None else None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        out = cls(leaves[0], leaves[1], aux[0], leaves[2])
        if leaves[3] is not None:
            out._score_bf16 = leaves[3]
        if leaves[4] is not None:
            out._score_i8 = leaves[4]
        if len(aux) > 1 and aux[1] is not None and leaves[5] is not None:
            cbs = (leaves[8], leaves[9]) if leaves[8] is not None else None
            out._edge_store = (aux[1], leaves[5], leaves[6], leaves[7],
                               cbs)
        return out


def _brute_n_threshold() -> int:
    """The exact-pass crossover row count — ONE reader, because the auto
    resolver and the guarded nn_descent fallback must agree on it."""
    import os

    return int(os.environ.get("RAFT_TPU_CAGRA_BRUTE_N", "200000"))


def _graph_algo_key(n: int, dim: int, k: int, mt) -> str:
    """Autotune bucket for the graph-builder race: the bench graph-build
    lane records the measured winner per shape class and ``algo="auto"``
    consults it before falling back to the cost-model threshold. The
    metric rides as a categorical tag — crossovers are measured per
    distance family, and a verdict raced under L2 must not steer an
    InnerProduct (or descent-incapable) build in the same shape class."""
    from ..ops import autotune

    return autotune.shape_bucket("cagra_knn_graph", m=mt.name, n=n,
                                 d=dim, k=k)


def _resolve_graph_algo(n: int, dim: int, k: int, algo: str, mt) -> str:
    """Concrete builder for ``algo="auto"``: a recorded race verdict for
    this shape bucket wins; otherwise the cost-model threshold.

    Threshold math (re-derive the measured crossover with
    ``scratch/exp_build_crossover.py``; anchors are BENCH_r05's
    roofline): the n²·d GEMM is never the wall — 2n²·d at 500k×128 is
    64 TFLOP ≈ 0.4 s at the measured 154.7 TF/s. The exact pass's real
    cost is **O(n²) corpus re-streaming + select**: every 16k-query
    chunk re-reads the n·d·4-byte corpus, so the HBM floor alone is
    ~0.5 s at 100k, ~12 s at 500k, ~50 s at 1M (639.8 GB/s streamed),
    and the in-kernel select rides on top (the k=96 build shape merges
    more than the k≤10 search shapes PR 3 measured near GEMM rate).
    NN-descent is ~linear: rounds·n·C candidate-row gathers (C ≈ 800 at
    the default knobs — tens of seconds at 500k, early-stop usually
    halves the round budget). The crossover therefore sits in the
    low-hundreds-of-k band; 200k is the conservative default — below it
    the exact graph costs ≤ a few seconds more and is better
    conditioned. (The old 1.2M default compared the exact pass against
    the far slower quarter-corpus IVF-PQ probe sweep that NN-descent
    replaced — that crossover died with the sweep.)"""
    if algo != "auto":
        return algo
    from ..ops import autotune
    from ..ops import nn_descent as nnd

    hit = autotune.lookup(_graph_algo_key(n, dim, k, mt))
    if hit in ("brute", "ivf_pq", "nn_descent") and (
            hit != "nn_descent" or nnd.supports(mt)):
        return hit
    if n <= _brute_n_threshold():
        return "brute"
    # past the exact pass's budget: the batched descent when it can
    # serve the metric, else the reference's ivf_pq candidate pass
    # (auto must never resolve to a builder that would reject the
    # request — that would poison the cagra.nn_descent guard site)
    return "nn_descent" if nnd.supports(mt) else "ivf_pq"


@tracing.annotate("raft_tpu::cagra::build_knn_graph")
def build_knn_graph(dataset, k: int, metric=DistanceType.L2Expanded,
                    seed: int = 0, batch: int = 32768,
                    algo: str = "auto", engine: str = "auto",
                    nnd_rounds: int = 0, init_graph=None,
                    progress=None, info=None) -> np.ndarray:
    """All-points kNN graph (cagra_build.cuh:43 build_knn_graph).

    ``algo``:

    * ``"brute"`` — exact all-pairs kNN, one query batch at a time.
      ``engine="fused"`` streams each batch through the fused
      distance+select kernel (``brute_force.prepare_fused`` + the
      ``pallas`` engine): the corpus stays HBM-resident in storage
      width and the in-kernel two-level select replaces the per-batch
      full-width top_k that dominated the exact build wall (366.8 s at
      500k×128, BENCH_r05). ``engine="matmul"`` is the GEMM + block-min
      top_k reference engine; the two produce BIT-IDENTICAL graphs
      (the fused kernel retires ties in lax.top_k order —
      tests/test_graph_build.py asserts it), so ``"auto"`` freely picks
      fused on TPU for fused-capable metrics and matmul elsewhere.
    * ``"nn_descent"`` — batched neighbor-of-neighbor descent
      (``ops/nn_descent.py``): O(rounds·n·C·d) instead of O(n²·d), the
      builder past the exact pass's budget. Approximate by design —
      graph-edge recall ~0.9+ at the bench operating points, absorbed
      by optimize()'s pruning and the search-time exact re-rank, the
      same tolerance the reference's IVF-PQ candidate pass leans on.
      Guarded: a builder failure falls back to the exact/ivf_pq path
      with the demotion recorded (``cagra.nn_descent`` site).
    * ``"ivf_pq"`` — the reference's own path: IVF-PQ search for 2k
      candidates, exact refine to k (gpu_top_k = k * refine_rate).
      Kept for reference parity and as nn_descent's large-n fallback.
    * ``"auto"`` — a measured race verdict for this shape bucket when
      one is recorded (the bench graph-build lane records them), else
      brute below ``RAFT_TPU_CAGRA_BRUTE_N`` rows (default 200k — see
      :func:`_resolve_graph_algo` for the crossover math), nn_descent
      above.

    ``nnd_rounds``/``init_graph``: NN-descent round cap (0 → knob
    default) and optional (n, k0) warm-start candidate lists (e.g. an
    IVF-PQ candidate pass). ``progress``: optional 3-arg hook — the
    batch loops call ``progress(done_rows, total_rows, elapsed_s)``;
    NN-descent reports rounds in the same shape,
    ``progress(round, rounds, elapsed_s)`` (one hook serves every
    builder, so ``algo="auto"`` and the guarded fallback can hand it to
    whichever path actually runs). ``info``: optional dict the call
    fills with the builder that actually ran (``info["algo"]``, plus
    ``info["engine"]`` on the brute path) — under ``algo="auto"`` or
    the ``cagra.nn_descent`` guard the resolved/demoted choice is
    otherwise invisible to the caller.

    Returns (n, k) int32 neighbor ids (self-edges removed).
    """
    import os

    from . import brute_force as bf_mod

    dataset = np.asarray(dataset, np.float32)
    n, dim = dataset.shape
    mt = canonical_metric(metric)
    expects(algo in ("auto", "brute", "ivf_pq", "nn_descent"),
            "unknown knn_graph algo %r", algo)
    expects(engine in ("auto", "fused", "matmul"),
            "unknown brute graph engine %r", engine)
    algo = _resolve_graph_algo(n, dim, k, algo, mt)

    if algo == "nn_descent":
        from ..ops import nn_descent as nnd

        # an unservable metric is an invalid REQUEST, not a builder
        # failure: raise before guarded_call so it can't persist a
        # demotion of the site (auto never routes here — see
        # _resolve_graph_algo — so this only fires on explicit asks)
        expects(nnd.supports(mt),
                "nn_descent supports L2/IP metrics, got %s", mt.name)

        # adapt the uniform 3-arg hook to build_graph's 4-arg per-round
        # call (the update rate stays a direct-API detail)
        nnd_progress = (None if progress is None else
                        lambda r, total, rate, s: progress(r, total, s))

        def _nnd():
            g = nnd.build_graph(dataset, k, metric=mt,
                                rounds=nnd_rounds, seed=seed,
                                init_graph=init_graph,
                                progress=nnd_progress)
            if info is not None:
                info["algo"] = "nn_descent"
            return g

        def _exact():
            return build_knn_graph(
                dataset, k, mt, seed, batch,
                algo="brute" if n <= _brute_n_threshold() else "ivf_pq",
                engine=engine, progress=progress, info=info)

        # a builder failure (compile OOM on an unrehearsed shape, device
        # loss mid-round) costs a demotion log line and a slower exact/
        # ivf_pq build, never the index
        return guarded_call("cagra.nn_descent", _nnd, _exact)

    if info is not None:
        info["algo"] = algo

    graph = np.zeros((n, k), np.int32)
    drop_self = jax.jit(partial(_drop_self_pad, k=k, n=n))
    batch = min(batch, n)

    if algo == "brute":
        if engine == "auto":
            # fused when the streaming kernel can serve the metric on
            # real hardware (interpret mode exists as the parity-test
            # twin, not a build engine); matmul elsewhere — both
            # produce the same graph bit for bit
            engine = ("fused" if jax.default_backend() == "tpu"
                      and bf_mod.fused_capable(mt) else "matmul")
        if info is not None:
            info["engine"] = engine
        # at memory scale, bigger distance-block chunks amortize the
        # matmul engine's per-chunk top_k fixed cost; respect an
        # explicit user workspace choice (the fused engine has no
        # distance block — its VMEM working set is per-tile)
        ws = (4096 if n > 400_000 and engine == "matmul"
              and "RAFT_TPU_MATMUL_WORKSPACE_MB" not in os.environ
              else None)
        part_cap = int(os.environ.get("RAFT_TPU_CAGRA_BRUTE_PART_N",
                                      "500000"))
        if n <= part_cap:
            index = bf_mod.build(dataset, mt)
            _brute_graph_loop(bf_mod, dataset, index, graph, drop_self,
                              k, n, batch, ws, engine, progress)
            return graph
        _parted_brute_graph(bf_mod, dataset, graph, drop_self, k, n, dim,
                            mt, batch, ws, part_cap, engine, progress)
        return graph

    n_lists = max(16, min(1024, int(np.sqrt(n) * 2)))
    # pq_bits=4 at pq_dim=dim: same code bits/row as pq_dim=dim/2 @ 8-bit
    # but an 8x narrower one-hot decode; int8 LUT doubles the MXU decode
    # rate (the round-4 scan rework — candidate quality is recovered by
    # the exact refine below)
    pq_dim = min(dim, 4 * ivf_pq_mod._default_pq_dim(dim))
    index = ivf_pq_mod.build(dataset, ivf_pq_mod.IndexParams(
        n_lists=n_lists, pq_dim=pq_dim, pq_bits=4, metric=mt, seed=seed))
    # candidate recall, not search recall, is the bar here (refine +
    # optimize()'s detour pruning tolerate imperfect candidates):
    # a quarter-of-corpus probe sweep would be minutes per batch at 500k
    n_probes = max(16, min(64, n_lists // 8))
    gpu_k = min(n, k * 2 + 1)  # refine_rate=2 + room for the self match
    dataset_bf16 = jnp.asarray(dataset, jnp.bfloat16)  # half the gather
    sp = ivf_pq_mod.SearchParams(n_probes, lut_dtype="int8")

    def step(idx_rows):
        qb = dataset[idx_rows]
        _, cand = ivf_pq_mod.search(index, qb, gpu_k, sp)
        _, ref = refine_mod.refine(dataset_bf16, qb, cand, k + 1, mt)
        return drop_self(ref, jnp.asarray(idx_rows))

    _graph_batch_loop(graph, batch, step, "cagra.knn_graph[ivf_pq]",
                      progress)
    return graph


def _graph_batch_loop(graph, batch, step, what, progress=None):
    """The ONE batch loop every graph-construction sweep shares (brute
    single-index, brute parted, ivf_pq candidate pass): tail batches
    wrap back to the full batch shape so every iteration hits the same
    compiled executable — a 1M-row compile costs seconds — and a
    progress hook breaks the minutes-long silence between build
    log lines (default: one log line at most every 30 s).
    ``step(idx_rows) -> (batch, k) ids``; the loop owns the tail slice
    and the host write-back."""
    import time as _time

    from ..core import logging as rlog

    n = graph.shape[0]
    t0 = last = _time.perf_counter()
    for b0 in range(0, n, batch):
        hi = min(b0 + batch, n)
        idx_rows = (np.arange(b0, b0 + batch) % n).astype(np.int32)
        graph[b0:hi] = np.asarray(step(idx_rows))[: hi - b0]
        now = _time.perf_counter()
        if progress is not None:
            progress(hi, n, now - t0)
        elif now - last > 30.0 and hi < n:
            rlog.log_info("%s: %d/%d rows (%.0fs)", what, hi, n, now - t0)
            last = now


def _parted_brute_graph(bf_mod, dataset, graph, drop_self, k, n, dim, mt,
                        batch, workspace_mb, part_cap, engine,
                        progress=None):
    """Exact kNN-graph sweep for corpora past ``part_cap`` rows
    (``RAFT_TPU_CAGRA_BRUTE_PART_N``): the corpus splits into equal
    ≤``part_cap`` parts — ONE shared search executable, padding rows
    masked by ``valid_rows``, per-part top-(k+1) merged exactly
    (knn_merge_parts) before self-edge removal. Shares the fused/matmul
    engine choice and the common batch loop with the single-index
    path."""
    from ..distance.distance_types import is_min_close

    # split against the 128-aligned cap, so the later round-up to the
    # 128-row tile can never push a part past part_cap (the compile-cap
    # this path exists to respect): n_parts = ceil(n / cap_al) guarantees
    # ceil(n / n_parts) <= cap_al, and rounding a value <= cap_al up to
    # 128 stays <= cap_al
    cap_al = max(128, (part_cap // 128) * 128)
    n_parts = -(-n // cap_al)
    part_n = ((-(-n // n_parts) + 127) // 128) * 128

    def part_slice(i):
        """Equal-shape part i, zero-padding only the tail slice (a full
        padded corpus copy would double host memory at the 1M scale
        this path exists for)."""
        sl = dataset[i * part_n:(i + 1) * part_n]
        if len(sl) < part_n:
            sl = np.concatenate(
                [sl, np.zeros((part_n - len(sl), dim), np.float32)])
        return sl

    indexes = [bf_mod.build(part_slice(i), mt) for i in range(n_parts)]
    valid = [max(0, min(part_n, n - i * part_n)) for i in range(n_parts)]
    kq = min(n, k + 1)
    if engine == "fused":
        # eager alignment BEFORE the jit trace (caches are never written
        # under a trace); each part's corpus then stays HBM-resident in
        # tile-aligned form across the whole sweep
        for ix in indexes:
            bf_mod.prepare_fused(ix)
        sfn = jax.jit(lambda q, idx, v: bf_mod.search(
            idx, q, kq, algo="pallas", valid_rows=v))
    else:
        sfn = jax.jit(lambda q, idx, v: bf_mod.search(
            idx, q, kq, algo="matmul", valid_rows=v,
            workspace_mb=workspace_mb))
    select_min = is_min_close(mt)

    def step(idx_rows):
        qb = jnp.asarray(dataset[idx_rows])
        ds_, is_ = [], []
        for i, (ix, v) in enumerate(zip(indexes, valid)):
            dd, ii = sfn(qb, ix, jnp.int32(v))
            ds_.append(dd)
            is_.append(jnp.where(ii >= 0, ii + i * part_n, -1))
        _, merged = bf_mod.knn_merge_parts(jnp.stack(ds_), jnp.stack(is_),
                                           select_min)
        return drop_self(merged, jnp.asarray(idx_rows))

    _graph_batch_loop(graph, batch, step,
                      f"cagra.knn_graph[brute.{engine}.parted]", progress)


def _brute_graph_loop(bf_mod, dataset, index, graph, drop_self, k, n,
                      batch, workspace_mb, engine, progress=None):
    """Exact-graph batch loop over one index: per query batch, either
    the streaming fused kernel (corpus HBM-resident in storage width,
    in-kernel two-level select — the per-batch full-width top_k wall is
    gone) or one MXU GEMM + block-min top_k."""
    kq = min(n, k + 1)
    if engine == "fused":
        # one eager alignment; every batch then reads the resident
        # corpus instead of re-padding per dispatch. The search itself
        # is guarded ("brute_force.fused" site): a kernel failure
        # demotes the sweep to the bit-identical GEMM engine mid-build.
        bf_mod.prepare_fused(index)

        def step(idx_rows):
            qb = jnp.asarray(dataset[idx_rows])
            _, cand = bf_mod.search(index, qb, kq, algo="pallas")
            return drop_self(cand, jnp.asarray(idx_rows))
    else:
        def step(idx_rows):
            qb = jnp.asarray(dataset[idx_rows])
            _, cand = bf_mod.search(index, qb, kq, algo="matmul",
                                    workspace_mb=workspace_mb)
            return drop_self(cand, jnp.asarray(idx_rows))

    _graph_batch_loop(graph, batch, step,
                      f"cagra.knn_graph[brute.{engine}]", progress)


def _drop_self_pad(ref, rows, *, k: int, n: int):
    """Per row: first k entries of ``ref`` that are valid and not the row
    itself, cycling valid neighbors to fill a shortfall ((n+1)%n fallback
    when empty). Vectorized replacement for the old per-row host loop."""
    w = ref.shape[1]
    valid = (ref >= 0) & (ref != rows[:, None])
    pos = jnp.arange(w, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(valid, pos, w + pos), axis=1)
    ref_s = jnp.take_along_axis(ref, order, axis=1)
    ok_s = jnp.take_along_axis(valid, order, axis=1)
    n_ok = jnp.sum(ok_s, axis=1, keepdims=True)             # (b, 1)
    idx = jnp.where(n_ok > 0, pos[None, :k] % jnp.maximum(n_ok, 1), 0)
    out = jnp.take_along_axis(ref_s, idx, axis=1)
    return jnp.where(n_ok > 0, out, (rows[:, None] + 1) % n).astype(jnp.int32)


def _detour_counts(graph_j, batch_nodes):
    """(b, d0) detour counts for a batch of nodes (kern_prune analog).

    Edge (i, N_i[b]) is detourable through N_i[a] (a < b, i.e. a closer
    neighbor) if the graph has the edge N_i[a] → N_i[b]. Membership is an
    all-compare with the equality reduction over the adjacency minor axis
    — O(d0³) VPU compares per node, but every op is a dense vector op
    XLA fuses into the reduction (order-insensitive: no pre-sorted
    adjacency needed). The O(d0² log d0) searchsorted alternative is
    asymptotically better and catastrophically slower here: its
    per-bisection-step ``take_along_axis`` lowers to per-ELEMENT gathers
    (~470M scalar loads per batch, measured 12.3 s/batch vs <0.5 s for
    this form — full optimize 277.8 s → 37.3 s at 100k).
    """
    nbrs = graph_j[batch_nodes]                       # (B, d0)
    b, d0 = nbrs.shape
    nbr_rows = graph_j[nbrs]                          # (B, d0, d0)
    # adj[x, a, t] = any_c nbr_rows[x, a, c] == nbrs[x, t]; the 4-D
    # broadcast never materializes — XLA fuses compare into the c-reduce
    adj = jnp.any(nbr_rows[:, :, :, None] == nbrs[:, None, None, :],
                  axis=2)                             # (B, a, t)
    tri = jnp.tril(jnp.ones((d0, d0), bool), k=-1).T  # a < t strictly
    return jnp.sum(adj & tri[None], axis=1)           # (B, d0)


@partial(jax.jit, static_argnames=("tail_w",))
def _merge_tail_batch(kept, cand, rows, tail_w: int):
    """Per-row: first ``tail_w`` candidates from ``cand`` (in order) that
    are valid, not self, and not already in ``kept`` or earlier in ``cand``;
    shortfall filled with the last kept edge. All batched tensor ops — the
    vectorized form of the reference's per-node rev/fwd merge loop."""
    b, w = cand.shape
    dup_kept = jnp.any(cand[:, :, None] == kept[:, None, :], axis=2)
    dup_prior = jnp.tril(cand[:, :, None] == cand[:, None, :], k=-1).any(axis=2)
    valid = (cand >= 0) & (cand != rows[:, None]) & ~dup_kept & ~dup_prior
    pos = jnp.arange(w, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(valid, pos, w + pos), axis=1)[:, :tail_w]
    tail = jnp.take_along_axis(cand, order, axis=1)
    ok = jnp.take_along_axis(valid, order, axis=1)
    return jnp.where(ok, tail, kept[:, -1:])


@partial(jax.jit, static_argnames=("graph_degree",))
def _prune_batch(graph_j, nodes, graph_degree: int):
    """One node-batch of detour counting + rank-composite prune
    (kern_prune analog): count, argsort the (detours, rank) key, keep
    the best ``graph_degree`` — all on device, only the (B, degree)
    result leaves the chip."""
    d0 = graph_j.shape[1]
    detours = _detour_counts(graph_j, nodes)
    # composite key (detours ≤ d0 ≤ 512 keeps it well inside int32)
    key = detours * d0 + jnp.arange(d0, dtype=jnp.int32)[None, :]
    order = jnp.argsort(key, axis=1, stable=True)[:, :graph_degree]
    return jnp.take_along_axis(graph_j[nodes], order, axis=1)


@partial(jax.jit, static_argnames=("keep_fwd", "rev_cap"))
def _rev_group_jit(pruned, keep_fwd: int, rev_cap: int):
    """Reverse-edge table (kern_make_rev_graph analog): stable sort by
    target + segment positions, capped at ``rev_cap`` per node."""
    n = pruned.shape[0]
    # column-major flatten: all rank-0 forward edges arrive first, so a
    # capped reverse list keeps edges from the *closest* forward links
    # rather than from low row ids (rank priority of the reference merge)
    tgt = pruned[:, :keep_fwd].T.reshape(-1)
    src = jnp.tile(jnp.arange(n, dtype=jnp.int32), keep_fwd)
    tgt = jnp.where((tgt >= 0) & (tgt < n), tgt, n)   # junk edges → row n
    so = jnp.argsort(tgt, stable=True)
    ts, cs = tgt[so], src[so]
    counts = jnp.bincount(ts, length=n + 1)
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(ts.shape[0], dtype=jnp.int32) - seg_start[ts]
    keep = (pos < rev_cap) & (ts < n)
    rev = jnp.full((n + 1, rev_cap), -1, jnp.int32)
    return rev.at[jnp.where(keep, ts, n),
                  jnp.where(keep, pos.astype(jnp.int32), 0)].set(
        jnp.where(keep, cs, -1))[:n]




def _rev_group_host(pruned: np.ndarray, keep_fwd: int,
                    rev_cap: int) -> np.ndarray:
    """Host mirror of :func:`_rev_group_jit` for node counts where the
    one monolithic device sort is unrehearsed on the chip (a
    32M-element np.argsort is ~2 s on the host)."""
    n = pruned.shape[0]
    tgt = pruned[:, :keep_fwd].T.reshape(-1).astype(np.int64)
    src = np.tile(np.arange(n, dtype=np.int32), keep_fwd)
    tgt = np.where((tgt >= 0) & (tgt < n), tgt, n)
    so = np.argsort(tgt, kind="stable")
    ts, cs = tgt[so], src[so]
    counts = np.bincount(ts, minlength=n + 1)
    seg_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(ts)) - seg_start[ts]
    keep = (pos < rev_cap) & (ts < n)
    rev = np.full((n, rev_cap), -1, np.int32)
    rev[ts[keep], pos[keep].astype(np.int64)] = cs[keep]
    return rev


@tracing.annotate("raft_tpu::cagra::optimize")
def optimize(knn_graph: np.ndarray, graph_degree: int,
             batch: int = 2048) -> np.ndarray:
    """Detour-count prune + reverse-edge merge (graph_core.cuh:128-191).

    Keep the ``graph_degree`` edges with fewest detours (ties → closer
    rank), then replace the tail half with reverse edges where available —
    the reference merges forward and reverse graphs 50/50. All phases
    run on device (kern_prune / kern_make_rev_graph analogs); prune and
    merge advance in constant-shape node batches (wrapped tails, one
    compiled executable each; per-batch dispatch costs only
    milliseconds each).
    """
    knn_graph = np.asarray(knn_graph, np.int32)
    n, d0 = knn_graph.shape
    expects(graph_degree <= d0, "graph_degree %d > intermediate %d",
            graph_degree, d0)
    # bound the live membership working set — the (B, d0, d0) adjacency
    # gather (int32) plus the (B, d0, d0) adj/hit planes; the 4-D
    # broadcast compare itself fuses into its reduction and never
    # materializes (measured: see _detour_counts)
    batch = max(256, min(batch * 8, (1 << 30) // max(d0 * d0 * 16, 1)))
    batch = min(batch, n)
    keep_fwd = graph_degree - graph_degree // 2
    tail_w = graph_degree - keep_fwd
    graph_j = jnp.asarray(knn_graph)

    pruned = np.zeros((n, graph_degree), np.int32)
    for b0 in range(0, n, batch):
        hi = min(b0 + batch, n)
        nodes = jnp.asarray(np.arange(b0, b0 + batch) % n)
        pruned[b0:hi] = np.asarray(_prune_batch(
            graph_j, nodes, graph_degree))[: hi - b0]

    pruned_j = jnp.asarray(pruned)
    import os as _os
    rev_jit_edges = int(_os.environ.get("RAFT_TPU_REV_JIT_EDGES",
                                        str(20 << 20)))
    if n * keep_fwd > rev_jit_edges:
        # scale guard (rehearsed to 500k nodes on device): beyond it the
        # stable argsort+scatter over all n*keep_fwd edges runs on host
        rev = jnp.asarray(_rev_group_host(pruned, keep_fwd, graph_degree))
    else:
        rev = _rev_group_jit(pruned_j, keep_fwd, graph_degree)

    # interleave reverse and forward-tail candidates 1:1 (rev first)
    fwd_tail = jnp.full((n, graph_degree), -1, jnp.int32)
    fwd_tail = fwd_tail.at[:, :tail_w].set(pruned_j[:, keep_fwd:])
    cand_j = jnp.stack([rev, fwd_tail], axis=2).reshape(n, 2 * graph_degree)

    out = pruned.copy()
    kept_j = pruned_j[:, :keep_fwd]
    for b0 in range(0, n, batch):
        b1 = min(b0 + batch, n)
        sel = jnp.asarray(np.arange(b0, b0 + batch) % n)
        out[b0:b1, keep_fwd:] = np.asarray(_merge_tail_batch(
            jnp.take(kept_j, sel, axis=0), jnp.take(cand_j, sel, axis=0),
            sel.astype(jnp.int32), tail_w))[: b1 - b0]
    return out


@tracing.annotate("raft_tpu::cagra::build")
def build(dataset, params: IndexParams | None = None) -> Index:
    """kNN graph (IVF-PQ path) → optimize → index (cagra_build.cuh:292)."""
    import time as _time

    from ..core import logging as rlog

    p = params or IndexParams()
    dataset = np.asarray(dataset, np.float32)
    n = len(dataset)
    mt = canonical_metric(p.metric)
    expects(mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                   DistanceType.InnerProduct),
            "cagra supports L2/IP metrics, got %s", mt.name)
    d0 = min(p.intermediate_graph_degree, n - 1)
    degree = min(p.graph_degree, d0)
    t0 = _time.perf_counter()
    ginfo = {}
    if p.build_algo is BuildAlgo.NN_DESCENT:
        # the batched device-resident builder (ops/nn_descent.py) with
        # the guarded exact/ivf_pq fallback; nn_descent_niter caps the
        # rounds (update-rate early stop usually fires first)
        knn = build_knn_graph(dataset, d0, mt, p.seed, algo="nn_descent",
                              nnd_rounds=p.nn_descent_niter, info=ginfo)
    else:
        # nnd_rounds rides along for the knn_graph_algo="nn_descent" and
        # auto-resolved descent routes — the knob must not silently work
        # on the BuildAlgo branch only
        knn = build_knn_graph(dataset, d0, mt, p.seed,
                              algo=p.knn_graph_algo,
                              nnd_rounds=p.nn_descent_niter, info=ginfo)
    # the builder that actually ran — under algo="auto" or a
    # cagra.nn_descent demotion this differs from the requested one, and
    # build_stats is the evidence block perf runs read
    galgo = ginfo.get("algo", p.knn_graph_algo)
    t1 = _time.perf_counter()
    graph = optimize(knn, degree)
    t2 = _time.perf_counter()
    seeds = build_covering_seeds(dataset, p, mt)
    t3 = _time.perf_counter()
    rlog.log_info(
        "cagra.build n=%d: knn_graph %.1fs (%s), optimize %.1fs, "
        "seeds %.1fs", n, t1 - t0, galgo, t2 - t1, t3 - t2)
    index = Index(jnp.asarray(dataset), jnp.asarray(graph), mt, seeds)
    # phase decomposition for harnesses (the bench records it on CAGRA
    # entries): a plain host attribute, NOT part of the pytree — it is
    # diagnostics, not index state
    index.build_stats = {"n": n, "knn_algo": galgo,
                         "knn_graph_s": round(t1 - t0, 1),
                         "optimize_s": round(t2 - t1, 1),
                         "seeds_s": round(t3 - t2, 1)}
    return index


def build_covering_seeds(dataset, p: "IndexParams", mt):
    """The seed-set POLICY (sizing + <64-row clamp) applied to a
    corpus → (s,) seed rows or None. One home for the policy so every
    index constructor — ``build`` and the mutable tier's warm-started
    merge rebuild (neighbors/mutable.py), which bypasses ``build`` to
    feed ``build_knn_graph`` an init graph — sizes seeds identically;
    a rebuild path that skipped this would silently regress to
    random-only seeding after the first merge."""
    from ..core import logging as rlog

    n = len(dataset)
    if p.seed_nodes < 0:
        # auto: scale coverage with the corpus; skip tiny corpora where
        # random seeding already covers the space
        n_seed = max(128, min(2048, n // 64))
        n_seed = n_seed if n > 4 * n_seed else 0
    else:
        # explicit request: honor it, clamped so the seed set stays a
        # strict covering subset; requests below search()'s 64-row
        # eligibility threshold would build dead weight (search ignores
        # smaller seed sets), so clamp them to 0 and say so
        n_seed = min(p.seed_nodes, n // 4)
        if 0 < n_seed < 64:
            rlog.log_warn(
                "cagra.build: seed_nodes=%d is below the 64-row search "
                "threshold; skipping seed construction", n_seed)
            n_seed = 0
    return _covering_seeds(dataset, n_seed, mt, p.seed) if n_seed > 0 \
        else None


def _covering_seeds(dataset, s: int, mt, seed: int) -> jax.Array:
    """(s,) sorted unique dataset row ids nearest to kmeans centroids:
    the shared traversal seed set (one small GEMM scores it for every
    query at search time).

    Coverage needs *spread*, not balanced partition quality, so the
    centroids come from a fixed-iteration Lloyd over a bounded subsample
    — one compiled executable (a full balanced-kmeans here was 125 s of
    the 100k build, >10x the phase's usefulness). The centroid→row step
    always uses L2: the seed set must cover the *geometry* of the corpus
    — under InnerProduct a max-IP pick would collapse onto a few
    high-norm rows and cover nothing."""
    from . import brute_force as bf_mod
    from .ivf_pq import _kmeans_fixed

    dataset = np.asarray(dataset, np.float32)
    n = len(dataset)
    rng = np.random.default_rng(seed)
    t = min(n, max(8 * s, 20_000))
    rows = rng.choice(n, size=t, replace=False)
    cent = _kmeans_fixed(jnp.asarray(dataset[rows]), s, 10,
                         jax.random.PRNGKey(seed))
    index = bf_mod.build(dataset, DistanceType.L2Expanded)
    _, ids = bf_mod.search(index, cent, 1, algo="matmul")
    return jnp.asarray(np.unique(np.asarray(ids[:, 0])), jnp.int32)


def _query_dists(qc, vecs, mt):
    """(m, c, d) candidate vectors → (m, c) distances to qc (m, d).
    bf16 ``vecs`` (the bandwidth-saving traversal mode) stay bf16 into
    the MXU contraction and accumulate in f32 — no (m, c, d) f32
    materialization between the gather and the dot."""
    if vecs.dtype == jnp.bfloat16:
        qcv = qc.astype(jnp.bfloat16)
        kw = {"preferred_element_type": jnp.float32}
    else:
        qcv = qc
        vecs = vecs.astype(jnp.float32)
        kw = {"precision": "highest", "preferred_element_type": jnp.float32}
    ip = jnp.einsum("mcd,md->mc", vecs, qcv, **kw)
    if mt is DistanceType.InnerProduct:
        return -ip
    q2 = jnp.sum(qc * qc, axis=1, keepdims=True)
    v2 = jnp.einsum("mcd,mcd->mc", vecs, vecs, **kw)
    return jnp.maximum(q2 + v2 - 2.0 * ip, 0.0)


def _gather_score(score, score_scales, cand, qc, mt):
    """Gather candidate rows + score against queries; the traversal's one
    HBM-bound op (cand rows are random 128-256 B lines, so bytes gathered
    — not FLOPs — bound the hop). int8 rows apply per-row scales after
    the gather (half the bf16 traffic)."""
    vecs = score[cand]
    if score_scales is not None:
        vecs = vecs.astype(jnp.float32) * score_scales[cand][..., None]
    return _query_dists(qc, vecs, mt)


def _seed_dists(qc, vecs, mt):
    """(s, d) shared seed vectors → (m, s) distances: one dense GEMM
    (every query scores the same rows — no gather)."""
    if vecs.dtype == jnp.bfloat16:
        qcv = qc.astype(jnp.bfloat16)
        kw = {"preferred_element_type": jnp.float32}
    else:
        qcv = qc
        vecs = vecs.astype(jnp.float32)
        kw = {"precision": "highest", "preferred_element_type": jnp.float32}
    ip = jnp.einsum("md,sd->ms", qcv, vecs, **kw)
    if mt is DistanceType.InnerProduct:
        return -ip
    q2 = jnp.sum(qc * qc, axis=1, keepdims=True)
    v2 = jnp.einsum("sd,sd->s", vecs, vecs, **kw)
    return jnp.maximum(q2 + v2[None, :] - 2.0 * ip, 0.0)


def _dup_mask(cand, keep=None):
    """(m, c) bool: ``cand[i, j]`` duplicates an entry of ``keep[i]`` or
    an *earlier* ``cand[i, j' < j]``.

    Sort-based replacement for the former O(c²)/O(c·itopk) broadcast
    equality planes (``jnp.tril(eq)`` over (m, c, c) — VMEM-hungry at
    itopk64·w4 and quadratic in ``search_width``): one stable argsort of
    the concatenated ids brings every duplicate run together, a single
    neighbor compare flags all but the run's first element, and the
    inverse permutation (a second integer argsort) carries the flags
    back. Stability makes "first" = lowest original position, and
    ``keep`` entries precede equal candidates in the concat order, so
    the semantics match the old masks exactly: any candidate equal to a
    keep entry, or to an earlier candidate, is flagged."""
    m, c = cand.shape
    allv = cand if keep is None else jnp.concatenate([keep, cand], axis=1)
    b = allv.shape[1] - c
    order = jnp.argsort(allv, axis=1, stable=True)
    sv = jnp.take_along_axis(allv, order, axis=1)
    dup_s = jnp.concatenate(
        [jnp.zeros((m, 1), bool), sv[:, 1:] == sv[:, :-1]], axis=1)
    inv = jnp.argsort(order, axis=1, stable=True)
    return jnp.take_along_axis(dup_s, inv, axis=1)[:, b:]


@partial(jax.jit, static_argnames=("itopk", "width", "max_iter", "k",
                                   "n_seeds", "mt_val", "min_iter",
                                   "engine", "kprime", "interp", "smode"))
def _search_jit(dataset, dataset_score, score_scales, graph, qc, mask_bits,
                seed_key, seed_rows, edge_vecs, edge_aux, edge_gp, itopk,
                width, max_iter, k, n_seeds, mt_val, min_iter=0,
                engine="gather", kprime=0, interp=False, edge_cb=None,
                edge_cbs=None, smode="dense"):
    """``dataset_score`` feeds the seed scoring and (engine="gather") the
    traversal's candidate gathers (bf16 in the default bandwidth-saving
    mode, int8 + per-row ``score_scales`` in the quarter-traffic mode);
    ``dataset`` (f32) re-scores the final top-k exactly, so returned
    distances are exact regardless. ``seed_rows``: optional (s,) shared
    covering seed set — scored by one GEMM and mixed with the per-query
    random seeds. ``engine="edge"``: the hop streams each parent's
    contiguous neighbor tile from ``edge_vecs``/``edge_aux`` (the
    prepare_traversal store) through the Pallas frontier-expansion
    kernel, which emits a per-parent top-``kprime`` — the merge width
    shrinks from width·degree to width·kprime. ``engine="fused"``: the
    whole hop loop collapses into ONE megakernel launch
    (ops/cagra_fused.py) — the frontier lives in VMEM across grid steps
    and ``edge_gp`` (the store's tile-padded graph rows) feeds the
    in-kernel id extraction; bit-identical to the edge engine by
    construction."""
    mt = DistanceType(mt_val)
    m, dim = qc.shape
    n = dataset.shape[0]
    degree = graph.shape[1]
    metric_s = "ip" if mt is DistanceType.InnerProduct else "l2"

    if engine in ("edge", "fused") and mask_bits is not None:
        # the bitset filter in edge-major layout: the kernel adds this
        # penalty in-VMEM, so filtered edges never reach the merge. One
        # (n, degree) gather per CALL (not per hop), loop-invariant
        pen_node = jnp.where(mask_bits, 0.0, jnp.inf).astype(jnp.float32)
        edge_pen = jnp.pad(pen_node[graph], ((0, 0), (
            0, round_up_to(edge_vecs.shape[1], 128) - degree)))
    else:
        edge_pen = None

    # seed the itopk buffer: per-query random nodes (random_seed init,
    # search_plan.cuh), plus the shared covering set when present
    if mask_bits is not None:
        # survivor-aware seeding (ops/filter_policy.py): uniform-over-n
        # seeds can ALL land on filtered rows under a high-selectivity
        # filter (empty result despite survivors). Sampling the r-th
        # set bit via the mask's cumulative sum is uniform over the
        # surviving rows by construction; an all-cleared mask keeps
        # every seed at +inf, so the empty-result contract holds.
        csum = jnp.cumsum(mask_bits.astype(jnp.int32))
        r = jax.random.randint(seed_key, (m, n_seeds), 0,
                               jnp.maximum(csum[-1], 1))
        seeds = jnp.minimum(jnp.searchsorted(csum, r + 1), n - 1)
        seed_d = _gather_score(dataset_score, score_scales, seeds, qc, mt)
        seed_d = jnp.where(mask_bits[seeds], seed_d, jnp.inf)
    else:
        seeds = jax.random.randint(seed_key, (m, n_seeds), 0, n)
        seed_d = _gather_score(dataset_score, score_scales, seeds, qc, mt)
    # dedup identical random seeds (mark later occurrences)
    seed_d = jnp.where(_dup_mask(seeds), jnp.inf, seed_d)
    if seed_rows is not None:
        svecs = dataset_score[seed_rows]              # (s, d) — tiny
        if score_scales is not None:
            svecs = svecs.astype(jnp.float32) \
                * score_scales[seed_rows][:, None]
        sd = _seed_dists(qc, svecs, mt)               # (m, s)
        if mask_bits is not None:
            sd = jnp.where(mask_bits[seed_rows][None, :], sd, jnp.inf)
        # a random seed colliding with a shared seed is a duplicate;
        # seed_rows is sorted unique (np.unique in _covering_seeds), so
        # membership is a searchsorted probe — not an (m, n_seeds, s)
        # broadcast compare
        pos = jnp.searchsorted(seed_rows, seeds)
        coll = jnp.take(seed_rows,
                        jnp.clip(pos, 0, seed_rows.shape[0] - 1)) == seeds
        seed_d = jnp.where(coll, jnp.inf, seed_d)
        seeds = jnp.concatenate(
            [jnp.broadcast_to(seed_rows[None, :], (m, seed_rows.shape[0])),
             seeds], axis=1)
        seed_d = jnp.concatenate([sd, seed_d], axis=1)
    total = seed_d.shape[1]
    if total < itopk:
        seed_d = jnp.concatenate(
            [seed_d, jnp.full((m, itopk - total), jnp.inf, jnp.float32)],
            axis=1)
        seeds = jnp.concatenate(
            [seeds, jnp.full((m, itopk - total), -1, jnp.int32)], axis=1)
    buf_d, srt = select_k(seed_d, itopk, select_min=True)
    buf_i = jnp.take_along_axis(seeds, srt, axis=1)
    explored = jnp.zeros((m, itopk), bool)

    def cond(state):
        _, buf_d, explored, it = state
        frontier_open = jnp.any(~explored & jnp.isfinite(buf_d))
        return (it < max_iter) & (frontier_open | (it < min_iter))

    cand_w = width * (kprime if engine == "edge" else degree)

    def body(state):
        buf_i, buf_d, explored, it = state
        # pick top `width` unexplored parents (pickup_next_parents :51)
        cand_d = jnp.where(explored, jnp.inf, buf_d)
        _, psel = select_k(cand_d, width, select_min=True)   # (m, w) positions
        parent_ids = jnp.take_along_axis(buf_i, psel, axis=1)
        parent_ok = jnp.isfinite(jnp.take_along_axis(cand_d, psel, axis=1))
        explored = explored.at[jnp.arange(m)[:, None], psel].set(True)
        psafe = jnp.where(parent_ok, parent_ids, 0)

        if engine == "edge":
            # streamed expansion: one contiguous edge-store tile per
            # parent through the Pallas kernel (bitset penalty applied
            # in-kernel), emitting per-parent top-kprime — only the
            # (m, w, deg) int32 graph rows are still gathered, 1/dim-th
            # of the former vector-gather bytes
            from ..ops.graph_expand import graph_expand

            pvals, pepos = graph_expand(psafe, qc, edge_vecs, edge_aux,
                                        kprime, metric=metric_s,
                                        degree=degree, pen=edge_pen,
                                        interpret=interp, mode=smode,
                                        cbm=edge_cb, cb_scale=edge_cbs)
            nbr = graph[psafe]                               # (m, w, deg)
            cand = jnp.take_along_axis(nbr, jnp.maximum(pepos, 0), axis=2)
            # empty kernel slots (epos -1) must not alias a real node id:
            # a phantom occurrence would dup-flag a later genuine one
            cand = jnp.where(pepos >= 0, cand, -1).reshape(m, cand_w)
            cd = pvals.reshape(m, cand_w)
            cand_ok = (jnp.repeat(parent_ok, kprime, axis=1)
                       & (pepos >= 0).reshape(m, cand_w))
        else:
            # expand: graph neighbors of parents (the random row gather)
            cand = graph[psafe].reshape(m, cand_w)           # (m, w·deg)
            cand_ok = jnp.repeat(parent_ok, degree, axis=1)
            cd = _gather_score(dataset_score, score_scales, cand, qc, mt)
            if mask_bits is not None:
                cand_ok = cand_ok & mask_bits[cand]
        # dedup vs itopk buffer (the hashmap stand-in) and within the
        # candidate block. Without this, near convergence most of the
        # block duplicates top buffer entries, floods the merge's top
        # slots, and evicts genuinely new candidates — measured recall
        # collapse 0.97 → 0.70 (sort-based: see _dup_mask)
        cand_ok = cand_ok & ~_dup_mask(cand, keep=buf_i)
        cd = jnp.where(cand_ok, cd, jnp.inf)

        # merge candidates into itopk (bitonic merge analog :94-200)
        all_d = jnp.concatenate([buf_d, cd], axis=1)
        all_i = jnp.concatenate([buf_i, cand], axis=1)
        all_e = jnp.concatenate(
            [explored, jnp.zeros((m, cand_w), bool)], axis=1)
        new_d, sel = select_k(all_d, itopk, select_min=True)
        new_i = jnp.take_along_axis(all_i, sel, axis=1)
        new_e = jnp.take_along_axis(all_e, sel, axis=1)
        return new_i, new_d, new_e, it + 1

    if engine == "fused":
        # ONE kernel launch for the whole traversal: the seeded buffer
        # goes in, the converged buffer comes out — no host-visible hop
        # loop remains (the fixed grid runs max_iter hops; converged
        # hops are exact no-ops, see ops/cagra_fused.fused_traverse)
        from ..ops.cagra_fused import fused_traverse

        buf_d, buf_i = fused_traverse(
            qc, buf_d, buf_i, edge_vecs, edge_aux, edge_gp, edge_pen,
            itopk=itopk, width=width, max_iter=int(max_iter),
            kprime=kprime, degree=degree, metric=metric_s,
            interpret=interp, mode=smode)
    else:
        state = (buf_i, buf_d, explored, jnp.int32(0))
        buf_i, buf_d, explored, _ = jax.lax.while_loop(cond, body, state)

    # exact f32 re-score + re-rank of the returned k (fixes any bf16
    # traversal rounding; one (m, k, d) gather)
    out_i = buf_i[:, :k]
    finite = jnp.isfinite(buf_d[:, :k])
    exact = _query_dists(qc, dataset[jnp.maximum(out_i, 0)], mt)
    exact = jnp.where(finite, exact, jnp.inf)
    out_d, order = select_k(exact, k, select_min=True)
    out_i = jnp.take_along_axis(out_i, order, axis=1)
    if mt is DistanceType.L2SqrtExpanded:
        out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
    elif mt is DistanceType.InnerProduct:
        out_d = jnp.where(jnp.isfinite(out_d), -out_d, -jnp.inf)
    out_i = jnp.where(jnp.isfinite(out_d) if mt is not DistanceType.InnerProduct
                      else out_d > -jnp.inf, out_i, -1)
    return out_d, out_i


def prepare_search(index: Index, candidate_dtype: str = "bfloat16") -> None:
    """Eagerly attach the low-precision traversal copy of the dataset
    (used by the matching ``SearchParams.candidate_dtype``). jit users
    call this once before tracing — an unprepared index re-quantizes
    inside every jitted call."""
    if candidate_dtype in ("bfloat16", "bf16"):
        if getattr(index, "_score_bf16", None) is None:
            index._score_bf16 = index.dataset.astype(jnp.bfloat16)
    elif candidate_dtype in ("int8", "i8"):
        if getattr(index, "_score_i8", None) is None:
            from .brute_force import quantize_rows

            index._score_i8 = quantize_rows(index.dataset, jnp.int8)


def prepare_traversal(index: Index, candidate_dtype: str = "int8",
                      pq_dim: int = 0, pq_lut: str = "int8") -> None:
    """Eagerly build the edge-resident candidate store and attach it to
    the index: for every node, its ``degree`` neighbors' coded vectors
    packed into one contiguous ``(n, deg_p, W)`` HBM array (plus a
    ``(n, 2, deg_p)`` f32 aux of per-edge dequant scales and norms), so
    the ``engine="edge"`` hop streams one contiguous tile per expanded
    parent instead of ``degree`` random 128-256 B lines — the GGNN
    co-location move (arXiv:1912.01059) in TPU form.

    Storage rungs (docs/perf.md "Storage ladder"; ``W`` = minor width at
    1M·deg64·d128):

    * ``"bfloat16"`` — W=dim_p bf16 (16.8 GB);
    * ``"int8"`` (default) — W=dim_p int8, per-edge scales (8.4 GB);
    * ``"int4"`` — W=dim_p/2 nibble-packed int8 (ops/quant.py
      split-half layout; unpacked in-kernel, 4.2 GB);
    * ``"pq"`` — W=pq_dim uint8 PQ codes per edge, decoded in-kernel by
      the ivf_pq one-hot LUT GEMM (~0.5 GB of codes at pq8·book256 —
      the rung that puts 100M·deg32 within one host's HBM). ``pq_dim``
      overrides the ``ops.quant.default_pq_dim`` subspace count;
      ``pq_lut`` picks the decode matrix precision ("int8" = the
      fp8-LUT role with exact int32 accumulation, or "f32").

    OPT-IN, exactly like ``brute_force.prepare_fused``: a read-only
    query never doubles index HBM as a side effect; ``tune_search``
    attaches it for the race and drops it again if the gather engine
    wins. Idempotent on a matching (dtype, degree) geometry — a second
    call is a no-op, no HBM double-alloc. The store travels through the
    Index pytree, so jitted functions taking the index as an argument
    reuse it; it is derived data and is NOT serialized (rebuild after
    :func:`load`). Never built under a jax trace (cache writes there
    would store tracers)."""
    from ..utils import in_jax_trace

    if in_jax_trace():
        return
    expects(candidate_dtype in ("int8", "i8", "bfloat16", "bf16",
                                "int4", "i4", "pq"),
            "edge store dtype must be int8/bfloat16/int4/pq, got %r",
            candidate_dtype)
    from ..ops import quant

    int8 = candidate_dtype in ("int8", "i8")
    int4 = candidate_dtype in ("int4", "i4")
    pq = candidate_dtype == "pq"
    dtype_str = ("int8" if int8 else "int4" if int4 else
                 "pq" if pq else "bfloat16")
    degree = index.graph_degree
    deg_p = round_up_to(degree, 32)       # int8 sublane tile (bf16 needs 16)
    dim_p = round_up_to(index.dim, 128)
    meta = (dtype_str, degree, deg_p, dim_p)
    cur = getattr(index, "_edge_store", None)
    if cur is not None and cur[0] == meta:
        return
    g = index.graph
    cbs = None
    if int8:
        cached = getattr(index, "_score_i8", None)
        if cached is None:
            cached = quant.quantize_rows(index.dataset, jnp.int8)
            index._score_i8 = cached   # int8 candidate_dtype searches reuse it
        stored, scales = cached
        en = (scales * scales) * jnp.sum(
            jnp.square(stored.astype(jnp.float32)), axis=1)
        es = scales[g]
    elif int4:
        stored, scales = quant.quantize_int4(index.dataset)
        low, high = quant.int4_nibbles(stored.astype(jnp.int32))
        en = (scales * scales) * jnp.sum(low * low + high * high, axis=1)
        es = scales[g]
    elif pq:
        # PQ row codes + the subspace-major decode table the expand
        # kernel consumes (ops/quant.pq_decode_table; int8 mode applies
        # the same per-subspace symmetric quantization as the ivf_pq
        # scan's fp8-LUT role)
        pqd = pq_dim or quant.default_pq_dim(index.dim)
        expects(dim_p % pqd == 0,
                "pq_dim %d must divide the padded dim %d", pqd, dim_p)
        cb = quant.train_pq_rows(index.dataset, pqd)
        stored = quant.encode_pq_rows(index.dataset, cb)   # (n, pqd) u8
        en = quant.pq_decoded_norms(stored, cb)
        es = jnp.ones(g.shape, jnp.float32)    # decode carries magnitude
        tbl = quant.pq_decode_table(cb)        # (pqd*book, dim_p) f32
        if pq_lut == "int8":
            cb_mat, cb_scale = quant.pq_int8_cb(tbl, pqd, cb.shape[1])
        else:
            cb_mat, cb_scale = tbl, jnp.ones((1, dim_p), jnp.float32)
        cbs = (cb_mat, cb_scale)
    else:
        stored = getattr(index, "_score_bf16", None)
        if stored is None:
            stored = index.dataset.astype(jnp.bfloat16)
            index._score_bf16 = stored
        en = jnp.sum(jnp.square(stored.astype(jnp.float32)), axis=1)
        es = jnp.ones(g.shape, jnp.float32)
    pad_d = deg_p - degree
    pad_f = 0 if (int4 or pq) else dim_p - index.dim
    if pad_d or pad_f:
        # gather + pad under one jit write a single padded output buffer;
        # eagerly, stored[g] then jnp.pad holds TWO copies of the store
        # transiently (jnp.pad copies even at zero width) — 2x of 8.2 GB
        # at the 1M int8 point would OOM a v5e.
        ev = jax.jit(lambda s, gg: jnp.pad(
            s[gg], ((0, 0), (0, pad_d), (0, pad_f))))(stored, g)
    else:
        ev = stored[g]
    # aux and graph rows are stored lane-padded (graph_expand.lane_rows:
    # the kernels' per-node DMA needs 128-lane rows). Graph rows ride
    # with the store: the fused megakernel DMAs each parent's id row
    # next to its edge tile (pad edges are masked in-kernel by
    # `col < degree`, so the pad id value is inert)
    lane_pad = round_up_to(degree, 128) - degree
    aux = jnp.pad(jnp.stack([es, en[g]], axis=1),
                  ((0, 0), (0, 0), (0, lane_pad)))
    gp = jnp.pad(g, ((0, 0), (0, lane_pad)))
    index._edge_store = (meta, ev, aux, gp, cbs)


def _store_mode(store) -> str:
    """Edge-store meta → the expand kernels' storage mode ("dense" for
    int8/bf16 rows, "int4"/"pq" for the packed rungs)."""
    if store is None:
        return "dense"
    tag = store[0][0]
    return tag if tag in ("int4", "pq") else "dense"


# store rungs whose expand kernels Mosaic refuses for TPU (int4: "Shape
# mismatch in input, indices and output" lowering a gather; pq: a
# pq_dim-wide per-node DMA slice "must be aligned to tiling (128)"):
# auto never picks a kernel engine for them on TPU, and an explicit ask
# raises instead of demoting in silence
_NO_TPU_KERNEL_RUNGS = ("int4", "pq")


def _plan_dims(p: "SearchParams", k: int):
    """(itopk, width, max_iter) of the traversal plan — ONE derivation,
    because ``search`` (the dispatch) and ``tune_search`` (the fused
    VMEM-capability gate) must agree on the hop budget a shape implies."""
    itopk = max(p.itopk_size, k)
    width = max(1, p.search_width)
    max_iter = p.max_iterations or (itopk // width + 16)
    # min_iterations must win over the auto max (the reference adjusts
    # max_iterations up the same way)
    return itopk, width, max(int(max_iter), int(p.min_iterations))


def _tune_key(index: Index, m: int, k: int, p: "SearchParams",
              store) -> str:
    """Autotune bucket for the engine race. Dtype-aware: the edge store's
    storage width (or the gather path's candidate_dtype) is part of the
    key — HBM-traffic-bound crossovers move with the element width, so a
    winner measured for one storage mode must not steer another's
    dispatch (the brute-force race set the precedent)."""
    from ..ops import autotune

    sd = store[0][0] if store is not None else str(p.candidate_dtype)
    return autotune.shape_bucket("cagra_search", n=index.size, m=m,
                                 d=index.dim, k=k, deg=index.graph_degree,
                                 itopk=max(p.itopk_size, k),
                                 w=max(1, p.search_width), store=sd)


def tune_search(index: Index, queries, k: int,
                params: SearchParams | None = None, reps: int = 3,
                suspect_floor_s: float = 0.0,
                store_dtype: str = "int8", engines=None):
    """Measure the traversal engines on-device for this shape class and
    cache the winner (consulted by ``engine="auto"``): the streamed
    edge-store hop (Pallas frontier expansion) and the one-dispatch
    megakernel (``engine="fused"``) race the XLA gather hop — every
    member of :data:`ENGINES` runs (the fused lane is skipped only when
    its VMEM working set exceeds the megakernel cap, see
    ``ops.cagra_fused.fused_capable``). Attaches the edge store for the
    race and DROPS it again when the gather engine wins — the store is
    ~``n·degree·dim`` bytes of extra HBM and only earns it behind a
    store-backed winning engine. Call eagerly (not under jit) — e.g.
    once at serving start, or from the bench harness. Returns
    (winner, timings)."""
    from ..ops import autotune
    from ..ops.cagra_fused import fused_capable

    p = params or SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    prepare_traversal(index, store_dtype)
    prepare_search(index, p.candidate_dtype)
    key = _tune_key(index, q.shape[0], k, p, index._edge_store)

    # the index rides as a jit ARGUMENT (closure-baking the dataset +
    # edge store as HLO constants would make the compile request
    # index-sized)
    def _engine(eng):
        return autotune.JitArgFn(jax.jit(
            lambda qq, idx, e=eng: search(idx, qq, k, p, engine=e)), index)

    itopk, width, max_iter = _plan_dims(p, k)
    ev = index._edge_store[1]
    # engines=None races the full registry (the drift guard holds the
    # default to ENGINES); an explicit subset is a caller's cost choice.
    # The megakernel sits the race out for PQ stores (no in-kernel PQ
    # decode — those shapes serve the per-hop edge engine) and for
    # over-VMEM working sets.
    cands = {e: _engine(e) for e in (engines or ENGINES)
             if e != "fused" or (
                 _store_mode(index._edge_store) != "pq" and fused_capable(
                     itopk, width, ev.shape[1], ev.shape[2], ev.dtype,
                     max_iter))}
    winner, timings = autotune.tune_best(key, cands, q, reps=reps,
                                         force=True,
                                         suspect_floor_s=suspect_floor_s,
                                         value_read=True)
    if winner not in ("edge", "fused"):
        index.__dict__.pop("_edge_store", None)
        # the raced key carried the STORE dtype; with the store dropped,
        # auto queries are storeless and key on candidate_dtype — mirror
        # the verdict there so the measured gather win stays reachable
        autotune.record(_tune_key(index, q.shape[0], k, p, None), winner)
    return winner, timings


@interop.auto_convert_output
@tracing.annotate("raft_tpu::cagra::search")
def search(
    index: Index,
    queries,
    k: int,
    params: SearchParams | None = None,
    filter: Optional[Bitset] = None,  # noqa: A002
    res=None,
    query_chunk: int = 0,
    engine: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched-frontier graph traversal (search_single_cta analog).

    ``res``/``query_chunk``: when a Resources carries a Deadline (or an
    explicit ``query_chunk`` is given), queries traverse in host-level
    chunks with a cancellation/deadline checkpoint between dispatches —
    ``DeadlineExceeded`` carries the completed chunks' partial results.
    ``engine``: overrides ``SearchParams.engine`` — "edge" (streamed
    edge-store hop via the Pallas frontier-expansion kernel; requires /
    eagerly builds the ``prepare_traversal`` store, and is guarded onto
    the gather path on kernel failure), "fused" (the one-dispatch
    traversal megakernel, ops/cagra_fused.py — same store requirement,
    guarded onto the edge→gather chain via ``cagra.fused_search``),
    "gather" (composed-XLA random row gather), or "auto" (autotune
    cache, then store-attached heuristic; fused only off a measured
    race verdict).
    """
    p = params or SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim, "bad query shape %s",
            tuple(q.shape))
    itopk, width, max_iter = _plan_dims(p, k)
    if filter is not None:
        from ..ops import filter_policy
        from ..utils import in_jax_trace

        if not in_jax_trace() and not filter_policy.adaptive_off():
            # selectivity-adaptive policy (ops/filter_policy.py): widen
            # itopk along the brownout ladder so survivor hits are not
            # crowded out of the frontier, and at extreme selectivity
            # cross over to an exact brute pass on the compacted
            # survivors (a graph walk through mostly-filtered nodes
            # stops converging long before that point). Ladder levels
            # land on existing compile buckets — zero new compiles.
            import dataclasses as _dc

            fd = filter_policy.decide_graph(filter, index.size, index.dim,
                                            k)
            if fd.use_brute:
                return filter_policy.crossover(
                    fd, "cagra",
                    lambda: filter_policy.survivor_brute_dense(
                        index.dataset, index.metric, q, k, filter),
                    lambda: search(index, q, k, p, filter, res,
                                   query_chunk, engine))
            if fd.level > 1:
                p = _dc.replace(p, itopk_size=min(
                    max(p.itopk_size, k) * fd.level,
                    max(index.size, k)))
                itopk, width, max_iter = _plan_dims(p, k)
    if (index.seed_nodes is not None and filter is None
            and index.seed_nodes.shape[0] >= 64):
        # the shared covering set does the heavy seeding; random seeds
        # stay only as degenerate-case insurance. Under a filter the
        # whole shared set can be masked out (a selective tenant
        # slice), and a degenerately small set (duplicate-heavy corpus)
        # covers too little — keep the full random count in both cases.
        n_seeds = min(itopk, 16 * p.num_random_samplings)
    else:
        n_seeds = min(itopk, max(width * index.graph_degree // 2,
                                 16 * p.num_random_samplings))
    mask_bits = filter.to_mask() if filter is not None else None
    key = jax.random.key(p.seed)
    expects(p.candidate_dtype in ("bfloat16", "bf16", "int8", "i8",
                                  "float32", "f32"),
            "unknown candidate_dtype %r", p.candidate_dtype)
    scales = None
    if p.candidate_dtype in ("bfloat16", "bf16", "int8", "i8"):
        # low-precision traversal copy, cached per index object (one
        # quantize pass) — never stored from inside a jax trace (leaked
        # tracers); see prepare_search
        int8 = p.candidate_dtype in ("int8", "i8")
        attr = "_score_i8" if int8 else "_score_bf16"
        cached = getattr(index, attr, None)
        if cached is None:
            from ..utils import in_jax_trace

            if in_jax_trace():
                if int8:
                    from .brute_force import quantize_rows

                    cached = quantize_rows(index.dataset, jnp.int8)
                else:
                    cached = index.dataset.astype(jnp.bfloat16)
            else:
                prepare_search(index, p.candidate_dtype)
                cached = getattr(index, attr)
        score, scales = cached if int8 else (cached, None)
    else:
        score = index.dataset
    expects(p.algo in ("auto", "single_cta", "multi_cta", "multi_kernel"),
            "unknown cagra search algo %r", p.algo)

    eng = engine or p.engine
    expects(eng in ("auto",) + ENGINES,
            "unknown cagra traversal engine %r", eng)
    store = getattr(index, "_edge_store", None)
    if eng == "auto":
        from ..ops import autotune

        hit = autotune.lookup(_tune_key(index, q.shape[0], k, p, store))
        if hit == "gather" or (hit in ("edge", "fused")
                               and store is not None):
            eng = hit
        elif (store is not None and jax.default_backend() == "tpu"
              and _store_mode(store) not in _NO_TPU_KERNEL_RUNGS):
            # a store someone paid for implies the streamed hop; without
            # one, auto never builds it — tune_search / prepare_traversal
            # are the opt-ins (a read-only query must not double HBM).
            # The megakernel only dispatches off a measured race verdict
            # (tune_search) — an unraced shape stays on the rehearsed
            # per-hop kernel.
            eng = "edge"
        else:
            eng = "gather"
    if eng in ("edge", "fused") and store is None:
        from ..utils import in_jax_trace

        expects(not in_jax_trace(),
                "engine=%r requires prepare_traversal(index) before "
                "tracing (the edge store cannot be built under jit)", eng)
        prepare_traversal(index)
        store = index._edge_store
    smode = _store_mode(store)
    if eng == "fused" and smode == "pq":
        # the megakernel has no in-kernel PQ decode (the edge engine
        # carries that rung); a PQ store serves the per-hop kernel —
        # same results, one launch per hop
        eng = "edge"
    interp = jax.default_backend() != "tpu"
    expects(interp or eng == "gather" or smode not in _NO_TPU_KERNEL_RUNGS,
            "the %s edge store's expand kernels do not compile for TPU "
            "(tests/test_tpu_compile.py); search it with engine='gather' "
            "or prepare an int8/bfloat16 store", smode)
    kprime = min(index.graph_degree, itopk)

    def run(qc, key=key):
        def _go(e):
            ev, ea, gp = ((store[1], store[2], store[3])
                          if e in ("edge", "fused") else (None, None, None))
            cbs = (store[4] if e in ("edge", "fused")
                   and len(store) > 4 and store[4] is not None
                   else (None, None))
            return _search_jit(index.dataset, score, scales, index.graph,
                               qc, mask_bits, key, index.seed_nodes, ev,
                               ea, gp, itopk, width, int(max_iter), k,
                               n_seeds, index.metric.value,
                               int(p.min_iterations), engine=e,
                               kprime=kprime, interp=interp,
                               edge_cb=cbs[0], edge_cbs=cbs[1],
                               smode=smode if e in ("edge", "fused")
                               else "dense")

        def _edge_guarded():
            # a frontier-kernel failure demotes this site to the exact
            # XLA gather path (ops/guarded.py) — one log line and a
            # slower call, never the request. The PQ rung carries its
            # own breaker (cagra.pq_expand): its in-kernel LUT decode is
            # a different program from the dense expand, and demoting
            # one rung must not take the other's kernel down with it.
            # (Two literal guarded_call sites on purpose — the drift
            # guard's source sweep discovers sites by string literal.)
            if smode == "pq":
                return guarded_call("cagra.pq_expand",
                                    lambda: _go("edge"),
                                    lambda: _go("gather"))
            return guarded_call("cagra.graph_expand",
                                lambda: _go("edge"), lambda: _go("gather"))

        if eng == "fused":
            # megakernel failure → the per-hop edge engine (itself
            # guarded onto the gather path): the fallback chain serves
            # bit-identical results at worst two demotion log lines
            from ..ops.cagra_fused import FUSED_SITE

            return guarded_call(FUSED_SITE,
                                lambda: _go("fused"), _edge_guarded)
        if eng == "edge":
            return _edge_guarded()
        return _go("gather")

    if query_chunk <= 0 and deadline.carried(res) is not None:
        query_chunk = max(1, min(q.shape[0], 1024))
    # a carried deadline always takes the chunked path: even a single
    # chunk needs its pre-dispatch checkpoint (an already-expired budget
    # must raise, not dispatch)
    if query_chunk > 0 and (query_chunk < q.shape[0]
                            or deadline.carried(res) is not None):
        # distinct key per chunk: reusing one key would hand every chunk
        # the same random seed rows (correlated sampling). Chunked runs
        # therefore draw different random seeds than the unchunked call
        # — neighbor quality is seed-robust (covering seed set + exact
        # f32 re-rank), but byte-level parity across chunk sizes is not
        # promised.
        return run_query_chunks(
            lambda qc, s0: run(qc, key=jax.random.fold_in(key, s0)),
            q, query_chunk, res)
    return run(q)


def save(index: Index, path) -> None:
    """Serialize dataset + graph (cagra_serialize.cuh analog). Files
    without a seed set are written as v1 so older readers stay able to
    load them."""
    arrs = {"dataset": index.dataset, "graph": index.graph}
    version = 1
    if index.seed_nodes is not None:
        arrs["seed_nodes"] = index.seed_nodes
        version = _SERIAL_VERSION
    save_arrays(path, "cagra", version,
                {"metric": index.metric.value}, arrs)


def load(path) -> Index:
    _, version, meta, arrs = load_arrays(path, "cagra")
    # v1 files have no seed_nodes; everything else is unchanged
    expects(version in (1, _SERIAL_VERSION),
            "unsupported version %d", version)
    seeds = arrs.get("seed_nodes")
    if seeds is not None:
        # canonicalize at the boundary: the search-time collision probe
        # (jnp.searchsorted) requires sorted unique ids — an externally
        # edited file with unsorted seeds would silently degrade dedup
        seeds = jnp.asarray(np.unique(np.asarray(seeds)), jnp.int32)
    return Index(jnp.asarray(arrs["dataset"]), jnp.asarray(arrs["graph"]),
                 DistanceType(meta["metric"]), seeds)


def health(index: Index, sample: int = 256) -> dict:
    """Index health report (docs/observability.md "Quality"): graph
    connectivity + quantization quality.

    The fixed out-degree graph's quality signal is its **in-degree
    distribution**: a node no edge points at is unreachable by traversal
    (only random/covering seeding can surface it), and a heavy-tailed
    in-degree concentrates traffic on hub rows. Because the index keeps
    the f32 dataset next to its quantized traversal caches
    (``prepare_search``/``prepare_traversal``), the report carries a
    *measured* sampled reconstruction error per cache, not just a bound.
    """
    from .brute_force import health_sample_rows, quantization_error

    # the connectivity half is graph-derived and the graph is immutable
    # post-build, but computing it means pulling the WHOLE graph to host
    # (256 MB at 1M x deg64) + a full bincount — far too heavy to repeat
    # inside every 10s SnapshotWriter tick once the index is watched.
    # Cache it on the index keyed by the array identities (both alive as
    # long as the index is).
    key = (id(index.graph), id(index.seed_nodes))
    cached = getattr(index, "_health_conn_cache", None)
    if cached is not None and cached[0] == key:
        conn = cached[1]
    elif index.size == 0:
        # an empty graph must report, not raise (np.min on an empty
        # in-degree array would)
        conn = {"graph_degree": int(index.graph.shape[1]),
                "in_degree": {"min": 0, "mean": 0.0, "p99": 0, "max": 0},
                "unreachable_nodes": 0, "unreachable_frac": 0.0,
                "unseeded_unreachable": 0, "seed_nodes": 0}
        index._health_conn_cache = (key, conn)
    else:
        g = np.asarray(index.graph)
        n, deg = g.shape
        flat = g.reshape(-1)
        indeg = np.bincount(flat[(flat >= 0) & (flat < n)], minlength=n)
        unreachable = indeg == 0
        seeds = None if index.seed_nodes is None \
            else np.asarray(index.seed_nodes)
        # unreachable AND outside the covering seed set: invisible to
        # traversal except through random seeding — the number that
        # predicts a recall ceiling
        unseeded = unreachable.copy()
        if seeds is not None and seeds.size:
            valid = seeds[(seeds >= 0) & (seeds < n)]
            unseeded[valid] = False
        conn = {
            "graph_degree": int(deg),
            "in_degree": {
                "min": int(indeg.min()),
                "mean": round(float(indeg.mean()), 2),
                "p99": int(np.percentile(indeg, 99)),
                "max": int(indeg.max())},
            "unreachable_nodes": int(unreachable.sum()),
            "unreachable_frac": round(float(unreachable.mean()), 5),
            "unseeded_unreachable": int(unseeded.sum()),
            "seed_nodes": 0 if seeds is None else int(seeds.shape[0]),
        }
        index._health_conn_cache = (key, conn)
    report = {"family": "cagra", "n": int(index.size),
              "dim": int(index.dim), "metric": index.metric.name, **conn}
    rows = health_sample_rows(index.size, sample)
    quant = {}
    orig = np.asarray(index.dataset[rows]) if rows.size else None
    i8 = getattr(index, "_score_i8", None)
    if i8 is not None and rows.size:
        q8, sc = i8
        deq = np.asarray(q8[rows], np.float32) * np.asarray(sc[rows])[:, None]
        quant["int8"] = quantization_error(orig, deq)
    bf = getattr(index, "_score_bf16", None)
    if bf is not None and rows.size:
        quant["bfloat16"] = quantization_error(
            orig, np.asarray(bf[rows], np.float32))
    es = getattr(index, "_edge_store", None)
    if es is not None:
        ev = es[1]
        quant["edge_store"] = {"dtype": es[0][0],
                               "shape": tuple(int(s) for s in ev.shape),
                               "bytes": int(ev.size * ev.dtype.itemsize)}
    if quant:
        report["quant"] = quant
    return report


def make_searcher(index: Index, params: SearchParams | None = None, *,
                  degrade=None, donate=False, **opts):
    """Stable batchable signature for the serving runtime
    (:mod:`raft_tpu.serve`): returns ``fn(queries, k, res=None) ->
    (distances, indices)`` with the traversal policy frozen at closure
    build time, so repeated bucketed-shape calls hit the same cached
    executables. ``opts`` forwards to :func:`search` (``filter``,
    ``query_chunk``, ``engine``, ...). Pinning ``engine="edge"`` or
    ``"fused"`` (via opts or ``params.engine``) builds the edge-resident
    candidate store at closure-build time, not on the first request —
    serve warmup then only pays the per-shape compiles.

    ``donate``: OPT-IN (default off) — donate the per-call query
    block's device buffer to the jitted search
    (``jax.jit(..., donate_argnums=)``), letting XLA reuse it for
    outputs; with the batcher's double-buffered dispatch two batches
    are in flight, and donation keeps that from doubling the transient
    buffer footprint. ``"auto"`` donates on TPU only (CPU ignores
    donation and warns per call). Caveats (docs/perf.md "One-dispatch
    search"): the donated path wraps ``search`` in an OUTER jit, so
    guarded-site breakers are consulted at trace time, not per call —
    a kernel-engine failure surfaces as the compile error instead of
    the demoted fallback, which is why it is opt-in; donation is
    skipped for deadline-carrying requests (the chunked host loop owns
    those), under ``degrade`` (per-call param changes would defeat the
    jit cache), and for caller-owned device arrays (donating those
    would delete the caller's buffer — only host-side blocks, the
    batcher's case, are donated).

    ``degrade``: a :class:`~raft_tpu.serve.degrade.BrownoutController`
    — under brownout its current level overrides
    ``itopk_size``/``search_width`` per call (docs/robustness.md)."""
    eng = opts.get("engine") or (params.engine if params is not None
                                 else None)
    if eng in ("edge", "fused"):
        prepare_traversal(index)
    base = params or SearchParams()
    if donate == "auto":
        donate = jax.default_backend() == "tpu"
    jits: dict = {}

    def _fn(queries, k, res=None):
        p = base if degrade is None else degrade.params(base)
        if (donate and res is None and degrade is None
                and not isinstance(queries, jax.Array)):
            fn = jits.get(k)
            if fn is None:
                fn = jax.jit(
                    lambda qq, ix, kk=k: search(ix, qq, kk, base, **opts),
                    donate_argnums=(0,))
                jits[k] = fn
            return fn(jnp.asarray(queries, jnp.float32), index)
        return search(index, queries, k, p, res=res, **opts)

    return _fn
