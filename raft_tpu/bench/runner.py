"""Param-sweep benchmark runner emitting Google-Benchmark-schema JSON.

Reference: cpp/bench/ann/src/common/benchmark.hpp:320-371 — per-case
counters {Recall, Latency, QPS=items_per_second, end_to_end}; algo/param
sweeps from raft-ann-bench YAML configs
(raft-ann-bench/run/conf/*.json); the same schema here so the
reference's data_export/plot tooling (and ours in plot.py) applies.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.errors import expects

__all__ = ["BenchResult", "default_configs", "run_benchmarks"]


@dataclasses.dataclass
class BenchResult:
    name: str                      # e.g. "raft_ivf_flat.nlist1024.nprobe20"
    algo: str
    build_time: float
    search_params: Dict[str, Any]
    qps: float
    latency_s: float
    recall: float
    k: int
    batch_size: int

    def to_gbench(self) -> Dict[str, Any]:
        """One Google-Benchmark `benchmarks[]` entry (benchmark.hpp:337)."""
        return {
            "name": f"{self.name}/search",
            "run_type": "iteration",
            "real_time": self.latency_s,
            "time_unit": "s",
            "items_per_second": self.qps,
            "Recall": self.recall,
            "Latency": self.latency_s,
            "end_to_end": self.latency_s,
            "k": self.k,
            "n_queries": self.batch_size,
            "GPU": 0.0,
            "build_time": self.build_time,
        }


def _bf_case(base, metric, dtype="float32"):
    from ..neighbors import brute_force

    def build():
        return brute_force.build(base, metric, dtype=dtype)

    def make_search(index, k):
        def fn(q):
            return brute_force.search(index, q, k)
        return fn

    return build, make_search, [{}]


def _ivf_flat_case(base, metric, n_lists, probe_sweep, dtype="float32"):
    from ..neighbors import ivf_flat

    def build():
        return ivf_flat.build(base, ivf_flat.IndexParams(
            n_lists=n_lists, metric=metric, dtype=dtype))

    def make_search(index, k, n_probes=20):
        sp = ivf_flat.SearchParams(n_probes=n_probes)

        def fn(q):
            return ivf_flat.search(index, q, k, sp)
        return fn

    return build, make_search, [{"n_probes": p} for p in probe_sweep]


def _ivf_pq_case(base, metric, n_lists, pq_dim, probe_sweep):
    from ..neighbors import ivf_pq

    def build():
        return ivf_pq.build(base, ivf_pq.IndexParams(
            n_lists=n_lists, pq_dim=pq_dim, metric=metric))

    def make_search(index, k, n_probes=20):
        sp = ivf_pq.SearchParams(n_probes=n_probes)

        def fn(q):
            return ivf_pq.search(index, q, k, sp)
        return fn

    return build, make_search, [{"n_probes": p} for p in probe_sweep]


def _cagra_case(base, metric, graph_degree, itopk_sweep):
    from ..neighbors import cagra

    def build():
        return cagra.build(base, cagra.IndexParams(
            graph_degree=graph_degree,
            intermediate_graph_degree=graph_degree * 2, metric=metric))

    def make_search(index, k, itopk=64):
        sp = cagra.SearchParams(itopk_size=itopk)

        def fn(q):
            return cagra.search(index, q, k, sp)
        return fn

    return build, make_search, [{"itopk": t} for t in itopk_sweep]


def default_configs(base, metric, algos: Sequence[str],
                    n_lists: Optional[int] = None,
                    pq_dim: Optional[int] = None,
                    probe_sweep: Optional[Sequence[int]] = None,
                    cagra_degree: int = 32,
                    itopk_sweep: Optional[Sequence[int]] = None,
                    dtype: str = "float32"):
    """The raft-ann-bench default tuning envelopes
    (docs/ann_benchmarks_param_tuning.md:10-96) scaled to dataset size;
    every envelope overridable to pin a BASELINE.md config exactly."""
    n = len(base)
    if n_lists is None:
        n_lists = max(64, min(4096, int(np.sqrt(n) * 2)))
    if pq_dim is None:
        pq_dim = max(8, (base.shape[1] // 2 // 8) * 8 or 8)
    if probe_sweep is None:
        probe_sweep = [1, 2, 5, 10, 20, 50, 100]
    if itopk_sweep is None:
        itopk_sweep = [32, 64, 128, 256]
    cases = {}
    for a in algos:
        dtag = "" if dtype == "float32" else f".{dtype}"
        if a == "raft_brute_force":
            cases[a] = (_bf_case(base, metric, dtype), dtag.lstrip("."))
        elif a == "raft_ivf_flat":
            cases[a] = (_ivf_flat_case(base, metric, n_lists,
                                       list(probe_sweep), dtype),
                        f"nlist{n_lists}{dtag}")
        elif a == "raft_ivf_pq":
            cases[a] = (_ivf_pq_case(base, metric, n_lists, pq_dim,
                                     list(probe_sweep)),
                        f"nlist{n_lists}.pq{pq_dim}")
        elif a == "raft_cagra":
            cases[a] = (_cagra_case(base, metric, cagra_degree,
                                    list(itopk_sweep)),
                        f"degree{cagra_degree}")
        else:
            expects(False, "unknown algo %r", a)
    return cases


def run_benchmarks(
    base: np.ndarray,
    queries: np.ndarray,
    gt_indices: np.ndarray,
    k: int = 10,
    metric: str = "sqeuclidean",
    algos: Sequence[str] = ("raft_brute_force", "raft_ivf_flat",
                            "raft_ivf_pq", "raft_cagra"),
    batch_size: Optional[int] = None,
    reps: int = 5,
    verbose: bool = True,
    dtype: str = "float32",
) -> List[BenchResult]:
    """Build + sweep search params per algo; measure QPS and recall@k."""
    import jax
    import jax.numpy as jnp

    from .. import stats
    from ..ops import autotune

    base = np.asarray(base, np.float32)
    queries = np.asarray(queries, np.float32)
    if dtype == "uint8":
        mn, mx = float(base.min()), float(base.max())
        sample = base[:: max(1, len(base) // 4096)]
        maybe_bytes = (mn >= 0 and mx <= 255
                       and np.all(sample == np.round(sample)))
        # full integrality scan only when the sample says bytes (float
        # corpora — the remap path — never pay it); chunked with
        # early-exit so no full-corpus temporary is materialized. Without
        # it a corpus with sparse fractional rows would skip the remap
        # and crash in the builder's byte validation mid-bench
        def _all_integral(a, rows=1 << 16):
            return all(np.array_equal(c, np.round(c))
                       for c in (a[i : i + rows]
                                 for i in range(0, len(a), rows)))

        if not (maybe_bytes and _all_integral(base)):
            # uint8 storage is exact bytes only: discretize float corpora
            # to the byte grid via an affine map applied to base AND
            # queries. The shared shift preserves L2 distance ordering
            # only — and only the dtype-consuming algos may be in the run
            # (ivf_pq/cagra would otherwise silently benchmark remapped
            # data vs original gt).
            from ..distance.distance_types import (DistanceType,
                                                   canonical_metric)

            expects(canonical_metric(metric) in (
                        DistanceType.L2Expanded, DistanceType.L2SqrtExpanded),
                    "uint8 on a float corpus requires an L2 metric (the "
                    "byte-grid shift reorders cosine/IP neighbors); got %r",
                    metric)
            expects(set(algos) <= {"raft_brute_force", "raft_ivf_flat"},
                    "uint8 on a float corpus: restrict --algorithms to "
                    "raft_brute_force/raft_ivf_flat (other algos ignore "
                    "dtype and would run on remapped data vs original gt)")
            scale = 255.0 / max(mx - mn, 1e-30)
            base = np.round((base - mn) * scale).astype(np.float32)
            queries = ((queries - mn) * scale).astype(np.float32)
    gt = np.asarray(gt_indices)[:, :k]
    if batch_size:
        queries = queries[:batch_size]
        gt = gt[:batch_size]
    expects(len(gt) == len(queries), "gt/queries length mismatch")

    results: List[BenchResult] = []
    for algo, ((build, make_search, sweep), tag) in default_configs(
            base, metric, algos, dtype=dtype).items():
        t0 = time.perf_counter()
        index = build()
        jax.block_until_ready(jax.tree.leaves(index))
        build_time = time.perf_counter() - t0
        if verbose:
            print(f"# {algo}: built in {build_time:.2f}s")
        for params in sweep:
            fn = make_search(index, k, **params)
            d, i = fn(queries)                      # warmup + compile
            jax.block_until_ready((d, i))
            # per-call-blocked median with per-rep input perturbation
            # (autotune.measure docstring); out0 reuses the warmup above
            # instead of re-warming
            qj = jnp.asarray(queries, jnp.float32)
            dt = autotune.measure(fn, qj, reps=reps, out0=(d, i))
            recall = float(stats.neighborhood_recall(np.asarray(i)[:, :k], gt))
            ptag = ".".join(f"{kk}{vv}" for kk, vv in params.items())
            name = ".".join(x for x in (algo, tag, ptag) if x)
            results.append(BenchResult(
                name=name, algo=algo, build_time=build_time,
                search_params=dict(params), qps=len(queries) / dt,
                latency_s=dt, recall=recall, k=k, batch_size=len(queries)))
            if verbose:
                r = results[-1]
                print(f"#   {name}: qps={r.qps:,.0f} recall@{k}={r.recall:.4f}")
    return results


def to_gbench_json(results: List[BenchResult], context: Dict[str, Any]
                   ) -> str:
    """Full Google-Benchmark JSON document (context + benchmarks[])."""
    return json.dumps({
        "context": context,
        "benchmarks": [r.to_gbench() for r in results],
    }, indent=2)
