#!/usr/bin/env python
"""Headline benchmark: prints ONE JSON line for the driver.

The full results JSON is additionally written (and fsynced) to
``RAFT_TPU_BENCH_JSON`` (default ``artifacts/bench_full.json``) BEFORE
anything hits stdout, and the headline entry sorts first in ``entries``
— so a truncated stdout capture can never lose measurements again.

Measures QPS at recall@10 for the BASELINE.md configs on a SIFT-like
synthetic corpus (clustered gaussian mixture; queries are FRESH samples
from the mixture, not perturbed corpus rows, so the nprobe sweep shows a
real recall frontier), plus brute-force QPS and an on-device roofline
probe so kernel throughput is reported against the measured peak of the
chip actually in use.

Two timings per entry:

* ``latency_ms`` — per-call-blocked median: every call pays the full
  dispatch round trip. Reported for context.
* ``qps`` — the VALUE-READ PIPELINED WALL (``measure_wall``): N calls on
  content-distinct query permutations dispatched back-to-back (dispatch
  overlaps compute — the reference harness's ``items_per_second``
  semantics, cpp/bench/ann/src/common/benchmark.hpp:337), every output
  folded into a scalar accumulator, and the window closed by a host-side
  ``float()`` of that accumulator: a host value transitively dependent
  on every output cannot materialize before the compute ran.

Every timing is additionally gated by a per-lane PHYSICAL floor — FLOPs
over the chip's published peak for GEMM lanes, grouped-scan bytes over
its HBM peak for list scans (``roofline.PEAKS``, keyed by device kind;
an unknown device is an error). Measurements below the floor are
discarded, not recorded. All data is generated ON DEVICE; recall is
computed on device against exact ground truth and only scalars leave
the chip.

The corpus is split into equal parts of at most 500k rows sharing ONE
compiled executable per algorithm (index as jit argument), and per-part
top-k results are merged exactly (knn_merge_parts) — the single-chip
form of the reference's data-sharded MNMG search
(detail/knn_merge_parts.cuh:172). The scale comes from
``RAFT_TPU_BENCH_SCALE`` alone (micro | small | mid | full).
"""
import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# --- reference baselines (QPS @ recall@10 = 0.95, 10k-query batches) -----
# RAFT 24.02 publishes QPS-vs-recall Pareto PLOTS, not numeric tables
# (docs/source/raft_ann_benchmarks.md:255-257; the positioning claim —
# CAGRA outperforming CPU HNSW and GPU state of the art at all recall
# levels — is README.md:74). The numbers below are therefore derived
# A100-class estimates; each derivation is pinned to the reference file
# it reads from and reported in the output so vs_baseline is traceable.
BASELINES = {
    "raft_brute_force": {
        "qps": 300_000.0,
        "derivation": (
            "GEMM+select design of detail/knn_brute_force.cuh:61: A100 "
            "TF32 peak ~156 TFLOP/s, 2*n*d = 256 MFLOP/query at 1Mx128 "
            "-> ~600k QPS GEMM ceiling; ~2x tiled select_k overhead -> "
            "300k"),
    },
    "raft_ivf_flat": {
        "qps": 50_000.0,
        "derivation": (
            "list-scan bandwidth bound (ivf_flat_interleaved_scan-inl."
            "cuh): nprobe=20 of nlist=1024 over 1Mx128xf32 reads ~10-30 "
            "MB/query depending on imbalance; A100 HBM 1.55 TB/s -> "
            "~50k QPS. Param envelope: ann_benchmarks_param_tuning.md:"
            "10-33"),
    },
    "raft_ivf_pq": {
        "qps": 200_000.0,
        "derivation": (
            "same probe fraction over 64B codes (ivf_pq_compute_"
            "similarity-inl.cuh:271 LUT scan) = ~8x less traffic than "
            "ivf_flat -> ~400k ceiling; LUT + refine overhead ~2x -> "
            "200k. Param envelope: ann_benchmarks_param_tuning.md:34-68"),
    },
    "raft_cagra": {
        "qps": 500_000.0,
        "derivation": (
            "published H100 batch-10 Pareto plots put graph search at "
            "~500k-1M QPS @0.95 for million-scale corpora (raft_ann_"
            "benchmarks.md:255-257, img/raft-vector-search-batch-10."
            "png); 500k is the conservative read"),
    },
}
BASELINE_QPS = {k: v["qps"] for k, v in BASELINES.items()}

# corpus geometry: a LOW-INTRINSIC-DIMENSION clustered mixture. Real ANN
# corpora (SIFT ~16 effective dims in 128 ambient) are hard for IVF
# because neighborhoods straddle partition boundaries in the low-dim
# manifold; full-rank gaussian clusters are trivially recoverable at any
# nprobe (measured: recall@np20 = 1.0 for every full-rank variant —
# scratch/exp_corpus_hard.py). Queries are fresh mixture draws, never
# perturbed corpus rows.
CORPUS_SCALE = float(os.environ.get("RAFT_TPU_BENCH_CSCALE", "1.0"))
CORPUS_INTRINSIC_D = int(os.environ.get("RAFT_TPU_BENCH_INTRINSIC_D", "16"))
CORPUS_CLUSTERS = int(os.environ.get("RAFT_TPU_BENCH_NCLUSTERS", "200"))


def robust_call(fn, what: str, tries: int = 3, deadline: float = 0.0):
    """Run a build/setup stage with retries (same transport-flake story as
    median_time; builds are minutes of work we must not lose to one
    dropped connection).

    ``deadline``: absolute ``time.perf_counter()`` cutoff — when a retry
    would start past it, give up immediately instead."""
    for t in range(tries):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001
            log(f"# {what}: attempt {t + 1}/{tries} failed: "
                f"{type(e).__name__}: {e}")
            if t + 1 == tries:
                raise
            if deadline and time.perf_counter() > deadline:
                log(f"# {what}: stage deadline passed; not retrying")
                raise
            time.sleep(20 * (t + 1))


def median_time(fn, *args, reps=5, tries=3, floor=0.0):
    """Per-call-blocked median (latency). Returns None after ``tries``
    consecutive failures or when the median is below its floor."""
    from raft_tpu.ops.autotune import TimingUnreliableError, measure

    for t in range(tries):
        try:
            return measure(fn, *args, reps=reps, suspect_floor_s=floor)
        except TimingUnreliableError as e:
            log(f"# measurement unreliable (no retry): {e}")
            return None
        except Exception as e:  # noqa: BLE001 - transport/compile flakes
            log(f"# measurement attempt {t + 1}/{tries} failed: "
                f"{type(e).__name__}: {e}")
            if t + 1 < tries:
                time.sleep(15 * (t + 1))
    return None


@contextlib.contextmanager
def algo_section(name):
    """One algorithm's persistent failure (or a deliberate budget skip)
    must not cost the whole run its output line: log and continue with
    the entries recorded so far."""
    try:
        yield
    except Exception as e:  # noqa: BLE001
        log(f"# {name} section ended early ({type(e).__name__}: {e}); "
            "continuing with remaining algos")


def make_corpus(n, d, nq, n_clusters=None, seed=0, scale=None,
                intrinsic_d=None):
    """The shared on-device corpus generator (bench/datasets.py) at this
    bench's env-tunable mixture knobs."""
    from raft_tpu.bench.datasets import make_corpus as _make

    return _make(n, d, nq,
                 n_clusters=CORPUS_CLUSTERS if n_clusters is None
                 else n_clusters, seed=seed,
                 scale=CORPUS_SCALE if scale is None else scale,
                 intrinsic_d=CORPUS_INTRINSIC_D if intrinsic_d is None
                 else intrinsic_d)


def device_recall(ids, gt):
    """Mean recall@k, computed on device; one scalar leaves the chip."""
    hit = jnp.any(ids[:, :, None] == gt[:, None, :], axis=2) & (gt >= 0)
    return float(jnp.sum(hit) / jnp.sum(gt >= 0))


def exercise_fbin_io(data, rows=100_000):
    """Round-trip a corpus slice through the raft-ann-bench fbin loader
    (bench/datasets.py) so the recorded artifact exercises the dataset IO
    path; returns the artifact note. Deliberately outside all timed
    sections."""
    from raft_tpu.bench import datasets as bds

    rows = min(rows, len(data))
    path = "/tmp/raft_tpu_bench_corpus.fbin"
    host = np.asarray(data[:rows])
    bds.write_fbin(path, host)
    back = bds.read_fbin(path)
    ok = back.shape == host.shape and bool(np.array_equal(back, host))
    os.remove(path)
    return {"fbin_roundtrip_rows": rows, "ok": ok}


class TwoPart:
    """Search a corpus split into equal-shape parts with ONE compiled
    executable, merging per-part top-k exactly. ``search_jit`` must be a
    jitted (queries, index, *extra) -> (dist, ids) callable with
    part-local ids; ``offsets`` map part-local ids to global; ``extras``
    optionally zips additional per-part jit arguments (e.g. a bf16 refine
    corpus). Indexes ride as jit ARGUMENTS, never closures (baked index
    constants would make every compile request index-sized)."""

    def __init__(self, search_jit, indexes, offsets, k, extras=None):
        from raft_tpu.neighbors import brute_force as _bf

        self.search_jit = search_jit
        self.indexes = indexes
        self.offsets = offsets
        self.extras = extras or [()] * len(indexes)
        self._merge = jax.jit(
            lambda d, i: _bf.knn_merge_parts(d, i, True))
        self.k = k

    def __call__(self, q, *_):
        ds, is_ = [], []
        for idx, off, extra in zip(self.indexes, self.offsets, self.extras):
            d, i = self.search_jit(q, idx, *extra)
            ds.append(d[:, : self.k])
            is_.append(jnp.where(i[:, : self.k] >= 0,
                                 i[:, : self.k] + off, -1))
        if len(ds) == 1:
            return ds[0], is_[0]
        return self._merge(jnp.stack(ds), jnp.stack(is_))


def store_bytes_of(indexes) -> dict:
    """{store_bytes, bytes_per_vector} for an index or list of part
    indexes, via the memz decomposition (serve/quality.device_bytes) —
    the storage-ladder evidence block recorded on cagra/ivf entries
    (ISSUE 13). Host-streamed indexes divide by ALL answered rows (cold
    included), so the number IS the rung's capacity claim."""
    from raft_tpu.serve import quality as _q

    idxs = indexes if isinstance(indexes, (list, tuple)) else [indexes]
    reps = [_q.device_bytes(ix) for ix in idxs]
    total = sum(r["total_device_bytes"] for r in reps)
    rows = sum(int(r.get("n_total") or r["n"]) for r in reps)
    return {"store_bytes": total,
            "bytes_per_vector": round(total / max(rows, 1), 2)}


def run_storage_ladder(lad_n: int, d: int, nq: int = 1000, k: int = 10,
                       out_json: str = None, graph_degree: int = 32,
                       hbm_budget_frac: float = 0.5) -> list:
    """Storage-ladder capacity rung (ROADMAP "Scale ladder, rung 1"):
    one corpus at ``lad_n`` rows, every cagra edge-store rung
    (int8 → int4 → pq) measured at fixed k with the exact-refine
    recipe, then the ivf_flat HBM-resident vs host-streamed
    decomposition under an HBM budget of ``hbm_budget_frac`` of the
    resident store. Each entry records ``store_bytes``,
    ``bytes_per_vector`` and the ratio vs the int8 rung — the
    ladder's capacity claims as bench artifacts, not README math.

    Standalone so the 10M TPU run and the CPU-gated proxy
    (``RAFT_TPU_BENCH_LADDER_N``) share one code path; ``main()`` wires
    it behind RAFT_TPU_BENCH_LADDER."""
    from raft_tpu.neighbors import (brute_force, cagra, ivf_flat,
                                    refine as refine_mod)

    entries = []
    t0 = time.perf_counter()
    data, queries = make_corpus(lad_n, d, nq, seed=21)
    qj = jnp.asarray(queries)
    # exact GT through the parted brute path (compile-cap safe at 10M)
    gt = jnp.asarray(np.argsort(
        (queries**2).sum(1)[:, None] - 2.0 * queries @ data[:100_000].T
        + (data[:100_000]**2).sum(1)[None, :],
        axis=1)[:, :k]) if lad_n <= 100_000 else None
    if gt is None:
        part_cap = 500_000
        parts = [data[i:i + part_cap] for i in range(0, lad_n, part_cap)]
        bfs = [brute_force.build(p) for p in parts]
        fn = jax.jit(lambda q, ix: brute_force.search(ix, q, k,
                                                      algo="matmul"))
        tp = TwoPart(fn, bfs,
                     [i * part_cap for i in range(len(parts))], k)
        gt = robust_call(lambda: tp(qj)[1], "ladder gt")
        del bfs
    log(f"# ladder corpus {lad_n}x{d} + gt in "
        f"{time.perf_counter() - t0:.0f}s")

    t0 = time.perf_counter()
    ci = robust_call(lambda: cagra.build(data, cagra.IndexParams(
        graph_degree=graph_degree,
        intermediate_graph_degree=graph_degree + graph_degree // 2,
        seed=0)), "ladder cagra build", tries=1)
    build_s = time.perf_counter() - t0
    log(f"# ladder cagra built in {build_s:.0f}s")
    dj = jnp.asarray(data)
    itopk = max(64, 4 * k)
    sp = cagra.SearchParams(itopk_size=itopk, search_width=2,
                            max_iterations=10)

    def refined(qs):
        _, cand = cagra.search(ci, qs, itopk, sp, engine="edge")
        return refine_mod.refine(dj, qs, cand, k)

    rung_bytes = {}
    for rung in ("int8", "int4", "pq"):
        ci.__dict__.pop("_edge_store", None)
        t0 = time.perf_counter()
        robust_call(lambda r=rung: cagra.prepare_traversal(ci, r),
                    f"ladder prepare {rung}", tries=1)
        prep_s = time.perf_counter() - t0
        sb = store_bytes_of(ci)
        ev = ci._edge_store[1]
        rung_bytes[rung] = int(ev.size * ev.dtype.itemsize)
        thr = median_time(lambda: jax.block_until_ready(
            refined(qj)), reps=3)
        rec = robust_call(lambda: device_recall(refined(qj)[1], gt),
                          f"ladder {rung} recall")
        e = {"algo": "storage_ladder",
             "name": f"storage_ladder.cagra.deg{graph_degree}.{rung}",
             "qps": round(nq / thr, 1) if thr else None,
             "latency_ms": None,
             "recall": round(float(rec), 4),
             "build_s": round(build_s + prep_s, 1),
             "corpus_n": lad_n, "engine": "edge",
             "edge_store_bytes": rung_bytes[rung],
             "edge_bytes_per_vector": round(rung_bytes[rung] / lad_n, 2),
             **sb}
        if "int8" in rung_bytes:
            e["edge_bytes_vs_int8"] = round(
                rung_bytes["int8"] / max(rung_bytes[rung], 1), 2)
        entries.append(e)
        log(f"#   {e['name']}: qps={e['qps']} recall={rec:.4f} "
            f"edge store {rung_bytes[rung]:,}B "
            f"({e.get('edge_bytes_vs_int8', 1.0)}x under int8)")
    ci.__dict__.pop("_edge_store", None)

    # ivf_flat: resident vs host-streamed under an HBM budget
    n_lists = max(64, min(8192, int(np.sqrt(lad_n) * 3)))
    fi = robust_call(lambda: ivf_flat.build(
        data, ivf_flat.IndexParams(n_lists=n_lists, seed=0)),
        "ladder ivf build", tries=1)
    ivf_flat.prepare_scan(fi)
    spf = ivf_flat.SearchParams(n_probes=max(8, n_lists // 50))
    res_bytes = store_bytes_of(fi)
    t_res = median_time(lambda: jax.block_until_ready(
        ivf_flat.search(fi, qj, k, spf, algo="pallas")), reps=3)
    rec_res = robust_call(lambda: device_recall(
        ivf_flat.search(fi, qj, k, spf, algo="pallas")[1], gt),
        "ladder ivf resident recall")
    # budget against the RAW list rows (what the planner admits), not
    # the memz total (which counts scan caches the tier doesn't move)
    budget_gb = lad_n * (d * 4 + 8) * hbm_budget_frac / (1 << 30)
    ivf_flat.prepare_host_stream(fi, budget_gb=budget_gb,
                                 sample_queries=queries[:256])
    tier = getattr(fi, "_host_tier", None)
    t_hs = median_time(lambda: jax.block_until_ready(
        ivf_flat.search(fi, qj, k, spf, algo="pallas")), reps=3)
    rec_hs = robust_call(lambda: device_recall(
        ivf_flat.search(fi, qj, k, spf, algo="pallas")[1], gt),
        "ladder ivf streamed recall")
    hs_bytes = store_bytes_of(fi)
    entries.append({
        "algo": "storage_ladder",
        "name": f"storage_ladder.ivf_flat.nlist{n_lists}.host_stream",
        "qps": round(nq / t_hs, 1) if t_hs else None, "latency_ms": None,
        "recall": round(float(rec_hs), 4), "build_s": 0.0,
        "corpus_n": lad_n, "hbm_budget_gb": round(budget_gb, 3),
        # the HBM-resident vs host-streamed decomposition the ROADMAP
        # bench gate asks for: where the bytes sit, what PCIe moved,
        # and what the split cost at fixed probes
        "decomposition": {
            "resident_qps": round(nq / t_res, 1) if t_res else None,
            "resident_recall": round(float(rec_res), 4),
            "resident_store_bytes": res_bytes["store_bytes"],
            "streamed_device_bytes": hs_bytes["store_bytes"],
            "host_tier": tier.snapshot() if tier is not None else None,
        },
        **hs_bytes})
    log(f"#   host_stream: resident {res_bytes['store_bytes']:,}B -> "
        f"device {hs_bytes['store_bytes']:,}B + host tier; streamed "
        f"recall {rec_hs:.4f} (resident {rec_res:.4f}) at "
        f"{budget_gb:.3f} GB budget")

    if out_json:
        payload = {"schema": "raft_tpu_bench_v1", "lane": "storage_ladder",
                   "n": lad_n, "d": d, "entries": entries}
        os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
        tmp = out_json + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out_json)
        log(f"# ladder artifact -> {out_json}")
    return entries


def run_fleet_ladder(n: int, d: int, nq: int = 256, k: int = 10,
                     out_json: str = None, hosts: int = 2, devs: int = 2,
                     hbm_budget_frac: float = 0.5) -> list:
    """Fleet storage-ladder rung (ISSUE 19 / docs/mnmg.md "Per-host
    storage tiers"): one virtual ``hosts × devs`` fleet, every
    ``FLEET_STORE_RUNGS`` rung built under a per-host HBM budget of
    ``hbm_budget_frac`` × the f32 resident rows, measured end-to-end
    through :meth:`Fleet.search` (resident + host-streamed cold lists).
    Each entry records rows/host, device bytes/host (budgeted AND
    unbudgeted-resident), host-tier bytes/host, recall, and the bytes
    ratio vs the float32 rung — the per-host capacity claims as
    artifacts, not README math. Exact rungs (float32/int8/int4)
    additionally assert bit-parity against their unbudgeted build: a
    capacity number from a build that changed the answers would be
    worthless. Run with ``d >= 64``: below that the int4 rung's 64-byte
    sublane-pair padding (``quant.int4_half_width``) dominates and the
    ladder is not byte-monotone."""
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.parallel import fleet as fleet_mod
    from raft_tpu.serve import quality as _q

    fl = fleet_mod.Fleet.virtual(hosts, devs)
    data, queries = make_corpus(n, d, nq, seed=23)
    data = np.asarray(data, np.float32)       # host packing wants numpy
    queries = np.asarray(queries, np.float32)
    qj = jnp.asarray(queries)
    gt = np.argsort(
        (queries ** 2).sum(1)[:, None] - 2.0 * queries @ data.T
        + (data ** 2).sum(1)[None, :], axis=1)[:, :k]

    n_lists = max(8, min(256, int(np.sqrt(n))))
    pq_dim = max(4, d // 4)
    # pq_bits=4: the edge-store books (16 entries/subspace). At bench
    # corpus sizes an 8-bit book is a ~400 KB fixed cost that swamps the
    # codes and would make the per-host capacity ratio measure the
    # quantizer, not the ladder; at fleet corpus sizes it amortizes away.
    p0 = ivf_pq.IndexParams(n_lists=n_lists, pq_dim=pq_dim, pq_bits=4,
                            seed=0)
    n_probes = max(4, n_lists // 8)
    rows_host = -(-n // hosts)
    budget_b = int(rows_host * fleet_mod.store_row_bytes("float32", d)
                   * hbm_budget_frac)

    def host_recall(ids):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[m]) & set(gt[m])) / k
                              for m in range(nq)]))

    def per_host_bytes(idx):
        rep = _q.device_bytes(idx)
        return (int(rep["total_device_bytes"]) // fl.n_shards
                * fl.topology.devs_per_host)

    entries = []
    f32_bytes_host = f32_resident_host = None
    for rung in fleet_mod.FLEET_STORE_RUNGS:
        sp = (ivf_pq.SearchParams(n_probes=n_probes) if rung == "pq"
              else ivf_flat.SearchParams(n_probes=n_probes))
        idx0 = robust_call(lambda r=rung: fl.build_ivf_pq(
            data, p0, store_dtype=r), f"fleet ladder {rung} build",
            tries=1)
        d0, i0, _ = fl.search(idx0, qj, k, sp)
        bytes0_host = per_host_bytes(idx0)
        idx = robust_call(lambda r=rung: fl.build_ivf_pq(
            data, p0, store_dtype=r, hbm_budget_gb=budget_b / (1 << 30),
            sample_queries=queries), f"fleet ladder {rung} budgeted",
            tries=1)
        d1, i1, _ = fl.search(idx, qj, k, sp)
        if rung != "pq":
            assert (np.array_equal(np.asarray(d0), np.asarray(d1))
                    and np.array_equal(np.asarray(i0), np.asarray(i1))), \
                f"budgeted {rung} diverged from unbudgeted build"
        thr = median_time(lambda: jax.block_until_ready(
            fl.search(idx, qj, k, sp)[0]), reps=3)
        bytes_host = per_host_bytes(idx)
        tier_host = max(
            (sum(int(idx._fleet_tiers[s].host_bytes)
                 for s in fl.topology.shards_of(h)
                 if s in idx._fleet_tiers) for h in range(hosts)),
            default=0)
        cold = {h: int((~m).sum())
                for h, m in idx._fleet_ctx["hot"].items()}
        if rung == "float32":
            f32_bytes_host = bytes_host
            f32_resident_host = bytes0_host
        e = {"algo": "fleet_ladder",
             "name": f"fleet_ladder.{hosts}x{devs}.{rung}",
             "qps": round(nq / thr, 1) if thr else None,
             "latency_ms": None,
             "recall": round(host_recall(i1), 4),
             "recall_unbudgeted": round(host_recall(i0), 4),
             "build_s": 0.0, "corpus_n": n,
             "store": rung, "topology": f"{hosts}x{devs}",
             "rows_per_host": rows_host,
             "device_bytes_per_host": bytes_host,
             "device_bytes_per_host_unbudgeted": bytes0_host,
             "host_tier_bytes_per_host": tier_host,
             "bytes_per_vector": round(bytes_host / rows_host, 2),
             "hbm_budget_bytes_per_host": budget_b,
             "cold_lists_per_host": cold,
             "bitwise_vs_unbudgeted": rung != "pq"}
        if f32_bytes_host:
            e["bytes_vs_float32"] = round(
                bytes_host / max(f32_bytes_host, 1), 4)
            # the ISSUE acceptance ratio: budgeted bytes vs the FULLY
            # RESIDENT f32 build (what an unladdered fleet would hold)
            e["bytes_vs_float32_resident"] = round(
                bytes_host / max(f32_resident_host, 1), 4)
        entries.append(e)
        log(f"#   {e['name']}: qps={e['qps']} recall={e['recall']} "
            f"bytes/host {bytes_host:,} "
            f"({e.get('bytes_vs_float32', 1.0)}x of f32) "
            f"cold={cold}")

    if out_json:
        payload = {"schema": "raft_tpu_bench_v1", "lane": "fleet_ladder",
                   "n": n, "d": d, "topology": f"{hosts}x{devs}",
                   "hbm_budget_bytes_per_host": budget_b,
                   "entries": entries}
        os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
        tmp = out_json + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out_json)
        log(f"# fleet ladder artifact -> {out_json}")
    return entries


def run_filter_sweep(n: int, d: int, nq: int = 100, k: int = 10,
                     out_json: str = None) -> list:
    """Filtered-search selectivity sweep (docs/perf.md "Filtered
    search"): at each filtered-out fraction × family, measure the
    ADAPTIVE policy (survivor-aware pruning + auto-widening +
    survivor-brute crossover — the defaults) against the FIXED policy
    (widen ladder pinned to level 1, crossover disabled), recording
    recall against the exact filtered oracle, p50 batch latency, the
    decision the policy took (widen level, effective probes, lists
    pruned, crossover routing) and the measured scan-vs-brute race
    verdict under the selectivity-bucketed autotune key. The summary
    block carries the acceptance verdicts: at 99.9% filtered-out the
    adaptive policy must hold ≥0.95× the family's unfiltered recall
    where the fixed policy collapses, and the survivor-brute must beat
    the widened scan. Standalone; ``main()`` wires it behind
    RAFT_TPU_BENCH_FILTER."""
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import cagra, ivf_flat, ivf_pq
    from raft_tpu.ops import filter_policy

    data, queries = make_corpus(n, d, nq, seed=33)
    X, Q = np.asarray(data), np.asarray(queries)
    qj = jnp.asarray(queries)
    rng = np.random.default_rng(51)

    def oracle(mask):
        """Exact filtered top-k ids, -1-padded past the survivor count."""
        ids = np.nonzero(mask)[0]
        sub = X[ids]
        dd = ((Q ** 2).sum(1)[:, None] + (sub ** 2).sum(1)[None, :]
              - 2.0 * Q @ sub.T)
        order = np.argsort(dd, axis=1, kind="stable")[:, :min(k, ids.size)]
        out = np.full((nq, k), -1, np.int64)
        out[:, :order.shape[1]] = ids[order]
        return out

    def recall_of(found, want):
        found = np.asarray(found)
        hits = sum(len(set(found[i][found[i] >= 0].tolist())
                       & set(want[i][want[i] >= 0].tolist()))
                   for i in range(found.shape[0]))
        return hits / max(int((want >= 0).sum()), 1)

    def with_env(tmp, fn):
        old = {kk: os.environ.get(kk) for kk in tmp}
        os.environ.update(tmp)
        try:
            return fn()
        finally:
            for kk, vv in old.items():
                if vv is None:
                    os.environ.pop(kk, None)
                else:
                    os.environ[kk] = vv

    gt = oracle(np.ones(n, bool))
    n_probes = 8
    fi = robust_call(lambda: ivf_flat.build(
        data, ivf_flat.IndexParams(n_lists=64, seed=0)),
        "filter ivf_flat build", tries=1)
    pi = robust_call(lambda: ivf_pq.build(
        data, ivf_pq.IndexParams(n_lists=64, pq_dim=16, seed=0)),
        "filter ivf_pq build", tries=1)
    ci = robust_call(lambda: cagra.build(data, cagra.IndexParams(
        graph_degree=32, intermediate_graph_degree=48, seed=0)),
        "filter cagra build", tries=1)
    spf = ivf_flat.SearchParams(n_probes=n_probes)
    spp = ivf_pq.SearchParams(n_probes=n_probes)
    spc = cagra.SearchParams(itopk_size=max(64, 4 * k))
    fams = {
        "ivf_flat": lambda f: ivf_flat.search(fi, qj, k, spf, filter=f),
        "ivf_pq": lambda f: ivf_pq.search(pi, qj, k, spp, filter=f),
        "cagra": lambda f: cagra.search(ci, qj, k, spc, filter=f),
    }
    brutes = {
        "ivf_flat": lambda f: filter_policy.survivor_brute_ivf(
            fi, ivf_flat.reconstruct, qj, k, f),
        "ivf_pq": lambda f: filter_policy.survivor_brute_ivf(
            pi, ivf_pq.reconstruct, qj, k, f),
        "cagra": lambda f: filter_policy.survivor_brute_dense(
            ci.dataset, ci.metric, qj, k, f),
    }
    unfiltered = {fam: round(recall_of(fn(None)[1], gt), 4)
                  for fam, fn in fams.items()}
    log(f"# filter sweep {n}x{d} nq={nq} k={k}; unfiltered recall "
        + " ".join(f"{f}={r}" for f, r in unfiltered.items()))

    FIXED = {"RAFT_TPU_FILTER_WIDEN_MAX": "1",
             "RAFT_TPU_FILTER_BRUTE_MAX": "0"}
    SCAN_ONLY = {"RAFT_TPU_FILTER_BRUTE_MAX": "0"}
    entries, extreme = [], {}
    for frac_out in (0.5, 0.9, 0.99, 0.999):
        surv_n = max(k, int(round(n * (1.0 - frac_out))))
        mask = np.zeros(n, bool)
        mask[rng.choice(n, surv_n, replace=False)] = True
        want = oracle(mask)
        selectivity = surv_n / n
        for fam, fn in fams.items():
            bs = Bitset.from_mask(jnp.asarray(mask))
            if fam == "cagra":
                fd = filter_policy.decide_graph(bs, n, d, k)
            else:
                fd = filter_policy.decide_ivf(
                    fi if fam == "ivf_flat" else pi, bs, n_probes, k, fam)
            t_ad = median_time(lambda: jax.block_until_ready(
                fn(bs)[1]), reps=3)
            r_ad = recall_of(fn(bs)[1], want)
            t_fx = with_env(FIXED, lambda: median_time(
                lambda: jax.block_until_ready(fn(bs)[1]), reps=3))
            r_fx = with_env(FIXED, lambda: recall_of(fn(bs)[1], want))
            # race the widened scan vs the compacted brute under the
            # bucketed key — the recorded winner steers later filtered
            # calls in this selectivity decade
            _key, winner, timings = filter_policy.tune_crossover(
                fam, n, d, k, selectivity,
                lambda: with_env(SCAN_ONLY, lambda: fn(bs)[1]),
                lambda: brutes[fam](bs)[1], reps=2)
            e = {"algo": "filter_sweep",
                 "name": f"filter_sweep.{fam}.out{frac_out}",
                 "family": fam, "filtered_out": frac_out,
                 "selectivity": round(selectivity, 6),
                 "survivors": surv_n,
                 "qps": round(nq / t_ad, 1) if t_ad else None,
                 "latency_ms": round(t_ad * 1e3, 2) if t_ad else None,
                 "recall": round(r_ad, 4),
                 "unfiltered_recall": unfiltered[fam],
                 "widen_level": fd.level,
                 "effective_probes": fd.n_probes or None,
                 "lists_pruned": fd.lists_pruned or None,
                 "crossover": bool(fd.use_brute),
                 "fixed_policy": {
                     "recall": round(r_fx, 4),
                     "latency_ms": round(t_fx * 1e3, 2) if t_fx else None},
                 "race": {"winner": winner,
                          "scan_s": round(timings.get("scan", 0), 4),
                          "brute_s": round(timings.get("brute", 0), 4)}}
            entries.append(e)
            if frac_out == 0.999:
                extreme[fam] = e
            log(f"#   {e['name']}: adaptive recall={r_ad:.4f} "
                f"({t_ad * 1e3:.1f}ms, level={fd.level} "
                f"pruned={fd.lists_pruned} brute={fd.use_brute}) "
                f"fixed recall={r_fx:.4f} ({t_fx * 1e3:.1f}ms) "
                f"race->{winner}")

    summary = {fam: {
        "adaptive_holds": e["recall"] >= 0.95 * e["unfiltered_recall"],
        "fixed_collapses": e["fixed_policy"]["recall"]
        < 0.95 * e["unfiltered_recall"],
        "brute_beats_scan": e["race"]["brute_s"] < e["race"]["scan_s"],
    } for fam, e in extreme.items()}
    for fam, v in summary.items():
        log(f"#   extreme-point verdict {fam}: {v}")

    if out_json:
        payload = {"schema": "raft_tpu_bench_v1", "lane": "filter_sweep",
                   "n": n, "d": d, "nq": nq, "k": k,
                   "unfiltered_recall": unfiltered,
                   "extreme_point_verdicts": summary,
                   "entries": entries}
        os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
        tmp = out_json + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out_json)
        log(f"# filter sweep artifact -> {out_json}")
    return entries


def main():
    from raft_tpu.utils import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.abspath(__file__)))
    budget_s = float(os.environ.get("RAFT_TPU_BENCH_BUDGET_S", "2400"))
    scale = os.environ.get("RAFT_TPU_BENCH_SCALE", "full")
    t_start = time.perf_counter()
    # micro: CPU-runnable harness smoke; small: single-chip quick run;
    # mid: one 500k part; full: the BASELINE 1M scale as two 500k parts
    n = {"full": 1_000_000, "mid": 500_000, "small": 100_000,
         "micro": 20_000}[scale]
    part_n = min(n, 500_000)
    n = (n // part_n) * part_n
    n_parts = n // part_n
    d, nq, k = 128, 10_000 if scale != "micro" else 1_000, 10
    # generic plausibility floor: no 10k-query search of this corpus
    # finishes faster (the per-lane floors below are tighter)
    suspect_floor = 0.001 if scale == "micro" else 0.002

    from raft_tpu.bench import roofline

    # published peaks of this chip: an unknown device raises here
    peak = roofline.peaks()
    peak_flops, peak_hbm = peak["bf16_flops"], peak["hbm_bytes_per_s"]
    from raft_tpu.ops import autotune as _autotune
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq, refine

    log(f"# corpus: {n}x{d} ({n_parts} part(s) of {part_n}), {nq} queries, "
        f"k={k}, mixture scale {CORPUS_SCALE}")
    data, queries = robust_call(lambda: make_corpus(n, d, nq), "corpus")
    parts = [data[i * part_n : (i + 1) * part_n] for i in range(n_parts)]
    offsets = [i * part_n for i in range(n_parts)]

    # ground truth: exact search over each part with one shared
    # executable, exact cross-part merge; query chunks give retries a
    # small failure unit
    # one jit object shared by the main GT stage and the capacity lane:
    # both search (1000, d) query chunks against 500k-part indexes, so
    # the capacity lane's ground truth is a cache hit, not a recompile
    gt_search_jit = jax.jit(lambda q, idx: brute_force.search(
        idx, q, k, algo="matmul"))

    def compute_gt():
        bfs = [brute_force.build(p, metric="sqeuclidean") for p in parts]
        tp = TwoPart(gt_search_jit, bfs, offsets, k)
        gchunk = 1000
        gt_deadline = t_start + 0.35 * budget_s
        big = part_n > 100_000
        parts_out = []
        for c0 in range(0, nq, gchunk):
            if big and time.perf_counter() > gt_deadline:
                raise RuntimeError(
                    f"ground truth stage deadline exceeded at [{c0}]")
            parts_out.append(robust_call(
                lambda c0=c0: jax.block_until_ready(
                    tp(queries[c0 : c0 + gchunk])[1]),
                f"ground truth [{c0}:{c0 + gchunk}]", tries=5,
                deadline=gt_deadline if big else 0.0))
        return bfs, jnp.concatenate(parts_out)

    try:
        bfs, gt = compute_gt()
    except Exception as e:  # noqa: BLE001
        if n <= 100_000:
            raise
        log(f"# part-scale ground truth failed ({type(e).__name__}): "
            "regenerating a 100k corpus and continuing")
        n = part_n = 100_000
        n_parts, scale = 1, "small"
        data, queries = robust_call(lambda: make_corpus(n, d, nq), "corpus")
        parts, offsets = [data], [0]
        bfs, gt = compute_gt()
    log("# ground truth done")
    gt_elapsed = time.perf_counter() - t_start
    hurry = gt_elapsed > budget_s / 6
    if hurry:
        log(f"# slow backend (corpus+GT took {gt_elapsed:.0f}s): "
            "trimming sweeps")

    entries = []

    def add_entry(algo, name, dt_thr, dt_lat, recall, build_s, extra=None,
                  batch=None, baseline_key="algo"):
        """``baseline_key``: "algo" (default) normalizes vs_baseline by the
        algo's 1M-lane reference QPS; None omits the ratio — entries whose
        corpus shape doesn't match the baseline derivation (the 2M
        capacity lane) must not report an apples-to-oranges number."""
        qps = (batch or nq) / dt_thr if dt_thr else 0.0
        e = {"algo": algo, "name": name, "qps": round(qps, 1),
             "latency_ms": round(dt_lat * 1e3, 1) if dt_lat else -1.0,
             "recall": round(recall, 4), "build_s": round(build_s, 1)}
        if baseline_key is not None:
            key = algo if baseline_key == "algo" else baseline_key
            e["vs_baseline"] = round(qps / BASELINE_QPS[key], 3)
        if extra:
            e.update(extra)
        entries.append(e)
        log(f"#   {name}: qps={qps:,.0f} (lat "
            f"{e['latency_ms']}ms) recall={recall:.4f}")
        return e

    # physically-derived per-lane plausibility floors (seconds/call):
    # the chip's PUBLISHED peaks (roofline.PEAKS) — no real call can
    # beat them, so a timing below one is a broken measurement.
    def floor_brute():
        return max(suspect_floor, 2.0 * nq * n * d / peak_flops)

    def floor_ivf(probes, row_bytes):
        # the query-grouped scan DMAs each probed list ONCE per 128-query
        # group (ops/ivf_scan.py pack_pairs), so kernel traffic scales
        # with (pairs/128) list windows — NOT per-query row counts; a
        # per-query model here once rejected an honest 92 ms measurement
        # with a 122 ms "floor"
        groups = nq * probes / 128.0
        window_rows = 1.5 * (part_n / 1024)   # imbalance slack
        scanned = groups * window_rows * row_bytes * n_parts
        return max(suspect_floor, scanned / peak_hbm)

    def floor_ivf_for(probes, row_bytes, batch_q, parts):
        """floor_ivf generalized to another corpus shape (the capacity
        lane): same scan-traffic model, same suspect_floor clamp."""
        groups = batch_q * probes / 128.0
        scanned = groups * 1.5 * (part_n / 1024) * row_bytes * parts
        return max(suspect_floor, scanned / peak_hbm)

    def measure_wall(tp, *args, floor=0.0, what="", calls: int = 10,
                     qset=None):
        """THE throughput measurement: pipelined, content-distinct,
        value-read wall.

        ``calls`` query sets with genuinely different CONTENT
        (device-side permutations) are dispatched back-to-back (no
        per-call blocking — dispatch overlaps compute, GBench
        items_per_second semantics), every call's output feeds a scalar
        accumulator, and the window closes with a host-side ``float()``
        of that accumulator: a host value transitively dependent on
        every output cannot materialize before the compute actually
        ran. The single read's round trip amortizes over ``calls``.
        Results below the lane's physical floor are discarded."""
        qs = queries if qset is None else qset
        try:
            # calls+1 permutations: the warm-up runs on a THROWAWAY set so
            # no timed call repeats content the backend has already served
            perms = [jnp.take(qs,
                              jax.random.permutation(
                                  jax.random.PRNGKey(100 + i), qs.shape[0]),
                              axis=0)
                     for i in range(calls + 1)]
            jax.block_until_ready(perms)
            d0 = tp(perms.pop(), *args[1:])[0]      # warm/compile
            float(jnp.sum(jnp.where(jnp.isfinite(d0[:, 0]), d0[:, 0], 0.0)))
            t0 = time.perf_counter()
            acc = None
            for p in perms:
                d = tp(p, *args[1:])[0]
                s = jnp.sum(jnp.where(jnp.isfinite(d[:, 0]), d[:, 0], 0.0))
                acc = s if acc is None else acc + s
            _ = float(acc)                          # forced value read
            dt = (time.perf_counter() - t0) / calls
        except Exception as e:  # noqa: BLE001
            log(f"# {what} wall measurement failed: "
                f"{type(e).__name__}: {e}")
            return None
        if dt < floor:
            log(f"# {what} wall {dt*1e3:.1f}ms below the physical floor "
                f"{floor*1e3:.1f}ms; lane unmeasurable in this window")
            return None
        return dt

    def measure_tp(tp, *args, reps=5, floor=None, what="", qset=None):
        """(throughput s/call, latency s/call). Throughput is the
        value-read pipelined wall; latency is the per-call-blocked
        median (reported for context, dropped below its floor)."""
        floor = suspect_floor if floor is None else floor
        lat = median_time(tp, *args, reps=reps, floor=floor)
        thr = measure_wall(tp, *args, floor=floor, what=what, qset=qset)
        return thr, lat

    # --- brute force (BASELINE config 1): measured-best engine ----------
    with algo_section('brute_force'):
        winner, timings = robust_call(
            lambda: brute_force.tune_search(bfs[0], queries, k, reps=3,
                                            suspect_floor_s=suspect_floor),
            "engine autotune")

        # per-engine decomposition: WHY the headline moved, not just that
        # it did. gemm_only times the bare distance GEMM (no select) on
        # one part; select_overhead is the GEMM engine's select cost on
        # top of it; fused_tflops is the fused engine's sustained rate
        # from the same race reps. All rates are per-part (scale-free).
        decomp = {}
        try:
            flops_part = 2.0 * nq * part_n * d

            def _gemm_only(qq, idx):
                dot = jax.lax.dot_general(
                    qq, idx.dataset, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision("highest"))
                return jnp.sum(jnp.where(jnp.isfinite(dot), dot, 0.0))

            g_s = _autotune.measure(
                jax.jit(_gemm_only), queries, bfs[0], reps=3,
                suspect_floor_s=max(suspect_floor, flops_part / peak_flops),
                value_read=True)
            decomp["gemm_only_tflops"] = round(flops_part / g_s / 1e12, 2)
            if timings.get("matmul"):
                decomp["select_overhead_ms"] = round(
                    (timings["matmul"] - g_s) * 1e3, 2)
            if timings.get("pallas"):
                decomp["fused_tflops"] = round(
                    flops_part / timings["pallas"] / 1e12, 2)
        except Exception as e:  # noqa: BLE001 - diagnostics must not
            log(f"# brute decomposition probe failed: "  # cost the lane
                f"{type(e).__name__}: {e}")

        sfn = jax.jit(lambda q, idx: brute_force.search(idx, q, k,
                                                        algo=winner))
        tp = TwoPart(sfn, bfs, offsets, k)
        thr, lat = measure_tp(tp, queries, floor=floor_brute(),
                              what="brute f32")
        if thr is not None:
            add_entry("raft_brute_force", f"raft_brute_force.{winner}",
                      thr, lat, 1.0, 0.0,
                      {"engine_timings_ms":
                       {kk: round(v * 1e3, 1) for kk, v in timings.items()},
                       "decomposition": decomp})
        # bf16 storage: half the scan HBM traffic; recall measured
        # against the f32 ground truth. Skipped in hurry mode.
        if not hurry:
            bf16s = robust_call(
                lambda: [brute_force.build(p, dtype=jnp.bfloat16)
                         for p in parts], "brute bf16 build")
            hfn = jax.jit(lambda q, idx: brute_force.search(
                idx, q, k, algo="matmul"))
            tph = TwoPart(hfn, bf16s, offsets, k)
            thr, lat = measure_tp(tph, queries, floor=floor_brute(),
                                  what="brute bf16")
            if thr is not None:
                rec = robust_call(
                    lambda: device_recall(tph(queries)[1], gt),
                    "brute bf16 recall")
                add_entry("raft_brute_force", "raft_brute_force.matmul.bf16",
                          thr, lat, rec, 0.0)
            del bf16s

    # --- ivf_flat (config 2: n_lists=1024/part, probe sweep) ------------
    flat_best = None
    with algo_section('ivf_flat'):
        t0 = time.perf_counter()
        fis = robust_call(lambda: [
            ivf_flat.build(p, ivf_flat.IndexParams(n_lists=1024, seed=0))
            for p in parts], "ivf_flat build")
        jax.block_until_ready(jax.tree.leaves(fis))
        flat_build = time.perf_counter() - t0
        for fi in fis:
            ivf_flat.prepare_scan(fi)
        log(f"# ivf_flat built in {flat_build:.0f}s")

        def measure_flat(probes):
            nonlocal flat_best
            sp = ivf_flat.SearchParams(n_probes=probes)
            fn = jax.jit(lambda q, idx, s=sp: ivf_flat.search(idx, q, k, s))
            tp = TwoPart(fn, fis, offsets, k)
            thr, lat = measure_tp(tp, queries,
                                  floor=floor_ivf(probes, d * 4),
                                  what=f"ivf_flat np{probes}")
            if thr is None:
                return None
            rec = robust_call(lambda: device_recall(tp(queries)[1], gt),
                              "ivf_flat recall")
            add_entry("raft_ivf_flat",
                      f"raft_ivf_flat.nlist1024.nprobe{probes}",
                      thr, lat, rec, flat_build,
                      extra=store_bytes_of(fis))
            if rec >= 0.95 and (flat_best is None
                                or nq / thr > flat_best[0]):
                # FULL entry name: the headline-first sort matches on it
                flat_best = (nq / thr, rec,
                             f"raft_ivf_flat.nlist1024.nprobe{probes}")
            return rec

        # config-2 anchor (nprobe=20) always measured; walk DOWN while
        # recall holds >=0.95, or UP if the anchor misses
        best_probes = 20
        rec20 = measure_flat(20)
        if not hurry and rec20 is not None:
            if rec20 >= 0.95:
                # bisect-capable down-walk: np15 sits in the gap where
                # the qualifying frontier usually lives (np20 barely
                # clears, np10 misses — r4: 0.9506 vs 0.8766)
                for probes in (15, 10, 5):
                    r = measure_flat(probes)
                    if r is None or r < 0.95:
                        break
                    best_probes = probes
            else:
                for probes in (25, 30, 40, 50, 100) if rec20 >= 0.93 \
                        else (50, 100):
                    best_probes = probes
                    r = measure_flat(probes)
                    if r is not None and r >= 0.95:
                        break
        # bf16 list storage at the best qualifying probe count
        if not hurry:
            t0 = time.perf_counter()
            fihs = robust_call(lambda: [
                ivf_flat.build(p, ivf_flat.IndexParams(
                    n_lists=1024, seed=0, dtype="bfloat16"))
                for p in parts], "ivf_flat bf16 build")
            jax.block_until_ready(jax.tree.leaves(fihs))
            bf16_build = time.perf_counter() - t0
            for fi in fihs:
                ivf_flat.prepare_scan(fi)
            fnh = jax.jit(lambda q, idx: ivf_flat.search(
                idx, q, k, ivf_flat.SearchParams(n_probes=best_probes)))
            tph = TwoPart(fnh, fihs, offsets, k)
            thr, lat = measure_tp(tph, queries,
                                  floor=floor_ivf(best_probes, d * 2),
                                  what="ivf_flat bf16")
            if thr is not None:
                rec = robust_call(
                    lambda: device_recall(tph(queries)[1], gt),
                    "ivf_flat bf16 recall")
                add_entry("raft_ivf_flat",
                          f"raft_ivf_flat.nlist1024.nprobe{best_probes}"
                          ".bf16",
                          thr, lat, rec, bf16_build,
                          extra=store_bytes_of(fihs))
                if rec >= 0.95 and nq / thr > (flat_best or (0,))[0]:
                    flat_best = (nq / thr, rec,
                                 f"raft_ivf_flat.nlist1024"
                                 f".nprobe{best_probes}.bf16")
            del fihs

    # --- serving_latency: p50/p99 per-request latency at fixed recall ---
    # The ROADMAP "kill the dispatch floor" success metric: requests
    # served through the serve/ runtime (admission -> coalesce -> bucket
    # pad -> dispatch -> demux) with stage telemetry sampling EVERY
    # batch, so the entry decomposes per-request latency into the five
    # stages the dispatch-floor attack must move (queue_wait /
    # bucket_pad / dispatch / device / demux, straight from the
    # <name>.stage.* histograms). Recall is fixed by construction: the
    # serving closure is the ivf_flat sweep's best qualifying probe
    # config over the same index parts, so the lane reports that entry's
    # measured recall. Closed-loop at bounded in-flight depth — an
    # open-loop flood would only measure queue saturation.
    with algo_section('serving_latency'):
        from raft_tpu.serve import metrics as serve_metrics
        from raft_tpu.serve.batcher import BucketLadder, MicroBatcher

        remaining = budget_s - (time.perf_counter() - t_start)
        from raft_tpu.core.errors import expects as _expects
        _expects(remaining > 240, "serving lane skip: %.0fs left < 240s",
                 remaining)
        sp_serve = ivf_flat.SearchParams(n_probes=best_probes)
        flat_name = f"raft_ivf_flat.nlist1024.nprobe{best_probes}"
        flat_entry = next((e for e in entries if e["name"] == flat_name),
                          None)
        kb_serve = 16          # one k bucket; requests ask k=10
        sfn_serve = jax.jit(lambda q, idx, s=sp_serve: ivf_flat.search(
            idx, q, kb_serve, s))
        tp_serve = TwoPart(sfn_serve, fis, offsets, kb_serve)

        def serve_search(q, kk, res=None):
            return tp_serve(jnp.asarray(q))

        reg_serve = serve_metrics.Registry()
        qhost = np.asarray(queries[:1000])
        # quality half of the lane (docs/observability.md "Quality"):
        # a recall sentinel re-executes sampled served requests through
        # the exact brute-force parts (the GT executables) and the lane
        # records its rolling serve.recall estimate next to the latency
        # numbers. Sampled shapes are padded to one fixed row count so
        # the reference costs exactly one extra compile.
        from raft_tpu.serve.quality import RecallSentinel
        _ref_tp = TwoPart(gt_search_jit, bfs, offsets, k)
        _ref_rows = 64

        def _sentinel_ref(qs, kk):
            m = qs.shape[0]
            pad = np.zeros((_ref_rows, d), np.float32)
            pad[:m] = qs
            rd, ri = _ref_tp(jnp.asarray(pad))
            return (np.asarray(rd)[:m, :kk], np.asarray(ri)[:m, :kk])

        sentinel = RecallSentinel(_sentinel_ref, sample=0.25,
                                  registry=reg_serve, family="ivf_flat",
                                  engine=f"nprobe{best_probes}",
                                  window=64, max_pending=16)
        # robustness half of the lane (docs/robustness.md): an SLO
        # engine + brownout controller ride along so a run that browned
        # out (stepped the degradation ladder) is distinguishable from a
        # clean one — the artifact records every level transition and
        # the final circuit-breaker states next to the stage
        # decomposition. Targets are generous (2x the ivf_flat lane's
        # typical p99) so a healthy run records zero transitions.
        from raft_tpu.ops import guarded as serve_guarded
        from raft_tpu.serve.degrade import BrownoutController
        from raft_tpu.serve.slo import SLOEngine, Targets
        slo_serve = SLOEngine(
            Targets(p99_latency_s=0.5, recall_floor=0.9,
                    recall_family="ivf_flat", recall_min_samples=4),
            registry=reg_serve, name="serve",
            fast_window_s=2.0, slow_window_s=6.0)
        brownout = BrownoutController(
            [{"max_wait_scale": 2.0}], slo=slo_serve,
            registry=reg_serve, min_dwell_s=2.0)
        b = MicroBatcher(serve_search, d,
                         ladder=BucketLadder((16, 64), (kb_serve,)),
                         registry=reg_serve, name="serve",
                         trace_sample=1.0, max_wait_s=0.002,
                         sentinel=sentinel, degrade=brownout)
        try:
            warm_compiles = b.warmup()
            rng_s = np.random.default_rng(11)
            n_req, inflight_cap = 200, 8
            req_sizes = rng_s.choice(
                [1, 2, 4, 8, 16, 32], size=n_req,
                p=[.3, .2, .2, .15, .1, .05])
            t0 = time.perf_counter()
            inflight = []
            for i_req, m in enumerate(req_sizes):
                s0 = int(rng_s.integers(0, len(qhost) - int(m)))
                inflight.append(b.submit(qhost[s0:s0 + int(m)], k))
                if len(inflight) >= inflight_cap:
                    inflight.pop(0).result(300)
                if (i_req + 1) % 50 == 0:
                    brownout.poll()     # the serving maintenance tick
            for r in inflight:
                r.result(300)
            serve_wall = time.perf_counter() - t0
            brownout.poll()
        finally:
            b.close()
            sentinel.drain(120.0)
            sentinel.close()
        snap = reg_serve.snapshot()
        sent_snap = sentinel.snapshot()
        serve_recall = sentinel.estimate("ivf_flat")
        lat = snap["histograms"]["serve.latency_s"]
        stage_hists = {s: snap["histograms"][f"serve.stage.{s}_s"]
                       for s in ("queue_wait", "bucket_pad", "dispatch",
                                 "device", "demux")}
        add_entry(
            "serving_latency",
            f"serving_latency.ivf_flat.nprobe{best_probes}",
            serve_wall, lat["p50"],
            flat_entry["recall"] if flat_entry else -1.0, 0.0,
            {"p50_ms": round(lat["p50"] * 1e3, 2),
             "p99_ms": round(lat["p99"] * 1e3, 2),
             "stage_p50_ms": {s: round(h["p50"] * 1e3, 3)
                              for s, h in stage_hists.items()},
             "stage_p99_ms": {s: round(h["p99"] * 1e3, 3)
                              for s, h in stage_hists.items()},
             "requests": n_req, "closed_loop_inflight": inflight_cap,
             "batches": int(snap["counters"]["serve.batches"]),
             "warmup_compiles": warm_compiles,
             "steady_state_recompiles": int(
                 serve_metrics.counter("serve.recompiles").value),
             # the online estimate next to the offline recall: these two
             # agreeing is the sentinel's calibration check
             "serve_recall_estimate": None if serve_recall is None
             else round(serve_recall, 4),
             "recall_sentinel": {
                 "sampled": sent_snap["sampled"],
                 "scored": sent_snap["scored"],
                 "dropped": sent_snap["dropped"],
                 "sample_rate": 0.25},
             # a silently-browned-out run must be distinguishable from
             # a clean one: final ladder level + every transition, and
             # the final breaker state of every site that opened
             "brownout": {
                 "level": brownout.level,
                 "transitions": brownout.snapshot()["transitions"]},
             "breakers": {site: ent["state"] for site, ent in
                          serve_guarded.breaker_snapshot().items()},
             "recall_source": flat_name, "trace_sample": 1.0},
            batch=n_req, baseline_key=None)

    # --- mutation: the mutable-tier write path (docs/mutation.md) -------
    # Records what mutability COSTS: WAL'd acked-upsert throughput, the
    # delta-tier search penalty (p50 with a populated delta fan-out vs
    # after the background merge folds it), and recall before/after the
    # merge scored by the RecallSentinel against an exact reference over
    # the live logical corpus. RAFT_TPU_BENCH_MUTATION=0 skips /
    # =1 forces past the budget gate.
    mut_env = os.environ.get("RAFT_TPU_BENCH_MUTATION")
    mut_left = budget_s - (time.perf_counter() - t_start)
    if mut_env != "0" and (mut_env == "1" or mut_left > 180):
        with algo_section('mutation'):
            import shutil
            import tempfile

            from raft_tpu.neighbors import mutable as mutable_mod
            from raft_tpu.serve.metrics import Registry as _MutReg
            from raft_tpu.serve.quality import RecallSentinel as _MutSent

            mut_dir = tempfile.mkdtemp(prefix="raft_tpu_mut_")
            try:
                base_n = min(100_000, int(parts[0].shape[0]))
                base = np.asarray(jax.device_get(parts[0][:base_n]),
                                  np.float32)
                qh = np.asarray(jax.device_get(queries[:256]), np.float32)
                t0 = time.perf_counter()
                midx = mutable_mod.create(os.path.join(mut_dir, "idx"),
                                          base, family="brute_force")
                mut_build = time.perf_counter() - t0

                def _mut_search(qs=qh, kk=k):
                    dd, ii = midx.search(qs, kk)
                    return float(jnp.sum(dd).block_until_ready())

                sealed_p50 = median_time(_mut_search, reps=7)
                # WAL'd upsert throughput: every batch is acked
                # (framed + CRC'd + fsynced) before the next starts
                up_rows, up_batch = 8192, 1024
                rng_m = np.random.default_rng(17)
                up = base[rng_m.integers(0, base_n, up_rows)] + \
                    rng_m.normal(scale=0.05,
                                 size=(up_rows, d)).astype(np.float32)
                t0 = time.perf_counter()
                for b0 in range(0, up_rows, up_batch):
                    midx.upsert(None, up[b0:b0 + up_batch])
                upsert_wall = time.perf_counter() - t0
                # measured BEFORE the merge rotates the log: WAL bytes
                # actually paid per acked row (frames + npy framing)
                wal_row_bytes = midx.wal_bytes() / up_rows
                delta_p50 = median_time(_mut_search, reps=7)

                # exact reference over the live logical corpus (ids in
                # the mutable tier == row positions in this concat)
                from raft_tpu.neighbors import brute_force as _bf
                _ref_idx = _bf.build(np.concatenate([base, up]))

                def _mut_ref(qs, kk):
                    rd, ri = _bf.search(_ref_idx, jnp.asarray(qs), kk)
                    return np.asarray(rd), np.asarray(ri)

                def _mut_recall(tag):
                    sent = _MutSent(_mut_ref, sample=1.0,
                                    registry=_MutReg(), family="mutable",
                                    engine=tag, window=64, max_pending=8)
                    dd, ii = midx.search(qh[:64], k)
                    sent.offer(qh[:64], k, np.asarray(dd), np.asarray(ii))
                    sent.drain(120.0)
                    est = sent.estimate("mutable")
                    sent.close()
                    return None if est is None else round(est, 4)

                recall_before = _mut_recall("pre_merge")
                t0 = time.perf_counter()
                verdict = midx.merge()
                merge_s = time.perf_counter() - t0
                merged_p50 = median_time(_mut_search, reps=7)
                recall_after = _mut_recall("post_merge")
                add_entry(
                    "mutation", f"mutation.brute{base_n // 1000}k",
                    upsert_wall, delta_p50,
                    recall_after if recall_after is not None else -1.0,
                    mut_build,
                    {"upsert_rows_per_s": round(up_rows / upsert_wall, 1),
                     "acked_batches": up_rows // up_batch,
                     "wal_bytes_per_row": round(wal_row_bytes, 1),
                     "sealed_p50_ms": None if sealed_p50 is None
                     else round(sealed_p50 * 1e3, 3),
                     "delta_p50_ms": None if delta_p50 is None
                     else round(delta_p50 * 1e3, 3),
                     "delta_p50_delta_ms": None
                     if None in (sealed_p50, delta_p50)
                     else round((delta_p50 - sealed_p50) * 1e3, 3),
                     "merged_p50_ms": None if merged_p50 is None
                     else round(merged_p50 * 1e3, 3),
                     "merge_verdict": verdict,
                     "merge_s": round(merge_s, 2),
                     "recall_sentinel_before_merge": recall_before,
                     "recall_sentinel_after_merge": recall_after},
                    batch=up_rows, baseline_key=None)
            finally:
                shutil.rmtree(mut_dir, ignore_errors=True)
    else:
        log(f"# mutation lane skipped ({mut_left:.0f}s left; "
            "set RAFT_TPU_BENCH_MUTATION=1 to force)")

    # --- multi_tenant: the serving fabric (docs/serving.md) -------------
    # 3 tenants over one shared index (co-batched dispatch): one
    # Zipfian-hot repeat-heavy tenant behind a token bucket, two cold
    # tenants. Records per-tenant p50/p99, the ISOLATION RATIO (cold
    # tenants' p99 with vs without the hot tenant — the fabric's
    # whole point), and the query-cache hit rate on the hot stream.
    # RAFT_TPU_BENCH_TENANCY=0 skips / =1 forces past the budget gate.
    ten_env = os.environ.get("RAFT_TPU_BENCH_TENANCY")
    ten_left = budget_s - (time.perf_counter() - t_start)
    if ten_env != "0" and (ten_env == "1" or ten_left > 120):
        with algo_section('multi_tenant'):
            from raft_tpu.serve import warmup as _twarm
            from raft_tpu.serve.batcher import BucketLadder as _TLad
            from raft_tpu.serve.metrics import Registry as _TReg
            from raft_tpu.serve.qcache import QueryCache as _TQC
            from raft_tpu.serve.tenancy import (RateLimitedError as _TRle,
                                                ServeFabric as _TFab)

            ten_n = min(50_000, int(parts[0].shape[0]))
            ten_idx = brute_force.build(parts[0][:ten_n])
            # ONE searcher closure shared by every tenant: same index +
            # params => the fabric co-batches across tenants, and
            # tenancy adds zero ladder shapes / zero extra compiles
            sfn_ten = brute_force.make_searcher(ten_idx)
            ten_ladder = _TLad((1, 8, 32), (16,))
            qh_t = np.asarray(jax.device_get(queries[:512]), np.float32)
            rng_t = np.random.default_rng(5)
            pool = qh_t[:64]    # the hot tenant's repeat pool
            zipf_picks = np.minimum(rng_t.zipf(1.3, size=4096) - 1, 63)
            _twarm.warmup(sfn_ten, ten_ladder, d, registry=_TReg(),
                          name="tenancy.warm")

            from raft_tpu.serve.admission import QueueFullError as _TQFE

            def _ten_submit(fab, nm, q_row, futs):
                # a cold submit outrunning the worker is backpressure,
                # not a lane failure: wait out the queue (bounded)
                for _ in range(600):
                    try:
                        futs.append(fab.submit(nm, q_row, k))
                        return
                    except _TQFE:
                        time.sleep(0.01)
                raise RuntimeError(f"tenant {nm} queue never drained")

            def _tenancy_pass(with_hot):
                cache = _TQC(capacity=4096, registry=_TReg())
                fab = _TFab(d, ladder=ten_ladder, cache=cache,
                            registry=_TReg(), name="tfab")
                try:
                    for nm in ("cold1", "cold2"):
                        fab.add_tenant(nm, search_fn=sfn_ten,
                                       queue_depth=1024)
                    if with_hot:
                        fab.add_tenant("hot", search_fn=sfn_ten,
                                       rate=2000.0, burst=64.0,
                                       queue_depth=1024)
                    futs, hot_shed, hp = [], 0, 0
                    for i in range(400):
                        _ten_submit(fab, "cold1",
                                    qh_t[(7 * i) % 512][None, :], futs)
                        _ten_submit(fab, "cold2",
                                    qh_t[(11 * i + 31) % 512][None, :],
                                    futs)
                        if with_hot:
                            for _ in range(2):
                                try:
                                    futs.append(fab.submit(
                                        "hot",
                                        pool[zipf_picks[hp]][None, :], k))
                                except _TRle:
                                    hot_shed += 1
                                except _TQFE:
                                    pass
                                hp += 1
                    for f in futs:
                        f.result(300)
                    if with_hot:
                        # steady-state repeat wave: the burst above is
                        # all submitted before its duplicates get
                        # served, so cache hits only show once entries
                        # exist — THIS wave is the repeat-traffic claim
                        wave = []
                        for j in range(200):
                            try:
                                wave.append(fab.submit(
                                    "hot",
                                    pool[zipf_picks[j]][None, :], k))
                            except (_TRle, _TQFE):
                                pass
                        for f in wave:
                            f.result(300)
                        futs += wave
                    lat = {}
                    for t in fab.tenants():
                        h = t.registry.histogram(f"{t.name}.latency_s")
                        lat[t.name] = (h.percentile(50), h.percentile(99))
                    served = len(futs)
                    hit = cache.snapshot()
                    cob = int(fab.snapshot()["cobatched_dispatches"])
                    return lat, hit, hot_shed, served, cob
                finally:
                    # a timeout/dispatch error must not leak the drain
                    # worker into the next lane's timings
                    fab.close()

            solo_lat, _, _, _, _ = _tenancy_pass(False)
            # qps is the COMBINED pass only (batch counts its futures;
            # folding the solo calibration pass in would halve it)
            t0 = time.perf_counter()
            comb_lat, hit, hot_shed, served, cob = _tenancy_pass(True)
            ten_wall = time.perf_counter() - t0
            iso = max(comb_lat[nm][1] / max(solo_lat[nm][1], 1e-6)
                      for nm in ("cold1", "cold2"))
            add_entry(
                "multi_tenant", f"tenancy.brute{ten_n // 1000}k.3tenants",
                ten_wall, comb_lat["cold1"][1], -1.0, 0.0,
                {"per_tenant_ms": {
                    nm: {"p50": round(p50 * 1e3, 3),
                         "p99": round(p99 * 1e3, 3)}
                    for nm, (p50, p99) in comb_lat.items()},
                 "cold_solo_p99_ms": {
                     nm: round(p99 * 1e3, 3)
                     for nm, (_p, p99) in solo_lat.items()},
                 # >1 means the hot tenant degraded the cold tenants;
                 # the ISSUE 15 isolation bar is 1.5
                 "isolation_ratio": round(iso, 3),
                 "hot_shed": hot_shed,
                 "cobatched_dispatches": cob,
                 "qcache": {"hit_rate": hit["hit_rate"],
                            "hits": hit["hits"],
                            "misses": hit["misses"],
                            "entries": hit["entries"]}},
                batch=served, baseline_key=None)
    else:
        log(f"# multi_tenant lane skipped ({ten_left:.0f}s left; "
            "set RAFT_TPU_BENCH_TENANCY=1 to force)")

    # --- ivf_pq (config 3) + refine -------------------------------------
    # kernel round 4: pq_bits=4 with pq_dim=d (same 512 code bits/row as
    # pq64x8 but an 8x narrower one-hot decode) + int8-quantized LUT (the
    # fp8-LUT role, double-rate MXU) + bf16 refine corpus (half the
    # gather traffic). See scratch/exp_hard_tune.py for the sweep.
    with algo_section('ivf_pq'):
        t0 = time.perf_counter()
        pis = robust_call(lambda: [
            ivf_pq.build(p, ivf_pq.IndexParams(
                n_lists=1024, pq_dim=min(d, 128), pq_bits=4, seed=0))
            for p in parts], "ivf_pq build")
        jax.block_until_ready(jax.tree.leaves(pis))
        pq_build = time.perf_counter() - t0
        for pi in pis:
            ivf_pq.prepare_scan(pi)
        log(f"# ivf_pq built in {pq_build:.0f}s")
        parts_bf16 = [jnp.asarray(p, jnp.bfloat16) for p in parts]
        jax.block_until_ready(parts_bf16)

        def pq_refined_tp(probes, ratio):
            """Per-part scan + per-part bf16 refine, exact merge (refine
            before merge == refine after merge for top-k)."""
            sp = ivf_pq.SearchParams(n_probes=probes, lut_dtype="int8")

            def body(q, idx, dd):
                _, cand = ivf_pq.search(idx, q, ratio * k, sp)
                return refine.refine(dd, q, cand, k)

            return TwoPart(jax.jit(body), pis, offsets, k,
                           extras=[(pb,) for pb in parts_bf16])

        def measure_pq(probes, ratio):
            tp = pq_refined_tp(probes, ratio)
            thr, lat = measure_tp(tp, queries,
                                  floor=floor_ivf(probes,
                                                  min(d, 128) // 2 + 4),
                                  what=f"ivf_pq np{probes} r{ratio}")
            if thr is None:
                return None
            rec = robust_call(
                lambda: device_recall(tp(queries)[1], gt), "ivf_pq recall")
            add_entry("raft_ivf_pq",
                      f"raft_ivf_pq.nlist1024.pq{min(d, 128)}x4.int8"
                      f".nprobe{probes}.refine{ratio}",
                      thr, lat, rec, pq_build,
                      extra=store_bytes_of(pis))
            return rec

        rec_a = measure_pq(20, 2)
        if not hurry:
            if rec_a is None:
                measure_pq(10, 2)
                measure_pq(20, 4)
            elif rec_a >= 0.95:
                measure_pq(10, 2)
                if rec_a < 0.995:
                    measure_pq(20, 4)
            else:
                # diagnose WHICH axis binds: if doubling refine doesn't
                # move recall, it is probe-limited (low-intrinsic-dim
                # corpora) and the probe walk should keep the cheap r2
                r4 = measure_pq(20, 4)
                quant_limited = (r4 is not None and rec_a is not None
                                 and r4 > rec_a + 0.01)
                ratio = 4 if quant_limited else 2
                # bisect-capable up-walk: a near-miss anchor (r4's
                # 0.9491 @ np20) explores 25/30/40 so a measured point
                # actually lands at the gate instead of jumping to
                # np50's 0.991 with the frontier unmeasured; 100 caps the
                # walk so the 0.95 gate always has a qualifying endpoint
                # (matching ivf_flat's walk)
                ups = (25, 30, 40, 50, 100) if rec_a >= 0.93 else (50, 100)
                for probes in ups:
                    r = measure_pq(probes, ratio)
                    if r is not None and r >= 0.95:
                        break
        del parts_bf16

    def cagra_decomposition(ci, eng_timings):
        """Per-hop decomposition of the CAGRA traversal: candidate
        fetch+score through each engine (the gather-tax evidence), the
        resident-vector score alone, and the dedup+merge — plus the
        gathered vs streamed byte counts per hop. All probes ride
        value-read measurements; diagnostics must not cost the lane."""
        from raft_tpu.matrix.select_k import select_k as _sel
        from raft_tpu.neighbors import cagra as _cg
        from raft_tpu.ops import graph_expand as _ge

        deg = ci.graph_degree
        w, itopk = 4, 32                  # probe anchor == the r5 headline
        # the block self-describes its operating point: it rides on the
        # sweep's OPENER entry, whose (itopk, width) can differ
        decomp = {"probe_itopk": itopk, "probe_width": w}
        kprime = min(deg, itopk)
        m = queries.shape[0]
        kk = jax.random.PRNGKey(5)
        cand = jax.random.randint(kk, (m, w * deg), 0, ci.size)
        parents = jax.random.randint(kk, (m, w), 0, ci.size,
                                     dtype=jnp.int32)
        mt = ci.metric

        def _fin(x):
            return jnp.sum(jnp.where(jnp.isfinite(x), x, 0.0))

        def probe(name, fn, *args):
            try:
                decomp[name] = round(_autotune.measure(
                    jax.jit(fn), *args, reps=3,
                    suspect_floor_s=suspect_floor, value_read=True) * 1e3,
                    2)
            except Exception as e:  # noqa: BLE001
                log(f"# cagra decomp probe {name} failed: "
                    f"{type(e).__name__}: {e}")

        # the old hop's HBM op: a random (m, w·deg) row gather + score
        probe("gather_ms",
              lambda q, c, ix: _fin(_cg._gather_score(
                  ix._score_bf16, None, c, q, mt)), queries, cand, ci)
        decomp["gathered_mb"] = round(m * w * deg * ci.dim * 2 / 1e6, 1)
        store = getattr(ci, "_edge_store", None)
        if store is not None:
            # the new hop's HBM op: streamed contiguous edge tiles
            probe("expand_ms",
                  lambda q, p, ix: _fin(_ge.graph_expand(
                      p, q, ix._edge_store[1], ix._edge_store[2], kprime,
                      metric="ip" if mt.name == "InnerProduct" else "l2",
                      degree=deg)[0]), queries, parents, ci)
            meta = store[0]
            itemsize = 2 if meta[0] == "bfloat16" else 1
            decomp["streamed_mb"] = round(
                m * w * meta[2] * meta[3] * itemsize / 1e6, 1)
        # score alone on resident vectors — isolates fetch from math
        vs = (getattr(ci, "_score_bf16", ci.dataset))[cand]
        probe("score_ms", lambda q, v: _fin(_cg._query_dists(q, v, mt)),
              queries, vs)
        del vs
        # dedup + merge at each engine's width (edge: w·kprime candidate
        # columns vs gather: w·deg — the shrink the per-parent top-k'
        # emission buys)
        def _merge(c, ids):
            dup = _cg._dup_mask(ids[:, itopk:], keep=ids[:, :itopk])
            c = jnp.concatenate(
                [c[:, :itopk], jnp.where(dup, jnp.inf, c[:, itopk:])],
                axis=1)
            return _fin(_sel(c, itopk, select_min=True)[0])

        for tag, cw in (("merge_ms", w * kprime),
                        ("merge_gather_ms", w * deg)):
            probe(tag, _merge,
                  jax.random.uniform(kk, (m, itopk + cw)),
                  jax.random.randint(kk, (m, itopk + cw), 0, ci.size))
        if eng_timings:
            decomp["engine_timings_ms"] = {
                kk_: round(v * 1e3, 1) for kk_, v in eng_timings.items()}
        return decomp

    # --- cagra (config 4: graph_degree=64) ------------------------------
    with algo_section('cagra'):
        remaining = budget_s - (time.perf_counter() - t_start)
        # round 6: knn_graph auto → nn_descent at 500k (the fused exact
        # pass below RAFT_TPU_CAGRA_BRUTE_N) cut the build from 366.8s
        # to minutes-fraction scale; the gates shrink accordingly. One
        # part only — the graph index demonstrates single-index scaling
        # (the sharded form is dryrun_multichip's job).
        cagra_n = part_n if remaining > 700 and part_n >= 500_000 else \
            min(n, 100_000 if scale != "micro" else 20_000)
        cagra_env = os.environ.get("RAFT_TPU_BENCH_CAGRA_N")
        if cagra_env:
            cagra_n = int(cagra_env)
        else:
            need_s = 400 if cagra_n > 50_000 else 120
            from raft_tpu.core.errors import expects as _expects
            _expects(remaining > need_s,
                     "budget skip: %.0fs left < %ds needed for a %d-row "
                     "cagra build", remaining, need_s, cagra_n)
        cdata = data[:cagra_n]
        if cagra_n == n:
            cgt = gt
        elif cagra_n == part_n:
            # part A's ground truth: rerun the part-A search fn
            cgt_fn = jax.jit(lambda q, idx: brute_force.search(
                idx, q, k, algo="matmul")[1])
            cgt = robust_call(lambda: jnp.concatenate(
                [cgt_fn(queries[c0 : c0 + 1000], bfs[0])
                 for c0 in range(0, nq, 1000)]), "cagra part gt")
        else:
            cgt_fn = jax.jit(lambda q, cd: brute_force.search(
                brute_force.build(cd), q, k, algo="matmul")[1])
            cgt = robust_call(lambda: cgt_fn(queries, cdata), "cagra gt")
        t0 = time.perf_counter()
        ci = robust_call(lambda: cagra.build(cdata, cagra.IndexParams(
            graph_degree=64, intermediate_graph_degree=96, seed=0)),
            "cagra build")
        jax.block_until_ready(jax.tree.leaves(ci))
        cagra_build = time.perf_counter() - t0
        # phase decomposition (knn_graph_s/optimize_s/seeds_s + which
        # builder auto picked): the evidence block for build-time PRs
        build_decomp = dict(getattr(ci, "build_stats", {}))
        cagra.prepare_search(ci)
        log(f"# cagra built ({cagra_n} rows) in {cagra_build:.0f}s: "
            f"{build_decomp}")
        # engine race: the streamed edge-store hop (prepare_traversal +
        # Pallas frontier expansion) vs the XLA gather hop, at the
        # anchor config. The winner is cached; when edge wins the store
        # stays attached and every algo-auto sweep search dispatches on
        # it, when gather wins the store is dropped (no idle HBM).
        eng_winner, eng_timings = "gather", {}
        if jax.default_backend() == "tpu":
            try:
                eng_winner, eng_timings = cagra.tune_search(
                    ci, queries, k,
                    cagra.SearchParams(itopk_size=32, search_width=4,
                                       max_iterations=5),
                    reps=3, suspect_floor_s=suspect_floor)
                log(f"# cagra engine race -> {eng_winner}")
            except Exception as e:  # noqa: BLE001
                log(f"# cagra engine race failed ({type(e).__name__}: "
                    f"{e}); staying on gather")
        try:
            cagra_decomp = cagra_decomposition(ci, eng_timings)
            log(f"# cagra decomposition: {cagra_decomp}")
        except Exception as e:  # noqa: BLE001
            log(f"# cagra decomposition failed ({type(e).__name__}: {e})")
            cagra_decomp = {}
        # sweep (itopk, search_width, max_iterations); measured sweep
        # 2026-07-31 (see bench.py history): covering seeds + few hops
        # (40,4,5) targets the [0.95, 0.965] recall band the r4 sweep
        # straddled (0.9401 @ itopk40.mi4 vs 0.9688 @ itopk32.mi5)
        sweep = (((32, 4, 5),) if hurry
                 else ((16, 8, 2), (32, 4, 3), (40, 4, 4), (40, 4, 5),
                       (32, 4, 5), (64, 4, 8)))
        opener = sweep[0]
        for itopk, width, mi in sweep:
            sp = cagra.SearchParams(itopk_size=itopk, search_width=width,
                                    max_iterations=mi)
            fn = jax.jit(lambda q, idx, s=sp: cagra.search(idx, q, k, s))
            thr, lat = measure_tp(fn, queries, ci, reps=3,
                                  what=f"cagra itopk{itopk}")
            if thr is None:
                continue
            rec = robust_call(lambda: device_recall(fn(queries, ci)[1], cgt),
                              "cagra recall")
            extra = {"corpus_n": cagra_n, "engine": eng_winner,
                     "build_decomposition": build_decomp,
                     **store_bytes_of(ci)}
            if (itopk, width, mi) == opener:
                extra["decomposition"] = cagra_decomp
            add_entry("raft_cagra",
                      f"raft_cagra.degree64.itopk{itopk}.w{width}"
                      f".mi{mi or 'auto'}",
                      thr, lat, rec, cagra_build, extra)
            if rec >= 0.995 and (itopk, width, mi) != opener:
                break

    # --- serving_latency.cagra: the one-dispatch megakernel behind the
    # serve runtime (ISSUE 12). The per-request story the ivf_flat
    # serving lane tells, on the graph index with engine="fused" — the
    # whole traversal is ONE kernel launch, so stage_p50_ms.dispatch is
    # the number the megakernel exists to move. `one_dispatch` is
    # verified structurally (jaxpr: no device-side hop loop survives,
    # each of whose iterations would be a separate kernel launch) and
    # recorded on the entry next to a per-batch host-dispatch counter.
    with algo_section('serving_latency.cagra'):
        from raft_tpu.ops import cagra_fused
        from raft_tpu.serve import metrics as cserve_metrics
        from raft_tpu.serve.batcher import BucketLadder as _CLadder, \
            MicroBatcher as _CBatcher

        remaining = budget_s - (time.perf_counter() - t_start)
        from raft_tpu.core.errors import expects as _expects
        _expects(remaining > 120,
                 "cagra serving lane skip: %.0fs left < 120s", remaining)
        sp_cs = cagra.SearchParams(itopk_size=32, search_width=4,
                                   max_iterations=5)
        es = getattr(ci, "_edge_store", None)
        if es is None:
            cagra.prepare_traversal(ci)
            es = ci._edge_store
        can_fuse = cagra_fused.fused_capable(
            32, 4, es[1].shape[1], es[1].shape[2], es[1].dtype, 5)
        serve_eng = ("fused" if can_fuse
                     and jax.default_backend() == "tpu" else eng_winner)
        kb_cs = 16
        # structural one-dispatch check: trace the fused program (cheap,
        # no compile/execution) and count surviving device-side loops
        disp_stats = {}
        if can_fuse:
            try:
                disp_stats = cagra_fused.one_dispatch_stats(
                    lambda q: cagra.search(ci, q, kb_cs, sp_cs,
                                           engine="fused"),
                    queries[:16])
            except Exception as e:  # noqa: BLE001
                log(f"# one_dispatch trace failed ({type(e).__name__}: "
                    f"{e})")
        # donate="auto": the donated double-buffered pair is the lane's
        # subject; the kernel path was just raced/rehearsed above, and a
        # dispatch failure here fails the lane's futures, not the run
        searcher_cs = cagra.make_searcher(ci, sp_cs, engine=serve_eng,
                                          donate="auto")
        host_dispatches = [0]

        def cs_search(q, kk, res=None):
            host_dispatches[0] += 1
            return searcher_cs(q, kk, res=res)

        reg_cs = cserve_metrics.Registry()
        bc = _CBatcher(cs_search, d, ladder=_CLadder((16, 64), (kb_cs,)),
                       registry=reg_cs, name="serve_cagra",
                       trace_sample=1.0, max_wait_s=0.002)
        try:
            cs_warm = bc.warmup()
            rng_cs = np.random.default_rng(13)
            qhost_cs = np.asarray(queries[:1000])
            n_req_cs, inflight_cap = 120, 8
            sizes = rng_cs.choice([1, 2, 4, 8, 16], size=n_req_cs,
                                  p=[.3, .25, .2, .15, .1])
            t0 = time.perf_counter()
            inflight = []
            for m_cs in sizes:
                s0 = int(rng_cs.integers(0, len(qhost_cs) - int(m_cs)))
                inflight.append(bc.submit(qhost_cs[s0:s0 + int(m_cs)], k))
                if len(inflight) >= inflight_cap:
                    inflight.pop(0).result(300)
            for r in inflight:
                r.result(300)
            cs_wall = time.perf_counter() - t0
        finally:
            bc.close()
        snap_cs = reg_cs.snapshot()
        # recall at the serving params, same engine (fused is
        # bit-identical to edge, but record what actually served)
        rec_cs = robust_call(lambda: device_recall(
            cagra.search(ci, queries[:1000], k, sp_cs,
                         engine=serve_eng)[1], cgt[:1000]),
            "cagra serve recall")
        lat_cs = snap_cs["histograms"]["serve_cagra.latency_s"]
        stage_cs = {s: snap_cs["histograms"][f"serve_cagra.stage.{s}_s"]
                    for s in ("queue_wait", "bucket_pad", "dispatch",
                              "device", "demux")}
        batches_cs = int(snap_cs["counters"]["serve_cagra.batches"])
        add_entry(
            "serving_latency",
            f"serving_latency.cagra.{serve_eng}.itopk32",
            cs_wall, lat_cs["p50"], rec_cs, 0.0,
            {"p50_ms": round(lat_cs["p50"] * 1e3, 2),
             "p99_ms": round(lat_cs["p99"] * 1e3, 2),
             "stage_p50_ms": {s: round(h["p50"] * 1e3, 3)
                              for s, h in stage_cs.items()},
             "stage_p99_ms": {s: round(h["p99"] * 1e3, 3)
                              for s, h in stage_cs.items()},
             "engine": serve_eng,
             # the acceptance bit: no device-side hop loop survives in
             # the fused program AND the serving path issued exactly one
             # host dispatch per batch
             "one_dispatch": bool(
                 disp_stats.get("one_dispatch", False)
                 and serve_eng == "fused"
                 and host_dispatches[0] - len(bc.ladder.shapes())
                 == batches_cs),
             "dispatch_structure": disp_stats,
             "host_dispatches": host_dispatches[0],
             "requests": n_req_cs, "closed_loop_inflight": inflight_cap,
             "batches": batches_cs, "warmup_compiles": cs_warm,
             "steady_state_recompiles": int(cserve_metrics.counter(
                 "serve.recompiles").value),
             "trace_sample": 1.0},
            batch=n_req_cs, baseline_key=None)

    # --- cagra at the BASELINE 1M scale (the lane's missing point) ------
    # The graph build is the cost. knn_graph auto → nn_descent at 1M
    # (O(rounds·n·C·d), batch-shaped programs — the 1M single-program
    # compile hang structurally cannot happen), which replaced the
    # parted exact pass whose n²·d ≈ 2.6e17 FLOP was ~25 min of MXU
    # time. Still budget-gated (build + optimize + sweep is minutes) and
    # a REDUCED sweep (one config, no vs_baseline ratio: a one-point
    # sweep is not the Pareto frontier the A100 baseline derivation
    # describes). RAFT_TPU_BENCH_CAGRA_1M=1 forces; =0 skips regardless.
    with algo_section('cagra_1m'):
        remaining = budget_s - (time.perf_counter() - t_start)
        from raft_tpu.core.errors import expects as _expects
        force_1m = os.environ.get("RAFT_TPU_BENCH_CAGRA_1M")
        _expects(force_1m != "0" and n >= 1_000_000,
                 "cagra 1M skip: forced=%s n=%d", force_1m, n)
        _expects(force_1m == "1" or (not hurry and remaining > 1200),
                 "cagra 1M skip: %.0fs left < 1200s for the nn_descent "
                 "graph build (set RAFT_TPU_BENCH_CAGRA_1M=1 to force)",
                 remaining)
        t0 = time.perf_counter()
        ci1m = robust_call(lambda: cagra.build(data, cagra.IndexParams(
            graph_degree=64, intermediate_graph_degree=96, seed=0)),
            "cagra 1M build", tries=1)
        jax.block_until_ready(jax.tree.leaves(ci1m))
        build_1m = time.perf_counter() - t0
        decomp_1m = dict(getattr(ci1m, "build_stats", {}))
        cagra.prepare_search(ci1m)
        log(f"# cagra 1M built in {build_1m:.0f}s: {decomp_1m}")
        # edge store at 1M: deg64×dim128 int8 = 8.2 GB — fits v5e HBM
        # next to the f32 dataset + bf16 copy; a build/OOM failure just
        # keeps the lane on the gather engine
        eng_1m = "gather"
        if jax.default_backend() == "tpu":
            try:
                cagra.prepare_traversal(ci1m)
                eng_1m = "edge"
            except Exception as e:  # noqa: BLE001
                log(f"# cagra 1M prepare_traversal failed "
                    f"({type(e).__name__}: {e}); gather engine")
        for itopk, width, mi in ((32, 4, 5), (40, 4, 5)):
            sp = cagra.SearchParams(itopk_size=itopk, search_width=width,
                                    max_iterations=mi)
            fn = jax.jit(lambda q, idx, s=sp: cagra.search(idx, q, k, s))
            thr, lat = measure_tp(fn, queries, ci1m, reps=3,
                                  what=f"cagra1M itopk{itopk}")
            if thr is None:
                continue
            rec = robust_call(
                lambda: device_recall(fn(queries, ci1m)[1], gt),
                "cagra 1M recall")
            add_entry("raft_cagra",
                      f"raft_cagra.1M.degree64.itopk{itopk}.w{width}"
                      f".mi{mi}",
                      thr, lat, rec, build_1m,
                      {"corpus_n": n, "reduced_sweep": True,
                       "engine": eng_1m,
                       "build_decomposition": decomp_1m,
                       **store_bytes_of(ci1m)},
                      baseline_key=None)
            if rec >= 0.95:
                break

    # --- storage ladder capacity rung (ISSUE 13 / ROADMAP rung 1) -------
    # Edge-store rungs int8 -> int4 -> pq at fixed k with exact refine,
    # plus the ivf_flat HBM-resident vs host-streamed decomposition, at
    # n=10M (RAFT_TPU_BENCH_LADDER_N overrides — the CPU-gated proxy).
    # RAFT_TPU_BENCH_LADDER=1 forces / =0 skips.
    with algo_section('storage_ladder'):
        remaining = budget_s - (time.perf_counter() - t_start)
        from raft_tpu.core.errors import expects as _expects
        force_lad = os.environ.get("RAFT_TPU_BENCH_LADDER")
        _expects(force_lad != "0" and (
            force_lad == "1" or (jax.default_backend() == "tpu"
                                 and not hurry and remaining > 2400)),
            "storage ladder skip: forced=%s %.0fs left < 2400s "
            "(set RAFT_TPU_BENCH_LADDER=1 to force)", force_lad,
            remaining)
        lad_n = int(os.environ.get("RAFT_TPU_BENCH_LADDER_N",
                                   str(10_000_000)))
        entries.extend(run_storage_ladder(lad_n, d, nq=1000, k=k))

    # --- fleet storage ladder (per-host HBM-budget tiers) ---------------
    # Every FLEET_STORE_RUNGS rung on a virtual 2x2 fleet under a
    # per-host budget (docs/mnmg.md "Per-host storage tiers").
    # RAFT_TPU_BENCH_FLEET_LADDER=1 runs it (default: skip — an
    # on-demand lane; scratch/check_bench_artifact.py validates it).
    with algo_section('fleet_ladder'):
        from raft_tpu.core.errors import expects as _expects
        _expects(os.environ.get("RAFT_TPU_BENCH_FLEET_LADDER") == "1",
                 "fleet ladder skip (set RAFT_TPU_BENCH_FLEET_LADDER=1 "
                 "to run)")
        _expects(len(jax.devices()) >= 4,
                 "fleet ladder skip: %d devices < 4 (CPU runs need "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
                 len(jax.devices()))
        fn_n = int(os.environ.get("RAFT_TPU_BENCH_FLEET_LADDER_N",
                                  "8192"))
        entries.extend(run_fleet_ladder(
            fn_n, d, nq=256, k=k,
            out_json=os.path.join("artifacts",
                                  "bench_fleet_ladder.json")))

    # --- filtered-search selectivity sweep ------------------------------
    # Adaptive vs fixed filter policy across filtered-out fractions
    # (docs/perf.md "Filtered search"). RAFT_TPU_BENCH_FILTER=1 runs it
    # (default: skip — an on-demand lane; its artifact backs the docs).
    with algo_section('filter_sweep'):
        from raft_tpu.core.errors import expects as _expects
        _expects(os.environ.get("RAFT_TPU_BENCH_FILTER") == "1",
                 "filter sweep skip (set RAFT_TPU_BENCH_FILTER=1 to run)")
        fs_n = int(os.environ.get("RAFT_TPU_BENCH_FILTER_N", "20000"))
        entries.extend(run_filter_sweep(
            fs_n, d, nq=100, k=k,
            out_json=os.path.join("artifacts", "bench_filter_sweep.json")))

    # --- graph-build race: fused exact all-pairs vs NN-descent ----------
    # The two CAGRA graph builders at one shape (100k×128 at k=96, the
    # real build's intermediate degree): wall-clock race plus the
    # approximate builder's graph-edge recall against the exact graph.
    # The winner is recorded in the autotune bucket build_knn_graph's
    # algo="auto" consults, so the race steers later builds of this
    # shape class the way the search-engine races steer dispatch.
    # RAFT_TPU_BENCH_GRAPH_LANE=1 forces / =0 skips.
    with algo_section('graph_build'):
        remaining = budget_s - (time.perf_counter() - t_start)
        from raft_tpu.core.errors import expects as _expects
        force_gl = os.environ.get("RAFT_TPU_BENCH_GRAPH_LANE")
        _expects(force_gl != "0" and n >= 100_000,
                 "graph lane skip: forced=%s n=%d", force_gl, n)
        _expects(force_gl == "1" or (not hurry and remaining > 400),
                 "graph lane skip: %.0fs left < 400s", remaining)
        gn, gk = 100_000, 96
        gdata = np.asarray(data[:gn])
        t0 = time.perf_counter()
        g_exact = cagra.build_knn_graph(gdata, gk, algo="brute")
        brute_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        g_nnd = cagra.build_knn_graph(gdata, gk, algo="nn_descent")
        nnd_s = time.perf_counter() - t0
        # graph-edge recall vs exact, chunked on device (equal chunks,
        # every slot valid -> the mean of chunk recalls is exact)
        ge, gj = jnp.asarray(g_exact), jnp.asarray(g_nnd)
        step = gn // 10
        g_rec = float(np.mean([device_recall(gj[c:c + step],
                                             ge[c:c + step])
                               for c in range(0, gn, step)]))
        # the verdict steers later algo="auto" builds of this shape
        # class, so speed alone must not crown a degraded graph: the
        # approximate builder only wins with edge recall at the bar the
        # PR's quality gate is built on (optimize() + the exact re-rank
        # absorb ~0.9; below it, downstream search recall drifts)
        winner = ("nn_descent" if nnd_s < brute_s and g_rec >= 0.9
                  else "brute")
        from raft_tpu.distance.distance_types import DistanceType as _DT
        _autotune.record(cagra._graph_algo_key(gn, d, gk,
                                               _DT.L2Expanded), winner)
        log(f"# graph build race: brute(fused) {brute_s:.0f}s vs "
            f"nn_descent {nnd_s:.0f}s (edge recall {g_rec:.4f}) "
            f"-> {winner}")
        add_entry("cagra_build", f"cagra_build.race100k.k{gk}",
                  min(brute_s, nnd_s), None, g_rec,
                  min(brute_s, nnd_s),
                  {"corpus_n": gn, "graph_k": gk,
                   "brute_fused_s": round(brute_s, 1),
                   "nn_descent_s": round(nnd_s, 1), "winner": winner,
                   "recall_note": "graph-edge recall of nn_descent vs "
                                  "the exact graph"},
                  batch=gn, baseline_key=None)
        del gdata, g_exact, g_nnd, ge, gj

    # --- ivf_pq capacity (config 3's structural win: 2M rows) -----------
    # PQ's reason to exist is corpora where raw f32 pressures memory
    # (the reference's DEEP-1B positioning): 2M x 128 = 1.02 GB raw vs
    # ~0.26 GB of pq128x4 codes. A fresh 2M mixture (its own exact
    # ground truth, 2k-query batches to bound the GT stage) makes this a
    # recorded, recall-checked, floor-gated bench entry instead of the
    # r4 one-off artifact.
    with algo_section('ivf_pq_capacity'):
        remaining = budget_s - (time.perf_counter() - t_start)
        from raft_tpu.core.errors import expects as _expects
        _expects(scale == "full" and not hurry and remaining > 650,
                 "capacity skip: scale=%s hurry=%s %.0fs left < 650s",
                 scale, hurry, remaining)
        cap_nq = 2_000
        # ~2.5 GB of host/device working set below: the try/finally
        # guarantees the release even when a stage raises mid-lane (an
        # OOM'd capacity lane must not starve every later section)
        cdata = cq = cparts = cbfs = ctp = cgt = None
        cpis = cparts_bf16 = None
        try:
            cdata, cq = robust_call(
                lambda: make_corpus(2_000_000, d, cap_nq, seed=7),
                "capacity corpus")
            cparts = [cdata[i * part_n:(i + 1) * part_n]
                      for i in range(len(cdata) // part_n)]
            coffs = [i * part_n for i in range(len(cparts))]
            cbfs = [brute_force.build(p, metric="sqeuclidean")
                    for p in cparts]
            ctp = TwoPart(gt_search_jit, cbfs, coffs, k)
            cgt = jnp.concatenate([
                robust_call(lambda c0=c0: jax.block_until_ready(
                    ctp(cq[c0:c0 + 1000])[1]), f"capacity gt [{c0}]")
                for c0 in range(0, cap_nq, 1000)])
            cbfs = ctp = None
            t0 = time.perf_counter()
            cpis = robust_call(lambda: [
                ivf_pq.build(p, ivf_pq.IndexParams(
                    n_lists=1024, pq_dim=min(d, 128), pq_bits=4, seed=0))
                for p in cparts], "capacity pq build")
            jax.block_until_ready(jax.tree.leaves(cpis))
            cap_build = time.perf_counter() - t0
            for pi in cpis:
                ivf_pq.prepare_scan(pi)
            cparts_bf16 = [jnp.asarray(p, jnp.bfloat16) for p in cparts]
            jax.block_until_ready(cparts_bf16)
            code_gb = sum(int(np.prod(pi.codes.shape))
                          for pi in cpis) / 1e9

            def measure_capacity(probes):
                sp = ivf_pq.SearchParams(n_probes=probes, lut_dtype="int8")

                def cap_body(q, idx, dd, s=sp):
                    _, cand = ivf_pq.search(idx, q, 2 * k, s)
                    return refine.refine(dd, q, cand, k)

                tp = TwoPart(jax.jit(cap_body), cpis, coffs, k,
                             extras=[(pb,) for pb in cparts_bf16])
                thr, lat = measure_tp(
                    tp, cq,
                    floor=floor_ivf_for(probes, min(d, 128) // 2 + 4,
                                        cap_nq, len(cparts)),
                    what=f"pq capacity np{probes}", qset=cq)
                if thr is None:
                    return None
                rec = robust_call(lambda: device_recall(tp(cq)[1], cgt),
                                  "pq capacity recall")
                # baseline_key=None: BASELINE_QPS['raft_ivf_pq'] is the
                # 1M-lane derivation — a 2M/2k-batch entry normalized by
                # it reads as a regression that isn't one
                add_entry("raft_ivf_pq",
                          f"raft_ivf_pq.capacity2M.nlist1024.pq{min(d, 128)}"
                          f"x4.int8.nprobe{probes}.refine2",
                          thr, lat, rec, cap_build,
                          {"corpus_n": len(cdata), "batch_queries": cap_nq,
                           "code_gb": round(code_gb, 3),
                           "raw_gb": round(len(cdata) * d * 4 / 1e9, 3)},
                          batch=cap_nq, baseline_key=None)
                return rec

            rec_cap = measure_capacity(20)
            if rec_cap is not None and rec_cap < 0.95:
                for probes in (30, 50):
                    r = measure_capacity(probes)
                    if r is not None and r >= 0.95:
                        break
        finally:
            del cdata, cq, cparts, cbfs, ctp, cgt, cparts_bf16, cpis

    # --- dataset IO: exercise the raft-ann-bench fbin loader ------------
    try:
        dataset_io = exercise_fbin_io(data)
        log(f"# fbin round-trip: {dataset_io}")
    except Exception as e:  # noqa: BLE001
        dataset_io = {"ok": False, "error": f"{type(e).__name__}: {e}"}

    # --- roofline: report utilization against the measured chip peak ----
    log("# probing roofline")
    try:
        # micro is the CPU harness smoke: the amortized 8192-wide matmul
        # loops are minutes of host time there and probe nothing real
        peaks = roofline.probe(quick=True) if scale != "micro" else {}
    except Exception as e:  # noqa: BLE001
        log(f"# roofline probe failed ({type(e).__name__}: {e}); "
            "omitting utilization")
        peaks = {}
    # utilization of the f32 matmul entry specifically (the bf16 variant
    # divides by the bf16 peak)
    util = -1.0
    bf_f32 = [e for e in entries if e["algo"] == "raft_brute_force"
              and ".bf16" not in e["name"]]
    if bf_f32 and peaks.get("matmul_f32_tflops"):
        gemm_tflops = (2.0 * n * d * bf_f32[0]["qps"]) / 1e12
        util = gemm_tflops / max(peaks["matmul_f32_tflops"], 1e-9)

    # headline: BASELINE config 2 (ivf_flat QPS @ recall>=0.95)
    if flat_best is not None:
        value, rec, tag = flat_best
        met = True
    else:
        flat_entries = [e for e in entries if e["algo"] == "raft_ivf_flat"]
        if flat_entries:
            top = max(flat_entries, key=lambda e: e["recall"])
            value, rec, tag = top["qps"], top["recall"], top["name"]
        else:   # every ivf_flat point flaked: say so, don't substitute
            value, rec, tag = 0.0, 0.0, "no-ivf-flat-measurements"
        met = False
    # headline entry FIRST in the list: a truncated tail capture of the
    # stdout line must lose padding entries, never the headline (round 5
    # lost the headline and the 1M entries to a 2000-char tail)
    entries.sort(key=lambda e: e["name"] != tag)
    out = {
        "metric": ("ivf_flat_qps_at_recall095_synth1M" if n >= 1_000_000
                   else f"ivf_flat_qps_at_recall095_synth{n // 1000}k"),
        "value": round(value, 1),
        "unit": "queries/s",
        "vs_baseline": round(value / BASELINE_QPS["raft_ivf_flat"], 3),
        "recall": round(rec, 4),
        "recall_target_met": met,
        "corpus": {"n": n, "d": d, "nq": nq, "k": k, "parts": n_parts,
                   "kind": "low-intrinsic-dim-clustered-synthetic",
                   "mixture_scale": CORPUS_SCALE,
                   "intrinsic_d": CORPUS_INTRINSIC_D,
                   "clusters": CORPUS_CLUSTERS,
                   "queries": "fresh-mixture-samples"},
        "qps_methodology": "value-read pipelined wall over content-"
                           "distinct query permutations (GBench "
                           "items_per_second analog; host float() of an "
                           "all-outputs accumulator closes the window); "
                           "latency_ms = per-call-blocked median",
        "entries": entries,
        "dataset_io": dataset_io,
        "roofline": peaks,
        "bf_gemm_utilization_of_measured_peak": round(util, 4),
        "timing_floor_trips": _autotune.suspect_events,
        "baselines": {a: b["derivation"] for a, b in BASELINES.items()},
        # BASELINE config 5 (multi-node sharded ivf_pq) has no QPS here:
        # one physical chip. Its correctness path runs elsewhere.
        "sharded_config5": {
            "status": "validated-functionally",
            "evidence": "8-device CPU-mesh tests (tests/test_sharded_ann"
                        ".py) + driver dryrun_multichip (brute force, "
                        "ivf_pq AND cagra recall-checked vs exact) + "
                        "2-process jax.distributed DCN smoke "
                        "(RAFT_TPU_DIST_TEST=1 tests/test_distributed.py"
                        ", passed 2026-07-31)"},
    }
    # durable artifact BEFORE any stdout: the full results JSON goes to a
    # file first (fsynced), so no stdout capture window can ever drop data
    # again; the one-line stdout summary then carries the file path
    artifact = os.environ.get("RAFT_TPU_BENCH_JSON",
                              os.path.join("artifacts", "bench_full.json"))
    try:
        adir = os.path.dirname(artifact)
        if adir:
            os.makedirs(adir, exist_ok=True)
        with open(artifact, "w") as f:
            json.dump(out, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        out["results_file"] = artifact
        log(f"# full results written to {artifact}")
    except OSError as e:
        log(f"# bench artifact write FAILED ({e}); stdout line is the "
            "only copy")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
