#!/usr/bin/env python
"""Chip smoke test: the 1M-vector search path, once, on a TPU.

    python chip_smoke.py            # one chip: the phases below, 1M x 128
    python chip_smoke.py --chips 4  # four chips: the sharded path only

One chip (BASELINE config 2's shape: SIFT-1M, 1M x 128 f32, L2, k=10,
10,000 fresh queries; the corpus is the clustered mixture of
``raft_tpu.bench.datasets.make_corpus``, generated on the device from
``--seed``). Every phase goes through the entry points a user calls:

* exact    — ``brute_force.search(algo="matmul")``: the ground truth;
* fused    — ``prepare_fused`` + the ``fused_knn`` kernel, recall >= 0.999
             against the exact ids (and whether it is bit-identical);
* served   — ``make_searcher`` -> ``serve.MicroBatcher`` over a
             ``BucketLadder``: after ``warmup``, 200 requests of 1-10
             queries, each equal to the direct fused answer, with zero
             recompiles;
* ivf_flat — n_lists=1024, n_probes=20, recall@10 >= 0.90;
* ivf_pq   — n_lists=1024, pq_dim=64, 8-bit, n_probes=20, + refine,
             recall@10 >= 0.90;
* cagra    — graph_degree=64 over the first 250k rows (the one cut of
             scale: a 1M graph build alone takes 712-866 s of the 1200 s),
             the exact kNN graph through the fused kernel (not the default
             NN-descent builder), default search engine — the XLA gather
             hop, no kernel, without an edge store — recall@10 >= 0.90
             against exact search of those rows;
* cagra_kernels — the same index's opt-in int8 edge store through the
             graph_expand and fused megakernel engines, recall@10 >= 0.90.

Four chips (4M x 128, 1M rows per chip): sharded exact brute force equal
to a single-device exact search of the same corpus, sharded IVF-PQ
(2k candidates refined to k) with recall@10 >= 0.90 against it, the
``allgather`` and ``ring`` merges bit-identical to each other, and every
shard's rows on its own device.

A phase fails on any exception, a missed gate, or a guarded kernel site
that was demoted to its XLA fallback (``guarded.demoted_sites()``):
every phase must be served by the kernels it names. The script exits
non-zero, printing no result, when JAX finds no TPU or any phase failed.
On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
DIM, K = 128, 10


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn):
    """(result, seconds) of ``fn()``, blocked on every array it returns."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _first_and_steady(fn):
    """Call ``fn`` twice: the first call compiles, the second is steady."""
    _, first = _timed(fn)
    out, steady = _timed(fn)
    return out, first, steady


def recall(ids, ref) -> float:
    """Mean recall@k of ``ids`` (m, k) against the reference ids."""
    import numpy as np

    ids, ref = np.asarray(ids), np.asarray(ref)
    return float((ids[:, :, None] == ref[:, None, :]).any(axis=2).sum()
                 / ref.size)


class Phases:
    """Runs named phases; a phase fails on any exception, including a
    guarded kernel site left demoted after it. Failures are collected so
    one chip run reports every phase."""

    def __init__(self):
        self.failed = []

    def run(self, name, body):
        from raft_tpu.ops import guarded

        log(f"== {name}")
        t0 = time.perf_counter()
        rec = None
        try:
            rec = body()
        except Exception as e:  # noqa: BLE001 - reported, and fails the run
            traceback.print_exc(file=sys.stdout)
            # a missed gate still reports the phase's readings
            log(f"   {name} readings: {json.dumps(getattr(e, 'rec', None))}")
        demoted = guarded.demoted_sites()
        ok = rec is not None and not demoted
        if not ok:
            self.failed.append(name)
        log(f"   {name}: {'ok' if ok else 'FAILED'} "
            f"wall={time.perf_counter() - t0:.3f}s "
            f"demoted_sites={json.dumps(demoted)} {json.dumps(rec)}")
        return rec


class GateMissed(AssertionError):
    def __init__(self, msg: str, rec: dict):
        super().__init__(msg)
        self.rec = rec


def _gate(rec: dict, name: str, value: float, floor: float) -> None:
    if not value >= floor:
        raise GateMissed(f"{name} = {value} below its gate {floor}", rec)


def run(devices, n: int = 1_000_000, nq: int = 10_000, seed: int = 0,
        n_lists: int = 1024, n_graph: int = 250_000) -> list:
    """The one-chip phases on ``devices[0]``; CAGRA indexes the first
    ``n_graph`` rows. Returns the failed phases."""
    import jax
    import numpy as np

    from raft_tpu import serve
    from raft_tpu.bench.datasets import make_corpus
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
    from raft_tpu.neighbors.refine import refine

    ph = Phases()
    st = {}
    # arrays made on devices[0] commit every phase there; no
    # default_device context: it is thread-local, and the batcher
    # dispatches from its own thread
    (data, queries), gen_s = _timed(
        lambda: make_corpus(n, DIM, nq, seed=seed, device=devices[0]))
    log(f"corpus {n}x{DIM} f32, {nq} queries, seed {seed}: "
        f"generated on device in {gen_s:.3f}s")

    def exact():
        index, build_s = _timed(lambda: brute_force.build(data))
        (d, i), first, steady = _first_and_steady(
            lambda: brute_force.search(index, queries, K, algo="matmul"))
        d, i = np.asarray(d), np.asarray(i)
        assert d.shape == i.shape == (nq, K), (d.shape, i.shape)
        assert np.isfinite(d).all() and ((i >= 0) & (i < n)).all()
        assert (np.diff(d, axis=1) >= 0).all(), "distances not sorted"
        st.update(index=index, d=d, i=i)
        return dict(engine="matmul", build_s=build_s, first_s=first,
                    steady_s=steady, recall=1.0)

    def fused():
        index = st["index"]
        _, prep_s = _timed(lambda: (brute_force.prepare_fused(index),
                                    index._fused_pad)[1])
        (d, i), first, steady = _first_and_steady(
            lambda: brute_force.search(index, queries, K, algo="pallas"))
        d, i = np.asarray(d), np.asarray(i)
        rec = dict(engine="pallas (fused_knn)", build_s=prep_s,
                   first_s=first, steady_s=steady, recall=recall(i, st["i"]),
                   ids_identical_to_matmul=bool(np.array_equal(i, st["i"])),
                   distances_identical_to_matmul=bool(
                       np.array_equal(d, st["d"])),
                   max_abs_distance_diff=float(np.abs(d - st["d"]).max()))
        _gate(rec, "fused recall", rec["recall"], 0.999)
        st.update(fused_d=d, fused_i=i)
        return rec

    def served():
        return _served(st["index"], np.asarray(queries), st["fused_d"],
                       st["fused_i"], seed, serve, brute_force)

    def flat():
        index, build_s = _timed(lambda: ivf_flat.build(
            data, ivf_flat.IndexParams(n_lists=n_lists, seed=seed)))
        sp = ivf_flat.SearchParams(n_probes=20)
        (_, i), first, steady = _first_and_steady(
            lambda: ivf_flat.search(index, queries, K, sp, algo="pallas"))
        rec = dict(engine="pallas (ivf_flat scan)", build_s=build_s,
                   first_s=first, steady_s=steady, recall=recall(i, st["i"]))
        _gate(rec, "ivf_flat recall", rec["recall"], 0.90)
        return rec

    def pq():
        index, build_s = _timed(lambda: ivf_pq.build(
            data, ivf_pq.IndexParams(n_lists=n_lists, pq_dim=64,
                                     pq_bits=8, seed=seed)))
        sp = ivf_pq.SearchParams(n_probes=20)

        def search():
            _, cand = ivf_pq.search(index, queries, 2 * K, sp,
                                    algo="pallas")
            return cand, refine(data, queries, cand, K)

        (cand, (_, i)), first, steady = _first_and_steady(search)
        # the XLA gather path scores the same codes: its candidates
        # separate a kernel fault from a build fault. 1k queries: it
        # dispatches ~12 at a time, ~260 s for all 10k on a v5e
        nx = min(nq, 1000)
        _, xla_cand = ivf_pq.search(index, queries[:nx], 2 * K, sp,
                                    algo="xla")
        rec = dict(engine="pallas (ivf_pq scan) + refine",
                   build_s=build_s, first_s=first, steady_s=steady,
                   recall=recall(i, st["i"]),
                   recall_before_refine=recall(np.asarray(cand)[:, :K],
                                               st["i"]),
                   xla_recall_before_refine=recall(
                       np.asarray(xla_cand)[:, :K], st["i"][:nx]))
        _gate(rec, "ivf_pq+refine recall", rec["recall"], 0.90)
        return rec

    def graph():
        # CAGRA indexes the first n_graph rows: its exact kNN graph
        # through the fused kernel took 712 s at 1M on a v5e and the
        # default NN-descent builder 866 s (PR 21 chip runs) — either
        # alone spends most of the 1200 s this script has
        sub = data[:n_graph]
        exact_i = np.asarray(brute_force.search(
            brute_force.build(sub), queries, K, algo="matmul")[1])
        index, build_s = _timed(lambda: cagra.build(
            sub, cagra.IndexParams(graph_degree=64, knn_graph_algo="brute",
                                   seed=seed)))
        (_, i), first, steady = _first_and_steady(
            lambda: cagra.search(index, queries, K))
        store = getattr(index, "_edge_store", None)
        # without an edge store, search's auto choice is the XLA gather
        # hop — no kernel; the kernels run in cagra_kernels below
        rec = dict(rows=n_graph, engine="edge (graph_expand)"
                   if store is not None else "gather (XLA, no kernel)",
                   build_s=build_s, first_s=first, steady_s=steady,
                   recall=recall(i, exact_i),
                   build_stats=getattr(index, "build_stats", {}))
        _gate(rec, "cagra recall", rec["recall"], 0.90)
        st.update(cagra=index, cagra_i=exact_i)
        return rec

    def graph_kernels():
        # the opt-in streamed engines over an int8 edge store: the
        # per-hop graph_expand kernel and the one-dispatch megakernel
        index = st.pop("cagra")
        _, prep_s = _timed(lambda: (cagra.prepare_traversal(index),
                                    index._edge_store[1])[1])
        rec = dict(rows=n_graph, store_s=prep_s)
        ids = {}
        for eng in ("edge", "fused"):
            (_, i), first, steady = _first_and_steady(
                lambda: cagra.search(index, queries, K, engine=eng))
            ids[eng] = np.asarray(i)
            rec[eng] = dict(first_s=first, steady_s=steady,
                            recall=recall(i, st["cagra_i"]))
            _gate(rec, f"cagra {eng} recall", rec[eng]["recall"], 0.90)
        rec["fused_equals_edge"] = bool(np.array_equal(ids["edge"],
                                                       ids["fused"]))
        return rec

    ph.run("exact", exact)
    if "exact" in ph.failed:
        return ph.failed        # nothing else can be checked
    ph.run("fused", fused)
    if "fused" not in ph.failed:
        ph.run("served", served)
    st.pop("index", None)       # frees the corpus copies for the next
    ph.run("ivf_flat", flat)
    ph.run("ivf_pq", pq)
    ph.run("cagra", graph)
    if "cagra" not in ph.failed:
        ph.run("cagra_kernels", graph_kernels)
    return ph.failed


def _served(index, queries, ref_d, ref_i, seed, serve, brute_force):
    """200 requests of 1-10 queries through the micro-batcher; every
    answer must equal the direct fused answer for its rows."""
    import numpy as np

    from raft_tpu.serve import metrics

    fn = brute_force.make_searcher(index, algo="pallas")
    ladder = serve.BucketLadder(query_buckets=(16, 64, 256), k_buckets=(16,))
    recompiles = metrics.counter("serve.recompiles")
    rng = np.random.default_rng(seed)
    with serve.MicroBatcher(fn, DIM, ladder=ladder, name="smoke") as mb:
        warm_compiles, warm_s = _timed(mb.warmup)
        before = recompiles.value
        with serve.count_compilations() as cc:
            t0 = time.perf_counter()
            reqs = []
            for _ in range(200):
                rows = rng.choice(len(queries), int(rng.integers(1, 11)),
                                  replace=False)
                reqs.append((rows, mb.submit(queries[rows], K)))
            got = [(rows, r.result(timeout=600)) for rows, r in reqs]
            serve_s = time.perf_counter() - t0
    bad_ids = sum(not np.array_equal(np.asarray(res.indices), ref_i[rows])
                  for rows, res in got)
    bad_d = sum(not np.array_equal(np.asarray(res.distances), ref_d[rows])
                for rows, res in got)
    rec = dict(engine="pallas (fused_knn) via MicroBatcher",
               warmup_s=warm_s, warmup_compiles=warm_compiles,
               requests=len(got), serve_s=serve_s,
               compiles_after_warmup=cc.count,
               recompiles=recompiles.value - before,
               answers_differing_ids=bad_ids,
               answers_differing_distances=bad_d)
    if bad_ids or bad_d or cc.count or rec["recompiles"]:
        raise AssertionError(f"served answers or compiles off: {rec}")
    return rec


def _check_placement(arr, devices) -> list:
    """Every shard of ``arr`` on its own device of the mesh, holding an
    equal block of its leading axis. Returns the per-device shapes."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
    devs = [s.device for s in shards]
    assert len(set(devs)) == len(devices) == len(shards), devs
    assert set(devs) == set(devices), (devs, devices)
    shapes = [tuple(s.data.shape) for s in shards]
    assert len(set(shapes)) == 1, shapes
    assert sum(s[0] for s in shapes) == arr.shape[0], shapes
    return shapes


def run_sharded(devices, n_per: int = 1_000_000, nq: int = 10_000,
                seed: int = 0, n_lists: int = 1024) -> list:
    """The sharded phases over all of ``devices``: ``n_per`` rows per
    device. Returns the failed phases."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from raft_tpu.bench.datasets import make_corpus
    from raft_tpu.neighbors import brute_force, ivf_pq
    from raft_tpu.neighbors.refine import refine
    from raft_tpu.ops import ring_topk
    from raft_tpu.parallel import sharded_ann, sharded_knn

    p = len(devices)
    assert len({d.id for d in devices}) == p, devices
    mesh = Mesh(np.array(devices), (sharded_knn.AXIS,))
    # ring_pallas halted a v5e 2x2 (PR 21): off every TPU engine choice
    engines = ["allgather", "ring"]
    mq = min(nq, 512)
    ph = Phases()
    st = {}
    (data, queries), gen_s = _timed(
        lambda: make_corpus(n_per * p, DIM, nq, seed=seed, device=devices[0]))
    log(f"corpus {n_per * p}x{DIM} f32 ({n_per} rows per device x {p}), "
        f"{nq} queries: generated on {devices[0]} in {gen_s:.3f}s")
    # the sharded entry points take host arrays and place them on the mesh
    host_data, host_q = np.asarray(data), np.asarray(queries)

    def single():
        with jax.default_device(devices[0]):
            index = brute_force.build(data)
            (d, i), first, steady = _first_and_steady(
                lambda: brute_force.search(index, queries, K, algo="matmul"))
        st.update(d=np.asarray(d), i=np.asarray(i))
        return dict(engine="matmul on one device", first_s=first,
                    steady_s=steady)

    def merges(family, search, *, gate=None):
        """Full-batch search on the default merge, then every merge
        engine on the first ``mq`` queries: bit-identical to each other
        and to the full batch's rows."""
        (d, i), first, steady = _first_and_steady(lambda: search(host_q, None))
        d, i = np.asarray(d), np.asarray(i)
        rec = dict(first_s=first, steady_s=steady,
                   engine=ring_topk.active_engines.get(family))
        for eng in engines:
            (de, ie), t = _timed(lambda: search(host_q[:mq], eng))
            served_by = ring_topk.active_engines.get(family)
            assert served_by == eng, f"{eng} served by {served_by}"
            assert np.array_equal(np.asarray(ie), i[:mq]), eng
            assert np.array_equal(np.asarray(de), d[:mq]), eng
            rec[f"{eng}_first_s"] = t
        rec["merges_bit_identical"] = engines
        return d, i, rec

    def knn():
        index = sharded_knn.build(host_data, mesh)
        rows = _check_placement(index.dataset, devices)
        _, i, rec = merges("knn", lambda q, e: sharded_knn.search(
            index, q, K, algo="matmul", merge_engine=e))
        assert np.array_equal(i, st["i"]), "sharded ids != single device"
        rec.update(shard_shapes=rows, ids_equal_single_device=True)
        return rec

    def pq():
        params = ivf_pq.IndexParams(n_lists=n_lists, pq_dim=64, pq_bits=8,
                                    seed=seed)
        index, build_s = _timed(
            lambda: sharded_ann.build_ivf_pq(host_data, mesh, params))
        rows = _check_placement(index.codes, devices)
        sp = ivf_pq.SearchParams(n_probes=20)
        # 2k candidates refined to k, as in the one-chip phase
        _, cand, rec = merges("ivf_pq", lambda q, e: sharded_ann.search_ivf_pq(
            index, q, 2 * K, sp, merge_engine=e))
        _, i = refine(data, queries, cand, K)
        rec.update(build_s=build_s, recall=recall(i, st["i"]),
                   recall_before_refine=recall(cand[:, :K], st["i"]),
                   code_shard_shapes=rows)
        _gate(rec, "sharded ivf_pq recall", rec["recall"], 0.90)
        return rec

    ph.run("single_device_exact", single)
    if ph.failed:
        return ph.failed
    ph.run("sharded_knn", knn)
    ph.run("sharded_ivf_pq", pq)
    return ph.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the sharded path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    from raft_tpu.utils import use_compile_cache

    use_compile_cache(HERE)
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        log("no TPU: this smoke test runs only on the chip")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices")
        return 1
    t0 = time.perf_counter()
    if args.chips == 1:
        failed = run(devices[:1], seed=args.seed)
    else:
        failed = run_sharded(devices[:args.chips], seed=args.seed)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    log(f"total wall {time.perf_counter() - t0:.3f}s; failed phases: "
        f"{failed}")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
