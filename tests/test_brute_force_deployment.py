"""The SIFT-shaped exact deployment's path at a small size: the serving
closure ``make_searcher(index, algo="pallas", precision="highest")``
against a plain float64 exact kNN, the fused path's spans in a profile,
and the counter that records which engine served a search."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.bench.datasets import make_corpus
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force
from raft_tpu.serve import metrics

D, K = 128, 10


def _exact(data, queries, k):
    """Plain float64 exact squared-L2 top-k: (distances, ids)."""
    x = np.asarray(data, np.float64)
    q = np.asarray(queries, np.float64)
    d = ((q[:, None, :] - x[None, :, :]) ** 2).sum(axis=2)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, axis=1), ids


@pytest.fixture(scope="module")
def corpus():
    data, queries = make_corpus(3000, D, 64, n_clusters=20, seed=7)
    return np.asarray(data), np.asarray(queries)


def test_serving_closure_matches_float64_exact(corpus):
    data, queries = corpus
    index = brute_force.build(data, metric="sqeuclidean")
    search = brute_force.make_searcher(index, algo="pallas",
                                       precision="highest")
    d, i = (np.asarray(a) for a in search(queries, K))
    ref_d, ref_i = _exact(data, queries, K)
    # each returned distance against the float64 distance of that id, as
    # a share of the exact 10th distance: the benchmark cell's dist_gap
    x, q = np.asarray(data, np.float64), np.asarray(queries, np.float64)
    pair = ((x[i] - q[:, None, :]) ** 2).sum(axis=2)
    gap = np.abs(d - pair).max(axis=1) / ref_d[:, -1]
    assert gap.max() <= 1e-5, gap.max()
    hits = (i[:, :, None] == ref_i[:, None, :]).any(axis=2).sum()
    assert hits / ref_i.size >= 0.999
    assert (np.diff(d, axis=1) >= 0).all()


def _profile_spans(tmp_path, fn):
    """``fn`` under a ``jax.profiler`` trace; its ``raft_tpu::`` spans,
    ``[(name, start_ns, end_ns)]``, from every host line."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith("raft_tpu::brute_force::")]


def _small_index(rows=512, dim=16):
    rng = np.random.default_rng(0)
    data = rng.standard_normal((rows, dim)).astype(np.float32)
    return brute_force.build(data), data[:8]


def test_fused_span_inside_the_search_span(tmp_path):
    index, q = _small_index()
    brute_force.prepare_fused(index)

    def search():
        jax.block_until_ready(brute_force.search(index, q, 4, algo="pallas"))

    search()                      # compile outside the profile
    spans = _profile_spans(tmp_path, search)
    by = {}
    for ev in spans:
        by.setdefault(ev[0], []).append(ev)
    # the corpus is aligned already: no copy, so no prepare span
    assert set(by) == {"raft_tpu::brute_force::search",
                       "raft_tpu::brute_force::fused"}
    (outer,), (fused,) = (by["raft_tpu::brute_force::search"],
                          by["raft_tpu::brute_force::fused"])
    assert outer[1] <= fused[1] and fused[2] <= outer[2]


def test_prepare_span_only_when_it_copies(tmp_path):
    index, _ = _small_index()

    def closures():
        brute_force.make_searcher(index, algo="pallas")
        brute_force.make_searcher(index, algo="pallas")   # cache hit

    spans = _profile_spans(tmp_path, closures)
    assert [n for n, *_ in spans] == ["raft_tpu::brute_force::prepare"]
    assert getattr(index, "_fused_pad", None) is not None


_ENGINES = ("pallas", "matmul", "scan", "crossover")


def _counts():
    return {e: metrics.counter(f"brute_force.search.{e}").value
            for e in _ENGINES}


@pytest.mark.parametrize("algo", ["pallas", "matmul", "scan", "auto"])
def test_engine_counter_rises_once_a_search(algo):
    index, q = _small_index()
    before = _counts()
    jax.block_until_ready(brute_force.search(index, q, 4, algo=algo))
    after = _counts()
    rose = {e: after[e] - before[e] for e in _ENGINES}
    # under auto the counter is the record of the engine it chose
    served = (max(rose, key=rose.get) if algo == "auto" else algo)
    assert served != "crossover"
    assert rose == {e: float(e == served) for e in _ENGINES}


def test_engine_counter_names_the_filter_crossover():
    index, q = _small_index()
    mask = np.zeros(index.size, bool)
    mask[::4] = True                          # 128 survivors: crossover
    before = _counts()
    _, ids = brute_force.search(index, q, 4, filter=Bitset.from_mask(
        jnp.asarray(mask)), algo="pallas")
    after = _counts()
    assert mask[np.asarray(ids)].all()
    rose = {e: after[e] - before[e] for e in _ENGINES}
    # the crossover, and the compacted set's own search under its engine
    assert rose.pop("crossover") == 1.0
    assert sorted(rose.values()) == [0.0, 0.0, 1.0]


def test_traced_search_counts_nothing():
    index, q = _small_index()
    before = _counts()
    jax.block_until_ready(jax.jit(
        lambda qq: brute_force.search(index, qq, 4, algo="matmul"))(q))
    assert _counts() == before
