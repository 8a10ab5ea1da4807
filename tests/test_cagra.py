"""CAGRA + NN-descent tests (analog of NEIGHBORS_ANN_CAGRA_TEST /
NEIGHBORS_ANN_NN_DESCENT_TEST): recall vs brute-force oracle (SURVEY.md §4)."""
import jax.numpy as jnp
import numpy as np
import pytest

from ann_utils import calc_recall, naive_knn
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import cagra, nn_descent


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(7)
    return rng.standard_normal((6_000, 32)).astype(np.float32)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(8)
    return rng.standard_normal((100, 32)).astype(np.float32)


@pytest.fixture(scope="module")
def knn_oracle(dataset):
    return naive_knn(dataset, dataset, 33)  # k+1: includes self


@pytest.fixture(scope="module")
def built_index(dataset):
    return cagra.build(dataset, cagra.IndexParams(
        intermediate_graph_degree=64, graph_degree=32, seed=0))


class TestNnDescent:
    @pytest.mark.slow
    def test_graph_quality(self, dataset, knn_oracle):
        k = 32
        graph = nn_descent.build(dataset, k, n_iters=20, seed=0)
        assert graph.shape == (len(dataset), k)
        assert (graph != np.arange(len(dataset))[:, None]).all()  # no self
        _, want_full = knn_oracle
        # drop the self column from the oracle (vectorized)
        rows = np.arange(len(dataset))[:, None]
        not_self = want_full != rows
        order = np.argsort(~not_self, axis=1, kind="stable")[:, :k]
        want = np.take_along_axis(want_full, order, axis=1)
        r = calc_recall(graph, want)
        assert r >= 0.85, f"nn_descent graph recall {r}"


class TestCagra:
    def test_structure(self, built_index, dataset):
        assert built_index.size == len(dataset)
        assert built_index.graph_degree == 32
        g = np.asarray(built_index.graph)
        assert g.min() >= 0 and g.max() < len(dataset)
        assert (g != np.arange(len(dataset))[:, None]).all()  # no self loops

    @pytest.mark.parametrize("itopk,min_recall", [(64, 0.90), (128, 0.95)])
    def test_recall(self, built_index, dataset, queries, itopk, min_recall):
        _, idx = cagra.search(built_index, queries, k=10,
                              params=cagra.SearchParams(itopk_size=itopk))
        _, want = naive_knn(dataset, queries, 10)
        r = calc_recall(np.asarray(idx), want)
        assert r >= min_recall, f"recall {r} < {min_recall} at itopk={itopk}"

    def test_distances_match_l2(self, built_index, dataset, queries):
        dist, idx = cagra.search(built_index, queries, k=5,
                                 params=cagra.SearchParams(itopk_size=64))
        d, i = np.asarray(dist), np.asarray(idx)
        for row in range(0, 100, 13):
            true = ((queries[row] - dataset[i[row, 0]]) ** 2).sum()
            assert abs(d[row, 0] - true) < 1e-1

    def test_search_width(self, built_index, dataset, queries):
        _, idx = cagra.search(built_index, queries, k=10,
                              params=cagra.SearchParams(itopk_size=64,
                                                        search_width=4))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.85

    @pytest.mark.slow
    def test_nn_descent_build(self, dataset, queries):
        index = cagra.build(dataset, cagra.IndexParams(
            intermediate_graph_degree=64, graph_degree=32,
            build_algo=cagra.BuildAlgo.NN_DESCENT, seed=0))
        _, idx = cagra.search(index, queries, k=10,
                              params=cagra.SearchParams(itopk_size=64))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.85

    def test_filter(self, built_index, dataset, queries):
        _, base = naive_knn(dataset, queries, 1)
        mask = np.ones(len(dataset), bool)
        mask[base[:, 0]] = False
        filt = Bitset.from_mask(jnp.asarray(mask))
        _, idx = cagra.search(built_index, queries, k=10,
                              params=cagra.SearchParams(itopk_size=64),
                              filter=filt)
        got = np.asarray(idx)
        assert all(base[i, 0] not in got[i] for i in range(len(got)))

    def test_save_load(self, tmp_path, built_index, queries):
        cagra.save(built_index, tmp_path / "cagra.raft")
        loaded = cagra.load(tmp_path / "cagra.raft")
        _, i1 = cagra.search(built_index, queries, k=5,
                             params=cagra.SearchParams(itopk_size=64))
        _, i2 = cagra.search(loaded, queries, k=5,
                             params=cagra.SearchParams(itopk_size=64))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_optimize_prunes_to_degree(self, dataset):
        knn = cagra.build_knn_graph(dataset[:2000], 32, seed=0)
        graph = cagra.optimize(knn, 16)
        assert graph.shape == (2000, 16)
        assert (graph != np.arange(2000)[:, None]).all()

    def test_rev_group_host_matches_jit(self):
        """The host fallback (scale guard for the monolithic device sort)
        must reproduce _rev_group_jit bit-for-bit."""
        rng = np.random.default_rng(7)
        n, keep_fwd, cap = 500, 8, 16
        pruned = rng.integers(-1, n, size=(n, 16)).astype(np.int32)
        want = np.asarray(cagra._rev_group_jit(
            jnp.asarray(pruned), keep_fwd, cap))
        got = cagra._rev_group_host(pruned, keep_fwd, cap)
        np.testing.assert_array_equal(got, want)

    def test_knn_graph_brute_exact(self, dataset, knn_oracle):
        """The brute path must produce the exact kNN graph."""
        sub = dataset[:2000]
        g = cagra.build_knn_graph(sub, 8, algo="brute")
        _, want_full = naive_knn(sub, sub, 9)
        rows = np.arange(2000)[:, None]
        not_self = want_full != rows
        order = np.argsort(~not_self, axis=1, kind="stable")[:, :8]
        want = np.take_along_axis(want_full, order, axis=1)
        assert calc_recall(g, want) >= 0.999

    def test_knn_graph_brute_parted_matches_single(self, dataset,
                                                   monkeypatch):
        """Past the compile cap the brute path splits into equal parts
        with masked padding and exact merge: same graph as one part."""
        sub = dataset[:1500]
        want = cagra.build_knn_graph(sub, 8, algo="brute")
        monkeypatch.setenv("RAFT_TPU_CAGRA_BRUTE_PART_N", "600")
        got = cagra.build_knn_graph(sub, 8, algo="brute")
        # per-row SET near-equality: part-shaped GEMMs reduce in a
        # different order, so near-tied neighbors can swap rank by one
        # ULP — including across the k boundary, which changes the set
        # for that row
        assert calc_recall(got, want) >= 0.999

    def test_knn_graph_ivf_pq_path(self, dataset):
        """The reference's ivf_pq+refine path stays available above the
        brute cutover (forced here via algo=). 1200 rows: the path cost
        is compile-dominated, so the corpus only needs to clear the
        n_lists floor — the r8 graph-build suite added ~14s of tier-1
        and this rung gave ~5s of it back."""
        g = cagra.build_knn_graph(dataset[:1200], 8, algo="ivf_pq")
        assert g.shape == (1200, 8)
        assert (g != np.arange(1200)[:, None]).all()

    def test_candidate_dtype_int8(self, built_index, dataset, queries):
        _, idx = cagra.search(built_index, queries, k=10,
                              params=cagra.SearchParams(
                                  itopk_size=64, candidate_dtype="int8"))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.85

    def test_seed_nodes_help_capped_traversal(self, built_index, dataset,
                                              queries):
        """The shared covering seed set (IndexParams.seed_nodes) must not
        hurt, and under a tight hop cap should beat random-only seeding
        (it starts the walk near every cluster)."""
        assert built_index.seed_nodes is not None
        unseeded = cagra.Index(built_index.dataset, built_index.graph,
                               built_index.metric, None)
        _, want = naive_knn(dataset, queries, 10)
        sp = cagra.SearchParams(itopk_size=32, search_width=4,
                                max_iterations=4)
        _, i_seed = cagra.search(built_index, queries, k=10, params=sp)
        _, i_rand = cagra.search(unseeded, queries, k=10, params=sp)
        r_seed = calc_recall(np.asarray(i_seed), want)
        r_rand = calc_recall(np.asarray(i_rand), want)
        # unclustered gaussian corpus at 4 hops: measured 0.77 vs 0.71
        # (clustered corpora show a larger gap — 0.90 vs 0.80)
        assert r_seed >= 0.7, r_seed
        assert r_seed >= r_rand - 0.02, (r_seed, r_rand)

    def test_index_as_jit_argument(self, built_index, dataset, queries):
        """The pytree carries the traversal caches and seed set
        byte-identical, so jitted functions can take the index as an
        ARGUMENT (not as index-sized baked closure constants)."""
        import jax

        cagra.prepare_search(built_index)
        leaves, td = jax.tree_util.tree_flatten(built_index)
        rebuilt = jax.tree_util.tree_unflatten(td, leaves)
        np.testing.assert_array_equal(np.asarray(built_index._score_bf16),
                                      np.asarray(rebuilt._score_bf16))
        np.testing.assert_array_equal(np.asarray(built_index.seed_nodes),
                                      np.asarray(rebuilt.seed_nodes))
        fn = jax.jit(lambda q, idx: cagra.search(
            idx, q, 10, cagra.SearchParams(itopk_size=64)))
        _, i1 = fn(queries, rebuilt)
        _, i2 = cagra.search(built_index, queries, k=10,
                             params=cagra.SearchParams(itopk_size=64))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_max_iterations_cap(self, built_index, dataset, queries):
        """A capped traversal still reaches usable recall (the bench's
        QPS@0.95 operating point) and never exceeds the cap's work."""
        _, idx = cagra.search(built_index, queries, k=10,
                              params=cagra.SearchParams(
                                  itopk_size=32, search_width=4,
                                  max_iterations=10))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.80
