"""Sharded ANN tests on the 8-device virtual CPU mesh (the raft-dask
LocalCUDACluster analog, SURVEY.md §4: distributed tests without a real
cluster exercise the real collective code paths)."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from ann_utils import calc_recall, naive_knn
from raft_tpu.neighbors import cagra, ivf_flat
from raft_tpu.parallel import sharded_ann


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:4]), ("shard",))


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(7)
    return rng.standard_normal((8_000, 32)).astype(np.float32)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(8)
    return rng.standard_normal((50, 32)).astype(np.float32)


# builds dominate this module's wall on the 1-core CI box (the 870s
# tier-1 timeout is tight): tests that search the same configuration
# share one module-scoped build — searches never mutate the index
@pytest.fixture(scope="module")
def flat_index16(mesh, dataset):
    return sharded_ann.build_ivf_flat(
        dataset, mesh, ivf_flat.IndexParams(n_lists=16, seed=0))


@pytest.fixture(scope="module")
def pq_index16(mesh, dataset):
    from raft_tpu.neighbors import ivf_pq

    return sharded_ann.build_ivf_pq(
        dataset, mesh, ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0))


class TestShardedIvfFlat:
    def test_recall_and_merge(self, mesh, dataset, queries, flat_index16):
        index = flat_index16
        assert index.n_shards == 4
        # full probes per shard → exact: merged result must match global knn
        d, i = sharded_ann.search_ivf_flat(
            index, queries, k=10, params=ivf_flat.SearchParams(n_probes=16))
        want_d, want_i = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(i), want_i) == 1.0
        np.testing.assert_allclose(np.asarray(d), want_d, rtol=1e-2, atol=1e-2)

    # tier-1 wall: one low-precision param suffices for the sharded path
    # (storage dtype never reaches the cross-shard merge); the full dtype
    # matrix is single-chip coverage (test_ivf_flat) + the slow lane
    @pytest.mark.parametrize("dtype", [
        "bfloat16",
        pytest.param("int8", marks=pytest.mark.slow),
        pytest.param("uint8", marks=pytest.mark.slow)])
    def test_low_precision_storage(self, mesh, dataset, queries, dtype):
        data, q = dataset, queries
        if dtype == "uint8":  # byte-valued corpus for exact uint8 storage
            data = np.round(np.clip(data * 40 + 128, 0, 255)
                            ).astype(np.float32)
            q = np.round(np.clip(q * 40 + 128, 0, 255)).astype(np.float32)
        index = sharded_ann.build_ivf_flat(
            data, mesh, ivf_flat.IndexParams(n_lists=16, seed=0,
                                             dtype=dtype))
        d, i = sharded_ann.search_ivf_flat(
            index, q, k=10, params=ivf_flat.SearchParams(n_probes=16))
        _, want_i = naive_knn(data, q, 10)
        r = calc_recall(np.asarray(i), want_i)
        floor = {"bfloat16": 0.95, "int8": 0.9, "uint8": 0.9999}[dtype]
        assert r > floor, r

    # tier-1 wall: a recall-only variant of test_recall_and_merge (the
    # partial-probe mechanics are single-chip coverage in test_ivf_flat)
    @pytest.mark.slow
    def test_partial_probes(self, mesh, dataset, queries, flat_index16):
        index = flat_index16
        _, i = sharded_ann.search_ivf_flat(
            index, queries, k=10, params=ivf_flat.SearchParams(n_probes=8))
        _, want_i = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(i), want_i) >= 0.7

    def test_uneven_rows(self, mesh, queries):
        rng = np.random.default_rng(3)
        data = rng.standard_normal((8_000 - 37, 32)).astype(np.float32)
        index = sharded_ann.build_ivf_flat(
            data, mesh, ivf_flat.IndexParams(n_lists=8, seed=0))
        d, i = sharded_ann.search_ivf_flat(
            index, queries, k=5, params=ivf_flat.SearchParams(n_probes=8))
        got = np.asarray(i)
        assert got.max() < len(data)
        _, want_i = naive_knn(data, queries, 5)
        assert calc_recall(got, want_i) == 1.0


class TestShardedCagra:
    def test_recall(self, mesh, dataset, queries):
        index = sharded_ann.build_cagra(
            dataset, mesh, cagra.IndexParams(
                intermediate_graph_degree=48, graph_degree=24, seed=0))
        d, i = sharded_ann.search_cagra(
            index, queries, k=10, params=cagra.SearchParams(itopk_size=64))
        _, want_i = naive_knn(dataset, queries, 10)
        got = np.asarray(i)
        assert got.max() < len(dataset)
        assert (got >= 0).all()
        r = calc_recall(got, want_i)
        assert r >= 0.9, f"sharded cagra recall {r}"

    def test_uneven_rows_no_padding_leak(self, mesh, queries):
        rng = np.random.default_rng(4)
        data = rng.standard_normal((4_000 - 13, 32)).astype(np.float32)
        index = sharded_ann.build_cagra(
            data, mesh, cagra.IndexParams(
                intermediate_graph_degree=32, graph_degree=16, seed=0))
        _, i = sharded_ann.search_cagra(
            index, queries, k=10, params=cagra.SearchParams(itopk_size=64))
        got = np.asarray(i)
        assert got.max() < len(data)  # no padded-row global ids


class TestShardedIvfPq:
    def test_recall_vs_single_shard(self, mesh, dataset, queries,
                                    pq_index16):
        from raft_tpu.neighbors import ivf_pq

        index = pq_index16
        assert index.n_shards == 4
        d, i = sharded_ann.search_ivf_pq(
            index, queries, k=10, params=ivf_pq.SearchParams(n_probes=16))
        got = np.asarray(i)
        assert got.max() < len(dataset) and (got >= -1).all()
        _, want_i = naive_knn(dataset, queries, 10)
        r = calc_recall(got, want_i)
        # PQ is lossy and random gaussian data is its worst case: the
        # single-index build at these params measures 0.586 on this data —
        # the sharded merge must stay at that quality level
        assert r >= 0.5, f"sharded ivf_pq recall {r}"

    def test_query_chunks_match_one_pass(self, queries, pq_index16,
                                         monkeypatch):
        """A batch past the workspace budget is searched in chunks of
        queries (the tail padded) with the one-pass answer."""
        from raft_tpu.neighbors import ivf_pq

        sp = ivf_pq.SearchParams(n_probes=16)
        d1, i1 = sharded_ann.search_ivf_pq(pq_index16, queries, 10, sp)
        per_q = (pq_index16.max_rows(16) * 8 * 8 + 16 * 8 * 256 * 4)
        monkeypatch.setattr(sharded_ann, "workspace_chunk_bytes",
                            lambda res: 7 * per_q)     # 50 = 7 × 7 + 1
        d2, i2 = sharded_ann.search_ivf_pq(pq_index16, queries, 10, sp)
        assert np.mean(np.asarray(i1) == np.asarray(i2)) > 0.99
        np.testing.assert_allclose(np.asarray(d2), np.asarray(d1),
                                   rtol=1e-5, atol=1e-5)

    # tier-1 wall (PR 8 pays for the quality-observability suite):
    # uneven-row stacking/rebasing stays tier-1 via the ivf_flat and
    # cagra uneven tests through the same merge chokepoint, and the
    # MULTICHIP dryrun gates ivf_pq global-id ranges + recall at 10k
    # rows/device every PR; this fresh-shape ivf_pq build (~14s of
    # compiles) moves to the slow lane
    @pytest.mark.slow
    def test_uneven_rows_no_padding_leak(self, mesh, queries):
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(5)
        data = rng.standard_normal((4_000 - 21, 32)).astype(np.float32)
        index = sharded_ann.build_ivf_pq(
            data, mesh, ivf_pq.IndexParams(n_lists=8, pq_dim=8, seed=0))
        d, i = sharded_ann.search_ivf_pq(
            index, queries, k=10, params=ivf_pq.SearchParams(n_probes=8))
        got = np.asarray(i)
        assert got.max() < len(data)
        assert (got >= 0).all()

    # tier-1 wall: the fast comms-injection equivalent lives in
    # test_core.py; this full sharded-search form moves to the slow lane
    @pytest.mark.slow
    def test_comms_injection(self, mesh, dataset, queries, pq_index16):
        """search via a Resources-injected communicator (comms_t pattern)."""
        from raft_tpu.comms import AxisComms
        from raft_tpu.core.resources import Resources
        from raft_tpu.neighbors import ivf_pq

        res = Resources(mesh=mesh)
        res.set_comms(AxisComms("shard", size=4))
        index = pq_index16
        d1, i1 = sharded_ann.search_ivf_pq(
            index, queries, k=5, params=ivf_pq.SearchParams(n_probes=16),
            res=res)
        d2, i2 = sharded_ann.search_ivf_pq(
            index, queries, k=5, params=ivf_pq.SearchParams(n_probes=16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
