"""IVF-Flat tests (analog of NEIGHBORS_ANN_IVF_TEST): recall vs brute-force
oracle over a param sweep, never exact equality (SURVEY.md §4)."""
import jax.numpy as jnp
import numpy as np
import pytest

from ann_utils import calc_recall, naive_knn
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import ivf_flat


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(7)
    return rng.standard_normal((20_000, 32)).astype(np.float32)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(8)
    return rng.standard_normal((100, 32)).astype(np.float32)


@pytest.fixture(scope="module")
def built_index(dataset):
    return ivf_flat.build(dataset, ivf_flat.IndexParams(n_lists=64, seed=0))


class TestIvfFlat:
    def test_structure(self, built_index, dataset):
        assert built_index.size == len(dataset)
        assert built_index.n_lists == 64
        sizes = built_index.list_sizes
        assert sizes.sum() == len(dataset)
        assert sizes.min() > 0
        # every source id appears exactly once on valid rows; capacity
        # slack rows carry the -1 sentinel
        ids = np.asarray(built_index.source_ids)
        valid = ids[ids >= 0]
        np.testing.assert_array_equal(np.sort(valid),
                                      np.arange(len(dataset)))
        caps = np.diff(built_index.list_offsets)
        assert (caps >= sizes).all()

    # NOTE: thresholds calibrated on unstructured gaussian data, where probing
    # 8/64 lists gives ~0.56 *upper-bound* recall (partition-limited, verified
    # against the probed-list membership oracle); real ANN datasets cluster
    # far better. 64/64 probes must be exact.
    @pytest.mark.parametrize("n_probes,min_recall", [(8, 0.50), (16, 0.68), (64, 0.9999)])
    def test_recall(self, built_index, dataset, queries, n_probes, min_recall):
        dist, idx = ivf_flat.search(built_index, queries, k=10,
                                    params=ivf_flat.SearchParams(n_probes))
        _, want = naive_knn(dataset, queries, 10)
        r = calc_recall(np.asarray(idx), want)
        assert r >= min_recall, f"recall {r} < {min_recall} at n_probes={n_probes}"

    def test_all_probes_is_exact(self, built_index, dataset, queries):
        dist, idx = ivf_flat.search(built_index, queries, k=5,
                                    params=ivf_flat.SearchParams(n_probes=64))
        want_d, want_i = naive_knn(dataset, queries, 5)
        np.testing.assert_allclose(np.asarray(dist), want_d, rtol=1e-2, atol=1e-2)

    def test_distances_match_l2(self, built_index, dataset, queries):
        dist, idx = ivf_flat.search(built_index, queries, k=3,
                                    params=ivf_flat.SearchParams(n_probes=32))
        d = np.asarray(dist)
        i = np.asarray(idx)
        # returned distances must equal true L2^2 to the returned ids
        for row in range(0, 100, 17):
            for col in range(3):
                true = ((queries[row] - dataset[i[row, col]]) ** 2).sum()
                assert abs(d[row, col] - true) < 1e-1

    def test_inner_product(self, dataset, queries):
        index = ivf_flat.build(dataset, ivf_flat.IndexParams(
            n_lists=32, metric="inner_product", seed=0))
        _, idx = ivf_flat.search(index, queries, k=10,
                                 params=ivf_flat.SearchParams(n_probes=16))
        _, want = naive_knn(dataset, queries, 10, "inner_product")
        assert calc_recall(np.asarray(idx), want) > 0.85

    def test_extend(self, dataset, queries):
        index = ivf_flat.build(dataset[:10_000], ivf_flat.IndexParams(n_lists=32, seed=0))
        index = ivf_flat.extend(index, dataset[10_000:],
                                np.arange(10_000, 20_000, dtype=np.int32))
        assert index.size == 20_000
        _, idx = ivf_flat.search(index, queries, k=10,
                                 params=ivf_flat.SearchParams(n_probes=16))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) > 0.9

    def test_extend_in_place_with_growth_slack(self, dataset, queries):
        # growth=2: the second half fits in slack, so extend keeps the SAME
        # offsets (the O(batch) in-place scatter path)
        p = ivf_flat.IndexParams(n_lists=32, seed=0, list_growth=2.0)
        index = ivf_flat.build(dataset[:10_000], p)
        off0 = index.list_offsets.copy()
        index2 = ivf_flat.extend(index, dataset[10_000:13_000],
                                 np.arange(10_000, 13_000, dtype=np.int32))
        np.testing.assert_array_equal(index2.list_offsets, off0)
        assert index2.size == 13_000
        _, idx = ivf_flat.search(index2, queries, k=10,
                                 params=ivf_flat.SearchParams(n_probes=16))
        _, want = naive_knn(dataset[:13_000], queries, 10)
        assert calc_recall(np.asarray(idx), want) > 0.85

    def test_extend_overflow_repacks(self, dataset, queries):
        # growth=1: slack is only alignment, so a large extend overflows
        # and triggers the device-side repack; results stay correct
        index = ivf_flat.build(dataset[:10_000],
                               ivf_flat.IndexParams(n_lists=32, seed=0))
        index2 = ivf_flat.extend(index, dataset[10_000:],
                                 np.arange(10_000, 20_000, dtype=np.int32))
        assert index2.size == 20_000
        ids = np.asarray(index2.source_ids)
        np.testing.assert_array_equal(np.sort(ids[ids >= 0]),
                                      np.arange(20_000))
        _, idx = ivf_flat.search(index2, queries, k=10,
                                 params=ivf_flat.SearchParams(n_probes=16))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) > 0.9

    def test_save_strips_slack(self, dataset, tmp_path, queries):
        p = ivf_flat.IndexParams(n_lists=32, seed=0, list_growth=2.0)
        index = ivf_flat.build(dataset[:5000], p)
        ivf_flat.save(index, tmp_path / "slack.raft")
        loaded = ivf_flat.load(tmp_path / "slack.raft")
        assert loaded.size == 5000
        assert loaded.data.shape[0] == 5000    # dense file, no slack
        d1, i1 = ivf_flat.search(index, queries, 5,
                                 ivf_flat.SearchParams(n_probes=32))
        d2, i2 = ivf_flat.search(loaded, queries, 5,
                                 ivf_flat.SearchParams(n_probes=32))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    @pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
    def test_low_precision_storage(self, dataset, queries, dtype):
        index = ivf_flat.build(dataset, ivf_flat.IndexParams(
            n_lists=64, seed=0, dtype=dtype))
        assert str(index.data.dtype) == dtype
        if dtype == "int8":
            assert index.scales is not None
        # full-probe search ≈ exact (quantization-limited)
        _, idx = ivf_flat.search(index, queries, k=10,
                                 params=ivf_flat.SearchParams(n_probes=64))
        _, want = naive_knn(dataset, queries, 10)
        r = calc_recall(np.asarray(idx), want)
        assert r > (0.95 if dtype == "bfloat16" else 0.9), r

    @pytest.mark.parametrize("dtype,rtol", [("float32", 0.0),
                                            ("bfloat16", 1e-2),
                                            ("int8", 2e-2)])
    def test_reconstruct(self, dataset, dtype, rtol):
        index = ivf_flat.build(dataset, ivf_flat.IndexParams(
            n_lists=64, seed=0, dtype=dtype))
        ids = np.asarray(index.source_ids)
        rows = np.flatnonzero(ids >= 0)[::97][:64]  # valid physical rows
        got = np.asarray(ivf_flat.reconstruct(index, rows))
        want = dataset[ids[rows]]
        if dtype == "float32":
            np.testing.assert_array_equal(got, want)
        else:
            err = np.abs(got - want).max(axis=1)
            scale = np.abs(want).max(axis=1)
            assert (err <= rtol * scale + 1e-6).all(), err.max()

    def test_uint8_byte_corpus(self):
        rng = np.random.default_rng(11)
        data = rng.integers(0, 256, (8000, 32)).astype(np.float32)
        q = rng.integers(0, 256, (50, 32)).astype(np.float32)
        u8 = ivf_flat.build(data, ivf_flat.IndexParams(
            n_lists=32, seed=0, dtype="uint8"))
        assert str(u8.data.dtype) == "uint8" and u8.scales is None
        # full probe: lossless storage → exact vs brute-force oracle
        _, idx = ivf_flat.search(u8, q, k=10,
                                 params=ivf_flat.SearchParams(n_probes=32))
        _, want = naive_knn(data, q, 10)
        assert calc_recall(np.asarray(idx), want) > 0.9999
        # reconstruct round-trips bytes exactly
        ids = np.asarray(u8.source_ids)
        rows = np.flatnonzero(ids >= 0)[:16]
        np.testing.assert_array_equal(
            np.asarray(ivf_flat.reconstruct(u8, rows)), data[ids[rows]])

    def test_uint8_save_load(self, tmp_path):
        rng = np.random.default_rng(12)
        data = rng.integers(0, 256, (2000, 16)).astype(np.float32)
        q = rng.integers(0, 256, (20, 16)).astype(np.float32)
        u8 = ivf_flat.build(data, ivf_flat.IndexParams(
            n_lists=8, seed=0, dtype="uint8"))
        ivf_flat.save(u8, tmp_path / "u8.raft")
        loaded = ivf_flat.load(tmp_path / "u8.raft")
        assert str(loaded.data.dtype) == "uint8"
        sp = ivf_flat.SearchParams(n_probes=8)
        _, i1 = ivf_flat.search(u8, q, 5, sp)
        _, i2 = ivf_flat.search(loaded, q, 5, sp)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_reconstruct_rejects_bad_rows(self, built_index):
        from raft_tpu.core.errors import RaftError
        cap = built_index.data.shape[0]
        with pytest.raises(RaftError):
            ivf_flat.reconstruct(built_index, [cap + 5])
        slack = np.flatnonzero(np.asarray(built_index.source_ids) < 0)
        if slack.size:
            with pytest.raises(RaftError):
                ivf_flat.reconstruct(built_index, [int(slack[0])])

    def test_bf16_pallas_scan_matches_xla(self, dataset, queries):
        index = ivf_flat.build(dataset, ivf_flat.IndexParams(
            n_lists=64, seed=0, dtype="bfloat16"))
        sp = ivf_flat.SearchParams(n_probes=16)
        dx, ix = ivf_flat.search(index, queries, 8, sp, algo="xla")
        dp, ip = ivf_flat.search(index, queries, 8, sp, algo="pallas")
        assert np.mean(np.asarray(ip) == np.asarray(ix)) > 0.97
        np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                                   rtol=5e-2, atol=5e-2)

    def test_low_precision_save_load(self, dataset, queries, tmp_path):
        for dtype in ("bfloat16", "int8"):
            index = ivf_flat.build(dataset[:5000], ivf_flat.IndexParams(
                n_lists=32, seed=0, dtype=dtype))
            ivf_flat.save(index, tmp_path / f"ivf_{dtype}.raft")
            loaded = ivf_flat.load(tmp_path / f"ivf_{dtype}.raft")
            assert str(loaded.data.dtype) == dtype
            sp = ivf_flat.SearchParams(n_probes=32)
            _, i1 = ivf_flat.search(index, queries, 5, sp, algo="xla")
            _, i2 = ivf_flat.search(loaded, queries, 5, sp, algo="xla")
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_build_empty_then_extend(self, dataset, queries):
        p = ivf_flat.IndexParams(n_lists=32, add_data_on_build=False, seed=0)
        index = ivf_flat.build(dataset, p)
        assert index.size == 0
        index = ivf_flat.extend(index, dataset)
        assert index.size == len(dataset)
        _, idx = ivf_flat.search(index, queries, k=5,
                                 params=ivf_flat.SearchParams(n_probes=16))
        _, want = naive_knn(dataset, queries, 5)
        assert calc_recall(np.asarray(idx), want) > 0.9

    def test_filter(self, built_index, dataset, queries):
        _, base = naive_knn(dataset, queries, 2)
        mask = np.ones(len(dataset), bool)
        mask[base[:, 0]] = False
        filt = Bitset.from_mask(jnp.asarray(mask))
        _, idx = ivf_flat.search(built_index, queries, k=10,
                                 params=ivf_flat.SearchParams(n_probes=64),
                                 filter=filt)
        got = np.asarray(idx)
        assert not np.isin(base[:, 0], got.ravel()).any() or all(
            base[i, 0] not in got[i] for i in range(len(got)))

    def test_save_load(self, tmp_path, built_index, queries, dataset):
        ivf_flat.save(built_index, tmp_path / "ivf.raft")
        loaded = ivf_flat.load(tmp_path / "ivf.raft")
        d1, i1 = ivf_flat.search(built_index, queries, k=5,
                                 params=ivf_flat.SearchParams(16))
        d2, i2 = ivf_flat.search(loaded, queries, k=5,
                                 params=ivf_flat.SearchParams(16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_query_chunking_matches(self, built_index, queries):
        d1, i1 = ivf_flat.search(built_index, queries, k=5,
                                 params=ivf_flat.SearchParams(16), query_chunk=7)
        d2, i2 = ivf_flat.search(built_index, queries, k=5,
                                 params=ivf_flat.SearchParams(16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_index_as_jit_argument(self, built_index, queries):
        """The pytree carries the aligned-DMA pad cache byte-identical,
        so jitted functions can take the index as an ARGUMENT (not as
        index-sized baked closure constants)."""
        import jax

        ivf_flat.prepare_scan(built_index)
        leaves, td = jax.tree_util.tree_flatten(built_index)
        rebuilt = jax.tree_util.tree_unflatten(td, leaves)
        c0, c1 = built_index._scan_pad, rebuilt._scan_pad
        assert c1[0] == c0[0]
        for a, b in zip(c0[1:], c1[1:]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        fn = jax.jit(lambda q, idx: ivf_flat.search(
            idx, q, 5, ivf_flat.SearchParams(16)))
        d1, i1 = fn(queries, rebuilt)
        d2, i2 = ivf_flat.search(built_index, queries, k=5,
                                 params=ivf_flat.SearchParams(16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)

    def test_k_larger_than_candidates(self, dataset, queries):
        index = ivf_flat.build(dataset[:500], ivf_flat.IndexParams(n_lists=64, seed=0))
        d, i = ivf_flat.search(index, queries, k=64,
                               params=ivf_flat.SearchParams(n_probes=1))
        assert d.shape == (100, 64)
        # padded tail rows marked -1
        assert (np.asarray(i) == -1).any()


class TestStreamingBuild:
    def test_build_from_batches_matches_bulk_recall(self, dataset, queries):
        batches = [dataset[i : i + 4096] for i in range(0, len(dataset), 4096)]
        p = ivf_flat.IndexParams(n_lists=32, seed=0)
        idx = ivf_flat.build_from_batches(iter(batches), p)
        assert idx.size == len(dataset)
        ids = np.asarray(idx.source_ids)
        np.testing.assert_array_equal(np.sort(ids[ids >= 0]),
                                      np.arange(len(dataset)))
        _, i = ivf_flat.search(idx, queries, 10,
                               ivf_flat.SearchParams(n_probes=16))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(i), want) > 0.85

    def test_iter_fbin_roundtrip(self, dataset, tmp_path):
        from raft_tpu.bench.datasets import iter_fbin, write_fbin

        write_fbin(tmp_path / "x.fbin", dataset[:5000])
        got = np.concatenate(list(iter_fbin(tmp_path / "x.fbin",
                                            batch_rows=1111)))
        np.testing.assert_array_equal(got, dataset[:5000])
