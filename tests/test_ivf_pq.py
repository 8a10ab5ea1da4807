"""IVF-PQ + refine tests (analog of NEIGHBORS_ANN_IVF_TEST pq cases +
cpp/test/neighbors/refine.cu): recall vs brute-force oracle, never exact
equality (SURVEY.md §4)."""
import jax.numpy as jnp
import numpy as np
import pytest

from ann_utils import calc_recall, naive_knn
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import ivf_pq, refine


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
    return ivf_pq.build(dataset, ivf_pq.IndexParams(
        n_lists=64, pq_dim=8, pq_bits=8, seed=0))


class TestIvfPq:
    def test_structure(self, built_index, dataset):
        assert built_index.size == len(dataset)
        assert built_index.n_lists == 64
        assert built_index.pq_dim == 8
        assert built_index.pq_len == 4
        assert built_index.rot_dim == 32
        assert built_index.list_sizes.sum() == len(dataset)
        ids = np.asarray(built_index.source_ids)
        np.testing.assert_array_equal(np.sort(ids[ids >= 0]),
                                      np.arange(len(dataset)))
        # rotation has orthonormal columns
        r = np.asarray(built_index.rotation)
        np.testing.assert_allclose(r.T @ r, np.eye(32), atol=1e-5)

    # thresholds calibrated on unstructured gaussian data — the PQ worst
    # case: the full-scan ADC oracle (exact search over reconstructions)
    # itself only reaches 0.552 recall@10 here, and n_probes=64 matches it
    # exactly; real datasets cluster far better.
    @pytest.mark.parametrize("n_probes,min_recall", [(16, 0.45), (64, 0.52)])
    def test_recall(self, built_index, dataset, queries, n_probes, min_recall):
        _, idx = ivf_pq.search(built_index, queries, k=10,
                               params=ivf_pq.SearchParams(n_probes))
        _, want = naive_knn(dataset, queries, 10)
        r = calc_recall(np.asarray(idx), want)
        assert r >= min_recall, f"recall {r} < {min_recall} at n_probes={n_probes}"

    def test_refine_lifts_recall(self, built_index, dataset, queries):
        _, cand = ivf_pq.search(built_index, queries, k=100,
                                params=ivf_pq.SearchParams(64))
        dist, idx = refine.refine(dataset, queries, cand, k=10)
        _, want = naive_knn(dataset, queries, 10)
        raw = calc_recall(np.asarray(cand[:, :10]), want)
        refined = calc_recall(np.asarray(idx), want)
        assert refined > raw
        assert refined >= 0.9
        # refined distances are exact L2^2
        d = np.asarray(dist)
        i = np.asarray(idx)
        for row in range(0, 100, 23):
            true = ((queries[row] - dataset[i[row, 0]]) ** 2).sum()
            assert abs(d[row, 0] - true) < 1e-1

    @pytest.mark.slow  # 23s single-core: variant-recall check; the
    # PER_SUBSPACE path keeps tier-1 coverage of the shared machinery
    def test_per_cluster_codebooks(self, dataset, queries):
        index = ivf_pq.build(dataset, ivf_pq.IndexParams(
            n_lists=32, pq_dim=8, codebook_kind=ivf_pq.CodebookGen.PER_CLUSTER,
            seed=0))
        assert index.codebooks.shape[0] == 32
        _, idx = ivf_pq.search(index, queries, k=10,
                               params=ivf_pq.SearchParams(32))
        _, want = naive_knn(dataset, queries, 10)
        # full-probe search matches the per-cluster ADC oracle (0.541) exactly
        assert calc_recall(np.asarray(idx), want) >= 0.5

    def test_inner_product(self, dataset, queries):
        index = ivf_pq.build(dataset, ivf_pq.IndexParams(
            n_lists=32, pq_dim=8, metric="inner_product", seed=0))
        dist, idx = ivf_pq.search(index, queries, k=10,
                                  params=ivf_pq.SearchParams(16))
        want_d, want = naive_knn(dataset, queries, 10, "inner_product")
        assert calc_recall(np.asarray(idx), want) >= 0.5
        # reported distances are (approximate) true inner products, descending
        d = np.asarray(dist)
        assert (np.diff(d, axis=1) <= 1e-3).all()

    def test_pq_bits_4(self, dataset, queries):
        index = ivf_pq.build(dataset, ivf_pq.IndexParams(
            n_lists=32, pq_dim=16, pq_bits=4, seed=0))
        assert index.pq_book_size == 16
        assert int(np.asarray(index.codes).max()) < 16
        _, idx = ivf_pq.search(index, queries, k=10,
                               params=ivf_pq.SearchParams(32))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.4

    @pytest.mark.slow  # 20s single-core for a relative recall-delta
    # check between two lut_dtype rungs of the same scan (cf. the
    # tier-1 budget note on test_int8_lut_pq_bits_4 below)
    def test_int8_lut_mode(self, dataset, queries):
        """fp8-LUT role (ivf_pq_types.hpp:110-146): the int8-quantized
        codebook scan must track the bf16 scan's recall closely."""
        index = ivf_pq.build(dataset, ivf_pq.IndexParams(
            n_lists=32, pq_dim=16, seed=0))
        _, want = naive_knn(dataset, queries, 10)
        _, idx_bf = ivf_pq.search(index, queries, k=10, algo="pallas",
                                  params=ivf_pq.SearchParams(16))
        _, idx_i8 = ivf_pq.search(
            index, queries, k=10, algo="pallas",
            params=ivf_pq.SearchParams(16, lut_dtype="int8"))
        r_bf = calc_recall(np.asarray(idx_bf), want)
        r_i8 = calc_recall(np.asarray(idx_i8), want)
        assert r_i8 >= r_bf - 0.03, (r_i8, r_bf)

    def test_int8_lut_pq_bits_4(self, dataset, queries):
        """int8 LUT composes with the 16-entry (pq_bits=4) codebooks."""
        index = ivf_pq.build(dataset, ivf_pq.IndexParams(
            n_lists=32, pq_dim=32, pq_bits=4, seed=0))
        _, idx = ivf_pq.search(
            index, queries, k=10, algo="pallas",
            params=ivf_pq.SearchParams(32, lut_dtype="int8"))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.5

    def test_non_divisible_dim_pads(self, queries):
        rng = np.random.default_rng(3)
        data = rng.standard_normal((5000, 30)).astype(np.float32)
        index = ivf_pq.build(data, ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, seed=0))
        assert index.rot_dim == 32 and index.dim == 30
        _, idx = ivf_pq.search(index, queries[:, :30], k=10,
                               params=ivf_pq.SearchParams(16))
        _, want = naive_knn(data, queries[:, :30], 10)
        assert calc_recall(np.asarray(idx), want) >= 0.5

    def test_reconstruct(self, built_index, dataset):
        rows = np.arange(0, 200)
        approx = np.asarray(ivf_pq.reconstruct(built_index, rows))
        orig = dataset[np.asarray(built_index.source_ids)[rows]]
        rel = np.linalg.norm(approx - orig) / np.linalg.norm(orig)
        assert rel < 0.5  # lossy but meaningful

    def test_extend(self, dataset, queries):
        p = ivf_pq.IndexParams(n_lists=32, pq_dim=8, seed=0)
        index = ivf_pq.build(dataset[:10_000], p)
        index = ivf_pq.extend(index, dataset[10_000:],
                              np.arange(10_000, 20_000, dtype=np.int32))
        assert index.size == 20_000
        _, idx = ivf_pq.search(index, queries, k=10,
                               params=ivf_pq.SearchParams(32))
        _, want = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.45

    def test_extend_in_place_with_growth_slack(self, dataset, queries):
        p = ivf_pq.IndexParams(n_lists=32, pq_dim=8, seed=0,
                               list_growth=2.0)
        index = ivf_pq.build(dataset[:10_000], p)
        off0 = index.list_offsets.copy()
        index2 = ivf_pq.extend(index, dataset[10_000:13_000],
                               np.arange(10_000, 13_000, dtype=np.int32))
        # fits in slack: same offsets, O(batch) in-place scatter
        np.testing.assert_array_equal(index2.list_offsets, off0)
        assert index2.size == 13_000
        _, idx = ivf_pq.search(index2, queries, k=10,
                               params=ivf_pq.SearchParams(32))
        _, want = naive_knn(dataset[:13_000], queries, 10)
        assert calc_recall(np.asarray(idx), want) >= 0.45

    def test_filter(self, built_index, dataset, queries):
        _, base = naive_knn(dataset, queries, 1)
        mask = np.ones(len(dataset), bool)
        mask[base[:, 0]] = False
        filt = Bitset.from_mask(jnp.asarray(mask))
        _, idx = ivf_pq.search(built_index, queries, k=10,
                               params=ivf_pq.SearchParams(64), filter=filt)
        got = np.asarray(idx)
        assert all(base[i, 0] not in got[i] for i in range(len(got)))

    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(0)
        for bits in (4, 5, 8):
            codes = rng.integers(0, 1 << bits, (100, 12)).astype(np.uint8)
            packed = ivf_pq.pack_codes(codes, bits)
            assert packed.shape[1] < 12 or bits == 8
            np.testing.assert_array_equal(
                ivf_pq.unpack_codes(packed, 12, bits), codes)

    def test_save_load(self, tmp_path, built_index, queries):
        ivf_pq.save(built_index, tmp_path / "pq.raft")
        loaded = ivf_pq.load(tmp_path / "pq.raft")
        d1, i1 = ivf_pq.search(built_index, queries, k=5,
                               params=ivf_pq.SearchParams(16))
        d2, i2 = ivf_pq.search(loaded, queries, k=5,
                               params=ivf_pq.SearchParams(16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_query_chunking_matches(self, built_index, queries):
        d1, i1 = ivf_pq.search(built_index, queries, k=5,
                               params=ivf_pq.SearchParams(16), query_chunk=7)
        d2, i2 = ivf_pq.search(built_index, queries, k=5,
                               params=ivf_pq.SearchParams(16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_index_as_jit_argument(self, built_index, queries,
                                   monkeypatch):
        """The Index pytree carries its scan-prep cache, so a jitted
        function may take the index as an ARGUMENT (arrays become
        program parameters, not index-sized closure-baked HLO
        constants) and
        must match the eager path WITHOUT re-deriving the cache (the
        in-trace _scan_prep fallback would silently mask a broken
        flatten/unflatten round-trip, so it is forbidden here)."""
        import jax

        ivf_pq.prepare_scan(built_index)
        leaves, td = jax.tree_util.tree_flatten(built_index)
        rebuilt = jax.tree_util.tree_unflatten(td, leaves)
        cache0, cache1 = built_index._scan_cache, rebuilt._scan_cache
        assert cache1 is not None
        # the cache must survive BYTE-IDENTICAL: off-TPU the search path
        # below doesn't consume it (pallas is TPU-only), so leaf mixups
        # must be caught here, not by the recall check
        assert cache1["n"] == cache0["n"] and cache1["lmax"] == cache0["lmax"]
        for key in ("codes_p", "norms_p", "cbm"):
            np.testing.assert_array_equal(np.asarray(cache0[key]),
                                          np.asarray(cache1[key]))

        def no_prep(*a, **k):  # noqa: ARG001
            raise AssertionError(
                "scan cache was re-derived under the trace: the pytree "
                "dropped it")

        monkeypatch.setattr(ivf_pq, "_scan_prep", no_prep)
        fn = jax.jit(lambda q, idx: ivf_pq.search(
            idx, q, 5, ivf_pq.SearchParams(16)))
        d1, i1 = fn(queries, rebuilt)
        d2, i2 = ivf_pq.search(built_index, queries, k=5,
                               params=ivf_pq.SearchParams(16))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)


class TestRefine:
    def test_refine_exact_when_candidates_cover(self, dataset, queries):
        # candidates = true top-30 → refine top-10 must equal naive top-10
        _, cand = naive_knn(dataset, queries, 30)
        dist, idx = refine.refine(dataset, queries, cand, k=10)
        want_d, want_i = naive_knn(dataset, queries, 10)
        np.testing.assert_allclose(np.asarray(dist), want_d, rtol=1e-2, atol=1e-2)
        assert calc_recall(np.asarray(idx), want_i) == 1.0

    def test_refine_handles_negative_ids(self, dataset, queries):
        _, cand = naive_knn(dataset, queries, 20)
        cand = np.asarray(cand)
        cand[:, 15:] = -1
        dist, idx = refine.refine(dataset, queries, cand, k=18)
        assert (np.asarray(idx)[:, -1] == -1).all()
        assert np.isinf(np.asarray(dist)[:, -1]).all()

    def test_refine_bf16_dataset(self, dataset, queries):
        """A bf16 corpus copy (half the gather traffic) must re-rank to
        near-identical top-k."""
        import jax.numpy as jnp

        _, cand = naive_knn(dataset, queries, 30)
        _, idx = refine.refine(jnp.asarray(dataset, jnp.bfloat16),
                               queries, cand, k=10)
        _, want_i = naive_knn(dataset, queries, 10)
        assert calc_recall(np.asarray(idx), want_i) >= 0.98

    def test_refine_uint8_dataset(self):
        """Byte corpora re-rank exactly through the uint8 gather path:
        quarter-traffic gather, widened to f32 AFTER the gather so the
        exact f32 contraction still runs."""
        import jax.numpy as jnp

        rng = np.random.default_rng(9)
        bdata = rng.integers(0, 256, size=(3000, 32)).astype(np.float32)
        bq = rng.integers(0, 256, size=(30, 32)).astype(np.float32)
        _, cand = naive_knn(bdata, bq, 30)
        _, idx = refine.refine(jnp.asarray(bdata, jnp.uint8), bq, cand,
                               k=10)
        _, want_i = naive_knn(bdata, bq, 10)
        assert calc_recall(np.asarray(idx), want_i) >= 0.98

    def test_refine_inner_product(self, dataset, queries):
        _, cand = naive_knn(dataset, queries, 30, "inner_product")
        dist, idx = refine.refine(dataset, queries, cand, k=10,
                                  metric="inner_product")
        want_d, want_i = naive_knn(dataset, queries, 10, "inner_product")
        assert calc_recall(np.asarray(idx), want_i) == 1.0
        np.testing.assert_allclose(np.asarray(dist), want_d, rtol=1e-2, atol=1e-2)


def test_pq_build_from_batches(dataset, queries):
    batches = [dataset[i : i + 4096] for i in range(0, len(dataset), 4096)]
    p = ivf_pq.IndexParams(n_lists=32, pq_dim=8, seed=0)
    idx = ivf_pq.build_from_batches(iter(batches), p)
    assert idx.size == len(dataset)
    _, i = ivf_pq.search(idx, queries, 10, ivf_pq.SearchParams(32))
    _, want = naive_knn(dataset, queries, 10)
    assert calc_recall(np.asarray(i), want) >= 0.45


def _mode_codebooks(cb, lut_mode):
    """The codebooks as ``lut_mode`` rounds them, in float64: bf16 casts
    them; int8 quantizes each subspace to symmetric int8 with its own
    scale, in float32 as ``ivf_pq_scan`` does."""
    if lut_mode == "bf16":
        return np.asarray(jnp.asarray(cb).astype(jnp.bfloat16), np.float64)
    if lut_mode == "int8":
        scale = np.maximum(np.abs(cb).max(axis=(1, 2)),
                           np.float32(1e-12)) / np.float32(127.0)
        q8 = np.clip(np.round(cb / scale[:, None, None]), -127, 127)
        return q8 * scale[:, None, None].astype(np.float64)
    return cb.astype(np.float64)


@pytest.mark.parametrize("pq_bits", [4, 8])
@pytest.mark.parametrize("lut_mode", ["f32", "bf16", "int8"])
def test_pq_scan_decode_exact(lut_mode, pq_bits):
    """The Pallas PQ scan's approximate distances are ||q - (c_l + dec)||²
    of the codebook as the LUT mode rounds it, and its ids are the top-k
    over the probed lists. The lists differ in size and start off the
    8-row DMA alignment; at pq_bits=8 the one-hot spans several row chunks
    (a ragged last one in bf16), so a slip in the code transpose or a
    subspace offset moves distances far past the tolerance."""
    from raft_tpu.ops.ivf_pq_scan import ivf_pq_scan, make_cb_matrix

    rng = np.random.default_rng(11 + pq_bits)
    pq_dim, pq_len, book, k = 32, 4, 1 << pq_bits, 10
    rot_dim = pq_dim * pq_len
    sizes = np.array([300, 37, 5, 261, 129, 83])
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    n, n_lists, m = int(sizes.sum()), len(sizes), 12
    codes = rng.integers(0, book, (n, pq_dim), dtype=np.uint8)
    cb = rng.standard_normal((pq_dim, book, pq_len)).astype(np.float32)
    centers = rng.standard_normal((n_lists, rot_dim)).astype(np.float32)
    q = rng.standard_normal((m, rot_dim)).astype(np.float32)
    probed = np.stack([rng.choice(n_lists, 3, replace=False)
                       for _ in range(m)]).astype(np.int32)

    cbm = _mode_codebooks(cb, lut_mode)
    dec = cbm[np.arange(pq_dim), codes].reshape(n, rot_dim)
    x = centers[np.repeat(np.arange(n_lists), sizes)] + dec
    ref = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    norms = (x * x).sum(1).astype(np.float32)

    vals, rows = ivf_pq_scan(
        jnp.asarray(codes), jnp.asarray(norms), jnp.asarray(centers),
        make_cb_matrix(jnp.asarray(cb)), jnp.asarray(probed),
        jnp.asarray(offsets, jnp.int32), jnp.asarray(sizes, jnp.int32),
        jnp.asarray(q), k, int(sizes.max()), pq_dim, book, lut_mode=lut_mode)
    vals, rows = np.asarray(vals), np.asarray(rows)

    for i in range(m):
        live = np.concatenate([np.arange(offsets[l], offsets[l] + sizes[l])
                               for l in probed[i]])
        want = live[np.argsort(ref[i, live], kind="stable")]
        assert len(set(rows[i])) == k and set(rows[i]) <= set(live)
        np.testing.assert_allclose(vals[i], ref[i, rows[i]], rtol=1e-4)
        kth, nxt = ref[i, want[k - 1]], ref[i, want[k]]
        if nxt - kth > 1e-4 * kth:
            assert set(rows[i]) == set(want[:k])
        else:                              # a tie at the k-th place
            assert (ref[i, rows[i]] <= kth * (1 + 1e-4)).all()
