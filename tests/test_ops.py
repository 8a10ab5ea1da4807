"""Oracle tests for the fused Pallas distance+top-k kernel (interpret mode
on CPU; the same code compiles for TPU — the `-m tpu` lane runs it there)."""
import numpy as np
import pytest

from raft_tpu.ops import fused_knn


def _oracle(q, x, metric):
    if metric == "l2":
        return ((q[:, None, :].astype(np.float64) - x[None, :, :]) ** 2).sum(-1)
    if metric == "cos":
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        xn = x / np.linalg.norm(x, axis=1, keepdims=True)
        return 1.0 - qn @ xn.T
    return -(q.astype(np.float64) @ x.T.astype(np.float64))


@pytest.mark.parametrize("m,n,d,k,metric", [
    (64, 1000, 32, 10, "l2"),
    (33, 300, 17, 5, "cos"),
    (16, 257, 96, 16, "ip"),
    (8, 2048, 128, 100, "l2"),   # k > tile lane width path
])
def test_fused_knn_oracle(m, n, d, k, metric):
    rng = np.random.default_rng(7)
    q = rng.standard_normal((m, d), dtype=np.float32)
    x = rng.standard_normal((n, d), dtype=np.float32)
    v, i = fused_knn(q, x, k, metric=metric, interpret=True)
    v, i = np.asarray(v), np.asarray(i)
    ref = _oracle(q, x, metric)
    ref_i = np.argsort(ref, axis=1)[:, :k]
    ref_v = np.take_along_axis(ref, ref_i, axis=1)
    np.testing.assert_allclose(v, ref_v, rtol=1e-4, atol=1e-4)
    recall = np.mean([len(set(i[r]) & set(ref_i[r])) / k for r in range(m)])
    assert recall == 1.0


def test_fused_knn_penalty_excludes_rows():
    rng = np.random.default_rng(3)
    q = rng.standard_normal((16, 32), dtype=np.float32)
    x = rng.standard_normal((500, 32), dtype=np.float32)
    pen = np.zeros(500, np.float32)
    pen[::2] = np.inf
    v, i = fused_knn(q, x, 8, penalty=pen, interpret=True)
    assert np.all(np.asarray(i) % 2 == 1)
    assert np.all(np.isfinite(np.asarray(v)))


def test_fused_knn_sparse_survivors_across_tiles():
    """<k unmasked rows spread over multiple tiles: unfilled slots must be
    -1/inf, never a duplicated real id (regression: the inf tie-scan used
    to re-emit column 0's retired id)."""
    rng = np.random.default_rng(9)
    q = rng.standard_normal((4, 64), dtype=np.float32)
    x = rng.standard_normal((2048, 64), dtype=np.float32)
    pen = np.full(2048, np.inf, np.float32)
    pen[[10, 1500]] = 0.0
    v, i = fused_knn(q, x, 3, penalty=pen, interpret=True)
    v, i = np.asarray(v), np.asarray(i)
    assert set(i[:, :2].ravel()) == {10, 1500}
    assert np.all(i[:, 2] == -1) and np.all(np.isinf(v[:, 2]))


def test_fused_knn_k_exceeds_valid_rows():
    """More requested neighbors than admissible rows → +inf / -1 padding."""
    rng = np.random.default_rng(4)
    q = rng.standard_normal((8, 16), dtype=np.float32)
    x = rng.standard_normal((40, 16), dtype=np.float32)
    pen = np.full(40, np.inf, np.float32)
    pen[:5] = 0.0
    v, i = fused_knn(q, x, 10, penalty=pen, interpret=True)
    v, i = np.asarray(v), np.asarray(i)
    assert np.all(np.isfinite(v[:, :5])) and np.all(np.isinf(v[:, 5:]))
    assert set(i[:, :5].ravel()) <= {0, 1, 2, 3, 4}
    assert np.all(i[:, 5:] == -1)


@pytest.mark.parametrize("metric", ["sqeuclidean", "euclidean", "cosine",
                                    "inner_product"])
def test_brute_force_pallas_matches_scan(metric):
    from raft_tpu.neighbors import brute_force

    rng = np.random.default_rng(11)
    x = rng.standard_normal((700, 48), dtype=np.float32)
    q = rng.standard_normal((50, 48), dtype=np.float32)
    index = brute_force.build(x, metric=metric)
    vs, is_ = brute_force.search(index, q, 10, algo="scan")
    vp, ip = brute_force.search(index, q, 10, algo="pallas")
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vs),
                               rtol=1e-4, atol=1e-4)
    agree = np.mean(np.asarray(ip) == np.asarray(is_))
    assert agree > 0.99  # ties may order differently


class TestIvfScanParity:
    """CPU interpret-mode parity for the query-grouped IVF scan kernels —
    the pallas paths must match the XLA gather paths bit-for-bit (flat)
    / to equal quality (PQ) without TPU hardware in the loop."""

    def test_ivf_flat_pallas_matches_xla(self):
        from raft_tpu.neighbors import ivf_flat

        rng = np.random.default_rng(21)
        data = rng.standard_normal((2000, 40), dtype=np.float32)
        q = rng.standard_normal((25, 40), dtype=np.float32)
        for metric in ["sqeuclidean", "cosine", "inner_product"]:
            index = ivf_flat.build(data, ivf_flat.IndexParams(
                n_lists=16, metric=metric, seed=0))
            dx, ix = ivf_flat.search(index, q, 8,
                                     ivf_flat.SearchParams(n_probes=16),
                                     algo="xla")
            dp, ip = ivf_flat.search(index, q, 8,
                                     ivf_flat.SearchParams(n_probes=16),
                                     algo="pallas")
            assert np.mean(np.asarray(ip) == np.asarray(ix)) > 0.99, metric
            np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                                       rtol=1e-3, atol=1e-3)

    def test_ivf_flat_pallas_byte_dtypes_match_xla(self):
        """int8 (per-row scales in-kernel) and uint8 (exact bytes) must
        track the XLA gather path through the pallas scan."""
        from raft_tpu.neighbors import ivf_flat

        rng = np.random.default_rng(23)
        data = rng.standard_normal((2000, 40)).astype(np.float32)
        q = rng.standard_normal((25, 40)).astype(np.float32)
        bdata = np.round(np.clip(data * 40 + 128, 0, 255)).astype(np.float32)
        bq = np.round(np.clip(q * 40 + 128, 0, 255)).astype(np.float32)
        for dtype, dd, qq, id_floor in (("int8", data, q, 0.9),
                                        ("uint8", bdata, bq, 0.999)):
            index = ivf_flat.build(dd, ivf_flat.IndexParams(
                n_lists=16, seed=0, dtype=dtype))
            dx, ix = ivf_flat.search(index, qq, 8,
                                     ivf_flat.SearchParams(n_probes=16),
                                     algo="xla")
            dp, ip = ivf_flat.search(index, qq, 8,
                                     ivf_flat.SearchParams(n_probes=16),
                                     algo="pallas")
            match = np.mean(np.asarray(ip) == np.asarray(ix))
            assert match > id_floor, (dtype, match)
            np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                                       rtol=5e-2, atol=5e-1)

    def test_ivf_pq_pallas_matches_xla(self):
        import jax.numpy as jnp

        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(22)
        data = rng.standard_normal((2000, 32), dtype=np.float32)
        q = rng.standard_normal((25, 32), dtype=np.float32)
        index = ivf_pq.build(data, ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, seed=0))
        # f32 LUT: both engines compute the same quantities exactly, so id
        # agreement is near-total (bf16 LUTs round differently per engine)
        sp = ivf_pq.SearchParams(n_probes=16, lut_dtype=jnp.float32)
        dx, ix = ivf_pq.search(index, q, 8, sp, algo="xla")
        dp, ip = ivf_pq.search(index, q, 8, sp, algo="pallas")
        assert np.mean(np.asarray(ip) == np.asarray(ix)) > 0.95
        # bf16 default: quality must match within tolerance
        spb = ivf_pq.SearchParams(n_probes=16)
        db, ib = ivf_pq.search(index, q, 8, spb, algo="pallas")
        overlap = np.mean([len(set(ib[r].tolist()) & set(ix[r].tolist())) / 8
                           for r in range(len(q))])
        assert overlap > 0.85

    def test_ivf_flat_pallas_filter_matches_xla(self):
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_flat

        rng = np.random.default_rng(31)
        data = rng.standard_normal((1500, 24), dtype=np.float32)
        q = rng.standard_normal((20, 24), dtype=np.float32)
        keep = rng.random(1500) > 0.4
        filt = Bitset.from_mask(keep)
        index = ivf_flat.build(data, ivf_flat.IndexParams(n_lists=12, seed=0))
        sp = ivf_flat.SearchParams(n_probes=12)
        dx, ix = ivf_flat.search(index, q, 8, sp, algo="xla", filter=filt)
        dp, ip = ivf_flat.search(index, q, 8, sp, algo="pallas", filter=filt)
        ip_np = np.asarray(ip)
        assert keep[ip_np[ip_np >= 0]].all()
        assert np.mean(ip_np == np.asarray(ix)) > 0.99
        np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                                   rtol=1e-3, atol=1e-3)

    def test_ivf_pq_pallas_filter_excludes(self):
        import jax.numpy as jnp

        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(32)
        data = rng.standard_normal((1500, 32), dtype=np.float32)
        q = rng.standard_normal((15, 32), dtype=np.float32)
        keep = rng.random(1500) > 0.5
        filt = Bitset.from_mask(keep)
        index = ivf_pq.build(data, ivf_pq.IndexParams(n_lists=12, pq_dim=8,
                                                      seed=0))
        sp = ivf_pq.SearchParams(n_probes=12, lut_dtype=jnp.float32)
        dx, ix = ivf_pq.search(index, q, 8, sp, algo="xla", filter=filt)
        dp, ip = ivf_pq.search(index, q, 8, sp, algo="pallas", filter=filt)
        ip_np = np.asarray(ip)
        assert keep[ip_np[ip_np >= 0]].all()
        assert np.mean(ip_np == np.asarray(ix)) > 0.95

    def test_ivf_flat_pallas_small_k_and_tail_lists(self):
        """k larger than some list sizes + uneven lists: sentinel handling."""
        from raft_tpu.neighbors import ivf_flat

        rng = np.random.default_rng(23)
        data = rng.standard_normal((300, 16), dtype=np.float32)
        q = rng.standard_normal((10, 16), dtype=np.float32)
        index = ivf_flat.build(data, ivf_flat.IndexParams(n_lists=12,
                                                          seed=0))
        d1, i1 = ivf_flat.search(index, q, 5,
                                 ivf_flat.SearchParams(n_probes=1),
                                 algo="pallas")
        i1 = np.asarray(i1)
        assert ((i1 >= -1) & (i1 < 300)).all()


def test_brute_force_pallas_filter():
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import brute_force

    rng = np.random.default_rng(12)
    x = rng.standard_normal((300, 32), dtype=np.float32)
    q = rng.standard_normal((20, 32), dtype=np.float32)
    keep = rng.random(300) > 0.5
    bs = Bitset.from_mask(keep)
    index = brute_force.build(x)
    vs, is_ = brute_force.search(index, q, 5, filter=bs, algo="scan")
    vp, ip = brute_force.search(index, q, 5, filter=bs, algo="pallas")
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vs),
                               rtol=1e-4, atol=1e-4)
    assert keep[np.asarray(ip)].all()
