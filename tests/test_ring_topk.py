"""Ring-top-k sharded merge tests (the `multichip` CPU emulation lane).

The acceptance bar (ISSUE 8): the ring merge is BIT-identical — order
included — to ``knn_merge_parts`` on the emulated 8-device mesh, with
exact-tie candidates, with dead shards under ``allow_partial=True``, and
under ``guarded_call`` fault injection (which must serve the allgather
path with identical results and record no demotion). The Pallas VMEM
fold is pinned against the XLA fold in interpret mode; the full remote-
DMA ring kernel compiles only on a real TPU (`tpu` lane test).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.ops import ring_topk
from raft_tpu.parallel import sharded_ann, sharded_knn
from raft_tpu.utils import shard_map_compat

pytestmark = pytest.mark.multichip


@pytest.fixture(autouse=True)
def _no_disk_autotune(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_AUTOTUNE_CACHE", "")


def _sharded_parts(mesh, d, gid):
    spec = NamedSharding(mesh, P("shard", None, None))
    return (jax.device_put(jnp.asarray(d), spec),
            jax.device_put(jnp.asarray(gid), spec))


def _merge_on_mesh(mesh, dd, gg, k, select_min, engine):
    p = mesh.shape["shard"]

    def body(ds, gs):
        return ring_topk.merge(ds[0], gs[0], k, select_min, axis="shard",
                               axis_size=p, engine=engine)

    f = shard_map_compat(body, mesh=mesh,
                         in_specs=(P("shard", None, None),) * 2,
                         out_specs=(P(), P()), check=False)
    return f(dd, gg)


@pytest.fixture(scope="module")
def parts():
    """(p=8, m, k) candidate blocks with cross-shard exact ties and one
    dead shard's (+inf, -1) sentinel block."""
    rng = np.random.default_rng(0)
    p, m, k = 8, 16, 7
    d = np.sort(rng.standard_normal((p, m, k)).astype(np.float32), axis=-1)
    d[3] = d[1]                      # bit-exact ties across shards
    gid = rng.integers(0, 100_000, size=(p, m, k)).astype(np.int32)
    d[5], gid[5] = np.inf, -1        # dead shard sentinels
    return d, gid


class TestMergeBitIdentity:
    @pytest.mark.parametrize("select_min", [True, False])
    def test_ring_matches_knn_merge_parts(self, multichip_mesh, parts,
                                          select_min):
        d, gid = parts
        d = d if select_min else -d
        k = d.shape[-1]
        ref = brute_force.knn_merge_parts(jnp.asarray(d), jnp.asarray(gid),
                                          select_min)
        dd, gg = _sharded_parts(multichip_mesh, d, gid)
        od, og = _merge_on_mesh(multichip_mesh, dd, gg, k, select_min,
                                "ring")
        np.testing.assert_array_equal(np.asarray(od), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(og), np.asarray(ref[1]))

    def test_allgather_engine_matches_reference(self, multichip_mesh, parts):
        # the fallback engine must BE the reference path
        d, gid = parts
        k = d.shape[-1]
        ref = brute_force.knn_merge_parts(jnp.asarray(d), jnp.asarray(gid),
                                          True)
        dd, gg = _sharded_parts(multichip_mesh, d, gid)
        od, og = _merge_on_mesh(multichip_mesh, dd, gg, k, True, "allgather")
        np.testing.assert_array_equal(np.asarray(od), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(og), np.asarray(ref[1]))


class TestVmemFoldKernel:
    @pytest.mark.parametrize("select_min", [True, False])
    def test_interpret_matches_xla_fold(self, select_min):
        """The merge step the TPU ring kernel runs per hop, through the
        real Pallas kernel in interpret mode, vs the lax.sort fold —
        ties (equal value, position decides) included."""
        rng = np.random.default_rng(1)
        m, w, k = 6, 9, 6
        rd = np.sort(rng.standard_normal((m, w)).astype(np.float32), -1)
        bd = np.sort(rng.standard_normal((m, w)).astype(np.float32), -1)
        bd[2] = rd[2]               # tie rows: position must decide
        rp = np.tile(np.arange(w, dtype=np.int32), (m, 1))
        bp = rp + 7 * w
        rg = rng.integers(0, 999, (m, w)).astype(np.int32)
        bg = rng.integers(0, 999, (m, w)).astype(np.int32)
        if not select_min:
            rd, bd = -rd, -bd
        args = tuple(map(jnp.asarray, (rd, rp, rg, bd, bp, bg)))
        want = ring_topk.merge_step(*args, k, select_min=select_min,
                                    engine="xla")
        got = ring_topk.merge_step(*args, k, select_min=select_min,
                                   engine="pallas", interpret=True)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def mesh4():
    """4-device mesh for the family-level flow: the ring program unrolls
    p−1 hops, so family compile cost halves at p=4 while the 8-device
    bit-identity acceptance stays with TestMergeBitIdentity above."""
    from jax.sharding import Mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    return Mesh(np.array(jax.devices()[:4]), ("shard",))


@pytest.fixture(scope="module")
def flat4(mesh4):
    rng = np.random.default_rng(2)
    data = rng.standard_normal((1_200, 16)).astype(np.float32)
    q = rng.standard_normal((12, 16)).astype(np.float32)
    index = sharded_ann.build_ivf_flat(
        data, mesh4, ivf_flat.IndexParams(n_lists=4, seed=0))
    return index, data, q


class TestShardedFamilies:
    """Every eager sharded search recompiles its shard_map program (~5 s
    on the CPU mesh), so the family-level acceptance flow — healthy
    bit-identity, ≥1 dead shard under allow_partial, fault-injected
    demotion to allgather, make_searcher/debugz pick-up — runs as ONE
    consolidated test with the minimum number of search dispatches."""

    @pytest.mark.slow  # ~30s single-core (5 eager shard_map compiles);
    # tier-1 keeps the per-family sharded coverage in test_sharded_ann
    # and the breaker arc drills in test_faults
    def test_ring_acceptance_flow(self, flat4):
        from raft_tpu.core import faults
        from raft_tpu.ops import guarded
        from raft_tpu.serve import debugz, metrics

        index, _, q = flat4
        sp = ivf_flat.SearchParams(n_probes=4)
        # 1-2) healthy: ring bit-identical to the allgather reference
        #      (2-tuple legacy API preserved)
        da, ia = sharded_ann.search_ivf_flat(index, q, 5, params=sp)
        dr, ir = sharded_ann.search_ivf_flat(index, q, 5, params=sp,
                                             merge_engine="ring")
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(da), np.asarray(dr))
        assert sharded_ann._ACTIVE_ENGINE["ivf_flat"] == "ring"

        # ops surface: the engine tag and shard health are live
        snap = debugz.snapshot()
        fams = snap["sharded"]["families"]
        assert fams["ivf_flat"]["merge_engine"] == "ring"
        assert all(all(ok) for ok in fams["ivf_flat"]["shards_ok"])
        assert isinstance(snap["sharded"]["ring_demotions"], int)
        assert "engine=ring" in debugz.render_text()

        # 3) dead shard under allow_partial through the RING engine: the
        #    loss reported, full answer from survivors, no dead-shard row
        #    surfaces (ring-vs-allgather identity for sentinel blocks is
        #    pinned against knn_merge_parts in TestMergeBitIdentity)
        index.mark_shard_failed(3)
        try:
            dpr, ipr, okr = sharded_ann.search_ivf_flat(
                index, q, 5, params=sp, allow_partial=True,
                merge_engine="ring")
        finally:
            index.mark_shard_failed(3, ok=True)
        assert list(okr) == [True, True, True, False]
        got = np.asarray(ipr)       # shard 3 = rows [900, 1200)
        assert not ((got >= 900) & (got < 1200)).any()
        assert (got >= 0).all() and np.isfinite(np.asarray(dpr)).all()
        hs = debugz.snapshot()["sharded"]["families"]["ivf_flat"]
        assert all(all(ok) for ok in hs["shards_ok"])  # re-marked healthy

        # 4) fault injection: the guarded site serves the allgather path
        #    with identical results, demotion NOT sticky, counter ticks
        before = metrics.counter("sharded.ring.demotions").value
        with faults.inject("kernel_compile", "sharded.ring_topk"):
            df, if_ = sharded_ann.search_ivf_flat(
                index, q, 5, params=sp, merge_engine="ring")
        np.testing.assert_array_equal(np.asarray(if_), np.asarray(ia))
        np.testing.assert_array_equal(np.asarray(df), np.asarray(da))
        assert "sharded.ring_topk" not in guarded.demoted_sites()
        assert metrics.counter("sharded.ring.demotions").value == before + 1
        assert sharded_ann._ACTIVE_ENGINE["ivf_flat"] == "allgather"

        # 5) healthy allow_partial (ring, post-fault: the path is live
        #    again): all-ok reported, ids identical to the reference
        d3, i3, ok3 = sharded_ann.search_ivf_flat(
            index, q, 5, params=sp, allow_partial=True,
            merge_engine="ring")
        assert ok3.all()
        np.testing.assert_array_equal(np.asarray(i3), np.asarray(ia))
        assert sharded_ann._ACTIVE_ENGINE["ivf_flat"] == "ring"

        # the serving closure threads merge_engine through to resolution
        # (raises in resolve_engine, before any compile)
        fn = sharded_ann.make_searcher(index, sp, merge_engine="bogus")
        with pytest.raises(Exception, match="merge engine"):
            fn(q, 5)

    def test_sharded_knn_ring_bit_identical(self, mesh4):
        rng = np.random.default_rng(3)
        data = rng.standard_normal((1_600 - 9, 16)).astype(np.float32)
        q = rng.standard_normal((8, 16)).astype(np.float32)
        index = sharded_knn.build(data, mesh4)
        d1, i1 = sharded_knn.search(index, q, 5, algo="scan")
        d2, i2 = sharded_knn.search(index, q, 5, algo="scan",
                                    merge_engine="ring")
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


class TestEngineResolution:
    def test_override_and_env(self, monkeypatch):
        assert ring_topk.resolve_engine(8, 5, 4, override="ring") == "ring"
        monkeypatch.setenv("RAFT_TPU_SHARDED_MERGE", "ring")
        assert ring_topk.resolve_engine(8, 5, 4) == "ring"
        monkeypatch.setenv("RAFT_TPU_SHARDED_MERGE", "allgather")
        assert ring_topk.resolve_engine(8, 5, 4) == "allgather"
        # param override beats env
        assert ring_topk.resolve_engine(8, 5, 4, override="ring") == "ring"
        with pytest.raises(Exception):
            ring_topk.resolve_engine(8, 5, 4, override="bogus")

    def test_subgroups_and_trivial_mesh_force_allgather(self):
        assert ring_topk.resolve_engine(8, 5, 4,
                                        plain_axis=False) == "allgather"
        assert ring_topk.resolve_engine(8, 5, 1,
                                        override="ring") == "allgather"

    def test_cpu_default_is_allgather_and_pallas_gated(self):
        # no TPU in tier-1: the remote-DMA kernel must never be resolved,
        # and asking for it degrades to the XLA ring, not an error
        assert not ring_topk.ring_capable(8, 5, backend="cpu")
        assert ring_topk.resolve_engine(8, 5, 4) == "allgather"
        assert ring_topk.resolve_engine(
            8, 5, 4, override="ring_pallas") == "ring"

    def test_tpu_never_resolves_the_ring_kernel(self, monkeypatch):
        # it halted a v5e 2x2 (PR 21): a TPU mesh defaults to allgather,
        # ignores a recorded ring_pallas verdict, and refuses an ask
        from types import SimpleNamespace

        from raft_tpu.ops import autotune

        tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert ring_topk.resolve_engine(8, 5, 4, mesh=tpu) == "allgather"
        monkeypatch.setattr(autotune, "lookup", lambda key: "ring_pallas")
        assert ring_topk.resolve_engine(8, 5, 4, mesh=tpu) == "allgather"
        with pytest.raises(Exception, match="halts a TPU"):
            ring_topk.resolve_engine(8, 5, 4, override="ring_pallas",
                                     mesh=tpu)

    def test_note_fallback_reports_to_ops_surface(self):
        from raft_tpu.serve import metrics

        before = metrics.counter("sharded.ring.demotions").value
        ring_topk.note_engine("knn", "ring")
        ring_topk.note_fallback("knn")
        assert ring_topk.active_engines["knn"] == "allgather"
        assert metrics.counter("sharded.ring.demotions").value == before + 1
        # shared dict: sharded_ann's ops surface sees the same state
        assert sharded_ann._ACTIVE_ENGINE is ring_topk.active_engines
        assert sharded_ann.ops_snapshot()["families"]["knn"][
            "merge_engine"] == "allgather"

    def test_mesh_aware_resolution(self, mesh4):
        # a CPU mesh must never resolve to the TPU-only remote-DMA
        # kernel, whatever the process default backend is
        assert ring_topk.resolve_engine(32, 5, 4, mesh=mesh4) == "allgather"
        assert "meshcpu" in ring_topk._bucket(8, 5, 4, jnp.float32, mesh4)

    def test_autotune_verdict_steers(self, multichip_mesh):
        from raft_tpu.ops import autotune

        winner, timings = ring_topk.tune_merge(multichip_mesh, m=8, k=5)
        assert winner in ring_topk.ENGINES
        assert set(timings) >= {"allgather", "ring"}
        assert ring_topk.resolve_engine(8, 5, 8) == winner
        autotune.forget(ring_topk._bucket(8, 5, 8, jnp.float32))


@pytest.mark.tpu
class TestTpuRingKernel:
    def test_ring_pallas_bit_identical(self):
        """The remote-DMA ring kernel vs the allgather merge on a real
        TPU mesh (RAFT_TPU_TEST_LANE=1; remote DMA has no CPU interpret
        emulation on this jax)."""
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs a multi-chip TPU mesh")
        mesh = Mesh(np.array(devs), ("shard",))
        p = len(devs)
        rng = np.random.default_rng(0)
        m, k = 32, 10
        d = np.sort(rng.standard_normal((p, m, k)).astype(np.float32), -1)
        gid = rng.integers(0, 1 << 20, size=(p, m, k)).astype(np.int32)
        ref = brute_force.knn_merge_parts(jnp.asarray(d),
                                          jnp.asarray(gid), True)
        dd, gg = _sharded_parts(mesh, d, gid)
        od, og = _merge_on_mesh(mesh, dd, gg, k, True, "ring_pallas")
        np.testing.assert_array_equal(np.asarray(od), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(og), np.asarray(ref[1]))
