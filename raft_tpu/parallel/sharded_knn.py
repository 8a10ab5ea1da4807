"""Multi-chip sharded exact kNN: the MNMG brute-force analog.

Reference pattern (SURVEY.md §2.11.3): each rank holds an index shard,
queries are broadcast, each rank computes its local top-k, and the per-shard
results are merged (detail/knn_merge_parts.cuh:172, orchestrated by
raft-dask + cuML kneighbors).

TPU design: the dataset is sharded along a mesh axis with `jax.sharding`;
`jax.shard_map` runs the single-chip tiled search per shard, local indices
are rebased to global ids from the shard's axis index, and the (k)-sized
candidate lists merge across ICI (:mod:`raft_tpu.ops.ring_topk`:
allgather + ``knn_merge_parts``, or the bit-identical ring engines with
O(k) traffic per hop) — cross-chip traffic is candidate lists only,
never raw vectors. Results come back device-resident: nothing on this
path blocks on readiness, callers sync when they consume.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.errors import expects
from ..distance.distance_types import is_min_close
from ..neighbors import brute_force
from ..ops import ring_topk
from ..utils import cdiv, shard_map_compat
from . import dispatch_cache

__all__ = ["ShardedIndex", "build", "search", "dryrun"]

AXIS = "shard"


class ShardedIndex:
    """Brute-force index sharded over a 1-D mesh axis.

    The dataset is padded to a multiple of the axis size and placed with
    rows sharded; padding rows are masked out at search time by the
    per-shard row-count carried in ``shard_sizes``.
    """

    def __init__(self, mesh: Mesh, dataset_sharded: jax.Array, n_total: int,
                 metric, metric_arg: float = 2.0):
        self.mesh = mesh
        self.dataset = dataset_sharded  # (n_pad, d), sharded over AXIS
        self.n_total = n_total
        self.metric = metric
        self.metric_arg = metric_arg

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[AXIS]

    @property
    def shard_rows(self) -> int:
        return self.dataset.shape[0] // self.n_shards


def build(dataset, mesh: Mesh, metric="sqeuclidean", metric_arg: float = 2.0) -> ShardedIndex:
    """Distribute the dataset row-sharded over ``mesh`` axis "shard"."""
    expects(AXIS in mesh.shape, "mesh must have a %r axis", AXIS)
    n, d = dataset.shape
    p = mesh.shape[AXIS]
    shard_rows = cdiv(n, p)
    n_pad = shard_rows * p
    data = np.zeros((n_pad, d), np.float32)
    data[:n] = np.asarray(dataset, np.float32)
    sharding = NamedSharding(mesh, P(AXIS, None))
    dataset_sharded = jax.device_put(jnp.asarray(data), sharding)
    return ShardedIndex(mesh, dataset_sharded, n, metric, metric_arg)


def search(index: ShardedIndex, queries, k: int, tile_size: int = 8192,
           algo: str | None = None, merge_engine: str | None = None
           ) -> Tuple[jax.Array, jax.Array]:
    """Sharded search: per-shard top-k then cross-shard merge.

    Queries are replicated; the result is replicated (every chip holds
    the merged answer) and DEVICE-RESIDENT — this path never blocks on
    readiness; callers sync when they consume the arrays.

    ``merge_engine``: force one of ``ops.ring_topk.ENGINES`` (ring or
    allgather merge — bit-identical); default resolves via
    ``RAFT_TPU_SHARDED_MERGE`` / the autotune verdict / backend.

    The compiled ``shard_map`` program is cached on the index per
    (engine, k, tile, algo) bucket (:mod:`.dispatch_cache`): repeat
    calls at a warmed shape dispatch a cached executable instead of
    re-tracing the whole sharded program.
    """
    select_min = is_min_close(index.metric)
    shard_rows = index.shard_rows
    n_total = index.n_total
    p = index.n_shards
    metric, metric_arg = index.metric, index.metric_arg
    # the per-shard compute runs on the mesh's devices, not the default
    # backend: only use the fused Pallas path when the mesh is TPU
    if algo is None:
        mesh_platform = index.mesh.devices.flat[0].platform
        algo = "auto" if mesh_platform == "tpu" else "scan"
    q = jnp.asarray(queries, jnp.float32)
    eng = ring_topk.resolve_engine(q.shape[0], k, p, override=merge_engine,
                                   mesh=index.mesh)
    cache = dispatch_cache.cache_of(index)

    def prog(merge_eng):
        key = dispatch_cache.program_key(
            "knn", merge_eng, index.mesh, None, None,
            (("k", k), ("tile", int(tile_size)), ("algo", algo),
             ("mt", metric), ("ma", metric_arg), ("n", int(n_total))))
        fn = cache.get(key) if dispatch_cache.enabled() else None
        if fn is None:
            def local_search(data_shard, qq):
                rank = jax.lax.axis_index(AXIS)
                base = rank * shard_rows
                # local exact search on this shard's rows; padding rows
                # (only the tail shard has them) are masked inside the
                # tiled scan so they can never displace true candidates
                # from the local top-k
                n_valid_local = jnp.clip(n_total - base, 0, shard_rows)
                local = brute_force.build(data_shard, metric, metric_arg)
                dist, idx = brute_force.search(local, qq, k,
                                               tile_size=tile_size,
                                               valid_rows=n_valid_local,
                                               algo=algo)
                gidx = jnp.where(idx >= 0, idx + base, -1)
                bad = jnp.inf if select_min else -jnp.inf
                dist = jnp.where(gidx >= 0, dist, bad)
                # only candidate lists cross ICI; vectors never move
                return ring_topk.merge(dist, gidx, k, select_min,
                                       axis=AXIS, axis_size=p,
                                       engine=merge_eng)

            sm = shard_map_compat(
                local_search,
                mesh=index.mesh,
                in_specs=(P(AXIS, None), P()),
                out_specs=(P(), P()),
                check=False,
            )
            fn = jax.jit(sm)
            if dispatch_cache.enabled():
                cache[key] = fn
            # else: fresh wrapper per call — re-trace/re-compile the
            # identical (bitwise) program; the measurement baseline
        return fn

    def run(e):
        with dispatch_cache.dispatch_label("knn", int(q.shape[0]), k):
            return prog(e)(index.dataset, q)

    return ring_topk.guarded_dispatch("knn", eng, run)


def dryrun(n_devices: int, ring_check: bool = True) -> None:
    """Driver hook: build an n-device mesh on whatever devices exist and run
    one full sharded search step on tiny shapes, verifying against the
    single-chip answer. ``ring_check=False`` skips the ring-engine
    cross-check (a second full search compile, ~4 s on the CPU mesh):
    the driver artifact keeps it; tier-1 covers the same path in
    tests/test_ring_topk.py."""
    devices = jax.devices()[:n_devices]
    expects(len(devices) == n_devices,
            "need %d devices, have %d", n_devices, len(devices))
    mesh = Mesh(np.array(devices), (AXIS,))
    rng = np.random.default_rng(0)
    # >=10k rows per device: big enough that a cross-shard merge bug
    # (rank mixing, id rebasing, padding leaks) actually surfaces
    data = rng.standard_normal((10_000 * n_devices - 17, 64)
                               ).astype(np.float32)
    q = rng.standard_normal((32, 64)).astype(np.float32)
    index = build(data, mesh)
    # pin both sides to the scan engine: the check below is exact-equality
    # on indices, which different engines may break on fp ties. Results
    # stay device-resident (no block_until_ready on the search path —
    # the np.asarray reads below are the sync point).
    dist, idx = jax.jit(
        lambda qq: search(index, qq, k=5, tile_size=128, algo="scan"))(q)
    # verify against single-device exact search (scan path: the comparison
    # is exact-equality on indices, so both sides must use the same engine)
    local = brute_force.build(data)
    ref_d, ref_i = brute_force.search(local, q, 5, tile_size=512, algo="scan")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_i))
    ring_note = ""
    if ring_check:
        # the ring merge engine must be BIT-identical to the allgather
        # merge (order included) — the driver artifact carries the
        # cross-engine check at the same scale as the single-chip one
        dist_r, idx_r = search(index, q, k=5, tile_size=128, algo="scan",
                               merge_engine="ring")
        np.testing.assert_array_equal(np.asarray(idx_r), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(dist_r), np.asarray(dist))
        ring_note = "; ring merge bit-identical"
    # report the engine that actually SERVED (fallbacks included), not a
    # fresh resolution
    eng = ring_topk.active_engines.get("knn", "-")
    print(f"dryrun_multichip ok: sharded brute force over {n_devices} "
          f"devices x {len(data) // n_devices + 1} rows, merged top-5 "
          f"matches single-chip exactly{ring_note} [engine={eng}]")
