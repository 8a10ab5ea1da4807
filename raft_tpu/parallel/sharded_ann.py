"""Multi-chip sharded ANN indexes (IVF-Flat and CAGRA): the MNMG analog for
approximate search.

Reference pattern (SURVEY.md §2.11.3, BASELINE north star "sharded IVF-PQ
DEEP-1B build on v5p-32"): each rank builds an index over its own rows;
queries are replicated; each rank searches locally and per-shard top-k
lists are merged (detail/knn_merge_parts.cuh:172). raft-dask bootstraps
this per worker; here one process drives the whole mesh.

TPU design: per-shard index arrays are **stacked along a leading axis and
sharded over the mesh** with `jax.sharding` (shape (p, ...) with spec
P(AXIS, ...)); the single-chip pure-array search cores
(ivf_flat.search_arrays, cagra._search_jit internals) run inside one
`shard_map`, then the per-shard (k)-wide result lists merge across ICI —
vectors never move between chips. Shard row counts are padded to a
common size; source ids carry GLOBAL row numbers so the merge is
trivial.

Shard health closes its own loop: ``mark_shard_failed`` masks a shard
out of every merge, and :func:`probe_shards` (periodic via
``SnapshotWriter(hooks=[probe_all])``) canary-probes dead shards and
flips ``shards_ok`` back once the fault clears — ``served_frac``
recovers without an operator (docs/robustness.md "Shard re-probe").

The cross-shard merge dispatches through :mod:`raft_tpu.ops.ring_topk`:
either the reference allgather + ``knn_merge_parts`` path or a ring
merge (``ppermute`` hops in XLA, or the Pallas ``make_async_remote_copy``
kernel on TPU) that keeps candidates device-resident with O(k) ICI
traffic per hop. All engines are bit-identical (order included), so the
ring engines are gated behind ``guarded_call("sharded.ring_topk")`` with
the allgather path as containment. Dead shards contribute (±inf, −1)
sentinel rows inside whichever engine runs, so the ``allow_partial``
degraded-merge contract survives unchanged.
"""
from __future__ import annotations

import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..comms import AxisComms
from ..core import faults
from ..core.resources import workspace_chunk_bytes
from ..core.errors import ShardsDownError, expects
from ..distance.distance_types import DistanceType, canonical_metric, is_min_close
from ..neighbors import cagra, ivf_flat, ivf_pq
from ..ops import ring_topk
from ..utils import cdiv, shard_map_compat
from . import dispatch_cache

__all__ = ["ShardedIvfFlat", "build_ivf_flat", "search_ivf_flat",
           "ShardedCagra", "build_cagra", "search_cagra",
           "ShardedIvfPq", "build_ivf_pq", "search_ivf_pq",
           "make_searcher", "warmup_searchers", "widen_rungs",
           "searcher_dim", "ops_snapshot", "health",
           "probe_shards", "probe_all"]

AXIS = "shard"

# guarded site for the ring merge engines (ops/guarded.py): a ring
# compile/execution failure demotes this process to the bit-identical
# allgather merge
MERGE_SITE = ring_topk.MERGE_SITE

# family -> merge engine that actually served the most recent search
# (the ops surface: serve/debugz.py reports which engine is serving).
# Shared with ops.ring_topk so sharded_knn's chokepoint reports here too.
_ACTIVE_ENGINE = ring_topk.active_engines

# live sharded indexes (weak: an operator dropping an index must not leak
# it through the ops surface) — debugz reads per-family shards_ok here
_LIVE = weakref.WeakSet()

# shard-MTTR bookkeeping: down-transition timestamps per shard site
# (``sharded_ann.<family>.shard<i>``), observed into the ``shard.mttr``
# histogram on the up-transition. The clock is module-injectable so a
# compressed-time soak (raft_tpu/soak) measures simulated MTTR.
_clock = time.monotonic
_downed_at: dict = {}


def _merged_shard_search(index, family: str, make_local, in_specs, arrays,
                         m: int, k: int, select_min: bool, comms, statics,
                         merge_engine=None, topology=None, donate_q=None):
    """One chokepoint for every sharded family's cross-shard merge:
    resolve the engine (param/env override → autotune verdict → backend
    default; a multi-host ``topology`` adds the hierarchical ICI/DCN
    tier), fetch the cached jitted ``shard_map`` program for this
    (engine, statics) bucket — tracing it ONCE on a miss from
    ``make_local()``'s per-shard closure (dead shards already masked to
    sentinel rows) — and gate every non-allgather engine behind
    ``guarded_call(MERGE_SITE)`` falling back to the bit-identical
    allgather program (which caches under its own key, so the fallback
    is also trace-once). Returns replica-identical (distances, ids).

    ``statics`` is the family's closure-baked (name, value) tuple — see
    docs/perf.md "Sharded dispatch" for the key anatomy. ``donate_q``:
    position of the replicated query array in ``arrays`` to donate to
    the compiled program (make_searcher(donate=True)); None keeps the
    caller's buffer. ``RAFT_TPU_SHARDED_DISPATCH=uncached`` restores
    the eager per-call trace (the bitwise comparison hook)."""
    mesh = index.mesh
    p = mesh.shape[AXIS]
    # ring engines permute over the raw mesh axis: an injected
    # communicator restricted to subgroups keeps the allgather path
    plain_axis = getattr(comms, "groups", True) is None
    eng = ring_topk.resolve_engine(m, k, p, override=merge_engine,
                                   plain_axis=plain_axis, mesh=mesh,
                                   topology=topology)
    cache = dispatch_cache.cache_of(index)

    def prog(e):
        key = dispatch_cache.program_key(
            family, e, mesh, topology, comms,
            (("k", k), ("dq", donate_q is not None)) + tuple(statics))
        fn = cache.get(key) if dispatch_cache.enabled() else None
        if fn is None:
            local_fn = make_local()

            def body(*xs):
                d, gi = local_fn(*xs)
                return ring_topk.merge(d, gi, k, select_min, comms=comms,
                                       axis=AXIS, axis_size=p, engine=e,
                                       topology=topology)

            sm = shard_map_compat(body, mesh=mesh,
                                  in_specs=tuple(in_specs),
                                  out_specs=(P(), P()), check=False)
            fn = jax.jit(sm, donate_argnums=(
                () if donate_q is None else (int(donate_q),)))
            if dispatch_cache.enabled():
                cache[key] = fn
            # else: fresh wrapper per call — re-trace/re-compile the
            # identical (bitwise) program; the measurement baseline
        return fn

    def run(e):
        with dispatch_cache.dispatch_label(family, m, k):
            return prog(e)(*arrays)

    return ring_topk.guarded_dispatch(family, eng, run)


def ops_snapshot() -> dict:
    """The sharded-serving ops surface (read by serve/debugz.py):
    per-family shard health of every live index, the merge engine each
    family's latest search actually resolved, and how many ring-merge
    calls this process served through the allgather fallback."""
    fams: dict = {}
    # WeakSet iteration is python-level and raises RuntimeError if a
    # build thread registers an index mid-snapshot (the background
    # SnapshotWriter case); retry rather than lose the whole section
    for _ in range(4):
        try:
            live = list(_LIVE)
            break
        except RuntimeError:
            continue
    else:
        live = []
    for idx in live:
        ent = fams.setdefault(idx.family, {"indexes": 0, "shards_ok": []})
        ent["indexes"] += 1
        ent["shards_ok"].append(
            [bool(b) for b in np.asarray(idx.shards_ok, bool)])
        # per-shard re-probe results (probe_shards), one entry per index
        # aligned with the shards_ok list: the operator's answer to "is
        # the dead shard coming back, and if not why". Copied under
        # retry: a background probe loop inserts here concurrently, and
        # losing the whole sharded section during an incident is exactly
        # when the operator is reading it
        for _ in range(4):
            try:
                probes = {str(i): dict(r)
                          for i, r in list(idx.last_probe.items())}
                break
            except RuntimeError:
                continue
        else:
            probes = {}
        ent.setdefault("last_probe", []).append(probes)
    for fam, eng in dict(_ACTIVE_ENGINE).items():
        fams.setdefault(fam, {"indexes": 0, "shards_ok": []})
        fams[fam]["merge_engine"] = eng
    demotions = 0.0
    try:
        from ..serve import metrics as _metrics

        demotions = _metrics.counter("sharded.ring.demotions").value
    except Exception:  # noqa: BLE001
        pass
    from ..ops import guarded

    return {"families": fams,
            "ring_demotions": int(demotions),
            "ring_demoted": MERGE_SITE in guarded.demoted_sites()}


def health(index) -> dict:
    """Sharded-index health report (docs/observability.md "Quality"):
    per-shard real row counts + the sticky ``shards_ok`` flags — the
    numbers that say how much of the corpus a degraded merge is actually
    serving, and whether the row split is balanced enough that one
    shard's loss costs ~1/p recall rather than a hot partition."""
    if isinstance(index, ShardedCagra):
        counts = np.asarray(index.counts, np.int64)
    elif isinstance(index, (ShardedIvfFlat, ShardedIvfPq)):
        # count from the host-side size tables, NOT the device arrays: a
        # multi-process fleet index's ``sizes`` spans non-addressable
        # devices and cannot be fetched host-side. A budget-tiered fleet
        # index's live tables hold HOT sizes only — its full counts live
        # in ``_rows_tbl_full`` (cold rows are still served, streamed;
        # they must not read as lost corpus and trigger the auto-widen)
        tbl = getattr(index, "_rows_tbl_full", None)
        if tbl is None:
            tbl = (index._sizes_host if isinstance(index, ShardedIvfPq)
                   else index._max_rows_tbl)
        counts = np.asarray([int(np.sum(s)) for s in tbl], np.int64)
    else:
        raise TypeError(
            f"no health report for sharded type {type(index).__name__}")
    ok = [bool(b) for b in np.asarray(index.shards_ok, bool)]
    served = int(counts[np.asarray(ok, bool)].sum())
    return {
        "family": f"sharded_{index.family}",
        "n_shards": int(index.n_shards),
        "shards_ok": ok,
        "healthy_shards": int(sum(ok)),
        "n_total": int(index.n_total),
        "shard_rows": [int(c) for c in counts],
        "served_rows": served,
        "served_frac": round(served / max(int(index.n_total), 1), 4),
        "row_skew": round(float(counts.max() / max(counts.min(), 1)), 3),
    }


def _shard_health(index, family: str) -> np.ndarray:
    """Effective per-shard validity for one search call: the index's
    sticky ``shards_ok`` flags (set by ``mark_shard_failed`` — e.g. after
    a failed build, corrupt shard load, or repeated timeouts) AND'd with
    any armed ``shard_dead``/``shard_timeout`` fault probes, so every
    degraded-merge path is deterministically testable."""
    ok = np.asarray(index.shards_ok, bool).copy()
    for i in range(ok.size):
        site = f"sharded_ann.{family}.shard{i}"
        if ok[i] and (faults.fired("shard_dead", site) is not None
                      or faults.fired("shard_timeout", site) is not None):
            ok[i] = False
    return ok


def _health_gate(ok: np.ndarray, allow_partial: bool,
                 family: str = "") -> None:
    """Dead shards without ``allow_partial=True`` are an error, not a
    silently-degraded answer — and ZERO surviving shards is total
    failure, not a degraded answer: an all-(+inf, -1) result piped
    downstream would silently wrap-index with -1.

    A tolerated degraded merge (``allow_partial=True`` with dead shards)
    is counted under ``sharded.degraded_searches.<family>`` — the signal
    previously surfaced only through the serve batcher's per-response
    bookkeeping, invisible to direct callers."""
    if not ok.all():
        if not allow_partial or not ok.any():
            raise ShardsDownError(ok)
        try:
            from ..serve import metrics as _metrics

            _metrics.counter(f"sharded.degraded_searches.{family}").inc()
        except Exception:  # noqa: BLE001 - telemetry must not fail a search
            pass


def _mark_shard(shards_ok: np.ndarray, family: str, i: int, ok: bool) -> None:
    """Set the sticky health flag; flight-record only an actual state
    TRANSITION — a health-check loop re-asserting the same state every
    second must not fill the bounded ring (per-search degradation is the
    counter above)."""
    changed = bool(shards_ok[i]) != bool(ok)
    shards_ok[i] = ok
    if not changed:
        return
    site = f"sharded_ann.{family}.shard{i}"
    try:
        from ..core import events as _events

        _events.record("shard_marked", site, ok=bool(ok))
    except Exception:  # noqa: BLE001
        pass
    # MTTR verdict (docs/soak.md): marked-dead → restored wall
    try:
        if not ok:
            _downed_at[site] = _clock()
        else:
            t0 = _downed_at.pop(site, None)
            if t0 is not None:
                from ..serve import metrics as _metrics

                _metrics.histogram(
                    "shard.mttr",
                    _metrics.MTTR_BUCKETS_S).observe(_clock() - t0)
    except Exception:  # noqa: BLE001 - telemetry must not undo a mark
        pass


def _canary_search(index, i: int, rows: int = 8) -> None:
    """Cheap per-shard canary: slice a few rows of the shard's float
    source arrays off the mesh, run an exact micro-search (rows vs
    themselves) on device, and require finite results. This exercises
    the shard's device round-trip and arithmetic without a ``shard_map``
    dispatch — even with the dispatch cache the first probe at an
    unwarmed shape would pay a whole-program trace, and a canary must
    stay cheap on a cold process. Raises on any failure."""
    site = f"sharded_ann.{index.family}.shard{i}"
    # armed shard faults keep the shard dead, so the recovery arc is
    # deterministically drillable: the probe fails while the fault
    # holds and succeeds the tick after it clears. Checked WITHOUT
    # consuming a firing (matches, not fired): a background probe tick
    # must not drain a count-limited fault budget armed for the search
    # path
    if any(f.matches(k, site) for f in faults.active()
           for k in ("shard_dead", "shard_timeout")):
        raise RuntimeError(f"shard fault armed at {site}")
    src = index._canary_source()
    # never ask for more rows than the source has (a 1-list/1-row shard
    # must still be probeable — a shape clamp that rounds UP would fail
    # its canary forever)
    rows = max(1, min(int(rows), int(src.shape[1])))
    x = jnp.asarray(src[i, :rows], jnp.float32)
    d = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    dd = np.asarray(d)
    if dd.shape != (rows, rows) or not np.isfinite(dd).all():
        raise RuntimeError(
            f"canary produced non-finite distances on shard {i}")


def probe_shards(index, *, rows: int = 8, probe_fn=None) -> dict:
    """Re-probe every shard currently marked failed; flip ``shards_ok``
    back on success (docs/robustness.md "Shard re-probe").

    ``mark_shard_failed`` has always been a one-way street in practice:
    nothing re-marked a shard after a transient ICI/driver fault, so
    ``served_frac`` never recovered. This closes the loop: each dead
    shard runs a cheap canary (:func:`_canary_search`, or ``probe_fn(
    index, shard)`` when injected); success re-marks the shard healthy
    (a ``shard_marked ok=True`` transition plus an explicit
    ``shard_restored`` flight-recorder event), failure records why and
    leaves the sticky flag alone. Healthy shards are never probed.

    Returns ``{shard: ok}`` for the shards probed. Per-shard last-probe
    results are kept on the index (``index.last_probe``) and surfaced in
    the debugz ``sharded`` section. Call on an interval from serving —
    e.g. ``SnapshotWriter(..., hooks=[sharded_ann.probe_all])``.
    """
    ok = np.asarray(index.shards_ok, bool)
    results: dict = {}
    for i in np.flatnonzero(~ok):
        i = int(i)
        site = f"sharded_ann.{index.family}.shard{i}"
        rec = {"ok": False, "ts": time.time(), "error": None}
        try:
            if probe_fn is not None:
                probe_fn(index, i)
            else:
                _canary_search(index, i, rows=rows)
            rec["ok"] = True
            index.mark_shard_failed(i, ok=True)
            try:
                from ..core import events as _events

                _events.record("shard_restored", site,
                               served_frac=health(index)["served_frac"])
            except Exception:  # noqa: BLE001 - telemetry must not undo
                pass           # the restore
        except Exception as e:  # noqa: BLE001 - a failed probe is a result
            rec["error"] = f"{type(e).__name__}: {e}"
            try:
                from ..serve import metrics as _metrics

                _metrics.counter(
                    f"sharded.probe_failures.{index.family}").inc()
            except Exception:  # noqa: BLE001
                pass
        index.last_probe[i] = rec
        results[i] = rec["ok"]
    return results


def probe_all(**kw) -> dict:
    """Probe every live sharded index with dead shards (the
    SnapshotWriter-hook form of :func:`probe_shards`); returns
    ``{family: {shard: ok}}`` merged across live indexes."""
    out: dict = {}
    for _ in range(4):
        try:
            live = list(_LIVE)
            break
        except RuntimeError:     # registration race (see ops_snapshot)
            continue
    else:
        live = []
    for idx in live:
        if not np.asarray(idx.shards_ok, bool).all():
            out.setdefault(idx.family, {}).update(probe_shards(idx, **kw))
    return out


def _shard_mask(mesh, ok: np.ndarray) -> jax.Array:
    """(p, 1) bool validity mask sharded over the mesh axis (rides into
    shard_map so each shard masks its own contribution pre-merge)."""
    return jax.device_put(jnp.asarray(ok.reshape(-1, 1)),
                          NamedSharding(mesh, P(AXIS, None)))


def _comms_of(mesh, res=None) -> AxisComms:
    """Communicator for the shard axis: the injected one when a Resources
    carries it (the reference's resource::get_comms path), else a fresh
    AxisComms over the mesh's axis."""
    if res is not None and res.has_comms():
        return res.comms
    return AxisComms(AXIS, size=mesh.shape[AXIS])


def _split_rows(n: int, p: int) -> list[np.ndarray]:
    """Balanced contiguous row ranges per shard (the reference shards row
    blocks); no shard is ever empty for n >= p."""
    expects(n >= p, "cannot shard %d rows over %d shards", n, p)
    return np.array_split(np.arange(n), p)


def _stack_pad(arrs: list[np.ndarray], pad_value=0,
               min_rows: int = 0) -> np.ndarray:
    """Stack along a new leading axis, padding dim 0 to the common max
    (or ``min_rows`` if larger — build_cagra uses it to guarantee every
    shard has at least one padding row for seed-padding sentinels)."""
    m = max(min_rows, max(a.shape[0] for a in arrs))
    out = np.full((len(arrs), m) + arrs[0].shape[1:], pad_value,
                  arrs[0].dtype)
    for i, a in enumerate(arrs):
        out[i, : a.shape[0]] = a
    return out


class ShardedIvfFlat:
    """Stacked per-shard IVF-Flat arrays, leading axis sharded over AXIS."""

    family = "ivf_flat"

    def __init__(self, mesh, data, data_norms, source_ids, centers,
                 center_norms, offsets, sizes, n_total, metric, max_rows_tbl,
                 scales=None, store=None, logical_dim=None):
        self.mesh = mesh
        self.data = data                    # (p, R, d) f32|bf16|int8|uint8
        self.data_norms = data_norms        # (p, R)
        self.source_ids = source_ids        # (p, R) global ids, -1 pad
        self.centers = centers              # (p, L, d)
        self.center_norms = center_norms    # (p, L)
        self.offsets = offsets              # (p, L) row offsets (per shard)
        self.sizes = sizes                  # (p, L) list sizes
        self.n_total = n_total
        self.metric = metric
        self._max_rows_tbl = max_rows_tbl   # host: n_probes → max_rows bound
        self.scales = scales                # (p, R) f32, int8/int4 modes
        # storage rung of the stacked rows ("float32"/"int8"/"int4"/...)
        # — "int4" means nibble-packed data whose last axis is the
        # packed half-width, so searches must decode via logical_dim
        self.store = store if store is not None else str(data.dtype)
        self.logical_dim = int(data.shape[-1] if logical_dim is None
                               else logical_dim)
        # sticky per-shard health flags (see mark_shard_failed)
        self.shards_ok = np.ones(mesh.shape[AXIS], bool)
        # shard -> last probe_shards result (debugz sharded section)
        self.last_probe: dict = {}
        _LIVE.add(self)

    def mark_shard_failed(self, i: int, ok: bool = False) -> None:
        """Flag shard ``i`` unhealthy: its results are masked out of every
        merge until re-marked ok (search then needs allow_partial=True)
        or a :func:`probe_shards` canary succeeds."""
        _mark_shard(self.shards_ok, "ivf_flat", i, ok)

    def _canary_source(self):
        """Small float per-shard array for :func:`probe_shards`."""
        return self.centers

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[AXIS]

    def max_rows(self, n_probes: int) -> int:
        """Static probe budget: max over shards of the n_probes largest
        lists summed. A budget-tiered fleet index computes the bound
        from the FULL size table (``_rows_tbl_full``): the live table
        holds hot sizes that change across tier steps, and this static
        is baked into the cached dispatch executables — the bound must
        not move on a re-tier (the zero-recompile tier-step contract).
        The full-table bound is a superset of any hot bound and the
        extra gather slots are masked sentinel rows, so results are
        bitwise unchanged."""
        tbl = getattr(self, "_rows_tbl_full", None)
        if tbl is None:
            tbl = self._max_rows_tbl
        return int(max(ivf_flat._probe_budget(s, n_probes) for s in tbl))


def build_ivf_flat(dataset, mesh: Mesh,
                   params: ivf_flat.IndexParams | None = None
                   ) -> ShardedIvfFlat:
    """Build one IVF-Flat index per shard over its contiguous row block
    (the raft-dask pattern: each worker indexes its own partition)."""
    expects(AXIS in mesh.shape, "mesh must have a %r axis", AXIS)
    p0 = params or ivf_flat.IndexParams()
    dataset = np.asarray(dataset, np.float32)
    n = len(dataset)
    p = mesh.shape[AXIS]
    parts = _split_rows(n, p)
    expects(p0.n_lists <= min(len(r) for r in parts),
            "n_lists %d > smallest shard %d", p0.n_lists,
            min(len(r) for r in parts))

    shards = [ivf_flat.build(dataset[rows], p0) for rows in parts]
    mt = shards[0].metric

    data = _stack_pad([np.asarray(s.data) for s in shards])
    norms = _stack_pad([np.asarray(s.data_norms) for s in shards])
    # rebase local ids to global row numbers
    gids = _stack_pad(
        [np.where(np.asarray(s.source_ids) >= 0,
                   np.asarray(s.source_ids) + parts[i][0], -1)
         for i, s in enumerate(shards)],
        pad_value=-1)
    centers = np.stack([np.asarray(s.centers) for s in shards])
    cnorms = np.stack([np.asarray(s.center_norms) for s in shards])
    offsets = np.stack([s.list_offsets[:-1] for s in shards]).astype(np.int32)
    sizes = np.stack([s.list_sizes for s in shards]).astype(np.int32)

    def put(x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    scales = None
    if shards[0].scales is not None:   # int8: per-row dequant factors
        scales = put(_stack_pad([np.asarray(s.scales) for s in shards]),
                     P(AXIS, None))
    return ShardedIvfFlat(
        mesh,
        put(data, P(AXIS, None, None)), put(norms, P(AXIS, None)),
        put(gids, P(AXIS, None)),
        put(centers, P(AXIS, None, None)), put(cnorms, P(AXIS, None)),
        put(offsets, P(AXIS, None)), put(sizes, P(AXIS, None)),
        n, mt, [s.list_sizes for s in shards], scales)


def search_ivf_flat(index: ShardedIvfFlat, queries, k: int,
                    params: ivf_flat.SearchParams | None = None,
                    res=None, allow_partial: bool = False,
                    merge_engine: str | None = None, filter=None,  # noqa: A002
                    donate: bool = False):
    """Replicated queries → per-shard local search → cross-shard merge
    (ring or allgather engine; see :func:`_merged_shard_search` — the
    compiled program is cached per index, so repeat calls at a warmed
    shape compile nothing).

    ``allow_partial=True`` accepts dead shards (``index.shards_ok`` or an
    armed ``shard_dead``/``shard_timeout`` fault): their contributions
    are masked out of the merge and the return becomes
    ``(distances, indices, shards_ok)`` reporting the loss. Default
    (False) raises :class:`ShardsDownError` when any shard is dead.
    The health mask rides into the program as a TRACED argument, so
    marking/restoring shards reuses the cached executable.
    ``merge_engine``: force one of ``ops.ring_topk.ENGINES`` (or
    ``"auto"``); default consults ``RAFT_TPU_SHARDED_MERGE`` and the
    autotune verdict for this shape bucket.
    ``filter``: optional GLOBAL-id sample bitset (n_total bits); the
    replicated mask rides into every shard's local search (shard
    source ids are global, so the gather indexes it directly). A
    filtered row yields the same (+inf, -1) sentinel the dead-shard
    path emits, so the merge needs no new semantics.
    ``donate=True`` donates the replicated query buffer to the compiled
    program (docs/perf.md "Sharded dispatch" donation caveats: only
    safe when the caller does not reuse ``queries``).
    """
    sp = params or ivf_flat.SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    n_probes = min(sp.n_probes, index.centers.shape[1])
    max_rows = index.max_rows(n_probes)
    mt = index.metric
    select_min = is_min_close(mt)
    comms = _comms_of(index.mesh, res)
    ok = _shard_health(index, "ivf_flat")
    _health_gate(ok, allow_partial, "ivf_flat")

    has_scales = index.scales is not None
    mask = filter.to_mask() if filter is not None else None
    has_filter = mask is not None
    int4_dim = (index.logical_dim
                if getattr(index, "store", None) == "int4" else None)

    def make_local():
        def local(data, norms, gids, centers, cnorms, offsets, sizes, okf,
                  qq, *rest):
            args = [a[0] for a in (data, norms, gids, centers, cnorms,
                                   offsets, sizes)]
            sc = rest[0][0] if has_scales else None
            mb = rest[int(has_scales)] if has_filter else None
            d, i = ivf_flat.search_arrays(
                args[0], args[1], args[2], args[3], args[4], args[5],
                args[6], qq, k, n_probes, max_rows, mt, mask_bits=mb,
                scales=sc, int4_dim=int4_dim)
            # dead-shard containment: an invalid shard's list is all
            # (+inf, -1) sentinel rows, so the merge is over survivors
            bad = jnp.inf if select_min else -jnp.inf
            d = jnp.where(okf[0, 0], d, bad)
            i = jnp.where(okf[0, 0], i, -1)
            return d, i
        return local

    in_specs = [P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                P(AXIS, None), P(AXIS, None), P()]
    arrays = [index.data, index.data_norms, index.source_ids,
              index.centers, index.center_norms, index.offsets,
              index.sizes, _shard_mask(index.mesh, ok), q]
    q_pos = 8                          # q's slot, for donation
    if has_scales:
        in_specs.append(P(AXIS, None))
        arrays.append(index.scales)
    if has_filter:
        in_specs.append(P())           # replicated: gids are global
        arrays.append(mask)
    statics = (("np", n_probes), ("mr", max_rows), ("mt", mt.name),
               ("sc", has_scales), ("f", has_filter), ("i4", int4_dim))
    d, i = _merged_shard_search(index, "ivf_flat", make_local, in_specs,
                                arrays, q.shape[0], k, select_min, comms,
                                statics, merge_engine,
                                topology=getattr(index, "topology", None),
                                donate_q=q_pos if donate else None)
    return (d, i, ok) if allow_partial else (d, i)


class ShardedCagra:
    """Stacked per-shard CAGRA graphs, leading axis sharded over AXIS."""

    family = "cagra"

    def __init__(self, mesh, data, graphs, bases, counts, n_total, metric,
                 seeds=None):
        self.mesh = mesh
        self.data = data        # (p, R, d) padded rows
        self.graphs = graphs    # (p, R, deg) LOCAL neighbor ids
        self.bases = bases      # (p,) global row base per shard
        self.counts = counts    # (p,) real (unpadded) rows per shard
        self.n_total = n_total
        self.metric = metric
        self.seeds = seeds      # (p, s) per-shard covering seed rows
                                # (sorted unique; invalid-id padded)
        self.shards_ok = np.ones(mesh.shape[AXIS], bool)
        self.last_probe: dict = {}
        _LIVE.add(self)

    def mark_shard_failed(self, i: int, ok: bool = False) -> None:
        """Flag shard ``i`` unhealthy (see ShardedIvfFlat.mark_shard_failed)."""
        _mark_shard(self.shards_ok, "cagra", i, ok)

    def _canary_source(self):
        return self.data

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[AXIS]


def build_cagra(dataset, mesh: Mesh,
                params: cagra.IndexParams | None = None) -> ShardedCagra:
    """Build one CAGRA graph per shard row block."""
    expects(AXIS in mesh.shape, "mesh must have a %r axis", AXIS)
    p0 = params or cagra.IndexParams()
    dataset = np.asarray(dataset, np.float32)
    n = len(dataset)
    p = mesh.shape[AXIS]
    parts = _split_rows(n, p)
    # per-shard COVERING seed sets ride along (stacked + padded): random
    # seeding alone collapses recall once shards hold >~1k rows — 32
    # random seeds cover 0.3% of a 10k-row shard and the traversal
    # strands in the wrong cluster (r5 dryrun: recall 0.27 vs 0.97)
    shards = [cagra.build(dataset[rows], p0) for rows in parts]
    mt = shards[0].metric

    counts = np.array([len(r) for r in parts], np.int32)
    seed_sets = [np.asarray(s.seed_nodes)
                 if s.seed_nodes is not None else np.zeros((0,), np.int32)
                 for s in shards]
    n_seed = max(ss.shape[0] for ss in seed_sets)
    # every shard's seed padding (count_i + pad_i sentinel ids, below)
    # must land on a real-but-invalid padded row: per-shard seed counts
    # are data-dependent (np.unique in _covering_seeds), so size the row
    # capacity to the worst pad, not a fixed slack
    max_pad = max((n_seed - ss.shape[0] for ss in seed_sets), default=0)
    cap = int(counts.max()) + max(8, max_pad + 1)
    data = _stack_pad([np.asarray(s.dataset) for s in shards],
                      min_rows=cap)
    graphs = _stack_pad([np.asarray(s.graph) for s in shards],
                        min_rows=cap)
    bases = np.array([r[0] for r in parts], np.int32)

    seeds = None
    if n_seed > 0:
        # pad each shard's sorted-unique seed list with ascending
        # INVALID row ids (count_i + j < cap): stays sorted unique, and
        # the search-time mask (valid rows only) scores them +inf
        padded = []
        for i, ss in enumerate(seed_sets):
            pad = n_seed - ss.shape[0]
            padded.append(np.concatenate(
                [ss, counts[i] + np.arange(pad, dtype=np.int32)]))
        seeds = np.stack(padded).astype(np.int32)

    def put(x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    return ShardedCagra(mesh, put(data, P(AXIS, None, None)),
                        put(graphs, P(AXIS, None, None)),
                        put(bases, P(AXIS)), put(counts, P(AXIS)), n, mt,
                        seeds=None if seeds is None
                        else put(seeds, P(AXIS, None)))


def search_cagra(index: ShardedCagra, queries, k: int,
                 params: cagra.SearchParams | None = None,
                 res=None, allow_partial: bool = False,
                 merge_engine: str | None = None, filter=None,  # noqa: A002
                 donate: bool = False):
    """Replicated queries → per-shard graph traversal → cross-shard merge.

    ``allow_partial``/``merge_engine``/``filter``/``donate``: contract
    of :func:`search_ivf_flat`. CAGRA shard rows are LOCAL (row = global
    id - base), so each shard slices its window out of the replicated
    global mask and folds it into the padding-row validity mask that
    already rides ``_search_jit``'s filter slot.
    """
    sp = params or cagra.SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    itopk = max(sp.itopk_size, k)
    width = max(1, sp.search_width)
    max_iter = sp.max_iterations or (itopk // width + 16)
    degree = index.graphs.shape[2]
    n_seeds = min(itopk, max(width * degree // 2,
                             16 * sp.num_random_samplings))
    mt = index.metric
    select_min = mt is not DistanceType.InnerProduct
    comms = _comms_of(index.mesh, res)
    ok = _shard_health(index, "cagra")
    _health_gate(ok, allow_partial, "cagra")

    has_seeds = index.seeds is not None
    mask = None
    if filter is not None:
        R = int(index.data.shape[1])
        mask = filter.to_mask()
        # pad the global mask with False so every shard's (base, base+R)
        # window is in range: lax.dynamic_slice CLAMPS an out-of-range
        # start, which would silently shift the last shard's window
        need = int(np.asarray(index.bases).max()) + R
        if mask.shape[0] < need:
            mask = jnp.pad(mask, (0, need - mask.shape[0]))
    has_filter = mask is not None

    def make_local():
        def local(data, graph, base, count, okf, qq, *rest):
            # padding rows (beyond this shard's real count) are masked
            # out so neither random nor covering seeding surfaces them
            valid = jnp.arange(data.shape[1], dtype=jnp.int32) < count[0]
            seed_rows = rest[0][0] if has_seeds else None
            if has_filter:
                gm = rest[int(has_seeds)]
                valid = valid & jax.lax.dynamic_slice(gm, (base[0],),
                                                      (data.shape[1],))
            # gather engine explicitly: shard-local data lives only
            # inside this trace, so an edge-resident store can never be
            # attached
            d, i = cagra._search_jit(
                data[0], data[0], None, graph[0], qq, valid,
                jax.random.key(sp.seed), seed_rows, None, None, None,
                itopk, width, int(max_iter), k, n_seeds, mt.value)
            gi = jnp.where(i >= 0, i + base[0], -1)
            gi = jnp.where(okf[0, 0], gi, -1)   # dead-shard containment
            bad = jnp.inf if select_min else -jnp.inf
            d = jnp.where(gi >= 0, d, bad)
            return d, gi
        return local

    in_specs = [P(AXIS, None, None), P(AXIS, None, None), P(AXIS), P(AXIS),
                P(AXIS, None), P()]
    arrays = [index.data, index.graphs, index.bases, index.counts,
              _shard_mask(index.mesh, ok), q]
    q_pos = 5                          # q's slot, for donation
    if has_seeds:
        in_specs.append(P(AXIS, None))
        arrays.append(index.seeds)
    if has_filter:
        in_specs.append(P())           # replicated; sliced per shard
        arrays.append(mask)
    statics = (("itopk", itopk), ("w", width), ("it", int(max_iter)),
               ("ns", n_seeds), ("rs", sp.seed), ("sd", has_seeds),
               ("f", has_filter), ("mt", mt.name))
    d, i = _merged_shard_search(index, "cagra", make_local, in_specs,
                                arrays, q.shape[0], k, select_min, comms,
                                statics, merge_engine,
                                topology=getattr(index, "topology", None),
                                donate_q=q_pos if donate else None)
    return (d, i, ok) if allow_partial else (d, i)


class ShardedIvfPq:
    """Stacked per-shard IVF-PQ arrays, leading axis sharded over AXIS.

    The BASELINE north-star layout (sharded IVF-PQ over a worker mesh): one
    compressed index per shard row block, merged per-query at search time.
    """

    family = "ivf_pq"

    def __init__(self, mesh, codes, source_ids, centers_rot, codebooks,
                 rotations, offsets, sizes, n_total, metric, pq_bits,
                 codebook_kind, sizes_host):
        self.mesh = mesh
        self.codes = codes              # (p, R, pq_dim) u8, cluster-sorted
        self.source_ids = source_ids    # (p, R) GLOBAL ids, -1 pad
        self.centers_rot = centers_rot  # (p, L, rot_dim)
        self.codebooks = codebooks      # (p, ...) per-shard codebooks
        self.rotations = rotations      # (p, rot_dim, dim)
        self.offsets = offsets          # (p, L) i32
        self.sizes = sizes              # (p, L) i32
        self.n_total = n_total
        self.metric = metric
        self.pq_bits = pq_bits
        self.codebook_kind = codebook_kind
        self._sizes_host = sizes_host   # list of per-shard np size arrays
        self.shards_ok = np.ones(mesh.shape[AXIS], bool)
        self.last_probe: dict = {}
        _LIVE.add(self)

    def mark_shard_failed(self, i: int, ok: bool = False) -> None:
        """Flag shard ``i`` unhealthy (see ShardedIvfFlat.mark_shard_failed)."""
        _mark_shard(self.shards_ok, "ivf_pq", i, ok)

    def _canary_source(self):
        return self.centers_rot

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[AXIS]

    def max_rows(self, n_probes: int) -> int:
        # full-table bound when budget-tiered (see
        # ShardedIvfFlat.max_rows: tier steps must not move this static)
        tbl = getattr(self, "_rows_tbl_full", None)
        if tbl is None:
            tbl = self._sizes_host
        return int(max(ivf_pq._probe_budget(s, n_probes) for s in tbl))


def build_ivf_pq(dataset, mesh: Mesh,
                 params: ivf_pq.IndexParams | None = None) -> ShardedIvfPq:
    """Build one IVF-PQ index per contiguous shard row block (the raft-dask
    per-worker build of BASELINE config 5)."""
    expects(AXIS in mesh.shape, "mesh must have a %r axis", AXIS)
    p0 = params or ivf_pq.IndexParams()
    dataset = np.asarray(dataset, np.float32)
    n = len(dataset)
    p = mesh.shape[AXIS]
    parts = _split_rows(n, p)

    shards = [ivf_pq.build(dataset[rows], p0) for rows in parts]
    mt = shards[0].metric

    codes = _stack_pad([np.asarray(s.codes) for s in shards])
    gids = _stack_pad(
        [np.where(np.asarray(s.source_ids) >= 0,
                   np.asarray(s.source_ids) + parts[i][0], -1)
         for i, s in enumerate(shards)],
        pad_value=-1)
    centers = np.stack([np.asarray(s.centers_rot) for s in shards])
    books = np.stack([np.asarray(s.codebooks) for s in shards])
    rots = np.stack([np.asarray(s.rotation) for s in shards])
    offsets = np.stack([s.list_offsets[:-1] for s in shards]).astype(np.int32)
    sizes = np.stack([s.list_sizes for s in shards]).astype(np.int32)

    def put(x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    ndim_spec = lambda a: P(AXIS, *([None] * (a.ndim - 1)))
    return ShardedIvfPq(
        mesh, put(codes, ndim_spec(codes)), put(gids, ndim_spec(gids)),
        put(centers, ndim_spec(centers)), put(books, ndim_spec(books)),
        put(rots, ndim_spec(rots)), put(offsets, ndim_spec(offsets)),
        put(sizes, ndim_spec(sizes)), n, mt, shards[0].pq_bits,
        shards[0].codebook_kind, [s.list_sizes for s in shards])


def search_ivf_pq(index: ShardedIvfPq, queries, k: int,
                  params: ivf_pq.SearchParams | None = None,
                  res=None, allow_partial: bool = False,
                  merge_engine: str | None = None, filter=None,  # noqa: A002
                  donate: bool = False):
    """Replicated queries → per-shard LUT search → cross-shard merge
    (knn_merge_parts.cuh:172 role, ring or allgather engine).

    ``allow_partial``/``merge_engine``/``filter``/``donate``: contract
    of :func:`search_ivf_flat` (PQ shard source ids are global, so the
    replicated mask indexes directly).
    """
    sp = params or ivf_pq.SearchParams()
    q = jnp.asarray(queries, jnp.float32)
    n_probes = min(sp.n_probes, index.centers_rot.shape[1])
    max_rows = index.max_rows(n_probes)
    mt = index.metric
    select_min = is_min_close(mt)
    comms = _comms_of(index.mesh, res)
    ok = _shard_health(index, "ivf_pq")
    _health_gate(ok, allow_partial, "ivf_pq")
    # dummy host offsets: _search_chunk reads offsets/sizes from the traced
    # args, never from the Index (search() does, but we bypass it)
    dummy_off = np.zeros(index.centers_rot.shape[1] + 1, np.int64)

    mask = filter.to_mask() if filter is not None else None
    has_filter = mask is not None
    # queries per gather pass, by ivf_pq.search's per-query workspace
    # bound: the whole batch in one pass (candidates × pq_dim gather +
    # the per-probe LUTs) needs over 100 GB at 10k queries × 1M rows/shard
    pq_dim = index.codes.shape[2]
    per_q = (max_rows * pq_dim * 8
             + n_probes * pq_dim * (1 << index.pq_bits) * 4)
    chunk = max(1, workspace_chunk_bytes(res) // per_q)

    def make_local():
        def local(codes, gids, centers, books, rots, offsets, sizes, okf,
                  qq, *rest):
            mb = rest[0] if has_filter else None
            shard = ivf_pq.Index(
                codes[0], gids[0], centers[0], books[0], rots[0],
                dummy_off, mt, index.pq_bits, index.codebook_kind)

            def one(qc):
                return ivf_pq._search_chunk(shard, qc, k, n_probes,
                                            max_rows, offsets[0], sizes[0],
                                            mb, sp.lut_dtype)

            m = qq.shape[0]
            if m <= chunk:
                d, i = one(qq)
            else:
                nc = cdiv(m, chunk)
                qs = jnp.pad(qq, ((0, nc * chunk - m), (0, 0)))
                d, i = jax.lax.map(one, qs.reshape(nc, chunk, -1))
                d, i = d.reshape(-1, k)[:m], i.reshape(-1, k)[:m]
            i = jnp.where(okf[0, 0], i, -1)     # dead-shard containment
            bad = jnp.inf if select_min else -jnp.inf
            d = jnp.where(i >= 0, d, bad)       # padded rows carry id -1
            return d, i
        return local

    in_specs = [P(AXIS, None, None), P(AXIS, None), P(AXIS, None, None),
                P(AXIS, *([None] * (index.codebooks.ndim - 1))),
                P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                P(AXIS, None), P()]
    arrays = [index.codes, index.source_ids, index.centers_rot,
              index.codebooks, index.rotations, index.offsets,
              index.sizes, _shard_mask(index.mesh, ok), q]
    q_pos = 8                          # q's slot, for donation
    if has_filter:
        in_specs.append(P())           # replicated: gids are global
        arrays.append(mask)
    statics = (("np", n_probes), ("mr", max_rows), ("mt", mt.name),
               ("lut", np.dtype(sp.lut_dtype).name), ("f", has_filter),
               ("b", index.pq_bits), ("qc", chunk),
               ("ck", getattr(index.codebook_kind, "name",
                              index.codebook_kind)))
    d, i = _merged_shard_search(index, "ivf_pq", make_local, in_specs,
                                arrays, q.shape[0], k, select_min, comms,
                                statics, merge_engine,
                                topology=getattr(index, "topology", None),
                                donate_q=q_pos if donate else None)
    return (d, i, ok) if allow_partial else (d, i)


def make_searcher(index, params=None, *, allow_partial: bool = False,
                  donate: bool = False, **opts):
    """Stable batchable signature for the serving runtime
    (:mod:`raft_tpu.serve`), dispatching on the sharded index type:
    returns ``fn(queries, k, res=None) -> (distances, indices)`` — or,
    with ``allow_partial=True``, ``(distances, indices, shards_ok)`` so
    the batcher can serve degraded answers through dead shards and
    surface the loss in its metrics and per-request responses.

    The closure hits the index's compiled-program cache: after a
    :func:`~raft_tpu.serve.warmup.warmup` sweep (or one cold call per
    shape bucket), repeat dispatches compile nothing. ``donate=True``
    donates the replicated query buffer to the cached program (the
    batcher's double-buffered closures pass freshly-built batches that
    are never reused); leave False when callers keep their query
    arrays — see docs/perf.md "Sharded dispatch" donation caveats."""
    fns = {ShardedIvfFlat: search_ivf_flat,
           ShardedCagra: search_cagra,
           ShardedIvfPq: search_ivf_pq}
    fn = fns.get(type(index))
    expects(fn is not None, "unsupported sharded index type %s",
            type(index).__name__)

    def _fn(queries, k, res=None):
        return fn(index, queries, k, params, res=res,
                  allow_partial=allow_partial, donate=donate, **opts)

    return _fn


def searcher_dim(index) -> int:
    """Query dimensionality a sharded/fleet index expects — what a
    warmup sweep should size its dummy batches to."""
    if hasattr(index, "logical_dim"):          # ShardedIvfFlat
        return int(index.logical_dim)
    if hasattr(index, "rotations"):            # ShardedIvfPq
        return int(index.rotations.shape[-1])
    if hasattr(index, "dataset"):              # sharded_knn.ShardedIndex
        return int(index.dataset.shape[1])
    return int(index.data.shape[-1])           # ShardedCagra


def widen_rungs(index, n_probes: int | None = None) -> list:
    """Every effective ``n_probes`` the degradation auto-widen
    (``fleet._effective_nprobe``) can reach from ``n_probes`` on this
    index — the ladder a warmup sweep must pre-compile so a host loss
    lands on an already-cached executable instead of a fresh trace.

    Loss granularity follows the index: host-granular when a multi-host
    topology is adopted (a DCN partition takes whole hosts), shard-
    granular otherwise. Survivor subsets are enumerated exactly up to
    10 units (handles row skew); larger fleets warm the uniform
    ``j/u`` fractions. CAGRA has no probe ladder — returns ``[]``."""
    from . import fleet as _fleet    # lazy: fleet imports this module

    if isinstance(index, ShardedCagra):
        return []
    centers = (index.centers if isinstance(index, ShardedIvfFlat)
               else index.centers_rot)
    n_lists = int(centers.shape[1])
    if n_probes is None:
        n_probes = (ivf_flat.SearchParams().n_probes
                    if isinstance(index, ShardedIvfFlat)
                    else ivf_pq.SearchParams().n_probes)
    npb = min(int(n_probes), n_lists)
    h = health(index)
    rows = np.asarray(h["shard_rows"], np.int64)
    total = max(int(h["n_total"]), 1)
    topo = getattr(index, "topology", None)
    if topo is not None and getattr(topo, "n_hosts", 1) > 1:
        dph = int(topo.devs_per_host)
        units = [int(rows[i * dph:(i + 1) * dph].sum())
                 for i in range(int(topo.n_hosts))]
    else:
        units = [int(r) for r in rows]
    u = len(units)
    fracs = set()
    if u <= 10:
        for bits in range(1, 2 ** u):    # every non-empty survivor set
            served = sum(r for j, r in enumerate(units) if bits >> j & 1)
            fracs.add(served / total)
    else:
        fracs.update(j / u for j in range(1, u + 1))
    rungs = {npb}
    for f in fracs:
        rungs.add(_fleet._effective_nprobe(npb, f, n_lists))
    return sorted(rungs)


def warmup_searchers(index, params=None, **opts) -> dict:
    """``{rung_name: closure}`` mapping for
    :func:`raft_tpu.serve.warmup.warmup`'s ``engines=`` sweep: the base
    params plus one cache-hitting closure per :func:`widen_rungs` rung,
    so the warmup pass pre-compiles the whole degraded ``n_probes``
    ladder. Each closure searches with ``n_probes`` REPLACED by the
    rung value — exactly the params the fleet's auto-widen will
    produce, so a later host loss lands on the warmed key. (The health
    mask itself is a traced argument: no rung needs a dead shard to
    compile.) Budget-tiered fleet indexes should warm through
    :meth:`~raft_tpu.parallel.fleet.Fleet.warmup_searchers` instead,
    which also drives the cold-list merge."""
    import dataclasses

    engs = {"base": make_searcher(index, params, **opts)}
    if isinstance(index, ShardedCagra):
        return engs
    sp = params or (ivf_flat.SearchParams()
                    if isinstance(index, ShardedIvfFlat)
                    else ivf_pq.SearchParams())
    centers = (index.centers if isinstance(index, ShardedIvfFlat)
               else index.centers_rot)
    base_np = min(int(sp.n_probes), int(centers.shape[1]))
    for eff in widen_rungs(index, sp.n_probes):
        if eff == base_np:
            continue                   # already covered by "base"
        engs[f"np{eff}"] = make_searcher(
            index, dataclasses.replace(sp, n_probes=eff), **opts)
    return engs
