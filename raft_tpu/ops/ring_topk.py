"""Ring-allreduce-top-k: the device-resident merge engine for sharded
search (SURVEY layer 2 ``comms_t``: the collective under every
distributed algorithm).

The allgather merge path materializes every shard's full candidate set
on every device — a (p, m, k) buffer per query batch — and then runs a
``select_k`` over the p·k-wide concatenation (knn_merge_parts.cuh:172).
This module replaces that with a ring: each shard keeps its local
(m, k) candidates resident, streams a block to its right neighbor at
each of the p−1 hops, and folds the arriving block into a running top-k
— so the live footprint stays O(k) per query and the merge work per hop
is a 2k-wide fold instead of one p·k-wide select.

Why the result is BIT-IDENTICAL (order included) to ``knn_merge_parts``:
``select_k``'s tie contract is lowest-column-first (lax.top_k
semantics; the KPASS kernel matches it by construction), so the merged
answer is exactly "the k best candidates of the (m, p·k) shard-ordered
concatenation under the total order (±distance, column position)".
Each candidate's column position is derivable — shard s's slot j sits
at column s·k + j — and top-k under a *total* order is associative, so
an incremental ring fold that carries (distance, gid) and re-derives
the position of each arriving block from its origin shard produces the
same k entries in the same order on every shard, dead-shard
(+inf, −1) sentinel rows included (they are ordinary candidates that
lose every comparison against a survivor, exactly as they do inside the
allgather's ``select_k``).

Three engines, one contract:

* ``allgather`` — the existing path, verbatim (``comms.allgather`` +
  ``knn_merge_parts``): the rehearsed fallback and the bit-identity
  reference.
* ``ring`` — the hop/mask logic in plain XLA: ``device_sendrecv``
  (a ``ppermute`` ring shift) store-and-forward with a
  (key, position)-lexicographic 2k-wide fold per hop. Runs on any
  backend — tier-1 asserts it bit-identical to ``knn_merge_parts`` on
  the 8-device virtual CPU mesh.
* ``ring_pallas`` — the TPU kernel: candidates live in VMEM,
  ``pltpu.make_async_remote_copy`` streams blocks over ICI with
  double-buffered slots, a remote credit semaphore gates slot reuse,
  and the same lexicographic fold runs in-VMEM at each hop. Zero HBM
  round trip for the gathered buffer, zero host sync.

A fourth, TOPOLOGY-AWARE composition sits above the three flat
engines: ``hier`` (multi-host fleets, :mod:`raft_tpu.parallel.topology`)
runs the ring within each host's ICI clique (grouped collectives over
``host_groups()`` — the flat ring engine verbatim, just on a subgroup),
then folds the per-host winner blocks across DCN with one grouped
allgather + lexicographic select. Each device moves ``(H−1)·m·k``
candidate cells over DCN instead of the flat allgather's ``(H−1)·D·m·k``
— a reduction factor of exactly ``devs_per_host``. Bit-identity to the
flat merge holds by a surrogate-position argument: a global top-k
member is always inside its own host's top-k (stage 1 keeps it), stage
1's stable sort emits each host block in ascending global-position
order, and host blocks occupy disjoint ascending global-position ranges
— so ranking stage-2 candidates by (±distance, host-block position
``h·k + j``) induces the same total order as (±distance, global concat
position), dead-shard (+inf, −1) sentinels included.

Engine resolution (``resolve_engine``) prefers a measured autotune
verdict (``tune_merge`` races the engines under a dtype/mesh-aware
key), then ``RAFT_TPU_SHARDED_MERGE``, then a backend default: the ring
kernel on TPU (VMEM budget permitting), allgather elsewhere. A
multi-host topology adds a tier ABOVE the autotune bucket — ``hier``
by default (the buckets were measured on single-host meshes and say
nothing about DCN) — while single-host meshes take the pre-existing
path byte-for-byte. Callers gate every non-allgather engine behind
``guarded_call("sharded.ring_topk")`` so a compile/execution failure on
an unrehearsed shape demotes to the bit-identical allgather path
instead of failing the query.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.errors import expects

__all__ = ["merge", "merge_step", "resolve_engine", "tune_merge",
           "ring_capable", "ENGINES", "ALL_ENGINES", "MERGE_SITE",
           "per_hop_bytes", "gathered_bytes", "active_engines",
           "note_engine", "note_fallback", "guarded_dispatch"]

ENGINES = ("allgather", "ring", "ring_pallas")
# the flat engines plus the topology-aware multi-host composition;
# "hier" needs a Topology at merge() time, so it lives outside ENGINES
# (the flat autotune/race vocabulary) but inside the dispatch contract
ALL_ENGINES = ENGINES + ("hier",)

# the guarded site every ring-engine dispatch runs under (ops/guarded.py):
# a ring compile/execution failure demotes to the allgather program
MERGE_SITE = "sharded.ring_topk"

_INT_BIG = 2 ** 30
# VMEM budget for the full-residency ring kernel: running state (3
# planes) + double-buffered comm slots (2×2 planes) + in/out (4 planes)
# + fold temporaries. Compiled for v5e (tests/test_tpu_compile.py):
# (512, 128) cells fit its 16 MB scoped VMEM, (1024, 128) do not
_VMEM_CELL_CAP = 64 * 1024


# --------------------------------------------------------------------------
# traffic accounting (the bench decomposition's ICI math)
# --------------------------------------------------------------------------

def per_hop_bytes(m: int, k: int) -> int:
    """Bytes one shard moves over ICI per ring hop: an (m, k) f32
    distance block + an (m, k) i32 id block."""
    return m * k * (4 + 4)


def gathered_bytes(m: int, k: int, p: int) -> int:
    """Bytes of the (p, m, k) candidate buffer every device materializes
    under the allgather merge (distances + ids)."""
    return p * m * k * (4 + 4)


# --------------------------------------------------------------------------
# the (key, position)-lexicographic fold — shared by every ring engine
# --------------------------------------------------------------------------

def _lex_topk(kd: jax.Array, pos: jax.Array, gid: jax.Array, dd: jax.Array,
              k: int) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-k of (..., w) candidates under the total order
    (key asc, position asc), carrying the untransformed distance and the
    global id. ``lax.sort`` with two key operands is exactly this order."""
    kd2, pos2, gid2, dd2 = lax.sort((kd, pos, gid, dd), dimension=-1,
                                    is_stable=True, num_keys=2)
    return kd2[..., :k], pos2[..., :k], gid2[..., :k], dd2[..., :k]


def _fold(state, blk, k: int):
    """One ring fold: merge the arriving block into the running top-k."""
    cat = tuple(jnp.concatenate([a, b], axis=-1)
                for a, b in zip(state, blk))
    return _lex_topk(*cat, k)


def merge_step(run_d, run_pos, run_gid, blk_d, blk_pos, blk_gid, k: int,
               select_min: bool = True, engine: str = "xla",
               interpret: Optional[bool] = None):
    """One hop's in-VMEM merge, standalone: fold an arriving (m, w2)
    candidate block into a running (m, w1) top-k under the
    (±distance, position) total order. Returns (d, pos, gid) each
    (m, k), best-first.

    ``engine="xla"``: the ``lax.sort`` fold (the hop logic the XLA ring
    uses). ``engine="pallas"``: the VMEM fold kernel the TPU ring kernel
    runs per hop — ``interpret=True`` exercises it off-TPU (the tier-1
    kernel-parity test)."""
    expects(engine in ("xla", "pallas"),
            "unknown merge_step engine %r (one of 'xla', 'pallas')", engine)
    kd_r = run_d if select_min else -run_d
    kd_b = blk_d if select_min else -blk_d
    if engine == "pallas":
        kd, pos, gid = _merge_step_pallas(
            kd_r, run_pos, run_gid, kd_b, blk_pos, blk_gid, k,
            jax.default_backend() != "tpu" if interpret is None
            else interpret)
    else:
        kd, pos, gid, _ = _fold(
            (kd_r, run_pos, run_gid, run_d),
            (kd_b, blk_pos, blk_gid, blk_d), k)
    return (kd if select_min else -kd), pos, gid


# --------------------------------------------------------------------------
# XLA ring engine (the hop/mask logic; every backend)
# --------------------------------------------------------------------------

def _ring_xla(d, gid, k: int, select_min: bool, comms):
    """Store-and-forward ring merge in plain XLA, called per shard
    inside ``shard_map``. p−1 ``device_sendrecv`` hops (the ppermute
    ring), O(k) traffic per hop, (key, pos)-lex fold on arrival."""
    p = comms.get_size()
    rank = comms.get_rank()
    m = d.shape[0]
    kd = d if select_min else -d
    slot = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (m, k))
    state = (kd, rank.astype(jnp.int32) * k + slot, gid, d)
    send_kd, send_gid = kd, gid
    for h in range(p - 1):
        recv_kd = comms.device_sendrecv(send_kd, 1)
        recv_gid = comms.device_sendrecv(send_gid, 1)
        src = jnp.mod(rank - (h + 1), p).astype(jnp.int32)
        blk = (recv_kd, src * k + slot, recv_gid,
               recv_kd if select_min else -recv_kd)
        state = _fold(state, blk, k)
        send_kd, send_gid = recv_kd, recv_gid
    return state[3], state[2]


# --------------------------------------------------------------------------
# hierarchical ICI/DCN engine (multi-host fleets)
# --------------------------------------------------------------------------

def _hier(d, gid, k: int, select_min: bool, axis: str, topology):
    """Two-stage topology-aware merge, called per shard inside
    ``shard_map`` over a host-major fleet mesh.

    Stage 1 (ICI): the flat XLA ring, unchanged, over this host's
    ``host_groups()`` clique — within-group ranks make the stamped
    positions host-LOCAL (``l·k + slot``), which stage 2 relies on.
    Stage 2 (DCN): grouped allgather over ``cross_groups()`` (one peer
    per host, group rows in host order) → an (H, m, k) winner stack →
    one (±distance, host-block position) lexicographic select over the
    ``H·k``-wide concatenation. Surrogate positions ``h·k + j`` induce
    the flat merge's global-position order (module docstring), so the
    output is bit-identical to every flat engine, replica-identical on
    all p shards. D == 1 degenerates to the pure DCN fold; H == 1 is
    rejected by resolve_engine (single-host meshes never route here).
    """
    from ..comms import AxisComms

    H, D = topology.n_hosts, topology.devs_per_host
    p = topology.n_shards
    if D > 1:
        ici = AxisComms(axis, size=p, groups=topology.host_groups())
        hd, hg = _ring_xla(d, gid, k, select_min, ici)
    else:
        hd, hg = d, gid
    dcn = AxisComms(axis, size=p, groups=topology.cross_groups())
    all_d = dcn.allgather(hd)                      # (H, m, k), host order
    all_g = dcn.allgather(hg)
    m = d.shape[0]
    dd = jnp.transpose(all_d, (1, 0, 2)).reshape(m, H * k)
    gg = jnp.transpose(all_g, (1, 0, 2)).reshape(m, H * k)
    kd = dd if select_min else -dd
    pos = jnp.broadcast_to(jnp.arange(H * k, dtype=jnp.int32), (m, H * k))
    _, _, gid2, dd2 = _lex_topk(kd, pos, gg, dd, k)
    return dd2, gid2


# --------------------------------------------------------------------------
# Pallas ring kernel (TPU): VMEM-resident candidates, remote DMA hops
# --------------------------------------------------------------------------

def _vmem_fold(cd, cp, cg, k: int, kp: int, extra=()):
    """The in-kernel fold: k (min-value, then min-position) extraction
    passes over a (m, w) candidate plane — the KPASS pattern with an
    explicit position plane as the tie key, so ties retire in the same
    lowest-column order ``select_k`` uses. Mosaic has no sort, so the
    ``lax.sort`` fold is re-expressed as masked min-reductions.

    ``extra``: optional int32 payload planes (same (m, w) shape) carried
    through the fold — each output slot gets the payload of the cell it
    extracted (the CAGRA megakernel rides its explored flags here).
    Returns ``(d, pos, gid, *extras)``."""
    m = cd.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (m, kp), 1)

    def extract(t, state):
        alive, nd, npos, ng = state[:4]
        nex = state[4:]
        live = alive != 0
        masked = jnp.where(live, cd, jnp.inf)
        best = jnp.min(masked, axis=1, keepdims=True)
        cand = live & (masked <= best)
        bpos = jnp.min(jnp.where(cand, cp, _INT_BIG), axis=1, keepdims=True)
        at = cand & (cp == bpos)
        # position uniqueness makes `at` single-cell among real
        # candidates, so a min-select extracts its gid; the sentinel must
        # exceed any legal global id (+inf pads share pos and select
        # their -1 gid together — the pad convention either way)
        g = jnp.min(jnp.where(at, cg, jnp.iinfo(jnp.int32).max), axis=1,
                    keepdims=True)
        hit = lane == t
        exs = tuple(
            jnp.where(hit,
                      jnp.min(jnp.where(at, ce, jnp.iinfo(jnp.int32).max),
                              axis=1, keepdims=True), ne)
            for ce, ne in zip(extra, nex))
        return (jnp.where(at, 0, alive), jnp.where(hit, best, nd),
                jnp.where(hit, bpos, npos), jnp.where(hit, g, ng)) + exs

    # alive rides as int32: Mosaic cannot legalize an scf.for (the
    # k > 32 fori_loop) carrying an i1 vector
    state = (jnp.ones(cd.shape, jnp.int32),
             jnp.full((m, kp), jnp.inf, jnp.float32),
             jnp.full((m, kp), _INT_BIG, jnp.int32),
             jnp.full((m, kp), -1, jnp.int32))
    state = state + tuple(jnp.zeros((m, kp), jnp.int32) for _ in extra)
    if k <= 32:
        for t in range(k):
            state = extract(t, state)
    else:
        state = lax.fori_loop(0, k, extract, state)
    return (state[1], state[2], state[3]) + tuple(state[4:])


def _merge_step_kernel(rd_ref, rp_ref, rg_ref, bd_ref, bp_ref, bg_ref,
                      od_ref, op_ref, og_ref, *, k: int, kp: int):
    cd = jnp.concatenate([rd_ref[...], bd_ref[...]], axis=1)
    cp = jnp.concatenate([rp_ref[...], bp_ref[...]], axis=1)
    cg = jnp.concatenate([rg_ref[...], bg_ref[...]], axis=1)
    od_ref[...], op_ref[...], og_ref[...] = _vmem_fold(cd, cp, cg, k, kp)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _merge_step_pallas(rd, rp, rg, bd, bp, bg, k: int, interpret: bool):
    """Standalone pallas_call around the VMEM fold (the unit the
    interpret-mode tier-1 test pins against the XLA fold)."""
    from jax.experimental import pallas as pl

    from ..utils import round_up_to

    m, w1 = rd.shape
    w2 = bd.shape[1]
    mp = round_up_to(m, 8)
    kp = round_up_to(k, 128)

    def pad(x, fill):
        return jnp.pad(x, ((0, mp - m), (0, 0)), constant_values=fill)

    args = [pad(rd.astype(jnp.float32), jnp.inf),
            pad(rp, _INT_BIG), pad(rg, -1),
            pad(bd.astype(jnp.float32), jnp.inf),
            pad(bp, _INT_BIG), pad(bg, -1)]
    out = pl.pallas_call(
        functools.partial(_merge_step_kernel, k=k, kp=kp),
        out_shape=[jax.ShapeDtypeStruct((mp, kp), jnp.float32),
                   jax.ShapeDtypeStruct((mp, kp), jnp.int32),
                   jax.ShapeDtypeStruct((mp, kp), jnp.int32)],
        interpret=interpret,
    )(*args)
    return tuple(o[:m, :k] for o in out)


def _ring_kernel(d_ref, g_ref, od_ref, og_ref, comm_d, comm_g, run_d,
                 run_p, run_g, send_sems, recv_sems, capacity_sem, *,
                 axis: str, p: int, k: int, kp: int):
    """The device-resident ring: one kernel instance per shard under
    ``shard_map``; p−1 double-buffered remote-DMA hops with the VMEM
    fold on arrival.

    Slot discipline (the semaphore-signalled double buffering): hop h
    writes the right neighbor's slot h%2; a slot written at hop h is
    consumed locally by the hop-h fold and re-read as the hop-(h+1)
    forward source, so it is free for the writer's hop-(h+2) reuse only
    after the hop-(h+1) send completes — at which point this shard
    signals one credit to its LEFT neighbor (the writer), and every
    send from hop 2 on first waits one credit. The opening barrier
    keeps a fast neighbor from writing before this kernel is live."""
    from jax.experimental.pallas import tpu as pltpu

    my_id = lax.axis_index(axis)
    right = lax.rem(my_id + 1, p)
    left = lax.rem(my_id + p - 1, p)

    barrier = pltpu.get_barrier_semaphore()
    for nb in (left, right):
        pltpu.semaphore_signal(barrier, inc=1, device_id=(nb,),
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)

    m = d_ref.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (m, kp), 1)
    # local block, position-stamped: shard s's slot j is concat column
    # s·k + j; kp-pad lanes carry (+inf, INT_BIG, -1) so they lose every
    # comparison (they are exactly the allgather pad convention)
    run_d[...] = d_ref[...]
    run_p[...] = jnp.where(lane < k, my_id.astype(jnp.int32) * k + lane,
                           _INT_BIG)
    run_g[...] = g_ref[...]

    for h in range(p - 1):
        slot = h % 2
        if h >= 2:
            pltpu.semaphore_wait(capacity_sem, 1)
        src_d = d_ref if h == 0 else comm_d.at[(h - 1) % 2]
        src_g = g_ref if h == 0 else comm_g.at[(h - 1) % 2]
        rdma_d = pltpu.make_async_remote_copy(
            src_ref=src_d, dst_ref=comm_d.at[slot],
            send_sem=send_sems.at[0], recv_sem=recv_sems.at[0],
            device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)
        rdma_g = pltpu.make_async_remote_copy(
            src_ref=src_g, dst_ref=comm_g.at[slot],
            send_sem=send_sems.at[1], recv_sem=recv_sems.at[1],
            device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)
        rdma_d.start()
        rdma_g.start()
        rdma_d.wait()        # send read done AND this hop's block landed
        rdma_g.wait()
        if h >= 1:
            # the hop-(h−1) slot is now fully consumed (folded at h−1,
            # forwarded just above): credit its writer
            pltpu.semaphore_signal(capacity_sem, inc=1, device_id=(left,),
                                   device_id_type=pltpu.DeviceIdType.MESH)
        src = lax.rem(my_id - (h + 1) + p * (h + 1), p).astype(jnp.int32)
        blk_p = jnp.where(lane < k, src * k + lane, _INT_BIG)
        nd, npos, ng = _vmem_fold(
            jnp.concatenate([run_d[...], comm_d[slot]], axis=1),
            jnp.concatenate([run_p[...], blk_p], axis=1),
            jnp.concatenate([run_g[...], comm_g[slot]], axis=1), k, kp)
        run_d[...], run_p[...], run_g[...] = nd, npos, ng

    od_ref[...] = run_d[...]
    og_ref[...] = run_g[...]


def _ring_pallas(d, gid, k: int, select_min: bool, axis: str, p: int):
    """The TPU ring engine, called per shard inside ``shard_map``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..utils import round_up_to

    m = d.shape[0]
    mp = round_up_to(max(m, 1), 8)
    kp = round_up_to(k, 128)
    kd = d.astype(jnp.float32) if select_min else -d.astype(jnp.float32)
    kd = jnp.pad(kd, ((0, mp - m), (0, kp - k)), constant_values=jnp.inf)
    g = jnp.pad(gid, ((0, mp - m), (0, kp - k)), constant_values=-1)

    out_d, out_g = pl.pallas_call(
        functools.partial(_ring_kernel, axis=axis, p=p, k=k, kp=kp),
        out_shape=[jax.ShapeDtypeStruct((mp, kp), jnp.float32),
                   jax.ShapeDtypeStruct((mp, kp), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((2, mp, kp), jnp.float32),   # comm slots: distances
            pltpu.VMEM((2, mp, kp), jnp.int32),     # comm slots: ids
            pltpu.VMEM((mp, kp), jnp.float32),      # running top-k: key
            pltpu.VMEM((mp, kp), jnp.int32),        # running top-k: position
            pltpu.VMEM((mp, kp), jnp.int32),        # running top-k: gid
            pltpu.SemaphoreType.DMA((2,)),          # send sems (d, gid)
            pltpu.SemaphoreType.DMA((2,)),          # recv sems (d, gid)
            pltpu.SemaphoreType.REGULAR,            # slot-free credits
        ],
        compiler_params=pltpu.CompilerParams(collective_id=7),
    )(kd, g)
    out_d = out_d[:m, :k]
    return (out_d if select_min else -out_d), out_g[:m, :k]


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def merge(d: jax.Array, gid: jax.Array, k: int, select_min: bool,
          comms=None, axis: str = "shard", axis_size: Optional[int] = None,
          engine: str = "allgather", topology=None
          ) -> Tuple[jax.Array, jax.Array]:
    """Cross-shard top-k merge, called per shard INSIDE ``shard_map``.

    ``d``/``gid``: this shard's (m, k) local candidates — distances and
    GLOBAL row ids, dead-shard rows already masked to (±inf, −1).
    Returns the replica-identical merged (m, k) lists, bit-identical
    across engines (module docstring). ``comms``: an
    :class:`~raft_tpu.comms.AxisComms`-shaped communicator; built over
    ``axis``/``axis_size`` when absent. ``ring_pallas`` ignores comms
    subgroups and requires a plain 1-D mesh axis. ``engine="hier"``
    requires ``topology`` (a host-major
    :class:`~raft_tpu.parallel.topology.Topology` matching the mesh
    axis) and builds its own grouped communicators from it."""
    from ..comms import AxisComms

    expects(engine in ALL_ENGINES, "unknown sharded merge engine %r", engine)
    if engine == "hier":
        expects(topology is not None,
                "engine='hier' needs a topology (parallel.topology)")
        expects(axis_size is None or int(axis_size) == topology.n_shards,
                "hier merge: axis_size %s != topology shards %d",
                axis_size, topology.n_shards)
        return _hier(d, gid, k, select_min, axis, topology)
    if comms is None:
        expects(axis_size is not None,
                "merge needs a comms object or an explicit axis_size")
        comms = AxisComms(axis, size=axis_size)
    if engine == "ring":
        return _ring_xla(d, gid, k, select_min, comms)
    if engine == "ring_pallas":
        p = axis_size if axis_size is not None else comms.get_size()
        return _ring_pallas(d, gid, k, select_min, axis, int(p))
    from ..neighbors import brute_force

    all_d = comms.allgather(d)
    all_i = comms.allgather(gid)
    return brute_force.knn_merge_parts(all_d, all_i, select_min)


# family -> merge engine that ACTUALLY served the most recent sharded
# search in this process (fallbacks overwrite the resolved engine), the
# ops surface debugz reads through sharded_ann.ops_snapshot
active_engines: dict = {}


def note_engine(family: str, engine: str) -> None:
    active_engines[family] = engine


def note_fallback(family: str) -> None:
    """A ring-engine call was served by the allgather fallback (guarded
    demotion or injected fault): record it for the ops surface."""
    active_engines[family] = "allgather"
    try:
        from ..serve import metrics as _metrics

        _metrics.counter("sharded.ring.demotions").inc()
    except Exception:  # noqa: BLE001 - telemetry must not fail a search
        pass


def guarded_dispatch(family: str, engine: str, run):
    """THE dispatch contract for every sharded merge caller
    (sharded_ann's chokepoint and sharded_knn.search): record the
    engine for the ops surface, run ``run(engine)``, and gate ring
    engines behind ``guarded_call(MERGE_SITE)`` with the bit-identical
    allgather program — fallback serves reported via
    :func:`note_fallback`. ``run``: engine name → merged results
    (typically dispatching a freshly built ``shard_map`` program)."""
    note_engine(family, engine)
    if engine == "allgather":
        return run("allgather")
    from .guarded import guarded_call

    def fallback():
        note_fallback(family)
        return run("allgather")

    return guarded_call(MERGE_SITE, lambda: run(engine), fallback)


def _mesh_device(mesh_or_device):
    """First device of the SEARCH mesh — engine capability and autotune
    keys must follow the mesh actually searched, not the process default
    backend (a CPU emulation mesh on a TPU host must not resolve to the
    TPU-only remote-DMA kernel, and its measurements must not steer TPU
    buckets)."""
    if mesh_or_device is None:
        return jax.devices()[0]
    devs = getattr(mesh_or_device, "devices", None)
    return devs.flat[0] if devs is not None else mesh_or_device


# the Pallas ring kernel halted a v5e 2x2 on its first call ("Semaphore
# (scratch argument 7) has a nonzero value upon exit from a Mosaic
# kernel", PR 21 chip run): no default, race or recorded verdict picks
# it, and an explicit ask on a TPU mesh raises
_RING_PALLAS_HALTS_TPU = ("the ring_pallas merge halts a TPU v5e 2x2 "
                          "(a DMA semaphore left nonzero at kernel exit); "
                          "use merge_engine='allgather' or 'ring'")


def ring_capable(m: int, k: int, backend: Optional[str] = None) -> bool:
    """Whether the Pallas ring kernel can run this shape: a real TPU
    (remote DMA has no interpret emulation on this jax) and the
    full-residency VMEM budget. ``backend``: the SEARCH mesh's platform
    (defaults to the process backend)."""
    from ..utils import round_up_to

    backend = backend or jax.default_backend()
    cells = round_up_to(max(m, 1), 8) * round_up_to(k, 128)
    return backend == "tpu" and cells <= _VMEM_CELL_CAP


def _bucket(m: int, k: int, p: int, dtype, mesh=None) -> str:
    from . import autotune

    dev = _mesh_device(mesh)
    kind = getattr(dev, "device_kind", dev.platform).replace(" ", "_")
    return autotune.shape_bucket("sharded_merge", m=m, k=k, p=p,
                                 dt=str(jnp.dtype(dtype)),
                                 mesh=f"{dev.platform}-{kind}")


def resolve_engine(m: int, k: int, p: int, dtype=jnp.float32,
                   override: Optional[str] = None,
                   plain_axis: bool = True, mesh=None,
                   topology=None) -> str:
    """Pick the merge engine for one sharded search call.

    Order: explicit ``override`` (search param) → ``RAFT_TPU_SHARDED_MERGE``
    env → the measured autotune verdict for this (m, k, p, dtype) bucket
    (mesh-aware: the bucket key carries the SEARCH mesh's platform/kind
    and p) → backend default (the ring kernel when the mesh is TPU and
    the shape fits VMEM, allgather elsewhere — the CPU emulation mesh
    serializes ring hops, so allgather stays its default).
    ``plain_axis=False`` (an injected communicator with subgroups)
    forces allgather: the ring engines permute over the raw mesh axis.
    ``mesh``: the mesh (or a device) the search runs on; defaults to the
    process default device.

    ``topology``: a :class:`~raft_tpu.parallel.topology.Topology` when
    the mesh spans hosts. A MULTI-host topology adds a tier above the
    autotune bucket: override/env still win (``ring_pallas`` demotes to
    ``hier`` — remote-DMA ring hops must not cross DCN), otherwise
    ``hier`` — flat-bucket verdicts were measured within one host and
    say nothing about DCN cost. ``topology=None`` or a single-host
    topology leaves this function's pre-existing behavior untouched
    (the byte-for-byte single-host guarantee)."""
    platform = _mesh_device(mesh).platform
    if not plain_axis or p <= 1:
        return "allgather"
    if topology is not None and topology.multi_host:
        expects(p == topology.n_shards,
                "resolve_engine: p=%d != topology shards %d", p,
                topology.n_shards)
        eng = override or os.environ.get("RAFT_TPU_SHARDED_MERGE") or None
        if eng is not None:
            eng = str(eng).lower()
            expects(eng in ALL_ENGINES + ("auto",),
                    "unknown sharded merge engine %r (env/param); one of %s",
                    eng, ALL_ENGINES + ("auto",))
            if eng == "ring_pallas":
                return "hier"
            if eng != "auto":
                return eng
        return "hier"
    eng = override or os.environ.get("RAFT_TPU_SHARDED_MERGE") or None
    if eng is not None:
        eng = str(eng).lower()
        expects(eng in ENGINES + ("auto",),
                "unknown sharded merge engine %r (env/param); one of %s",
                eng, ENGINES + ("auto",))
        if eng != "auto":
            expects(eng != "ring_pallas" or platform != "tpu",
                    _RING_PALLAS_HALTS_TPU)
            if eng == "ring_pallas" and not ring_capable(m, k, platform):
                return "ring"
            return eng
    from . import autotune

    hit = autotune.lookup(_bucket(m, k, p, dtype, mesh))
    if hit in ENGINES and hit != "ring_pallas":
        return hit
    return "allgather"


def tune_merge(mesh, m: int, k: int, select_min: bool = True,
               axis: str = "shard", reps: int = 5, engines=None):
    """Race the merge engines on this mesh for a (m, k) candidate shape
    and record the winner under the dtype/mesh-aware bucket — the
    decision ``resolve_engine`` (and through it every
    ``make_searcher`` sharded closure) picks up. Returns
    (winner, {engine: median_s}). Eager only."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..utils import shard_map_compat
    from . import autotune

    p = mesh.shape[axis]
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.standard_normal((p, m, k)).astype(np.float32))
    d = jnp.sort(d, axis=-1) if select_min else -jnp.sort(d, axis=-1)
    gid = jnp.arange(p * m * k, dtype=jnp.int32).reshape(p, m, k)
    dd = jax.device_put(d, NamedSharding(mesh, P(axis, None, None)))
    gg = jax.device_put(gid, NamedSharding(mesh, P(axis, None, None)))

    names = engines or [e for e in ENGINES if e != "ring_pallas"]

    def make(eng):
        def body(ds, gs):
            return merge(ds[0], gs[0], k, select_min, axis=axis,
                         axis_size=p, engine=eng)
        return jax.jit(shard_map_compat(
            body, mesh=mesh, in_specs=(P(axis, None, None),) * 2,
            out_specs=(P(), P()), check=False))

    cands = {eng: make(eng) for eng in names}
    return autotune.tune_best(_bucket(m, k, p, jnp.float32, mesh), cands,
                              dd, gg, reps=reps, force=True)
