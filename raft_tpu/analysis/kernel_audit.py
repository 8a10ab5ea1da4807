"""Pass 1: the Pallas kernel registry and its jaxpr-structural audits.

Every ``pl.pallas_call`` site in the library is registered here with one
or more *variants* — representative (storage dtype, mode, shape)
configurations traced through :func:`jax.make_jaxpr`. Tracing is
abstract evaluation: no compile, no device, CPU-cheap — but the traced
``pallas_call`` equation exposes exactly the structure Mosaic will see
(block mappings with memory spaces, scratch avals, the kernel jaxpr),
so the checks run against the real program, not a hand-maintained
shadow spec. An unregistered new kernel fails the registry drift guard
in ``tests/test_analysis.py``.

Checks per traced site (rules; docs/analysis.md has the incident log):

* ``vmem-budget`` — VMEM footprint derived from the VMEM block mappings
  (×2: the grid pipeline double-buffers streamed blocks) plus VMEM
  scratch, against the tightest per-generation budget × an occupancy
  cap that leaves headroom for the temporaries Mosaic keeps live.
* ``lane-misaligned`` / ``sublane-misaligned`` — last dim of a VMEM
  block/scratch must be a 128 multiple, second-to-last a dtype-dependent
  sublane multiple (f32 8 / bf16 16 / int8 32); size-1 dims are exempt
  (scalar rows/columns lower through broadcasts, not tiles).
* ``fragile-repeat`` — ``pltpu.repeat`` inside a kernel body: its
  interpret-mode and Mosaic semantics have diverged (the ivf_pq decode
  built on it passed interpret tests and returned recall 0.07 on a
  v5e). Any use must be re-verified on real TPU before trust.
* ``fragile-reshape`` — an in-kernel reshape that changes the lane
  (minor) dim at sub-128 granularity: the relayout Mosaic handles least
  reliably (the reason graph_expand routes queries with a one-hot
  matmul instead).
* ``dma-unwaited`` — more ``dma_start`` than ``dma_wait`` equations: a
  started async (remote) copy some path never waits on.
* ``sem-unpaired`` — a REGULAR (non-DMA) semaphore that is signaled but
  never waited, or waited but never signaled, in the kernel body (the
  ring kernel's credit/barrier discipline).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

from . import Finding

# ---------------------------------------------------------------------------
# budgets and tiling constants (pallas guide: ~16 MB VMEM/core; min tile
# sublane×lane by dtype: f32 (8,128), bf16 (16,128), int8 (32,128))
# ---------------------------------------------------------------------------

VMEM_BUDGETS_BYTES: Dict[str, int] = {
    "v4": 16 << 20,
    "v5e": 16 << 20,
    "v5p": 16 << 20,
}
# fraction of the budget a single kernel's declared working set may
# claim: Mosaic keeps fold/concat temporaries live beyond the declared
# blocks (the reason cagra_fused budgets 8 MB of 16)
VMEM_OCCUPANCY = 0.75

_SUBLANE = {4: 8, 2: 16, 1: 32}
_LANE = 128

_CALL_RE = re.compile(r"pl\.pallas_call\(")

# primitives considered host-callback-free kernel internals; anything in
# this set inside a kernel body is a fragility finding
_REPEAT_PRIMS = {"repeat"}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSite:
    """One literal ``pl.pallas_call`` site. ``occurrence`` is its 0-based
    index among the file's ``pl.pallas_call(`` matches (for line
    anchoring); ``variants`` maps variant name → zero-arg builder
    returning ``(fn, args)`` for :func:`jax.make_jaxpr`, or ``None``
    when the variant cannot trace in this process (reported as skipped,
    never silently dropped)."""

    name: str
    path: str
    occurrence: int
    variants: Tuple[Tuple[str, Callable], ...]


def _v_fused_knn(dtype: str):
    def build():
        import jax.numpy as jnp

        from ..ops.fused_knn import fused_knn

        m, n, d, k = 512, 2048, 128, 64
        q = jnp.zeros((m, d), jnp.float32)
        if dtype == "int4":
            data = jnp.zeros((n, d // 2), jnp.int8)
            return (functools.partial(fused_knn, k=k, interpret=True,
                                      int4_dim=d),
                    (q, data, ),
                    {"scales": jnp.ones((n,), jnp.float32)})
        if dtype == "int8":
            data = jnp.zeros((n, d), jnp.int8)
            return (functools.partial(fused_knn, k=k, interpret=True),
                    (q, data),
                    {"scales": jnp.ones((n,), jnp.float32)})
        kw = {}
        if dtype == "f32_pen":
            kw["penalty"] = jnp.zeros((n,), jnp.float32)
        data = jnp.zeros((n, d),
                         jnp.bfloat16 if dtype == "bf16" else jnp.float32)
        return (functools.partial(fused_knn, k=k, interpret=True, **kw),
                (q, data), {})
    return build


def _v_select_k():
    import jax.numpy as jnp

    from ..matrix.select_k import _kpass_2d

    vals = jnp.zeros((512, 4096), jnp.float32)
    return (lambda v: _kpass_2d(v, 64, True), (vals,), {})


def _v_ivf_flat(flavor: str):
    def build():
        import jax.numpy as jnp

        from ..ops.ivf_scan import ivf_flat_scan

        n, d, L, m, p, lmax, k = 1024, 128, 8, 128, 4, 256, 32
        data = jnp.zeros(
            (n, d), jnp.int8 if flavor == "int8_pen" else jnp.float32)
        norms = jnp.zeros((n,), jnp.float32)
        probed = jnp.zeros((m, p), jnp.int32)
        offsets = jnp.arange(L, dtype=jnp.int32) * (n // L)
        sizes = jnp.full((L,), n // L, jnp.int32)
        q = jnp.zeros((m, d), jnp.float32)
        kw = {"interpret": True}
        if flavor == "int8_pen":
            kw["penalty"] = jnp.zeros((n,), jnp.float32)
            kw["scales"] = jnp.ones((n,), jnp.float32)
        return (functools.partial(ivf_flat_scan, k=k, lmax=lmax, **kw),
                (data, norms, probed, offsets, sizes, q), {})
    return build


def _v_ivf_pq(lut: str):
    def build():
        import jax.numpy as jnp

        from ..ops.ivf_pq_scan import ivf_pq_scan, make_cb_matrix

        n, pq_dim, book, pq_len = 1024, 32, 256, 4
        L, m, p, lmax, k = 8, 128, 4, 256, 32
        rot_dim = pq_dim * pq_len
        codes = jnp.zeros((n, pq_dim), jnp.uint8)
        norms = jnp.zeros((n,), jnp.float32)
        centers = jnp.zeros((L, rot_dim), jnp.float32)
        cbm = make_cb_matrix(jnp.zeros((pq_dim, book, pq_len), jnp.float32))
        probed = jnp.zeros((m, p), jnp.int32)
        offsets = jnp.arange(L, dtype=jnp.int32) * (n // L)
        sizes = jnp.full((L,), n // L, jnp.int32)
        q = jnp.zeros((m, rot_dim), jnp.float32)
        kw = {}
        mode = lut
        if lut == "f32_pen":
            mode = "f32"
            kw["penalty"] = jnp.zeros((n,), jnp.float32)
        return (functools.partial(ivf_pq_scan, k=k, lmax=lmax,
                                  pq_dim=pq_dim, book=book, lut_mode=mode,
                                  interpret=True, **kw),
                (codes, norms, centers, cbm, probed, offsets, sizes, q), {})
    return build


def _v_graph_expand(mode: str):
    def build():
        import jax.numpy as jnp

        from ..ops.graph_expand import graph_expand

        m, width, n, deg_p, d, k_out = 64, 2, 1024, 64, 128, 32
        parents = jnp.zeros((m, width), jnp.int32)
        q = jnp.zeros((m, d), jnp.float32)
        aux = jnp.zeros((n, 2, deg_p), jnp.float32)
        kw: dict = {"mode": mode, "interpret": True}
        if mode == "int4":
            vecs = jnp.zeros((n, deg_p, d // 2), jnp.int8)
        elif mode == "pq":
            pq_dim, book = 16, 256
            vecs = jnp.zeros((n, deg_p, pq_dim), jnp.uint8)
            kw["cbm"] = jnp.zeros((pq_dim * book, d), jnp.int8)
            kw["cb_scale"] = jnp.ones((1, d), jnp.float32)
        else:
            vecs = jnp.zeros((n, deg_p, d), jnp.int8)
            if mode == "dense_pen":
                kw = {"mode": "dense", "interpret": True,
                      "pen": jnp.zeros((n, deg_p), jnp.float32)}
        return (functools.partial(graph_expand, k_out=k_out, **kw),
                (parents, q, vecs, aux), {})
    return build


def _v_cagra_fused(mode: str):
    def build():
        import jax.numpy as jnp

        from ..ops.cagra_fused import fused_traverse

        m, n, deg_p, d, itopk, width, kprime = 32, 1024, 64, 128, 64, 2, 32
        q = jnp.zeros((m, d), jnp.float32)
        bd = jnp.zeros((m, itopk), jnp.float32)
        bi = jnp.zeros((m, itopk), jnp.int32)
        aux = jnp.zeros((n, 2, deg_p), jnp.float32)
        gph = jnp.zeros((n, deg_p), jnp.int32)
        kw: dict = {"itopk": itopk, "width": width, "max_iter": 2,
                    "kprime": kprime, "degree": deg_p, "interpret": True}
        if mode == "int4":
            vecs = jnp.zeros((n, deg_p, d // 2), jnp.int8)
            kw["mode"] = "int4"
        else:
            vecs = jnp.zeros((n, deg_p, d), jnp.int8)
            if mode == "pen":
                kw["pen"] = jnp.zeros((n, deg_p), jnp.float32)
        return (functools.partial(fused_traverse, **kw),
                (q, bd, bi, vecs, aux, gph), {})
    return build


def _v_merge_step():
    import jax.numpy as jnp

    from ..ops.ring_topk import merge_step

    m, k = 64, 64
    args = (jnp.zeros((m, k), jnp.float32), jnp.zeros((m, k), jnp.int32),
            jnp.zeros((m, k), jnp.int32), jnp.zeros((m, k), jnp.float32),
            jnp.zeros((m, k), jnp.int32), jnp.zeros((m, k), jnp.int32))
    return (functools.partial(merge_step, k=k, engine="pallas",
                              interpret=True), args, {})


def _v_ring_pallas():
    """The remote-DMA ring kernel, traced (never run) under shard_map on
    the CPU mesh — remote DMA has no interpret emulation on this jax,
    but abstract tracing exposes the full DMA/semaphore structure."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..ops import ring_topk
    from ..utils import shard_map_compat

    devs = jax.devices()
    if len(devs) < 2:
        return None
    p = min(4, len(devs))
    mesh = Mesh(np.array(devs[:p]), ("shard",))
    m, k = 64, 128

    def body(d, g):
        return ring_topk._ring_pallas(d[0], g[0], k, True, "shard", p)

    fn = shard_map_compat(body, mesh=mesh,
                          in_specs=(P("shard", None, None),) * 2,
                          out_specs=(P(), P()), check=False)
    return (fn, (jnp.zeros((p, m, k), jnp.float32),
                 jnp.zeros((p, m, k), jnp.int32)), {})


SITES: Tuple[KernelSite, ...] = (
    KernelSite("fused_knn", "raft_tpu/ops/fused_knn.py", 0, (
        ("f32", _v_fused_knn("f32")),
        ("bf16", _v_fused_knn("bf16")),
        ("int8", _v_fused_knn("int8")),
        ("int4", _v_fused_knn("int4")),
        ("f32_pen", _v_fused_knn("f32_pen")),
    )),
    KernelSite("select_k.kpass", "raft_tpu/matrix/select_k.py", 0, (
        ("f32", _v_select_k),
    )),
    KernelSite("ivf_flat.scan", "raft_tpu/ops/ivf_scan.py", 0, (
        ("f32", _v_ivf_flat("f32")),
        ("int8_pen", _v_ivf_flat("int8_pen")),
    )),
    KernelSite("ivf_pq.scan", "raft_tpu/ops/ivf_pq_scan.py", 0, (
        ("f32", _v_ivf_pq("f32")),
        ("bf16", _v_ivf_pq("bf16")),
        ("int8", _v_ivf_pq("int8")),
        ("f32_pen", _v_ivf_pq("f32_pen")),
    )),
    KernelSite("cagra.graph_expand", "raft_tpu/ops/graph_expand.py", 0, (
        ("dense", _v_graph_expand("dense")),
        ("dense_pen", _v_graph_expand("dense_pen")),
        ("int4", _v_graph_expand("int4")),
        ("pq", _v_graph_expand("pq")),
    )),
    KernelSite("cagra.fused_search", "raft_tpu/ops/cagra_fused.py", 0, (
        ("dense", _v_cagra_fused("dense")),
        ("pen", _v_cagra_fused("pen")),
        ("int4", _v_cagra_fused("int4")),
    )),
    KernelSite("ring_topk.merge_step", "raft_tpu/ops/ring_topk.py", 0, (
        ("fold", _v_merge_step),
    )),
    KernelSite("ring_topk.ring_pallas", "raft_tpu/ops/ring_topk.py", 1, (
        ("remote_dma", _v_ring_pallas),
    )),
)


def registered_counts() -> Dict[str, int]:
    """path → number of registered literal ``pl.pallas_call`` sites (the
    drift guard compares this against the source grep)."""
    out: Dict[str, int] = {}
    for s in SITES:
        out[s.path] = max(out.get(s.path, 0), s.occurrence + 1)
    return out


def pallas_call_sites(root: str) -> Dict[str, int]:
    """Source grep: path → count of literal ``pl.pallas_call(`` call
    sites under ``raft_tpu/`` (comment/docstring mentions don't match
    the call regex)."""
    out: Dict[str, int] = {}
    pkg = os.path.join(root, "raft_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        if "analysis" in os.path.relpath(dirpath, pkg).split(os.sep):
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            full = os.path.join(dirpath, fn)
            with open(full) as f:
                n = len(_CALL_RE.findall(f.read()))
            if n:
                out[os.path.relpath(full, root)] = n
    return out


def site_line(root: str, site: KernelSite) -> int:
    """Line of the site's literal ``pl.pallas_call(`` (best effort)."""
    try:
        with open(os.path.join(root, site.path)) as f:
            lines = f.read().splitlines()
    except OSError:
        return 0
    hits = [i for i, t in enumerate(lines, 1) if _CALL_RE.search(t)]
    return hits[site.occurrence] if site.occurrence < len(hits) else 0


# ---------------------------------------------------------------------------
# jaxpr introspection
# ---------------------------------------------------------------------------

def _subjaxprs(params):
    from jax.extend import core as jex_core

    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _walk_eqns(sub)


def pallas_eqns(closed_jaxpr) -> list:
    return [e for e in _walk_eqns(closed_jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def _aval_of(ref_aval):
    inner = getattr(ref_aval, "inner_aval", ref_aval)
    return inner


def _memspace(ref_aval) -> str:
    return str(getattr(ref_aval, "memory_space", "") or "")


def _is_vmem(ref_aval) -> bool:
    """A ref that lives in VMEM: explicit vmem, or the default (None)
    memory space — which lowers to VMEM on TPU. Excludes ANY (HBM),
    SMEM and semaphore refs."""
    ms = _memspace(ref_aval).lower()
    return ms in ("", "none") or "vmem" in ms


def _is_semaphore(ref_aval) -> bool:
    return "semaphore" in _memspace(ref_aval) or \
        "sem" in str(_aval_of(ref_aval).dtype)


def _bytes_of(aval) -> int:
    try:
        return math.prod(aval.shape) * aval.dtype.itemsize
    except (TypeError, AttributeError):
        return 0


@dataclasses.dataclass
class SiteReport:
    """Structural summary of one traced pallas_call (the CLI's --json
    payload and the check input)."""

    site: str
    variant: str
    grid: tuple
    vmem_block_bytes: int      # VMEM in/out blocks, single-buffered
    vmem_scratch_bytes: int
    vmem_total_bytes: int      # blocks ×2 (grid pipeline) + scratch
    misaligned: List[str]
    fragile: List[str]
    dma_starts: int
    dma_waits: int
    unpaired_sems: List[str]


def _alignment_issues(site: str, tag: str, aval) -> List[Tuple[str, str]]:
    """(rule, detail) for one VMEM-resident aval."""
    out = []
    shape = tuple(aval.shape)
    if not shape:
        return out
    itemsize = aval.dtype.itemsize
    lane = shape[-1]
    if lane > 1 and lane % _LANE:
        out.append(("lane-misaligned",
                    f"{tag} {aval.dtype}{list(shape)}: minor dim {lane} "
                    f"is not a {_LANE} multiple"))
    if len(shape) >= 2:
        sub = shape[-2]
        need = _SUBLANE.get(itemsize, 8)
        if sub > 1 and sub % need:
            out.append(("sublane-misaligned",
                        f"{tag} {aval.dtype}{list(shape)}: sublane dim "
                        f"{sub} is not a {need} multiple ({aval.dtype} "
                        f"tiles pad to {need})"))
    return out


def audit_eqn(site: str, variant: str, eqn) -> Tuple[SiteReport,
                                                     List[Tuple[str, str]]]:
    """Run every structural check on one traced pallas_call equation.
    Returns (report, [(rule, message)])."""
    gm = eqn.params["grid_mapping"]
    kjaxpr = eqn.params["jaxpr"]
    issues: List[Tuple[str, str]] = []

    block_bytes = 0
    mis: List[str] = []
    for bm in gm.block_mappings:
        ref_aval = bm.transformed_block_aval
        if not _is_vmem(ref_aval):
            continue
        aval = _aval_of(ref_aval)
        block_bytes += _bytes_of(aval)
        for rule, detail in _alignment_issues(site, f"block[{bm.origin}]",
                                              aval):
            issues.append((rule, detail))
            mis.append(detail)

    n_scratch = gm.num_scratch_operands
    scratch_avals = (list(kjaxpr.invars[-n_scratch:]) if n_scratch else [])
    scratch_bytes = 0
    sem_vars = []
    for i, var in enumerate(scratch_avals):
        ref_aval = var.aval
        if _is_semaphore(ref_aval):
            sem_vars.append((i, var))
            continue
        if not _is_vmem(ref_aval):
            continue
        aval = _aval_of(ref_aval)
        scratch_bytes += _bytes_of(aval)
        for rule, detail in _alignment_issues(site, f"scratch[{i}]", aval):
            issues.append((rule, detail))
            mis.append(detail)

    total = 2 * block_bytes + scratch_bytes
    budget = int(min(VMEM_BUDGETS_BYTES.values()) * VMEM_OCCUPANCY)
    if total > budget:
        worst = min(VMEM_BUDGETS_BYTES, key=VMEM_BUDGETS_BYTES.get)
        issues.append((
            "vmem-budget",
            f"declared VMEM working set {total / (1 << 20):.1f} MiB "
            f"(blocks ×2 + scratch) exceeds the {worst} budget "
            f"{VMEM_BUDGETS_BYTES[worst] / (1 << 20):.0f} MiB × "
            f"{VMEM_OCCUPANCY} occupancy"))

    # fragile primitives + DMA/semaphore pairing inside the kernel body
    fragile: List[str] = []
    dma_starts = dma_waits = 0
    signaled: set = set()
    waited: set = set()
    known_sem_ids = {id(var) for _i, var in sem_vars}
    unattributed_sem_ops = 0
    for keqn in _walk_eqns(kjaxpr):
        nm = keqn.primitive.name
        if nm in _REPEAT_PRIMS:
            fragile.append(
                "pltpu.repeat: interpret semantics are element-wise "
                "(np.repeat) on this jax while Mosaic tiles (np.tile) — "
                "re-verify on real TPU (the ivf_pq pq_bits=4 xfail)")
            issues.append(("fragile-repeat", fragile[-1]))
        elif nm == "reshape":
            in_shape = tuple(keqn.invars[0].aval.shape)
            out_shape = tuple(keqn.outvars[0].aval.shape)
            in_lane = in_shape[-1] if in_shape else 1
            out_lane = out_shape[-1] if out_shape else 1
            if (in_lane != out_lane
                    and any(d > 1 and d % _LANE for d in (in_lane,
                                                          out_lane))):
                detail = (f"sub-128-lane reshape {list(in_shape)} -> "
                          f"{list(out_shape)}: minor-dim relayout Mosaic "
                          "handles least reliably")
                fragile.append(detail)
                issues.append(("fragile-reshape", detail))
        elif nm == "dma_start":
            dma_starts += 1
        elif nm == "dma_wait":
            dma_waits += 1
        elif nm in ("semaphore_signal", "semaphore_wait"):
            ids = {id(v) for v in keqn.invars if not hasattr(v, "val")}
            (signaled if nm == "semaphore_signal" else waited).update(ids)
            # an op on a semaphore threaded through a control-flow
            # sub-jaxpr binds a DIFFERENT Var than the scratch invar —
            # id matching cannot attribute it (get_barrier_semaphore's
            # fresh var is the benign top-level case)
            sem_operands = {
                id(v) for v in keqn.invars
                if not hasattr(v, "val") and _is_semaphore(v.aval)}
            if sem_operands and not (sem_operands & known_sem_ids):
                in_top = any(keqn2 is keqn for keqn2 in kjaxpr.eqns)
                if not in_top:
                    unattributed_sem_ops += 1

    if dma_starts > dma_waits:
        issues.append((
            "dma-unwaited",
            f"{dma_starts} dma_start vs {dma_waits} dma_wait equations: "
            "a started async copy is never waited on some path"))

    unpaired: List[str] = []
    # regular (non-DMA) semaphores: every one must be both signaled and
    # waited somewhere in the body. DMA semaphores are consumed by
    # dma_wait and are covered by the count check above. LIMITATION:
    # signal/wait inside a control-flow sub-jaxpr (fori_loop/cond body)
    # binds inner Vars id-matching cannot attribute to the scratch
    # invar — when such ops exist the pairing verdict would be
    # unreliable in BOTH directions, so the check stands down rather
    # than emit a false finding (docs/analysis.md).
    for i, var in sem_vars:
        if unattributed_sem_ops:
            break
        if "dma" in str(_aval_of(var.aval).dtype):
            continue
        s, w = id(var) in signaled, id(var) in waited
        if s != w:
            what = "signaled but never waited" if s else \
                "waited but never signaled"
            unpaired.append(f"scratch[{i}] {what}")
            issues.append((
                "sem-unpaired",
                f"regular semaphore scratch[{i}] is {what} in the kernel "
                "body — a hung or leaking credit on hardware"))

    rep = SiteReport(site=site, variant=variant, grid=tuple(gm.grid),
                     vmem_block_bytes=block_bytes,
                     vmem_scratch_bytes=scratch_bytes,
                     vmem_total_bytes=total, misaligned=mis,
                     fragile=fragile, dma_starts=dma_starts,
                     dma_waits=dma_waits, unpaired_sems=unpaired)
    return rep, issues


def trace_variant(builder) -> Optional[list]:
    """Build and trace one variant → pallas_call eqns (None = variant
    skipped in this process, e.g. no multi-device mesh)."""
    import jax

    built = builder()
    if built is None:
        return None
    fn, args, kwargs = built
    closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    return pallas_eqns(closed)


def run(root: str, collect_reports: Optional[list] = None) -> List[Finding]:
    """Audit every registered site; returns findings (symbols are
    ``site:variant``-stable so the baseline survives line drift)."""
    findings: List[Finding] = []
    for site in SITES:
        line = site_line(root, site)
        for vname, builder in site.variants:
            try:
                eqns = trace_variant(builder)
            except Exception as e:  # noqa: BLE001 - a trace failure IS
                # a finding: the kernel cannot even shape-trace
                findings.append(Finding(
                    "trace-failed", site.path, f"{site.name}:{vname}",
                    f"variant failed to trace: {type(e).__name__}: {e}",
                    line))
                continue
            if eqns is None:
                continue
            for eqn in eqns:
                rep, issues = audit_eqn(site.name, vname, eqn)
                if collect_reports is not None:
                    collect_reports.append(rep)
                for rule, msg in issues:
                    # symbol carries the variant only for shape-dependent
                    # rules; structural rules dedupe across variants
                    structural = rule in ("fragile-repeat", "dma-unwaited",
                                          "sem-unpaired")
                    sym = site.name if structural else \
                        f"{site.name}:{vname}"
                    findings.append(Finding(rule, site.path, sym, msg,
                                            line))
    return findings
