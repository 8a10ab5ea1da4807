"""Pass 2: serving hot-path audits — sync discipline and recompile
hazards.

The serving stack's latency contract rests on two invariants the last
six PRs kept re-litigating by hand:

* **No unconditional host sync on a dispatch path.** TPU dispatch is
  asynchronous; one stray ``jax.block_until_ready``/``jax.device_get``
  serializes the double-buffered batcher against device time (the PR 12
  dispatch-floor work existed to remove exactly these). Syncs are legal
  only on *sampled probes* (the batcher's ``if probe:`` device stage,
  mutable's pre-warm tick) or off the hot path (warmup, save/load,
  tune/bench). Rule ``hotpath-sync`` flags the rest.
* **No host callbacks inside a searcher program, and no continuous
  jit statics.** A callback primitive in a ``make_searcher`` closure's
  jaxpr round-trips every batch through Python; a float-valued (or
  signature-drifted) ``static_argnames`` entry bypasses the shape-bucket
  executable cache and recompiles per distinct value. Rules
  ``hotpath-callback`` (jaxpr, via :func:`audit_searcher`),
  ``jit-static-float`` and ``jit-static-missing`` (AST, whole tree).

:func:`jaxpr_stats` is the generalized form of
``cagra_fused.one_dispatch_stats`` (which now delegates here): it
counts kernel launches, device-side loops OUTSIDE kernel bodies (each
iteration of one is a dispatch round trip), and callback primitives in
any traced callable — the bench serving lane, the one-dispatch test and
the pod session all read the same counter set.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from . import Finding

__all__ = ["jaxpr_stats", "audit_searcher", "run", "HOTPATH_MODULES",
           "CALLBACK_PRIMS", "sync_lint", "sync_lint_source",
           "jit_static_lint", "jit_static_lint_source",
           "shardmap_lint", "shardmap_lint_source"]

# primitives that round-trip through the host per execution
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "host_callback", "outside_call", "infeed", "outfeed",
})

# the serving-reachable modules the sync lint scans: everything under
# serve/ — including tenancy.py (the fabric worker's dispatch/demux is
# a serving hot path) and qcache.py (a cache hit runs on the submit
# thread; test_analysis pins both into the scanned set) — plus every
# module that defines a make_searcher closure (or is dispatched from
# one)
HOTPATH_MODULES = (
    "raft_tpu/serve",
    "raft_tpu/neighbors/brute_force.py",
    "raft_tpu/neighbors/cagra.py",
    "raft_tpu/neighbors/ivf_flat.py",
    "raft_tpu/neighbors/ivf_pq.py",
    "raft_tpu/neighbors/mutable.py",
    "raft_tpu/neighbors/host_stream.py",
    "raft_tpu/parallel/sharded_ann.py",
    "raft_tpu/parallel/sharded_knn.py",
    "raft_tpu/parallel/fleet.py",
    "raft_tpu/parallel/dispatch_cache.py",
)

_SYNC_CALLS = {"block_until_ready", "device_get"}
# a sync inside a function whose name marks it off the hot path is fine
_OFFPATH_FN = re.compile(
    r"warm|prepare|tune|bench|save|load|export|__main__")
# ... as is one under a sampled-probe conditional
_PROBE_COND = re.compile(r"probe|sample|rate|tick|warm")

# -- rule hotpath-shardmap-rebuild ------------------------------------------
# constructing a shard_map per call re-traces (and usually recompiles)
# the WHOLE sharded program on every search — the dispatch tax the
# per-index compiled-program cache (parallel/dispatch_cache) exists to
# kill. Legal off the hot path (builds/training/warmup/tuning/dryruns,
# tier re-planning) ...
_SHARDMAP_CALLS = {"shard_map", "shard_map_compat"}
_SHARDMAP_OFFPATH = re.compile(
    r"warm|prepare|tune|bench|save|load|export|__main__|build|train"
    r"|dryrun|pack|plan|retier")
# ... or under a compiled-program-cache miss conditional (trace once,
# store, dispatch many)
_CACHE_MISS_COND = re.compile(r"cache|miss|compil|is None|not in")


# ---------------------------------------------------------------------------
# jaxpr-structural audit (the one_dispatch_stats generalization)
# ---------------------------------------------------------------------------

def jaxpr_stats(fn, *args) -> dict:
    """Trace ``fn(*args)`` (abstract — nothing executes) and report its
    dispatch structure: ``pallas_calls`` (kernel launch sites),
    ``while_loops``/``scans`` (device loops OUTSIDE kernel bodies — each
    ``while`` iteration is a separate kernel-launch round trip),
    ``callbacks`` (host round trips per execution, by primitive name),
    and ``one_dispatch`` (no device loop remains: the whole program is
    one straight-line executable per call).

    Plain python scalars (int/float/bool/str/None) among ``args`` are
    treated as static — a searcher closure's ``k`` is a shape/branch
    input, not a traced value (exactly as ``jax.jit`` statics would
    hold it on the serving path)."""
    import jax
    from jax.extend import core as jex_core

    static = {i for i, a in enumerate(args)
              if a is None or isinstance(a, (int, float, bool, str))}
    traced = [a for i, a in enumerate(args) if i not in static]

    def call(*dyn):
        it = iter(dyn)
        full = [args[i] if i in static else next(it)
                for i in range(len(args))]
        return fn(*full)

    jaxpr = jax.make_jaxpr(call)(*traced)
    counts = {"pallas_calls": 0, "while_loops": 0, "scans": 0}
    callbacks: List[str] = []

    def _subjaxprs(params):
        for v in params.values():
            vals = v if isinstance(v, (tuple, list)) else (v,)
            for x in vals:
                if isinstance(x, jex_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jex_core.Jaxpr):
                    yield x

    def walk(jx):
        for eqn in jx.eqns:
            nm = eqn.primitive.name
            if nm == "pallas_call":
                counts["pallas_calls"] += 1
                continue           # hop loops INSIDE a kernel are free
            if nm == "while":
                counts["while_loops"] += 1
            elif nm == "scan":
                counts["scans"] += 1
            elif nm in CALLBACK_PRIMS:
                callbacks.append(nm)
            for sub in _subjaxprs(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    counts["callbacks"] = callbacks
    counts["one_dispatch"] = counts["while_loops"] == 0
    return counts


def audit_searcher(name: str, fn, *args) -> Tuple[dict, List[Finding]]:
    """Audit one serving closure (a ``make_searcher`` product or any
    ``fn(queries, k)``-shaped callable): trace it and flag host-callback
    primitives. Returns ``(jaxpr_stats, findings)`` — dispatch-floor
    counts ride along for the caller (the pod session asserts
    ``one_dispatch`` for the fused engine; other engines legitimately
    loop)."""
    stats = jaxpr_stats(fn, *args)
    findings = [
        Finding("hotpath-callback", "<traced>", f"{name}:{prim}",
                f"searcher closure '{name}' reaches host-callback "
                f"primitive '{prim}': every batch round-trips through "
                "Python on the dispatch path")
        for prim in sorted(set(stats["callbacks"]))
    ]
    return stats, findings


# ---------------------------------------------------------------------------
# AST: unconditional-sync lint
# ---------------------------------------------------------------------------

def _is_sync_call(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _SYNC_CALLS:
        return f.attr
    return None


def _is_shardmap_call(node: ast.Call) -> Optional[str]:
    f = node.func
    name = (f.attr if isinstance(f, ast.Attribute)
            else f.id if isinstance(f, ast.Name) else None)
    return name if name in _SHARDMAP_CALLS else None


class _CallSiteVisitor(ast.NodeVisitor):
    """Flag calls matched by ``matcher`` unless an enclosing function
    name matches ``offpath`` or an enclosing ``if`` condition matches
    ``cond_cover`` (the sampled-probe / cache-miss escape hatches)."""

    def __init__(self, matcher, offpath, cond_cover):
        self.matcher = matcher
        self.offpath = offpath
        self.cond_cover = cond_cover
        self.fn_stack: List[str] = []
        self.if_stack: List[str] = []
        self.hits: List[Tuple[int, str, str]] = []  # (line, call, fn)

    def visit_FunctionDef(self, node):
        self.fn_stack.append(node.name)
        # a nested def runs later, unconditionally — it must not inherit
        # an enclosing `if probe:` as sampled-probe cover
        saved, self.if_stack = self.if_stack, []
        self.generic_visit(node)
        self.if_stack = saved
        self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_If(self, node):
        # the test expression itself runs unconditionally: a sync call
        # INSIDE the condition must not inherit the condition as cover
        self.visit(node.test)
        try:
            cond = ast.unparse(node.test)
        except Exception:  # noqa: BLE001 - unparse is best-effort
            cond = ""
        self.if_stack.append(cond)
        for child in node.body:
            self.visit(child)
        self.if_stack.pop()
        for child in node.orelse:
            self.visit(child)

    def visit_Call(self, node):
        name = self.matcher(node)
        if name is not None:
            off_path = any(self.offpath.search(fn) for fn in self.fn_stack)
            covered = any(self.cond_cover.search(c) for c in self.if_stack)
            if not off_path and not covered:
                fn = ".".join(self.fn_stack) or "<module>"
                self.hits.append((node.lineno, name, fn))
        self.generic_visit(node)


def sync_lint_source(src: str, rel_path: str) -> List[Finding]:
    """Sync lint for one module's source (exposed for the fixture
    tests)."""
    visitor = _CallSiteVisitor(_is_sync_call, _OFFPATH_FN, _PROBE_COND)
    visitor.visit(ast.parse(src))
    return [Finding(
        "hotpath-sync", rel_path, f"{fn}:{call}",
        f"unconditional jax.{call} in serving-reachable "
        f"'{fn}' — syncs belong on sampled probes or off-path "
        "helpers (warmup/save/tune) only", line)
        for line, call, fn in visitor.hits]


def sync_lint(root: str) -> List[Finding]:
    from . import iter_module_paths

    findings = []
    for rel in iter_module_paths(root, HOTPATH_MODULES):
        with open(os.path.join(root, rel)) as f:
            src = f.read()
        findings += sync_lint_source(src, rel.replace(os.sep, "/"))
    return findings


def shardmap_lint_source(src: str, rel_path: str) -> List[Finding]:
    """Per-call shard_map-rebuild lint for one module's source (exposed
    for the fixture tests): any ``shard_map``/``shard_map_compat``
    construction in a serving-reachable module must sit off the hot
    path (build/train/warmup/tune/... function) or under a compiled-
    program-cache miss conditional (``if fn is None:`` — the
    trace-once/dispatch-many pattern of parallel/dispatch_cache)."""
    visitor = _CallSiteVisitor(_is_shardmap_call, _SHARDMAP_OFFPATH,
                               _CACHE_MISS_COND)
    visitor.visit(ast.parse(src))
    return [Finding(
        "hotpath-shardmap-rebuild", rel_path, f"{fn}:{call}",
        f"per-call {call} construction in serving-reachable "
        f"'{fn}': every search re-traces the whole sharded program "
        "(~hundreds of XLA programs per call at fleet scale) — route "
        "it through the per-index compiled-program cache "
        "(parallel/dispatch_cache)", line)
        for line, call, fn in visitor.hits]


def shardmap_lint(root: str) -> List[Finding]:
    from . import iter_module_paths

    findings = []
    for rel in iter_module_paths(root, HOTPATH_MODULES):
        with open(os.path.join(root, rel)) as f:
            src = f.read()
        findings += shardmap_lint_source(src, rel.replace(os.sep, "/"))
    return findings


# ---------------------------------------------------------------------------
# AST: recompile-hazard lint (jit statics)
# ---------------------------------------------------------------------------

def _static_argnames(call: ast.Call) -> Optional[List[Tuple[str, int]]]:
    """``static_argnames`` literals of a ``jax.jit`` /
    ``[functools.]partial(jax.jit, ...)`` call, with lines (both the
    attribute and the bare-imported ``partial`` spellings — cagra.py
    uses the bare form)."""
    f = call.func
    is_jit = (isinstance(f, ast.Attribute) and f.attr == "jit"
              and isinstance(f.value, ast.Name) and f.value.id == "jax")
    is_partial = ((isinstance(f, ast.Attribute) and f.attr == "partial")
                  or (isinstance(f, ast.Name) and f.id == "partial"))
    is_partial_jit = (
        is_partial and bool(call.args)
        and isinstance(call.args[0], ast.Attribute)
        and call.args[0].attr == "jit")
    if not (is_jit or is_partial_jit):
        return None
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        names: List[Tuple[str, int]] = []
        vals = (kw.value.elts if isinstance(kw.value, (ast.Tuple, ast.List))
                else [kw.value])
        for v in vals:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.append((v.value, v.lineno))
        return names
    return []


def _float_params(fn: ast.FunctionDef) -> Dict[str, str]:
    """Parameter name → evidence string for continuous-valued params
    (float annotation or float default)."""
    out: Dict[str, str] = {}
    args = fn.args
    params = args.posonlyargs + args.args + args.kwonlyargs
    for a in params:
        ann = a.annotation
        if isinstance(ann, ast.Name) and ann.id == "float":
            out[a.arg] = "annotated float"
    defaults = list(args.defaults)
    pos = args.posonlyargs + args.args
    for a, d in zip(pos[len(pos) - len(defaults):], defaults):
        if isinstance(d, ast.Constant) and isinstance(d.value, float):
            out.setdefault(a.arg, f"float default {d.value}")
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is not None and isinstance(d, ast.Constant) \
                and isinstance(d.value, float):
            out.setdefault(a.arg, f"float default {d.value}")
    return out


def jit_static_lint_source(src: str, rel_path: str) -> List[Finding]:
    """Recompile-hazard lint for one module's source: every
    ``static_argnames`` entry must name a real parameter
    (``jit-static-missing`` — a typo silently turns the static into a
    traced arg or a TypeError) and must not be continuous-valued
    (``jit-static-float`` — each distinct float compiles a fresh
    executable, bypassing the shape buckets)."""
    findings: List[Finding] = []
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return findings
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        statics: List[Tuple[str, int]] = []
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call):
                got = _static_argnames(dec)
                if got:
                    statics += got
        if not statics:
            continue
        params = {a.arg for a in (node.args.posonlyargs + node.args.args
                                  + node.args.kwonlyargs)}
        floaty = _float_params(node)
        for name, line in statics:
            if name not in params:
                findings.append(Finding(
                    "jit-static-missing", rel_path,
                    f"{node.name}:{name}",
                    f"static_argnames entry '{name}' is not a "
                    f"parameter of {node.name}() — signature "
                    "drift makes it a silently-traced arg", line))
            elif name in floaty:
                findings.append(Finding(
                    "jit-static-float", rel_path,
                    f"{node.name}:{name}",
                    f"static arg '{name}' of {node.name}() is "
                    f"continuous-valued ({floaty[name]}): every "
                    "distinct value compiles a fresh executable, "
                    "bypassing the shape-bucket cache", line))
    return findings


def jit_static_lint(root: str) -> List[Finding]:
    """Whole-tree recompile-hazard sweep (see
    :func:`jit_static_lint_source`)."""
    findings = []
    pkg = os.path.join(root, "raft_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        if "analysis" in os.path.relpath(dirpath, pkg).split(os.sep):
            continue
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            full = os.path.join(dirpath, fname)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            with open(full) as f:
                findings += jit_static_lint_source(f.read(), rel)
    return findings


def run(root: str) -> List[Finding]:
    return sync_lint(root) + jit_static_lint(root) + shardmap_lint(root)
