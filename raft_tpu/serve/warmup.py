"""Startup warmup for the bucket ladder, plus compilation-count
instrumentation and the always-on recompile watch.

The recompile-avoidance guarantee of :mod:`raft_tpu.serve.batcher` is
only worth anything if every ladder shape is compiled BEFORE traffic
arrives — an un-warmed bucket turns the first unlucky request into a
multi-second XLA compile stall. :func:`warmup` dispatches a dummy batch
through the live search closure at every (query-bucket × k-bucket)
shape and blocks on the results, so steady-state serving hits only
cached executables.

The matching measurement listens on JAX's public monitoring stream:
every executable JAX builds — a backend compile or a persistent-cache
load, both through ``compile_or_get_cached`` — records one
``/jax/core/compile/backend_compile_duration`` event. It comes in two
layers:

* :func:`install_recompile_watch` registers ONE listener per process
  (idempotent) that (a) increments the always-on
  ``serve.compiles`` total, and (b) for compiles carrying a
  non-warmup :func:`compile_context` label (the batcher sets its
  ``<name>:<rows>x<k>`` shape bucket around every dispatch) — i.e. a
  SERVING-PATH post-warmup recompile, the rare degradation signal —
  additionally increments ``serve.recompiles`` and records an
  ``xla_compile`` flight-recorder event. Warmup-context and unlabeled
  compiles (a warmup sweep, an index build mid-serve) are counted but
  get no ring event: hundreds of legitimate first compiles must not
  churn the demotion/shed events out of the bounded recorder.
  ``serve.recompiles`` reads 0 right after a clean warmup.
* :func:`count_compilations` subscribes a counter to that stream for
  the duration of a block, letting the load test assert the headline
  property literally: after warmup, a stream of mixed-size requests
  causes **zero** new XLA compilations.
"""
from __future__ import annotations

import contextlib
import threading
from typing import List, Optional

import jax
import numpy as np

from ..core.errors import expects

__all__ = ["CompileCounter", "count_compilations", "warmup",
           "warmup_sharded", "install_recompile_watch", "compile_context"]


class CompileCounter:
    """Mutable count of XLA backend compiles inside a
    :func:`count_compilations` block."""

    def __init__(self):
        self.count = 0


# persistent watch state: live subscriber counters
_watch_lock = threading.Lock()
_watch_subs: List[CompileCounter] = []
_watch_installed = False
_ctx = threading.local()        # .label (str), .warmup (bool)

# the event JAX records once per executable it builds (jax._src.dispatch
# BACKEND_COMPILE_EVENT; the name is part of the public monitoring stream)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _register_listener(callback) -> None:
    """Subscribe ``callback(event, duration_s, **kw)`` to JAX's duration
    events (raises RuntimeError if this jax has no such listener API — a
    vacuous zero would silently gut every recompile assertion, and
    callers degrading gracefully catch RuntimeError)."""
    from jax import monitoring

    register = getattr(monitoring,
                       "register_event_duration_secs_listener", None)
    if register is None:
        raise RuntimeError(
            "jax.monitoring.register_event_duration_secs_listener not "
            f"found on jax {jax.__version__}; update serve.warmup to this "
            "version's compile event")
    register(callback)


@contextlib.contextmanager
def compile_context(label: str, warmup: bool = False):
    """Label compiles observed by the watch for the dynamic extent of the
    block (thread-local — the batcher worker labels its own dispatches).
    ``warmup=True`` additionally exempts them from ``serve.recompiles``.
    Cheap: two attribute writes; safe with the watch uninstalled."""
    prev = (getattr(_ctx, "label", None), getattr(_ctx, "warmup", False))
    _ctx.label, _ctx.warmup = label, warmup
    try:
        yield
    finally:
        _ctx.label, _ctx.warmup = prev


def install_recompile_watch() -> None:
    """Install the persistent compile listener (idempotent; see module
    docstring). Raises RuntimeError when this jax has no listener API."""
    global _watch_installed
    with _watch_lock:
        if _watch_installed:
            return

        def _on_event(event, duration_s, **_kw):
            if event != _COMPILE_EVENT:
                return
            with _watch_lock:
                subs = list(_watch_subs)
            for c in subs:
                c.count += 1
            label = getattr(_ctx, "label", None)
            in_warmup = bool(getattr(_ctx, "warmup", False))
            try:
                from . import metrics as _metrics

                # total compile magnitude, visible in any snapshot
                _metrics.counter("serve.compiles").inc()
                # SERVING-PATH post-warmup recompiles are the rare
                # degradation signal: only those earn a flight-recorder
                # event + the serve.recompiles counter (the batcher
                # labels every dispatch). A warmup sweep is ~100+
                # compiles and an operator building a second index
                # mid-serve is hundreds of legitimate first compiles —
                # per-compile ring events would churn the demotion/shed
                # events out of the bounded ring (same dampening as
                # faults._emit_fire / sharded _mark_shard).
                if label is not None and not in_warmup:
                    from ..core import events as _events

                    _events.record("xla_compile", label, warmup=False)
                    _metrics.counter("serve.recompiles").inc()
            except Exception:  # noqa: BLE001 - telemetry must not break compiles
                pass

        _register_listener(_on_event)
        _watch_installed = True


@contextlib.contextmanager
def count_compilations():
    """Count XLA compilations during the block (yields a
    :class:`CompileCounter`). Installs the persistent watch on first use
    and subscribes to it — nested/concurrent blocks each see every
    compile. Raises if this jax version has no compile listener API."""
    install_recompile_watch()
    counter = CompileCounter()
    with _watch_lock:
        _watch_subs.append(counter)
    try:
        yield counter
    finally:
        with _watch_lock:
            _watch_subs.remove(counter)


def warmup(search_fn, ladder, dim: int, dtype=np.float32, registry=None,
           name: str = "serve", prepare=None, engines=None,
           shapes=None) -> int:
    """Dispatch a dummy batch through ``search_fn`` at every ladder shape
    and block on each result. Returns the number of XLA compilations the
    sweep triggered (0 when the process is already warm). Records
    ``<name>.warmup.shapes`` (gauge) and ``<name>.warmup.compiles``
    (counter); warmup compiles are exempt from ``serve.recompiles``
    (they are the warmup, not a post-warmup regression).

    ``prepare``: optional zero-arg callable run BEFORE the sweep for
    index-side cache builds that must not land on the first unlucky
    request — e.g. ``lambda: brute_force.prepare_fused(index)``,
    ``lambda: cagra.prepare_traversal(index, "pq")`` (an edge store is
    seconds of gather+pack — and the PQ rung minutes of codebook
    training — at corpus scale, and the jitted ladder shapes can only
    reuse it if it exists before their first trace), or
    ``lambda: ivf_flat.prepare_host_stream(index)`` (restructuring the
    resident layout mid-traffic would recompile every bucket).

    ``engines``: optional ``{engine_name: search_fn}`` mapping — every
    engine closure is swept across the FULL ladder (``search_fn`` may
    be None then). This is how a multi-engine family pre-compiles every
    traversal engine at the serving buckets (the cagra fused megakernel
    must never be first-request compiled; the engine drift guard in
    tests/test_quality.py holds every registered engine to it).

    ``shapes``: optional explicit ``[(query_bucket, k_bucket), ...]``
    subset to warm instead of the ladder's full cross product — a
    tenant swap (:meth:`raft_tpu.serve.tenancy.Tenant.swap`) warms the
    replacement index only at the shapes that tenant has actually
    served, off the hot path."""
    from . import metrics as _metrics

    reg = registry or _metrics.default_registry
    if prepare is not None:
        prepare()
    if engines is not None:
        # an explicitly-empty mapping (every engine capability-filtered
        # out) warms nothing — it must NOT fall back to search_fn, which
        # the engines contract allows to be None
        fns = dict(engines)
    else:
        expects(search_fn is not None,
                "warmup needs a search_fn or an engines mapping")
        fns = {"": search_fn}
    if shapes is None:
        sweep = [(mb, kb) for mb in ladder.query_buckets
                 for kb in ladder.k_buckets]
    else:
        sweep = [(int(mb), int(kb)) for mb, kb in shapes]
    n_shapes = 0
    with count_compilations() as cc:
        for eng, fn in fns.items():
            tag = f":{eng}" if eng else ""
            for mb, kb in sweep:
                q = np.zeros((mb, int(dim)), dtype)
                with compile_context(f"{name}:warmup{tag}:{mb}x{kb}",
                                     warmup=True):
                    out = fn(q, kb)
                    # block the FULL output pytree: compiles are lazy
                    # until the dispatch executes, and a 3-tuple
                    # (shards_ok) or donated-closure output whose
                    # tail leaves were never forced would leave the
                    # first real request a residual trace to pay
                    jax.block_until_ready(out)
                n_shapes += 1
    reg.gauge(f"{name}.warmup.shapes").set(n_shapes)
    reg.counter(f"{name}.warmup.compiles").inc(cc.count)
    return cc.count


def warmup_sharded(index, k_buckets, m_buckets=(8, 64), *, dim=None,
                   dtype=np.float32, params=None, registry=None,
                   name: str = "sharded", fleet=None, **opts) -> int:
    """Pre-compile a sharded/fleet index's dispatch ladder: every
    (m-bucket × k-bucket) shape, for the base params AND every
    degradation auto-widen ``n_probes`` rung a shard/host loss can
    produce (:func:`raft_tpu.parallel.sharded_ann.widen_rungs`) — so a
    ``mark_host_failed`` widen or a tier step lands on a cached
    executable with ZERO compiles, and steady-state sharded serving
    never traces.

    The searchers themselves stay sync-free on the hot path — all the
    blocking happens here, inside the warmup compile context, so the
    sweep's compiles are counted but exempt from ``serve.recompiles``
    and the ``xla_compile`` ring (module docstring).

    ``fleet``: pass the owning :class:`~raft_tpu.parallel.fleet.Fleet`
    for fleet-adopted indexes — the rung closures then dispatch through
    ``Fleet.search`` so a budgeted build's cold-list merge warms with
    the resident programs. ``dim`` defaults to the index's query
    dimensionality; extra ``opts`` reach the searchers (e.g.
    ``allow_partial=True``, ``merge_engine=``). Returns the compile
    count of the sweep (0 when already warm)."""
    from ..parallel import sharded_ann

    if fleet is not None:
        engines = fleet.warmup_searchers(index, params, **opts)
    else:
        engines = sharded_ann.warmup_searchers(index, params, **opts)
    if dim is None:
        dim = sharded_ann.searcher_dim(index)
    shapes = [(int(mb), int(kb)) for mb in m_buckets for kb in k_buckets]
    return warmup(None, None, dim, dtype, registry=registry, name=name,
                  engines=engines, shapes=shapes)
