"""Measurement-driven engine/tile selection.

The reference hard-codes per-arch dispatch heuristics (e.g.
``choose_select_k_algorithm``, matrix/detail/select_k-inl.cuh:48-72, and the
ivf_pq kernel-variant table, detail/ivf_pq_search.cuh:615-676) tuned offline
per GPU generation. A TPU deployment sees far more variance — chip
generation, VMEM size and dispatch cost all move the crossovers — so
raft_tpu picks engines by *measuring them on the device actually in
use* and caching the winner.

Methodology note: each candidate is timed with a ``block_until_ready`` per
call (some backends elide dead dispatches, so blocking once after N calls
under-reports by orders of magnitude) and the median of several calls is
used. Winners are cached in-process and, when ``RAFT_TPU_AUTOTUNE_CACHE``
names a JSON file (or the default per-user cache path is writable), across
processes.

Nothing autotunes implicitly under ``jit`` tracing: callers consult
``lookup`` (cache-only, never measures) on traced values and expose an
explicit ``tune``/warmup entry point for eager callers and the bench.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax

from ..core import logging as rlog

__all__ = ["shape_bucket", "lookup", "record", "forget", "entries",
           "measure", "measure_throughput", "measure_value_read_wall",
           "tune_best", "cache_path", "load_cache", "save_cache",
           "TimingUnreliableError"]


class TimingUnreliableError(RuntimeError):
    """A median timed below the physical plausibility floor: no honest
    number exists for this window. Callers should skip the measurement
    rather than record an impossible one."""

_MEM_CACHE: Dict[str, str] = {}
# keys recorded with persist=False (guard demotions): NEVER written to
# disk, even when a later ordinary record() triggers save_cache()
_EPHEMERAL: set = set()
_DISK_LOADED = False

# count of plausibility-floor trips (see measure); benches report it so
# a recorded number can be traced to a defended measurement window
suspect_events = 0


def cache_path() -> Optional[str]:
    """Resolve the on-disk cache location (None disables persistence)."""
    p = os.environ.get("RAFT_TPU_AUTOTUNE_CACHE")
    if p == "":
        return None
    if p:
        return p
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "raft_tpu", "autotune.json")


def load_cache() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    p = cache_path()
    if not p or not os.path.exists(p):
        return
    try:
        with open(p) as f:
            disk = json.load(f)
        for k, v in disk.items():
            _MEM_CACHE.setdefault(k, v)
    except (OSError, ValueError) as e:
        rlog.log_warn("autotune cache %s unreadable: %s", p, e)


def save_cache() -> None:
    p = cache_path()
    if not p:
        return
    try:
        os.makedirs(os.path.dirname(p), exist_ok=True)
        tmp = p + f".tmp{os.getpid()}"
        durable = {k: v for k, v in _MEM_CACHE.items()
                   if k not in _EPHEMERAL}
        with open(tmp, "w") as f:
            json.dump(durable, f, indent=1, sort_keys=True)
        os.replace(tmp, p)
    except OSError as e:
        rlog.log_warn("autotune cache %s unwritable: %s", p, e)


def _log2_bucket(x: int) -> int:
    return max(0, int(x - 1).bit_length())


def shape_bucket(family: str, **dims) -> str:
    """Cache key: backend + device kind + family + log2-bucketed dims.

    Integer dims bucket by log2; string values pass through verbatim as
    categorical tags (e.g. the brute-force race keys on the corpus
    storage dtype — ``store='bfloat16'`` — because HBM-traffic-bound
    crossovers move with the element width, and a winner measured for
    one storage mode must not steer another's dispatch)."""
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", dev.platform).replace(" ", "_")
    parts = [dev.platform, kind, family]
    parts += [f"{name}{_log2_bucket(v) if isinstance(v, int) else v}"
              for name, v in sorted(dims.items())]
    return ":".join(parts)


def lookup(key: str) -> Optional[str]:
    """Cache-only lookup; safe to call from trace time. Never measures."""
    load_cache()
    return _MEM_CACHE.get(key)


def record(key: str, choice: str, persist: bool = True) -> None:
    """Record a winner. ``persist=False`` keeps the entry in-process only
    (used for guard demotions from transient failures that must not
    poison later processes through the disk cache) — such keys are also
    excluded from every later ``save_cache`` dump."""
    load_cache()
    _MEM_CACHE[key] = choice
    if persist:
        _EPHEMERAL.discard(key)
        save_cache()
    else:
        _EPHEMERAL.add(key)
    if ":guard:" not in key:
        # flight recorder: race verdicts steer future dispatch, so they
        # are operational events (guard demotions already record their
        # own richer guarded_demotion event — skip the double entry)
        try:
            from ..core import events as _events

            _events.record("autotune_verdict", key, choice=choice,
                           persist=persist)
        except Exception:  # noqa: BLE001 - telemetry must not break tuning
            pass


def entries() -> Dict[str, str]:
    """Point-in-time copy of every cached verdict (engine race winners
    AND guard demotions) — the debugz verdict table."""
    load_cache()
    return dict(_MEM_CACHE)


def forget(key: str) -> None:
    """Drop an entry (guard reset / test isolation). A durable (persisted)
    entry also rewrites the disk cache — an operator re-arming a demoted
    site must not have the stale demotion resurrected by the next
    process's load_cache."""
    was_durable = key in _MEM_CACHE and key not in _EPHEMERAL
    _MEM_CACHE.pop(key, None)
    _EPHEMERAL.discard(key)
    if was_durable:
        save_cache()


def _value_read(out) -> None:
    """Force a host-side value read of the output: some backends lie
    about ``block_until_ready`` itself (buffers report ready before the
    compute ran), and only a host value transitively dependent on the
    output is proof of completion. Costs one tiny dispatch + round trip."""
    import jax.numpy as jnp

    leaves = [l for l in jax.tree_util.tree_leaves(out)
              if isinstance(l, jax.Array)]
    if leaves:
        x = leaves[0].ravel()[:1].astype(jnp.float32)
        float(jnp.where(jnp.isfinite(x), x, 0.0)[0])


def _timed_reps(fn: Callable, args, reps: int, out0, value_read=False):
    import jax.numpy as jnp

    out = out0
    first = args[0] if args else None
    can_vary = (isinstance(first, jax.Array)
                and jnp.issubdtype(first.dtype, jnp.inexact))

    ts = []
    for r in range(reps):
        if can_vary:
            a0 = _perturbed(first, out, r)
            # settle the perturbation ops before the timed window opens:
            # for microsecond-scale probes the 3-4 eager ops building a0
            # would otherwise still be in flight at t0
            jax.block_until_ready(a0)
            args_r = (a0,) + args[1:]
        else:
            args_r = args
        t0 = time.perf_counter()
        out = fn(*args_r)
        jax.block_until_ready(out)
        if value_read:
            _value_read(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def measure(fn: Callable, *args, reps: int = 5, out0=None,
            suspect_floor_s: float = 0.0,
            value_read: bool = False) -> float:
    """Median seconds per call, one blocking sync per call (see module
    docstring for why per-call blocking is load-bearing).

    Each rep scales the first float-array argument by a distinct factor
    a few ulps above 1 (dtype-aware — an additive 1e-6 would round away
    entirely for bf16 or large-magnitude f32) AND adds a *real* (nonzero,
    1e-12-scaled) dependency on the previous rep's output — a `* 0` chain
    could be shortcut by a value-analyzing backend, and the chain +
    perturb makes every rep distinct, ordered, real work.

    ``out0``: pre-warmed output of ``fn(*args)`` — pass it to skip the
    internal warmup call when the caller already compiled+ran ``fn``.

    ``suspect_floor_s``: physical-plausibility floor — a lower bound no
    real call could beat (e.g. FLOPs over the chip's peak, see
    ``bench.roofline.PEAKS``). A median below it raises
    :class:`TimingUnreliableError`: recording nothing beats recording an
    impossible number. 0 disables the check.
    """
    if out0 is None:
        out0 = fn(*args)
        jax.block_until_ready(out0)      # compile + warm

    med = _timed_reps(fn, args, reps, out0, value_read=value_read)
    _check_floor("measure", med, suspect_floor_s)
    return med


def _check_floor(what: str, med: float, floor_s: float) -> None:
    """Raise :class:`TimingUnreliableError` for a median below the
    physical-plausibility floor (counted in ``suspect_events``)."""
    if floor_s and med < floor_s:
        global suspect_events
        suspect_events += 1
        raise TimingUnreliableError(
            f"{what}: median {med:.3g}s below plausibility floor "
            f"{floor_s:.3g}s")


def _perturbed(first, out_prev, r: int):
    """Next-rep first argument: a few ulps of multiplicative variation per
    rep plus a real (nonzero, tiny-scaled) dependency on the previous
    output — every rep is distinct, ordered, uncacheable work (see
    ``measure``)."""
    import jax.numpy as jnp

    ulp = float(jnp.finfo(first.dtype).eps)
    a0 = first * jnp.asarray(1 + (r + 1) * 4 * ulp, first.dtype)
    leaves = jax.tree_util.tree_leaves(out_prev)
    if leaves and isinstance(leaves[0], jax.Array):
        dep = leaves[0].ravel()[0]
        depf = jnp.where(jnp.isfinite(dep), dep, 0).astype(jnp.float32)
        sgn = jnp.sign(depf) + (depf == 0)
        a0 = a0 + (sgn * (4 * float(jnp.finfo(first.dtype).tiny))
                   ).astype(first.dtype)
    return a0


class JitArgFn:
    """``tune_best`` candidate wrapper for engines whose jitted callable
    takes a large operand (an index pytree) as a jit ARGUMENT —
    closure-baking it would trace the arrays into the HLO as constants
    (a compile request the size of the index)."""

    def __init__(self, fitted: Callable, arg):
        self._f = fitted
        self._arg = arg

    def __call__(self, qq):
        return self._f(qq, self._arg)


def measure_throughput(fn: Callable, *args, depth: int = 6, reps: int = 3,
                       out0=None, suspect_floor_s: float = 0.0) -> float:
    """Steady-state seconds per call with ``depth`` in-flight calls.

    ``measure`` blocks once per call, so every call pays the full
    dispatch round trip — that is a *latency* number. Serving systems and the reference harness measure
    *throughput*: Google Benchmark's ``items_per_second`` runs iterations
    back-to-back with one wall clock around the whole loop
    (cpp/bench/ann/src/common/benchmark.hpp:337). This does the same:
    ``depth`` calls are enqueued with only the final output blocked, so
    dispatch overlaps device compute.

    Elision/replay defenses carry over from ``measure``: every call's
    first float-array argument is perturbed by a distinct ulp factor AND
    carries a real data dependency on the *previous call's output* — the
    chain forces ordering, makes each dispatch value-distinct, and means
    blocking the last output transitively waits for all of them.

    ``suspect_floor_s`` is a per-call plausibility floor as in
    ``measure`` (compared against wall/depth). Returns
    median-of-``reps`` seconds per call.
    """
    import jax.numpy as jnp

    if out0 is None:
        out0 = fn(*args)
        jax.block_until_ready(out0)      # compile + warm

    first = args[0] if args else None
    can_vary = (isinstance(first, jax.Array)
                and jnp.issubdtype(first.dtype, jnp.inexact))

    def run_window(f, out_prev, base):
        # the perturbation counter spans windows: restarting it per
        # window would make window 2+ bitwise replays of window 1
        t0 = time.perf_counter()
        out = out_prev
        for r in range(depth):
            if can_vary:
                a0 = _perturbed(first, out, base + r)
                out = f(a0, *args[1:])
            else:
                out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / depth, out

    ts = []
    out = out0
    for w in range(reps):
        dt, out = run_window(fn, out, w * depth)
        ts.append(dt)
    ts.sort()
    med = ts[len(ts) // 2]
    _check_floor("measure_throughput", med, suspect_floor_s)
    return med


def measure_value_read_wall(fn: Callable, inputs: Sequence, *args,
                            warm_input=None) -> float:
    """Wall seconds/call over ``inputs`` with a VALUE-READ close.

    The strongest timing this library has against elided work: each
    call gets a genuinely different first input, calls are dispatched
    back-to-back (dispatch overlaps compute), the FIRST array leaf of
    every output folds into a scalar accumulator, and the window closes
    with a host ``float()`` of that accumulator — which cannot
    materialize before the compute feeding those leaves ran
    (see bench.py's methodology notes).
    NOTE the guarantee covers the dependency chain of each output's
    first leaf; when ``fn`` is one jitted executable (the usual case)
    that is the whole program, but outputs assembled from several
    independent dispatches are only partially pinned. Pass
    ``warm_input`` (a throwaway input NOT in ``inputs``) to warm/compile
    outside the window so no timed call repeats content the backend has
    already served.
    """
    import jax.numpy as jnp

    def fold(out):
        leaves = [l for l in jax.tree_util.tree_leaves(out)
                  if isinstance(l, jax.Array)]
        x = leaves[0].ravel()[:1].astype(jnp.float32)
        return jnp.where(jnp.isfinite(x), x, 0.0)[0]

    if warm_input is not None:
        float(fold(fn(warm_input, *args)))
    t0 = time.perf_counter()
    acc = None
    for inp in inputs:
        s = fold(fn(inp, *args))
        acc = s if acc is None else acc + s
    _ = float(acc)
    return (time.perf_counter() - t0) / len(inputs)


def tune_best(key: str, candidates: Mapping[str, Callable], *args,
              reps: int = 5,
              force: bool = False,
              suspect_floor_s: float = 0.0,
              value_read: bool = False) -> Tuple[str, Dict[str, float]]:
    """Measure every candidate on device, record + return the winner.

    Returns (winner name, {name: median seconds}). Failures (e.g. a kernel
    whose constraints reject the shape) disqualify that candidate. When no
    candidate produced an honest timing but at least one was merely
    unmeasurable (TimingUnreliableError — below its plausibility floor), the
    first such working candidate is returned uncached; when every
    candidate genuinely failed, RuntimeError is raised.
    """
    if not force:
        hit = lookup(key)
        if hit in candidates:
            return hit, {}
    timings: Dict[str, float] = {}
    unreliable_names: list = []
    for name, fn in candidates.items():
        try:
            timings[name] = measure(fn, *args, reps=reps,
                                    suspect_floor_s=suspect_floor_s,
                                    value_read=value_read)
        except TimingUnreliableError as e:
            unreliable_names.append(name)
            rlog.log_warn("autotune %s: candidate %s unmeasurable: %s",
                          key, name, e)
        except Exception as e:  # noqa: BLE001 - any engine failure = skip
            rlog.log_warn("autotune %s: candidate %s failed: %s", key, name, e)
    if not timings:
        if unreliable_names:
            # at least one engine WORKS but timed below its floor: fall back to the first such candidate WITHOUT
            # caching, so a later honest window re-measures (genuinely
            # failing candidates are never the fallback)
            fallback = unreliable_names[0]
            rlog.log_warn("autotune %s: no measurable candidate (below "
                          "the timing floor); defaulting to %r (not cached)",
                          key, fallback)
            return fallback, {}
        raise RuntimeError(f"autotune {key}: every candidate failed")
    winner = min(timings, key=timings.get)
    record(key, winner)
    rlog.log_info("autotune %s -> %s (%s)", key, winner,
              {n: f"{t*1e3:.1f}ms" for n, t in timings.items()})
    return winner, timings
