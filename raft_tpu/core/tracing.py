"""Tracing/profiling ranges: TPU-native analog of the reference's NVTX layer.

Reference: raft/core/nvtx.hpp:84 (RAII ``nvtx::range`` pushed at every public
entry point). Here every range enters a ``jax.profiler.TraceAnnotation``, so
it shows up on the host thread's line of an operator's profile (TensorBoard,
Perfetto, ``jax.profiler.ProfileData``) on the same clock as the device's
ops. No switch gates it: with no profiler collecting, an annotation costs
about half a microsecond, and names follow ``raft_tpu::<module>::<step>``.

A span *timer* can additionally be installed with :func:`set_timer`
(``raft_tpu.serve.metrics.enable_span_metrics`` does): every range and
annotated call then reports its wall duration under its span name,
giving the serving metrics per-stage latency histograms for free. A
range given ``out=`` also writes its duration into that dict, which is
how the serving batcher times its stages once, from its own spans.

Request-lifecycle layer (docs/observability.md): the serving runtime
stamps every request with a **trace ID** (:func:`new_trace_id`) and
binds the active IDs around dispatch (:func:`bind_trace`), so anything
that fires mid-dispatch — a guarded demotion, an injected fault, an XLA
recompile (all recorded in :mod:`raft_tpu.core.events`) — is stamped
with the requests it hit. Sampled requests additionally log their full
stage decomposition into a bounded in-process **span log**
(:func:`log_spans` / :func:`recent_spans`). Sampling is governed by
``RAFT_TPU_TRACE_SAMPLE`` (:func:`sample_rate`, validated float in
[0, 1], default 0 = off).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import math
import os
import threading
import time
import uuid
from typing import Callable, Dict, Iterator, List, Optional

import jax

__all__ = ["range", "annotate", "set_timer", "new_trace_id", "bind_trace",
           "current_traces", "current_trace", "sample_rate", "log_spans",
           "recent_spans", "clear_span_log", "set_span_log_capacity"]

# (span_name, seconds) observer; None = timing off (the default)
_timer: Optional[Callable[[str, float], None]] = None


def set_timer(fn: Optional[Callable[[str, float], None]]) -> None:
    """Install (or clear with None) the span-duration observer. Spans
    report host wall time between entry and exit — for searches that is
    dispatch-to-value time, the serving-relevant quantity."""
    global _timer
    _timer = fn


@contextlib.contextmanager
def range(name: str,  # noqa: A001 - mirrors nvtx::range
          out: Optional[Dict[str, float]] = None) -> Iterator[None]:
    """Context-managed trace range (analog of ``raft::common::nvtx::range``).

    Always a ``TraceAnnotation`` in the profile; its wall duration goes
    to ``out[name]`` when ``out`` is given and to the installed timer."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        timer = _timer
        if out is not None or timer is not None:
            dt = time.perf_counter() - t0
            if out is not None:
                out[name] = dt
            if timer is not None:
                timer(name, dt)


def annotate(name: str | None = None):
    """Decorator form: wrap a public API function in a trace range.

    The wrapper carries ``__raft_traced__ = True`` so the drift-guard
    test (tests/test_telemetry.py) can assert every public
    ``neighbors/*`` search/build entry point stays instrumented."""

    def deco(fn):
        label = name or f"raft_tpu::{fn.__qualname__}"

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with range(label):
                return fn(*args, **kwargs)

        wrapper.__raft_traced__ = True
        return wrapper

    return deco


# -- trace IDs -------------------------------------------------------------
# Thread-local, not a contextvar: the serving worker is one daemon thread
# that binds per-batch, and probes (guarded_call, faults, the compile
# spy) run synchronously on that same thread.
_trace = threading.local()


# process-random prefix + atomic counter: unique without paying a
# per-request urandom syscall on the submit hot path (every Request
# gets an ID even with telemetry fully off — events stamp lazily)
_id_prefix = uuid.uuid4().hex[:8]
_id_counter = itertools.count(1)


def new_trace_id() -> str:
    """A fresh 16-hex trace ID (one per request entering the serving
    pipeline)."""
    return f"{_id_prefix}{next(_id_counter) & 0xFFFFFFFF:08x}"


@contextlib.contextmanager
def bind_trace(*trace_ids: str) -> Iterator[None]:
    """Bind the active trace IDs for the dynamic extent of the block (the
    requests currently being dispatched). Events recorded inside
    (:func:`raft_tpu.core.events.record` with ``trace_id=None``) are
    stamped with them. Nests: the previous binding is restored."""
    prev = getattr(_trace, "ids", ())
    _trace.ids = tuple(trace_ids)
    try:
        yield
    finally:
        _trace.ids = prev


def current_traces() -> tuple:
    """The trace IDs bound on this thread (empty tuple when none)."""
    return getattr(_trace, "ids", ())


def current_trace() -> Optional[str]:
    """First bound trace ID, or None."""
    ids = getattr(_trace, "ids", ())
    return ids[0] if ids else None


# -- sampling knob ---------------------------------------------------------
def sample_rate(value=None, env: str = "RAFT_TPU_TRACE_SAMPLE",
                name: str = "trace_sample") -> float:
    """Resolve and validate a sampling-rate knob.

    ``value=None`` reads the ``env`` variable (default
    ``RAFT_TPU_TRACE_SAMPLE``; ``0`` = sampling off); an explicit value
    (float or string) bypasses the env. The rate must parse as a float
    in [0, 1] — anything else raises ValueError at construction time,
    not silently at the first sampled request. Other samplers (the
    recall sentinel's ``RAFT_TPU_RECALL_SAMPLE``) reuse this validation
    by passing their own ``env``/``name``."""
    # blame the actual source: the env var only on the env-read path
    src = env if value is None else name
    raw = os.environ.get(env, "0") if value is None else value
    try:
        r = float(raw)
    except (TypeError, ValueError):
        raise ValueError(
            f"{src} must be a float in [0, 1], got {raw!r}")
    if math.isnan(r) or not 0.0 <= r <= 1.0:
        raise ValueError(
            f"{src} must be in [0, 1], got {raw!r}")
    return r


# -- sampled per-request span log ------------------------------------------
_span_lock = threading.Lock()
_span_log: collections.deque = collections.deque(maxlen=256)


def log_spans(trace_id: str, stages: Dict[str, float], **meta) -> dict:
    """Append one sampled request's stage decomposition to the span log.

    ``stages`` maps stage name -> seconds (the serving batcher records
    queue_wait / bucket_pad / dispatch / device / demux); ``meta`` is
    free-form context (rows, k, dispatch bucket)."""
    entry = {"ts": time.time(), "trace_id": trace_id, "stages": dict(stages)}
    if meta:
        entry.update(meta)
    with _span_lock:
        _span_log.append(entry)
    return entry


def recent_spans(n: Optional[int] = None) -> List[dict]:
    """Most recent sampled span records, oldest first (``n=None`` = all,
    ``n=0`` = none)."""
    with _span_lock:
        items = list(_span_log)
    if n is None:
        return items
    return items[-n:] if n > 0 else []


def clear_span_log() -> None:
    with _span_lock:
        _span_log.clear()


def set_span_log_capacity(n: int) -> None:
    """Resize the span log (keeps the newest records)."""
    global _span_log
    with _span_lock:
        _span_log = collections.deque(_span_log, maxlen=int(n))
