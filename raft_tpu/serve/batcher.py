"""Shape-bucketed micro-batching scheduler: the query-serving runtime's
core loop.

Every search entry point in this library is a bare function call — one
caller, one pre-shaped query batch. A serving stack has neither: many
concurrent callers, each with 1..few-hundred queries and its own k and
latency budget. The standard inference-server answer, TPU-idiomatic
form:

* **Coalesce**: requests drain from an :class:`~.admission.AdmissionQueue`
  under a max-wait / max-batch policy and are concatenated row-wise.
* **Bucket, don't recompile**: the concatenated block is padded up to a
  fixed :class:`BucketLadder` of (query-rows × k) shapes. XLA
  executables are cached by input shape, so after
  :meth:`MicroBatcher.warmup` has dispatched every ladder shape once,
  steady-state traffic of ANY mix of request sizes hits only cached
  executables — zero recompiles (asserted by the load test with
  :func:`~.warmup.count_compilations`). Padding rows are zeros and k is
  rounded up a bucket; both are sliced away at demux (top-k lists are
  sorted, so the first k of a k-bucket answer IS the exact k answer, and
  per-row results are independent of other rows in the batch).
* **Dispatch through the existing paths**: the batcher is generic over a
  ``search_fn(queries, k, res=None)`` closure — build one with the
  ``make_searcher`` helpers on brute_force / ivf_flat / ivf_pq / cagra
  or :func:`raft_tpu.parallel.sharded_ann.make_searcher` (whose
  ``allow_partial=True`` degraded merges surface ``shards_ok`` per
  response and in the metrics).
* **Deadlines end-to-end**: a request's
  :class:`~raft_tpu.core.deadline.Deadline` is enforced at admission pop
  and again pre-dispatch (shed, ``<name>.shed``); the tightest live
  deadline rides into the search as ``res``, so a mid-batch expiry
  raises between chunk dispatches and completed rows are still
  delivered — fully-covered requests succeed, the rest fail with their
  own partial slice attached (``<name>.deadline_exceeded``).

The worker is one daemon thread: TPU dispatch is asynchronous, so a
single submitting thread keeps the device pipelined while callers block
on per-request futures. Dispatch and demux are **double-buffered**
(ISSUE 12): while batch N's device→host transfer and per-request
slicing run on the host, batch N+1 is already dispatched and computing
— the demux wall overlaps device time instead of serializing with it.
Depth is exactly two, and an idle queue demuxes immediately, so the
overlap never delays delivery. Pair with
``make_searcher(..., donate=)`` closures so the in-flight pair does not
double the transient device-buffer footprint (docs/serving.md).

A popped batch splits per k bucket before dispatch (one k per
executable), so heavily mixed-k traffic trades fill ratio for
k-padding — watch ``<name>.batch_fill`` and give hot k values their own
bucket rather than widening an existing one.

**Request-lifecycle telemetry** (docs/observability.md): the worker's
steps are trace ranges, one each per batch — ``raft_tpu::serve::pop``
(waiting for requests), ``dispatch`` with ``pad`` inside, ``demux``
with ``fetch`` and ``deliver`` inside — so an operator's profile names
what the worker did in every device idle gap. Every request carries a
trace ID, and with ``trace_sample > 0`` (ctor arg or the
``RAFT_TPU_TRACE_SAMPLE`` env knob) sampled batches record a five-stage
latency decomposition per request — ``queue_wait`` (submit → worker
pop), ``bucket_pad`` (the ``pad`` range), ``dispatch`` (the
``dispatch`` range: late shed, pad and the search call's enqueue),
``device`` (a ``block_until_ready`` probe — measured only on sampled
batches, so steady-state dispatch stays asynchronous), ``demux`` (the
``demux`` range: device→host transfer, slicing and delivery) — into
``<name>.stage.*_s`` histograms and the sampled span log
(:func:`raft_tpu.core.tracing.recent_spans`). The worker binds the
batch's trace IDs around dispatch, so demotions/faults/recompiles
firing mid-batch land in the flight recorder stamped with the requests
they hit.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core import events, faults, logging as rlog, tracing
from ..core.deadline import Deadline, DeadlineExceeded
from ..core.errors import expects
from . import warmup as _warmup
from .admission import AdmissionQueue, Request, SearchResult

__all__ = ["BucketLadder", "MicroBatcher", "coalesce_block"]

# the five per-request stages (docs/observability.md)
STAGES = ("queue_wait", "bucket_pad", "dispatch", "device", "demux")

# the worker's trace ranges (docs/observability.md)
POP = "raft_tpu::serve::pop"
DISPATCH = "raft_tpu::serve::dispatch"
PAD = "raft_tpu::serve::pad"
DEMUX = "raft_tpu::serve::demux"
FETCH = "raft_tpu::serve::fetch"
DELIVER = "raft_tpu::serve::deliver"


def triage_partial(live: Sequence, offs: Sequence[int],
                   e: DeadlineExceeded):
    """Classify every request of a mid-batch deadline expiry (pure —
    callers credit their own counters/events): returns
    ``(served, expired, retry)`` where ``served`` is
    ``[(request, SearchResult)]`` for rows fully inside the delivered
    partial, ``expired`` is ``[(request, covered_rows, own_partial)]``
    for requests whose OWN deadline is spent (``own_partial`` their
    slice or None), and ``retry`` the collateral co-batched requests to
    re-dispatch. Shared by :class:`MicroBatcher` and the multi-tenant
    fabric so the slicing boundary math and the termination argument
    (every recursion drops the expired owners, so the retried group's
    tightest deadline is strictly looser) live in exactly one place."""
    from .admission import SearchResult

    if e.partial is not None:
        pd, pi = np.asarray(e.partial[0]), np.asarray(e.partial[1])
        done = pd.shape[0]
    else:
        pd = pi = None
        done = 0
    served, expired, retry = [], [], []
    for r, o in zip(live, offs):
        if o + r.rows <= done:
            served.append((r, SearchResult(pd[o:o + r.rows, :r.k],
                                           pi[o:o + r.rows, :r.k], None)))
            continue
        if r.deadline is None or not r.deadline.expired():
            retry.append(r)
            continue
        own = None
        if done > o:
            own = (pd[o:done, :r.k], pi[o:done, :r.k])
        expired.append((r, max(0, done - o), own))
    return served, expired, retry


def coalesce_block(live: Sequence, mb: int, dim: int):
    """Concatenate the live requests' query rows into one zero-padded
    (mb, dim) f32 block; returns ``(block, offsets)`` with each
    request's row offset. Shared by :class:`MicroBatcher` and the
    multi-tenant fabric (:mod:`raft_tpu.serve.tenancy`) so co-batched
    dispatch and demux slicing agree on one layout."""
    block = np.zeros((mb, dim), np.float32)
    offs: List[int] = []
    off = 0
    for r in live:
        block[off:off + r.rows] = r.queries
        offs.append(off)
        off += r.rows
    return block, offs


class BucketLadder:
    """The fixed set of dispatch shapes: ascending query-row buckets ×
    ascending k buckets. ``bucket_queries``/``bucket_k`` round a request
    up to the smallest covering bucket; anything beyond the largest
    bucket is a submit-time error (split such callers upstream)."""

    def __init__(self,
                 query_buckets: Sequence[int] = (8, 32, 128, 512),
                 k_buckets: Sequence[int] = (16, 64, 128)):
        self.query_buckets = tuple(int(b) for b in query_buckets)
        self.k_buckets = tuple(int(b) for b in k_buckets)
        for name, bs in (("query_buckets", self.query_buckets),
                         ("k_buckets", self.k_buckets)):
            expects(len(bs) > 0, "%s must be non-empty", name)
            expects(all(b > 0 for b in bs), "%s must be positive", name)
            expects(tuple(sorted(set(bs))) == bs,
                    "%s must be ascending and unique, got %s", name, bs)

    @property
    def max_queries(self) -> int:
        return self.query_buckets[-1]

    @property
    def max_k(self) -> int:
        return self.k_buckets[-1]

    def bucket_queries(self, m: int) -> int:
        expects(1 <= m <= self.max_queries,
                "request of %d query rows outside ladder (max bucket %d)",
                m, self.max_queries)
        return next(b for b in self.query_buckets if b >= m)

    def bucket_k(self, k: int) -> int:
        expects(1 <= k <= self.max_k,
                "k=%d outside ladder (max k bucket %d)", k, self.max_k)
        return next(b for b in self.k_buckets if b >= k)

    def shapes(self) -> List[Tuple[int, int]]:
        """Every (query_bucket, k_bucket) pair — the warmup set."""
        return [(mb, kb) for mb in self.query_buckets
                for kb in self.k_buckets]


class MicroBatcher:
    """Micro-batching front end over one built index's search closure.

    ``search_fn(queries, k, res=None) -> (distances, indices)`` (or a
    3-tuple ending in ``shards_ok`` for degraded sharded searchers) must
    accept any ladder shape; ``dim`` is the query width used for padding
    and warmup. ``autostart=False`` lets tests enqueue a deterministic
    backlog before the worker drains it. ``trace_sample`` is the
    request-telemetry sampling rate (None reads ``RAFT_TPU_TRACE_SAMPLE``,
    validated; 0 disables stage decomposition entirely — see module
    docstring). ``sentinel``: an optional
    :class:`~raft_tpu.serve.quality.RecallSentinel` — served requests
    are offered to it after delivery for online recall estimation
    (docs/observability.md "Quality"). ``degrade``: an optional
    :class:`~raft_tpu.serve.degrade.BrownoutController` — its current
    level scales the coalescing max-wait (pair it with
    ``make_searcher(..., degrade=...)`` so search params degrade too;
    docs/robustness.md).
    """

    def __init__(self, search_fn: Callable, dim: int, *,
                 ladder: Optional[BucketLadder] = None,
                 max_wait_s: float = 0.002,
                 max_batch_requests: int = 64,
                 queue_depth: int = 256,
                 registry=None,
                 name: str = "serve",
                 autostart: bool = True,
                 trace_sample: Optional[float] = None,
                 sentinel=None,
                 degrade=None,
                 clock: Callable[[], float] = time.monotonic):
        from . import metrics as _metrics

        self._search = search_fn
        self._dim = int(dim)
        self.ladder = ladder or BucketLadder()
        self._max_wait_s = float(max_wait_s)
        self._max_batch = int(max_batch_requests)
        self._name = name
        self._clock = clock
        self._reg = registry or _metrics.default_registry
        # optional quality probe (serve/quality.RecallSentinel): served
        # requests are offered AFTER delivery; its disabled cost is one
        # None check here plus one flag check inside offer()
        self._sentinel = sentinel
        # optional brownout controller (serve/degrade.py): under a
        # latency brownout the batcher widens its max-wait by the
        # level's scale — bigger batches, fewer dispatches
        self._degrade = degrade
        rate = tracing.sample_rate(trace_sample)
        # stage telemetry: None = off (the hot path checks exactly this);
        # every ceil(1/rate)-th batch gets the full five-stage story
        self._probe_every = math.ceil(1.0 / rate) if rate > 0 else 0
        self._probe_tick = 0
        self._stages = None
        if self._probe_every:
            self._stages = {s: self._reg.histogram(f"{name}.stage.{s}_s")
                            for s in STAGES}
        try:
            # always-on recompile stream: a post-warmup recompile must be
            # visible in any snapshot (serve.recompiles + xla_compile
            # events labeled with this batcher's dispatch buckets)
            _warmup.install_recompile_watch()
        except RuntimeError as e:
            rlog.log_warn("serve %s: recompile watch unavailable (%s)",
                          name, e)
        self.queue = AdmissionQueue(queue_depth, registry=self._reg,
                                    prefix=name, clock=clock)
        r = self._reg
        self._requests = r.counter(f"{name}.requests")
        self._served = r.counter(f"{name}.served")
        self._batches = r.counter(f"{name}.batches")
        self._errors = r.counter(f"{name}.errors")
        self._dlx = r.counter(f"{name}.deadline_exceeded")
        self._degraded = r.counter(f"{name}.degraded_batches")
        self._healthy = r.gauge(f"{name}.healthy_shards")
        self._latency = r.histogram(f"{name}.latency_s")
        self._batch_latency = r.histogram(f"{name}.batch_latency_s")
        self._fill = r.histogram(f"{name}.batch_fill",
                                 _metrics.RATIO_BUCKETS)
        self._thread: Optional[threading.Thread] = None
        if autostart:
            self.start()

    # -- lifecycle --------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name=f"{self._name}-batcher", daemon=True)
        self._thread.start()

    def close(self, timeout: float = 10.0) -> None:
        """Stop admitting, drain what is queued, stop the worker."""
        self.queue.close()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self) -> "MicroBatcher":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- client API -------------------------------------------------------
    def submit(self, queries, k: int,
               deadline: Optional[Deadline] = None) -> Request:
        """Enqueue a request; returns its future. Raises
        :class:`~.admission.QueueFullError` under backpressure and
        ValueError-family errors for off-ladder shapes."""
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        expects(q.ndim == 2 and q.shape[1] == self._dim,
                "queries must be (m, %d), got %s", self._dim, q.shape)
        self.ladder.bucket_queries(q.shape[0])   # validate against ladder
        self.ladder.bucket_k(k)
        req = Request(q, k, deadline, enqueued_at=self._clock())
        self.queue.submit(req)
        self._requests.inc()
        return req

    def search(self, queries, k: int, deadline: Optional[Deadline] = None,
               timeout: Optional[float] = None) -> SearchResult:
        """Synchronous convenience: submit + block for the result."""
        return self.submit(queries, k, deadline).result(timeout)

    def warmup(self) -> int:
        """Pre-compile every ladder shape through the live search path;
        returns the number of XLA compilations that took (0 on a warm
        process). See :func:`raft_tpu.serve.warmup.warmup`."""
        return _warmup.warmup(self._search, self.ladder, self._dim,
                              registry=self._reg, name=self._name)

    # -- worker -----------------------------------------------------------
    def _run(self) -> None:
        # double-buffered dispatch (docs/serving.md): `pending` is a
        # dispatched-but-not-demuxed group. Batch N+1 is DISPATCHED
        # before batch N is demuxed, so the device computes N+1 while
        # the host blocks on N's device→host transfer — the demux wall
        # no longer serializes with device time. Depth is exactly two:
        # one group on device, one being coalesced. When the queue is
        # idle the pending group is demuxed immediately (overlap must
        # never delay delivery behind the coalescing wait).
        pending = None
        while True:
            wait = self._max_wait_s
            if self._degrade is not None:
                try:
                    wait *= self._degrade.max_wait_scale()
                except Exception:  # noqa: BLE001 - a broken controller
                    pass           # must not stall the worker
            if pending is not None and len(self.queue) == 0:
                pending = self._safe_demux(pending)
            with tracing.range(POP):
                batch = self.queue.pop_batch(
                    self._max_batch, 0.0 if pending is not None else wait,
                    max_rows=self.ladder.max_queries)
            if not batch:
                if pending is not None:
                    pending = self._safe_demux(pending)
                    continue
                if self.queue.closed:
                    return
                continue
            # operator knob: simulate a stalled worker/device
            # (RAFT_TPU_FAULTS='slow_dispatch@<name>.batch=0.1')
            faults.sleep_if(f"{self._name}.batch")
            now = self._clock()
            for r in batch:
                r.dequeued_at = now
            groups: dict = {}
            for r in batch:
                groups.setdefault(self.ladder.bucket_k(r.k), []).append(r)
            for kb in sorted(groups):
                reqs = groups[kb]
                # a deadline-carrying group dispatches through the
                # blocking chunked host loop — deliver the finished
                # pending batch BEFORE entering it (the overlap contract
                # assumes dispatch returns asynchronously; post-warmup
                # zero-recompile steady state covers the compile case)
                if pending is not None and any(r.deadline is not None
                                               for r in reqs):
                    pending = self._safe_demux(pending)
                cur = None
                try:
                    cur = self._dispatch_phase(kb, reqs)
                except Exception as e:  # noqa: BLE001 - worker must survive
                    self._errors.inc()
                    rlog.log_warn(
                        "serve %s: batch dispatch failed (%s: %s)",
                        self._name, type(e).__name__, e)
                    try:
                        events.record(
                            "dispatch_error", f"{self._name}.batch",
                            trace_id=[r.trace_id for r in reqs],
                            error=f"{type(e).__name__}: {e}")
                    except Exception:  # noqa: BLE001 - a record failure
                        pass           # must not strand the futures
                    for r in reqs:
                        if not r.done():
                            r.set_exception(e)
                # demux N only AFTER N+1's dispatch is in flight
                if pending is not None:
                    pending = self._safe_demux(pending)
                pending = cur

    def _safe_demux(self, pend) -> None:
        """Demux a dispatched group; a demux failure (a poisoned device
        buffer surfacing at transfer) fails that group's futures, never
        the worker. Returns None (the cleared pending slot)."""
        try:
            self._demux_phase(pend)
        except Exception as e:  # noqa: BLE001 - worker must survive
            self._errors.inc()
            rlog.log_warn("serve %s: batch demux failed (%s: %s)",
                          self._name, type(e).__name__, e)
            for r in pend["live"]:
                if not r.done():
                    r.set_exception(e)
        return None

    def _tightest_deadline(self, reqs: List[Request]) -> Optional[Deadline]:
        carried = [r.deadline for r in reqs if r.deadline is not None]
        if not carried:
            return None
        return min(carried, key=lambda d: d.remaining())

    def _dispatch_group(self, kb: int, reqs: List[Request]) -> None:
        """Dispatch + demux in one step (the unpipelined path: partial
        re-dispatch after a mid-batch deadline expiry)."""
        pend = self._dispatch_phase(kb, reqs)
        if pend is not None:
            self._demux_phase(pend)

    def _dispatch_phase(self, kb: int, reqs: List[Request]):
        """Coalesce + pad + issue the (asynchronous) search dispatch.
        Returns the pending-demux state, or None when nothing was
        dispatched (all shed, or a deadline expired mid-dispatch and
        partials were delivered)."""
        spans: dict = {}     # the batch's range durations (tracing.range)
        expired = None
        with tracing.range(DISPATCH, out=spans):
            # late shed: a deadline can expire between admission pop and
            # here (e.g. an earlier group's dispatch, or an armed slow
            # worker)
            live = []
            for r in reqs:
                if r.deadline is not None and r.deadline.expired():
                    self.queue.shed(r)
                else:
                    live.append(r)
            if not live:
                return None
            # stage-telemetry probe decision: one falsy check when
            # disabled; when enabled, every _probe_every-th group tells
            # the full story
            probe = False
            if self._stages is not None:
                self._probe_tick += 1
                probe = (self._probe_tick - 1) % self._probe_every == 0
            rows = sum(r.rows for r in live)
            mb = self.ladder.bucket_queries(rows)
            with tracing.range(PAD, out=spans):
                block, offs = coalesce_block(live, mb, self._dim)
            try:
                # bind the batch's trace IDs + label the compile context:
                # a demotion, fault or recompile firing inside the search
                # is stamped with the requests (and shape bucket) it hit
                with tracing.bind_trace(*(r.trace_id for r in live)), \
                        _warmup.compile_context(f"{self._name}:{mb}x{kb}"):
                    out = self._search(block, kb,
                                       res=self._tightest_deadline(live))
            except DeadlineExceeded as e:
                expired = e
        if expired is not None:
            self._deliver_partial(kb, live, offs, expired)
            return None
        return {"kb": kb, "live": live, "offs": offs, "out": out,
                "probe": probe, "spans": spans, "mb": mb, "rows": rows}

    def _demux_phase(self, pend) -> None:
        """Block on the dispatched group's results, slice them back to
        requests, deliver, and record the stage telemetry. Runs AFTER
        the next group's dispatch is in flight (the double buffer)."""
        kb, live, offs, out = (pend["kb"], pend["live"], pend["offs"],
                               pend["out"])
        probe, spans, mb, rows = (pend["probe"], pend["spans"], pend["mb"],
                                  pend["rows"])
        device_dt = 0.0
        if probe:
            # the off-hot-path device probe: dispatch is asynchronous, so
            # the search call above returns before the device finishes;
            # only sampled batches pay this sync (steady state never does)
            t_dev = self._clock()
            jax.block_until_ready(out)
            device_dt = self._clock() - t_dev
        shards_ok = None
        if isinstance(out, tuple) and len(out) == 3:
            d, i, shards_ok = out
        else:
            d, i = out
        with tracing.range(DEMUX, out=spans):
            with tracing.range(FETCH):
                d = np.asarray(d)
                i = np.asarray(i)
            with tracing.range(DELIVER):
                self._deliver(live, offs, d, i, shards_ok)
                self._served.inc(len(live))
                self._batches.inc()
                self._reg.counter(f"{self._name}.dispatch.{mb}x{kb}").inc()
                self._batch_latency.observe(spans[DISPATCH])
                self._fill.observe(rows / mb)
        if probe:
            # AFTER delivery, and guarded: a failing observer (a
            # user-supplied registry) must not fail a batch whose
            # results were already computed, nor delay them behind
            # 5 histogram writes per co-batched request
            try:
                tel = self._stages
                bucket = f"{mb}x{kb}"
                for r in live:
                    stages = {"queue_wait": max(0.0, r.dequeued_at
                                                - r.enqueued_at),
                              "bucket_pad": spans[PAD],
                              "dispatch": spans[DISPATCH],
                              "device": device_dt, "demux": spans[DEMUX]}
                    for s, v in stages.items():
                        tel[s].observe(v)
                    tracing.log_spans(r.trace_id, stages, rows=r.rows,
                                      k=r.k, bucket=bucket)
            except Exception:  # noqa: BLE001 - telemetry must not
                pass           # break serving

    def _deliver(self, live, offs, d, i, shards_ok) -> None:
        """Slice the fetched block back to its requests and deliver."""
        if shards_ok is not None:
            ok = np.asarray(shards_ok, bool)
            self._healthy.set(int(ok.sum()))
            if not ok.all():
                self._degraded.inc()
        results = [SearchResult(d[o:o + r.rows, :r.k],
                                i[o:o + r.rows, :r.k], shards_ok)
                   for r, o in zip(live, offs)]
        now = self._clock()
        for r, res_r in zip(live, results):
            r.set_result(res_r)
            self._latency.observe(now - r.enqueued_at)
        if self._sentinel is not None:
            # recall sampling: AFTER delivery (results are already in
            # callers' hands) and guarded — the sentinel contract is
            # never-blocks, but a hostile replacement must not strand a
            # served batch either
            try:
                for r, res_r in zip(live, results):
                    self._sentinel.offer(
                        r.queries, r.k, res_r.distances, res_r.indices,
                        trace_id=r.trace_id)
            except Exception:  # noqa: BLE001 - telemetry must not break
                pass           # serving

    def _deliver_partial(self, kb: int, live: List[Request],
                         offs: List[int], e: DeadlineExceeded) -> None:
        """Mid-batch deadline expiry: the search delivered rows
        [0, done). Requests fully inside succeed; requests whose OWN
        deadline is spent fail with their slice of the partial attached
        (may be None); the rest were collateral of a co-batched tighter
        deadline and are re-dispatched — a request without a budget must
        never fail on someone else's. Terminates: every recursion drops
        the expired-deadline owners, so the retried group carries a
        strictly looser tightest deadline."""
        served, expired, retry = triage_partial(live, offs, e)
        now = self._clock()
        for r, res_r in served:
            r.set_result(res_r)
            self._latency.observe(now - r.enqueued_at)
            self._served.inc()
        for r, covered, own in expired:
            self._dlx.inc()
            try:
                events.record("deadline_exceeded", f"{self._name}.dispatch",
                              trace_id=r.trace_id, rows=r.rows,
                              covered_rows=covered)
            except Exception:  # noqa: BLE001 - telemetry must not strand
                pass           # the future
            r.set_exception(DeadlineExceeded(
                f"raft_tpu serve: deadline exceeded mid-batch; "
                f"{covered} of {r.rows} query rows completed "
                f"({'attached' if own is not None else 'empty'})",
                partial=own))
        if retry:
            self._reg.counter(f"{self._name}.redispatched").inc(len(retry))
            self._dispatch_group(kb, retry)
