"""An open loop: requests of a few queries each arrive on a Poisson
schedule and go through the program's micro-batcher, whether or not
earlier ones have finished.

Mix parameters: ``rate_per_s`` (requests a second: a cell's own, given
in ``cells/<cell>.json``, since it is 0.8 of that cell's knee),
``queries_per_request`` ([least, most], uniform), ``k``,
``query_buckets`` and ``k_buckets`` (the batcher's shape ladder),
``check_rows`` (answer rows, drawn from the seed, that the reference
checks after the window), and ``trace_seconds`` (a traced run's window,
run after the measured one).

Every seed sends the same work: ``round(rate * seconds)`` requests whose
sizes take each value of the range equally often and whose gaps are the
same quantiles of an exponential distribution, in an order drawn from the
seed. No request carries a deadline. A request the admission queue
refuses (its backpressure) is sent again after a millisecond, as its
contract asks of callers, and fails only if it is still refused a minute
after the window. Each request's latency runs from the time it was due
to be sent to the time its answer is on the host, so a stall counts
against every request behind it.
"""
import queue
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from raft_tpu import serve
from raft_tpu.serve import metrics as serve_metrics

LEAD_S = 0.05        # from the window's start to the first arrival's clock
BACKOFF_S = 0.001    # a refused request's wait before it is sent again


def _rate(mix: dict) -> float:
    if "rate_per_s" not in mix:
        raise KeyError("an open loop needs rate_per_s: give the cell's "
                       "offered rate in cells/<cell>.json")
    return float(mix["rate_per_s"])


def schedule(rate: float, seconds: float, sizes_range, rng):
    """(due times (n,), sizes (n,)): the same multiset for every ``rng``."""
    n = max(1, int(round(rate * seconds)))
    lo, hi = int(sizes_range[0]), int(sizes_range[1])
    sizes = lo + np.arange(n) % (hi - lo + 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    return np.cumsum(rng.permutation(gaps)), rng.permutation(sizes)


def n_queries(mix: dict, cfg: dict, seconds: float) -> int:
    rates = [_rate(mix)] + list(mix.get("sweep_rates", ()))
    n = max(1, int(round(max(rates) * seconds)))
    return n * int(mix["queries_per_request"][1]) + 256


def prepare(ctx) -> dict:
    """The batcher over the family's searcher, every ladder shape warmed,
    and a few requests sent through the worker before the window."""
    mix = ctx.mix
    ladder = serve.BucketLadder(query_buckets=tuple(mix["query_buckets"]),
                                k_buckets=tuple(mix["k_buckets"]))
    reg = serve_metrics.Registry()
    # a positive sample rate makes the worker stamp dequeued_at on every
    # request; at this rate only the first batch after start-up, sent
    # here in set-up, is probed with a blocking device wait
    mb = serve.MicroBatcher(ctx.searcher, ctx.cfg["dim"], ladder=ladder,
                            registry=reg, name="bench", trace_sample=1e-12)
    mb.warmup()
    host_q = np.asarray(ctx.queries)
    k = int(mix["k"])
    warm = host_q[-256:]
    for m in ladder.query_buckets:
        for r in [mb.submit(warm[j:j + 1], k) for j in range(m)]:
            r.result(timeout=600)
    return {"mb": mb, "reg": reg, "host_q": host_q[:-256], "k": k}


def run(plan: dict, ctx, seconds: float, rate: float = None) -> dict:
    mb, reg, host_q, k = plan["mb"], plan["reg"], plan["host_q"], plan["k"]
    rate = _rate(ctx.mix) if rate is None else rate
    rng = np.random.default_rng(ctx.host_seed(2))
    due, sizes = schedule(rate, seconds, ctx.mix["queries_per_request"], rng)
    n = len(due)
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    if offs[-1] + sizes[-1] > len(host_q):
        raise ValueError("query pool smaller than the schedule needs")
    fill = reg.histogram("bench.batch_fill", serve_metrics.RATIO_BUCKETS)
    fill0 = (fill.count, fill.sum)
    lag = np.zeros(n)
    depth = np.zeros(n, np.int64)
    done_at = np.full(n, np.nan)
    qwait = np.full(n, np.nan)
    dists, ids = [None] * n, [None] * n
    reqs = [None] * n
    errors = {}
    handoff = queue.SimpleQueue()
    close_by = [None]

    def collect():
        # each answer is kept as its two arrays and the request let go, so
        # the window holds no growing heap of the program's objects
        while True:
            j = handoff.get()
            if j is None:
                return
            r = reqs[j]
            if r is None:
                continue
            while True:
                left = (1.0 if close_by[0] is None
                        else close_by[0] - time.perf_counter())
                if left <= 0:
                    errors[j] = "missing"
                    break
                try:
                    res = r.result(timeout=min(left, 1.0))
                    done_at[j] = time.perf_counter()
                    dists[j], ids[j] = res.distances, res.indices
                    if r.dequeued_at > 0:
                        qwait[j] = r.dequeued_at - r.enqueued_at
                    break
                except TimeoutError:
                    continue
                except Exception as e:  # noqa: BLE001 - a failure counts
                    errors[j] = "raised"
                    ctx.note_error(e)
                    break
            reqs[j] = None

    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    t0 = time.perf_counter() + LEAD_S
    give_up = t0 + seconds + 60.0
    refusals = 0
    try:
        for j in range(n):
            target = t0 + due[j]
            wait = target - time.perf_counter()
            if wait > 0:
                with TraceAnnotation("bench.gen_sleep"):
                    time.sleep(wait)
            sent = time.perf_counter()
            lag[j] = sent - target
            depth[j] = len(mb.queue)
            while True:
                try:
                    with TraceAnnotation("bench.submit"):
                        reqs[j] = mb.submit(
                            host_q[offs[j]:offs[j] + sizes[j]], k)
                    break
                except serve.QueueFullError as e:
                    # backpressure: the caller waits and sends again, as
                    # the admission queue's contract asks; the request's
                    # latency still runs from its due time
                    refusals += 1
                    if time.perf_counter() > give_up:
                        errors[j] = "refused"
                        ctx.note_error(e)
                        break
                    with TraceAnnotation("bench.backoff"):
                        time.sleep(BACKOFF_S)
                except Exception as e:  # noqa: BLE001 - a failure counts
                    errors[j] = "raised"
                    ctx.note_error(e)
                    break
            handoff.put(j)
    finally:
        # the answers due in the window get a minute past its close
        close_by[0] = max(t0 + seconds, time.perf_counter()) + 60.0
        handoff.put(None)
        with TraceAnnotation("bench.collect"):
            collector.join()
    elapsed = time.perf_counter() - t0
    ok = ~np.isnan(done_at)
    lat = (done_at - (t0 + due))[ok]
    qwait = qwait[ok & ~np.isnan(qwait)]
    dfill = fill.count - fill0[0]
    half = n // 2
    answers = []
    for j in range(n):
        if ok[j]:
            answers.append((j, host_q[offs[j]:offs[j] + sizes[j]],
                            np.asarray(dists[j]), np.asarray(ids[j])))
    return {"ops": n, "raised": sum(v in ("raised", "refused")
                                    for v in errors.values()),
            "missing": sum(v == "missing" for v in errors.values()),
            "causes": {c: sum(v == c for v in errors.values())
                       for c in ("raised", "refused", "missing")},
            "counters": {c: reg.counter(f"bench.{c}").value
                         for c in ("errors", "shed", "rejected")},
            "refused_attempts": refusals,
            "elapsed_s": elapsed, "latency_s": lat, "queue_wait_s": qwait,
            "gen_lag_s": lag, "rate": rate, "requests": n,
            "completed": int(ok.sum()),
            "queries": int(sizes[ok].sum()),
            "batch_fill": ((fill.sum - fill0[1]) / dfill) if dfill else None,
            "depth_peak": [int(depth[:half].max(initial=0)),
                           int(depth[half:].max(initial=0))],
            "lag_mean_s": [float(lag[:half].mean()) if half else 0.0,
                           float(lag[half:].mean())],
            "answers": answers}


def close(plan: dict) -> None:
    mb = plan.pop("mb", None)
    if mb is not None:
        mb.close()
    plan.clear()
