"""A closed loop with one caller: each call searches one block of queries,
and the next call goes only once the previous call's answer is on the
host, as a batch job that uses the ids does.

Mix parameters: ``batch_queries`` (rows a call), ``blocks`` (distinct query
blocks, made from the seed and used in turn), ``k``, and
``sample_rows_per_call`` (answer rows a call keeps, drawn from the seed,
for the check against the reference after the window).
"""
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def n_queries(mix: dict, cfg: dict, seconds: float) -> int:
    return int(mix["batch_queries"]) * int(mix["blocks"])


def prepare(ctx) -> dict:
    """Stage the query blocks on the device and warm the one call shape."""
    mix, k = ctx.mix, int(ctx.mix["k"])
    b = int(mix["batch_queries"])
    qs = ctx.queries
    blocks = [jax.device_put(qs[i * b:(i + 1) * b], ctx.device)
              for i in range(int(mix["blocks"]))]
    host = [np.asarray(x) for x in blocks]
    search = ctx.search
    for _ in range(2):          # the second call finds every program
        jax.block_until_ready(search(blocks[0], k))
    return {"blocks": blocks, "host": host, "search": search, "k": k}


def run(plan: dict, ctx, seconds: float) -> dict:
    """Calls until ``seconds`` have passed; the window ends with the
    answer of the last call that started inside it."""
    blocks, host, search, k = (plan["blocks"], plan["host"], plan["search"],
                               plan["k"])
    rng = np.random.default_rng(ctx.host_seed(1))
    keep = int(ctx.mix["sample_rows_per_call"])
    b = blocks[0].shape[0]
    answers, raised, calls = [], 0, []
    t0 = time.perf_counter()
    while True:
        j = len(calls) % len(blocks)
        try:
            with TraceAnnotation("bench.call"):
                d, i = search(blocks[j], k)
                with TraceAnnotation("bench.fetch"):
                    d, i = np.asarray(d), np.asarray(i)
        except Exception as e:  # noqa: BLE001 - a failed call is counted
            raised += 1
            ctx.note_error(e)
            d = i = None
        calls.append(j)
        if d is not None:
            rows = rng.choice(b, keep, replace=False)
            answers.append((len(calls) - 1, host[j][rows], d[rows], i[rows]))
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    return {"ops": len(calls), "raised": raised, "missing": 0,
            "queries": b * (len(calls) - raised), "elapsed_s": elapsed,
            "calls": calls, "answers": answers}


def close(plan: dict) -> None:
    plan.clear()
