"""Find the knee of a served cell on the chip: one set-up, then one
open-loop window at each offered rate, lowest first.

    python3 benchmark/sweep.py --workload sift1m-ivf_pq.served --seed 5 \\
        --seconds 10 --rates 280,320,360,400

One JSON line a rate: requests answered a second, p50/p95/p99, the median
latency, the peak queue depth and the mean generator lag in each half of
the window, the mean queue wait and batch fill, the failures by cause,
and the longest garbage collection. The knee is the highest rate at which
the backlog does not grow: the median latency stays flat from the first
half to the second. The peak depth and the mean lag are shown too, but a
host stall of a tenth of a second lifts them at any rate. A cell's rate
is set at 0.8 of the knee in ``cells/<cell>.json``.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    sys.path[:0] = [HERE, ROOT]
    from raft_tpu.utils import use_compile_cache

    use_compile_cache(ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("the sweep runs only on the chip", file=sys.stderr)
        return 2
    import harness

    c = harness.set_up(args.workload, args.seed, args.seconds, False,
                       devices[:1],
                       overrides={"traffic": {"sweep_rates": rates}})
    for rate in rates:
        with harness.GcPauses() as gcp:
            out = c.kind.run(c.plan, c.ctx, args.seconds, rate=rate)
        lat = out["latency_s"]
        print(json.dumps({
            "rate": rate, "requests": out["requests"],
            "answered_per_s": out["completed"] / out["elapsed_s"],
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "p50_ms_halves": [float(np.median(h)) * 1e3
                              for h in np.array_split(lat, 2)],
            "queue_depth_peak_halves": out["depth_peak"],
            "gen_lag_mean_ms_halves": [x * 1e3 for x in out["lag_mean_s"]],
            "queue_wait_ms": float(out["queue_wait_s"].mean()) * 1e3,
            "batch_fill": out["batch_fill"], "causes": out["causes"],
            "refused_attempts": out["refused_attempts"],
            "counters": out["counters"],
            "gc_longest_ms": max((p[1] * 1e3 for p in gcp.pauses),
                                 default=0.0)}), flush=True)
    harness.free_program(c)
    return 0


if __name__ == "__main__":
    sys.exit(main())
