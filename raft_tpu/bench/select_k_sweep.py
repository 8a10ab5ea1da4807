"""On-device select_k cost sweep across (rows, n, k).

Produces the recorded measurement behind ``select_k``'s single-engine
design note (the measured analog of the reference's per-arch
``choose_select_k_algorithm`` table, matrix/detail/select_k-inl.cuh:48-72):
every point runs ``tune_select_k`` — per-call-blocked medians — purely as
a calibration record (nothing dispatches on it).

Run: ``python -m raft_tpu.bench.select_k_sweep [out.json]`` on the target
device.
"""
from __future__ import annotations

import json
import sys

GRID = [
    # (rows, n, k): brute-force merge shapes, IVF coarse shapes, wide rows
    (128, 1024, 10),
    (1024, 1024, 64),
    (128, 16384, 10),
    (1024, 16384, 32),
    (128, 65536, 10),
    (512, 65536, 32),
    (64, 262144, 10),
    (64, 262144, 128),
]


def run(out_path: str | None = None) -> dict:
    import jax

    from ..matrix.select_k import tune_select_k

    results = []
    for rows, n, k in GRID:
        winner, timings = tune_select_k(rows, n, k, reps=5)
        entry = {"rows": rows, "n": n, "k": k, "winner": winner,
                 "ms": {name: round(t * 1e3, 2)
                        for name, t in timings.items()}}
        results.append(entry)
        print(f"# rows={rows} n={n} k={k}: {winner} {entry['ms']}",
              file=sys.stderr, flush=True)
    dev = jax.devices()[0]
    doc = {
        "device": f"{dev.platform}:{getattr(dev, 'device_kind', '?')}",
        "methodology": ("tune_select_k: per-call-blocked median of 5, "
                        "per-rep input perturb + output chain "
                        "(each rep distinct work)"),
        "results": results,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
    return doc


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else None
    doc = run(out)
    print(json.dumps(doc))
