"""Tiny sizes at which every cell runs on the CPU in a few seconds.

The tiny corpus has few, low-dimensional clusters so that its 10th
neighbours lie as close, against the vectors' norms, as they do at a
million rows: distance gaps then read as they do at full size."""
import jax

import harness

CELLS = ("sift1m-ivf_flat.batch10k", "sift1m-ivf_pq.batch10k",
         "sift1m-ivf_pq.served")
SEED = 2**31 + 11           # wider than 32 signed bits, as the driver's are

OVERRIDES = {
    "config": {"n_rows": 2048, "index": {"n_lists": 16},
               "search": {"n_probes": 8},
               "corpus": {"n_clusters": 2, "intrinsic_dim": 4}},
    "traffic": {"batch_queries": 32, "blocks": 2, "sample_rows_per_call": 8,
                "check_rows": 96, "query_buckets": [16], "trace_seconds": None,
                "rate_per_s": 20},
}


def run(cell, seconds=0.5, trace=False, **kw):
    kw.setdefault("overrides", OVERRIDES)
    return harness.run_cell(cell, kw.pop("seed", SEED), seconds, trace,
                            jax.devices()[:1], **kw)
