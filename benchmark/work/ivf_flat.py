"""The least work an IVF-Flat search needs, from shapes and probed lists.

It counts what the search has to do whatever engine serves it: each
distinct probed list read once per call, the queries read and the answers
written, and one multiply-add per dimension for every (query, member of a
probed list) pair. Nothing here reads the implementation.
"""
import numpy as np


def work(cfg: dict, list_sizes, probes, k: int) -> dict:
    """``probes``: (m, n_probes) list ids of one call's m queries."""
    sizes = np.asarray(list_sizes, np.int64)
    probes = np.asarray(probes)
    d = int(cfg["dim"])
    row_bytes = d * 4                               # float32 rows
    distinct = np.unique(probes)
    m = probes.shape[0]
    bytes_ = (int(sizes[distinct].sum()) * row_bytes
              + m * d * 4                           # queries
              + m * k * 8)                          # float32 dist + int32 id
    flops = 2 * d * int(sizes[probes].sum())
    return {"bytes": bytes_, "flops": flops}
