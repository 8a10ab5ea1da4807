"""The least work an IVF-PQ search with an exact re-rank needs, from shapes
and probed lists.

Bytes: the codes of each distinct probed list once per call, the raw rows
the re-rank reads (``refine_ratio * k`` candidates a query), the queries
and the answers. Operations: one lookup table per (query, probe) pair
(``2 * pq_len`` for each of the ``2**pq_bits`` entries of each of the
``pq_dim`` subspaces), one add per subspace for every scanned candidate,
and ``2 * dim`` for every re-ranked candidate. Nothing here reads the
implementation.
"""
import numpy as np


def work(cfg: dict, list_sizes, probes, k: int) -> dict:
    """``probes``: (m, n_probes) list ids of one call's m queries."""
    sizes = np.asarray(list_sizes, np.int64)
    probes = np.asarray(probes)
    d = int(cfg["dim"])
    ip = cfg["index"]
    pq_dim, bits = int(ip["pq_dim"]), int(ip["pq_bits"])
    pq_len = d // pq_dim
    cand = int(cfg["search"]["refine_ratio"]) * k
    m, n_probes = probes.shape
    distinct = np.unique(probes)
    code_bytes = pq_dim * bits // 8
    bytes_ = (int(sizes[distinct].sum()) * code_bytes
              + m * cand * d * 4                    # re-rank row reads
              + m * d * 4                           # queries
              + m * k * 8)                          # answers
    lut = m * n_probes * pq_dim * (1 << bits) * 2 * pq_len
    scan = pq_dim * int(sizes[probes].sum())
    rerank = m * cand * 2 * d
    return {"bytes": bytes_, "flops": lut + scan + rerank}
