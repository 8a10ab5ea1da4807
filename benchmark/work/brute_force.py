"""The least work an exact brute-force search needs, from shapes.

Whatever engine serves it, a call reads the corpus once (float32 rows)
with its row norms, reads the queries and writes the answers, and takes
one multiply-add per dimension for every (query, corpus row) pair. The
fused kernel streams the corpus once per query tile; that re-reading is
the implementation's cost and is not counted. Nothing here reads the
implementation.
"""
import numpy as np


def work(cfg: dict, list_sizes, probes, k: int) -> dict:
    """``list_sizes``: ``[n]``, the one list that holds every row;
    ``probes``: (m, 1), every query probing it."""
    n = int(np.asarray(list_sizes, np.int64).sum())
    m = np.asarray(probes).shape[0]
    d = int(cfg["dim"])
    bytes_ = (n * d * 4                             # corpus, once
              + n * 4                               # row norms
              + m * d * 4                           # queries
              + m * k * 8)                          # float32 dist + int32 id
    return {"bytes": bytes_, "flops": 2 * m * n * d}
