"""IVF-PQ with an exact re-rank, through the program's own entry points.

A search takes ``refine_ratio * k`` candidates from the PQ scan and
re-ranks them to ``k`` with ``refine`` against the raw corpus on the
device; the re-rank's distances are the ones returned. ``coarse`` gives
what the roofline's work counting needs: the probe space (the index's
rotation), the centres and the list sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.neighbors import ivf_pq
from raft_tpu.neighbors.refine import refine



def build(data, cfg: dict):
    ip = cfg["index"]
    index = ivf_pq.build(data, ivf_pq.IndexParams(
        n_lists=ip["n_lists"], pq_dim=ip["pq_dim"], pq_bits=ip["pq_bits"],
        metric=cfg["metric"], seed=ip["seed"]))
    jax.block_until_ready((index.codes, index.codebooks))
    return {"index": index, "data": data,
            "params": ivf_pq.SearchParams(n_probes=cfg["search"]["n_probes"]),
            "ratio": int(cfg["search"]["refine_ratio"]),
            "metric": cfg["metric"]}


def search(state, queries, k: int):
    _, cand = ivf_pq.search(state["index"], queries, state["ratio"] * k,
                            state["params"], algo="pallas")
    return refine(state["data"], queries, cand, k, state["metric"])


def make_searcher(state):
    def fn(queries, k, res=None):
        return search(state, queries, k)
    return fn


def coarse(state):
    index = state["index"]
    rot = index.rotation
    return (lambda q: jnp.matmul(jnp.asarray(q, jnp.float32), rot.T,
                                 precision=jax.lax.Precision.HIGHEST),
            index.centers_rot, np.asarray(index.list_sizes, np.int64))
