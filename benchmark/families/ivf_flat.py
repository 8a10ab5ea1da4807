"""IVF-Flat through the program's own entry points.

``build`` makes the index, ``search`` is the direct path a batch caller
takes, ``make_searcher`` the closure the micro-batcher serves, and
``coarse`` what the roofline's work counting needs: the probe space, the
centres and the list sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.neighbors import ivf_flat


def build(data, cfg: dict):
    ip = cfg["index"]
    index = ivf_flat.build(data, ivf_flat.IndexParams(
        n_lists=ip["n_lists"], metric=cfg["metric"], seed=ip["seed"]))
    ivf_flat.prepare_scan(index)
    jax.block_until_ready((index.data, index._scan_pad[1:]))
    return {"index": index,
            "params": ivf_flat.SearchParams(n_probes=cfg["search"]["n_probes"]),
            "precision": cfg["precision"]}


def search(state, queries, k: int):
    return ivf_flat.search(state["index"], queries, k, state["params"],
                           algo="pallas", precision=state["precision"])


def make_searcher(state):
    return ivf_flat.make_searcher(state["index"], state["params"],
                                  algo="pallas",
                                  precision=state["precision"])


def coarse(state):
    """(project(queries) -> probe-space queries, centres, list sizes)."""
    index = state["index"]
    return (lambda q: jnp.asarray(q, jnp.float32), index.centers,
            np.asarray(index.list_sizes, np.int64))
