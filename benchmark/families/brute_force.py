"""Exact brute-force search through the program's own entry points.

``build`` stores the corpus and its row norms; ``make_searcher`` is the
serving closure, which aligns the corpus for the fused kernel once
(``prepare_fused``) when it is built; ``search`` is the direct path a
batch caller takes, through the fused distance+top-k kernel. ``coarse``
gives what the roofline's work counting needs: brute force is one list
that holds every row, probed by every query, so the probe space is the
queries themselves, one zero centre and the list size ``[n]``.
"""
import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.neighbors import brute_force


def build(data, cfg: dict):
    index = brute_force.build(data, metric=cfg["metric"])
    jax.block_until_ready((index.dataset, index.norms))
    return {"index": index, "precision": cfg["precision"]}


def search(state, queries, k: int):
    return brute_force.search(state["index"], queries, k, algo="pallas",
                              precision=state["precision"])


def make_searcher(state):
    return brute_force.make_searcher(state["index"], algo="pallas",
                                     precision=state["precision"])


def coarse(state):
    """(project(queries) -> probe-space queries, centres, list sizes)."""
    index = state["index"]
    return (lambda q: jnp.asarray(q, jnp.float32),
            jnp.zeros((1, index.dim), jnp.float32),
            np.asarray([index.size], np.int64))
