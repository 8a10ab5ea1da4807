"""The SIFT-shaped synthetic corpus, generated on the device from a seed.

A copy of ``raft_tpu.bench.datasets.make_corpus`` kept with the benchmark,
so that no change to the program can move the data a cell is measured on.
A low-intrinsic-dimension clustered mixture: points live near a random
``intrinsic_dim``-dimensional subspace (cluster centres and spread both
low-rank) plus small ambient noise, so that neighbourhoods straddle IVF
partition boundaries the way SIFT's do. Queries are fresh samples of the
same mixture, not perturbed corpus rows, drawn from a key of their own:
one corpus (a configuration's fixed data set, as SIFT-1M is one) serves
every query seed.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np


def device_key(seed: int, stream: int = 0):
    """A JAX key for any whole-number seed (wider than 32 bits too)."""
    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]))


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "nq", "n_clusters", "intrinsic_dim", "noise"))
def _gen(key, qkey, *, n, d, nq, n_clusters, intrinsic_dim, noise):
    kw, kc, kx, ka, _, _, ke, _ = jax.random.split(key, 8)
    kq, kp, kf = jax.random.split(qkey, 3)
    w = jax.random.normal(kw, (intrinsic_dim, d), jnp.float32)
    w = w / jnp.linalg.norm(w, axis=1, keepdims=True)
    centers = jax.random.normal(kc, (n_clusters, intrinsic_dim), jnp.float32)
    assign = jax.random.randint(ka, (n,), 0, n_clusters)
    z = centers[assign] + jax.random.normal(kx, (n, intrinsic_dim),
                                            jnp.float32)
    data = z @ w + noise * jax.random.normal(ke, (n, d), jnp.float32)
    qassign = jax.random.randint(kq, (nq,), 0, n_clusters)
    qz = centers[qassign] + jax.random.normal(kp, (nq, intrinsic_dim),
                                              jnp.float32)
    queries = qz @ w + noise * jax.random.normal(kf, (nq, d), jnp.float32)
    return data, queries


def make(key, qkey, n: int, d: int, nq: int, n_clusters: int,
         intrinsic_dim: int, noise: float, device=None):
    """``(data (n, d), queries (nq, d))`` float32, made in one jitted call
    on ``device``: the corpus from ``key``, the queries from ``qkey``, both
    from the one mixture that ``key`` fixes."""
    if device is not None:
        key, qkey = jax.device_put((key, qkey), device)
    return jax.block_until_ready(_gen(
        key, qkey, n=int(n), d=int(d), nq=int(nq),
        n_clusters=int(n_clusters), intrinsic_dim=int(intrinsic_dim),
        noise=float(noise)))
