"""The plain reference: exact k nearest neighbours under squared L2.

It imports nothing of the program and takes nothing the program made: only
the corpus (made by ``corpus.py``) and the queries. Two parts:

* ``pair_distances``: the exact distance of each returned (query, id)
  pair, as ``sum((q - x)**2)`` in float64 on the host;
* ``knn``: the exact top-k ids of each query, on the device in blocks of
  corpus rows at ``Precision.HIGHEST``, so that it fits beside the corpus.

``knn_bf16x3`` is the control: the same search with every product taken
as three bfloat16 passes (the split that ``Precision.HIGH`` makes on a
TPU), written out with ``reduce_precision`` so that it computes alike on
every platform.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np


def pair_distances(data, queries, ids) -> np.ndarray:
    """(m, k) exact squared distances of ``queries`` (m, d) to the rows
    ``ids`` (m, k) of ``data``; float64; inf where an id is out of range."""
    ids = np.asarray(ids)
    n = data.shape[0]
    ok = (ids >= 0) & (ids < n)
    rows = np.asarray(jnp.take(data, jnp.asarray(np.where(ok, ids, 0)),
                               axis=0), np.float64)
    q = np.asarray(queries, np.float64)
    d = ((rows - q[:, None, :]) ** 2).sum(axis=2)
    return np.where(ok, d, np.inf)


def _bf16(a):
    """``a`` rounded to bfloat16's 8-bit mantissa, kept in float32: an
    explicit op that no compiler pass folds away."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dot_bf16x3(q, x):
    """q @ x.T as three bfloat16 passes (hi*hi + hi*lo + lo*hi) with
    float32 accumulation; each pass's products are exact in float32."""
    qh = _bf16(q)
    ql = _bf16(q - qh)
    xh = _bf16(x)
    xl = _bf16(x - xh)

    def mm(a, b):
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)

    return mm(qh, xh) + mm(qh, xl) + mm(ql, xh)


@functools.partial(jax.jit, static_argnames=("k", "block", "bf16x3"))
def _knn(data, queries, *, k: int, block: int, bf16x3: bool):
    n, d = data.shape
    nb = -(-n // block)
    pad = nb * block - n
    x = jnp.pad(data, ((0, pad), (0, 0))).reshape(nb, block, d)
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)

    def one(carry, xb_i):
        best_d, best_i = carry
        xb, i = xb_i
        if bf16x3:
            ip = _dot_bf16x3(queries, xb)
        else:
            ip = jnp.matmul(queries, xb.T,
                            precision=jax.lax.Precision.HIGHEST)
        dist = q2 + jnp.sum(xb * xb, axis=1)[None, :] - 2.0 * ip
        rows = i * block + jnp.arange(block)
        dist = jnp.where(rows[None, :] < n, dist, jnp.inf)
        neg, loc = jax.lax.top_k(-dist, k)
        cd = jnp.concatenate([best_d, -neg], axis=1)
        ci = jnp.concatenate([best_i, rows[loc]], axis=1)
        neg2, sel = jax.lax.top_k(-cd, k)
        return (-neg2, jnp.take_along_axis(ci, sel, axis=1)), None

    m = queries.shape[0]
    init = (jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32))
    (dist, ids), _ = jax.lax.scan(one, init, (x, jnp.arange(nb)))
    return dist, ids


def _blocked(data, queries, k, bf16x3, q_block=2048, row_block=65536):
    data = jnp.asarray(data, jnp.float32)
    row_block = min(row_block, data.shape[0])
    out_d, out_i = [], []
    q = np.asarray(queries, np.float32)
    for s in range(0, q.shape[0], q_block):
        qb = q[s:s + q_block]
        m = qb.shape[0]
        if m < q_block and q.shape[0] > q_block:
            qb = np.pad(qb, ((0, q_block - m), (0, 0)))  # one compiled shape
        d, i = _knn(data, jax.device_put(qb, next(iter(data.devices()))),
                    k=k, block=row_block, bf16x3=bf16x3)
        out_d.append(np.asarray(d)[:m])
        out_i.append(np.asarray(i)[:m])
    return np.concatenate(out_d), np.concatenate(out_i)


def knn(data, queries, k: int):
    """Exact ``(distances, ids)`` (m, k) of ``queries`` over ``data``."""
    return _blocked(data, queries, k, bf16x3=False)


def knn_bf16x3(data, queries, k: int):
    """The control: ``knn`` one precision step down (three bf16 passes)."""
    return _blocked(data, queries, k, bf16x3=True)
