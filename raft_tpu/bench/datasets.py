"""Dataset IO + ground truth for the bench harness.

Formats (raft-ann-bench get_dataset/split_groundtruth):
- ``.fbin``/``.ibin``: big-ann-benchmarks binary — int32 (n, d) header then
  row-major f32/i32 payload.
- ann-benchmarks ``.hdf5``: train/test/neighbors/distances datasets.
- synthetic specs: ``blobs-{n}x{d}``, ``uniform-{n}x{d}`` generated with
  raft_tpu.random (no network in the TPU environment; real corpora can be
  dropped into the dataset dir as fbin/hdf5).
"""
from __future__ import annotations

import os
import re
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

from ..core.errors import expects

__all__ = ["read_fbin", "write_fbin", "read_ibin", "write_ibin",
           "iter_fbin", "load_dataset", "resolve_lane_dataset",
           "generate_groundtruth", "make_corpus"]


def _read_bin(path, dtype) -> np.ndarray:
    with open(path, "rb") as f:
        n, d = np.fromfile(f, np.int32, 2)
        return np.fromfile(f, dtype, int(n) * int(d)).reshape(int(n), int(d))


def _write_bin(path, arr, dtype) -> None:
    arr = np.ascontiguousarray(arr, dtype)
    with open(path, "wb") as f:
        np.asarray(arr.shape, np.int32).tofile(f)
        arr.tofile(f)


def read_fbin(path) -> np.ndarray:
    return _read_bin(path, np.float32)


def write_fbin(path, arr) -> None:
    _write_bin(path, arr, np.float32)


def iter_fbin(path, batch_rows: int = 1 << 17):
    """Stream an fbin file in bounded row batches via mmap — the
    out-of-core reader for corpora larger than host memory (DEEP-1B /
    wiki-all class; feeds ivf_*.build_from_batches). Host memory stays
    O(batch_rows * d)."""
    with open(path, "rb") as f:
        n, d = np.fromfile(f, np.int32, 2)
    n, d = int(n), int(d)
    mm = np.memmap(path, np.float32, mode="r", offset=8, shape=(n, d))
    for b0 in range(0, n, batch_rows):
        yield np.asarray(mm[b0 : b0 + batch_rows])


def read_ibin(path) -> np.ndarray:
    return _read_bin(path, np.int32)


def write_ibin(path, arr) -> None:
    _write_bin(path, arr, np.int32)


_SYNTH = re.compile(r"^(blobs|uniform)-(\d+)x(\d+)$")


def load_dataset(
    name: str,
    dataset_dir: Optional[str] = None,
    n_queries: int = 10_000,
    seed: int = 1234,
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray], str]:
    """→ (base, queries, gt_indices or None, metric).

    ``name`` is a synthetic spec (``blobs-1000000x128``), an
    ann-benchmarks HDF5 basename (``sift-128-euclidean`` with
    ``{name}.hdf5`` in ``dataset_dir``), or a big-ann layout directory
    (``{name}/base.fbin``, ``query.fbin``, optional
    ``groundtruth.neighbors.ibin``). Metric is inferred: "-angular"/"-dot"
    → inner-product family, else sqeuclidean (the raft-ann-bench mapping).
    """
    dataset_dir = dataset_dir or os.environ.get(
        "RAFT_TPU_DATASET_DIR", "datasets")
    m = _SYNTH.match(name)
    if m:
        kind, n, d = m.group(1), int(m.group(2)), int(m.group(3))
        from .. import random as rrnd
        rng = rrnd.RngState(seed)
        if kind == "blobs":
            base, _ = rrnd.make_blobs(n + n_queries, d,
                                      n_clusters=max(16, d // 2),
                                      cluster_std=3.0, rng=rng)
            base = np.asarray(base)
        else:
            base = np.asarray(rrnd.uniform(rng, (n + n_queries, d)))
        return base[:n], base[n:], None, "sqeuclidean"

    h5 = Path(dataset_dir) / f"{name}.hdf5"
    if h5.exists():
        import h5py

        with h5py.File(h5, "r") as f:
            base = np.asarray(f["train"], np.float32)
            queries = np.asarray(f["test"], np.float32)
            gt = (np.asarray(f["neighbors"], np.int32)
                  if "neighbors" in f else None)
        # ann-benchmarks conventions: -angular ground truth is cosine
        # distance (NOT raw dot product — unnormalized vectors rank
        # differently); -dot is inner product
        if name.endswith("-angular"):
            metric = "cosine"
        elif name.endswith("-dot"):
            metric = "inner_product"
        else:
            metric = "sqeuclidean"
        return base, queries, gt, metric

    d = Path(dataset_dir) / name
    if (d / "base.fbin").exists():
        base = read_fbin(d / "base.fbin")
        queries = read_fbin(d / "query.fbin")
        gtp = d / "groundtruth.neighbors.ibin"
        gt = read_ibin(gtp) if gtp.exists() else None
        return base, queries, gt, "sqeuclidean"

    expects(False, "dataset %r not found (no synthetic match, %s, or %s)",
            name, str(h5), str(d / "base.fbin"))


# big-ann dataset-dir names accepted as "the" SIFT-1M corpus, in
# preference order (get_dataset drops it as sift-1m; older mirrors use
# sift1m/sift)
_LANE_FBIN_NAMES = ("sift-1m", "sift1m", "sift")
_LANE_HDF5_NAME = "sift-128-euclidean"


def resolve_lane_dataset(
    dataset_dir: Optional[str] = None,
    budget_rows: int = 100_000,
) -> Tuple[str, str]:
    """→ (dataset name for :func:`load_dataset`, kind).

    The *standing Pareto lane* (ROADMAP item 2a) runs on SIFT-1M so
    every perf PR moves a number the community recognizes. Resolution
    order: a big-ann fbin dir (``sift-1m/base.fbin``, the
    raft-ann-bench ``get_dataset`` layout), then the ann-benchmarks
    HDF5 (``sift-128-euclidean.hdf5``), else a small-budget synthetic
    fallback (``blobs-{budget_rows}x128`` — SIFT's dim, bounded rows)
    so zero-egress environments still exercise the full pipeline.
    ``kind`` is ``"fbin"`` / ``"hdf5"`` / ``"synthetic-fallback"`` —
    lane artifacts record it so a fallback run can never be mistaken
    for a real SIFT number.
    """
    dataset_dir = dataset_dir or os.environ.get(
        "RAFT_TPU_DATASET_DIR", "datasets")
    root = Path(dataset_dir)
    for cand in _LANE_FBIN_NAMES:
        if (root / cand / "base.fbin").exists():
            return cand, "fbin"
    if (root / f"{_LANE_HDF5_NAME}.hdf5").exists():
        return _LANE_HDF5_NAME, "hdf5"
    return f"blobs-{int(budget_rows)}x128", "synthetic-fallback"


def generate_groundtruth(base, queries, k: int = 100,
                         metric: str = "sqeuclidean",
                         batch: int = 10_000) -> Tuple[np.ndarray, np.ndarray]:
    """Exact kNN ground truth on-device (generate_groundtruth CLI analog;
    the reference also uses its own brute force for this)."""
    import jax

    from ..neighbors import brute_force

    index = brute_force.build(np.asarray(base, np.float32), metric)
    outs_d, outs_i = [], []
    for b0 in range(0, len(queries), batch):
        d, i = brute_force.search(index, queries[b0 : b0 + batch], k)
        jax.block_until_ready((d, i))
        outs_d.append(np.asarray(d))
        outs_i.append(np.asarray(i))
    return np.concatenate(outs_d), np.concatenate(outs_i)


def make_corpus(n: int, d: int, nq: int, n_clusters: int = 200, seed: int = 0,
                scale: float = 1.0, intrinsic_d: int = 16, device=None):
    """SIFT-like synthetic corpus, generated on device from ``seed``.

    A low-intrinsic-dimension clustered mixture: points live near a
    random ``intrinsic_d``-dim subspace (cluster centers and
    within-cluster spread both low-rank) plus small ambient noise, so
    neighborhoods straddle IVF partition boundaries the way SIFT's do.
    Queries are FRESH mixture samples, not perturbed corpus rows.
    ``device``: where the arrays are made (default: JAX's default
    device). Returns ``(data (n, d), queries (nq, d))`` f32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kw, kc, kx, ka, kq, kp, ke, kf = jax.random.split(key, 8)
        w = jax.random.normal(kw, (intrinsic_d, d), jnp.float32)
        w = w / jnp.linalg.norm(w, axis=1, keepdims=True)
        centers_z = jax.random.normal(kc, (n_clusters, intrinsic_d),
                                      jnp.float32) * scale
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
        z = centers_z[assign] + jax.random.normal(kx, (n, intrinsic_d),
                                                  jnp.float32)
        data = z @ w + 0.1 * jax.random.normal(ke, (n, d), jnp.float32)
        qassign = jax.random.randint(kq, (nq,), 0, n_clusters)
        qz = centers_z[qassign] + jax.random.normal(kp, (nq, intrinsic_d),
                                                    jnp.float32)
        queries = qz @ w + 0.1 * jax.random.normal(kf, (nq, d), jnp.float32)
        return data, queries

    key = jax.random.PRNGKey(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(gen(key))
