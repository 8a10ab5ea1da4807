"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; per the project contract all
sharding/collective code is exercised on `--xla_force_host_platform_device_count=8`
CPU devices (the driver separately dry-run-compiles the multi-chip path).
Env vars must be set before jax is imported anywhere.
"""
import os

# Tests run on the CPU: JAX_PLATFORMS=cpu in the environment plus the
# in-process switch below (which also holds when the variable is unset).
# XLA_FLAGS must be set before jax initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# RAFT_TPU_TEST_LANE=1 keeps the real accelerator visible so `-m tpu`
# tests compile on device; the default lane pins everything to the
# 8-device virtual CPU mesh.
_TPU_LANE = os.environ.get("RAFT_TPU_TEST_LANE", "") == "1"
if not _TPU_LANE:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

if not _TPU_LANE:
    assert jax.device_count() == 8, "tests expect the 8-device virtual CPU mesh"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def res():
    from raft_tpu.core import Resources

    return Resources(seed=0)


@pytest.fixture(scope="session")
def multichip_mesh():
    """The CPU multi-device emulation lane (``multichip`` marker): an
    8-device mesh over the virtual CPU devices this conftest forces via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the driver's
    dryrun runs the same body in a subprocess with the same flag). Skips
    rather than fails when the interpreter was initialized without the
    flag, so ``multichip`` tests are runnable standalone too."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("multichip lane needs the 8-device virtual CPU mesh "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return Mesh(np.array(devs[:8]), ("shard",))


# The CI box has ONE CPU core (nproc=1), so the smoke lane is a measured
# file subset, not parallelism:
#   python -m pytest -q -m "smoke and not slow"
# covers comms, matrix, distance, sharded brute-force, linalg/sparse,
# core, brute force and random/stats. Measured ~90-110 s serial on an
# idle box (per-file timings 2026-07-31) but 133-175 s under contention
# (judge 2026-08-01, rerun 2026-08-02): treat the promise as ~2-3 min,
# not <2. The full not-slow lane stays the depth lane (~13 min).
_SMOKE_FILES = {
    "test_comms.py", "test_matrix.py", "test_distance.py",
    "test_sharded_knn.py", "test_linalg_sparse_ops.py", "test_core.py",
    "test_brute_force.py", "test_random_stats.py",
}

# (file, test) pairs measured >=14 s on the 8-device CPU mesh (pytest
# --durations, 2026-07-31): excluded from the `not heavy` lane. Keyed by
# file because bare names collide (e.g. test_comms_injection exists fast
# in test_core.py and slow in test_sharded_ann.py).
_HEAVY = {
    ("test_sharded_ann.py", "test_uneven_rows_no_padding_leak"),
    ("test_ivf_pq.py", "test_per_cluster_codebooks"),
    ("test_sharded_ann.py", "test_comms_injection"),
    ("test_ops.py", "test_ivf_flat_pallas_matches_xla"),
    ("test_ivf_pq.py", "test_pq_build_from_batches"),
    ("test_ops.py", "test_ivf_pq_pallas_filter_excludes"),
    ("test_sharded_ann.py", "test_uneven_rows"),
    ("test_ops.py", "test_ivf_flat_pallas_filter_matches_xla"),
    ("test_ivf_pq.py", "test_int8_lut_mode"),
    ("test_ivf_pq.py", "test_non_divisible_dim_pads"),
    ("test_sharded_ann.py", "test_low_precision_storage"),
    ("test_sharded_ann.py", "test_recall_vs_single_shard"),
    ("test_ivf_flat.py", "test_uint8_byte_corpus"),
    ("test_sharded_ann.py", "test_recall_and_merge"),
    ("test_ivf_flat.py", "test_uint8_save_load"),
    ("test_ivf_flat.py", "test_k_larger_than_candidates"),
    ("test_ops.py", "test_ivf_flat_pallas_small_k_and_tail_lists"),
    ("test_ivf_flat.py", "test_build_from_batches_matches_bulk_recall"),
}


def pytest_collection_modifyitems(config, items):
    """Skip `tpu`-marked tests unless the TPU lane is active (and, in the
    TPU lane, skip everything else — collectives expect the CPU mesh);
    auto-mark the measured heavy tail for the smoke lane."""
    skip_tpu = pytest.mark.skip(reason="needs RAFT_TPU_TEST_LANE=1 + a TPU")
    skip_cpu = pytest.mark.skip(reason="TPU lane runs only -m tpu tests")
    on_tpu = _TPU_LANE and jax.default_backend() == "tpu"
    for item in items:
        fname = item.path.name
        if ((fname, item.originalname) in _HEAVY
                or (fname, item.name) in _HEAVY):
            item.add_marker(pytest.mark.heavy)
        if fname in _SMOKE_FILES:
            item.add_marker(pytest.mark.smoke)
        is_tpu_test = "tpu" in item.keywords
        if is_tpu_test and not on_tpu:
            item.add_marker(skip_tpu)
        elif not is_tpu_test and _TPU_LANE:
            item.add_marker(skip_cpu)
