"""The work counting against hand counts at tiny shapes, and the peaks."""
import numpy as np
import pytest

import harness
import peaks

WORK = harness.load_module(f"{harness.HERE}/work/ivf_flat.py")
WORK_PQ = harness.load_module(f"{harness.HERE}/work/ivf_pq.py")
SIZES = np.array([5, 7, 0, 11])
PROBES = np.array([[0, 1], [1, 3], [0, 1]])     # 3 queries, 2 probes


def test_ivf_flat_by_hand():
    cfg = {"dim": 8}
    w = WORK.work(cfg, SIZES, PROBES, k=2)
    # distinct lists 0, 1, 3: 23 rows of 32 B; queries 3 x 32 B;
    # answers 3 x 2 x 8 B
    assert w["bytes"] == 23 * 32 + 96 + 48
    # scanned rows per query: 12, 18, 12 -> 42, two ops per dimension
    assert w["flops"] == 2 * 8 * 42


def test_ivf_pq_by_hand():
    cfg = {"dim": 8, "index": {"pq_dim": 4, "pq_bits": 2},
           "search": {"refine_ratio": 2}}
    w = WORK_PQ.work(cfg, SIZES, PROBES, k=2)
    # codes: 23 rows x 4 subspaces x 2 bits = 23 B; re-rank 3 x 4 rows of
    # 32 B; queries 96 B; answers 48 B
    assert w["bytes"] == 23 + 3 * 4 * 32 + 96 + 48
    # tables: 3 x 2 probes x 4 subspaces x 4 entries x 2 x pq_len 2;
    # scan: 4 adds x 42 candidates; re-rank: 3 x 4 x 2 x 8
    assert w["flops"] == 3 * 2 * 4 * 4 * 2 * 2 + 4 * 42 + 3 * 4 * 2 * 8


def test_least_time_and_its_bound():
    peak = peaks.lookup("TPU v5 lite")
    t, bound = peaks.least_seconds({"bytes": 819e9, "flops": 1.0}, peak)
    assert (t, bound) == (pytest.approx(1.0), "bytes")
    t, bound = peaks.least_seconds({"bytes": 1.0, "flops": 394e12}, peak)
    assert (t, bound) == (pytest.approx(2.0), "ops")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("cpu")
