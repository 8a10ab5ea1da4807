"""A run whose timed path is broken underneath comes out not correct, for
each fault a search cell can have: an answer altered where it is made,
and half of a batch left out (its rows given the other half's answers)."""
import types

import numpy as np
import pytest

import harness
import tiny

N_ROWS = tiny.OVERRIDES["config"]["n_rows"]


def _break(fault, out, queries):
    d, i = np.array(out[0]), np.array(out[1])
    if fault == "altered":
        i[:, 0] = (i[:, 0] + 1) % N_ROWS
    else:
        real = np.flatnonzero(np.abs(np.asarray(queries)).sum(axis=1) > 0)
        rest = real[(len(real) + 1) // 2:]
        d[rest], i[rest] = d[real[:len(rest)]], i[real[:len(rest)]]
    return d, i


def _broken_loader(fault):
    real_load = harness.load_module

    def load(path):
        mod = real_load(path)
        if "families" not in path:
            return mod
        fam = types.SimpleNamespace(**vars(mod))

        def make_searcher(state):
            fn = mod.make_searcher(state)
            return lambda q, k, res=None: _break(fault, fn(q, k), q)

        fam.search = lambda state, q, k: _break(fault, mod.search(state, q, k),
                                                q)
        fam.make_searcher = make_searcher
        return fam

    return load


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
@pytest.mark.parametrize("cell", tiny.CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    monkeypatch.setattr(harness, "load_module", _broken_loader(fault))
    res = tiny.run(cell)
    assert res["correct"] is False
    assert res["failed"] > 0
    assert res["checks"]["dist_gap"]["value"] > \
        res["checks"]["dist_gap"]["limit"]


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_kernel_serving_its_fallback_is_not_correct(cell):
    """A scan kernel that fails opens its site's breaker, and the exact
    XLA fallback answers: right answers, but not from the path the cell
    times."""
    from raft_tpu.core import faults
    from raft_tpu.ops import guarded

    try:
        with faults.inject("kernel_fault", "ivf_*.scan"):
            res = tiny.run(cell)
    finally:
        guarded.reset()
    assert res["correct"] is False
    assert res["checks"]["demoted_sites"]["value"] > 0
    assert res["device"]["demoted_sites"]
