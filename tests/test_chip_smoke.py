"""CPU rehearsal of ``chip_smoke.py``'s control flow.

The script's phase bodies run here at a tiny size on the CPU (Pallas
kernels in interpret mode), so a wrong path, argument or gate fails in
tier-1 instead of on the chip. The script itself only runs on a TPU:
``main`` must refuse the CPU.
"""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def test_one_chip_phases():
    assert chip_smoke.run(jax.devices()[:1], n=4096, nq=64, n_lists=32,
                          n_graph=2048) == []


def test_sharded_phases_on_four_devices():
    assert chip_smoke.run_sharded(jax.devices()[:4], n_per=2048, nq=64,
                                  n_lists=16) == []


def test_refuses_cpu(monkeypatch):
    # a set cache dir keeps main from placing the cache in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    assert chip_smoke.main([]) == 1


def test_missed_gate_fails_phase_and_reports_readings(capsys):
    ph = chip_smoke.Phases()

    def body():
        chip_smoke._gate({"recall": 0.5}, "x recall", 0.5, 0.9)

    assert ph.run("x", body) is None
    assert ph.failed == ["x"]
    assert 'x readings: {"recall": 0.5}' in capsys.readouterr().out
