"""Every cell end to end at a tiny size on the CPU (kernels in interpret
mode), and the command's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_cell_runs_and_is_correct(cell):
    res = tiny.run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) >= {"recall_at_10", "setup_s"}
    assert ("qps" if cell.endswith("batch10k") else "p95_ms") in res["metrics"]
    assert list(res)[-1] == "checks"
    assert json.loads(json.dumps(res)) == res


def test_traced_run_reports_per_layer_metrics():
    # the served mix traces a short window of its own after the measured one
    over = {"config": tiny.OVERRIDES["config"],
            "traffic": tiny.OVERRIDES["traffic"] | {"trace_seconds": 0.3}}
    res = tiny.run("sift1m-ivf_pq.served", trace=True, overrides=over)
    assert res["correct"]
    # the CPU has no device plane: only what the records hold is read
    assert set(res["metrics"]) == {"queue_wait_ms.served",
                                   "batch_fill.served",
                                   "gen_lag_ms.served", "build_s.ivf_pq"}


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sift1m-ivf_flat.batch10k", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_command_refuses_a_machine_without_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_refused_requests_are_sent_again(monkeypatch):
    """The admission queue's backpressure delays a request; it is not a
    failure. Every window request of two rows or more is refused once."""
    from raft_tpu import serve

    real = serve.MicroBatcher.submit
    refuse = [True]

    def flaky(self, queries, k, deadline=None):
        if len(queries) >= 2:
            refuse[0] = not refuse[0]
            if not refuse[0]:
                raise serve.QueueFullError("admission queue full")
        return real(self, queries, k, deadline)

    monkeypatch.setattr(serve.MicroBatcher, "submit", flaky)
    res = tiny.run("sift1m-ivf_pq.served")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
