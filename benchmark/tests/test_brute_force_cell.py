"""The exact brute-force cell at a tiny size on the CPU (the fused kernel
in interpret mode): it runs end to end and is correct, its control is not,
and its work counting gives the hand counts."""
import numpy as np

import harness
import tiny

CELL = "sift1m-brute.batch10k"
# brute force probes its one list: a traced run counts work at 1 probe
OVERRIDES = {
    "config": {"n_rows": 2048, "search": {"n_probes": 1},
               "corpus": {"n_clusters": 2, "intrinsic_dim": 4}},
    "traffic": {"batch_queries": 32, "blocks": 2, "sample_rows_per_call": 8,
                "check_rows": 96},
}
WORK = harness.load_module(f"{harness.HERE}/work/brute_force.py")


def test_cell_runs_and_is_correct():
    res = tiny.run(CELL, overrides=OVERRIDES)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert res["checks"]["recall_at_10"]["value"] == 1.0


def test_traced_run_reads_the_build():
    res = tiny.run(CELL, trace=True, overrides=OVERRIDES)
    assert res["correct"], res["checks"]
    # the CPU has no device plane: the roofline share has nothing to read
    assert set(res["metrics"]) == {"build_s.brute_force"}


def test_control_is_not_correct():
    res = tiny.run(CELL, overrides=OVERRIDES, control=True)
    gap = res["checks"]["dist_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]


def test_work_by_hand():
    # 3 queries over one list of 20 rows of width 8, k=2
    w = WORK.work({"dim": 8}, np.array([20]), np.zeros((3, 1), int), k=2)
    # corpus 20 x 32 B, norms 20 x 4 B, queries 3 x 32 B, answers 3 x 2 x 8 B
    assert w["bytes"] == 20 * 32 + 20 * 4 + 96 + 48
    # every (query, row) pair: two ops per dimension
    assert w["flops"] == 2 * 3 * 20 * 8


def test_roofline_reads_only_its_family():
    reader = harness.load_module(
        f"{harness.HERE}/metrics/fused_knn_roofline.py")
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    rec = {"config": {"family": "brute_force"}, "peak": lambda: peak,
           "traced_work": [{"bytes": 10, "flops": 300}] * 2,
           "trace": {"device_in_span_s": {"bench.call": 12.0}}}
    # bound by ops: 600 / 100 = 6 s of 12 s in the calls
    assert reader.read(rec) == 50.0
    rec["config"] = {"family": "ivf_flat"}
    assert reader.read(rec) is None
