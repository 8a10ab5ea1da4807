"""A later change adds a configuration, a traffic mix, a served cell with
its own offered rate, and a per-layer metric by adding files and entries
alone: the harness takes them up by name."""
import json
import os
import shutil

import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_new_config_mix_and_metric_by_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p.relative_to(bench): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}

    cfg = json.loads((bench / "configs" / "sift1m-ivf_flat.json").read_text())
    cfg.update(name="toy-ivf_flat", index={"n_lists": 8, "seed": 0})
    (bench / "configs" / "toy-ivf_flat.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "trickle.json").write_text(json.dumps({
        "kind": "closed", "batch_queries": 16, "blocks": 3, "k": 10,
        "sample_rows_per_call": 4, "check_rows": 48}))
    (bench / "cells" / "toy-ivf_flat.served.json").write_text(json.dumps({
        "rate_per_s": 30}))
    (bench / "metrics" / "calls_made.py").write_text(
        "def read(rec):\n    return rec['window']['ops']\n")

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "toy-ivf_flat", "source": "a test", "reduced": [],
        "file": "benchmark/configs/toy-ivf_flat.json", "why": "a test"})
    spec["workloads"].append({
        "name": "toy-ivf_flat.trickle", "config": "toy-ivf_flat",
        "traffic": "trickle", "chips": 1, "why": "a test"})
    spec["workloads"].append({
        "name": "toy-ivf_flat.served", "config": "toy-ivf_flat",
        "traffic": "served", "chips": 1, "why": "a test"})
    spec["per_layer"].append({
        "name": "calls_made", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "recall_at_10", "workloads": ["toy-ivf_flat.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    over = {"config": tiny.OVERRIDES["config"] | {"index": {"n_lists": 8}}}
    res = tiny.run("toy-ivf_flat.trickle", trace=True, overrides=over,
                   root=str(tmp_path), bench_dir=str(bench))
    assert res["correct"], res["checks"]
    assert res["metrics"]["calls_made"]["value"] == res["attempted"] > 0
    served = tiny.run("toy-ivf_flat.served", overrides={
        "config": over["config"],
        "traffic": {"query_buckets": [16], "check_rows": 96}},
        root=str(tmp_path), bench_dir=str(bench), seconds=1.0)
    assert served["correct"], served["checks"]
    assert served["attempted"] == 30     # the cell's own rate, a second
    # nothing that was there changed
    assert {p: before[p] for p in before} == {
        p: (bench / p).read_bytes() for p in before}
