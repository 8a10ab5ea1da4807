"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A copy of ``raft_tpu.bench.roofline.PEAKS`` kept in ``peaks.json`` with the
benchmark. A device kind that is not in the table is an error: a default
would put one chip's numbers under another chip's name.
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def lookup(device_kind: str, path: str = os.path.join(HERE, "peaks.json")):
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(work: dict, peak: dict):
    """(seconds, bound) the chip needs at least for ``work``: the larger
    of operations over peak operations and bytes over peak bandwidth."""
    t_ops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
