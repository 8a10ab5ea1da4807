"""The program's own spans in a traced run: what the library was doing
while the device sat idle.

    python3 benchmark/spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell once as ``run.py --trace 1`` does (its result line is
printed as ``run.py`` prints it), keeps the profile's events, and then
prints one more JSON line: ``reduce`` of those events and the
``readings`` they give.

The program's spans are the ``jax.profiler.TraceAnnotation`` events of
``raft_tpu.core.tracing`` on the host, named ``raft_tpu::<module>::<step>``
(docs/observability.md). ``reduce`` works on the events that
``traces.load`` gives, with the window, device busy time and idle gaps
that ``traces.reduce`` uses.
"""
import json
import sys

import traces

PREFIX = "raft_tpu::"
LONG_GAP_S = 0.05

SERVE_DISPATCH = "raft_tpu::serve::dispatch"
SERVE_DEMUX = "raft_tpu::serve::demux"
SEARCHES = ("raft_tpu::ivf_flat::search", "raft_tpu::ivf_pq::search",
            "raft_tpu::refine")


def reduce(events, top: int = 10, long_s: float = LONG_GAP_S) -> dict:
    """Per program span name inside the traced window: ``span_n`` (its
    events that reach into the window), ``span_sum_s`` (their seconds,
    clipped to the window), ``span_s`` and ``device_in_span_s`` (the
    union of the events, and device seconds inside it, as ``traces``
    counts them for ``bench.*``), ``idle_in_span_s`` (device-idle
    seconds inside that union); ``idle_gaps``: the ``top`` longest idle
    gaps of the first device and every gap of ``long_s`` or more, each
    ``[label, seconds, start in the window]``. A gap's label is the
    program span that covers most of it, the innermost (shortest) on a
    tie, and where none covers it the ``bench.*`` span ``traces``
    would name. Returns ``{}`` when the trace holds no device op."""
    wins = [(s, e) for p, ln, n, s, e in events
            if n == traces.WINDOW and not p.startswith("/device:")]
    ops = {}
    for p, ln, n, s, e in events:
        if p.startswith("/device:") and ln == traces.OP_LINE:
            ops.setdefault(p, []).append((s, e))
    if not wins or not ops:
        return {}
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    window_s = (hi - lo) / 1e9
    busy = {p: traces._union(traces._clip(v, lo, hi))
            for p, v in sorted(ops.items())}
    prog, bench = {}, {}
    for p, ln, n, s, e in events:
        if p.startswith("/device:"):
            continue
        if n.startswith(PREFIX):
            prog.setdefault(n, []).append((s, e))
        elif n.startswith(traces.SPAN_PREFIX) and n != traces.WINDOW:
            bench.setdefault(n, []).append((s, e))
    clipped = {n: traces._clip(v, lo, hi) for n, v in prog.items()}
    unions = {n: traces._union(v) for n, v in clipped.items()}
    bench = {n: traces._union(traces._clip(v, lo, hi))
             for n, v in bench.items()}
    dev_in = {n: sum(traces._overlap(b, u) for b in busy.values())
              / len(busy) / 1e9 for n, u in unions.items()}
    span_s = {n: sum(e - s for s, e in u) / 1e9 for n, u in unions.items()}

    def label(s, e):
        best, key = None, (0.0, 0.0)
        for n, u in unions.items():
            c = traces._overlap([[s, e]], u)
            if c <= 0.0:
                continue
            dur = min(b - a for a, b in prog[n]
                      if min(e, b) > max(s, a))
            if (c, -dur) > key:
                best, key = n, (c, -dur)
        return best or traces._label(s, e, bench)

    first = busy[min(busy)]
    gaps, t = [], lo
    for s, e in first + [[hi, hi]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    kept = [g for i, g in enumerate(gaps)
            if i < top or (g[1] - g[0]) / 1e9 >= long_s]
    return {"window_s": window_s,
            "busy_s": sum(sum(e - s for s, e in u) for u in busy.values())
            / len(busy) / 1e9,
            "span_n": {n: len(v) for n, v in clipped.items()},
            "span_sum_s": {n: sum(e - s for s, e in v) / 1e9
                           for n, v in clipped.items()},
            "span_s": span_s, "device_in_span_s": dev_in,
            "idle_in_span_s": {n: span_s[n] - dev_in[n] for n in unions},
            "idle_gaps": [[label(s, e), (e - s) / 1e9, (s - lo) / 1e9]
                          for s, e in kept]}


def readings(red: dict) -> dict:
    """What the program's spans say of a reduced trace, under the names
    of the per-layer metrics they would feed (in ms and %): the mean
    dispatch and demux step of the serving batcher, and the share of the
    window that the device sat idle inside the batcher's dispatch and
    demux (``idle_host_share.served``) or inside a search call
    (``idle_host_share.batch``), beside the whole idle share. A name
    whose spans are missing is left out."""
    if not red:
        return {}
    n, tot, idle = red["span_n"], red["span_sum_s"], red["idle_in_span_s"]
    w = red["window_s"]
    out = {"idle_share": 100.0 * (1.0 - red["busy_s"] / w)}
    for metric, span in (("dispatch_ms.served", SERVE_DISPATCH),
                         ("demux_ms.served", SERVE_DEMUX)):
        if n.get(span):
            out[metric] = 1e3 * tot[span] / n[span]
    if SERVE_DISPATCH in idle or SERVE_DEMUX in idle:
        out["idle_host_share.served"] = 100.0 * (
            idle.get(SERVE_DISPATCH, 0.0) + idle.get(SERVE_DEMUX, 0.0)) / w
    if any(s in idle for s in SEARCHES):
        out["idle_host_share.batch"] = 100.0 * sum(
            idle.get(s, 0.0) for s in SEARCHES) / w
    return out


def main(argv=None) -> int:
    import run

    kept = []
    load = traces.load

    def keep(logdir):
        kept.append(load(logdir))
        return kept[-1]

    traces.load = keep
    argv = list(sys.argv[1:] if argv is None else argv)
    rc = run.main(argv + ["--trace", "1"])
    if rc == 0 and kept:
        red = reduce(kept[-1])
        print(json.dumps({"spans": red, "readings": readings(red)}),
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
