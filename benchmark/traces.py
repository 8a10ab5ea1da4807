"""Reduction of a profiler trace to device busy time, time per named
device op, device time inside the benchmark's own host spans, and the
longest idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (or an
XSpace given as text, for tests) into plain events; ``reduce`` works on
those alone. A device op is an event on an ``XLA Ops`` line of a
``/device:`` plane. The benchmark's host spans are the
``jax.profiler.TraceAnnotation`` events whose names start with ``bench.``;
the span named ``bench.window`` marks the traced window.
"""
import bisect
import glob
import os

from jax.profiler import ProfileData

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
NO_SPAN = "host_other"


def _events(pd):
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            float(e.start_ns), float(e.end_ns)))
    return out


def load(logdir: str):
    """Events of the newest ``.xplane.pb`` under ``logdir``."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    return _events(ProfileData.from_file(max(files, key=os.path.getmtime)))


def load_text(text: str):
    return _events(ProfileData.from_text_proto(text))


def op_name(name: str, start: float, modules) -> str:
    """``<program>/<op>``: the op's HLO name (the text before `` = ``,
    without ``%``) under the name of the program running at ``start``
    (``modules``: sorted ``(start, end, name)``), its hash dropped."""
    op = name.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if i >= 0 and modules[i][1] > start:
        return modules[i][2].split("(", 1)[0] + "/" + op
    return op


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a, b):
    """Total length of the intersection of two unions of intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _label(s, e, spans):
    """The name of the host span that covers most of [s, e]."""
    best, cover = NO_SPAN, 0.0
    for n, u in spans.items():
        i = max(0, bisect.bisect_right(u, [s, float("inf")]) - 1)
        c = 0.0
        while i < len(u) and u[i][0] < e:
            c += max(0.0, min(e, u[i][1]) - max(s, u[i][0]))
            i += 1
        if c > cover:
            best, cover = n, c
    return best


def reduce(events, top: int = 10) -> dict:
    """Busy and idle seconds of the device(s) inside the traced window,
    seconds per device op name, device seconds inside each ``bench.*``
    host span name, and ``breakdown`` (the ``top`` ops by time, the ``top``
    longest idle gaps with the host span that covers most of each).
    Returns ``{}`` when the trace holds no device op."""
    wins = [(s, e) for p, ln, n, s, e in events
            if n == WINDOW and not p.startswith("/device:")]
    ops = [(p, n, s, e) for p, ln, n, s, e in events
           if p.startswith("/device:") and ln == OP_LINE]
    mods = {}
    for p, ln, n, s, e in events:
        if p.startswith("/device:") and ln == MODULE_LINE:
            mods.setdefault(p, []).append((s, e, n))
    mods = {p: sorted(v) for p, v in mods.items()}
    ops = [(p, op_name(n, s, mods.get(p, [])), s, e) for p, n, s, e in ops]
    if not wins or not ops:
        return {}
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    window_s = (hi - lo) / 1e9
    planes = sorted({p for p, *_ in ops})
    busy_by_plane = {p: _union(_clip([(s, e) for q, _, s, e in ops if q == p],
                                     lo, hi)) for p in planes}
    busy_s = sum(sum(e - s for s, e in u) for u in busy_by_plane.values())
    busy_s = busy_s / len(planes) / 1e9
    per_op = {}
    for _, n, s, e in ops:
        c = _clip([(s, e)], lo, hi)
        if c:
            per_op[n] = per_op.get(n, 0.0) + (c[0][1] - c[0][0]) / 1e9
    spans = {}
    for p, ln, n, s, e in events:
        if (n.startswith(SPAN_PREFIX) and n != WINDOW
                and not p.startswith("/device:")):
            spans.setdefault(n, []).append((s, e))
    spans = {n: _union(_clip(v, lo, hi)) for n, v in spans.items()}
    device_in_span = {n: sum(_overlap(busy_by_plane[p], u) for p in planes)
                      / len(planes) / 1e9 for n, u in spans.items()}
    # idle gaps of the first device plane, each labelled by the host span
    # that covers most of it
    busy0 = busy_by_plane[planes[0]]
    gaps, t = [], lo
    for s, e in busy0 + [[hi, hi]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(s, e, spans), (e - s) / 1e9) for s, e in gaps[:top]]
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "n_devices": len(planes), "ops_s": per_op,
            "device_in_span_s": device_in_span,
            "span_s": {n: sum(e - s for s, e in u) / 1e9
                       for n, u in spans.items()},
            "breakdown": {"device_ops": [[n, v] for n, v in top_ops],
                          "idle_gaps": [[n, v] for n, v in labelled[:top]]}}
