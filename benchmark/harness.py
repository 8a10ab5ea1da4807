"""The run body: one cell, once, on the devices it is given.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file, the family driver
``families/<family>.py`` the configuration names, the traffic mix
``traffic/<mix>.json`` and the generator ``traffic/<kind>.py`` it names,
the cell's own parameters ``cells/<cell>.json``, merged over its mix
where the file exists (a served cell's offered rate), the work counting
``work/<family>.py``, and one reader ``metrics/<metric>.py`` for each
metric. Adding a configuration, a mix or
a metric adds files and entries; nothing here changes.

A run: set-up (the configuration's corpus and the seed's queries on the
device, the index build, the traffic's own warm-up, and the heap made so
far moved out of the garbage collector's reach), the measured window
(traced with ``trace``), then, with the program's state freed, the check
of the window's answers against the plain reference, and the metrics.
"""
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import corpus  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import traces  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, root: str = ROOT, bench_dir: str = HERE):
    """(spec, cell, configuration, mix) of ``workload``, found by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, conf["file"]))
    mix = load_json(os.path.join(bench_dir, "traffic",
                                 cell["traffic"] + ".json"))
    own = os.path.join(bench_dir, "cells", workload + ".json")
    if os.path.exists(own):
        mix = _merged(mix, load_json(own))
    return spec, cell, cfg, mix


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def _merged(base: dict, over) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merged(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class Ctx:
    """What a traffic generator is handed: the cell's parameters, its
    queries and search entry points, seeds, and the trace switch."""

    def __init__(self, seed: int, cfg: dict, mix: dict, device):
        self.seed, self.cfg, self.mix, self.device = seed, cfg, mix, device
        self.errors = []
        self.queries = self.search = self.searcher = None
        self._win = None

    def host_seed(self, stream: int):
        return np.random.SeedSequence([int(self.seed), 1000 + int(stream)])

    def note_error(self, e: BaseException) -> None:
        if len(self.errors) < 5:
            self.errors.append(f"{type(e).__name__}: {e}")

    def start_trace(self, logdir: str) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
        self._win = TraceAnnotation(traces.WINDOW)
        self._win.__enter__()

    def stop_trace(self) -> None:
        if self._win is None:
            return
        self._win.__exit__(None, None, None)
        self._win = None
        jax.profiler.stop_trace()


class GcPauses:
    """The cyclic garbage collector's collections over a span. A
    collection holds every thread, so a long one is a host stall."""

    def __init__(self):
        self.pauses = []            # (start, seconds, generation)
        self._t = None

    def _note(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)

    def summary(self) -> str:
        if not self.pauses:
            return "0 collections"
        _, longest, gen = max(self.pauses, key=lambda p: p[1])
        return (f"{len(self.pauses)} collections, "
                f"{sum(p[1] for p in self.pauses) * 1e3:.3f} ms in all, "
                f"longest {longest * 1e3:.3f} ms (generation {gen})")


def _opened_sites(before: dict) -> list:
    """Guarded sites that serve their fallback, or opened since ``before``
    (a ``breaker_snapshot``): the timed path was then not the kernels."""
    from raft_tpu.ops import guarded

    return sorted(site for site, b in guarded.breaker_snapshot().items()
                  if b["state"] != "closed"
                  or b["opens"] > before.get(site, {}).get("opens", 0))


def _probes(project, centers, queries, n_probes: int) -> np.ndarray:
    q = project(queries)
    d = (jnp.sum(q * q, axis=1, keepdims=True)
         + jnp.sum(centers * centers, axis=1)[None, :]
         - 2.0 * jnp.matmul(q, centers.T,
                            precision=jax.lax.Precision.HIGHEST))
    return np.asarray(jax.lax.top_k(-d, n_probes)[1])


def check_answers(data, answers, k: int, limits: dict, check_rows: int,
                  rng) -> dict:
    """Hold the window's answers to the reference.

    ``answers``: ``[(op, queries (m, d), distances (m, k), ids (m, k))]``.
    Every answer is checked for form: k ids in range and distinct,
    distances finite and ascending. A sample of ``check_rows`` rows or
    more, drawn with ``rng``, is checked against the reference: the gap
    between each returned distance and the exact distance of that id, as
    a share of the exact k-th distance, and recall@k against the exact
    neighbours. Returns the ops at fault and the numbers compared."""
    n = data.shape[0]
    bad = set()
    for op, q, d, i in answers:
        m = q.shape[0]
        if d.shape != (m, k) or i.shape != (m, k):
            bad.add(op)
            continue
        s = np.sort(i, axis=1)
        if (not np.isfinite(d).all() or (np.diff(d, axis=1) < 0).any()
                or (i < 0).any() or (i >= n).any()
                or (s[:, 1:] == s[:, :-1]).any()):
            bad.add(op)
    chosen, rows = [], 0
    for a in rng.permutation(len(answers)):
        op, q, d, i = answers[a]
        if op in bad or d.shape != (q.shape[0], k):
            continue
        chosen.append(answers[a])
        rows += q.shape[0]
        if rows >= check_rows:
            break
    if not chosen:
        return {"bad_ops": bad, "rows": 0, "dist_gap": float("inf"),
                "dist_gap_median": float("inf"), "recall": 0.0}
    q = np.concatenate([c[1] for c in chosen])
    d = np.concatenate([c[2] for c in chosen]).astype(np.float64)
    i = np.concatenate([c[3] for c in chosen])
    exact_pair = reference.pair_distances(data, q, i)
    ref_d, ref_i = reference.knn(data, q, k)
    scale = np.maximum(np.asarray(ref_d[:, -1], np.float64), 1e-30)
    gap = (np.abs(d - exact_pair).max(axis=1)) / scale
    op_of_row = np.concatenate([[c[0]] * c[1].shape[0] for c in chosen])
    bad |= set(op_of_row[gap > limits["dist_gap_max"]].tolist())
    hits = (i[:, :, None] == ref_i[:, None, :]).any(axis=2).sum()
    return {"bad_ops": bad, "rows": int(q.shape[0]),
            "dist_gap": float(gap.max()),
            "dist_gap_median": float(np.median(gap)),
            "recall": float(hits / ref_i.size)}


class Cell:
    """A cell after set-up: its parameters, the family and traffic modules,
    the corpus, the program's state and the traffic's warmed plan."""


def set_up(workload: str, seed: int, seconds: float, trace: bool, devices,
           *, root: str = ROOT, bench_dir: str = HERE, overrides=None,
           control: bool = False) -> Cell:
    """The configuration's corpus and the seed's queries on the device,
    the index build, and the traffic's own warm-up.

    ``overrides``: ``{"config": {...}, "traffic": {...}}`` merged over the
    files (tests run every cell at a tiny size this way). ``control``: the
    control, the reference one precision step below the configuration's,
    takes the search's place."""
    c = Cell()
    c.spec, _, cfg, mix = cell_spec(workload, root, bench_dir)
    c.cfg = cfg = _merged(cfg, (overrides or {}).get("config"))
    c.mix = mix = _merged(mix, (overrides or {}).get("traffic"))
    c.fam = fam = load_module(os.path.join(bench_dir, "families",
                                           cfg["family"] + ".py"))
    c.kind = kind = load_module(os.path.join(bench_dir, "traffic",
                                             mix["kind"] + ".py"))
    device = devices[0]
    c.k = k = int(mix["k"])
    c.ctx = ctx = Ctx(seed, cfg, mix, device)
    with TraceAnnotation("bench.setup"):
        t = time.perf_counter()
        cc = dict(cfg["corpus"])
        c.data, queries = corpus.make(
            corpus.device_key(cc.pop("seed"), 0), corpus.device_key(seed, 1),
            int(cfg["n_rows"]), int(cfg["dim"]),
            kind.n_queries(mix, cfg, seconds), device=device, **cc)
        c.corpus_s = time.perf_counter() - t
        t = time.perf_counter()
        c.state = state = fam.build(c.data, cfg)
        c.build_s = time.perf_counter() - t
        if control:
            data = c.data

            def fn(q, kk, res=None):
                return reference.knn_bf16x3(data, q, kk)

            ctx.search, ctx.searcher = fn, fn
        else:
            ctx.search = functools.partial(fam.search, state)
            ctx.searcher = fam.make_searcher(state)
        ctx.queries = queries
        t = time.perf_counter()
        c.plan = plan = kind.prepare(ctx)
        c.warm_s = time.perf_counter() - t
        c.work = None
        if trace and plan.get("blocks") is not None:
            wmod = load_module(os.path.join(bench_dir, "work",
                                            cfg["family"] + ".py"))
            project, centers, sizes = fam.coarse(state)
            c.work = [wmod.work(cfg, sizes, _probes(
                project, centers, b, int(cfg["search"]["n_probes"])), k)
                for b in plan["blocks"]]
        # what set-up made lives for the whole run: moved out of the
        # collector's reach, as a server does after its warm-up, it is not
        # walked again by each full collection inside the window
        gc.collect()
        gc.freeze()
    return c


def free_program(c: Cell) -> None:
    """Close the traffic's plan and drop the program's state."""
    c.kind.close(c.plan)
    c.plan = c.state = None
    c.ctx.search = c.ctx.searcher = None
    gc.unfreeze()
    gc.collect()


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices, *, root: str = ROOT, bench_dir: str = HERE,
             overrides=None, control: bool = False, t_start=None) -> dict:
    """One run of ``workload``; returns the result line as a dict (see
    ``set_up`` for ``overrides`` and ``control``)."""
    from raft_tpu.ops import guarded

    t_start = time.perf_counter() if t_start is None else t_start
    breakers = guarded.breaker_snapshot()
    c = set_up(workload, seed, seconds, trace, devices, root=root,
               bench_dir=bench_dir, overrides=overrides, control=control)
    spec, cfg, mix, kind, ctx = c.spec, c.cfg, c.mix, c.kind, c.ctx
    device, k, data, plan = devices[0], c.k, c.data, c.plan
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.4f}: corpus {c.corpus_s:.4f} s, build "
        f"{c.build_s:.4f} s, warm-up {c.warm_s:.4f} s")

    from raft_tpu.serve import count_compilations

    # a mix with ``trace_seconds`` is traced in a window of its own after
    # the measured one: stopping a trace stalls the host for seconds,
    # which an open loop cannot absorb inside its window
    trace_s = mix.get("trace_seconds") if trace else None
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = None
    with count_compilations() as cc, GcPauses() as gcp:
        if trace and trace_s is None:
            ctx.start_trace(logdir)
        try:
            out = kind.run(plan, ctx, seconds)
        finally:
            ctx.stop_trace()
        if trace_s is not None:
            ctx.start_trace(logdir)
            try:
                traced = kind.run(plan, ctx, trace_s)
            finally:
                ctx.stop_trace()
    log(f"window: {out['ops']} ops in {out['elapsed_s']:.4f} s, "
        f"{cc.count} compiles inside it; garbage collector: "
        f"{gcp.summary()}")
    demoted = _opened_sites(breakers)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    mem = max((m for m in mem if m is not None), default=None)

    # the program's state goes before the reference runs
    del plan
    free_program(c)

    tr, traced_work = {}, None
    if trace:
        tr = traces.reduce(traces.load(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        if c.work is not None:
            traced_work = [c.work[j] for j in (traced or out)["calls"]]

    limits = cfg["limits"]
    chk = check_answers(data, out["answers"], k, limits,
                        int(mix["check_rows"]),
                        np.random.default_rng(ctx.host_seed(3)))
    failed = int(out["raised"] + out["missing"] + len(chk["bad_ops"]))
    log(f"failed by cause: raised {out['raised']}, never answered "
        f"{out['missing']}, wrong {len(chk['bad_ops'])}; "
        f"{chk['rows']} rows held to the reference, distance gap "
        f"{chk['dist_gap']!r} at most, {chk['dist_gap_median']!r} median")
    if "causes" in out:
        log("served: " + json.dumps({
            "causes": out["causes"], "counters": out["counters"],
            "refused_attempts": out["refused_attempts"],
            "queue_depth_peak_halves": out["depth_peak"],
            "gen_lag_mean_s_halves": out["lag_mean_s"],
            "requests": out["requests"], "completed": out["completed"]}))
    for e in ctx.errors:
        log(f"error: {e}")

    rec = {"cell": workload, "config": cfg, "traffic": mix, "seed": seed,
           "setup_s": setup_s, "build_s": c.build_s, "window": out,
           "recall": chk["recall"], "trace": tr, "traced_work": traced_work,
           "peak": lambda: peaks.lookup(device.device_kind)}
    if traced_work and tr:
        tot = {key: sum(w[key] for w in traced_work)
               for key in ("bytes", "flops")}
        least, bound = peaks.least_seconds(tot, rec["peak"]())
        log(f"roofline work of {len(traced_work)} traced calls: "
            f"{tot['bytes']} bytes, {tot['flops']} ops; least "
            f"{least:.6f} s, bound by {bound}; device time in the calls "
            f"{tr['device_in_span_s'].get('bench.call', 0.0):.6f} s")
    metrics = {}
    for m in metrics_for(spec, workload, trace):
        reader = load_module(os.path.join(bench_dir, "metrics",
                                          m["name"] + ".py"))
        v = reader.read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": mem,
           "demoted_sites": demoted}
    result = {"correct": failed == 0 and not demoted
              and chk["recall"] >= limits["recall_at_10_min"]
              and chk["dist_gap"] <= limits["dist_gap_max"],
              "attempted": int(out["ops"]), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace and tr:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
        log("trace: " + json.dumps({
            "ops_s": dict(sorted(tr["ops_s"].items(),
                                 key=lambda kv: -kv[1])[:25]),
            "device_in_span_s": tr["device_in_span_s"],
            "span_s": tr["span_s"]}))
    result["checks"] = {
        "failed": {"value": failed, "limit": 0},
        "recall_at_10": {"value": chk["recall"],
                         "limit": limits["recall_at_10_min"]},
        "dist_gap": {"value": chk["dist_gap"],
                     "limit": limits["dist_gap_max"]},
        "demoted_sites": {"value": len(demoted), "limit": 0}}
    return result
