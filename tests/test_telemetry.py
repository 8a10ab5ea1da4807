"""Request-lifecycle telemetry tests: trace-ID propagation + stage
decomposition through the serving pipeline, the flight recorder, the
recompile watch, the debugz ops surface, and the zero-overhead-when-off
guarantee (docs/observability.md).

Everything except the recompile-watch test runs on STUB searchers (no
XLA compiles) so the whole file stays well under the tier-1 budget.
"""
import glob
import io
import json
import os
import time

import jax
import numpy as np
import pytest

from raft_tpu.core import events, faults, serialize, tracing
from raft_tpu.core.deadline import Deadline, DeadlineExceeded
from raft_tpu.core.errors import CorruptIndexError
from raft_tpu.serve import batcher as batcher_mod, debugz, metrics
from raft_tpu.serve.batcher import STAGES, BucketLadder, MicroBatcher

pytestmark = pytest.mark.serve

DIM = 16


def stub_search(queries, k, res=None):
    m = queries.shape[0]
    return (np.zeros((m, k), np.float32),
            np.tile(np.arange(k, dtype=np.int32), (m, 1)))


@pytest.fixture
def reg():
    return metrics.Registry()


@pytest.fixture(autouse=True)
def _clean_rings():
    events.clear()
    tracing.clear_span_log()
    yield


class TestTracingPrimitives:
    def test_trace_ids_and_binding(self):
        a, b = tracing.new_trace_id(), tracing.new_trace_id()
        assert a != b and len(a) == 16
        assert tracing.current_traces() == ()
        with tracing.bind_trace(a):
            assert tracing.current_trace() == a
            with tracing.bind_trace(a, b):
                assert tracing.current_traces() == (a, b)
            assert tracing.current_traces() == (a,)
        assert tracing.current_trace() is None

    def test_child_span_collects(self):
        out = {}
        with tracing.range("unit::outer", out=out):
            with tracing.range("unit::stage", out=out):
                time.sleep(0.001)
        assert out["unit::stage"] >= 0.001
        assert out["unit::outer"] >= out["unit::stage"]

    def test_sample_rate_validation(self, monkeypatch):
        monkeypatch.delenv("RAFT_TPU_TRACE_SAMPLE", raising=False)
        assert tracing.sample_rate() == 0.0
        monkeypatch.setenv("RAFT_TPU_TRACE_SAMPLE", "0.25")
        assert tracing.sample_rate() == 0.25
        assert tracing.sample_rate(1.0) == 1.0     # explicit beats env
        for bad in ("nope", "-0.1", "1.5", "nan"):
            monkeypatch.setenv("RAFT_TPU_TRACE_SAMPLE", bad)
            with pytest.raises(ValueError):
                tracing.sample_rate()
        # the knob is validated at batcher construction, not first sample
        with pytest.raises(ValueError):
            MicroBatcher(stub_search, DIM, trace_sample=2.0,
                         autostart=False)

    def test_span_log_ring(self):
        for i in range(5):
            tracing.log_spans(f"t{i}", {"dispatch": 0.001 * i}, rows=1)
        spans = tracing.recent_spans(3)
        assert [s["trace_id"] for s in spans] == ["t2", "t3", "t4"]
        tracing.set_span_log_capacity(2)
        try:
            assert len(tracing.recent_spans()) == 2
        finally:
            tracing.set_span_log_capacity(256)


class TestEventsRing:
    def test_record_recent_export(self, tmp_path):
        events.record("unit_kind", "unit.site", detail=7)
        with tracing.bind_trace("abc123"):
            events.record("unit_kind", "unit.site2")
        evs = events.recent(kind="unit_kind")
        assert len(evs) == 2
        assert evs[0]["trace_id"] is None and evs[0]["detail"] == 7
        assert evs[1]["trace_id"] == "abc123"
        assert evs[1]["seq"] > evs[0]["seq"]
        assert events.counts()["unit_kind"] == 2
        lines = events.to_jsonl(kind="unit_kind").strip().splitlines()
        assert len(lines) == 2 and json.loads(lines[0])["site"] == "unit.site"
        path = tmp_path / "events.jsonl"
        assert events.export_jsonl(str(path)) == 2
        assert len(path.read_text().strip().splitlines()) == 2

    def test_bounded_ring(self):
        events.set_capacity(8)
        try:
            for i in range(20):
                events.record("flood", f"s{i}")
            evs = events.recent()
            assert len(evs) == 8 and evs[-1]["site"] == "s19"
        finally:
            events.set_capacity(events.DEFAULT_CAPACITY)
            events.clear()


class TestTracePropagation:
    def test_cobatched_pair_distinct_decompositions(self, reg):
        """Two requests coalesced into ONE batch: each yields its own
        five-stage decomposition (own trace ID, own queue_wait, shared
        batch stages) in the span log AND the stage histograms."""
        b = MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                         registry=reg, autostart=False, trace_sample=1.0,
                         max_wait_s=0.001)
        r1 = b.submit(np.zeros((3, DIM), np.float32), 5)
        time.sleep(0.002)      # make the two queue waits distinguishable
        r2 = b.submit(np.zeros((2, DIM), np.float32), 5)
        assert r1.trace_id != r2.trace_id
        b.start()
        r1.result(60)
        r2.result(60)
        b.close()
        assert reg.counter("serve.batches").value == 1   # truly co-batched
        spans = {s["trace_id"]: s for s in tracing.recent_spans()}
        assert set(spans) == {r1.trace_id, r2.trace_id}
        s1, s2 = spans[r1.trace_id], spans[r2.trace_id]
        for s in (s1, s2):
            assert set(s["stages"]) == set(STAGES)
            assert s["bucket"] == "8x8"
        # distinct decompositions: r1 waited ~2ms longer than r2; the
        # shared batch stages agree exactly
        assert s1["stages"]["queue_wait"] > s2["stages"]["queue_wait"]
        assert s1["stages"]["dispatch"] == s2["stages"]["dispatch"]
        # pad lies inside the dispatch range; the dispatch histogram
        # observes the same range
        assert s1["stages"]["bucket_pad"] <= s1["stages"]["dispatch"]
        assert s1["rows"] == 3 and s2["rows"] == 2
        # metrics snapshot carries the five-stage latency decomposition
        snap = reg.snapshot()["histograms"]
        for s in STAGES:
            assert snap[f"serve.stage.{s}_s"]["count"] == 2
        assert snap["serve.batch_latency_s"]["sum"] == pytest.approx(
            s1["stages"]["dispatch"])

    def test_queue_wait_stamped_without_sampling(self, reg):
        """Every popped request gets its dequeue stamp, whatever the
        sampling rate: a caller's queue-wait reading does not depend on
        it."""
        b = MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                         registry=reg, autostart=False, trace_sample=0,
                         max_wait_s=0.0)
        r = b.submit(np.zeros((1, DIM), np.float32), 5)
        time.sleep(0.002)
        b.start()
        r.result(60)
        b.close()
        assert r.dequeued_at - r.enqueued_at >= 0.002

    def test_sampling_interval(self, reg):
        """trace_sample=0.5 decomposes every 2nd batch (deterministic
        counter, not a coin flip)."""
        b = MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                         registry=reg, autostart=False, trace_sample=0.5,
                         max_wait_s=0.0)
        reqs = []
        b.start()
        for _ in range(4):     # serial singles: 4 batches
            r = b.submit(np.zeros((1, DIM), np.float32), 5)
            r.result(60)
            reqs.append(r)
        b.close()
        assert reg.counter("serve.batches").value == 4
        sampled = {s["trace_id"] for s in tracing.recent_spans()}
        assert sampled == {reqs[0].trace_id, reqs[2].trace_id}

    def test_sampling_rate_never_exceeded(self):
        """ceil(1/rate), not round: 0.7 must probe every 2nd batch, never
        100% (the knob bounds telemetry's latency cost from above)."""
        b = MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                         registry=metrics.Registry(), autostart=False,
                         trace_sample=0.7)
        assert b._probe_every == 2


class TestFlightRecorder:
    def test_demotion_and_sheds_stamped_with_trace_id(self, reg):
        """The acceptance drill: an injected guarded demotion and a
        deadline shed both land in the recorder stamped with the
        originating request's trace ID."""
        from raft_tpu.ops import guarded

        if any(f.kind == "kernel_compile" for f in faults.active()):
            pytest.skip("ambient kernel faults are served as injected "
                        "(non-demoting) failures")

        def demoting_search(queries, k, res=None):
            def boom():
                raise RuntimeError("mosaic lowering died")

            guarded.guarded_call("telemetry.kernel", boom,
                                 lambda: None)
            return stub_search(queries, k)

        b = MicroBatcher(demoting_search, DIM,
                         ladder=BucketLadder((8,), (8,)), registry=reg,
                         autostart=False, max_wait_s=0.001)
        req = b.submit(np.zeros((2, DIM), np.float32), 4)
        dead = b.submit(np.zeros((2, DIM), np.float32), 4,
                        deadline=Deadline(0.0))
        b.start()
        try:
            req.result(60)
            with pytest.raises(DeadlineExceeded):
                dead.result(60)
        finally:
            b.close()
            guarded.reset()
        demo = events.recent(kind="guarded_demotion")
        assert len(demo) == 1 and demo[0]["site"] == "telemetry.kernel"
        assert demo[0]["trace_id"] == req.trace_id
        shed = events.recent(kind="deadline_shed")
        assert len(shed) == 1 and shed[0]["trace_id"] == dead.trace_id
        assert shed[0]["site"] == "serve.shed"

    def test_mid_batch_deadline_event(self, reg):
        def ticking(ticks):
            it = iter(ticks)
            return lambda: next(it)

        def expiring(queries, k, res=None):
            raise DeadlineExceeded("deadline", partial=None)

        b = MicroBatcher(expiring, DIM, ladder=BucketLadder((8,), (8,)),
                         registry=reg, autostart=False, max_wait_s=0.001)
        # live through ctor/pop/dispatch/tightest probes, expired at the
        # partial-delivery check
        req = b.submit(np.zeros((2, DIM), np.float32), 4,
                       deadline=Deadline(1.0, clock=ticking(
                           [0., .1, .2, .3, 2.0, 2.1])))
        b.start()
        with pytest.raises(DeadlineExceeded):
            req.result(60)
        b.close()
        evs = events.recent(kind="deadline_exceeded")
        assert len(evs) == 1 and evs[0]["trace_id"] == req.trace_id

    def test_fault_fire_metric_and_event(self):
        before = metrics.counter(
            "faults.fired.slow_dispatch.telemetry.drill").value
        ev_before = len(events.recent(kind="fault_injected"))
        with faults.inject("slow_dispatch", "telemetry.drill", value=0.0):
            faults.sleep_if("telemetry.drill")
            faults.sleep_if("telemetry.drill")   # per-batch drill re-fire
        # counter carries the magnitude (every fire) ...
        assert metrics.counter(
            "faults.fired.slow_dispatch.telemetry.drill").value \
            == before + 2
        # ... but the bounded ring records only the fault's FIRST fire
        evs = events.recent(kind="fault_injected")
        assert len(evs) == ev_before + 1
        assert evs[-1]["site"] == "telemetry.drill"
        assert evs[-1]["kind"] == "fault_injected"

    def test_shard_mark_records_only_transitions(self):
        """Re-asserting an unchanged shard health state (a health-check
        loop) must not churn the bounded ring — only transitions land."""
        from raft_tpu.parallel.sharded_ann import _mark_shard

        ok = np.ones(4, bool)
        before = len(events.recent(kind="shard_marked"))
        _mark_shard(ok, "unit", 2, False)      # transition: healthy->dead
        _mark_shard(ok, "unit", 2, False)      # re-assert: no new event
        _mark_shard(ok, "unit", 2, True)       # transition: dead->healthy
        _mark_shard(ok, "unit", 2, True)       # re-assert: no new event
        evs = events.recent(kind="shard_marked")
        assert len(evs) == before + 2
        assert evs[-1]["ok"] is True and not evs[-2]["ok"]

    def test_corrupt_load_metric_and_event(self):
        before = metrics.counter("serialize.corrupt_load").value
        with pytest.raises(CorruptIndexError):
            serialize.load_arrays(io.BytesIO(b"not a raft_tpu file at all"))
        assert metrics.counter("serialize.corrupt_load").value == before + 1
        evs = events.recent(kind="corrupt_index")
        assert evs and evs[-1]["site"] == "header"

    def test_autotune_verdict_event(self):
        from raft_tpu.ops import autotune

        key = "cpu:test:telemetry_family:n1"
        try:
            autotune.record(key, "stub_engine", persist=False)
            evs = events.recent(kind="autotune_verdict")
            assert evs and evs[-1]["site"] == key
            assert evs[-1]["choice"] == "stub_engine"
            assert key in autotune.entries()
        finally:
            autotune.forget(key)


class TestRecompileWatch:
    def test_stream_counter_and_labels(self):
        from raft_tpu.serve import warmup as wu

        wu.install_recompile_watch()
        before = metrics.counter("serve.recompiles").value
        total_before = metrics.counter("serve.compiles").value
        with wu.compile_context("telemetry:16x8"):
            jax.block_until_ready(
                jax.jit(lambda x: x * 3.7 + 1)(np.arange(33, dtype=np.float32)))
        assert metrics.counter("serve.recompiles").value >= before + 1
        evs = events.recent(kind="xla_compile")
        assert any(e["site"] == "telemetry:16x8" and not e["warmup"]
                   for e in evs)
        # warmup-context compiles are counted in the totals but exempt
        # from the post-warmup counter AND from the bounded ring (a
        # ~100-compile warmup sweep must not churn out demotion events)
        before = metrics.counter("serve.recompiles").value
        with wu.compile_context("telemetry:warm", warmup=True):
            jax.block_until_ready(
                jax.jit(lambda x: x * 2.5 - 3)(np.arange(34, dtype=np.float32)))
        assert metrics.counter("serve.recompiles").value == before
        assert metrics.counter("serve.compiles").value >= total_before + 2
        assert not any(e["site"] == "telemetry:warm"
                       for e in events.recent(kind="xla_compile"))
        # count_compilations subscribes to the same persistent stream
        with wu.count_compilations() as cc:
            jax.block_until_ready(
                jax.jit(lambda x: x - 0.125)(np.arange(35, dtype=np.float32)))
        assert cc.count >= 1


class TestDebugz:
    def test_snapshot_and_render(self, reg, tmp_path):
        with MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                          registry=reg, max_wait_s=0.001,
                          trace_sample=1.0) as b:
            b.search(np.zeros((2, DIM), np.float32), 5, timeout=60)
            events.record("unit_kind", "debugz.site")
            reg.histogram("unit.empty_h")     # NaN min/max must scrub
            snap = debugz.snapshot(batcher=b, registry=reg)
            # registry omitted -> the batcher's OWN registry, not the
            # default one (where its dispatch counters never land)
            assert debugz.snapshot(batcher=b)["ladder"]["dispatches"][
                "8x8"] == 1
            text = debugz.render_text(batcher=b, registry=reg)
            w = debugz.SnapshotWriter(str(tmp_path / "debugz.json"),
                                      interval_s=60.0, batcher=b,
                                      registry=reg)
            w.write_once()
        assert snap["ladder"]["dispatches"]["8x8"] == 1
        assert snap["ladder"]["queue_depth"] == 0
        assert snap["metrics"]["counters"]["serve.served"] == 1
        assert isinstance(snap["autotune"], dict)
        assert any(e["kind"] == "unit_kind" for e in snap["events"])
        assert snap["spans"]            # trace_sample=1.0 logged the request
        # strict-JSON-safe end to end: empty histograms must not leak
        # bare NaN tokens into on-disk post-mortem snapshots
        json.dumps(snap, allow_nan=False)
        # tail size 0 means "omit", not "everything in the ring"
        empty = debugz.snapshot(batcher=b, registry=reg, events_n=0,
                                spans_n=0)
        assert empty["events"] == [] and empty["spans"] == []
        assert "bucket ladder" in text and "8x8: 1 dispatches" in text
        assert "flight recorder" in text
        disk = json.loads((tmp_path / "debugz.json").read_text())
        assert disk["metrics"]["counters"]["serve.served"] == 1

    def test_snapshot_writer_background(self, reg, tmp_path):
        path = tmp_path / "bg.json"
        w = debugz.SnapshotWriter(str(path), interval_s=0.01, registry=reg)
        with w:
            deadline = time.monotonic() + 5.0
            while not path.exists() and time.monotonic() < deadline:
                time.sleep(0.01)
        assert path.exists() and "metrics" in json.loads(path.read_text())


class TestDriftGuard:
    # the public search/build verbs every neighbors family must trace
    VERBS = {"build", "search", "extend", "build_from_batches",
             "build_knn_graph", "knn", "eps_nn", "refine", "optimize"}

    def test_every_entry_point_is_annotated(self):
        import raft_tpu.neighbors as nb

        missing = []
        for mod_name in nb.__all__:
            mod = getattr(nb, mod_name)
            if mod_name == "ann_types":
                continue
            for fn_name in getattr(mod, "__all__", ()):
                if fn_name not in self.VERBS:
                    continue
                fn = getattr(mod, fn_name)
                if not getattr(fn, "__raft_traced__", False):
                    missing.append(f"{mod_name}.{fn_name}")
        assert not missing, (
            f"public neighbors entry points missing tracing.annotate: "
            f"{missing} — wrap them (docs/observability.md drift guard)")

    def test_every_literal_event_kind_is_registered(self):
        """Every literal flight-recorder kind emitted anywhere in the
        library must be in events.WELL_KNOWN_KINDS (operators grep
        dashboards by kind — a new emitter must announce its
        vocabulary), and every registered kind the docstring promises
        must actually be registered."""
        import os
        import re

        import raft_tpu

        root = os.path.dirname(raft_tpu.__file__)
        # events.record / _events.record / mutable's self._event helper,
        # with a literal first argument (possibly on the next line)
        pat = re.compile(
            r"(?:\bevents\.record|\b_events\.record|self\._event)"
            r"\(\s*\n?\s*\"([a-z_]+)\"")
        found = {}
        for dirpath, _dirs, files in os.walk(root):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                with open(path) as f:
                    for kind in pat.findall(f.read()):
                        found.setdefault(kind, []).append(
                            os.path.relpath(path, root))
        assert found, "the kind scan found nothing — pattern rot?"
        unregistered = {k: v for k, v in found.items()
                        if k not in events.WELL_KNOWN_KINDS}
        assert not unregistered, (
            f"flight-recorder kinds not in events.WELL_KNOWN_KINDS: "
            f"{unregistered} — register them (core/events.py docstring)")
        # the multi-tenant vocabulary this PR registered is present
        assert {"tenant_shed", "tenant_swap",
                "qcache_stale"} <= events.WELL_KNOWN_KINDS


def _profile_spans(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` trace; the ``raft_tpu::``
    spans of each host thread line, as ``[[(name, start_ns, end_ns)]]``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.end_ns) for e in line.events
                     if e.name.startswith("raft_tpu::")]
            if spans:
                lines.append(spans)
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class TestProfileSpans:
    """The library's trace ranges reach an operator's profile with no
    switch set: the batcher's steps on its worker's line, and a search's
    steps inside its entry point's span."""

    def test_batcher_steps_nest_on_the_worker_line(self, reg, tmp_path):
        b = MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                         registry=reg, autostart=False, max_wait_s=0.001)
        req = b.submit(np.zeros((2, DIM), np.float32), 5)

        def serve_one():
            b.start()
            req.result(60)
            b.close()

        lines = _profile_spans(tmp_path, serve_one)
        serve = [ln for ln in lines
                 if any(n.startswith("raft_tpu::serve::") for n, *_ in ln)]
        assert len(serve) == 1, "the worker's steps span several lines"
        by = {}
        for ev in serve[0]:
            by.setdefault(ev[0], []).append(ev)
        names = (batcher_mod.POP, batcher_mod.DISPATCH, batcher_mod.PAD,
                 batcher_mod.DEMUX, batcher_mod.FETCH, batcher_mod.DELIVER)
        assert set(by) == set(names)
        # one dispatch and one demux for the one batch; pop before both
        for n in names[1:]:
            assert len(by[n]) == 1, n
        (disp,), (dmx,) = by[batcher_mod.DISPATCH], by[batcher_mod.DEMUX]
        assert _inside(by[batcher_mod.PAD][0], disp)
        assert _inside(by[batcher_mod.FETCH][0], dmx)
        assert _inside(by[batcher_mod.DELIVER][0], dmx)
        assert by[batcher_mod.FETCH][0][2] <= by[batcher_mod.DELIVER][0][1]
        assert disp[2] <= dmx[1]
        assert by[batcher_mod.POP][0][2] <= disp[1]

    def test_ivf_flat_search_steps_inside_the_search_span(self, tmp_path):
        from raft_tpu.neighbors import ivf_flat

        rng = np.random.default_rng(0)
        data = rng.standard_normal((256, DIM)).astype(np.float32)
        index = ivf_flat.build(data, ivf_flat.IndexParams(n_lists=4,
                                                          seed=0))
        sp = ivf_flat.SearchParams(n_probes=2)
        q = data[:8]

        def search():
            jax.block_until_ready(
                ivf_flat.search(index, q, 4, sp, algo="pallas"))

        search()                      # compile outside the profile
        (line,) = _profile_spans(tmp_path, search)
        ev = {n: (n, s, e) for n, s, e in line}
        assert len(ev) == len(line)           # one span of each name
        outer = ev.pop("raft_tpu::ivf_flat::search")
        coarse = ev["raft_tpu::ivf_flat::coarse"]
        scan = ev["raft_tpu::ivf_flat::scan"]
        assert coarse[2] <= scan[1]
        # every step, the eager select_k of the probe among them, lies
        # inside the entry point's span
        for inner in ev.values():
            assert _inside(inner, outer), inner


class TestZeroOverheadWhenOff:
    def test_disabled_path_runs_no_device_probe(self, reg, monkeypatch):
        """With sampling off, the serving hot path must never sync the
        device (the accidental-always-on-probe regression guard)."""
        from raft_tpu.serve import batcher as batcher_mod

        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(batcher_mod.jax, "block_until_ready",
                            lambda x: (calls.append(1), real(x))[1])
        monkeypatch.delenv("RAFT_TPU_TRACE_SAMPLE", raising=False)
        spans_before = len(tracing.recent_spans())
        with MicroBatcher(stub_search, DIM, ladder=BucketLadder((8,), (8,)),
                          registry=reg, max_wait_s=0.001) as b:
            for _ in range(4):
                b.search(np.zeros((2, DIM), np.float32), 5, timeout=60)
        assert calls == [], "sampling disabled but the batcher synced " \
                            "the device (always-on probe regression)"
        assert len(tracing.recent_spans()) == spans_before
        assert not any(name.startswith("serve.stage.")
                       for name in reg.snapshot()["histograms"])

    def test_disabled_annotate_overhead_within_noise(self):
        """Disabled tracing probes must stay branch-cheap: the annotate
        wrapper with timer off + tracing off is bounded by an absolute
        per-call overhead far below any real probe (a stray histogram
        observe or block_until_ready per call would blow it by orders
        of magnitude). Generous bound: timing on the 1-core CI box is
        noisy."""
        tracing.set_timer(None)

        def raw(x):
            return x + 1

        wrapped = tracing.annotate("unit::overhead")(raw)

        def bench(fn, n=20000):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for i in range(n):
                    fn(i)
                best = min(best, (time.perf_counter() - t0) / n)
            return best

        base = bench(raw)
        cost = bench(wrapped)
        assert cost - base < 20e-6, (
            f"annotate overhead {cost - base:.2e}s/call with no profiler "
            "collecting — a probe is running on the idle path")
