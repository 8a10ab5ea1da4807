"""The program-span reduction on the recorded trace of ``test_traces``,
with the program's own spans added on two more host thread lines."""
import pytest

import spans
import traces
from test_traces import XSPACE

# A batcher worker's nested steps and a batch caller's search and
# re-rank, on the window [1000, 21000] ns, whose device idles in
# [6000, 9000] and [11000, 17000]. One pop straddles the window's start,
# one lies past its end.
WORKER = """
  lines { id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 11 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 14 offset_ps: 5000000 duration_ps: 4500000 }
    events { metadata_id: 15 offset_ps: 5500000 duration_ps: 2500000 }
    events { metadata_id: 16 offset_ps: 8000000 duration_ps: 1500000 }
    events { metadata_id: 12 offset_ps: 10500000 duration_ps: 7000000 }
    events { metadata_id: 13 offset_ps: 10800000 duration_ps: 6400000 }
    events { metadata_id: 11 offset_ps: 21500000 duration_ps: 500000 }
  }
  lines { id: 8 name: "python" timestamp_ns: 0
    events { metadata_id: 17 offset_ps: 1000000 duration_ps: 4800000 }
    events { metadata_id: 18 offset_ps: 5800000 duration_ps: 400000 }
  }
  event_metadata { key: 11 value { id: 11 name: "raft_tpu::serve::pop" } }
  event_metadata { key: 12 value { id: 12 name: "raft_tpu::serve::dispatch" } }
  event_metadata { key: 13 value { id: 13 name: "raft_tpu::serve::pad" } }
  event_metadata { key: 14 value { id: 14 name: "raft_tpu::serve::demux" } }
  event_metadata { key: 15 value { id: 15 name: "raft_tpu::serve::fetch" } }
  event_metadata { key: 16 value { id: 16 name: "raft_tpu::serve::deliver" } }
  event_metadata { key: 17 value { id: 17 name: "raft_tpu::ivf_pq::search" } }
  event_metadata { key: 18 value { id: 18 name: "raft_tpu::refine" } }
"""
_AT = '  event_metadata { key: 1 value { id: 1 name: "bench.window" } }'
assert XSPACE.count(_AT) == 1
WITH_PROGRAM = XSPACE.replace(_AT, WORKER.strip("\n") + "\n" + _AT)


@pytest.fixture(scope="module")
def events():
    return traces.load_text(WITH_PROGRAM)


@pytest.fixture(scope="module")
def reduced(events):
    return spans.reduce(events)


def test_program_spans_leave_the_trace_reduction_as_it_was(events):
    # every value of traces.reduce, and so every per-layer metric read
    # from it, is the same with the program's spans in the trace
    assert traces.reduce(events) == traces.reduce(traces.load_text(XSPACE))


def test_counts_and_seconds_inside_the_window(reduced):
    n, tot = reduced["span_n"], reduced["span_sum_s"]
    # the pop past the window's end is not counted; the one across its
    # start counts its 500 ns inside
    assert n["raft_tpu::serve::pop"] == 1
    assert tot["raft_tpu::serve::pop"] == pytest.approx(0.5e-6)
    assert n["raft_tpu::serve::dispatch"] == 1
    assert tot["raft_tpu::serve::dispatch"] == pytest.approx(7e-6)
    assert tot["raft_tpu::serve::demux"] == pytest.approx(4.5e-6)
    assert "bench.call" not in n


def test_idle_inside_each_span(reduced):
    idle, dev = reduced["idle_in_span_s"], reduced["device_in_span_s"]
    # busy [1000,6000] [9000,11000] [17000,21000]
    assert idle == pytest.approx({
        "raft_tpu::serve::pop": 0.0,
        "raft_tpu::serve::dispatch": 6e-6, "raft_tpu::serve::pad": 6e-6,
        "raft_tpu::serve::demux": 3e-6, "raft_tpu::serve::fetch": 2e-6,
        "raft_tpu::serve::deliver": 1e-6,
        "raft_tpu::ivf_pq::search": 0.0, "raft_tpu::refine": 0.2e-6})
    assert dev["raft_tpu::serve::dispatch"] == pytest.approx(1e-6)
    assert reduced["span_s"]["raft_tpu::serve::pad"] == pytest.approx(6.4e-6)


def test_gap_takes_the_innermost_program_span(reduced):
    # [11000,17000]: dispatch and the pad inside it both cover all of
    # it, so the shorter pad names it; [6000,9000]: demux covers all,
    # its fetch 2000 ns, its deliver 1000, the re-rank 200
    assert reduced["idle_gaps"] == [
        ["raft_tpu::serve::pad", pytest.approx(6e-6), pytest.approx(10e-6)],
        ["raft_tpu::serve::demux", pytest.approx(3e-6), pytest.approx(5e-6)]]


def test_without_program_spans_the_benchmark_span_names_a_gap():
    red = spans.reduce(traces.load_text(XSPACE))
    assert red["span_n"] == {}
    assert [g[0] for g in red["idle_gaps"]] == [
        n for n, _ in traces.reduce(traces.load_text(XSPACE))
        ["breakdown"]["idle_gaps"]]


def test_long_gaps_are_kept_beyond_the_top():
    red = spans.reduce(traces.load_text(WITH_PROGRAM), top=1, long_s=2e-6)
    assert [g[0] for g in red["idle_gaps"]] == ["raft_tpu::serve::pad",
                                                "raft_tpu::serve::demux"]
    red = spans.reduce(traces.load_text(WITH_PROGRAM), top=1, long_s=1.0)
    assert len(red["idle_gaps"]) == 1


@pytest.mark.parametrize("metric,value", [
    ("dispatch_ms.served", 7e-3),
    ("demux_ms.served", 4.5e-3),
    ("idle_host_share.served", 45.0),
    ("idle_host_share.batch", 1.0),
    ("idle_share", 45.0),
])
def test_readings(reduced, metric, value):
    got = spans.readings(reduced)
    assert got[metric] == pytest.approx(value)
    # the host path's idle is a part of the whole
    assert got["idle_host_share.served"] <= got["idle_share"] + 1e-9
    assert got["idle_host_share.batch"] <= got["idle_share"]


def test_readings_leave_out_what_has_no_spans():
    red = spans.reduce(traces.load_text(XSPACE))
    assert set(spans.readings(red)) == {"idle_share"}
    assert spans.readings({}) == {}
