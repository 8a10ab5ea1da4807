"""The trace reduction on a small recorded trace with known answers."""
import pytest

import traces

# one device plane; host spans on two threads. Times in ns: line offsets
# are in ps. The window is [1000, 21000].
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 17000000 duration_ps: 6000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 16000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%_scan_groups.1 = (f32[4]{0}) custom-call()" } }
  event_metadata { key: 3 value { id: 3 name: "select_k" } }
  event_metadata { key: 4 value { id: 4 name: "jit_search" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 5 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5500000 }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 2500000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 3000000 }
  }
  lines { id: 6 name: "gen" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 12000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 13000000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
  event_metadata { key: 3 value { id: 3 name: "bench.fetch" } }
  event_metadata { key: 4 value { id: 4 name: "bench.gen_sleep" } }
  event_metadata { key: 5 value { id: 5 name: "not_ours" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    return traces.reduce(traces.load_text(XSPACE))


def test_busy_and_idle(reduced):
    # ops clipped to [1000, 21000] ns: [1000,2000] [2000,6000]
    # [9000,11000] [17000,21000] -> 11000 ns busy of 20000
    assert reduced["window_s"] == pytest.approx(20e-6)
    assert reduced["busy_s"] == pytest.approx(11e-6)
    assert reduced["n_devices"] == 1


def test_time_per_named_op(reduced):
    # HLO names under the program that ran them; select_k ran outside any
    # program's span and keeps its bare name
    assert reduced["ops_s"] == pytest.approx(
        {"jit_search/fusion.1": 3e-6, "jit_search/_scan_groups.1": 4e-6,
         "select_k": 4e-6})


def test_device_time_inside_host_spans(reduced):
    # bench.call covers [1000,6500] and [9000,12000]: 5000 + 2000 busy
    assert reduced["device_in_span_s"]["bench.call"] == pytest.approx(7e-6)
    assert reduced["device_in_span_s"]["bench.fetch"] == pytest.approx(0.0)
    assert "not_ours" not in reduced["span_s"]


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert [n for n, _ in ops][:1] in (["jit_search/_scan_groups.1"],
                                       ["select_k"])
    assert sorted(v for _, v in ops) == pytest.approx([3e-6, 4e-6, 4e-6])
    # idle gaps: [6000,9000] (fetch covers 6500-9000), [11000,17000]
    # (call to 12000, gen_sleep 12000-17000)
    assert reduced["breakdown"]["idle_gaps"] == [
        ["bench.gen_sleep", pytest.approx(6e-6)],
        ["bench.fetch", pytest.approx(3e-6)]]


def test_no_device_ops_reads_nothing():
    host_only = XSPACE.split("planes {\n  id: 2")[0].replace(
        '"/device:TPU:0"', '"/host:CPU"')
    assert traces.reduce(traces.load_text(host_only)) == {}
