"""The trace reduction on a small synthetic ``.xplane.pb`` text proto."""

import pytest
from jax.profiler import ProfileData

from bench.harness import trace

# one chip: ops at [0,2) conv, [3,4) fusion, [8,12) conv (cut at the
# window's end, 10), a while loop around [0,4); host: window [0,10), block [1,4), poll [4,9) with a
# nested dispatch [5,7).  Times in microseconds from the line origin.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 4000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "conv_mvu_xnor.3" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.12" } }
  event_metadata { key: 3 value { id: 3 name: "conv_mvu_xnor" } }
  event_metadata { key: 4 value { id: 4 name: "jit__stream" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "block" } }
  event_metadata { key: 3 value { id: 3 name: "poll" } }
  event_metadata { key: 4 value { id: 4 name: "dispatch" } }
}
"""
SPANS = frozenset({"block", "poll", "dispatch"})


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(ProfileData.from_text_proto(XSPACE), host_spans=SPANS)


def test_window_and_busy_time(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(10e-6)
    # ops inside [0, 10): [0, 4) under the loop, then [8, 10) clipped
    assert summary.busy_s == pytest.approx(6e-6)


def test_kernel_time_by_name(summary):
    seconds, calls = summary.kernel("conv_mvu_xnor")
    assert calls == 2
    assert seconds == pytest.approx(4e-6)
    assert summary.kernel("fusion") == (pytest.approx(1e-6), 1)
    assert summary.kernel("mvu_xnor") == (0.0, 0)
    # only the "XLA Ops" line counts: the module span is not an op; the
    # loop is busy time but not an op of its own
    assert "jit__stream" not in summary.op_s
    assert "while" not in summary.op_s


def test_idle_gaps_by_innermost_host_span(summary):
    # the one gap, [4,8), has its midpoint 6 under dispatch (inside poll)
    assert summary.idle_by_host == {"dispatch": pytest.approx(4e-6)}
    b = summary.breakdown()
    assert b["idle_gaps"] == [["dispatch", pytest.approx(4e-6)]]
    assert b["device_ops"][0] == ["conv_mvu_xnor", pytest.approx(4e-6)]


def test_a_trace_without_the_window_span_is_refused():
    bad = XSPACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(ProfileData.from_text_proto(bad), host_spans=SPANS)


def test_base_name_strips_the_instance_suffix_and_the_hlo_text():
    assert trace.base_name("conv_mvu_xnor.17") == "conv_mvu_xnor"
    assert trace.base_name("mvu_int") == "mvu_int"
    assert trace.base_name("%mvu_xnor.18 = s32[8,512]{1,0} custom-call("
                           "u32[8,8]{1,0} %pad.57)") == "mvu_xnor"
    assert trace.base_name("%copy-start.7 = (u32[512,8]) copy-start(x)") == \
        "copy-start"
