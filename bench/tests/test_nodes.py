"""Per-node attribution and the idle gap partition on a synthetic trace."""

import pytest
from jax.profiler import ProfileData

from bench.harness import nodes, trace
from bench.tests.test_trace import SPANS, XSPACE

FC0 = "jit(_stream)/while/body/closed_call/fc0.mvu/jit(mvu_int_pallas)/mvu_int"
FC1 = "jit(_stream)/while/body/closed_call/fc1.mvu/jit(mvu_int_pallas)/mvu_int"
NODES = ("in", "fc0.mvu", "fc1.mvu")
RUNS_US = (10, 30, 50)  # each run of the program starts here and lasts 10
# within a run, microseconds from its start: the pad and the output stack
# are in no node; a loop holds fc0's kernel and fusion, a gap, fc1's kernel
# and a gap.  The events name HLO instructions; the compiled program's text
# gives their op names.
RUN_OPS = ((0, 1, 1), (1, 9, 2), (1, 3, 3), (3, 4.5, 4), (5, 8, 5),
           (9, 10, 6))
HLO = f"""
  %pad.1 = s8[128,640]{{1,0}} pad(%p, %c), padding=0_0x0_40, metadata={{op_name="jit(_stream)/while/body/pad"}}
  %mvu_int.3 = s32[128,64]{{1,0}} custom-call(%pad.1), custom_call_target="tpu_custom_call", metadata={{op_name="{FC0}/pallas_call"}}
  %fusion.4 = s8[128,64]{{1,0}} fusion(%mvu_int.3), kind=kLoop, calls=%fused_computation, metadata={{op_name="{FC0}/convert_element_type"}}
  ROOT %mvu_int.5 = f32[128,8]{{1,0}} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={{op_name="{FC1}/pallas_call" stack_frame_id=3}}
"""
FUSION_ONLY = HLO.splitlines()[3]


def _ps(us: float) -> int:
    return int(round(us * 1e6))


def _xspace(window_us=(0, 70), device_shift_ns=0) -> str:
    """Three calls of a closed loop.  ``device_shift_ns`` moves the device's
    clock against the host's, as the profiler's alignment may."""
    ops, modules, host = [], [], []
    for k, r in enumerate(RUNS_US):
        run_id = f"stats {{ metadata_id: 1 int64_value: {101 + k} }}"
        modules.append(f"events {{ metadata_id: 7 offset_ps: {_ps(r)} "
                       f"duration_ps: {_ps(10)} {run_id} }}")
        # the runtime reports the run done 1 us after its last op
        host.append(f"events {{ metadata_id: 5 offset_ps: {_ps(r + 11)} "
                    f"duration_ps: {_ps(0.5)} {run_id} }}")
        for a, b, meta in RUN_OPS:
            ops.append(f"events {{ metadata_id: {meta} offset_ps: "
                       f"{_ps(r + a)} duration_ps: {_ps(b - a)} }}")
        # the client: enqueue holds engine.dispatch; block waits for the
        # result, 3 us past the run's last op
        for meta, a, b in ((2, r - 4, r - 1), (3, r - 3.5, r - 2),
                           (4, r - 1, r + 13)):
            host.append(f"events {{ metadata_id: {meta} offset_ps: {_ps(a)} "
                        f"duration_ps: {_ps(b - a)} }}")
    w0, w1 = window_us
    host.append(f"events {{ metadata_id: 1 offset_ps: {_ps(w0)} "
                f"duration_ps: {_ps(w1 - w0)} }}")
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: {device_shift_ns} {" ".join(ops)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: {device_shift_ns} {" ".join(modules)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "pad.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "while.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%mvu_int.3 = s32[128,64]{{1,0}} custom-call(%pad.1)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "fusion.4" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "mvu_int.5" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "copy.6" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "jit__stream" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "run_id" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 0 {" ".join(host)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "enqueue" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "engine.dispatch" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "block" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "CompleteCallbacks" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "run_id" }} }}
}}
"""


def _reduce(text, hlo=HLO):
    return nodes.reduce(ProfileData.from_text_proto(text), nodes=NODES,
                        hlo_ops=nodes.hlo_op_names(hlo))


@pytest.fixture(scope="module")
def summary():
    return _reduce(_xspace())


def test_hlo_text_maps_instruction_names_to_op_names():
    ops = nodes.hlo_op_names(HLO)
    assert ops == {"pad.1": "jit(_stream)/while/body/pad",
                   "mvu_int.3": FC0 + "/pallas_call",
                   "fusion.4": FC0 + "/convert_element_type",
                   "mvu_int.5": FC1 + "/pallas_call"}
    assert nodes.node_of(ops["fusion.4"], NODES) == "fc0.mvu"
    assert nodes.node_of(ops["pad.1"], NODES) is None
    assert nodes.instruction("%mvu_int.3 = s32[128,64]{1,0} custom-call()") \
        == "mvu_int.3"
    assert nodes.instruction("fusion.4") == "fusion.4"


def test_device_time_by_node_plumbing_and_loop_gaps_apart(summary):
    assert summary.window_s == pytest.approx(70e-6)
    assert summary.busy_s == pytest.approx(30e-6)  # every run is busy end to end
    assert summary.node_s == {"fc0.mvu": pytest.approx(3 * 3.5e-6),
                              "fc1.mvu": pytest.approx(3 * 3e-6)}
    # per run: the pad 1, the stack 1, the loop's gaps 0.5 and 1
    assert summary.plumbing_s == pytest.approx(3 * 3.5e-6)
    assert summary.plumbing_share() == pytest.approx(35.0)
    assert sum(summary.node_s.values()) + summary.plumbing_s == \
        pytest.approx(summary.busy_s)
    assert summary.unscoped == {"pad": 3, "copy": 3}
    assert summary.runs == 3
    node, us = summary.bottleneck(batch=4)
    assert node == "fc0.mvu" and us == pytest.approx(3.5 / 4)
    assert summary.us_per_sample(batch=4) == {"fc0.mvu": pytest.approx(3.5 / 4),
                                              "fc1.mvu": pytest.approx(3 / 4)}
    assert summary.device_nodes()[0] == ["fc0.mvu", pytest.approx(10.5e-6)]


def test_a_kernel_without_an_op_name_is_plumbing():
    s = _reduce(_xspace(), hlo=FUSION_ONLY)
    assert "fc1.mvu" not in s.node_s
    assert s.unscoped["mvu_int"] == 6
    assert s.plumbing_s == pytest.approx(3 * 10e-6 - 3 * 1.5e-6)


def test_three_calls_partition_every_gap_exactly(summary):
    # two gaps of 10 us between three runs: from the previous block's end
    # to this dispatch's end the host has 5; the previous result comes
    # back from its completion callback to its block's end, 2; the rest,
    # 3, is the launch with the callback's own 1 us
    assert [(g.launch_s, g.fetch_s, g.host_s) for g in summary.gaps] == [
        (pytest.approx(3e-6), pytest.approx(2e-6), pytest.approx(5e-6))] * 2
    assert summary.dispatch_s == [pytest.approx(1.5e-6)] * 3
    assert summary.dispatch_ms() == pytest.approx(1.5e-3)
    assert summary.gap_ms("launch") == pytest.approx(3e-3)
    assert summary.gap_ms("fetch") == pytest.approx(2e-3)
    assert summary.gap_ms("host") == pytest.approx(5e-3)
    # idle between runs is the window's idle less its two edges of 10 us
    gaps = sum(g.launch_s + g.fetch_s + g.host_s for g in summary.gaps)
    assert gaps == pytest.approx(summary.idle_s - 20e-6)


@pytest.mark.parametrize("shift_ns", [-6000, 6000])
def test_the_partition_ignores_where_the_device_clock_sits(summary, shift_ns):
    moved = _reduce(_xspace(window_us=(-10, 80), device_shift_ns=shift_ns))
    assert [(g.launch_s, g.fetch_s, g.host_s) for g in moved.gaps] == [
        (pytest.approx(g.launch_s), pytest.approx(g.fetch_s),
         pytest.approx(g.host_s)) for g in summary.gaps]


def test_a_run_the_runtime_never_reported_is_not_matched():
    s = _reduce(_xspace().replace("int64_value: 102", "int64_value: 999", 1))
    # the middle run carries a run_id that no callback reports: no gap
    # is measured across it
    assert s.gaps == []


def test_runs_cut_by_the_window_count_for_time_not_for_runs():
    s = _reduce(_xspace(window_us=(15, 45)))
    assert s.runs == 1  # only the run at 30 lies wholly inside
    assert s.busy_s == pytest.approx(5e-6 + 10e-6 + 0)
    node, us = s.bottleneck(batch=1)
    assert node == "fc0.mvu" and us == pytest.approx(3.5)
    assert len(s.dispatch_s) == 1  # at 26.5; the one at 46.5 is outside


def test_on_the_harness_fixture_busy_agrees_and_nothing_is_a_node():
    profile = ProfileData.from_text_proto(XSPACE)
    s = nodes.reduce(profile, nodes=NODES, hlo_ops={})
    t = trace.reduce(profile, host_spans=SPANS)
    assert s.busy_s == pytest.approx(t.busy_s)
    assert s.window_s == pytest.approx(t.window_s)
    assert s.node_s == {} and s.plumbing_s == pytest.approx(t.busy_s)
    assert s.gaps == [] and s.bottleneck(batch=1) is None
