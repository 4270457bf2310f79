"""Operation and byte counts against hand counts, and the peaks table."""

import json
import os

import pytest

from bench.models import qnn
from bench.roofline import work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_nid_fc0_by_hand():
    # 600 -> 64 at 2-bit weights and inputs, 2-bit outputs, 128 samples:
    # 2*128*64*600 ops; 64*600*2/8 weight bytes, 128*600*2/8 in, 128*64*2/8 out
    ops, nbytes = work.dense(128, 64, 600, w_bits=2, a_bits=2, o_bits=2)
    assert ops == 9_830_400
    assert nbytes == 9_600 + 19_200 + 2_048


def test_cnv_conv2_by_hand():
    # the second conv: 30x30x64 in, 28x28x64 out, K = 3*3*64 = 576, 1-bit
    ops, nbytes = work.conv(1, 30, 30, 64, 64, 3, w_bits=1, a_bits=1,
                            o_bits=1)
    assert ops == 2 * 28 * 28 * 64 * 576 == 57_802_752
    assert nbytes == 64 * 576 // 8 + 30 * 30 * 64 // 8 + 28 * 28 * 64 // 8


def test_config_kernel_calls_match_the_hand_counts():
    cnv = qnn.kernel_calls(_config("cnv_full"), 1)
    assert len(cnv["conv_mvu_xnor"]) == 6 and len(cnv["mvu_xnor"]) == 3
    assert cnv["conv_mvu_xnor"][1][0] == 57_802_752
    # about 119 MOP per image over the nine layers
    assert qnn.ops_per_sample(_config("cnv_full")) == pytest.approx(119e6, rel=0.01)
    nid = qnn.kernel_calls(_config("nid_mlp"), 128)
    assert list(nid) == ["mvu_int"] and len(nid["mvu_int"]) == 4
    assert nid["mvu_int"][0] == work.dense(128, 64, 600, w_bits=2, a_bits=2,
                                           o_bits=2)
    # the head writes float32 logits
    assert nid["mvu_int"][3] == work.dense(128, 1, 64, w_bits=2, a_bits=2,
                                           o_bits=32)


def test_work_does_not_depend_on_the_datapath():
    # the same layer counted for an xnor and an int8 kernel: 2 ops per MAC
    assert work.dense(8, 512, 256, w_bits=1, a_bits=1, o_bits=1)[0] == \
        work.dense(8, 512, 256, w_bits=8, a_bits=8, o_bits=8)[0]


def test_peaks_and_bounds():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    # CNV's second conv is compute-bound, a 1-image 256->512 xnor dense
    # layer is bandwidth-bound
    assert work.least_time(*work.conv(1, 30, 30, 64, 64, 3, w_bits=1,
                                      a_bits=1, o_bits=1), v5e)[1] == "compute"
    assert work.least_time(*work.dense(1, 512, 256, w_bits=1, a_bits=1,
                                       o_bits=1), v5e)[1] == "bandwidth"


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_every_declared_metric_reads_a_number():
    from bench.harness import cell, spec, trace

    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for name in cells:
        c = spec.load(name)
        calls = c.family.kernel_calls(c.config, 1)
        t = trace.TraceSummary(
            chips=1, window_s=2.0, busy_s=1.5,
            op_s={k: 1.0 for k in calls},
            op_count={k: 10 * len(v) for k, v in calls.items()},
            idle_by_host={}, longest_gaps=[])
        ctx = cell.Context(c.config, c.family, "TPU v5 lite", rate=1000.0,
                           plan={"batch": 8, "n_micro": 8, "microbatch": 1},
                           trace=t)
        for m in c.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            assert value is not None and 0 < value, (name, m["name"])
            if m["unit"] == "%":
                assert value <= 100, (name, m["name"], value)
