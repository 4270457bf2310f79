"""The ResNet family's comparison and work counts at test size.

The comparison that decides ``correct`` has to pass the program, fail a run
whose answers are altered, and fail its control (the reference's float
path in bfloat16).  The work counts of a strided conv have to equal a hand
count.  Runs off the chip go through the whole harness on a copy of the
benchmark with the ResNet cut to 32x32 images, widths divided by 16 and
one block per stage.
"""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench.harness import cell
from bench.models import resnet
from bench.roofline import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "resnet50_w1a2.batch1024"


def _config():
    with open(os.path.join(BENCH, "configs", "resnet50_w1a2.json")) as f:
        return json.load(f)


def small(spec: dict) -> dict:
    """The configuration at test size: 32x32 images, widths / 16, one
    block per stage, 10 classes."""
    out = json.loads(json.dumps(spec))
    out["input"]["shape"] = [32, 32, 3]
    out["stem"]["out"] //= 16
    out["stages"] = [dict(s, blocks=1, mid=s["mid"] // 16, out=s["out"] // 16)
                     for s in spec["stages"]]
    out["head"]["out"] = 10
    out["init"]["calibration_images"] = 8
    return out


@pytest.fixture(scope="module")
def tiny():
    spec = small(_config())
    return spec, jax.device_get(resnet.init_params(spec, 2**33 + 7))


def test_the_control_fails_the_comparison(tiny):
    spec, params = tiny
    ref = resnet.Reference(spec, params)
    x = np.asarray(resnet.make_inputs(spec, 16, 5))
    assert ref.judge(x, ref.forward(x))[0].sum() == 0
    assert ref.judge(x, ref.control(x))[0].sum() > cell.LIMITS["wrong_rows"]


def test_folded_thresholds_lie_between_integers(tiny):
    """Calibration centres every threshold on a half step, so a float32
    fold decides each integer accumulator as the float64 one does."""
    spec, params = tiny
    for st in resnet.walk(spec):
        if st.op != "conv":
            continue
        p = {k: np.asarray(params[st.name][k], np.float64)
             for k in ("gamma", "beta", "mean", "var")}
        scale = resnet.Reference(spec, params).weights[st.name][1]
        t = (((np.arange(1, 4) - 0.5)[None] - p["beta"][:, None])
             * np.sqrt(p["var"] + resnet.EPS)[:, None] / p["gamma"][:, None]
             + p["mean"][:, None]) / scale[:, None]
        assert np.all(np.abs(t - np.floor(t) - 0.5) < 0.01), st.name


def test_a_strided_conv_counts_by_hand():
    steps = {st.name: st for st in resnet.walk(_config())}
    # stage 2's first 3x3: 56x56x128 -> 28x28x128 at stride 2, pad 1, K=1152
    c2 = steps["s2b0.c2"]
    assert (c2.in_shape, c2.out_shape, c2.k) == ((56, 56, 128),
                                                 (28, 28, 128), 1152)
    ops, nbytes = resnet.conv_work(1, c2, a_bits=2, o_bits=2)
    assert ops == 2 * 28 * 28 * 128 * 3 * 3 * 128 == 231_211_008
    assert nbytes == 128 * 1152 // 8 + 56 * 56 * 128 * 2 // 8 + \
        28 * 28 * 128 * 2 // 8
    # not the valid stride-1 work that work.conv counts for the same map
    assert work.conv(1, 56, 56, 128, 128, 3, w_bits=1, a_bits=2,
                     o_bits=2)[0] > 3 * ops
    # the 7x7/2 stem: 224 -> 112, 8-bit pixels in, two digit calls a pass
    stem = steps["stem"]
    ops, nbytes = resnet.conv_work(1, stem, a_bits=8, o_bits=2)
    assert ops == 2 * 112 * 112 * 64 * 7 * 7 * 3
    assert nbytes == 64 * 147 + 224 * 224 * 3 + 112 * 112 * 64 * 2 // 8
    calls = resnet.kernel_calls(_config(), 1)
    assert calls["conv_mvu_standard"] == [(ops / 2, nbytes / 2)] * 2
    assert len(calls["conv_mvu_binary"]) == 52
    assert len(calls["add_threshold"]) == 16 and len(calls["mvu_int"]) == 2
    # about 4.1 GMAC, 8.2 GOP an image
    assert resnet.ops_per_sample(_config()) == pytest.approx(8.2e9, rel=0.01)


def _small_root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    (root / "bench" / "configs" / "resnet50_w1a2.json").write_text(
        json.dumps(small(_config())))
    p = root / "bench" / "traffic" / "batch1024.json"
    p.write_text(json.dumps(dict(json.loads(p.read_text()), batch=4,
                                 distinct_batches=2)))
    return str(root)


def _alter_first_answer(monkeypatch):
    from repro.core.engine import FusedEngine

    dispatch = FusedEngine.dispatch

    def faulty(self, x, **kw):
        out, plan = dispatch(self, x, **kw)
        return out.at[0].add(1), plan

    monkeypatch.setattr(FusedEngine, "dispatch", faulty)


@pytest.mark.parametrize("fault", [None, "answer_altered"])
def test_a_run_off_the_chip(tmp_path, monkeypatch, fault):
    root = _small_root(tmp_path)
    if fault:
        _alter_first_answer(monkeypatch)
    result, checks = cell.run(CELL, 2**32 + 11, 0.5, False, root=root,
                              require_chip=False)
    assert result["correct"] is (fault is None), checks
    assert set(result["metrics"]) == {"setup_s", "samples_per_s"}
    if fault:
        assert checks["wrong_rows"]["value"] > 0
    else:
        assert result["failed"] == 0 and result["window_programs"] == 0
