"""The comparison that decides ``correct``: its control has to fail it,
sound runs pass it, and a run with the timed path broken fails it.

The control is the reference one precision down (its float path in
bfloat16); it is run here at the configurations' own widths on a few
inputs.  The harness tests drive a whole run off the chip (no look for a
TPU, Pallas in interpret mode) on a shrunken copy of the benchmark.
"""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench.harness import cell
from bench.models import qnn

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,n", [("nid_mlp", 512), ("cnv_full", 4)])
@pytest.mark.parametrize("seed", [1, 2, 2**33 + 7])
def test_the_control_fails_the_comparison(name, n, seed):
    spec = _config(name)
    ref = qnn.Reference(spec, jax.device_get(qnn.init_params(spec, seed)))
    x = np.asarray(qnn.make_inputs(spec, n, seed))
    bad, _ = ref.judge(x, ref.control(x))
    assert bad.sum() > cell.LIMITS["wrong_rows"]
    assert ref.judge(x, ref.forward(x))[0].sum() == 0


def test_a_choice_at_a_tie_is_accepted_either_way():
    spec = _config("nid_mlp")
    ref = qnn.Reference(spec, jax.device_get(qnn.init_params(spec, 3)))
    x = np.asarray(qnn.make_inputs(spec, 256, 3))
    # plant an ambiguous threshold exactly where some accumulator lands
    lay = ref.layers[0]
    q, _ = ref.weights[lay.name]
    acc = np.asarray(x, np.float32) @ q
    ref.choices = [("t", 0, 0, 1, int(np.median(acc[:, 0])), 0.0)]
    flipped = ref.forward(x, flip=frozenset({0}))
    assert not np.array_equal(flipped, ref.forward(x))
    bad, note = ref.judge(x, flipped)
    assert bad.sum() == 0 and note["flipped"]
    # a row that no assignment explains still counts
    wrong = flipped.copy()
    wrong[5] += 1.0
    assert ref.judge(x, wrong)[0].sum() == 1


# open-loop cells the benchmark does not declare yet (PERF.md, Open
# questions); their generator and readers are run here all the same
STREAM_CELLS = [
    {"name": "nid_mlp.stream", "config": "nid_mlp", "traffic": "stream",
     "chips": 1, "why": "open loop"},
    {"name": "nid_mlp.stream_x4", "config": "nid_mlp", "traffic": "stream_x4",
     "chips": 4, "why": "open loop over four replicas"}]
STREAM_METRICS = [
    {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock", "workloads": [c["name"] for c in STREAM_CELLS]}
    for n in ("latency_p99_ms", "latency_p50_ms")]


def _small_root(tmp_path):
    """A copy of the benchmark at CPU size: CNV cut to 16x16 images and
    four convs, calls of 8 images, the streams at 100 requests/s (the
    four-replica stream, whose rate no sweep has set yet, takes the
    one-replica mix)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] += STREAM_CELLS
    bench["end_to_end"] += STREAM_METRICS
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    p = root / "bench" / "configs" / "cnv_full.json"
    cfg = json.loads(p.read_text())
    cfg["input"]["shape"] = [16, 16, 3]
    cfg["layers"] = [{"op": "conv", "out": 8}, {"op": "conv", "out": 8},
                     {"op": "maxpool", "size": 2}, {"op": "conv", "out": 16},
                     {"op": "conv", "out": 16}, {"op": "flatten"},
                     {"op": "dense", "out": 16},
                     {"op": "dense", "out": 10, "head": True}]
    p.write_text(json.dumps(cfg))
    for mix in ("batch", "batch4096"):
        p = root / "bench" / "traffic" / f"{mix}.json"
        p.write_text(json.dumps(dict(json.loads(p.read_text()), batch=8,
                                     distinct_batches=2)))
    stream = root / "bench" / "traffic" / "stream.json"
    slow = json.dumps(dict(json.loads(stream.read_text()), rate_hz=100))
    for mix in ("stream", "stream_x4"):
        (root / "bench" / "traffic" / f"{mix}.json").write_text(slow)
    return str(root)


def _alter_first_answer(monkeypatch):
    """The fault: every engine call returns its first answer altered."""
    from repro.core.engine import FusedEngine

    dispatch = FusedEngine.dispatch

    def faulty(self, x, **kw):
        out, plan = dispatch(self, x, **kw)
        return out.at[0].add(1), plan

    monkeypatch.setattr(FusedEngine, "dispatch", faulty)


@pytest.mark.parametrize("workload", ["cnv_full.batch", "nid_mlp.batch",
                                      "nid_mlp.stream", "nid_mlp.stream_x4"])
@pytest.mark.parametrize("fault", [None, "answer_altered"])
def test_a_run_off_the_chip(tmp_path, monkeypatch, workload, fault):
    root = _small_root(tmp_path)
    if fault:
        _alter_first_answer(monkeypatch)
    result, checks = cell.run(workload, 2**32 + 11, 0.5, False, root=root,
                              require_chip=False)
    assert result["correct"] is (fault is None), checks
    assert list(result)[-1] == "checks"
    rate = {"cnv_full.batch": "samples_per_s", "nid_mlp.batch": "records_per_s"}
    assert set(result["metrics"]) >= {"setup_s", rate.get(workload, "setup_s")}
    if fault:
        assert checks["wrong_rows"]["value"] > 0
    else:
        assert result["failed"] == 0 and result["window_programs"] == 0


def test_the_command_needs_a_chip(tmp_path):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "nid_mlp.batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr
