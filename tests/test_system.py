"""End-to-end behaviour tests: train + crash/restart equivalence, the NID
use case, the data pipeline, and the fault-tolerance manager."""

import itertools
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp


def _tiny_model():
    from repro.configs import get_reduced
    from repro.models.model import build

    cfg = get_reduced("yi-9b").replace(dtype="float32", remat=False)
    return build(cfg), cfg


def _batches(cfg, n, b=4, s=32, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s + 1)), jnp.int32)}
        for _ in range(n)
    ]


def test_train_crash_resume_equivalence():
    """Training interrupted at step 4 and resumed from checkpoint reaches the
    exact same loss trajectory as the uninterrupted run."""
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train_loop
    from repro.optim import adamw

    model, cfg = _tiny_model()
    mesh = make_mesh((1, 1))
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=8)
    data = _batches(cfg, 20)

    with tempfile.TemporaryDirectory() as d1:
        _, _, full_hist = train_loop(
            model, mesh, steps=8, batch_iter=itertools.cycle(data),
            opt_cfg=opt, ckpt_dir=d1, ckpt_every=100, log_every=100)

    with tempfile.TemporaryDirectory() as d2:
        # run to step 4 with checkpoint cadence 2, then "crash"
        train_loop(model, mesh, steps=4, batch_iter=itertools.cycle(data),
                   opt_cfg=opt, ckpt_dir=d2, ckpt_every=2, log_every=100)
        # restart: resumes from step 4, continues with the same data order
        resumed_iter = itertools.cycle(data)
        for _ in range(4):  # advance the stream to where the crash happened
            next(resumed_iter)
        _, _, resumed_hist = train_loop(
            model, mesh, steps=8, batch_iter=resumed_iter,
            opt_cfg=opt, ckpt_dir=d2, ckpt_every=100, log_every=100)

    np.testing.assert_allclose(resumed_hist, full_hist[4:], rtol=1e-4, atol=1e-5)


def test_nid_end_to_end():
    from benchmarks.nid_mlp import accuracy_check

    out = accuracy_check(steps=200)
    assert out["float_acc"] > 0.95
    assert out["mvu_int_acc"] > 0.95
    # Table 7: bottleneck stage interval 12 cycles (layer 0: NF1 x SF12)
    assert out["pipeline_interval_cycles"] == 12
    assert out["bottleneck"] == "fc0.mvu"


def test_synthetic_lm_structure_learnable():
    from repro.data.pipeline import SyntheticLM

    data = SyntheticLM(64, 32, 8, seed=3, jump_prob=0.0)
    b = next(iter(data))
    data.close()
    assert b["tokens"].shape == (8, 33)
    # with jump_prob=0 the stream is exactly tok[t+1] = perm[tok[t]]
    toks = b["tokens"]
    assert (data.perm[toks[:, :-1]] == toks[:, 1:]).all()


def test_checkpoint_manager_and_watchdog():
    from repro.checkpoint import ckpt
    from repro.distributed.fault_tolerance import CheckpointManager, StepWatchdog

    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, every=2, keep=2, use_async=True)
        for step in range(1, 7):
            mgr.maybe_save(step, tree)
        mgr.wait()
        assert ckpt.available_steps(d) == [4, 6]  # keep=2
        step, restored = mgr.resume_latest(jax.eval_shape(lambda: tree))
        assert step == 6
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))

    wd = StepWatchdog(straggler_factor=50.0)
    for _ in range(10):
        with wd:
            pass
    assert wd.stragglers == 0 and wd.median >= 0


def test_atomic_save_never_leaves_partial():
    from repro.checkpoint import ckpt

    tree = {"w": jnp.zeros((8, 8))}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, tree)
        # a .tmp dir from a crashed save must not be listed
        os.makedirs(os.path.join(d, "step_00000002.tmp"))
        assert ckpt.available_steps(d) == [1]


def test_dryrun_helpers():
    """Pure helpers of the dry-run harness (import after jax init so the
    XLA_FLAGS side effect cannot change this process's device count)."""
    jax.devices()  # lock in single-device config first
    from repro.launch import dryrun
    from repro.launch.shapes import all_cells_with_skips

    cells = all_cells_with_skips()
    assert len(cells) == 40
    skips = [c for c in cells if c[2]]
    assert len(skips) == 7  # 7 long_500k skips for full-attention archs
    assert all(s == "long_500k" for _, s, _ in skips)

    hlo = """
HloModule m
%region_body.1 (a: bf16[8,16]) -> bf16[8,16] {
  %x = bf16[8,16]{1,0} all-reduce(bf16[8,16] %a), replica_groups={}
  ROOT %y = bf16[8,16]{1,0} add(%x, %x)
}
ENTRY %main (p: bf16[8,16]) -> bf16[8,16] {
  %w = bf16[8,16]{1,0} while(bf16[8,16] %p), body=%region_body.1, condition=%c
  %g = bf16[32,16]{1,0} all-gather(bf16[8,16] %w), dimensions={0}
  ROOT %r = bf16[8,16]{1,0} slice(%g)
}
"""
    out = dryrun.parse_collective_bytes(hlo, scan_trips=10)
    assert out["all-reduce"] == 8 * 16 * 2 * 10  # body scaled by trips
    assert out["all-gather"] == 32 * 16 * 2
    # total applies the 2x ring factor to all-reduce
    assert out["total_bytes"] == 2 * out["all-reduce"] + out["all-gather"]

    from repro.configs import get_config

    cfg = get_config("jamba-1.5-large-398b")
    v1 = dryrun.shallow_variant(cfg, 1)
    assert v1.num_layers == cfg.attn_period and v1.scan_unroll
    assert dryrun.scan_trip_count(cfg) == 9


def test_param_count_model_flops_sane():
    from repro.configs import ARCH_IDS, get_config

    # spot-check advertised sizes (within 20%)
    expect = {"yi-9b": 8.8e9, "command-r-plus-104b": 104e9,
              "qwen3-moe-235b-a22b": 235e9, "mamba2-780m": 0.78e9,
              "jamba-1.5-large-398b": 398e9}
    for arch, n in expect.items():
        got = get_config(arch).param_count
        assert abs(got - n) / n < 0.35, (arch, got, n)
    # active < total for MoE
    for arch in ("granite-moe-3b-a800m", "qwen3-moe-235b-a22b", "jamba-1.5-large-398b"):
        cfg = get_config(arch)
        assert cfg.active_param_count < cfg.param_count
