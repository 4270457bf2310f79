"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps on
a host-device mesh, with checkpointing + resume + the full sharded train
step (same code path the 256/512-chip dry-run lowers).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import os
import time

import jax


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--quant", default=None,
                    help="mvu_w8a8|mvu_w4a8: route projections through the "
                         "paper's MVU datapath (QAT fake-quant)")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train_loop
    from repro.models.model import build
    from repro.optim import adamw

    # ~100M params: yi-9b family scaled down (8 layers, d=768)
    cfg = get_config("yi-9b").replace(
        name="yi-100m", num_layers=8, d_model=768, num_heads=12,
        num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32000,
        dtype="float32", remat=False,
    )
    if args.quant:
        cfg = cfg.replace(linear_backend=args.quant)
    model = build(cfg)
    n_params = cfg.param_count
    print(f"[train_lm] {cfg.name}: ~{n_params/1e6:.0f}M params, "
          f"{n_dev} devices, quant={args.quant}")

    shape = {1: (1, 1), 2: (2, 1), 4: (2, 2), 8: (4, 2)}.get(n_dev, (n_dev, 1))
    mesh = make_mesh(shape)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    t0 = time.time()
    _, _, hist = train_loop(
        model, mesh, steps=args.steps, batch_iter=iter(data),
        opt_cfg=adamw.AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps),
        ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 4, 10),
    )
    data.close()
    dt = time.time() - t0
    toks = args.steps * args.batch * args.seq
    print(f"[train_lm] loss {hist[0]:.3f} -> {hist[-1]:.3f} in {dt:.0f}s "
          f"({toks/dt:.0f} tok/s); ckpts in {args.ckpt_dir}")
    if args.steps >= 100:
        assert hist[-1] < hist[0] - 1.0, "loss should drop by >1 nat on synthetic LM"


if __name__ == "__main__":
    main()
