"""Serve a small LM with batched requests: prefill + lockstep decode,
optionally through the paper's integer MVU datapath (post-training W8A8).

Run:  PYTHONPATH=src python examples/serve_lm.py --requests 8 --quant mvu_w8a8
"""

import argparse
import time

import jax
import numpy as np


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--quant", default=None, help="mvu_w8a8: integer serving")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.serve import Request, serve_loop
    from repro.models.layers import quantize_model_params
    from repro.models.model import build

    cfg = get_config("yi-9b").replace(
        name="yi-serve", num_layers=4, d_model=256, num_heads=8,
        num_kv_heads=4, head_dim=32, d_ff=512, vocab_size=1000,
        dtype="float32", remat=False,
    )
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if args.quant:
        # post-training quantization: every projection -> integer MVU params
        cfg = cfg.replace(linear_backend=args.quant)
        model = build(cfg)

        params = quantize_model_params(params, args.quant)
        print(f"[serve_lm] weights quantized to {args.quant} (integer MVU datapath)")

    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, rng.integers(4, 12)),
                max_new=args.max_new, t_submit=time.time())
        for i in range(args.requests)
    ]
    t0 = time.time()
    done = serve_loop(model, params, reqs, batch=args.batch, max_len=64)
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in done)
    print(f"[serve_lm] served {len(done)} requests, {total_new} tokens "
          f"in {dt:.1f}s ({total_new/dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.rid}: prompt={r.prompt[:6].tolist()}... -> {r.out[:8]}")
    assert all(len(r.out) == args.max_new for r in done)


if __name__ == "__main__":
    main()
