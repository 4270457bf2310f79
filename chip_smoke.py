"""Smoke run of the main path (build -> Accelerator -> serve) on a TPU.

    python chip_smoke.py            # one chip: NID-MLP, NID serving, CNV FULL
    python chip_smoke.py --chips 4  # four chips: replica pool, 4-stage pipeline

One process, which starts no others.  Every phase checks its outputs
bit-exactly against the same graph built with ``backend="xla"`` (the
``kernels/ref.py`` oracles) or against the single-device path, prints one
JSON line of results, and raises on the first mismatch.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and nothing else; it is
printed only when every phase passed.  Without a TPU, or without the
repository's ``src/repro`` next to this file, the script exits non-zero
before any phase runs.  Weights and inputs are random, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BUCKETS = (1, 8, 32, 128)
# every failure-handling counter a healthy run must leave at zero
SERVING_FAULT_COUNTERS = ("retries", "dispatch_failures", "quarantines",
                          "shed", "deadline_misses", "timeouts",
                          "corrupt_batches", "rejected")


class SmokeError(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": True,
                      "wall_s": time.perf_counter() - t0, **fields}),
          flush=True)


def exact(got, want, what: str) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape and np.array_equal(got, want),
          f"{what}: output differs from the reference "
          f"({int(np.sum(got != want)) if got.shape == want.shape else 'shape'}"
          f" mismatches)")


def compiled_kernels(engine, x) -> list[str]:
    """Pallas kernels in the program the engine runs for input ``x``."""
    import jax

    from repro.kernels import ops

    return ops.tpu_kernel_names(jax.jit(engine).lower(x).compile().as_text())


def serve_all(acc, pool, xs):
    """Submit every row of ``xs`` as one request, drain, and return the
    outputs in request order plus the batcher (for its metrics)."""
    import numpy as np

    batcher = acc.serve(batch_buckets=BUCKETS, pool=pool)
    rids = []
    for row in xs:
        rids.append(batcher.submit(row))
        batcher.poll()
    batcher.drain(timeout=300)
    outs = []
    for rid in rids:
        r = batcher.pop_result(rid)
        check(r is not None and not r.shed, f"request {rid} got no result")
        outs.append(r.out)
    snap = batcher.metrics.snapshot()
    bad = {k: snap[k] for k in SERVING_FAULT_COUNTERS if snap[k]}
    check(not bad, f"serving failure counters are not zero: {bad}")
    check(pool.quarantines == 0, "a replica was quarantined")
    return np.stack(outs), snap


def nid_build(seed: int, **overrides):
    from repro.build import build
    from repro.configs import nid_mlp

    kw = dict(mode="standard", weight_bits=nid_mlp.WEIGHT_BITS,
              act_bits=nid_mlp.INPUT_BITS, folding=nid_mlp.foldings(),
              verify="all", seed=seed, name="nid_mlp")
    kw.update(overrides)
    return build(nid_mlp.build_graph(seed), **kw)


def nid_inputs(seed: int, batch: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import nid_mlp

    rng = np.random.default_rng(seed + 1)
    k = nid_mlp.LAYERS[0][0]
    return jnp.asarray(rng.integers(0, 2**nid_mlp.INPUT_BITS, (batch, k)),
                       jnp.int32)


# ------------------------------------------------------------- one chip
def phase_nid(seed: int):
    t0 = time.perf_counter()
    acc = nid_build(seed, target="serving")
    ref = nid_build(seed, target="engine", backend="xla", name="nid_mlp_xla")
    x = nid_inputs(seed, 1024)
    exact(acc(x), ref(x), "NID engine vs backend='xla' build")
    kernels = compiled_kernels(acc.engine, x)
    check(kernels == ["mvu_int"] * 4,
          f"expected 4 mvu_int Pallas kernels, compiled {kernels}")
    check(acc.report.cycle_time_source == "measured",
          "the serving build recorded no measured cycle time")
    emit("nid_mlp", t0, batch=int(x.shape[0]), bit_exact=True,
         tpu_custom_calls=len(kernels), kernels=kernels,
         s_per_cycle=float(acc.calibration["s_per_cycle"]),
         cycle_time_source=acc.report.cycle_time_source)
    return acc


def phase_nid_serving(acc, seed: int, device) -> None:
    import numpy as np

    from repro.serving import ReplicaPool

    t0 = time.perf_counter()
    xs = np.asarray(nid_inputs(seed + 2, 64))
    want = np.asarray(acc.engine(xs))
    got, snap = serve_all(acc, ReplicaPool(acc.engine, devices=[device]), xs)
    exact(got, want, "served results vs direct engine")
    emit("nid_serving", t0, requests=len(xs), bit_exact=True,
         buckets=list(BUCKETS),
         fault_counters={k: snap[k] for k in SERVING_FAULT_COUNTERS},
         smoke_latency_ms={"p50": snap["p50_ms"], "p99": snap["p99_ms"]})


def phase_cnv(seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.build import build
    from repro.configs import cnv_bnn

    t0 = time.perf_counter()
    spec = cnv_bnn.FULL
    graph = cnv_bnn.build_graph(spec, seed=seed)
    kw = dict(target="engine", mode="xnor", weight_bits=spec.weight_bits,
              act_bits=spec.act_bits, verify="all", seed=seed)
    acc = build(graph, name="cnv_full", **kw)
    ref = build(graph, backend="xla", name="cnv_full_xla", **kw)
    rng = np.random.default_rng(seed + 3)
    x = jnp.asarray(rng.integers(0, 2, (8, spec.image, spec.image, 3)),
                    jnp.int32)
    exact(acc(x), ref(x), "CNV FULL engine vs backend='xla' build")
    kernels = compiled_kernels(acc.engine, x)
    check(kernels.count("conv_mvu_xnor") == len(spec.channels)
          and kernels.count("mvu_xnor") == len(spec.fc),
          f"CNV FULL compiled unexpected Pallas kernels: {kernels}")
    emit("cnv_full", t0, images=int(x.shape[0]), bit_exact=True,
         tpu_custom_calls=len(kernels), kernels=sorted(set(kernels)))


# ----------------------------------------------------------- four chips
def phase_replica_pool(seed: int, devices) -> None:
    import numpy as np

    from repro.serving import ReplicaPool

    t0 = time.perf_counter()
    acc = nid_build(seed, target="engine")
    xs = np.asarray(nid_inputs(seed + 4, 4 * BUCKETS[-1]))
    one, _ = serve_all(acc, ReplicaPool(acc.engine, devices=devices[:1]), xs)
    pool = ReplicaPool(acc.engine, devices=devices)
    four, _ = serve_all(acc, pool, xs)
    exact(four, one, f"{len(devices)}-replica pool vs 1-replica pool")
    check(all(r.dispatched > 0 for r in pool.replicas),
          f"not every replica dispatched: {pool.load()}")
    # each replica computes on its own chip
    for r in pool.replicas:
        others = tuple(o.index for o in pool.replicas if o is not r)
        pending = pool.dispatch(xs[:BUCKETS[-1]], [], n_valid=BUCKETS[-1],
                                exclude=others)
        check(pending.out.devices() == {r.device},
              f"replica {r.index} output on {pending.out.devices()}, "
              f"not {r.device}")
        exact(pending.resolve(), one[:BUCKETS[-1]],
              f"replica {r.index} direct dispatch")
    emit("replica_pool", t0, replicas=len(pool), requests=len(xs),
         bit_exact=True, dispatches=pool.load())


def phase_pipeline(seed: int, devices) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.build import build
    from repro.configs import cnv_bnn
    from repro.core.ir import Node
    from repro.launch.mesh import make_mesh

    t0 = time.perf_counter()
    d, n_layers, bits = cnv_bnn.FULL.fc[0], 8, 2
    rng = np.random.default_rng(seed + 5)
    g = [Node("input", "in", {"shape": (d,), "bits": bits})]
    for i in range(n_layers):
        w = rng.normal(0, 1 / np.sqrt(d), (d, d)).astype(np.float32)
        g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
        g.append(Node("batchnorm", f"bn{i}", {}, {
            "gamma": jnp.asarray(rng.uniform(0.5, 1.5, d).astype(np.float32)),
            "beta": jnp.asarray(rng.uniform(-0.5, 0.5, d).astype(np.float32)),
            "mean": jnp.asarray(rng.normal(0, 1, d).astype(np.float32)),
            "var": jnp.asarray(rng.uniform(0.5, 2, d).astype(np.float32)),
        }))
        g.append(Node("quant_act", f"act{i}", {"bits": bits, "act_scale": 1.0}))
    acc = build(g, target="pipeline", mode="standard", weight_bits=4,
                act_bits=bits, seed=seed, name="mvu_chain_512")
    n_micro, mb = 8, 128
    xs = jnp.asarray(rng.integers(0, 2**bits, (n_micro, mb, d)), jnp.int32)
    mesh = make_mesh((len(devices),), ("stage",), devices=devices)
    got = acc.as_pipeline(mesh)(xs)
    want = np.asarray(acc(xs.reshape(n_micro * mb, d))).reshape(n_micro, mb, d)
    exact(got, want, f"{len(devices)}-stage pipeline vs single-device engine")
    emit("pipeline", t0, stages=len(devices), layers=n_layers, width=d,
         microbatches=n_micro, microbatch=mb, bit_exact=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths that exist across chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package at {src}; run this script from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no TPU: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); this run needs a TPU chip and has "
              "no CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(json.dumps({"phase": "start", "device_kind": devices[0].device_kind,
                      "chips": args.chips, "compile_cache": cache_dir,
                      "cache_entries_at_start": cached}), flush=True)
    if args.chips == 1:
        acc = phase_nid(args.seed)
        phase_nid_serving(acc, args.seed, devices[0])
        phase_cnv(args.seed)
    else:
        phase_replica_pool(args.seed, devices[:args.chips])
        phase_pipeline(args.seed, devices[:args.chips])
    print(json.dumps({"phase": "end", "cache_entries_at_end": len(
        os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}), flush=True)
    # count = the chips the phases used (one-chip phases touch devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
