import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, with ShapeDtypeStruct inputs (no allocation):

  * compiled.memory_analysis()  -- per-device bytes (proves it fits)
  * compiled.cost_analysis()    -- per-device HLO FLOPs / bytes accessed
  * collective bytes parsed from compiled.as_text() (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute),
    with while-body collectives multiplied by the layer-scan trip count
  * the three roofline terms (seconds) + dominant bottleneck

Results are written to experiments/dryrun/<arch>__<shape>__<mesh>.json;
benchmarks/roofline.py renders the EXPERIMENTS.md tables from them.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all            # every cell, both meshes
"""

import argparse
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _result_bytes(line: str) -> float:
    """Sum byte sizes of all typed shapes on the result side of an HLO line."""
    lhs = line.split(" = ", 1)
    if len(lhs) != 2:
        return 0.0
    rhs = lhs[1]
    # result type(s) precede the op name: e.g. "(bf16[8,128]{1,0}, u32[]) all-reduce("
    head = rhs.split("(", 1)[0] if not rhs.startswith("(") else rhs[: rhs.index(") ") + 1]
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(head):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str, scan_trips: int) -> dict:
    """Per-collective byte totals; while-body ops scaled by scan_trips.

    Byte model per chip: all-reduce moves ~2x its payload (ring), others
    ~1x the result payload.  Collectives inside while-loop bodies (the
    layer scans) execute once per trip.
    """
    # split into computations: "name { ... }"
    comp_bytes: dict[str, dict] = {}
    cur = None
    while_bodies: set[str] = set()
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?([\w.\-]+)\s*\([^)]*\)\s*->.*{$", s)
        if m or (s.startswith("ENTRY")):
            name = m.group(1) if m else "ENTRY"
            cur = name
            comp_bytes.setdefault(cur, {c: 0.0 for c in COLLECTIVES})
            continue
        if cur is None:
            continue
        for b in re.finditer(r"body=%?([\w.\-]+)", s):
            while_bodies.add(b.group(1))
        for c in COLLECTIVES:
            # match the op invocation, not tuple-element accesses
            if re.search(rf"\)?\s{c}[\.\(]|=\s*\(?[a-z0-9\[\],{{}} ]*\)?\s*{c}\(", s) or f" {c}(" in s:
                comp_bytes[cur][c] += _result_bytes(s)
                break

    out = {c: 0.0 for c in COLLECTIVES}
    for name, per in comp_bytes.items():
        mult = scan_trips if any(name.startswith(w) or w in name for w in while_bodies) else 1
        for c, v in per.items():
            out[c] += v * mult
    out["total_bytes"] = sum(
        (2.0 if c == "all-reduce" else 1.0) * v for c, v in out.items()
        if c in COLLECTIVES
    )
    return out


def scan_trip_count(cfg) -> int:
    if cfg.encdec:
        # encoder and decoder scans run with equal trip counts (whisper-tiny:
        # 4+4); the linear cost extrapolation treats one trip = one enc layer
        # + one dec layer.
        assert cfg.enc_layers == cfg.num_layers, "encdec extrapolation assumes equal depths"
        return cfg.num_layers
    if cfg.is_hybrid:
        return cfg.num_layers // cfg.attn_period
    return cfg.num_layers


def shallow_variant(cfg, trips: int):
    """Config with `trips` scan iterations, scan unrolled (no HLO while)."""
    p = cfg.attn_period if cfg.is_hybrid else 1
    kw = {"num_layers": trips * p, "scan_unroll": True}
    if cfg.encdec:
        kw["enc_layers"] = trips * p
    return cfg.replace(**kw)


HBM_PER_CHIP = 16e9  # v5e

_PROJ_NAMES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")


def quantize_param_shapes(tree, backend: str):
    """Dense (in,out) projection shapes -> integer MVU deployment shapes
    ((out,in) int8 values + (out,) f32 scale), leading stack dims kept.
    Serving cells with a mvu_* linear backend lower the true integer
    datapath; memory analysis then reflects the quantized weight residency
    (the paper's lever on the decode memory term)."""

    def walk(node, name):
        if isinstance(node, dict):
            if (
                name in _PROJ_NAMES
                and set(node) == {"w"}
                and len(node["w"].shape) >= 2
            ):
                shape = node["w"].shape
                lead, (din, dout) = shape[:-2], shape[-2:]
                wdt = jnp.int4 if backend in ("mvu_w4a8", "mvu_w4a4") else jnp.int8
                return {
                    "values": jax.ShapeDtypeStruct((*lead, dout, din), wdt),
                    "scale": jax.ShapeDtypeStruct((*lead, dout), jnp.float32),
                }
            return {k: walk(v, k) for k, v in node.items()}
        return node

    return walk(tree, "")


# --------------------------------------------------------------------- cells
def build_cell(cfg, shape_name: str, mesh, *, fsdp: bool | None = None):
    """Returns (fn, example args (ShapeDtypeStructs), donate, in_shardings,
    cfg, accounting).

    fsdp=None -> automatic: enable ZeRO-3 2D weight sharding whenever the
    TP-only parameter (+optimizer, for train) footprint would exceed half
    the 16 GB v5e HBM (command-r-plus-104b, qwen3-moe-235b, jamba-398b).
    """
    from repro.distributed.sharding import (
        batch_shardings, cache_pspecs, param_shardings,
    )
    from repro.launch.shapes import SHAPES
    from repro.launch.train import make_train_step
    from repro.models.model import build
    from repro.optim import adamw

    from repro.distributed.sharding import bytes_per_device

    spec = SHAPES[shape_name]
    model = build(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if cfg.linear_backend.startswith("mvu_") and spec.kind != "train":
        # serving with the paper's engine: integer-deployed projections
        params_shape = quantize_param_shapes(params_shape, cfg.linear_backend)
    if fsdp is None:
        tp_only = bytes_per_device(params_shape, param_shardings(mesh, params_shape), mesh)
        if spec.kind == "train":
            tp_only *= 5.0  # + fp32 grads/moments
        fsdp = tp_only > HBM_PER_CHIP / 2
    p_shard = param_shardings(mesh, params_shape, fsdp=fsdp)
    acct = {"params_dev": bytes_per_device(params_shape, p_shard, mesh),
            "state_dev": 0.0, "fsdp": bool(fsdp)}

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    if spec.kind == "train":
        b, s = spec.global_batch, spec.seq_len
        batch = {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}
        if cfg.family == "vlm":
            batch["prefix_embeds"] = jax.ShapeDtypeStruct((b, 256, cfg.d_model), jnp.bfloat16)
        if cfg.encdec:
            batch["enc_embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        opt_shape = jax.eval_shape(adamw.init, params_shape)
        o_shard = {
            "mu": p_shard, "nu": p_shard,
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        b_shard = batch_shardings(mesh, batch)
        fn = make_train_step(model, adamw.AdamWConfig())
        return fn, (params_shape, opt_shape, batch), (0, 1), (p_shard, o_shard, b_shard), cfg, acct

    if spec.kind == "prefill":
        b, s = spec.global_batch, spec.seq_len
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.family == "vlm":
            batch["prefix_embeds"] = jax.ShapeDtypeStruct((b, 256, cfg.d_model), jnp.bfloat16)
        if cfg.encdec:
            batch["enc_embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        state_shape = jax.eval_shape(lambda: model.init_decode_state(b, s))
        s_shard = cache_pspecs(mesh, state_shape, seq_over_model=True)
        b_shard = batch_shardings(mesh, batch)
        acct["state_dev"] = bytes_per_device(state_shape, s_shard, mesh)
        return (
            model.prefill,
            (params_shape, batch, state_shape),
            (2,),
            (p_shard, b_shard, s_shard),
            cfg,
            acct,
        )

    # decode
    b, s = spec.global_batch, spec.seq_len
    state_shape = jax.eval_shape(lambda: model.init_decode_state(b, s))
    seq_sp = s >= 32768  # SP: shard long KV caches over "model"
    s_shard = cache_pspecs(mesh, state_shape, seq_over_model=seq_sp)
    if b >= dp_size:
        tok_shard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(dp))
    else:
        tok_shard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    acct["state_dev"] = bytes_per_device(state_shape, s_shard, mesh)
    return (
        model.decode_step,
        (params_shape, state_shape, tokens),
        (1,),
        (p_shard, s_shard, tok_shard),
        cfg,
        acct,
    )


def _compile_cell(cfg, shape_name, mesh, fsdp=None):
    fn, args, donate, shardings, cfg, acct = build_cell(cfg, shape_name, mesh, fsdp=fsdp)
    t0 = time.time()

    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    return compiled, t_lower, t_compile, acct


def _cost_of(compiled) -> tuple[float, float]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    return float(cost.get("flops", 0.0)), float(cost.get("bytes accessed", 0.0))


def analytic_hbm_bytes(cfg, spec, mesh, *, params_dev: float, state_dev: float) -> float:
    """Fused-stream HBM traffic estimate per device per step (bytes).

    The CPU backend's "bytes accessed" counts every HLO operand with no
    fusion, overstating TPU HBM traffic by orders of magnitude; this model
    counts the irreducible streams a fused TPU program must move:

      train:   3x weight reads (fwd + remat-fwd + bwd) + param update r/w
               + fp32 grads r/w + fp32 moments r/w (2 moments)
               + remat-boundary activations (L x B_dev x S x d, w+r)
               + fp32 logits (w+r)
      prefill: 1x weight read + cache write + boundary activations
      decode:  1x weight read + full cache read + tiny writes
    """
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    b_dev = max(1, spec.global_batch // dp)
    s = spec.seq_len
    trips = scan_trip_count(cfg)
    d = cfg.d_model
    model_shards = mesh.shape.get("model", 1)

    if spec.kind == "train":
        n_param = cfg.param_count / model_shards  # elements per device (TP)
        acts = trips * b_dev * s * d * 2 * 2  # bf16 boundary saves, w+r
        logits = b_dev * s * cfg.vocab_size / model_shards * 4 * 2
        return (
            3 * params_dev  # bf16 weight streams
            + 2 * params_dev  # param read+write at update
            + 2 * n_param * 4  # fp32 grads w+r
            + 4 * n_param * 4  # two fp32 moments r+w
            + acts + logits
        )
    if spec.kind == "prefill":
        acts = trips * b_dev * s * d * 2 * 2
        return params_dev + state_dev + acts
    # decode: weights once + the whole cache read (+ small writes)
    return params_dev + state_dev * 1.05


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             quant: str | None = None, seq_sp: bool = False,
             fsdp: bool | None = None, naive_attn: bool = False,
             kv_quant: bool = False,
             save_dir: str = "experiments/dryrun",
             save_hlo: bool = False, tag_suffix: str = "") -> dict:
    from repro.configs import get_config
    from repro.core.resource_model import (
        HBM_BW, ICI_BW_PER_LINK, PEAK_BF16_FLOPS, roofline_terms,
    )
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, skip_reason

    reason = skip_reason(arch, shape_name)
    tag = f"{arch}__{shape_name}__{mesh_name}" + (f"__{quant}" if quant else "") + tag_suffix
    if reason:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": reason}
        _save(save_dir, tag, rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch)
    if quant:
        cfg = cfg.replace(linear_backend=quant)
    if seq_sp:
        cfg = cfg.replace(seq_sharded_acts=True)
    if naive_attn:
        cfg = cfg.replace(attn_q_chunk=0)
    if kv_quant:
        cfg = cfg.replace(kv_quant=True)

    # 1) full-depth compile: THE dry-run artifact (memory fit + lowering proof)
    compiled, t_lower, t_compile, acct = _compile_cell(cfg, shape_name, mesh, fsdp)
    fsdp_used = acct["fsdp"]
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    trips = scan_trip_count(cfg)
    coll_while = parse_collective_bytes(hlo, trips)

    # 2) cost extrapolation: XLA's cost_analysis counts while bodies ONCE,
    # so compile shallow UNROLLED variants (1 and 2 scan trips) and use
    #   total = c1 + (trips - 1) * (c2 - c1)
    # which is exact for identical stacked layers (embed/head/optimizer are
    # depth-constant, per-layer work is the slope).  Collectives from the
    # unrolled HLO extrapolate the same way.
    c1, _, _, _ = _compile_cell(shallow_variant(cfg, 1), shape_name, mesh, fsdp_used)
    c2, _, _, _ = _compile_cell(shallow_variant(cfg, 2), shape_name, mesh, fsdp_used)
    f1, b1 = _cost_of(c1)
    f2, b2 = _cost_of(c2)
    coll1 = parse_collective_bytes(c1.as_text(), 1)
    coll2 = parse_collective_bytes(c2.as_text(), 1)
    # slopes clamped >= 0: XLA occasionally fuses the 2-trip variant more
    # aggressively than the 1-trip one, which would extrapolate negative.
    flops_dev = f1 + (trips - 1) * max(f2 - f1, 0.0)
    bytes_dev = b1 + (trips - 1) * max(b2 - b1, 0.0)
    coll = {
        k: coll1[k] + (trips - 1) * max(coll2[k] - coll1[k], 0.0)
        for k in coll1
    }

    spec = SHAPES[shape_name]
    tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode" else 1)
    n_active = cfg.active_param_count
    mult = 6 if spec.kind == "train" else 2
    model_flops = mult * n_active * tokens

    roof_hlo = roofline_terms(
        flops_dev * chips, bytes_dev * chips, coll["total_bytes"], chips=chips
    )
    # fused-stream memory estimate (the CPU backend HLO byte count has no
    # fusion and overstates HBM traffic; see analytic_hbm_bytes docstring)
    bytes_analytic = analytic_hbm_bytes(cfg, spec, mesh,
                                        params_dev=acct["params_dev"],
                                        state_dev=acct["state_dev"])
    roof = roofline_terms(
        flops_dev * chips, bytes_analytic * chips, coll["total_bytes"], chips=chips
    )
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "quant": quant,
        "fsdp": fsdp_used,
        "seq_sp": seq_sp,
        "naive_attn": naive_attn,
        "kv_quant": kv_quant,
        "chips": chips,
        "kind": spec.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None) if hasattr(mem, "peak_memory_in_bytes") else None,
        },
        "cost": {"flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
                 "trip1": {"flops": f1, "bytes": b1},
                 "trip2": {"flops": f2, "bytes": b2}},
        "collectives": coll,
        "collectives_whileparse": coll_while,
        "scan_trips": trips,
        "model_flops": model_flops,
        "useful_flops_ratio": model_flops / max(flops_dev * chips, 1.0),
        "bytes_analytic_per_device": bytes_analytic,
        "accounting": acct,
        "roofline": roof,
        "roofline_hlo_bytes": roof_hlo,
        "hw": {"peak_flops": PEAK_BF16_FLOPS, "hbm_bw": HBM_BW,
               "link_bw": ICI_BW_PER_LINK},
    }
    _save(save_dir, tag, rec)
    if save_hlo:
        with open(os.path.join(save_dir, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def _save(save_dir: str, tag: str, rec: dict):
    os.makedirs(save_dir, exist_ok=True)
    with open(os.path.join(save_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--quant", default=None)
    ap.add_argument("--seq-sp", action="store_true")
    ap.add_argument("--naive-attn", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--suffix", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-dir", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    from repro.configs import ARCH_IDS
    from repro.launch.shapes import SHAPES

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{arch} x {shape} x {mesh_name}"
                try:
                    t0 = time.time()
                    fsdp = None if args.fsdp is None else (args.fsdp == "on")
                    rec = run_cell(arch, shape, mesh_name, quant=args.quant,
                                   seq_sp=args.seq_sp, fsdp=fsdp,
                                   naive_attn=args.naive_attn,
                                   kv_quant=args.kv_quant,
                                   save_dir=args.save_dir, save_hlo=args.save_hlo,
                                   tag_suffix=args.suffix)
                    if rec.get("skipped"):
                        print(f"[dryrun] SKIP {tag}: {rec['skipped']}", flush=True)
                    else:
                        r = rec["roofline"]
                        print(
                            f"[dryrun] OK   {tag}: compile {rec['compile_s']}s "
                            f"dominant={r['dominant']} bound={r['bound_s']:.4f}s "
                            f"mem/dev={rec['memory']['argument_bytes']}",
                            flush=True,
                        )
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] FAIL {tag}: {e!r}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
