"""Paper Tables 6/7: the network-intrusion-detection MLP.

Builds the exact 4-layer / 2-bit MLP of Table 6 with the paper's PE/SIMD
folding, then reports per layer:

  * resource model (LUT/FF/BRAM analogs), weight-memory + input-buffer
    depths (Eq. 2),
  * execution cycles: our NF*SF model + the FINN pipeline depth of 5
    reproduces Table 7's 17/13/13 cycles exactly,
  * synthesis-time analogs (XLA ref vs Pallas kernel compile),
  * functional check: integer MVU inference on the synthetic UNSW-NB15
    stand-in reaches the accuracy of its float teacher.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import compile_probe, emit, hls_ref_fn, rtl_kernel_fn
from repro.configs import nid_mlp
from repro.core.folding import Folding, to_tpu_blocks
from repro.core.resource_model import mvu_resources

PIPELINE_DEPTH = 5  # FINN MVU register stages (input, simd, adder, acc, out)


def run(out=None):
    rows = []
    for i, (k, n, pe, simd) in enumerate(nid_mlp.LAYERS):
        fold = Folding(pe, simd)
        # paper PE/SIMD need not divide (layer0: 600/50=12 exact; 64/64=1)
        res = mvu_resources(n, k, fold, mode="standard",
                            weight_bits=nid_mlp.WEIGHT_BITS,
                            act_bits=nid_mlp.INPUT_BITS, n_pixels=1,
                            n_thresh=2**nid_mlp.INPUT_BITS - 1)
        cycles = fold.cycles(n, k, 1)
        a_s = jax.ShapeDtypeStruct((128, k), jnp.int8)
        w_s = jax.ShapeDtypeStruct((n, k), jnp.int8)
        hls = compile_probe(hls_ref_fn("standard", k), a_s, w_s)
        blocks = to_tpu_blocks(fold, "standard", n=n, k=k)
        rtl = compile_probe(rtl_kernel_fn("standard", k, blocks), a_s, w_s)
        rows.append({
            "layer": i, "K": k, "N": n, "PE": pe, "SIMD": simd,
            "exec_cycles_model": cycles + PIPELINE_DEPTH,
            "exec_cycles_paper_rtl": [17, 13, 13, 13][i],
            "wmem_depth": res.weight_mem_depth,
            "inbuf_depth": res.input_buffer_depth,
            "rtl_lut_bytes": res.lut_bytes,
            "rtl_ff_bytes": res.ff_bytes,
            "rtl_bram_bytes": res.bram_bytes,
            "hls_temp_bytes": hls["temp_bytes"],
            "hls_compile_s": round(hls["total_s"], 4),
            "rtl_compile_s": round(rtl["total_s"], 4),
        })
    emit(rows, out)
    return rows


def accuracy_check(n_train: int = 4096, n_test: int = 1024, steps: int = 300):
    """Train float MLP on synthetic NID data, streamline to 2-bit MVU graph
    through the ``repro.build`` pipeline (the QAT flow opts into the
    ``streamline`` step by name), compare integer-pipeline accuracy against
    the float model."""
    import repro.build as rbuild
    from repro.core import dataflow
    from repro.core.ir import Node
    from repro.data.nid import make_dataset

    x_train, y_train = make_dataset(n_train, seed=0)
    x_test, y_test = make_dataset(n_test, seed=1)

    dims = [600, 64, 64, 64, 1]
    key = jax.random.PRNGKey(0)
    ws = []
    for k, n in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        ws.append(jax.random.normal(sub, (n, k)) * (1.0 / np.sqrt(k)))

    def fwd(ws, x):
        h = x.astype(jnp.float32)
        for i, w in enumerate(ws):
            h = h @ w.T
            if i < len(ws) - 1:
                h = jnp.clip(jnp.round(jnp.maximum(h, 0)), 0, 3)  # 2-bit act
        return h[..., 0]

    def loss(ws, x, y):
        logit = fwd(ws, x)
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    # straight-through trick: quantized forward, float backward
    def loss_ste(ws, x, y):
        h = x.astype(jnp.float32)
        for i, w in enumerate(ws):
            h = h @ w.T
            if i < len(ws) - 1:
                hq = jnp.clip(jnp.round(jnp.maximum(h, 0)), 0, 3)
                h = h + jax.lax.stop_gradient(hq - h)
        logit = h[..., 0]
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    step = jax.jit(lambda ws, x, y: jax.tree.map(
        lambda w, g: w - 0.03 * g, ws, jax.grad(loss_ste)(ws, x, y)))
    xb = jnp.asarray(x_train, jnp.float32)
    yb = jnp.asarray(y_train, jnp.float32)
    for _ in range(steps):
        ws = step(ws, xb, yb)

    float_acc = float(jnp.mean((fwd(ws, jnp.asarray(x_test, jnp.float32)) > 0)
                               == jnp.asarray(y_test)))

    # streamline into the integer MVU dataflow graph
    graph = [Node("input", "in", {"shape": (600,), "bits": 2})]
    for i, w in enumerate(ws):
        graph.append(Node("linear", f"fc{i}", {}, {"w": w}))
        if i < len(ws) - 1:
            n = w.shape[0]
            graph.append(Node("batchnorm", f"bn{i}", {}, {
                "gamma": jnp.ones((n,)), "beta": jnp.zeros((n,)),
                "mean": jnp.zeros((n,)), "var": jnp.ones((n,)) - 1e-5,
            }))
            graph.append(Node("quant_act", f"act{i}", {"bits": 2, "act_scale": 1.0}))
    # the streamlining flow: BN+quant fold into thresholds at lowering time
    # (on float weights), so "streamline" replaces the engine targets'
    # runtime fuse steps in the step list
    acc = rbuild.build(
        graph, target="interpret", mode="standard", weight_bits=8, act_bits=2,
        folding=nid_mlp.foldings(), name="nid_mlp_qat",
        steps=("validate", "lower", "streamline", "finalize", "fold",
               "dataflow"))
    stream = acc.graph
    out = acc.interpret(jnp.asarray(x_test, jnp.int32))
    # final layer emits raw int32 accumulator (no thresholds on the head);
    # the integer acc must be scaled by the head's weight scale for sign.
    mvu_nodes = [n for n in stream if n.op == "mvu"]
    scale = mvu_nodes[-1].params["mvu"].out_scale
    logits = out[..., 0] * (scale[0] if scale is not None else 1.0)
    int_acc = float(jnp.mean((logits > 0) == jnp.asarray(y_test)))
    sched = dataflow.schedule(stream)
    return {
        "float_acc": float_acc,
        "mvu_int_acc": int_acc,
        "pipeline_interval_cycles": sched.steady_state_interval,
        "pipeline_latency_cycles": sched.latency_cycles,
        "bottleneck": sched.bottleneck.name,
    }


def run_quick(out_dir: str | None = None) -> dict:
    """One JSON record: Table 7 cycle parity + the QAT accuracy check."""
    from benchmarks.common import emit_json

    rows = run(out=None)
    acc = accuracy_check(steps=200)
    claims = {
        "cycles_match_paper": all(
            r["exec_cycles_model"] == r["exec_cycles_paper_rtl"] for r in rows),
        "int_acc_tracks_float": acc["mvu_int_acc"] >= acc["float_acc"] - 0.05,
    }
    record = {
        "name": "nid_mlp",
        "layers": rows,
        "accuracy": acc,
        "claims": claims,
        "summary": f"cycles {'==' if claims['cycles_match_paper'] else '!='} "
                   f"paper; float={acc['float_acc']:.3f} "
                   f"int={acc['mvu_int_acc']:.3f}",
    }
    if not all(claims.values()):
        raise AssertionError(f"NID-MLP claims failed: {claims}")
    if out_dir:
        emit_json(record, f"{out_dir}/nid_mlp.json")
    return record


if __name__ == "__main__":
    print(run_quick(out_dir="experiments/bench"))
