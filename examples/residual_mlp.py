"""Residual (skip-connection) MLP: the DAG IR end-to-end.

The chain-era IR could only express straight-line models; this example
exercises everything the DAG lift added, on a NID-style variant with a
residual connection around the middle layer:

      in(600) -> fc0 -> bn0 -> act0 --+--> fc1 -> bn1 -> act1 --+
                                      |                         v
                                      +-----------------------> add("res")
                                                                 |
                                                                 v
                                                             fc2 -> out(1)

  1. author the fan-out/fan-in graph (``repro.configs.residual_mlp``),
  2. validate it (``ir.validate_graph``: arity, broadcast, single sink),
  3. build it for all three targets -- interpret, engine, pipeline --
     through the ``repro.build`` step pipeline with every verification
     hook on, each transform held bit-exact against the DAG interpreter,
  4. print the lowered topology: edge list, branch labels, and the
     join's branch-latency skew + FIFO depth from the dataflow schedule,
  5. write the BuildReport JSON (now carrying ``edges`` and per-node
     ``inputs``/``branch``) next to the other committed reports.

Run:  PYTHONPATH=src python examples/residual_mlp.py [--fast]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax.numpy as jnp

from repro.build import build
from repro.configs import residual_mlp
from repro.core import ir


def main(fast: bool = False):
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    batch = 64 if fast else 256
    graph = residual_mlp.build_graph()
    print("== residual NID-MLP variant: 600-64-(64+skip)-1 @ 2-bit ==")
    ir.validate_graph(graph)
    labels = ir.branch_labels(graph)
    for node, ins, out_shape in ir.io_shapes(graph):
        srcs = ", ".join(node.inputs) if node.inputs else "-"
        print(f"  {node.name:5s} ({node.op:9s}) <- {srcs:12s} "
              f"-> {out_shape}  [branch {labels[node.name]}]")

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2**residual_mlp.INPUT_BITS,
                                 (batch, residual_mlp.LAYERS[0][0])),
                    jnp.int32)

    print("== repro.build: same graph, three targets, all verified ==")
    accs = {}
    for target in ("interpret", "engine", "pipeline"):
        # the engine build writes the committed BuildReport artifact
        out_dir = "experiments/build" if target == "engine" else None
        accs[target] = build(graph, target=target, mode="standard",
                             weight_bits=residual_mlp.WEIGHT_BITS,
                             act_bits=residual_mlp.INPUT_BITS,
                             folding=residual_mlp.foldings(),
                             name="residual_mlp", output_dir=out_dir)
        rep = accs[target].report
        print(f"  target {target:9s}: steps {' -> '.join(rep.step_names)} "
              f"| verified {sum(1 for s in rep.steps if s.verified)}")

    ref = np.asarray(accs["interpret"](x))
    for target in ("engine", "pipeline"):
        got = np.asarray(accs[target](x))
        same = np.array_equal(got, ref)
        print(f"  {target:9s} vs interpret: bit-exact={same}")
        assert same, f"{target} diverged from the DAG reference interpreter"

    acc = accs["engine"]
    rep = acc.report
    print("== lowered DAG topology (from the BuildReport) ==")
    print(f"  edges          : {['->'.join(e) for e in rep.edges]}")
    print(f"  node branches  : "
          f"{ {n.name: n.branch for n in rep.nodes} }")
    sched = acc.engine.schedule
    print(f"  interval       : {sched.steady_state_interval} cycles "
          f"(bottleneck {sched.bottleneck.name})")
    print(f"  critical path  : {sched.latency_cycles} cycles "
          f"(longest path, not the stage sum)")
    for j in sched.joins:
        skew = max(j.branch_latency) - min(j.branch_latency)
        print(f"  join {j.name!r}     : branches {j.branches}, "
              f"latencies {j.branch_latency} (skew {skew}) "
              f"-> FIFO depth {j.fifo_depth}")
    assert sched.joins and sched.joins[0].fifo_depth >= 2
    print(f"  build report   : {rep.path}")
    print("OK: skip-connection graph builds and streams bit-exactly "
          "on every target")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smaller probe batch (CI smoke)")
    main(fast=ap.parse_args().fast)
