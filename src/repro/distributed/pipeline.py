"""Pipeline-parallel streaming executor: the FINN dataflow graph on a TPU
mesh (DESIGN.md section 2).

FINN instantiates one compute unit per layer and streams activations
through AXI links; the TPU analog assigns contiguous layer ranges to mesh
devices along a "stage" axis and streams *microbatches* through
``ppermute`` links (GPipe schedule).  The correspondences:

    AXI stream / TVALID-TREADY      ppermute send (statically scheduled)
    FIFO between layers             the in-flight microbatch buffer
    FINN folding / rate balancing   equal per-stage layer counts (the
                                    folding pass equalizes stage cycles)
    II = 1 steady state             one microbatch per stage per tick
    pipeline bubbles                (S-1) fill + (S-1) drain ticks

``pipeline_apply`` is generic over the per-stage function; gradients flow
through (jax.grad of the whole schedule works) so it serves for training
and for serving.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_params_split(params_stacked, n_stages: int):
    """Reshape a (L, ...)-stacked layer-param tree to (n_stages, L/S, ...)."""

    def r(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree.map(r, params_stacked)


def pipeline_apply(
    layer_fn: Callable,  # (layer_params, x) -> x
    stage_params,  # tree with leading (n_stages, layers_per_stage, ...)
    x: jax.Array,  # (n_micro, micro_batch, ...)
    mesh: Mesh,
    *,
    axis: str = "stage",
) -> jax.Array:
    """Run the microbatched GPipe schedule over the ``axis`` mesh axis."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= n_stages, "need >= n_stages microbatches to fill the pipe"

    def stage_fn(params, xs):
        # params: (1, layers_per_stage, ...); xs: (n_micro, mb, ...)
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])  # current microbatch at this stage
        out = jnp.zeros_like(xs)

        def apply_stage(b):
            def body(h, p):
                return layer_fn(p, h), None

            h, _ = jax.lax.scan(body, b, params)
            return h

        def tick(t, carry):
            buf, out = carry
            # stage 0 ingests microbatch t (when available)
            take = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs, take, 0, keepdims=False)
            cur = jnp.where(stage == 0, fresh, buf)
            y = apply_stage(cur)
            # last stage emits microbatch (t - n_stages + 1)
            emit_idx = jnp.clip(t - n_stages + 1, 0, n_micro - 1)
            do_emit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            emitted = jnp.where(do_emit, y, jax.lax.dynamic_index_in_dim(out, emit_idx, 0, keepdims=False))
            out = jax.lax.dynamic_update_index_in_dim(out, emitted, emit_idx, 0)
            # stream to the next stage (the AXI link)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return nxt, out

        buf, out = jax.lax.fori_loop(0, n_ticks, tick, (buf, out))
        # every stage returns its local out buffer; only the last stage's is
        # real.  Returning per-stage (out_specs=P(axis)) keeps autodiff exact:
        # cotangents route only into the last stage's block.
        return out

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(pspec_params, P()),
        out_specs=P(axis),
        check_vma=False,
    )
    stacked = fn(stage_params, x)  # (n_stages * n_micro, mb, ...)
    return stacked[(n_stages - 1) * n_micro :]


def pipeline_occupancy(n_stages: int, n_micro: int) -> dict:
    """Static GPipe schedule accounting: ticks, bubbles, occupancy.

    The schedule runs ``n_micro + n_stages - 1`` ticks; each stage computes
    for ``n_micro`` of them and idles through ``n_stages - 1`` fill/drain
    bubbles -- the paper's pipeline-fill latency term, counted in ticks
    instead of cycles.  ``occupancy`` is the busy fraction per stage.
    """
    ticks = n_micro + n_stages - 1
    bubble = n_stages - 1
    return {
        "n_stages": n_stages,
        "n_micro": n_micro,
        "ticks": ticks,
        "bubble_ticks_per_stage": bubble,
        "occupancy": n_micro / ticks if ticks else 0.0,
    }


def emit_schedule_spans(tracer, n_stages: int, n_micro: int,
                        t0: float, t1: float) -> dict:
    """Reconstruct the per-stage GPipe timeline as trace lanes.

    Spans inside ``shard_map``/``jit`` cannot be recorded (the schedule is
    one fused XLA program), so the executor measures the wall interval
    ``[t0, t1]`` and lays the *static* schedule over it: tick width
    ``(t1-t0)/ticks``, stage ``s`` busy with microbatch ``m`` during tick
    ``s + m``, idle ticks emitted as ``bubble`` spans.  One lane
    (``stageN``) per stage; returns the occupancy accounting.
    """
    occ = pipeline_occupancy(n_stages, n_micro)
    tick_s = (t1 - t0) / occ["ticks"]
    for s in range(n_stages):
        lane = f"stage{s}"
        for tick in range(occ["ticks"]):
            m = tick - s
            a, b = t0 + tick * tick_s, t0 + (tick + 1) * tick_s
            if 0 <= m < n_micro:
                tracer.emit_span(f"micro{m}", a, b, cat="pipeline",
                                 tid=lane, stage=s, micro=m, tick=tick)
            else:
                tracer.emit_span("bubble", a, b, cat="pipeline",
                                 tid=lane, stage=s, tick=tick)
    return occ


def sequential_reference(layer_fn, params_stacked, x):
    """Oracle: run all layers sequentially on every microbatch."""

    def body(h, p):
        return layer_fn(p, h), None

    def one(mb):
        h, _ = jax.lax.scan(body, mb, params_stacked)
        return h

    return jax.vmap(one)(x)
