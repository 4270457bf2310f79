"""Fused streaming dataflow engine: the whole lowered graph as ONE executable.

The paper's central argument (section 5.3) is architectural: FINN instantiates
one MVU per layer, chains them with small AXI FIFOs, and lets the slowest
stage set the initiation interval — no monolithic controller, no per-layer
host round-trips.  ``dataflow.execute`` reproduces the *semantics* of that
graph but runs it as an eager Python loop: one XLA dispatch per node, float
batchnorm/quant epilogues on the host path, nothing fused.  ``FusedEngine``
is the runtime analog of the paper's full dataflow build:

    paper (section 5.3)                      FusedEngine
    ------------------------------------     ------------------------------------
    MVTU: thresholds fused after the         ``lowering.fuse_epilogues`` folds
    accumulator (Fig. 3, T&geq; unit)        batchnorm+quant_act into the MVU
                                             kernel's threshold epilogue
    one compute unit per layer, AXI          one jit'd program; stages traced
    streams between them                     back-to-back, XLA fuses transfers
    FIFO decoupling (5.3.2): small           microbatch streaming: the batch is
    buffers absorb producer bursts           split into ``StreamPlan.n_micro``
                                             chunks scanned through the chain
    II = bottleneck stage cycles             ``DataflowSchedule.steady_state_
                                             interval`` sizes the microbatch plan
    multi-FPGA / SLR partitioning            ``as_pipeline`` maps stages onto a
                                             device mesh via
                                             ``distributed.pipeline.pipeline_apply``

The microbatch size comes from the schedule: one microbatch is the
bottleneck MVU's resident input tile (``block_m`` — the Eq. 2 input buffer),
i.e. exactly one producer burst, so every stage's kernel runs a single
M step per microbatch and the decoupling FIFO between stages never holds
more than one burst — the same "big enough to decouple, small enough to
fit" sizing rule FINN applies to its AXI FIFOs.  The smallest FIFO depth
caps in-flight microbatches on the multi-device pipeline schedule.

Usage::

    graph  = lowering.finalize(lowering.lower_to_mvu(g))  # may keep bn/quant
    engine = FusedEngine(graph)      # fuses epilogues, compiles on first call
    y      = engine(x)               # bit-exact with dataflow.execute(graph, x)
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.core import dataflow, ir, lowering
from repro.core.ir import Graph


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Microbatch schedule for one engine invocation (FINN FIFO analog)."""

    n_micro: int  # microbatches streamed through the stage chain
    microbatch: int  # samples per microbatch (batch padded up to n*mb)
    interval_cycles: int  # bottleneck stage cycles (steady-state II)
    fifo_bound: int  # smallest inter-stage FIFO depth (pipeline in-flight cap)


class FusedEngine:
    """Compile a lowered :class:`~repro.core.ir.Graph` into a single jit'd,
    microbatch-streaming executable.

    * Epilogue fusion: standalone ``batchnorm``/``quant_act`` successors of
      each MVU are folded into the kernel's multi-threshold epilogue at
      compile time (``fuse=False`` keeps the graph as-is).
    * Streaming: batches are split into microbatches per :meth:`plan` and
      scanned through the stage chain — the statically-scheduled analog of
      FINN's FIFO-decoupled layer pipeline.
    * The node semantics come from :func:`repro.core.dataflow.node_runner`,
      the same definition the eager interpreter uses, so outputs are
      bit-exact with ``dataflow.execute`` on the unfused graph.
    """

    TUNE_MODES = ("off", "cache", "auto")

    def __init__(self, graph: Graph, *, fuse: bool = True,
                 microbatches: int | None = None,
                 tune: str = "off", cache=None,
                 tune_kwargs: dict | None = None):
        if tune not in self.TUNE_MODES:
            raise ValueError(f"tune must be one of {self.TUNE_MODES}, got {tune!r}")
        g: Graph = lowering.fuse_epilogues(graph) if fuse else list(graph)
        # swu+mvu pairs collapse into the line-buffer conv kernel, so the
        # im2col matrix never materializes between stages (FINN's SWU->MVU
        # AXI stream; the conv analog of epilogue fusion).
        self.graph = lowering.fuse_swu(g) if fuse else g
        self._tile: int | None = None
        if tune != "off":
            # tune="cache" is a pure lookup over committed results -- no
            # timer ever runs at construction; tune="auto" measures cache
            # misses once and records them (see repro.core.autotune).
            from repro.core import autotune

            cache = cache if cache is not None else autotune.default_cache()
            self.graph = autotune.tune_graph(self.graph, cache=cache,
                                             mode=tune, **(tune_kwargs or {}))
            # the engine-level entry lives in the same device namespace as
            # the node entries, so a device override must scope both lookups
            device = (tune_kwargs or {}).get("device")
            ent = cache.get(autotune.engine_key(self.graph, device=device))
            if ent is not None:
                self._tile = max(1, int(ent["microbatch"]))
        self.schedule = dataflow.schedule(self.graph)
        # stage order is the dataflow (topological) order -- identical to
        # list order for chains, and the streaming order for branched graphs
        order = ir.toposort(self.graph)
        narrow = dataflow.int8_inputs(order)
        runners = [dataflow.node_runner(n, int8_input=n.name in narrow)
                   for n in order]
        self._fns = tuple(fn for _, fn in runners)
        self.params = [p for p, _ in runners]
        self._names = tuple(n.name for n in order)
        self._in_names = tuple(n.inputs for n in order)
        self._out_name = ir.graph_output(self.graph).name
        self._microbatches = microbatches
        self._jit = jax.jit(self._stream, static_argnums=(2,))

    # ------------------------------------------------------------- schedule
    def plan(self, batch: int) -> StreamPlan:
        """Derive the microbatch schedule from the dataflow schedule.

        The microbatch size is the bottleneck MVU's resident input tile
        (its ``block_m`` — the paper Eq. 2 input buffer holds one tile of
        activations while the NF x SF loop drains it), so each streamed
        microbatch is exactly one producer burst: every stage's kernel runs
        a single M step and the inter-stage FIFO never sees more than one
        burst in flight.  ``n_micro`` is then the number of bursts the batch
        decomposes into; ``fifo_bound`` (smallest FIFO depth) caps in-flight
        microbatches on the :meth:`as_pipeline` multi-device schedule, where
        stages genuinely overlap.
        """
        s = self.schedule
        if not s.stages or batch <= 1:
            interval = s.steady_state_interval if s.stages else 0
            return StreamPlan(1, max(batch, 1), interval, 0)
        fifo_bound = max(2, min(st.fifo_depth for st in s.stages))
        # Samples per burst: a dense stage's kernel holds block_m samples per
        # M tile; a conv stage's M tile holds block_m *pixels*, i.e.
        # block_m // n_pixels whole images -- the conv bottleneck sets the
        # microbatch for the whole chain.  An engine-level autotune entry
        # (``autotune.tune_engine``) overrides the heuristic tile.
        tile = self._tile or min(max(1, st.block_m // st.n_pixels)
                                 for st in s.stages)
        n_micro = max(1, min(math.ceil(batch / tile), batch))
        if self._microbatches is not None:
            n_micro = max(1, min(self._microbatches, batch))
        return StreamPlan(
            n_micro, -(-batch // n_micro), s.steady_state_interval, fifo_bound
        )

    # -------------------------------------------------------------- forward
    def _chain(self, params, x):
        # traced once under jit: the env is a compile-time dict of traced
        # values, so fan-out reuses one stream and joins consume both arms
        # inside the same fused program -- no interpreter overhead survives.
        env: dict = {}
        for name, ins, p, fn in zip(self._names, self._in_names,
                                    params, self._fns):
            args = (x,) if not ins else tuple(env[s] for s in ins)
            env[name] = fn(p, *args)
        return env[self._out_name]

    def _stream(self, params, x, n_micro: int):
        b = x.shape[0]
        if n_micro <= 1:
            return self._chain(params, x)
        mb = -(-b // n_micro)
        pad = n_micro * mb - b
        if pad:
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        xs = x.reshape(n_micro, mb, *x.shape[1:])
        ys = jax.lax.map(functools.partial(self._chain, params), xs)
        return ys.reshape(n_micro * mb, *ys.shape[2:])[:b]

    def dispatch(self, x: jax.Array, *, params=None,
                 tracer=None) -> tuple[jax.Array, StreamPlan]:
        """Non-blocking submit: enqueue one batch, return the un-resolved
        device array plus the stream plan it runs under.

        JAX dispatch is asynchronous -- the call returns as soon as the
        computation is enqueued on its device, so a serving front-end can go
        straight back to admitting requests and block only when it resolves
        the result (``np.asarray`` / ``jax.block_until_ready``).  ``params``
        overrides the engine's resident parameters with a replica's copy
        (``repro.serving.pool`` places them per device); the computation
        runs wherever the committed operands live.

        ``tracer`` (a :class:`repro.telemetry.Tracer`) records the host-side
        enqueue as an ``engine.dispatch`` span -- the duration is submit
        cost, not compute (the call does not block); per-node compute spans
        come from :meth:`profile`.
        """
        plan = self.plan(int(x.shape[0]))
        params = self.params if params is None else params
        if tracer is None:
            return self._jit(params, x, plan.n_micro), plan
        with tracer.span("engine.dispatch", cat="engine",
                         batch=int(x.shape[0]), n_micro=plan.n_micro,
                         microbatch=plan.microbatch,
                         interval_cycles=plan.interval_cycles):
            out = self._jit(params, x, plan.n_micro)
        return out, plan

    def profile(self, x: jax.Array, tracer, *, drift=None
                ) -> tuple[jax.Array, StreamPlan]:
        """Instrumented run: per-node, per-microbatch duration spans.

        The jit'd :meth:`dispatch` path is one fused program -- XLA leaves
        no per-node boundary to time -- so profiling re-runs the SAME node
        runners (``dataflow.node_runner``, the definitions the fused chain
        traced) eagerly per microbatch, blocking after each node.  Every op
        is per-sample, so the output is bit-exact with :meth:`dispatch`;
        only the timing differs (each node pays its own dispatch, which is
        the point).  Span tree::

            engine.profile
              micro0
                <node name>   one span per graph node, cat="node"
              micro1
                ...

        ``drift`` (a :class:`repro.telemetry.DriftMonitor`) receives each
        node span duration keyed by node name -- with predictions from
        ``DriftMonitor.from_schedule(engine.schedule, s_per_cycle)`` this
        compares measured per-node intervals against the calibrated cycle
        model online.
        """
        b = int(x.shape[0])
        plan = self.plan(b)
        mb = plan.microbatch
        pad = plan.n_micro * mb - b
        xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
        xs = xp.reshape(plan.n_micro, mb, *x.shape[1:])
        outs = []
        with tracer.span("engine.profile", cat="engine", batch=b,
                         n_micro=plan.n_micro, microbatch=mb):
            for m in range(plan.n_micro):
                with tracer.span(f"micro{m}", cat="engine"):
                    env: dict = {}
                    for name, ins, p, fn in zip(self._names, self._in_names,
                                                self.params, self._fns):
                        with tracer.span(name, cat="node", micro=m) as sp:
                            args = ((xs[m],) if not ins
                                    else tuple(env[s] for s in ins))
                            env[name] = jax.block_until_ready(fn(p, *args))
                        if drift is not None:
                            drift.observe(name, sp.dur)
                    outs.append(env[self._out_name])
        y = jnp.concatenate(outs)[:b]
        return y, plan

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.dispatch(x)[0]

    # ---------------------------------------------------------- multi-device
    def as_pipeline(self, mesh, *, axis: str = "stage", tracer=None):
        """Map stages onto mesh devices, one layer range per device, reusing
        :func:`repro.distributed.pipeline.pipeline_apply` (ppermute links as
        the AXI streams).

        Stacking per-stage params requires a homogeneous chain: every node an
        MVU of the same (N, K) and mode (not xnor — its static packed width
        breaks stacking) with a uniform epilogue.  Heterogeneous graphs run
        single-device via ``__call__``.  Returns ``run(xs)`` taking
        microbatched input ``(n_micro, mb, K)``.

        With ``tracer``, each ``run`` records a ``pipeline.run`` span plus
        reconstructed per-stage occupancy lanes: the schedule is one fused
        XLA program (nothing to time inside), so the measured wall interval
        is overlaid with the static GPipe schedule -- busy ``microN`` spans
        and ``bubble`` fill/drain spans per stage, with the occupancy
        fraction in the span args (see
        :func:`repro.distributed.pipeline.emit_schedule_spans`).
        """
        from repro.distributed.pipeline import (
            emit_schedule_spans,
            pipeline_apply,
            stage_params_split,
        )
        from repro.kernels import ops as kops

        non_input = [n for n in self.graph if n.op != "input"]
        if any(n.op != "mvu" for n in non_input):
            raise ValueError(
                "as_pipeline needs a pure MVU chain; fuse_epilogues removes "
                f"bn/quant nodes, got ops {[n.op for n in non_input]}"
            )
        cfgs = [n.attrs["config"] for n in non_input]
        shapes = {(c.mode, c.out_features, c.in_features) for c in cfgs}
        if len(shapes) != 1 or cfgs[0].mode == "xnor":
            raise ValueError(f"stages must be homogeneous non-xnor MVUs, got {shapes}")
        thr = [n.params["mvu"].thresholds for n in non_input]
        scl = [n.params["mvu"].out_scale for n in non_input]
        for part in (thr, scl):
            if any(p is None for p in part) and not all(p is None for p in part):
                raise ValueError("stages must share one epilogue form")
        stacked = {"w": jnp.stack([n.params["mvu"].weights for n in non_input])}
        if thr[0] is not None:
            stacked["t"] = jnp.stack(thr)
        if scl[0] is not None:
            stacked["s"] = jnp.stack(scl)
        # one stage fn runs every layer: narrow only if every input fits
        narrow = {n.name for n in non_input} <= dataflow.int8_inputs(self.graph)
        layer_fn = kops.mvu_layer_fn(
            cfgs[0].mode, backend=cfgs[0].backend, int8_input=narrow,
            **cfgs[0].kernel_blocks()
        )
        n_stages = mesh.shape[axis]
        stage_params = stage_params_split(stacked, n_stages)

        def run(xs: jax.Array) -> jax.Array:
            if tracer is None:
                return pipeline_apply(layer_fn, stage_params, xs, mesh,
                                      axis=axis)
            n_micro = int(xs.shape[0])
            with tracer.span("pipeline.run", cat="pipeline",
                             n_stages=n_stages, n_micro=n_micro) as sp:
                out = jax.block_until_ready(
                    pipeline_apply(layer_fn, stage_params, xs, mesh, axis=axis)
                )
            occ = emit_schedule_spans(tracer, n_stages, n_micro,
                                      sp.t0, sp.t1)
            sp.args.update(occupancy=occ["occupancy"],
                           bubble_ticks=occ["bubble_ticks_per_stage"])
            return out

        return run
